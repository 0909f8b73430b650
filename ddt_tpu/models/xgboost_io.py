"""XGBoost models from the library's JSON (`Booster.save_model("m.json")`):
a `gbtree` booster to the layout that scores it on the device, a
`models/tree.TreeEnsemble` (heap) where the deepest tree fits
`lightgbm_io.HEAP_MAX_DEPTH` levels, else a `NodeListEnsemble`; several
classes (`multi:softprob`) as softmax's round-major trees in either.

    ens = from_xgboost_json(open("m.json").read())     # raw thresholds
    mapper = threshold_bin_mapper(ens, n_bins=256)     # models/lightgbm_io
    proba = api.predict(ens, X, mapper=mapper, cfg=cfg)    # [rows, classes]

What is read (the schema of the library's 1.x-3.x JSON; numbers may be
strings there): `learner.learner_model_param` (`num_class`, `num_feature`,
`base_score`), `learner.objective.name`, `learner.gradient_booster` (`name`,
`model.trees[i]`: `left_children`, `right_children` (-1: a leaf),
`split_indices`, `split_conditions` (of a leaf: its VALUE, `eta` already in
it), `default_left`, `split_type`, `loss_changes`, `tree_param.
size_leaf_vector`; `model.tree_info[i]`: tree i's class). Nothing of the
library is imported.

THE STRICT TEST. XGBoost sends a row LEFT where `x < t`; this repository
(and LightGBM, and scikit-learn) where `x <= t`. The import stores

    threshold_raw = nextafter(float32(t), -inf)

and for every float32 x, `x <= nextafter(t, -inf)` is `x < t`: the largest
float32 below t is the last one the strict test lets through (+-0.0 compare
equal and sit on the same side either way; t = +inf becomes the largest
finite float32, below which +inf does not lie). So the walk, the mapper
that ranks the thresholds (`threshold_bin_mapper`), the quantizer and every
kernel stay as they are. A threshold of -inf or NaN has no float32 below it
and is refused by name (no row goes left there in the library either).

MISSING VALUES, the import's one argument. `missing=True` (the default):
the data may hold NaN, every node keeps its `default_left`, and binned rows
carry NaN in the reserved top bin, so of 256 bins a column may carry 254
thresholds. `missing=False` (a set known to hold none): the directions are
dropped, NaN gets no bin, and a column may carry 255, which is what the
library's `max_bin` 256 can produce. More than that is refused by name by
`threshold_bin_mapper`, as for any import (bins wider than a byte have no
kernel here).

Where this departs from the library, each time without changing the leaf a
float32 row reaches: margins are summed in float32 in tree order there and
in float32 in the kernel's order here (agreement to float32 rounding, equal
on dyadic leaf values); `base_score` is held as the MARGIN it stands for
(`binary:logistic`: its logit; the others: itself, which `multi:softprob`
adds to every class alike, so that the softmax forgets it); leaf values are
final (`learning_rate` 1: `eta` is in them).

Refused by name: boosters `dart` (trees dropped and rescaled at training
time carry weights this layout has no field for) and `gblinear`; category
sets (`split_type` 1); vector leaves (`size_leaf_vector` > 1, the library's
`multi_output_tree`); a `tree_info` that is not round-major (tree i to class
i % classes: `num_parallel_tree` > 1 is not); an objective without a link
function here; a per-class `base_score` whose entries differ; the binary
UBJSON file (`.ubj`: save the model as `.json`).
"""

from __future__ import annotations

import json

import numpy as np

from ddt_tpu.models.lightgbm_io import HEAP_MAX_DEPTH
from ddt_tpu.models.tree import NodeListEnsemble, TreeEnsemble

# objective -> (this repository's loss, base_score -> the margin it means)
_OBJECTIVES = {
    "binary:logistic": ("logloss", lambda p: float(np.log(p / (1.0 - p)))),
    "reg:squarederror": ("mse", float),
    "multi:softprob": ("softmax", float),
}

_UBJSON = ("the binary UBJSON form of an XGBoost model (.ubj) is not read; "
           "save it as JSON: Booster.save_model('model.json')")


def load_xgboost(path, missing: bool = True):
    """`from_xgboost_json` of the file at `path`; `.ubj` refused by name."""
    if str(path).endswith(".ubj"):
        raise ValueError(f"{path}: {_UBJSON}")
    with open(path, "rb") as f:
        return from_xgboost_json(f.read(), missing=missing)


def _number(v) -> float:
    """A scalar of the schema: a number, its string, or `[x]` of either
    (3.x writes `base_score` as a vector); entries that differ are refused."""
    if isinstance(v, str):
        v = json.loads(v) if v.lstrip().startswith("[") else float(v)
    values = np.unique(np.asarray(v, np.float64))
    if len(values) != 1:
        raise ValueError(
            "from_xgboost_json: a per-class base_score whose entries differ "
            f"({v}) has no field here: one base score a model")
    return float(values[0])


def _tree_arrays(tree: dict, t: int) -> dict:
    """One `trees[t]` as this repository numbers it: the internal nodes and
    the leaves apart, each in the library's order (the root stays node 0; a
    child reference c < 0 is leaf ~c), what the root does not reach left
    out; `depth`: the nodes on the longest path; `slot`: every node's heap
    index (the root 0, children 2 s + 1 and 2 s + 2)."""
    if int(tree.get("tree_param", {}).get("size_leaf_vector", 1)) > 1:
        raise ValueError(
            f"from_xgboost_json: tree {t} holds vector leaves "
            "(size_leaf_vector > 1, multi_strategy='multi_output_tree'); "
            "one value a leaf and one tree a class are supported")
    left = np.asarray(tree["left_children"], np.int64)
    right = np.asarray(tree["right_children"], np.int64)
    n = len(left)
    is_leaf = left < 0
    kinds = np.asarray(tree.get("split_type", ()), np.int64)
    if len(kinds) and kinds[~is_leaf].any():
        raise ValueError(
            f"from_xgboost_json: tree {t} splits on a category set "
            "(split_type 1), which neither layout holds for an import; "
            "ordinal splits only")
    reached = np.zeros(n, bool)
    slot = np.zeros(n, np.int64)
    frontier, depth = np.zeros(1, np.int64), 0
    while len(frontier):
        reached[frontier] = True
        inner = frontier[~is_leaf[frontier]]
        depth += len(inner) > 0
        frontier = np.concatenate([left[inner], right[inner]])
        if depth > n or ((frontier < 0) | (frontier >= n)).any():
            raise ValueError(f"from_xgboost_json: tree {t}: a child index "
                             "points outside the tree, or round a cycle")
        with np.errstate(over="ignore"):    # read of trees a heap holds only
            slot[left[inner]] = 2 * slot[inner] + 1
            slot[right[inner]] = 2 * slot[inner] + 2
    at = np.nonzero(reached & ~is_leaf)[0]
    lv = np.nonzero(reached & is_leaf)[0]
    number = np.zeros(n, np.int64)
    number[at], number[lv] = np.arange(len(at)), ~np.arange(len(lv))
    cond = np.asarray(tree["split_conditions"], np.float32)
    if (np.isnan(cond[at]) | (cond[at] == -np.inf)).any():
        raise ValueError(
            f"from_xgboost_json: tree {t} tests x < -inf or x < NaN: no "
            "float32 lies below such a threshold (THE STRICT TEST)")
    gain = np.asarray(tree.get("loss_changes", np.zeros(n)), np.float32)
    return dict(
        feature=np.asarray(tree["split_indices"], np.int32)[at],
        threshold_raw=np.nextafter(cond[at], np.float32(-np.inf)),
        split_gain=gain[at],
        default_left=np.asarray(tree["default_left"], bool)[at],
        left_child=number[left[at]], right_child=number[right[at]],
        leaf_value=cond[lv], depth=int(depth), slot=slot[at],
        leaf_slot=slot[lv])


def from_xgboost_json(model, missing: bool = True
                      ) -> "TreeEnsemble | NodeListEnsemble":
    """An XGBoost `gbtree` model (the JSON text `Booster.save_model` writes,
    or the dict it parses to) as an ensemble with RAW thresholds: a heap
    where its deepest tree fits `HEAP_MAX_DEPTH` levels, else a node list
    (`has_bin_thresholds` False until `threshold_bin_mapper` ranks them).
    `missing`: whether the data can hold NaN (module docstring). The module
    docstring lists what is refused, each by name."""
    if isinstance(model, (bytes, bytearray, str)):
        try:
            model = json.loads(model)
        except (ValueError, UnicodeDecodeError) as e:
            raise ValueError(f"from_xgboost_json: not JSON ({e}); "
                             + _UBJSON) from None
    learner = model["learner"]
    booster = learner["gradient_booster"]
    if booster["name"] != "gbtree":
        raise ValueError(
            f"from_xgboost_json: booster {booster['name']!r} is not "
            "supported (gbtree alone: dart's dropped trees carry weights "
            "and gblinear holds no tree)")
    objective = learner["objective"]["name"]
    if objective not in _OBJECTIVES:
        raise ValueError(
            f"from_xgboost_json: objective {objective!r} has no link "
            f"function here; supported: {sorted(_OBJECTIVES)}")
    loss, to_margin = _OBJECTIVES[objective]
    param = learner["learner_model_param"]
    C = max(int(param.get("num_class", 0)), 1)
    if (loss == "softmax") != (C > 1):
        raise ValueError(f"from_xgboost_json: objective {objective!r} with "
                         f"num_class {param.get('num_class')}")
    trees = booster["model"]["trees"]
    info = np.asarray(booster["model"].get("tree_info", ()), np.int64)
    if len(info) != len(trees) or (info != np.arange(len(trees)) % C).any():
        raise ValueError(
            "from_xgboost_json: tree_info is not round-major (tree i to "
            f"class i % {C}): num_parallel_tree > 1, or trees reordered; "
            "the class of a tree is its place in the round here")
    if not trees:
        raise ValueError("from_xgboost_json: the model holds no tree")
    parts = [_tree_arrays(tree, t) for t, tree in enumerate(trees)]
    meta = dict(n_features=int(param["num_feature"]),
                learning_rate=1.0,      # eta is in the leaf values
                base_score=to_margin(_number(param.get("base_score", 0.5))),
                loss=loss, n_classes=max(C, 2), has_raw_thresholds=True,
                missing_bin=bool(missing))
    depth = max(1, max(p["depth"] for p in parts))
    T = len(parts)
    if depth <= HEAP_MAX_DEPTH:
        n_nodes = 2 ** (depth + 1) - 1
        out = dict(feature=np.full((T, n_nodes), -1, np.int32),
                   threshold_bin=np.zeros((T, n_nodes), np.int32),
                   threshold_raw=np.zeros((T, n_nodes), np.float32),
                   is_leaf=np.zeros((T, n_nodes), bool),
                   leaf_value=np.zeros((T, n_nodes), np.float32),
                   split_gain=np.zeros((T, n_nodes), np.float32),
                   default_left=np.zeros((T, n_nodes), bool))
        for t, p in enumerate(parts):
            for k in ("feature", "threshold_raw", "split_gain",
                      "default_left"):
                out[k][t, p["slot"]] = p[k]
            out["is_leaf"][t, p["leaf_slot"]] = True
            out["leaf_value"][t, p["leaf_slot"]] = p["leaf_value"]
        if not missing:
            out["default_left"] = None
        return TreeEnsemble(**out, max_depth=depth, **meta)
    N = max(1, max(len(p["feature"]) for p in parts))
    L = max(len(p["leaf_value"]) for p in parts)
    out = dict(feature=np.full((T, N), -1, np.int32),
               threshold_bin=np.zeros((T, N), np.int32),
               threshold_raw=np.zeros((T, N), np.float32),
               split_gain=np.zeros((T, N), np.float32),
               left_child=np.zeros((T, N), np.int32),
               right_child=np.zeros((T, N), np.int32),
               default_left=np.zeros((T, N), bool),
               leaf_value=np.zeros((T, L), np.float32))
    for t, p in enumerate(parts):
        for k, a in out.items():
            if k in p:              # threshold_bin: the mapper's, later
                a[t, :len(p[k])] = p[k]
    if not missing:
        out["default_left"] = None
    return NodeListEnsemble(
        **out, n_leaves=np.asarray([len(p["leaf_value"]) for p in parts],
                                   np.int32),
        has_bin_thresholds=False, **meta)
