"""CatBoost JSON model interop: the oblivious layout's import and export.

`from_catboost_json` reads the library's JSON export (`model.save_model(path,
format="json")`) into a `models/tree.ObliviousEnsemble`; `to_catboost_json`
writes one back, and the round trip (export -> parse -> identical
predictions) keeps the writer honest without CatBoost installed. What is
read (REMEMBERED from the library's exporter, no network here; the hand-
written fixture in tests/test_oblivious.py is the format as this file
takes it):

    oblivious_trees[t].splits[d]    {"split_type": "FloatFeature",
                                     "float_feature_index": i, "border": b}
                                    d = 0 is the LOW bit of the leaf index
    oblivious_trees[t].leaf_values  2^len(splits) values, leaf i at index i;
                                    of C classes (`MultiClass`) 2^len(splits)
                                    x C, LEAF-major, the class innermost:
                                    leaf i's value for class c at i C + c
                                    (`leaf_weights` stays 2^len(splits))
    features_info.float_features[i] {"feature_index", "flat_feature_index",
                                     "borders": ascending, "has_nans",
                                     "nan_value_treatment"}
    scale_and_bias                  [scale, bias] or [scale, [bias]]; of C
                                    classes [scale, [bias_0 .. bias_{C-1}]]
    model_info.params.loss_function.type   the objective, where the key is
                                    there: Logloss, CrossEntropy, RMSE,
                                    MultiClass (the `loss` argument wins)

A split is `x > border`, and `border` is one of the feature's `borders`: its
RANK there is the split's bin, and a row's bin is the number of the
feature's borders below its value, so `x > border_k` is `bin(x) > k`
EXACTLY for every float32 x (the argument of
`lightgbm_io.threshold_bin_mapper`; `ObliviousEnsemble.bin_mapper` is the
mapper). A feature carries at most `n_bins - 1` borders (254 under the
library's CPU default `border_count=254`: 255 bins). A tree of fewer splits
than the deepest is padded in its HIGH bits with splits no bin passes.

What is REFUSED, by name (`_refuse`): a model the oblivious layout and its
kernel have no field for. Scoring such a model needs work this system does
not do yet (ROADMAP.md): category statistics looked up from a hash table at
scoring time (CTR features), one-hot category splits, text and embedding
features, `MultiClassOneVsAll` (vector leaves under a sigmoid a class, where
`MultiClass`'s are under one softmax), vector leaves whose width is not
every tree's and the bias's own, trees that are not
symmetric (`grow_policy` Depthwise / Lossguide export `trees`, not
`oblivious_trees`), and a feature that saw NaN in training and sends it to
a side of its own (`nan_value_treatment` AsFalse / AsTrue: a learned NaN
side for an oblivious split).
"""

from __future__ import annotations

import json

import numpy as np

from ddt_tpu.models.tree import OBLIVIOUS_NEVER, ObliviousEnsemble


def _refuse(has: bool, what: str) -> None:
    """The one wording of what the import cannot carry, named."""
    if has:
        raise ValueError(
            f"from_catboost_json: the model carries {what}, which the "
            "oblivious layout (models/tree.ObliviousEnsemble) and its "
            "kernel do not support yet")


# The library's objectives as this system names them (`ObliviousEnsemble.
# loss`): what `model_info.params.loss_function.type` may say.
_OBJECTIVES = {"Logloss": "logloss", "CrossEntropy": "logloss",
               "RMSE": "mse", "MultiClass": "softmax"}


def _objective(m: dict) -> "str | None":
    """`model_info.params.loss_function.type`, where the export carries it
    (`params` may be the library's JSON text of itself)."""
    params = (m.get("model_info") or {}).get("params") or {}
    if isinstance(params, str):
        params = json.loads(params)
    return (params.get("loss_function") or {}).get("type")


def from_catboost_json(model: "str | dict", loss: str | None = None,
                       n_bins: int | None = None) -> ObliviousEnsemble:
    """An ObliviousEnsemble from CatBoost's JSON export (its text, or the
    parsed dict). `loss`: the model's objective as this system names it;
    by default what `model_info` says (`_OBJECTIVES`), and where it says
    nothing "softmax" for vector leaves and "logloss" otherwise. `n_bins`:
    the bins the rows will be binned into, the widest border list + 1 by
    default."""
    m = json.loads(model) if isinstance(model, str) else model
    info = m.get("features_info", {})
    objective = _objective(m)
    _refuse(objective == "MultiClassOneVsAll",
            "the objective MultiClassOneVsAll (a sigmoid a class over its "
            "vector leaves, where MultiClass takes one softmax)")
    _refuse("oblivious_trees" not in m and "trees" in m,
            "non-symmetric trees (grow_policy Depthwise or Lossguide)")
    _refuse(bool(info.get("categorical_features")) or bool(info.get("ctrs"))
            or bool(m.get("ctr_data")),
            "categorical features (category statistics looked up from a "
            "hash table at scoring time, or one-hot category splits)")
    _refuse(bool(info.get("text_features")), "text features")
    _refuse(bool(info.get("embedding_features")), "embedding features")
    floats = sorted(info.get("float_features", []),
                    key=lambda f: f.get("feature_index", 0))
    for f in floats:
        _refuse(bool(f.get("has_nans")) and f.get(
            "nan_value_treatment", "AsIs") != "AsIs",
            f"a NaN side for float feature {f.get('feature_index')} "
            f"(nan_value_treatment {f.get('nan_value_treatment')!r})")
    borders = [np.asarray(f.get("borders") or [], np.float32) for f in floats]
    for i, b in enumerate(borders):
        if len(b) > 1 and not (np.diff(b) > 0).all():
            raise ValueError(f"from_catboost_json: float feature {i}'s "
                             "borders are not ascending")
    widest = max((len(b) for b in borders), default=0)
    n_bins = widest + 1 if n_bins is None else int(n_bins)
    if widest > n_bins - 1 or n_bins > 256:
        raise ValueError(
            f"from_catboost_json: a feature carries {widest} borders, more "
            f"than the {min(n_bins, 256) - 1} of {min(n_bins, 256)} bins (a "
            "model trained with border_count > 255?): it cannot be scored "
            "on binned uint8 rows")

    trees = m.get("oblivious_trees", [])
    depth = max((len(t["splits"]) for t in trees), default=0)
    if depth < 1:
        raise ValueError("from_catboost_json: no tree with a split")
    T = len(trees)
    feat = np.zeros((T, depth), np.int32)
    rank = np.full((T, depth), OBLIVIOUS_NEVER, np.int32)
    raw = np.full((T, depth), np.inf, np.float32)
    scale, bias = m.get("scale_and_bias", [1.0, 0.0])
    bias = np.atleast_1d(np.asarray(bias, np.float64))
    C = len(bias)      # the width of a leaf: every tree's, and the bias's
    leaves = np.zeros((T, 1 << depth, C), np.float32)
    for t, tree in enumerate(trees):
        splits = tree["splits"]
        n_values = len(tree["leaf_values"])
        _refuse(n_values % (1 << len(splits)) != 0,
                f"a leaf_values list that is no multiple of 2^splits (tree "
                f"{t}: {n_values} values for {len(splits)} splits)")
        _refuse(n_values != C << len(splits),
                f"vector leaves of a width that is not the bias's (tree {t}: "
                f"{n_values} leaf values for {len(splits)} splits, "
                f"{n_values >> len(splits)} a leaf; a bias of {C} values)")
        for d, sp in enumerate(splits):
            kind = sp.get("split_type", "FloatFeature")
            _refuse(kind != "FloatFeature",
                    f"a split of type {kind!r} (tree {t}, split {d})")
            i = int(sp["float_feature_index"])
            b = np.float32(sp["border"])
            if not 0 <= i < len(borders):
                raise ValueError(f"from_catboost_json: tree {t} splits "
                                 f"float feature {i} of {len(borders)}")
            k = int(np.searchsorted(borders[i], b))
            if k == len(borders[i]) or borders[i][k] != b:
                raise ValueError(
                    f"from_catboost_json: tree {t} splits float feature "
                    f"{i} at {b!r}, which is none of its borders")
            feat[t, d], rank[t, d], raw[t, d] = i, k, b
        # leaf-major, the class innermost: [2^splits, C]
        leaves[t, :1 << len(splits)] = np.reshape(tree["leaf_values"],
                                                  (-1, C))

    if loss is None:
        loss = _OBJECTIVES.get(objective, "softmax" if C > 1 else "logloss")
    edges = np.full((len(borders), n_bins - 1), np.inf, np.float32)
    for i, b in enumerate(borders):
        edges[i, :len(b)] = b
    return ObliviousEnsemble(
        split_feature=feat, split_bin=rank,
        leaf_value=leaves if C > 1 else leaves[:, :, 0],
        n_features=len(borders), scale=float(scale),
        bias=bias if C > 1 else float(bias[0]),
        loss=loss, n_bins=n_bins, split_raw=raw, borders=edges)


def to_catboost_json(ens: ObliviousEnsemble) -> str:
    """CatBoost's JSON export of an oblivious ensemble that carries its
    border lists (`borders`: an import does): the keys `from_catboost_json`
    reads, a shallow tree's filler splits left out."""
    if ens.borders is None:
        raise ValueError("to_catboost_json needs the model's border lists "
                         "(ObliviousEnsemble.borders)")
    live = ens.live_splits
    trees = []
    for t in range(ens.n_trees):
        d_t = int(live[t].sum())
        if not live[t, :d_t].all():
            raise ValueError(f"tree {t}: a filler split below a live one")
        trees.append({
            "splits": [{
                "split_type": "FloatFeature",
                "float_feature_index": int(ens.split_feature[t, d]),
                "border": float(ens.borders[ens.split_feature[t, d],
                                            ens.split_bin[t, d]]),
            } for d in range(d_t)],
            # (vector leaves: leaf-major, the class innermost)
            "leaf_values": [float(v) for v in
                            ens.leaf_value[t, :1 << d_t].reshape(-1)],
        })
    floats = [{
        "feature_index": i, "flat_feature_index": i,
        "borders": [float(b) for b in row[np.isfinite(row)]],
        "has_nans": False, "nan_value_treatment": "AsIs",
    } for i, row in enumerate(ens.borders)]
    return json.dumps({
        "oblivious_trees": trees,
        "features_info": {"float_features": floats},
        "scale_and_bias": [float(ens.scale),
                           np.atleast_1d(ens.bias).tolist()],
        "model_info": {"params": {"loss_function": {"type": next(
            k for k, v in _OBJECTIVES.items() if v == ens.loss)}}},
    })
