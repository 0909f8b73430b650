"""LightGBM model.txt interop (round-2 verdict item 8).

`to_lightgbm_text` renders a TreeEnsemble in LightGBM's plain-text model
format so the eventual real-data validation (docs/REAL_DATA.md) can diff
models tree-by-tree against a LightGBM run — not just compare AUCs —
and so LightGBM tooling (its own Booster(model_str=...), SHAP, treelite)
can load models trained here. `from_lightgbm_text` is the repo's own
re-parser: the round-trip test (export -> parse -> identical predictions)
keeps the writer honest without LightGBM installed.

Which layout an import yields. `from_lightgbm_text` returns the HEAP
(`TreeEnsemble`, [T, 2^(depth+1)-1]) for trees the heap traversal kernel
serves, at most `HEAP_MAX_DEPTH` levels, and the NODE LIST
(`NodeListEnsemble`: LightGBM's own arrays, 2 x num_leaves - 1 entries a
tree) for deeper ones: the shape decides, there is no flag. LightGBM grows
leaf-wise, so its published settings (num_leaves=255) give trees 13-20
levels deep whose heap would be 2^21 slots for 509 entries; the node list
holds them as they are and the path-matrix kernel (ops/predict_paths.py)
scores them. The node list carries ordinal splits, with or without NaN
default directions (LightGBM's `use_missing`, its default: a model trained
on data with a missing value in it), one output column or several
classes as softmax's round-major trees (`multiclass`: tree t to class t %
num_class, LightGBM's own order), and (PR 55) LightGBM's CATEGORY SETS as
they are: a categorical node's bitset is kept (`NodeListEnsemble`, CATEGORY
SETS: the library's `cat_boundaries` / `cat_threshold`), not expanded. Only
a model the heap holds AFTER the expansion below (`HEAP_MAX_DEPTH` levels)
still imports as a heap of one-vs-rest chains. An import
carries RAW thresholds only; `threshold_bin_mapper` ranks them into bins
and returns the BinMapper whose edges they are (NaN in the reserved top
bin where the model has directions for it; a category column's named ids a
bin each and every other value one bin more), after which the model
scores binned rows on the device exactly as it scores raw ones on the host.

Format notes (LightGBM's text serialization, stable since v2):
- one `Tree=<i>` block per tree; arrays are space-separated lines
- internal nodes are numbered 0..num_leaves-2, leaves 0..num_leaves-1;
  child references encode leaves as ~leaf_idx (i.e. -(leaf_idx+1))
- routing: value <= threshold goes LEFT (same rule as this repo's
  threshold_raw semantics)
- decision_type bit 1 (value 2) = missing values default LEFT; bits 2-3
  = missing type (0 none, 1 zero, 2 NaN)
- leaf_value carries the FINAL additive contribution (shrinkage already
  applied); the ensemble's base score is folded into tree 0's leaves
  (LightGBM's boost_from_average does the same)

Exportable models: ordinal splits need raw thresholds (train through a
BinMapper). Categorical one-vs-rest splits export as LightGBM categorical
nodes (decision_type bit 0): the node's `threshold` is an index into
`cat_boundaries`, which offsets into the `cat_threshold` uint32 bitset
array; a value v routes LEFT when bit v is set. One-vs-rest means every
exported bitset has exactly ONE bit set (the matched category). NaN
handling on cat nodes mirrors ordinal nodes (missing type NaN + per-node
default direction) — that matches this repo's traversal, not LightGBM's
own NaN-in-categorical convention (to_lightgbm_text warns when a model
mixes the two, so users don't assume cross-tool NaN parity on cat
splits).

Import breadth (round-5): the re-parser ALSO accepts multi-bit bitsets —
the externally-trained-LightGBM case (a real LightGBM categorical split
sends a SET of categories left). A k-bit set is expanded into a chain of
k one-vs-rest nodes: each chain link tests one member category (matched
goes LEFT into a copy of the original left subtree); the last link's
right child is the original right subtree. Routing is exactly equivalent
— including NaN rows, which follow the node's default direction at every
link (default-left exits into the left subtree at link 0; default-right
falls through the whole chain into the right subtree). Costs: tree depth
grows by k-1 per multi-bit node (the heap overflows past depth 30 and
raises, naming the node), the left subtree is materialised k times, and
split_gain is recorded on the first link only (0 on the rest) so
gain-sum feature importances are preserved.
"""

from __future__ import annotations

import warnings

import numpy as np

from ddt_tpu.models.tree import (CAT_SET_WORDS, NodeListEnsemble,
                                 TreeEnsemble, node_list_from_trees, set_bits)

# The deepest heap an import yields: what the heap traversal kernel's VMEM
# plan takes at any feature count (ops/predict_pallas.predict_pallas_fits,
# unrouted; tests/test_node_list.py holds the two together). At 11 levels
# that kernel asks 8 MXU weight tiles a tree, the path-matrix form 6 for
# up to 256 leaves.
HEAP_MAX_DEPTH = 11

_MISSING_NAN = 2 << 2        # decision_type missing-type field: NaN
_DEFAULT_LEFT = 2            # decision_type default-left bit
_CATEGORICAL = 1             # decision_type categorical-split bit


def _objective(ens: TreeEnsemble) -> str:
    if ens.loss == "logloss":
        return "binary sigmoid:1"
    if ens.loss == "softmax":
        return f"multiclass num_class:{ens.n_classes}"
    return "regression"


def _fmt(values) -> str:
    return " ".join(f"{float(v):.17g}" for v in values)


def _fmt_int(values) -> str:
    return " ".join(str(int(v)) for v in values)


def _tree_block(t: int, n_leaves: int, fields: dict, shrinkage: float,
                n_cat: int = 0) -> list[str]:
    """One `Tree=<t>` block's lines from its arrays (`fields`, in
    LightGBM's order); the statistics this repo does not keep are zeros."""
    n_int = max(1, n_leaves - 1)
    lines = [f"Tree={t}", f"num_leaves={n_leaves}", f"num_cat={n_cat}"]
    for k in ("split_feature", "split_gain", "threshold", "decision_type",
              "left_child", "right_child", "leaf_value"):
        fmt = _fmt if k in ("split_gain", "threshold", "leaf_value") \
            else _fmt_int
        lines.append(f"{k}=" + fmt(fields[k]))
    lines += [
        "leaf_weight=" + _fmt([0.0] * n_leaves),
        "leaf_count=" + _fmt_int([0] * n_leaves),
        "internal_value=" + _fmt([0.0] * n_int),
        "internal_weight=" + _fmt([0.0] * n_int),
        "internal_count=" + _fmt_int([0] * n_int),
    ]
    if n_cat:
        lines += ["cat_boundaries=" + _fmt_int(fields["cat_boundaries"]),
                  "cat_threshold=" + _fmt_int(fields["cat_threshold"])]
    return lines + ["is_linear=0", f"shrinkage={shrinkage:.17g}", ""]


def _node_list_blocks(ens: NodeListEnsemble) -> list[str]:
    """The `Tree=` blocks of a node list: its arrays as they are (they are
    LightGBM's), leaf values with the shrinkage applied and the base score
    folded into round 0 (tree 0; of softmax's classes a tree each)."""
    lines: list[str] = []
    for t in range(ens.n_trees):
        L = int(ens.n_leaves[t])
        n = L - 1
        lv = ens.leaf_value[t, :L].astype(np.float64) * ens.learning_rate
        if t < ens.leaf_columns:    # round 0: a tree of every class
            lv = lv + ens.base_score
        threshold = ens.threshold_raw[t, :n].astype(np.float64)
        decision = np.asarray(
            _MISSING_NAN + _DEFAULT_LEFT * ens.default_left[t, :n]
            if ens.missing_routes else [0] * n, np.int64)
        # A set node: the library's own form, the bitset's index in the
        # tree as the threshold; missing type NaN (NaN goes right) or None
        # (NaN counts as id 0), never default-left.
        bounds, words = [0], []
        sets = ens.cat_index[t, :n] if ens.cat_index is not None \
            else np.full(n, -1)
        for i in np.flatnonzero(sets >= 0):
            s0 = int(sets[i])
            threshold[i] = len(bounds) - 1
            decision[i] = _CATEGORICAL + (
                0 if ens.cat_nan_as_zero[s0] else _MISSING_NAN)
            words += list(ens.cat_threshold[
                ens.cat_boundaries[s0]:ens.cat_boundaries[s0 + 1]])
            bounds.append(len(words))
        lines += _tree_block(t, L, {
            "split_feature": ens.feature[t, :n],
            "split_gain": ens.split_gain[t, :n],
            "threshold": threshold,
            "decision_type": decision,
            "left_child": ens.left_child[t, :n],
            "right_child": ens.right_child[t, :n],
            "leaf_value": lv,
            "cat_boundaries": bounds, "cat_threshold": words,
        }, ens.learning_rate, n_cat=len(bounds) - 1)
    return lines


def to_lightgbm_text(ens: "TreeEnsemble | NodeListEnsemble",
                     feature_names: list[str] | None = None) -> str:
    """Render the ensemble (either layout) as a LightGBM model.txt string."""
    if not ens.has_raw_thresholds:
        raise ValueError(
            "LightGBM export needs raw-value thresholds; train through a "
            "BinMapper (api.train) or fill them with "
            "reference.numpy_trainer._fill_raw_thresholds first"
        )
    cat_set = (set(int(f) for f in ens.cat_features)
               if ens.has_cat_splits and ens.cat_features is not None
               else set())
    if feature_names is None:
        feature_names = [f"Column_{i}" for i in range(ens.n_features)]
    C = ens.n_classes if ens.loss == "softmax" else 1
    lines = [
        "tree",
        "version=v3",
        f"num_class={C}",
        f"num_tree_per_iteration={C}",
        "label_index=0",
        f"max_feature_idx={ens.n_features - 1}",
        f"objective={_objective(ens)}",
        "feature_names=" + " ".join(feature_names),
        "feature_infos=" + " ".join(["[-inf:inf]"] * ens.n_features),
        "",
    ]
    if isinstance(ens, NodeListEnsemble):
        return "\n".join(lines + _node_list_blocks(ens) + [
            "end of trees", "", "pandas_categorical:null", ""])
    use_missing = ens.missing_bin and ens.default_left is not None
    if use_missing and cat_set:
        warnings.warn(
            "exporting a model with BOTH learned NaN default directions "
            "and categorical splits: this repo routes NaN on categorical "
            "nodes by the per-node default direction, which differs from "
            "LightGBM's own NaN-in-categorical convention — the exported "
            "model scores NaN rows differently when loaded into real "
            "LightGBM (module docstring, 'NaN handling')",
            stacklevel=2,
        )
    for t in range(ens.n_trees):
        # Pre-order walk of the heap: internal nodes and leaves numbered
        # in encounter order (root = internal 0, LightGBM's convention).
        split_feature: list[int] = []
        split_gain: list[float] = []
        threshold: list[float] = []
        decision_type: list[int] = []
        left_child: list[int] = []
        right_child: list[int] = []
        leaf_value: list[float] = []
        cat_boundaries: list[int] = [0]    # prefix offsets into cat words
        cat_threshold: list[int] = []      # uint32 bitset words

        def walk(slot: int) -> int:
            """Returns the LightGBM child reference for heap `slot`:
            internal index, or ~leaf_idx for a leaf."""
            if ens.is_leaf[t, slot] or ens.feature[t, slot] < 0:
                v = float(ens.leaf_value[t, slot]) * ens.learning_rate
                if t < C:                      # fold base into round 0
                    v += ens.base_score
                leaf_value.append(v)
                return -len(leaf_value)        # ~(leaf_idx) == -(idx+1)
            i = len(split_feature)
            feat = int(ens.feature[t, slot])
            split_feature.append(feat)
            split_gain.append(float(ens.split_gain[t, slot]))
            dt = 0
            if feat in cat_set:
                # One-vs-rest: a single-bit bitset (matched category goes
                # LEFT); threshold holds the index into cat_boundaries.
                k = int(ens.threshold_bin[t, slot])
                words = [0] * (k // 32 + 1)
                words[k // 32] = 1 << (k % 32)
                threshold.append(float(len(cat_boundaries) - 1))
                cat_threshold.extend(words)
                cat_boundaries.append(len(cat_threshold))
                dt |= _CATEGORICAL
            else:
                threshold.append(float(ens.threshold_raw[t, slot]))
            if use_missing:
                dt |= _MISSING_NAN
                if ens.default_left[t, slot]:
                    dt |= _DEFAULT_LEFT
            decision_type.append(dt)
            left_child.append(0)               # patched after recursion
            right_child.append(0)
            left_child[i] = walk(2 * slot + 1)
            right_child[i] = walk(2 * slot + 2)
            return i

        walk(0)
        lines += _tree_block(t, len(leaf_value), {
            "split_feature": split_feature, "split_gain": split_gain,
            "threshold": threshold, "decision_type": decision_type,
            "left_child": left_child, "right_child": right_child,
            "leaf_value": leaf_value, "cat_boundaries": cat_boundaries,
            "cat_threshold": cat_threshold,
        }, ens.learning_rate, n_cat=len(cat_boundaries) - 1)
    lines += ["end of trees", "", "pandas_categorical:null", ""]
    return "\n".join(lines)


def _parse_block(lines: list[str], i: int) -> tuple[dict, int]:
    d: dict = {}
    while i < len(lines) and lines[i].strip():
        k, _, v = lines[i].partition("=")
        d[k] = v
        i += 1
    return d, i


def _node_list_of(trees: list, nan_routes: bool, tree_bits: list = (),
                  **meta) -> NodeListEnsemble:
    """The parsed `Tree=` blocks as a node list: LightGBM's arrays as they
    are, a categorical node's bitset with them (`tree_bits[t][n]`: the ids
    of node n's set, None of an ordinal node).
    Raw thresholds only (`threshold_bin_mapper` ranks them).
    `nan_routes`: some node's missing type is NaN; every node then carries
    a default direction, its default-left bit where its missing type is
    NaN and RIGHT elsewhere (where NaN > threshold would send it: the heap
    import's rule)."""
    def ints(blk, k):
        return [int(float(v)) for v in blk[k].split()]

    def floats(blk, k):
        return [float(v) for v in blk[k].split()]

    per_tree = []
    at_set, sets, nan_as_zero = [], [], []      # the category-set nodes
    for t, blk in enumerate(trees):
        nodes = []
        if int(blk["num_leaves"]) > 1:
            sf, th = ints(blk, "split_feature"), floats(blk, "threshold")
            dts = ints(blk, "decision_type")
            cols = [sf, [0] * len(sf), th, floats(blk, "split_gain"),
                    ints(blk, "left_child"), ints(blk, "right_child")]
            if nan_routes:
                # (a set node has no direction: its NaN rule is its own)
                cols.append([dt >> 2 == 2 and bool(dt & _DEFAULT_LEFT)
                             and not dt & _CATEGORICAL for dt in dts])
            nodes = list(zip(*cols))
            for n, bits in enumerate(tree_bits[t] if tree_bits else ()):
                if bits is not None:
                    at_set.append((t, n))
                    sets.append([b for b in bits if b >= 0])
                    nan_as_zero.append(dts[n] >> 2 != 2)
        per_tree.append((nodes, floats(blk, "leaf_value")))
    ens = node_list_from_trees(
        per_tree, learning_rate=1.0,    # leaf values are final contributions
        base_score=0.0,                 # folded into tree 0's leaves
        has_raw_thresholds=True, has_bin_thresholds=False,
        missing_bin=nan_routes, **meta)
    if sets:
        ens.set_category_nodes(at_set, sets, nan_as_zero=nan_as_zero)
    return ens


def threshold_bin_mapper(ens: "TreeEnsemble | NodeListEnsemble",
                         n_bins: int = 255):
    """The bin mapper FROM THE MODEL'S OWN THRESHOLDS, and the model's
    `threshold_bin` filled to match: per feature the sorted distinct raw
    thresholds are the mapper's edges and a node's bin is its threshold's
    rank, so `x <= t` is `bin(x) <= rank(t)` EXACTLY for every float32 x
    (data/quantizer.py: bin = the number of edges below x). An imported
    model carries raw thresholds only; with this mapper it reaches the
    binned device path:

        ens = TreeEnsemble.from_lightgbm_text(text)
        mapper = threshold_bin_mapper(ens)
        api.predict(ens, X_float, mapper=mapper, cfg=cfg)

    A feature may carry at most `n_bins - 1` distinct thresholds (254 under
    LightGBM's max_bin=255); more is refused by name. A heap's ordinal
    splits, and a node list's ordinal splits and CATEGORY SETS: a column
    some set node asks is a category column (one that an ordinal node asks
    too is refused: the library never makes one), every raw id that some
    set of the model names on it gets a bin of its own, in the ids' order,
    and every other value (an id the model never names, one past a bitset,
    a negative value; NaN too where the column's nodes have the library's
    missing type NaN, id 0's bin where they have None or Zero: LightGBM's
    rule, `NodeListEnsemble`) ONE bin more, which no set holds
    (`BinMapper.category_ids`); the sets' bins are filled to match
    (`cat_bin_sets`). At most `n_bins` ids a column may be named (255 under
    max_bin=255, with the bin of the rest a uint8's 256); more is refused
    by name.
    A model with learned NaN directions (`missing_bin` and `default_left`)
    gets the mapper of the "learn" policy: NaN takes the reserved top bin
    `n_bins - 1` (254), values the bins below it, so a feature may carry
    one threshold less (253). Without directions NaN rows take bin 0 (the
    mapper's "zero" policy) where the raw walk sends them right: bin them
    yourself if the data has any."""
    from ddt_tpu.data.quantizer import BinMapper

    if not ens.has_raw_thresholds:
        raise ValueError("threshold_bin_mapper needs raw thresholds")
    sets = ens.cat_nodes if isinstance(ens, NodeListEnsemble) else None
    if ens.has_cat_splits and sets is None:
        raise ValueError(
            "threshold_bin_mapper covers ordinal splits and a node list's "
            "category sets: this heap carries one-vs-rest category nodes, "
            "whose bins are the category ids themselves")
    missing = bool(ens.missing_bin) and ens.default_left is not None
    live = (ens.live_nodes if isinstance(ens, NodeListEnsemble)
            else ~ens.is_leaf & (ens.feature >= 0))
    n_edges = n_bins - 1 - missing
    edges = np.full((ens.n_features, n_bins - 1), np.inf, np.float32)
    category_ids = _rank_category_sets(ens, sets, live, n_bins) \
        if sets is not None and sets.any() else None
    for f in range(ens.n_features):
        at = live & (ens.feature == f)
        if category_ids and f in category_ids:
            continue
        distinct = np.unique(ens.threshold_raw[at])
        if len(distinct) > n_edges:
            raise ValueError(
                f"feature {f} carries {len(distinct)} distinct thresholds, "
                f"more than the {n_edges} edges of {n_bins} bins"
                + (" of which the top one is NaN's" if missing else "")
                + " (a model trained with max_bin > 255?): it cannot be "
                "scored on binned uint8 rows")
        edges[f, :len(distinct)] = distinct
        ens.threshold_bin[at] = np.searchsorted(distinct,
                                                ens.threshold_raw[at])
    if isinstance(ens, NodeListEnsemble):
        ens.has_bin_thresholds = True
    ens.n_bins = n_bins
    return BinMapper(edges=edges, n_bins=n_bins, missing_bin=missing,
                     category_ids=category_ids)


def _rank_category_sets(ens: NodeListEnsemble, sets: np.ndarray,
                        live: np.ndarray, n_bins: int) -> dict:
    """`threshold_bin_mapper` of a node list's category columns: column ->
    (the raw ids its sets name, ascending; whether NaN counts as id 0), and
    `ens.cat_bin_sets` filled with the sets over those ids' ranks."""
    out = {}
    ens.cat_bin_sets = np.zeros((len(ens.cat_bin_sets), CAT_SET_WORDS),
                                np.uint32)
    tt, nn = np.nonzero(sets)
    ss, col = ens.cat_index[tt, nn], ens.feature[tt, nn]
    for f in np.unique(col):
        f = int(f)
        if (live & ~sets & (ens.feature == f)).any():
            raise ValueError(
                f"feature {f} is asked by category-set nodes and by ordinal "
                "nodes: its values would need two binnings (LightGBM makes "
                "no such model)")
        mine = ss[col == f]
        zero = ens.cat_nan_as_zero[mine]
        if zero.any() != zero.all():
            raise ValueError(
                f"feature {f}: its category-set nodes disagree on the "
                "missing type (NaN goes right in some and counts as id 0 "
                "in others); one binning cannot serve both")
        ids_of = [_set_ids(ens, s) for s in mine]
        ids = np.unique(np.concatenate(ids_of)) if ids_of else np.zeros(
            0, np.int64)
        if len(ids) > min(n_bins, 255):
            raise ValueError(
                f"feature {f}: the model's category sets name {len(ids)} "
                f"ids, more than the {min(n_bins, 255)} that {n_bins} bins "
                "of a uint8 leave beside the bin of every other value (a "
                "model trained with max_bin > 255?): it cannot be scored on "
                "binned uint8 rows")
        for s, mine_ids in zip(mine, ids_of):
            set_bits(ens.cat_bin_sets[s], np.searchsorted(ids, mine_ids))
        out[f] = (ids.astype(np.int64), bool(zero.all()))
    return out


def _set_ids(ens: NodeListEnsemble, s: int) -> np.ndarray:
    """The raw category ids of set `s`, ascending."""
    words = ens.cat_threshold[ens.cat_boundaries[s]:ens.cat_boundaries[s + 1]]
    return np.flatnonzero(np.unpackbits(
        np.ascontiguousarray(words, "<u4").view(np.uint8),
        bitorder="little")).astype(np.int64)


def from_lightgbm_text(text: str) -> "TreeEnsemble | NodeListEnsemble":
    """Parse a LightGBM model.txt back into an ensemble: a TreeEnsemble
    (heap) for trees of at most HEAP_MAX_DEPTH levels, a NodeListEnsemble
    for deeper ones, NaN default directions and category sets with them
    (module docstring, 'Which layout').

    Supports what to_lightgbm_text writes (numerical splits, categorical
    nodes, optional NaN-missing default directions) PLUS
    externally-trained models with multi-category bitsets, which a heap
    holds expanded into equivalent one-vs-rest chains (module docstring,
    'Import breadth': a model of at most HEAP_MAX_DEPTH levels after the
    expansion) and a node list as they are."""
    lines = text.splitlines()
    head, i = _parse_block(lines, 0)
    n_features = int(head["max_feature_idx"]) + 1
    C = int(head.get("num_class", 1))
    obj = head.get("objective", "regression")
    loss = ("logloss" if obj.startswith("binary")
            else "softmax" if obj.startswith("multiclass") else "mse")

    trees = []
    while i < len(lines):
        if not lines[i].startswith("Tree="):
            i += 1
            continue
        blk, i = _parse_block(lines, i)
        trees.append(blk)

    # Per-internal-node category-bit lists (None for numerical nodes),
    # parsed ONCE per tree: both the depth computation and the placement
    # need them — a k-bit categorical set expands into a k-link chain, so
    # it contributes k levels of depth where a numerical node adds 1.
    def bits_of(blk, t: int) -> list:
        if int(blk["num_leaves"]) == 1:
            return []
        sf = [int(v) for v in blk["split_feature"].split()]
        th = [float(v) for v in blk["threshold"].split()]
        dt = [int(float(v)) for v in blk["decision_type"].split()]
        cb = ct = None
        if int(blk.get("num_cat", "0")) != 0:
            cb = [int(v) for v in blk["cat_boundaries"].split()]
            ct = [int(v) for v in blk["cat_threshold"].split()]
        out: list = []
        for ref in range(len(sf)):
            if not (dt[ref] & _CATEGORICAL):
                out.append(None)
                continue
            if cb is None:
                # Malformed/foreign input: categorical decision_type bit
                # set but the tree block carries no bitset arrays. Fail
                # loudly like the other validation paths (a None subscript
                # would raise an opaque TypeError here otherwise).
                raise ValueError(
                    f"tree {t} node {ref}: categorical decision_type but "
                    "num_cat=0 (no cat_boundaries/cat_threshold arrays)"
                )
            cat_idx = int(th[ref])
            words = ct[cb[cat_idx]:cb[cat_idx + 1]]
            bits = [w * 32 + b for w, word in enumerate(words)
                    for b in range(32) if word >> b & 1]
            if not bits and (dt[ref] >> 2) == 2 and dt[ref] & _DEFAULT_LEFT:
                # Empty bitset + NaN-missing + default-LEFT: no category
                # matches, but NaN rows still exit into the LEFT subtree,
                # so the node cannot collapse away. Emit one match-nothing
                # link (sentinel category -1: LightGBM category values are
                # non-negative, so no real value ever equals it) whose
                # default_left carries the NaN route.
                bits = [-1]
            out.append(bits)
        return out

    tree_bits = [bits_of(b, t) for t, b in enumerate(trees)]

    # Depth of each parsed tree (longest root->leaf path), counting each
    # k-bit categorical node as the k levels its expansion chain occupies
    # (an all-rows-right empty bitset collapses to its RIGHT subtree:
    # 0 levels, and the dropped left subtree contributes no depth).
    def depth_of(blk, bits) -> int:
        if int(blk["num_leaves"]) == 1:
            return 0
        lc = [int(v) for v in blk["left_child"].split()]
        rc = [int(v) for v in blk["right_child"].split()]

        def d(ref: int) -> int:
            if ref < 0:
                return 0
            b = bits[ref]
            if b is None:                      # numerical node
                return 1 + max(d(lc[ref]), d(rc[ref]))
            if not b:                          # collapsed empty bitset
                return d(rc[ref])
            return len(b) + max(d(lc[ref]), d(rc[ref]))
        return d(0)

    max_depth = max(1, max(depth_of(b, bi)
                           for b, bi in zip(trees, tree_bits)))
    if max_depth > HEAP_MAX_DEPTH:
        # (a node list's NaN directions are its ORDINAL nodes': a model of
        # set nodes alone has none, and its program is the plain compare)
        nan_routes = any(
            (int(float(v)) >> 2) == 2 and not int(float(v)) & _CATEGORICAL
            for b in trees for v in b.get("decision_type", "").split())
        return _node_list_of(trees, nan_routes, tree_bits,
                             n_features=n_features, loss=loss,
                             n_classes=max(C, 2))
    n_nodes = 2 ** (max_depth + 1) - 1
    T = len(trees)
    feature = np.full((T, n_nodes), -1, np.int32)
    threshold_bin = np.zeros((T, n_nodes), np.int32)
    threshold_raw = np.zeros((T, n_nodes), np.float32)
    is_leaf = np.zeros((T, n_nodes), bool)
    leaf_value = np.zeros((T, n_nodes), np.float32)
    split_gain = np.zeros((T, n_nodes), np.float32)
    default_left = np.zeros((T, n_nodes), bool)
    any_missing = False
    cat_feats: set[int] = set()    # features with categorical nodes
    ord_feats: set[int] = set()    # features with numerical nodes

    for t, blk in enumerate(trees):
        bits_t = tree_bits[t]
        lv = [float(v) for v in blk["leaf_value"].split()]
        if int(blk["num_leaves"]) == 1:
            is_leaf[t, 0] = True
            leaf_value[t, 0] = lv[0]
            continue
        sf = [int(v) for v in blk["split_feature"].split()]
        sg = [float(v) for v in blk["split_gain"].split()]
        th = [float(v) for v in blk["threshold"].split()]
        dt = [int(float(v)) for v in blk["decision_type"].split()]
        lc = [int(v) for v in blk["left_child"].split()]
        rc = [int(v) for v in blk["right_child"].split()]

        def place(ref: int, slot: int, dup: bool = False) -> None:
            # `dup`: this subtree is a repeated COPY made by chain
            # expansion — its split gains are zeroed so gain-sum feature
            # importances count each original split exactly once.
            nonlocal any_missing
            if ref < 0:
                is_leaf[t, slot] = True
                leaf_value[t, slot] = lv[~ref]
                return
            bits = bits_t[ref]
            miss = (dt[ref] >> 2) == 2         # NaN missing type
            if miss:
                any_missing = True
            if bits is None:                   # numerical split
                ord_feats.add(sf[ref])
                feature[t, slot] = sf[ref]
                split_gain[t, slot] = 0.0 if dup else sg[ref]
                threshold_raw[t, slot] = th[ref]
                if miss:
                    default_left[t, slot] = bool(dt[ref] & _DEFAULT_LEFT)
                place(lc[ref], 2 * slot + 1, dup)
                place(rc[ref], 2 * slot + 2, dup)
                return
            if not bits:
                # Empty bitset reaching here means no category matches
                # AND NaN routes right too (default-right, or no missing
                # handling) — bits_of keeps a sentinel link otherwise —
                # so the node collapses to its right subtree; the no-op
                # split's gain vanishes with it.
                place(rc[ref], slot, dup)
                return
            # Categorical set -> a chain of one-vs-rest links: link j
            # tests bits[j] (matched goes LEFT into a copy of the left
            # subtree); the last link's right child is the right subtree.
            # NaN rows follow the node's default direction at EVERY link,
            # so default-left exits left at link 0 and default-right
            # falls through the chain — exactly the un-expanded routing.
            cat_feats.add(sf[ref])
            cur = slot
            for j, b in enumerate(bits):
                feature[t, cur] = sf[ref]
                # Gain on the first link only (same once-per-split rule).
                split_gain[t, cur] = 0.0 if dup or j > 0 else sg[ref]
                # Cat columns hold category ids in BOTH representations,
                # so bin and raw thresholds coincide.
                threshold_bin[t, cur] = b
                threshold_raw[t, cur] = float(b)
                if miss:
                    default_left[t, cur] = bool(dt[ref] & _DEFAULT_LEFT)
                place(lc[ref], 2 * cur + 1, dup or j > 0)
                if j < len(bits) - 1:
                    cur = 2 * cur + 2
            place(rc[ref], 2 * cur + 2, dup)

        place(0, 0)

    both = cat_feats & ord_feats
    if both:
        raise ValueError(
            f"features {sorted(both)} appear in both categorical and "
            "numerical nodes; TreeEnsemble derives split type from the "
            "feature, so mixed use is unrepresentable"
        )

    return TreeEnsemble(
        feature=feature,
        threshold_bin=threshold_bin,
        threshold_raw=threshold_raw,
        is_leaf=is_leaf,
        leaf_value=leaf_value,
        split_gain=split_gain,
        max_depth=max_depth,
        n_features=n_features,
        learning_rate=1.0,          # leaf values are final contributions
        base_score=0.0,             # folded into round 0's leaves
        loss=loss,
        n_classes=max(C, 2),
        has_raw_thresholds=True,
        cat_features=(np.asarray(sorted(cat_feats), np.int32)
                      if cat_feats else None),
        default_left=default_left if any_missing else None,
        # Raw-value traversal tests np.isnan directly; missing_bin=True
        # just switches the learned default_left directions on.
        missing_bin=any_missing,
    )
