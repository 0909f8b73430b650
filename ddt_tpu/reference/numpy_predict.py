"""The plain scoring reference: an ensemble's raw scores in straightforward
NumPy — no kernels, no chunking, no leaf pushdown, no padding.

The semantics, on BINNED rows (uint8/int bins, as `api.predict(...,
binned=True)` takes them): a tree is a heap of 2^(depth+1)-1 nodes; a row at
node n goes LEFT, to 2n+1, when `bin[feature[n]] <= threshold_bin[n]`, else
RIGHT, to 2n+2, until `is_leaf[n]`; the tree then scores `leaf_value[n]`.
The class of tree t is `t % n_classes` (round-major: softmax boosts one tree a
class a round); raw score [rows, classes] = base_score + learning_rate x the
sum of the class's leaf values ([rows] for the one-output losses). Two
optional routings, as the trainer writes them: on a categorical feature
(`cat_features`) the node is one-vs-rest, `bin == threshold_bin` goes left
and every other bin right; with a reserved missing bin (`missing_bin`, the
top bin `n_bins - 1`) a row whose bin is the reserved one follows the node's
learned `default_left`.

Where this departs from the contract of `ops/predict.py` (and its Pallas twin
`ops/predict_pallas.py`), all of it arithmetic and none of it routing:

- It STOPS at a leaf. The device paths push every leaf's value down to the
  bottom level and walk all `max_depth` levels (always-left below a leaf);
  the leaf reached is the same.
- It adds `learning_rate * leaf_value` tree by tree, in tree order, in
  `dtype` (float32 by default; float64 for tolerance studies). The device
  paths sum a block of trees' values in one dot (64 trees on the one-hot
  path, 128 on the kernel, in an order that belongs to the compiler), add
  the blocks, and multiply by the learning rate once at the end. So the two
  agree to float32 rounding of a sum of `n_trees / n_classes` terms, not
  bitwise.
- No tree is padded, no row is chunked, and raw-threshold (float feature)
  scoring is not covered: bins only.

`tests/test_reference_predict.py` holds `api.predict` to it.

The NODE LIST (models/tree.NodeListEnsemble; `leaf_of_rows_node_list`,
`predict_raw_node_list`): tree t is `n_leaves[t] - 1` internal nodes and
`n_leaves[t]` leaves. A row starts at node 0; at internal node n it goes
LEFT, to `left_child[n]`, when `bin[feature[n]] <= threshold_bin[n]`, else
RIGHT, to `right_child[n]`; a negative child c is leaf `~c` and the tree
scores `leaf_value[~c]` (a tree of one leaf scores `leaf_value[0]`). With
learned NaN directions (`missing_bin` and `default_left`, as in the heap) a
row whose bin is the reserved one, `n_bins - 1`, goes LEFT where
`default_left[n]` and else RIGHT, whatever the threshold. Raw
score [rows] = base_score + learning_rate x the sum over the trees. Ordinal
splits and one output column only. Where the device path (the path-matrix
form, `ops/predict.py` and its kernel `ops/predict_paths.py`) departs from
this walk, all of it arithmetic and none of it routing:

- It does not walk. Every node's compare is made for every row, and the
  leaf reached is the one whose whole path agrees (`m == len`); the leaf is
  the walk's, for every row and tree. The NaN route is folded into a second
  threshold (a node answers right when `thr < bin < up`, `up` the NaN bin
  where it sends NaN left): the same answer for every bin.
- It pads a tree's nodes and leaves to a multiple of 128 lanes with nodes
  that always answer left and leaves no path reaches, and the trees to
  whole blocks with trees of no leaf.
- It sums the leaf values of a block of trees lane by lane and then over
  the lanes, adds the blocks, and multiplies by the learning rate once at
  the end: float32 rounding of a sum in another order, not bitwise.
  `tests/test_node_list.py` holds `api.predict` to it.

The AVERAGED FOREST (a node list with VECTOR LEAVES, `leaf_value`
[T, L, C], `loss` "mean"; scikit-learn's random forests through
`models/sklearn_io.py`; `predict_proba_node_list`): the same walk of the
UNCUT tree, and the score [rows, C] is the mean over the trees of the
reached leaves' vectors, in float64. It knows nothing of sub-trees, links
or lanes. Where the device path departs (`ops/predict.py`, "The chain"),
again all arithmetic and no routing:

- It cuts a tree into connected sub-trees of at most 256 lanes and resolves
  every sub-tree for every row; a row's activity follows its one chain of
  sub-trees, and the exit it ends in is the walk's leaf, for every row and
  tree.
- A leaf's float32 vector is held as three bfloat16 pieces (exactly) and
  picked by a 0/1 matmul; the pieces are summed over the trees in float32,
  piece by piece, then added and divided by the tree count: float32
  rounding of a sum in another order (1e-8 of a class share), not bitwise.
- Against scikit-learn itself: thresholds are float32 here (rounded down:
  the same answer for every float32 row) where the library keeps float64,
  and leaf vectors are normalised once at import where `predict_proba`
  normalises at every call. `tests/test_forest.py` holds `api.predict` to
  this file and to the library.

The OBLIVIOUS ensemble (models/tree.ObliviousEnsemble; CatBoost's symmetric
trees; `leaf_of_rows_oblivious`, `predict_raw_oblivious`): tree t of depth D
is D splits and 2^D leaf values. Split d sets BIT d of the row's leaf index
when `bin[split_feature[t, d]] > split_bin[t, d]` (the first split is the
low bit; the same rule as above: `<=` goes left, leaves the bit clear), and
the tree scores `leaf_value[t, index]`. Raw score [rows] = bias + scale x
the sum over the trees. No missing-value route, no category split. With
VECTOR LEAVES (`leaf_value` [T, 2^D, C], `bias` [C]; the library's
`MultiClass`) the tree scores the C values of the ONE leaf it reaches, the
margins are [rows, C], `m_c = bias[c] + scale x the sum over the trees of
leaf_value[t, index, c]`, and the answer is their softmax (`softmax`, in
the margins' dtype: float64 for the tolerance studies). Where the device path (`ops/predict.py`'s jax.numpy form and
its kernel `ops/predict_oblivious.py`) departs, all of it arithmetic and
none of it routing:

- A split's column is selected by a one-hot matrix product, not indexed;
  bins below 256 and 0/1 are exact in bfloat16 and the sum has one term.
- The trees go in groups of 128, a tree a lane; the lanes past the last
  tree hold splits no bin passes and leaves of 0.
- A group's leaf values are summed lane by lane and then over the lanes,
  the groups are added, and the scale is applied once at the end: float32
  rounding of a sum in another order, not bitwise.
- Vector leaves are looked up a class at a time against ONE index plane (C
  multiplexers over the same bits), the margins leave the kernel
  class-major `[C, rows]`, and the softmax is `jax.nn.softmax` in float32
  on the device (stage `predict:link`).
  `tests/test_oblivious.py` and `tests/test_oblivious_mc.py` hold
  `api.predict` to it.

AN XGBOOST MODEL, from the library's own JSON (`predict_xgboost_json`): a
float64 walk of the arrays `Booster.save_model("m.json")` writes, on RAW
float rows. It imports nothing of `models/` and knows nothing of node
lists, heaps, sub-trees, bins or lanes. Tree i is `left_children`,
`right_children` (-1: a leaf), `split_indices`, `split_conditions` and
`default_left`, the root node 0: at an internal node n a row whose value x
of column `split_indices[n]` is NaN goes LEFT where `default_left[n]` and
else RIGHT; any other row goes LEFT where `x < split_conditions[n]`
(STRICT: the library's test) and else RIGHT; a leaf n scores
`split_conditions[n]` (`eta` is in it) into class `tree_info[i]`. Margins
[rows, classes] = the base margin + the class's sum (`binary:logistic`:
logit(`base_score`), one column; the others `base_score` itself); the answer
is their softmax (`multi:softprob`), their sigmoid (`binary:logistic`) or
the margin (`reg:squarederror`). Where this departs from the library, and
the import (`models/xgboost_io.py`) from both:

- The library casts a row's values to float32 and compares float32; so does
  this walk (x and the condition are float32 values, compared as float64:
  the same answer). The import stores `nextafter(condition, -inf)` and
  tests `<=`: the same answer for every float32 x, which
  `tests/test_xgboost.py` holds ON the thresholds, a float32 either side of
  them, at +-0.0 and at +-inf.
- The library sums a row's margins in float32 in tree order; this walk sums
  float64 (the tolerance studies' yardstick); the device sums float32 in
  the kernel's order. Dyadic leaf values: all three bit-equal.
- `base_score` 0.5 of `multi:softprob` is added to every class alike and
  the softmax forgets it; it is kept so that margins compare.
"""

from __future__ import annotations

import numpy as np


def leaf_of_rows(ens, t: int, Xb: np.ndarray) -> np.ndarray:
    """Heap index of the leaf each row of `Xb` ends in, in tree `t`."""
    rows = np.arange(Xb.shape[0])
    node = np.zeros(Xb.shape[0], np.int64)
    feature, thr, is_leaf = (ens.feature[t], ens.threshold_bin[t],
                             ens.is_leaf[t])
    cat = ens.cat_features if ens.cat_features is not None else ()
    use_missing = bool(ens.missing_bin) and ens.default_left is not None
    for _ in range(ens.max_depth):
        f = np.maximum(feature[node], 0)         # a leaf's feature is -1
        b = Xb[rows, f].astype(np.int64)
        right = b > thr[node]
        if len(cat):
            right = np.where(np.isin(f, cat), b != thr[node], right)
        if use_missing:
            right = np.where(b == ens.n_bins - 1,
                             ~ens.default_left[t][node], right)
        node = np.where(is_leaf[node], node, 2 * node + 1 + right)
    return node


def predict_raw(ens, Xb: np.ndarray, dtype=np.float32) -> np.ndarray:
    """Raw scores of `ens` (a models/tree.TreeEnsemble) over binned rows:
    [rows, n_classes] for softmax, [rows] otherwise, accumulated in `dtype`
    in tree order."""
    n_out = ens.n_classes if ens.loss == "softmax" else 1
    out = np.full((Xb.shape[0], n_out), ens.base_score, dtype)
    lr = dtype(ens.learning_rate)
    for t in range(ens.feature.shape[0]):
        leaf = leaf_of_rows(ens, t, Xb)
        out[:, t % n_out] += lr * ens.leaf_value[t].astype(dtype)[leaf]
    return out if n_out > 1 else out[:, 0]


def leaf_of_rows_node_list(ens, t: int, Xb: np.ndarray) -> np.ndarray:
    """Index of the leaf each row of `Xb` ends in, in tree `t` of a
    node-list ensemble: the walk, a level of every row's at a time. At a
    CATEGORY-SET node (`cat_index[t, n]` = s >= 0) a row goes left iff bit
    `bin` of `cat_bin_sets[s]` (256 bits, uint32 [8]) is set; the NaN
    directions of ordinal nodes mean nothing there."""
    rows = np.arange(Xb.shape[0])
    if ens.n_leaves[t] == 1:
        return np.zeros(len(rows), np.int64)
    use_missing = bool(ens.missing_bin) and ens.default_left is not None
    cur = np.zeros(len(rows), np.int64)          # a node, or ~leaf
    while (cur >= 0).any():
        n = np.maximum(cur, 0)
        b = Xb[rows, ens.feature[t][n]].astype(np.int64)
        left = b <= ens.threshold_bin[t][n]
        if use_missing:
            left = np.where(b == ens.n_bins - 1, ens.default_left[t][n],
                            left)
        if getattr(ens, "cat_index", None) is not None:
            s = ens.cat_index[t][n]
            word = ens.cat_bin_sets[np.maximum(s, 0), np.minimum(b >> 5, 7)]
            in_set = (b < 256) & ((word >> (b & 31).astype(np.uint32)) & 1
                                  ).astype(bool)
            left = np.where(s >= 0, in_set, left)
        nxt = np.where(left, ens.left_child[t][n], ens.right_child[t][n])
        cur = np.where(cur >= 0, nxt, cur)
    return ~cur


def predict_raw_node_list(ens, Xb: np.ndarray,
                          dtype=np.float32) -> np.ndarray:
    """Raw scores [rows] of a models/tree.NodeListEnsemble over binned
    rows, accumulated in `dtype` in tree order."""
    out = np.full(Xb.shape[0], ens.base_score, dtype)
    lr = dtype(ens.learning_rate)
    for t in range(ens.feature.shape[0]):
        leaf = leaf_of_rows_node_list(ens, t, Xb)
        out += lr * ens.leaf_value[t].astype(dtype)[leaf]
    return out


def predict_proba_node_list(ens, Xb: np.ndarray) -> np.ndarray:
    """Mean class distributions float64 [rows, C] of an averaged forest (a
    models/tree.NodeListEnsemble with vector leaves) over binned rows: the
    reached leaves' vectors summed in float64 in tree order, divided by the
    tree count."""
    total = np.zeros((Xb.shape[0], ens.leaf_value.shape[2]), np.float64)
    for t in range(ens.feature.shape[0]):
        total += ens.leaf_value[t].astype(np.float64)[
            leaf_of_rows_node_list(ens, t, Xb)]
    return total / ens.feature.shape[0]


def leaf_of_rows_oblivious(ens, t: int, Xb: np.ndarray) -> np.ndarray:
    """Index of the leaf each row of `Xb` ends in, in tree `t` of an
    oblivious ensemble: bit d is split d's answer."""
    idx = np.zeros(Xb.shape[0], np.int64)
    for d in range(ens.split_feature.shape[1]):
        b = Xb[:, ens.split_feature[t, d]].astype(np.int64)
        idx |= (b > ens.split_bin[t, d]).astype(np.int64) << d
    return idx


def predict_raw_oblivious(ens, Xb: np.ndarray,
                          dtype=np.float32) -> np.ndarray:
    """Raw scores [rows] of a models/tree.ObliviousEnsemble over binned
    rows ([rows, C] of vector leaves, a sum a class): the leaf values
    summed in `dtype` in tree order, then scaled, the bias (a vector's
    entry a class) added."""
    total = np.zeros((Xb.shape[0],) + ens.leaf_value.shape[2:], dtype)
    for t in range(ens.split_feature.shape[0]):
        total += ens.leaf_value[t].astype(dtype)[
            leaf_of_rows_oblivious(ens, t, Xb)]
    return np.asarray(ens.bias, dtype) + dtype(ens.scale) * total


def softmax(margins: np.ndarray) -> np.ndarray:
    """Class probabilities [rows, C] of margins [rows, C], in their dtype:
    exp(m_c - max m) over its sum."""
    e = np.exp(margins - margins.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


# objective -> (margin of base_score, link), as the library defines them
_XGB_OBJECTIVES = {
    "binary:logistic": (lambda p: np.log(p / (1.0 - p)),
                        lambda m: 1.0 / (1.0 + np.exp(-m))),
    "reg:squarederror": (float, lambda m: m),
    "multi:softprob": (float, None),        # the softmax, below
}


def predict_xgboost_json(model: dict, X: np.ndarray,
                         raw: bool = False) -> np.ndarray:
    """An XGBoost `gbtree` model's answer over RAW float rows `X`, float64:
    class probabilities [rows, classes] (`multi:softprob`), probabilities
    [rows] (`binary:logistic`) or values [rows] (`reg:squarederror`);
    `raw`: the margins. `model`: the dict the library's JSON parses to.
    The module docstring has the semantics."""
    learner = model["learner"]
    param = learner["learner_model_param"]
    to_margin, link = _XGB_OBJECTIVES[learner["objective"]["name"]]
    C = max(int(param.get("num_class", 0)), 1)
    booster = learner["gradient_booster"]["model"]
    X = np.asarray(X, np.float32).astype(np.float64)
    rows = np.arange(X.shape[0])
    out = np.full((X.shape[0], C), to_margin(float(param["base_score"])),
                  np.float64)
    for tree, cls in zip(booster["trees"], booster["tree_info"]):
        left = np.asarray(tree["left_children"], np.int64)
        right = np.asarray(tree["right_children"], np.int64)
        column = np.asarray(tree["split_indices"], np.int64)
        cond = np.asarray(tree["split_conditions"], np.float32).astype(
            np.float64)
        nan_left = np.asarray(tree["default_left"], bool)
        node = np.zeros(X.shape[0], np.int64)
        while True:
            inner = left[node] >= 0
            if not inner.any():
                break
            x = X[rows, column[node]]
            go_left = np.where(np.isnan(x), nan_left[node], x < cond[node])
            node = np.where(inner, np.where(go_left, left[node],
                                            right[node]), node)
        out[:, int(cls)] += cond[node]
    if raw or link is not None:
        out = out if C > 1 else out[:, 0]
        return out if raw else link(out)
    return softmax(out)
