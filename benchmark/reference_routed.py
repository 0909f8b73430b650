"""The plain reference of a ROUTED ensemble's scoring, NumPy, float64: trees
whose nodes carry a learned direction for missing values and one-vs-rest
category tests beside the ordinal compare.

Imports nothing of the program and nothing of the benchmark's other
references. The semantics, as the source system's trainer defines them
(`missing_policy="learn"`, `cat_features`): a tree is a heap of
2^(depth+1)-1 nodes. At internal node n, with feature f = feature[n],
threshold t = threshold_bin[n], a row whose bin is b = Xb[row, f] goes LEFT,
to child 2n+1, when the FIRST of these three tests that applies says so, and
else right, to 2n+2:

    1. missing   the ensemble reserves a NaN bin (`missing_bin`, the top bin
                 of the binning: 254 at 255 bins) and b is that bin:
                 left iff default_left[n], whatever the feature's kind;
    2. category  f is one of `cat_features`: left iff b == t (the matched
                 category alone goes left, the rest right);
    3. ordinal   left iff b <= t.

Missing overrides category overrides ordinal. Raw score = base +
learning_rate * the sum over the trees, in tree order, of the reached leaf's
value. `routes` says which test decided how many node visits, so that a
caller can refuse a sample in which one of them never did.
"""

from __future__ import annotations

import numpy as np

# What decided a node visit, in the order `routes` counts them.
ROUTES = ("ordinal compare", "category match (sent left)",
          "category rest (sent right)", "NaN bin sent left",
          "NaN bin sent right")


def leaf_of_rows(feature, threshold_bin, is_leaf, depth: int, Xb: np.ndarray,
                 default_left=None, missing_bin: int | None = None,
                 cat_features=(), routes: np.ndarray | None = None
                 ) -> np.ndarray:
    """Heap index of the leaf each row of uint8 `Xb` ends in, for ONE tree.
    `missing_bin` None: the ensemble reserves no NaN bin (test 1 never
    applies). `routes` (int64 [5], optional) is added to: the visits of
    internal nodes by what decided them, in ROUTES' order."""
    rows = np.arange(Xb.shape[0])
    node = np.zeros(Xb.shape[0], np.int64)
    is_cat = np.isin(np.arange(Xb.shape[1]), cat_features)
    none = np.zeros(len(rows), bool)
    for _ in range(depth):
        f = np.maximum(feature[node], 0)
        b, t, cat = Xb[rows, f], threshold_bin[node], is_cat[f]
        miss = none if missing_bin is None else b == missing_bin
        left = np.where(cat, b == t, b <= t)
        if missing_bin is not None:
            left = np.where(miss, default_left[node], left)
        inner = ~is_leaf[node]
        if routes is not None:
            plain = inner & ~miss
            routes += [np.count_nonzero(plain & ~cat),
                       np.count_nonzero(plain & cat & left),
                       np.count_nonzero(plain & cat & ~left),
                       np.count_nonzero(inner & miss & left),
                       np.count_nonzero(inner & miss & ~left)]
        node = np.where(inner, 2 * node + 2 - left, node)
    return node


def raw_scores(tables: dict, depth: int, learning_rate: float, base: float,
               Xb: np.ndarray, missing_bin: int | None = None,
               cat_features=(), routes: np.ndarray | None = None
               ) -> np.ndarray:
    """float64 raw scores [rows] of the whole ensemble over `Xb`. `tables`:
    feature, threshold_bin, is_leaf, leaf_value, and default_left where
    `missing_bin` is given, each [trees, nodes]."""
    out = np.full(Xb.shape[0], float(base), np.float64)
    for t in range(tables["feature"].shape[0]):
        leaf = leaf_of_rows(
            tables["feature"][t], tables["threshold_bin"][t],
            tables["is_leaf"][t], depth, Xb,
            None if missing_bin is None else tables["default_left"][t],
            missing_bin, cat_features, routes)
        out += learning_rate * tables["leaf_value"][t].astype(np.float64)[leaf]
    return out
