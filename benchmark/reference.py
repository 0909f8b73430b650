"""The plain reference: a tree ensemble's scoring semantics in NumPy, float64.

Imports nothing of the program and takes nothing the program computed except
the ANSWER under test (the node tables of an ensemble, the scores of a call).
A tree is a heap of 2^(depth+1)-1 nodes; a row goes LEFT at node n when
`bin[feature[n]] <= threshold_bin[n]`, to child 2n+1, else to 2n+2, until a
leaf; raw score = base + learning_rate * sum over trees of the leaf's value.
"""

from __future__ import annotations

import numpy as np


def leaf_of_rows(feature, threshold_bin, is_leaf, depth: int,
                 Xb: np.ndarray) -> np.ndarray:
    """Heap index of the leaf each row of uint8 `Xb` ends in, for ONE tree."""
    rows = np.arange(Xb.shape[0])
    node = np.zeros(Xb.shape[0], np.int64)
    for _ in range(depth):
        f = np.maximum(feature[node], 0)
        right = Xb[rows, f] > threshold_bin[node]
        node = np.where(is_leaf[node], node, 2 * node + 1 + right)
    return node


def raw_scores(tables: dict, depth: int, learning_rate: float, base: float,
               Xb: np.ndarray, n_trees: int | None = None) -> np.ndarray:
    """float64 raw scores of the first `n_trees` trees over `Xb`."""
    T = tables["feature"].shape[0] if n_trees is None else n_trees
    out = np.full(Xb.shape[0], float(base), np.float64)
    for t in range(T):
        leaf = leaf_of_rows(tables["feature"][t], tables["threshold_bin"][t],
                            tables["is_leaf"][t], depth, Xb)
        out += learning_rate * tables["leaf_value"][t].astype(np.float64)[leaf]
    return out
