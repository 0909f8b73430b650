"""The plain reference of a NODE-LIST ensemble's scoring, NumPy, float64:
leaf-wise trees held as LightGBM holds them.

Imports nothing of the program and nothing of the benchmark's other
references. The semantics (LightGBM's own, ordinal splits): a tree of L
leaves is L - 1 internal nodes, numbered from the root, node 0. At internal
node n a row whose bin is b = Xb[row, feature[n]] goes LEFT, to
left_child[n], when b <= threshold_bin[n], and else RIGHT, to
right_child[n]. A child reference c >= 0 is an internal node, c < 0 is leaf
~c, and the tree scores leaf_value[~c]. Raw score = base + learning_rate *
the sum over the trees, in tree order, of the reached leaf's value.

`control` puts ONE thing wrong, for the runs that `correct` has to fail:
    "bfloat16_leaves"    leaf values rounded to bfloat16 (the nearest
                         precision below the configuration's float32)
    "strict_less"        b < threshold goes left, at every node
    "swapped_children"   left and right exchanged at ONE node a tree
                         (the tree's node of index seed % (L - 1))
    "sibling_leaf"       for 1% of the (row, tree) pairs, the other child
                         of the reached leaf's parent, where that is a leaf
"""

from __future__ import annotations

import numpy as np

CONTROLS = ("bfloat16_leaves", "strict_less", "swapped_children",
            "sibling_leaf")


def bfloat16(values: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)


def leaf_of_rows(feature, threshold_bin, left_child, right_child,
                 Xb: np.ndarray, strict: bool = False):
    """(leaf index, nodes on its path) of each row of uint8 `Xb`, for ONE
    tree: the walk, one level of every row's at a time."""
    rows = np.arange(Xb.shape[0])
    cur = np.zeros(Xb.shape[0], np.int64)          # a node, or ~leaf
    depth = np.zeros(Xb.shape[0], np.int64)
    while True:
        inner = cur >= 0
        if not inner.any():
            return ~cur, depth
        n = np.where(inner, cur, 0)
        b, t = Xb[rows, feature[n]], threshold_bin[n]
        left = b < t if strict else b <= t
        cur = np.where(inner, np.where(left, left_child[n], right_child[n]),
                       cur)
        depth += inner


def raw_scores(tables: dict, learning_rate: float, base: float,
               Xb: np.ndarray, visited: np.ndarray | None = None,
               control: str | None = None, seed: int = 0):
    """(float64 raw scores [rows], the deepest path any row took) of the
    whole ensemble over `Xb`. `tables`: feature, threshold_bin, left_child,
    right_child [trees, L-1] and leaf_value [trees, L]. `visited` (bool
    [trees, L], optional) is set where a row reached the leaf."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    out = np.full(Xb.shape[0], float(base), np.float64)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    deepest = 0
    values = tables["leaf_value"]
    if control == "bfloat16_leaves":
        values = bfloat16(values)
    for t in range(tables["feature"].shape[0]):
        left, right = tables["left_child"][t], tables["right_child"][t]
        if control == "swapped_children":
            n = seed % len(left)
            left, right = left.copy(), right.copy()
            left[n], right[n] = right[n], left[n]
        leaf, depth = leaf_of_rows(
            tables["feature"][t], tables["threshold_bin"][t], left, right,
            Xb, strict=control == "strict_less")
        if control == "sibling_leaf":
            # the other child of the leaf's parent, where it is a leaf too
            sibling = np.arange(values.shape[1])
            both = (left < 0) & (right < 0)
            sibling[~left[both]], sibling[~right[both]] = (~right[both],
                                                           ~left[both])
            off = rng.random(len(leaf)) < 0.01
            leaf = np.where(off, sibling[leaf], leaf)
        if visited is not None:
            visited[t, leaf] = True
        deepest = max(deepest, int(depth.max(initial=0)))
        out += learning_rate * values[t].astype(np.float64)[leaf]
    return out, deepest
