"""The plain reference of an AVERAGED FOREST's scoring, NumPy, float64: a
random forest's trees held as node lists with a class distribution a leaf.

Imports nothing of the program and nothing of the benchmark's other
references. The semantics (scikit-learn's `predict_proba` of a
`RandomForestClassifier`): a tree of L leaves is L - 1 internal nodes,
numbered from the root, node 0. At internal node n a row whose bin is b =
Xb[row, feature[n]] goes LEFT, to left_child[n], when b <= threshold_bin[n],
and else RIGHT, to right_child[n]. A child reference c >= 0 is an internal
node, c < 0 is leaf ~c, and the tree answers leaf_value[~c], a vector over
the classes. The forest's answer [rows, classes] is the MEAN over the trees
of those vectors: the sum in tree order, divided by the tree count. The walk
is of the UNCUT tree: it knows nothing of sub-trees, links or lanes.

`patched` gives the tables with ONE thing wrong, for the runs that
`correct` has to fail (the program is handed the patched tables, or the
reference of the patched tables stands in for the program, and either is
held to the reference of the right ones):
    "strict_less"       b < threshold goes left, at every node
    "swapped_classes"   two class columns exchanged in every leaf (the
                        classes 3 and 8)
    "not_divided"       the sum over the trees, not their mean
    "bfloat16_scores"   every leaf's vector, so every tree's score, rounded
                        to bfloat16 (the nearest precision below the
                        configuration's float32)
    "dropped_chain"     leaves more than 8 nodes down answer nothing: what
                        a chain of sub-trees that loses its links gives
"""

from __future__ import annotations

import numpy as np

CONTROLS = ("strict_less", "swapped_classes", "not_divided",
            "bfloat16_scores", "dropped_chain")
CHAIN_KEPT_NODES = 8


def bfloat16(values: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)


def patched(tables: dict, control: str | None) -> dict:
    """The tables with the control's ONE thing wrong (`None`: as they are).
    "strict_less" lowers every threshold by one bin (b <= t - 1 is b < t);
    "not_divided" multiplies every leaf's vector by the tree count, in
    float64 (the mean of those is the sum of the right ones)."""
    if control is None:
        return tables
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    out = dict(tables)
    values = tables["leaf_value"]
    if control == "strict_less":
        out["threshold_bin"] = tables["threshold_bin"] - 1
    elif control == "swapped_classes":
        out["leaf_value"] = values.copy()
        out["leaf_value"][..., [3, 8]] = values[..., [8, 3]]
    elif control == "not_divided":
        out["leaf_value"] = values.astype(np.float64) * values.shape[0]
    elif control == "bfloat16_scores":
        out["leaf_value"] = bfloat16(values)
    else:
        out["leaf_value"] = np.where(
            (tables["leaf_depth"] > CHAIN_KEPT_NODES)[..., None],
            np.float32(0), values)
    return out


def leaf_of_rows(feature, threshold_bin, left_child, right_child,
                 Xb: np.ndarray):
    """(leaf index, nodes on its path) of each row of uint8 `Xb`, for ONE
    tree: the walk, one level at a time of every row still on its way."""
    leaf = np.zeros(Xb.shape[0], np.int64)
    depth = np.zeros(Xb.shape[0], np.int64)
    rows = np.arange(Xb.shape[0])
    n = np.zeros(Xb.shape[0], np.int64)
    while len(rows):
        left = Xb[rows, feature[n]] <= threshold_bin[n]
        nxt = np.where(left, left_child[n], right_child[n]).astype(np.int64)
        depth[rows] += 1
        done = nxt < 0
        leaf[rows[done]] = ~nxt[done]
        rows, n = rows[~done], nxt[~done]
    return leaf, depth


def class_scores(tables: dict, Xb: np.ndarray,
                 visited: np.ndarray | None = None):
    """(float64 mean class vectors [rows, classes], the deepest path any
    row took, the nodes a row passed in a tree on average) of the whole
    forest over `Xb`. `tables`: feature, threshold_bin, left_child,
    right_child [trees, N], leaf_value [trees, L, classes], n_leaves
    [trees]. `visited` (bool [trees, L], optional) is set where a row
    reached the leaf."""
    T = tables["feature"].shape[0]
    out = np.zeros((Xb.shape[0], tables["leaf_value"].shape[2]), np.float64)
    deepest, passed = 0, 0
    for t in range(T):
        if tables["n_leaves"][t] == 1:
            leaf = np.zeros(Xb.shape[0], np.int64)
            depth = leaf
        else:
            leaf, depth = leaf_of_rows(
                tables["feature"][t], tables["threshold_bin"][t],
                tables["left_child"][t], tables["right_child"][t], Xb)
        if visited is not None:
            visited[t, leaf] = True
        deepest = max(deepest, int(depth.max(initial=0)))
        passed += int(depth.sum())
        out += tables["leaf_value"][t].astype(np.float64)[leaf]
    return out / T, deepest, passed / max(1, T * Xb.shape[0])
