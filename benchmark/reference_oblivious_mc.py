"""The plain reference of a CatBoost MULTICLASS model's scoring, NumPy,
float64: the library's JSON export walked as it is written, the softmax
included.

Imports nothing of the program and nothing of the benchmark's other
references. The semantics (the library's exported plain applier, `MultiClass`):
tree t of the dict's `oblivious_trees` is D `splits` (`float_feature_index`,
`border`) and `leaf_values`, 2^D x C numbers LEAF-major with the class
innermost. Over a row x,

    index  = sum over d of [ x[float_feature_index_d] > border_d ] << d

(the FIRST split is the LOW bit), the tree scores the C values
leaf_values[index C .. index C + C - 1] into the C classes, and with
`scale_and_bias` = [scale, [bias_0 .. bias_{C-1}]]

    m_c = scale x the sum over the trees of class c's value + bias_c
    p_c = exp(m_c - max m) / the sum over c' of exp(m_c' - max m)

The rows are the cell's BINNED rows taken as values: the drawn model's
borders are k + 0.5 (`datagen_oblivious_mc.py`), so a bin b passes border k
exactly where b > k.

`patched` puts ONE thing wrong in the dict, for the runs that `correct` has
to fail: the program is handed the wrong model and its answer held to the
right one. ("no_link" changes nothing in the dict: the job then asks the
program for the margins.)
    "bfloat16_leaves"     leaf values rounded to bfloat16 (the nearest
                          precision below the configuration's float32)
    "class_major_leaves"  `leaf_values` written class-major ([C, 2^D]), so
                          that a leaf-major reader takes a class's run of
                          leaves for a leaf's run of classes
    "greater_equal"       `>=` for `>`: every split's border moved to the
                          next lower of its column's (rank 0 stays)
    "high_bit_first"      a tree's splits reversed: the index built with
                          the first split as the HIGH bit
    "drop_bias"           the bias of one class (the largest in size) 0
    "no_link"             the margins handed back for the probabilities
"""

from __future__ import annotations

import copy

import numpy as np

CONTROLS = ("bfloat16_leaves", "class_major_leaves", "greater_equal",
            "high_bit_first", "drop_bias", "no_link")
TREES_A_STEP = 64


def bfloat16(values: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)


def n_classes(model: dict) -> int:
    return len(np.atleast_1d(model["scale_and_bias"][1]))


def patched(model: dict, control: str | None) -> dict:
    """The dict with the control's ONE thing wrong (`None` and "no_link":
    as it is)."""
    if control is None or control == "no_link":
        return model
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    out = copy.deepcopy(model)
    C = n_classes(model)
    if control == "drop_bias":
        bias = out["scale_and_bias"][1]
        bias[int(np.argmax(np.abs(bias)))] = 0.0
        return out
    lists = [f["borders"] for f in model["features_info"]["float_features"]]
    for tree in out["oblivious_trees"]:
        if control == "bfloat16_leaves":
            tree["leaf_values"] = bfloat16(np.asarray(
                tree["leaf_values"], np.float32)).astype(np.float64).tolist()
        elif control == "class_major_leaves":
            tree["leaf_values"] = np.asarray(tree["leaf_values"]).reshape(
                -1, C).T.reshape(-1).tolist()
        elif control == "high_bit_first":
            tree["splits"] = tree["splits"][::-1]
        else:                                   # greater_equal
            for sp in tree["splits"]:
                own = lists[sp["float_feature_index"]]
                k = own.index(sp["border"])
                sp["border"] = own[max(k - 1, 0)]
    return out


def margins(model: dict, X: np.ndarray, visited: np.ndarray | None = None):
    """(float64 margins [rows, C], int64 [D]: the (row, tree) visits in
    which each bit was set) of the whole model over the rows `X` (any
    numeric dtype: the values a split's border is compared with).
    `visited` (bool [trees, 2^D], optional) is set where a row reached the
    leaf. Every tree has the model's D splits. The rows are turned over
    once, so that a split's column is one contiguous read; trees go
    TREES_A_STEP at a time."""
    trees = model["oblivious_trees"]
    scale, bias = model["scale_and_bias"]
    bias = np.atleast_1d(np.asarray(bias, np.float64))
    C, T, D = len(bias), len(trees), len(trees[0]["splits"])
    feature = np.array([[sp["float_feature_index"] for sp in t["splits"]]
                        for t in trees], np.int64)
    border = np.array([[sp["border"] for sp in t["splits"]] for t in trees],
                      np.float64)
    leaf = np.array([t["leaf_values"] for t in trees], np.float64).reshape(
        T, 1 << D, C)                       # leaf-major, the class innermost
    cols = np.ascontiguousarray(X.T)                      # [F, rows]
    total = np.zeros((X.shape[0], C), np.float64)
    bit_set = np.zeros(D, np.int64)
    for t0 in range(0, T, TREES_A_STEP):
        t1 = min(T, t0 + TREES_A_STEP)
        index = np.zeros((t1 - t0, X.shape[0]), np.int64)
        for d in range(D):
            bit = cols[feature[t0:t1, d]].astype(np.float64) \
                > border[t0:t1, d, None]
            bit_set[d] += int(bit.sum())
            index |= bit.astype(np.int64) << d
        if visited is not None:
            visited[np.arange(t0, t1)[:, None], index] = True
        total += leaf[np.arange(t0, t1)[:, None], index].sum(axis=0)
    return bias + float(scale) * total, bit_set


def softmax(m: np.ndarray) -> np.ndarray:
    """Class probabilities [rows, C] of margins [rows, C], float64."""
    e = np.exp(m - m.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)
