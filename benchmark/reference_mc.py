"""The plain reference of a MULTICLASS ensemble's scoring, NumPy, float64.

Imports nothing of the program (only `reference.py`, the benchmark's own walk
of one tree). Softmax boosting grows one tree a class a round and stores them
round-major: tree t scores class `t % n_classes`. Raw score [rows, classes] =
base + learning_rate * the sum, in tree order, of the class's trees' leaf
values. A permutation of the class columns, or one class's trees added to
another's, is a wrong answer here, which a one-column reference could not see.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference


def bfloat16(values: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), as float32: the
    control of a configuration whose leaves are float32."""
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)


def raw_scores(tables: dict, depth: int, learning_rate: float, base: float,
               n_classes: int, Xb: np.ndarray) -> np.ndarray:
    """float64 raw scores [rows, n_classes] of the whole ensemble over `Xb`.
    One thread a class (each writes its own column; NumPy's indexing
    releases the lock), which changes no sum."""
    out = np.full((Xb.shape[0], n_classes), float(base), np.float64)

    def one_class(c: int) -> None:
        for t in range(c, tables["feature"].shape[0], n_classes):
            leaf = reference.leaf_of_rows(
                tables["feature"][t], tables["threshold_bin"][t],
                tables["is_leaf"][t], depth, Xb)
            out[:, c] += learning_rate * tables["leaf_value"][t].astype(
                np.float64)[leaf]

    with ThreadPoolExecutor(n_classes) as ex:
        list(ex.map(one_class, range(n_classes)))
    return out
