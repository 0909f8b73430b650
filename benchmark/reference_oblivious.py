"""The plain reference of an OBLIVIOUS ensemble's scoring, NumPy, float64:
symmetric trees as CatBoost holds them.

Imports nothing of the program and nothing of the benchmark's other
references. The semantics (the library's exported plain applier): tree t of
depth D is D splits (split_feature[t, d], split_bin[t, d]) and 2^D leaf
values. Over a binned row b,

    index = sum over d of [ b[split_feature[t, d]] > split_bin[t, d] ] << d

(the FIRST split is the LOW bit), the tree scores leaf_value[t, index], and
the raw score is bias + scale x the sum over the trees.

`control` puts ONE thing wrong, for the runs that `correct` has to fail. Each
is also a PATCH of the tables (`patched`), so that a control run can hand the
program the wrong model and hold its answer to the right one:
    "bfloat16_leaves"   leaf values rounded to bfloat16 (the nearest
                        precision below the configuration's float32)
    "greater_equal"     `>=` for `>` at every split
    "high_bit_first"    the index built with the first split as the HIGH bit
    "next_feature"      one split a tree (the first) asks the next column
"""

from __future__ import annotations

import numpy as np

CONTROLS = ("bfloat16_leaves", "greater_equal", "high_bit_first",
            "next_feature")
TREES_A_STEP = 64


def bfloat16(values: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)


def patched(tables: dict, control: str | None, n_features: int = 0) -> dict:
    """The tables with the control's ONE thing wrong (`None`: as they are).
    "greater_equal" lowers every border by one rank (b > k - 1 is b >= k);
    "high_bit_first" reverses a tree's splits (the index comes out with its
    bits reversed); "next_feature" needs `n_features`, to wrap."""
    if control is None:
        return tables
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    out = dict(tables)
    if control == "bfloat16_leaves":
        out["leaf_value"] = bfloat16(tables["leaf_value"])
    elif control == "greater_equal":
        out["split_bin"] = tables["split_bin"] - 1
    elif control == "high_bit_first":
        out["split_feature"] = np.ascontiguousarray(
            tables["split_feature"][:, ::-1])
        out["split_bin"] = np.ascontiguousarray(tables["split_bin"][:, ::-1])
    else:
        moved = tables["split_feature"].copy()
        moved[:, 0] = (moved[:, 0] + 1) % n_features
        out["split_feature"] = moved
    return out


def raw_scores(tables: dict, scale: float, bias: float, Xb: np.ndarray,
               visited: np.ndarray | None = None,
               control: str | None = None):
    """(float64 raw scores [rows], int64 [D]: the (row, tree) visits in
    which each bit was set) of the whole ensemble over uint8 `Xb`.
    `tables`: split_feature, split_bin [trees, D], leaf_value [trees, 2^D].
    `visited` (bool [trees, 2^D], optional) is set where a row reached the
    leaf. The rows are turned over once, so that a split's column is one
    contiguous read; trees go TREES_A_STEP at a time."""
    tables = patched(tables, control, Xb.shape[1])
    feature, border = tables["split_feature"], tables["split_bin"]
    leaf = tables["leaf_value"].astype(np.float64)
    T, D = feature.shape
    cols = np.ascontiguousarray(Xb.T)                     # [F, rows]
    total = np.zeros(Xb.shape[0], np.float64)
    bit_set = np.zeros(D, np.int64)
    for t0 in range(0, T, TREES_A_STEP):
        t1 = min(T, t0 + TREES_A_STEP)
        index = np.zeros((t1 - t0, Xb.shape[0]), np.int64)
        for d in range(D):
            bit = cols[feature[t0:t1, d]].astype(np.int64) \
                > border[t0:t1, d, None]
            bit_set[d] += int(bit.sum())
            index |= bit.astype(np.int64) << d
        if visited is not None:
            visited[np.arange(t0, t1)[:, None], index] = True
        total += np.take_along_axis(leaf[t0:t1], index, axis=1).sum(axis=0)
    return bias + scale * total, bit_set
