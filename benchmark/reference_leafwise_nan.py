"""The plain reference of a NODE-LIST ensemble's scoring WITH LEARNED NaN
DIRECTIONS, NumPy, float64: leaf-wise trees held as LightGBM holds them.

Imports nothing of the program and nothing of the benchmark's other
references. The semantics (LightGBM's own, `use_missing`, ordinal splits): a
tree of L leaves is L - 1 internal nodes, numbered from the root, node 0. At
internal node n a row whose bin is b = Xb[row, feature[n]] goes

    where b == nan_bin (the reserved top bin):  LEFT when default_left[n],
                                                else RIGHT
    any other b:                                LEFT when b <= threshold_bin[n],
                                                else RIGHT

to left_child[n] or right_child[n]. A child reference c >= 0 is an internal
node, c < 0 is leaf ~c, and the tree scores leaf_value[~c]. Raw score = base
+ learning_rate * the sum over the trees, in tree order, of the reached
leaf's value.

`control` puts ONE thing wrong, for the runs that `correct` has to fail. Each
is also a PATCH of the tables (`patched`), so that a control run can hand
the program the wrong model and hold its answer to the right one:
    "flipped_default_left"  every node's default direction reversed
    "nan_as_ordinary_bin"   no NaN route: the NaN bin is compared like any
                            bin, and lies above every threshold (always
                            right)
    "strict_less"           b < threshold goes left, at every node
    "bfloat16_leaves"       leaf values rounded to bfloat16 (the nearest
                            precision below the configuration's float32)
"""

from __future__ import annotations

import numpy as np

CONTROLS = ("flipped_default_left", "nan_as_ordinary_bin", "strict_less",
            "bfloat16_leaves")


def bfloat16(values: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)


def patched(tables: dict, control: str | None) -> dict:
    """The tables with the control's ONE thing wrong (`None`: as they are).
    "nan_as_ordinary_bin" drops `default_left`: tables without it have no
    NaN route. "strict_less" lowers every threshold by one bin (b <= t - 1
    is b < t)."""
    if control is None:
        return tables
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    out = dict(tables)
    if control == "flipped_default_left":
        out["default_left"] = ~tables["default_left"]
    elif control == "nan_as_ordinary_bin":
        del out["default_left"]
    elif control == "strict_less":
        out["threshold_bin"] = tables["threshold_bin"] - 1
    else:
        out["leaf_value"] = bfloat16(tables["leaf_value"])
    return out


def leaf_of_rows(tree: dict, Xb: np.ndarray, nan_bin: int):
    """ONE tree (its rows of the tables) over uint8 `Xb`: (leaf index, nodes
    on its path, node visits the NaN route sent left, sent right, visits an
    ordinal compare decided) of each row: the walk, one level at a time
    of every row that has not reached its leaf."""
    leaf = np.zeros(Xb.shape[0], np.int64)
    depth = np.zeros(Xb.shape[0], np.int64)
    rows = np.arange(Xb.shape[0])                  # the rows still walking
    n = np.zeros(Xb.shape[0], np.int64)            # ... and their nodes
    nan_left = nan_right = ordinal = 0
    routed = "default_left" in tree
    while len(rows):
        b = Xb[rows, tree["feature"][n]]
        left = b <= tree["threshold_bin"][n]
        if routed:
            nan = b == nan_bin
            left = np.where(nan, tree["default_left"][n], left)
            nan_left += int((nan & left).sum())
            nan_right += int((nan & ~left).sum())
            ordinal += int((~nan).sum())
        else:
            ordinal += len(rows)
        depth[rows] += 1
        nxt = np.where(left, tree["left_child"][n], tree["right_child"][n])
        done = nxt < 0
        leaf[rows[done]] = ~nxt[done]
        rows, n = rows[~done], nxt[~done]
    return leaf, depth, nan_left, nan_right, ordinal


def raw_scores(tables: dict, learning_rate: float, base: float,
               Xb: np.ndarray, nan_bin: int,
               visited: np.ndarray | None = None,
               control: str | None = None):
    """(float64 raw scores [rows], the deepest path any row took, the node
    visits decided by [the NaN route to the left, to the right, an ordinal
    compare]) of the whole ensemble over `Xb`. `tables`: feature,
    threshold_bin, default_left, left_child, right_child [trees, L-1] and
    leaf_value [trees, L]. `visited` (bool [trees, L], optional) is set
    where a row reached the leaf."""
    tables = patched(tables, control)
    out = np.full(Xb.shape[0], float(base), np.float64)
    deepest, visits = 0, np.zeros(3, np.int64)
    for t in range(tables["feature"].shape[0]):
        leaf, depth, *routes = leaf_of_rows(
            {k: v[t] for k, v in tables.items()}, Xb, nan_bin)
        if visited is not None:
            visited[t, leaf] = True
        deepest = max(deepest, int(depth.max(initial=0)))
        visits += routes
        out += learning_rate * tables["leaf_value"][t].astype(
            np.float64)[leaf]
    return out, deepest, visits
