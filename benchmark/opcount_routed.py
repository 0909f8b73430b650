"""Operations and bytes of a ROUTED scoring call (an ensemble with a learned
direction for missing values and one-vs-rest category nodes), by the rules at
the top of `opcount.py`: from the cell's shapes, never from a kernel's
padding, tiling or grouping, and never from how a kernel routes (integer
selects on the VPU are no matmul operations: a kernel that needs nine of them
a node gets no credit for them).
"""

from __future__ import annotations


def traverse_call_routed(shapes: dict) -> tuple[float, float]:
    """Matmul-and-compare traversal of one call: every row against every
    internal node of every tree over F features; bytes: the binned rows in,
    float32 scores out, the node tables once at 15 B a node (feature,
    threshold, leaf value and leaf flag as `opcount.py` counts them, 13 B,
    and one byte each for the learned direction and the category flag)."""
    R, F, T = shapes["rows"], shapes["features"], shapes["n_trees"]
    internal = 2 ** shapes["max_depth"] - 1
    nodes = 2 ** (shapes["max_depth"] + 1) - 1
    ops = 2.0 * R * F * T * internal
    nbytes = R * F + 4 * R + T * nodes * 15
    return ops, float(nbytes)
