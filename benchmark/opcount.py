"""Operations and bytes a kernel's work NEEDS, counted from the cell's shapes.

Never from a kernel's padding, tiling or chunking, so that a kernel PR cannot
make them stale or move them: a kernel that pads to 256 bins, or visits the
rows twice, gets no credit for the extra. Each function takes the
configuration's `shapes` and returns (operations, bytes) of ONE job (one
build, one scoring call). `readers/roofline_share.py` finds a function here
by the name a metric's file gives.
"""

from __future__ import annotations


def traverse_call(shapes: dict) -> tuple[float, float]:
    """Matmul-and-compare traversal of one scoring call: every row against
    every internal node of every tree over F features; bytes: the binned
    rows in, float32 scores out, the node tables once."""
    R, F, T = shapes["rows"], shapes["features"], shapes["n_trees"]
    internal = 2 ** shapes["max_depth"] - 1
    nodes = 2 ** (shapes["max_depth"] + 1) - 1
    ops = 2.0 * R * F * T * internal
    nbytes = R * F + R * 4 + T * nodes * 13
    return ops, float(nbytes)
