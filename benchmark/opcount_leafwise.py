"""Operations and bytes of a NODE-LIST scoring call by the PATH-MATRIX
(GEMM) strategy, by the rules at the top of `opcount.py`: from the cell's
shapes, never from a kernel's padding, tiling or blocking.

This is the GEMM strategy's count (Hummingbird, OSDI 2020): a dense feature
select, every row against every internal node of every tree over F features,
and a dense path resolve, every row's L - 1 node answers against every
leaf's path. A walk of the tree needs far fewer operations a row (about 10
compares at 255 leaves), so the number says how well the chip runs THIS
strategy, not how well it scores trees. A kernel that leaves the strategy
(a sparse path product, a packed select, a walk) needs a `benchmark` issue
for its own count BEFORE it can be measured against this file: held to this
count it would read over 100% of the peak, which the harness refuses
(`readers/roofline_share.py`: the `impossible_reading` trap).
"""

from __future__ import annotations


def traverse_call_paths(shapes: dict) -> tuple[float, float]:
    """One call: 2 R T (L-1) (F + L) operations: the select's 2 R T (L-1) F
    and the resolve's 2 R T (L-1) L; bytes: the binned rows in, float32
    scores out, the node tables once (feature, threshold and two children
    at 4 B each a node, 4 B a leaf value)."""
    R, F, T = shapes["rows"], shapes["features"], shapes["n_trees"]
    L = shapes["n_leaves"]
    ops = 2.0 * R * T * (L - 1) * (F + L)
    nbytes = R * F + 4 * R + T * ((L - 1) * 16 + L * 4)
    return ops, float(nbytes)
