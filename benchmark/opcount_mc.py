"""Operations and bytes of a MULTICLASS scoring call, by the rules at the top
of `opcount.py`: from the cell's shapes, never from a kernel's padding, tiling,
grouping or table blocks (a kernel that re-reads its tables every row tile
gets no credit for the re-reading).
"""

from __future__ import annotations


def traverse_call_mc(shapes: dict) -> tuple[float, float]:
    """Matmul-and-compare traversal of one call: every row against every
    internal node of every tree over F features (n_trees counts ALL trees,
    rounds x classes); bytes: the binned rows in, float32 scores of every
    class out, the node tables once (feature, threshold, leaf value and
    leaf flag: 13 B a node)."""
    R, F, T = shapes["rows"], shapes["features"], shapes["n_trees"]
    C = shapes["n_classes"]
    internal = 2 ** shapes["max_depth"] - 1
    nodes = 2 ** (shapes["max_depth"] + 1) - 1
    ops = 2.0 * R * F * T * internal
    nbytes = R * F + 4 * R * C + T * nodes * 13
    return ops, float(nbytes)
