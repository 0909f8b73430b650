"""Operations and bytes of an AVERAGED FOREST's scoring call by the GEMM
strategy, by the rules at the top of `opcount.py`: from the MODEL's work,
never from a kernel's padding, tiling, blocking or cut.

The forest's trees are ragged (a tree grown to purity has the leaves its
data gave it), so the count is taken from the drawn forest's own SKELETON
(`datagen_forest.skeleton`, which the job puts under `shapes["skeleton"]`:
internal nodes, leaves, the entries of the leaves' paths, all summed over
the trees) and the cell's shapes. A row and the forest cost

    2 F (internal nodes)     the feature select: every node's bin
    2 (path entries)         the path resolve: every leaf's path answered,
                             one multiply-add an entry of a path (a leaf 14
                             nodes down has 14; the sparse product, which
                             no cut of the tree into sub-trees can shrink
                             or grow)
    2 (leaves) C             the class dot: every leaf's vector against
                             whether the row reached it

and nothing for padding (a sub-tree's unused lanes, K rows past column 784,
class lanes past 10), for the chain that links sub-trees, or for any tile of
an implementation: it reads the same work whatever kernel serves, and a
kernel cannot pass 100% by cutting the trees otherwise. As in
`opcount_leafwise.py` this is the strategy's count: a walk of the tree needs
some 14 compares a row and tree.
"""

from __future__ import annotations


def traverse_call_forest(shapes: dict) -> tuple[float, float]:
    """One call; bytes: the binned rows in, float32 [rows, classes] out, the
    node tables once (feature, threshold and two children at 4 B each a
    node, 4 B a class a leaf)."""
    R, F, C = shapes["rows"], shapes["features"], shapes["n_classes"]
    sk = shapes["skeleton"]
    ops = 2.0 * R * (F * sk["nodes"] + sk["path_entries"]
                     + sk["leaves"] * C)
    nbytes = R * F + 4 * R * C + sk["nodes"] * 16 + sk["leaves"] * C * 4
    return ops, float(nbytes)
