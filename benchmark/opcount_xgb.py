"""Operations and bytes of an XGBoost multiclass model's scoring call by the
GEMM strategy, by the rules at the top of `opcount.py`: from the MODEL's
work, never from a kernel's padding, tiling, blocking or cut.

The model's trees are ragged (a round's tree of a rare class has tens of
leaves, the first round's thousands), so the count is taken from the drawn
model's own SKELETON (`datagen_xgb.skeleton`, which the job puts under
`shapes["skeleton"]`: internal nodes, leaves, the entries of the leaves'
paths, all summed over the trees) and the cell's shapes. A row and the
model cost

    2 F (internal nodes)     the feature select: every node's bin
    2 (path entries)         the path resolve: every leaf's path answered,
                             one multiply-add an entry of a path (the sparse
                             product, which no cut of a tree into sub-trees
                             can shrink or grow)
    2 (leaves)               the class sum: every leaf's ONE value (a tree
                             scores one class) against whether the row
                             reached it

and nothing for padding (a sub-tree's unused lanes, a tree of 30 leaves in
256 lanes, K rows past column 54, the zeros a leaf holds in the six classes
that are not its tree's, the three pieces a float32 value is held in), for
the chain that links sub-trees, for the softmax, or for any tile of an
implementation: padding reads as a LOWER share, never as one over 100%. As
in `opcount_forest.py` this is the strategy's count: a walk of the tree
needs some 12 compares a row and tree.
"""

from __future__ import annotations


def traverse_call_xgb(shapes: dict) -> tuple[float, float]:
    """One call; bytes: the binned rows in, float32 [rows, classes] out, the
    node tables once (feature, threshold and two children at 4 B each a
    node, 4 B a leaf)."""
    R, F, C = shapes["rows"], shapes["features"], shapes["n_classes"]
    sk = shapes["skeleton"]
    ops = 2.0 * R * (F * sk["nodes"] + sk["path_entries"] + sk["leaves"])
    nbytes = R * F + 4 * R * C + sk["nodes"] * 16 + sk["leaves"] * 4
    return ops, float(nbytes)
