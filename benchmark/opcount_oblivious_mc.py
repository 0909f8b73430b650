"""Operations and bytes of an OBLIVIOUS scoring call over VECTOR LEAVES, by
the rules at the top of `opcount.py`: from the cell's shapes, never from a
kernel's padding, tiling or blocking, and never from which unit of the chip
does the leaf lookup.

The count is `opcount_oblivious.py`'s rule (every row against each of a
tree's D splits over F features, the select, and each of the D answers
against the tree's 2^D leaves, the resolve) plus the leaf product a vector
leaf adds: the one-hot of the row's leaf against the tree's 2^D x C leaf
values, 2 x 2^D x C operations a (row, tree). At the Covertype cell's
shapes (F 54, D 6, C 7) that is 2 x (6 x 118 + 448) = 2,312 operations a
(row, tree).

THE CEILING. The shipped kernel looks the leaves up on the VPU, C
multiplexers of 2^D - 1 vector selects over the one index: 441 selects a
(row, tree), a vreg of 1,024 (row, tree) pairs a select. At four vector
selects a cycle (every VALU slot of a bundle, which no body reaches: the
Epsilon body's best stretch holds 1.6) a v5e core at 1.5 GHz resolves
1,024 x 4 x 1.5e9 / 441 = 1.39e10 (row, tree) pairs a second, and the
counted 2,312 operations at 197 TFLOP/s are 8.52e10 a second: the share
cannot pass 16.3%, about a sixth, while the resolve stays on the VPU. A
form that takes the lookup to the MXU keeps float32 leaf values as three
bfloat16 pieces (3 x 2^D x C products where this file counts one), so it
does MORE matmul work than is counted here, and no form that keeps float32
leaves can read over 100%.
"""

from __future__ import annotations


def traverse_call_oblivious_mc(shapes: dict) -> tuple[float, float]:
    """One call: 2 R T (D (F + 2^D) + 2^D C) operations; bytes: the binned
    rows in, float32 [R, C] out, the model once (feature and border at 4 B
    each a split, 4 B a leaf value a class)."""
    R, F, T = shapes["rows"], shapes["features"], shapes["n_trees"]
    D, C = shapes["depth"], shapes["n_classes"]
    leaves = 1 << D
    ops = 2.0 * R * T * (D * (F + leaves) + leaves * C)
    nbytes = R * F + 4 * R * C + T * (8 * D + 4 * C * leaves)
    return ops, float(nbytes)
