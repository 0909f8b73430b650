"""Operations and bytes of an OBLIVIOUS scoring call, by the rules at the
top of `opcount.py`: from the cell's shapes, never from a kernel's padding,
tiling or blocking.

The count is the dense strategy's, the leaf-wise count's rule
(`opcount_leafwise.py`) read for a symmetric tree: every row against each
of a tree's D splits over F features (the select), and each of the D
answers against the tree's 2^D leaves (the resolve). The shipped kernel
resolves on the VPU and pads F to whole blocks of 128 columns and the trees
to whole groups of 128, so at the Epsilon cell's shapes its matmuls alone
are 2 R x 8064 x 6 x 2048 operations against this file's 2 R x 8000 x 6 x
2064 (99.98% of them, 99.97% with the last row tile's padding), and at the
MXU's own clock they take 1,209.7 ms where this count is 1,207.0 ms at the
published peak: the highest share it can read is 99.77% (PERF.md section 5
has the arithmetic). A kernel that packs the 48,000 select columns into 375 lane
tiles with nothing left over would do 2 R x 48,000 x 2048 and read 100.8%:
it needs a `benchmark` issue for its own count first (the
`impossible_reading` trap of `readers/roofline_share.py`).
"""

from __future__ import annotations


def traverse_call_oblivious(shapes: dict) -> tuple[float, float]:
    """One call: 2 R T D (F + 2^D) operations; bytes: the binned rows in,
    float32 scores out, the model once (feature and border at 4 B each a
    split, 4 B a leaf value)."""
    R, F, T = shapes["rows"], shapes["features"], shapes["n_trees"]
    D = shapes["depth"]
    ops = 2.0 * R * T * D * (F + (1 << D))
    nbytes = R * F + 4 * R + T * (D * 8 + (1 << D) * 4)
    return ops, float(nbytes)
