"""Pallas TPU traversal kernel for batch ensemble scoring — binned data.

Why this kernel exists (round-5 phase breakdown, docs/PERF.md): the pure-XLA
one-hot predict path is bound by the comparison matrix's HBM traffic — the
[row_chunk, tree_chunk, Nint] compare bits are ~33 MB per chunk pair, ~644 GB
total for the 10M x 1000 config against the v5e's ~820 GB/s, while the MXU
part of the matmul is ~0.2 ms of the 1.13 s P1 phase. Same disease the
histogram kernel had (ops/hist_pallas.py), same cure: build the per-tile
working set IN VMEM and never let it touch HBM. The only HBM traffic is the
binned input itself (R x F, at the width it comes in: uint8 from the
backend) plus the tiny tree tables and the [C, R] scores — the comparison
matrix, feature one-hots, and descent state live and die inside one row
tile's VMEM residency.

Layout strategy. The grid is (row tiles, table blocks): one step scores one
tile of TILE_R rows against one BLOCK of G tree groups; TILE_R is the
plan's (`table_plan`, `_step_rows`): 256 rows where the step holds 256 MXU
weight tiles or more, up to 1,024 where it holds fewer, so that a step of
one small group does not pay a step's tail and fixed cost four times for
the work of one. Trees are taken in
GROUPS of TREE_GROUP = 128, one vreg's lanes and one MXU weight tile,
whatever tree_chunk the compiled ensemble was laid out with. The tables
STREAM from HBM a block at a time (Mosaic double-buffers the windows, so the
next block's DMA runs under this block's matmuls); the [C, TILE_R] output
block and the row tile stay resident over the block axis, the output written
by the first block and added to by the others. G is not a knob
(`table_plan`): the most groups whose double-buffered windows fit
_VMEM_BUDGET_BYTES beside the working set, evened out over the blocks, and
ALL of them where the whole ensemble fits: a 1000-tree depth-6 ensemble
(8 groups, 1.3 MB) is one block, its grid (tiles, 1), its tables fetched
once and resident as before. 500 rounds x 7 classes at depth 8 are 28
groups in 4 blocks of 7. The kernel's trace is G groups long whatever the
tree count. A group holds U trees in its lanes 0..U-1 (`trees_per_group`):
128, or WHOLE ROUNDS of the C classes, 126 at C = 7, where that lets a
block's groups share their class dot (Class scatter, below). The padded
tree count is padded again, inside the jitted program, to a multiple of
G x U with trees that score 0, as the lanes past U do:

    X     [TILE_R, F]        uint8 or int32 bins, as the caller holds
                             them (HBM interface, below), widened to int32
                             once a grid step. In-VMEM the matmul's left
                             operand, bf16 [TILE_R, K]: the row tile as it
                             is (P = 1, K = F), or two copies of it side by
                             side, the second times 256 (P = 2; below).
    feat  [nb, G*Nint, 128]  ONE PLANE PER ROW: row g*Nint + n of block b
    thr/dl/cat               holds node n of the 128 trees of the block's
                             group g. A node's table entries are a row load
                             at lane offset 0 and a sublane broadcast (the
                             only form of broadcast Mosaic gives a layout
                             on both sides of a select), no lane slices,
                             no gathers anywhere. thr is f32 bins at P = 1
                             and at P = 2 int32, prepared for the compare
                             on the packed word (predict_effective_pallas);
                             in the folded routed form the three planes
                             are h, delta and c, all f32 (below).
    val   [nb, G*W, 128]     bottom-level pushed-down leaf values, same.
    coh   [8k, 128]          class one-hot of a group's lanes, TURNED
                             OVER: class c on sublane c (C padded to whole
                             sublanes), lane l hot where l % C == c. ONE
                             for the whole ensemble, fetched once;
                             [nb, 8k, G*128], 128 lanes a group, where each
                             group keeps its own class dot.
    out   [C, TILE_R]        the block's share of the scores, CLASS-MAJOR:
                             the tile's rows on the lanes.

The HBM interface (PR 36): the two arrays of the ROWS cross between XLA and
the kernel at the data's own width. HBM pads an array's last dimension to
128 lanes, so an `s32[R, F]` copy of the chunk is 512 B a row whatever F
(1.02 GB for 2M rows where the uint8 chunk is 56 MB at F = 28), and an
`f32[R, 1]` result the same again to carry 4 B a row: the XLA fusions that
wrote the one and read the other were 100 and 68 ms of a 100M-row call
(PERF.md section 6, PR 35). So the row block is the caller's uint8 (or
int32; any other integer is cast to int32 in XLA first) and the int32 copy
exists a tile at a time, in VMEM; the grid is cdiv(R, TILE_R) over the
UNPADDED rows, the last block ragged (rows are independent: what the block
holds past row R decides nothing that is written, and Pallas drops what
the out block holds there); and the result is `f32[C, R]`, rows on the
lanes, 8 MB a 2M-row chunk at C = 1, which the epilogue scales in one pass
and, at C > 1, hands on as `[R, C]` in the layout it already has.

P nodes share one MXU weight tile (`nodes_per_tile`: from F and whether the
ensemble carries a routing table, nothing else). A node's matmul contracts
over K = F of the weight tile's 128 rows, and the kernel is bound by the
NUMBER of MXU results it asks for, so the idle rows carry a second node of
the same 128 trees. The left operand is [x | 256 x] (each copy starting at
a multiple of 8 rows), the weight tile the two nodes' feature one-hots
stacked along K, and one matmul returns

    word[row, tree] = colval_a + 256 * colval_b

Bins are integers 0..255 and the powers of two are exact in bfloat16, every
product is exact in f32, and every partial sum is a sum of distinct bytes
below 2^16, so the word is the exact integer in any summation order; the
same feature in both nodes is no special case, each copy of x having its
own K rows. To read the bytes the VPU wants the integer, and an f32 -> i32
convert costs it two to three operations a vreg: instead a column of ones
in x times _MANTISSA (1.5 x 2^23) in the weight tile (or, where the tile
has no 8 rows to spare, F = 57..64, one VPU add) puts the word in the low
bits of the f32's mantissa, and the bitcast is the conversion. The high
byte then compares as the whole word against (thr + 1) << 8 plus the
constant high bits, one operation as before; the low byte behind an `& 255`.
Every goes_right bit, every leaf and every score is the unpacked form's:
tests/test_predict_pallas.py demands the bits. The pairs are the mux
tree's SIBLINGS (2n+1 low, 2n+2 high), computed when their parent is
visited, and the root alone: 2^(depth-1) weight tiles a group where one a
node is 2^depth - 1 (`mxu_tiles_per_group`: 32 for 63, 128 for 255). F > 64
keeps one node a tile, the program it was, and so does an ensemble with the
missing or the categorical table (the routed form, next paragraph). Three
nodes a tile (a node with its two
children, 24 bits, no room for the mantissa trick) lost to two on the v5e:
3,082 cycles a depth-6 step of 256 rows x 128 trees against 2,696, one node
a tile 4,654 (PERF.md sections 5 and 6, PR 28).

The ROUTED form (an ensemble with the missing or the categorical table;
one node a tile) spends the tile's idle K rows on the routing itself
(`routes_in_tile`, PR 32). What `_descend_comp` decides at a node is a
function of the bin b of the node's feature and of three constants of the
(node, tree): the threshold, whether the node is one-vs-rest, the learned
direction. All of it is linear in b, in m = [b == missing_bin_value] and
in 1, up to ONE absolute value. So the left operand is [2x | m | ones]
(each part from a multiple of 8 K rows; built once a grid step), the
node's weight tile its feature one-hot over the x rows, delta times the
same one-hot over the m rows and c in the first ones row, and the matmul
returns

    w[row, tree] = 2 b + delta m + c          goes_right = |w| > h

  ordinal node, threshold t:   c = -t,  h = t:  |2b - t| > t  <=>  b > t
  category node, bin t:        c = -2t, h = 0:  |2b - 2t| > 0 <=>  b != t
  NaN bin, learned left:       delta = -2 nan (w = -t) or 2 (t - nan) (w = 0)
  NaN bin, learned right:      delta = 512, past every |w| a bin reaches

(`_folded_routes`, in the jitted prologue; an ordinal node that sends
every bin right, t < 0, is a category node that matches none; a
pushed-down leaf has an empty one-hot, so w = c = -255 against h = 255:
left.) Without the categorical table there is no doubling, no ones row
and no absolute value: w = b + delta m against h = t, delta -(nan + 1) or
256. Without the missing table no m rows. Every constant is an integer of
at most 256 in magnitude or an even one of at most 512, exact in
bfloat16, and every partial sum an integer below 2^11: w is exact in any
summation order, as the packed word is. The operands stay feat and two or
three planes a node (h where thr was, delta where dl, c where cat), so
the table plan does not know the difference. It serves where the left
operand fits the tile's 128 K rows, counted for both tables whichever
are there: 2 x 8 ceil(F / 8) + 8 <= 128, F <= 56. Wider routed models
keep the integer routing of `_descend_comp`, term for term, on the VPU
(the form every routed model had before): a compare and a convert, a
broadcast and a test of the `cat` plane, a second compare, convert and
select, a third compare for the NaN bin, a broadcast of the `dl` plane, a
subtract, a select and a last != 0 a node, which bound the kernel to the
VPU: 5,678 cycles a depth-6 step with both tables in PR 28's probe (28
features, 8 groups a grid step), 5,649 through `api.predict` at the CTR
model's 39 features and 100 trees (PERF.md section 5, PR 31), against
4,516 for the unrouted one-node form. Folded, that model's step is 4,866
cycles (PR 32), bit-equal scores: the MXU's 69 results a group (4,416
cycles) and a grid step of one group. One table alone was never far from
there (the missing table 4,892 -> 4,865, the categorical one 4,972 ->
4,763): the three-way select of both was what cost. Two nodes a tile on
the routed form lost before the fold, 14%, to reading a byte out of the
packed word for the integer routing; on the folded form it is open
(ROADMAP A1).

Per tree group of the block (static Python loop, traced once), per weight
tile (depth-first, at the parent of its nodes):
    foh [K, 128] bf16: per node a one-hot [F, 128] built on the VPU by
        SUBLANE-broadcasting the node's feature row against a sublane iota
        (the hist_pallas transposed-kernel trick), the two joined along
        the sublanes with the mantissa row, then one MXU weight tile:
        colval = X @ foh — the exact bin value of each node's feature at
        every (row, tree) of the group, one a byte.
    goes_right = colval > thr per node, a predicate used as it is. The
        routed form, folded: |w| > h, the tile's result against the h
        plane (w > h with the missing table alone); past 56 features the
        integer routing of ops/predict._descend_comp on the `cat` and
        `dl` planes, term for term, then != 0.
    Value mux tree: a full tree whose Nint comparison bits are all known
        is a multiplexer over its W leaf values —
        leaf(n) = where(goes_right(n), leaf(2n+2), leaf(2n+1)), the leaves
        being val's rows. Nint compares + Nint selects per (row, tree)
        where the path has depth nodes: there is no node index k, no k == i
        and no leaf select. Depth-first keeps depth + 1 value planes live,
        and at P = 2 one packed plane a level.
    Class scatter: acc[c, row] = sum over lanes of coh[c, lane] *
        vsum[row, lane] (f32, HIGHEST; both operands contract their
        LANES, so the classes land on the sublanes and the rows on the
        lanes: the out block, no transpose of it), ONE dot a grid step,
        vsum the lane-by-lane f32 sum of the block's G root value planes
        (one plane live across the groups, 32 vreg adds a group). HIGHEST
        makes the product six bfloat16 passes and a split of the plane
        into three bfloat16 parts on the VPU: a dot a group was 384 of
        the 2,432 MXU cycles of a depth-6 group. Turned over, the value
        plane is the dot's weight operand (two tiles of it a pass) and the
        one-hot's 8 sublanes the streamed one. What that costs depends on
        the step around it (ms a 2M-row chunk through `api.predict` on
        the v5e, the parent's [TILE_R, 128] @ [128, C] dot / this one /
        the parent's dot followed by an XLU transpose of its result;
        PERF.md section 6, PR 36): the routed one-group step 25.346 /
        24.939 / 25.637, 1000 trees x depth 6 in a block of 8 groups
        90.548 / 91.006 / 90.798, Covertype's streamed 7-class step
        1,228.4 / 1,236.4 / 1,228.7. This form is the one within 1% of
        the parent everywhere, and ONE form ships; why it loses 0.4-0.6%
        to the transpose where two nodes share a tile and a block holds
        7-8 groups, and wins 0.7-8% everywhere else tried (one group a
        step; one node a tile), is open. What lets the
        lanes be added first: tree t's class is t % C, so lane l is class
        l % C in EVERY group where a group holds whole rounds,
        U = C (128 // C) trees (128 wherever C divides 128, always at
        C = 1; 126 at C = 7, lanes 126 and 127 left to trees that score
        0). Where whole rounds take more groups than they save in dots
        (10 classes at depth 8) the groups keep 128 trees and a dot each,
        as a block of one group does (`table_plan` decides, from C, the
        tree count and G; `class_dots_per_step` says which). On the v5e
        a depth-6 step of 256 rows x 128 trees went from 2,573 to 2,177
        cycles and a depth-8 step from 8,783 to 8,423 (PERF.md section 6,
        PR 34).

Contract: the SAME leaf per (row, tree) as ops/predict.predict_raw
(missing-value routing, categorical one-vs-rest, softmax round-major classes
all preserved). The float accumulation adds a block's groups lane by lane
and sums the 128 lanes in one dot where the one-hot path sums tree_chunk
trees at a time, and the summation order inside a dot belongs to the
compiler, so scores agree to f32 rounding, not bitwise
(tests/test_predict_pallas.py: equality on dyadic leaf values, a 1e-6
tolerance on random ones).
Interpret mode auto-selects off-TPU (utils/device.platform), same pattern
as hist_pallas.py; the dispatch rule is `kernel_serves`, below (the
`use_pallas` flag on predict_raw / predict_raw_effective, one-hot fallback).
"""

from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddt_tpu.telemetry.annotations import op_scope, traced_scope
from ddt_tpu.telemetry.costmodel import costed
from ddt_tpu.utils import device

# VMEM ceiling: the kernel's working set + one block of tree tables and
# the row tile in Mosaic's double-buffered operand windows must fit the
# 16 MiB scoped-VMEM limit; 12 MB leaves the same headroom hist_pallas
# budgets. It sets how many tree groups a table block holds (table_plan).
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_DEFAULT_TILE_R = 256
# Trees per group: the lanes of one vreg and the columns of one MXU weight
# tile, so that every node plane [TILE_R, 128] is whole vregs. Not the
# compiled ensemble's tree_chunk (64): on the v5e the same mux tree over
# 64-lane planes, half of every vreg empty, took 524.8 ms for 2M rows x
# 1024 trees against 194.2 ms over 128-lane planes (PERF.md section 6,
# PR 26).
TREE_GROUP = 128
# Working-set bytes of a tile beside the operand windows: the bf16 copies
# of the rows, the weight tile in flight (one-hots, colval, predicate),
# with two nodes a tile the packed plane a level, and the depth + 1 value
# planes of the depth-first mux tree, each [TILE_R, 128] f32. It grows
# with the rows and not with the tree count: no [TILE_R, Nint*128] array
# exists. Taken from the compiler's own account: AOT compiles for a
# described v5e with the kernel's scoped limit forced to 1 MiB
# (pltpu.CompilerParams(vmem_limit_bytes=2**20) and every group in one
# block), so that each refusal names its scoped allocation; the row
# tile's two windows (2 KiB a row) are added to it, as PR 26 counted
# (compile check, PR 28; KiB a row; 1000 trees, 28 features, 1 class,
# tile 256 unless said; in brackets one node a tile, the parent's
# program, under the same probe):
#   no optional operand, two nodes a tile: depth 4 / 6 / 8: under 6 / 7.0
#       / 9.0 [under 6 / 7.2 / 9.2] (9 trees: under 6; 4000 trees: 7.0;
#       tile 512: 6.8 [7.3]; 64 features: 7.1 [7.2]; depth 8, 54 features,
#       7 classes: 9.1 [9.2], and 8.9 at tile 512 [9.4]): the packed plane
#       a level costs nothing the compiler did not already hold
#   one of them (one node a tile, as PR 26 read them): missing, depth 6 /
#       7: 7.4 / 8.4; cat, depth 6: 7.8
#   both (one node a tile): depth 3 / 5 / 6 / 7 / 8: 7.4 / 15.3 / 19.0 /
#       27.4 / 34.9 (depth 6 at tile 512: 18.7; 4000 trees: 19.0; 64
#       features: 19.4; depth 8, 54 features, 7 classes: 35.1)
# Only the three-way integer routing of both operands makes the compiler
# keep something per node (two nodes a tile WITH a routing table kept far
# more: 30.0 with the missing table alone at depth 6, 26.4 with both;
# one more reason they keep one node a tile). 12 KiB a row, and 192 B a
# node more with both, bound every probe by an eighth or more.
# What it keeps is kept for TWO groups and no more (compile check, PR 31;
# the same probe at 39 features, depth 6, both tables, scoped MiB at tile
# 256 without the row tile's windows): 1 group a block 2.09, 2 / 3 / 4 / 8
# groups 4.08 / 3.96 / 4.34 / 4.34 (28 features: 2.09, then 4.09 / 3.96 /
# 4.25 / 4.25); the missing table alone 1.09, 1.32, 1.34 at 1, 4, 8 groups.
# So both tables cost 65 B a row and node in a block of one group (the CTR
# model's 100 trees: 10.4 KiB a row with the windows, under _ROW_BYTES
# alone) and 190 B from two groups on: the compiler holds the next group's
# routing planes while this group's mux tree finishes, and never a third
# group's. Depth 4 / 5 / 7 with both: 1 group under 1.0 / 1.48 / 3.24, 4
# groups 2.12 / 3.32 / 6.07. Why it does so for the three-way select and
# not for one table is still open; the count below stays the bound.
# That is the INTEGER routing, which since PR 32 only models of more than
# 56 features take (`routes_in_tile` 0). The folded routed form has no
# three-way select and keeps nothing per node (compile check, PR 32; the
# same probe, 39 features, scoped MiB at tile 256 without the row tile's
# windows, 1 MiB = 4 KiB a row): both tables, 100 trees 1.12; 1000 trees
# in blocks of 2 / 8 groups at depth 6: 1.25 / 1.47 (56 features 1.48);
# depth 4 / 5 / 7 / 8 / 9 / 10: under 1.0 / 1.03 / 1.47 / 1.87 (54
# features 1.88) / 1.85 / 2.12; the categorical table alone, depth 6 / 8
# / 10: 1.48 / 1.85 / 2.07; the missing table alone: under 1.0 / 1.11 /
# 1.11. So 10.5 KiB a row with the windows at depth 10, an eighth under
# _ROW_BYTES, which bounds the folded form alone. (With the predicate
# BEFORE the subtrees, the unrouted order, the same probes read 1.81 at
# depth 6, 2.46 at depth 8 and 3.09 at depth 10: 14.4 KiB a row.)
# The plane that sums a block's groups before the one class dot costs the
# compiler nothing it did not hold (compile check, PR 34; the same probe,
# every group in one block, scoped MiB at tile 256 without the row tile's
# windows, the parent's program in brackets): 1000 trees, 28 features,
# depth 6 / 8: 1.14 / 1.67 [1.25 / 1.76]; 4000 trees 1.14 [1.25]; 130
# trees 1.08 [1.20]; 3 classes 1.14 [1.25]; 64 features 1.20 [1.28]; 3,500
# trees x depth 8 x 54 features x 7 classes in whole rounds 1.68 [1.79];
# tile 512 at depth 6: 2.33 [2.41]; only one node a tile (65 features)
# grows, 1.38 [1.32]: 7.5 KiB a row with the windows. Depth 4 compiles
# under the probe's 1 MiB, as the parent does.
_ROW_BYTES = 12 * 1024
_ROW_NODE_BYTES_BOTH = 192
# The rows a grid step takes (`_step_rows`): 256, doubled while the step's
# MXU weight tiles times its rows stay within _STEP_MXU_TILES (the
# 1000-tree cell's 8 groups x 32) times 256, _STEP_ROWS_MOST at most, and
# never more than a _STEP_ROWS_SHARE-th of the program's rows
# (`TablePlan.step_rows`).
_STEP_MXU_TILES = 256
_STEP_ROWS_MOST = 1024
_STEP_ROWS_SHARE = 16
# ... and the working set a row of such a longer step is charged, where a
# 256-row step's (_ROW_BYTES, which decides G and whether the kernel
# serves at all) would refuse 1,024 rows by itself. From the compiler's
# own account again (compile check, PR 42: the 1 MiB probe above, every
# group in one block, uint8 rows, the scoped allocation WITHOUT the
# operand windows, which the compiler allots apart: a window past the
# limit is refused under its own size; KiB a row at tile 1024, at tile
# 512 in brackets; linear in the rows: tile 2048 reads 10.98 / 8.97 MiB
# where 1024 reads 5.48 / 4.47):
#   two nodes a tile, 100 trees, 28 features, depth 4 / 5 / 6 / 7 / 8:
#       2.45 / 3.47 / 4.47 / 5.50 / 6.52 [2.42 / 3.50 / 4.48 / 5.46 /
#       6.50]; 250 / 500 trees (2 / 4 groups) at depth 6: 4.98 / 5.00;
#       1000 trees [5.02]; 64 features 4.53, depth 8 6.53; 7 classes, 54
#       features: 210 trees x depth 6 5.03 [4.98], 105 x depth 8 6.59
#   one node a tile, 65 / 128 features, depth 6: 4.46 / 4.51, depth 8:
#       6.46 / 6.47; 256 features 5.80, 512 [6.96]: 5-6 B a row and
#       feature past 128, which the row tile's windows, charged at 32
#       bits, cover (8 B)
#   folded, both tables, 39 features, depth 4 / 5 / 6 / 7 / 8: 3.38 /
#       3.50 / 5.48 / 5.41 / 7.42 [3.22 / 3.46 / 5.42 / 5.34 / 7.32]; 56
#       features 5.48 / 7.43 at depth 6 / 8; 250 trees 5.40, 500 [5.82];
#       the categorical table alone 5.47 / 7.37, the missing one 2.51 /
#       4.39
#   integer routing (57 features), one table, depth 6: 4.48 / 4.51,
#       depth 8 6.53; both, 100 trees, depth 4 / 5 / 6 / 7: 3.67 / 6.16 /
#       8.65 / 13.10; 250 trees, depth 4 / 5: 7.61 / 12.23, depth 6
#       [15.24]; 500 trees, depth 4: 9.59: under 8 KiB and
#       _ROW_NODE_BYTES_BOTH a node by a ninth (10.8 / 13.8 / 19.8)
# 8 KiB a row bounds every reading of the forms that keep nothing per
# node by 7% (depth 8, folded), and with the row tile's windows charged
# as 32-bit (2 KiB a row where the uint8 tile and the [C, rows] block
# take 0.3) the whole estimate stands a quarter over the compiler's.
_STEP_ROW_BYTES = 8 * 1024
# Rows (K) of one MXU weight tile.
_MXU_ROWS = 128
# What one class dot costs the MXU, in weight tiles (results [TILE_R, 128])
# for every 128 class columns: HIGHEST makes an f32 product six bfloat16
# passes, each a whole tile whatever C is (1 class pads to 128 columns).
_CLASS_DOT_TILES = 6
# 1.5 * 2^23: an integer below 2^22 added to it is the low bits of the
# f32's mantissa, so that the bitcast is the conversion (the packed word
# has 16 bits).
_MANTISSA = 12582912.0
_MANTISSA_BITS = 0x4B400000


def _copy_stride(n_features: int) -> int:
    """K rows from one copy of the row tile to the next: the features, up
    to a multiple of the 8 sublanes the one-hot is built and joined by."""
    return -(-n_features // 8) * 8


def nodes_per_tile(n_features: int, optional_operands: int = 0) -> int:
    """P, the nodes that share one MXU weight tile: 2 where two copies of
    the features fit its 128 K rows (F <= 64) and the ensemble carries
    neither the missing nor the categorical table, else 1. Read from the
    input, decided by timings on the v5e (PERF.md section 6, PR 28): three
    nodes a tile lost to two, and with a routing table the kernel is
    bound by the VPU's integer routing, where reading a node's byte out
    of the packed word costs more than the MXU results saved (270.8 ms
    against 236.6 for 2M rows x 1000 trees with both tables)."""
    packs = (not optional_operands
             and 2 * _copy_stride(n_features) <= _MXU_ROWS)
    return 2 if packs else 1


def routes_in_tile(n_features: int, optional_operands: int = 0) -> int:
    """The routing tables whose routes ride the MXU weight tile (the
    FOLDED routed form, module docstring): all the ensemble carries where
    [2x | m | ones], two copies of the features and a block of 8, fits
    the tile's 128 K rows (F <= 56), else 0: the integer routing on the
    VPU serves, as before PR 32. 0 also without a routing table. Read
    from the input like `nodes_per_tile`; one rule whichever table is
    there (a lone table leaves some of those rows unused)."""
    fits = 2 * _copy_stride(n_features) + 8 <= _MXU_ROWS
    return optional_operands if fits else 0


def mxu_tiles_per_group(max_depth: int, n_features: int,
                        optional_operands: int = 0) -> int:
    """MXU weight tiles (results [TILE_R, 128]) a tree group costs a row
    tile: one a node, or the root's and one a pair of siblings."""
    n_int = (1 << max_depth) - 1
    if nodes_per_tile(n_features, optional_operands) == 1:
        return n_int
    return (n_int + 1) // 2


def _window_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of one pipelined 32-bit operand window: padded to whole
    (8, 128) tiles, and double-buffered."""
    return 2 * (-(-rows // 8) * 8) * (-(-cols // 128) * 128) * 4


def _vmem_bytes(groups: int, max_depth: int, n_features: int,
                n_classes: int, tile_r: int, optional_operands: int,
                row_bytes: int = _ROW_BYTES) -> int:
    """VMEM a grid step takes with `groups` tree groups a table block: the
    table windows (feat i32, thr f32, dl and cat i32 where present, bottom
    values, class one-hot: one plane a row), the row tile's windows and
    the working set, which the integer routing of both tables makes grow
    by the node (`_ROW_NODE_BYTES_BOTH`; not the folded form). The rows'
    two windows and the class one-hot's are charged as the 32-bit
    [TILE_R, F], [TILE_R, C] and [G*128, C] they were before PR 36, an
    upper bound of the uint8 tile, the [C, TILE_R] block and the
    [8k, G*128] one-hot: G decides every cell's program, and no cell's G
    may move for bytes nobody was short of. The class
    window is charged a group whether or not the block shares one class
    dot and one [128, C] window (`table_plan`): dropping the term would
    let Covertype's model hold 11 groups where it holds 9, which the
    evening-out turns into 3 blocks of 10, 30 groups of which two are
    empty: 7.1% more work for 3.8% fewer table walks (PR 34). Whether the
    freed windows should buy a larger G is open, with that trap."""
    n_int = (1 << max_depth) - 1
    tables = ((2 + optional_operands) * _window_bytes(groups * n_int,
                                                      TREE_GROUP)
              + _window_bytes(groups * (n_int + 1), TREE_GROUP)
              + _window_bytes(groups * TREE_GROUP, n_classes))
    per_node = optional_operands == 2 and not routes_in_tile(n_features, 2)
    work = tile_r * (row_bytes
                     + (n_int * _ROW_NODE_BYTES_BOTH if per_node else 0))
    rows = _window_bytes(tile_r, n_features) + _window_bytes(tile_r,
                                                             n_classes)
    return tables + work + rows


def _step_rows(groups: int, max_depth: int, n_features: int, n_classes: int,
               optional_operands: int) -> int:
    """The rows a grid step of `groups` tree groups takes: 256, doubled
    while the step still asks the MXU for no more than the 1000-tree
    cell's step asks (_STEP_MXU_TILES weight tiles, 8 groups x 32, x 256
    rows), _STEP_ROWS_MOST at most, then halved while the kernel's VMEM
    at that tile (`_vmem_bytes` at _STEP_ROW_BYTES a row) is past the
    budget; 256 always remains. What a longer step buys: the weight
    tiles' one-hots are built once a step whatever its rows, and a step's
    tail (the last selects of the mux tree, the value plane's split, the
    class dot, the out block: 372 bundles with 12 matmul pushes in the CTR
    model's step, nothing to run under) and its fixed cost (some 220
    cycles) are paid once: the one-group routed step of 63 tiles stood at
    85% of its MXU time at 256 rows where the 1000-tree step of 256 tiles
    stands at 94%. On the v5e (PERF.md section 6, PR 42; ms a 2M-row chunk
    at 256 / 512 / 1,024 rows a step): the CTR model's routed group
    26.43 / 25.02 / 24.34 (its kernel in the cell 1,247.1 -> 1,138.8 ms a
    call, the scores the same bits), 100 / 250 / 500 unrouted trees
    15.55 / 14.07 / 13.71, 26.44 / 25.09 / 24.76, 48.66 / 47.29 / 46.80,
    depth 8 in one group 48.19 / 46.86 / 46.46. Read from the plan's own
    G and tiles; no knob. (A step of 128 tiles reads another 1% at 1,024
    rows and the 1000-tree step of 256 tiles 1.7% at 512, where it once
    LOST 1.8%: left to a PR that claims those cells, ROADMAP A1.)"""
    tiles = groups * mxu_tiles_per_group(max_depth, n_features,
                                         optional_operands)
    rows = _DEFAULT_TILE_R
    while (rows < _STEP_ROWS_MOST
           and 2 * rows * tiles <= _DEFAULT_TILE_R * _STEP_MXU_TILES):
        rows *= 2
    while rows > _DEFAULT_TILE_R and _vmem_bytes(
            groups, max_depth, n_features, n_classes, rows,
            optional_operands, _STEP_ROW_BYTES) > _VMEM_BUDGET_BYTES:
        rows //= 2
    return rows


class TablePlan(typing.NamedTuple):
    """How an ensemble's node tables meet the kernel (`table_plan`)."""

    table_groups: int      # TREE_GROUPs that hold trees (n_tg)
    groups_per_step: int   # G: groups a table block; 0 = nothing fits
    blocks: int            # table blocks a row tile walks; 1 = resident
    table_bytes: int       # HBM bytes of all the blocks' tables, read once
                           # (a shared class one-hot: once an ensemble)
    tile_rows: int         # rows a grid step of a long program takes
    nodes_per_tile: int    # P: nodes that share one MXU weight tile
    mxu_tiles_per_group: int   # weight tiles a group costs a row tile
    routing_tables: int    # the missing and categorical tables it carries
    routes_in_tile: int    # ... of them, routed inside the MXU weight tile
    trees_per_group: int   # U: lanes of a group that hold trees (whole rounds)
    class_dots_per_step: int   # 1: the block's groups share one class dot
    row_operand_bytes: int     # a bin of the row block as HBM holds it: 1 or 4
    scores_class_major: int    # 1: the result leaves as [C, rows], lane-dense

    @property
    def tree_group(self) -> int:
        """Lane width of the kernel's tree planes; 0 in NO_PLAN."""
        return TREE_GROUP if self.table_groups else 0

    @property
    def rows_per_step(self) -> int:
        """`tile_rows`, as the spans name it."""
        return self.tile_rows

    def step_rows(self, rows: int) -> int:
        """The rows a grid step of a program of `rows` rows takes:
        `tile_rows`, halved while that is more than a
        _STEP_ROWS_SHARE-th of them, down to 256: what the last step
        holds past the program's rows (under a step) must cost less than
        the longer step saves, and a serving client's batch of a few
        thousand rows must not pay for rows it does not have."""
        step = self.tile_rows
        while step > _DEFAULT_TILE_R and step * _STEP_ROWS_SHARE > rows:
            step //= 2
        return step

    def span_counts(self) -> dict:
        """The plan as the `ddt:predict:ensemble` span carries it."""
        return {k: getattr(self, k) for k in SPAN_COUNTS}

    def root_counts(self) -> dict:
        """What of the plan every call's `ddt:predict` root span repeats
        (a node list's plan, ops/predict_paths.PathPlan, says more)."""
        return {"routing_tables": self.routing_tables}


# The one list of what the program says of a plan; what each name means is
# in docs/OBSERVABILITY.md. SPAN_COUNTS: the counts of the
# `ddt:predict:ensemble` span (backends/tpu.py), in the order they print.
# PHASES_COUNTS: those of them `cli predict` repeats in `phases_ms`;
# `table_bytes` is one walk of the blocks, and a call's whole re-read is
# the root span's `tables_streamed_bytes`, which `phases_ms` has instead.
SPAN_COUNTS = ("tree_group", "table_groups", "groups_per_step",
               "table_bytes", "nodes_per_tile", "mxu_tiles_per_group",
               "routing_tables", "routes_in_tile", "trees_per_group",
               "class_dots_per_step", "row_operand_bytes",
               "scores_class_major", "rows_per_step")
PHASES_COUNTS = tuple(k for k in SPAN_COUNTS if k != "table_bytes")
# This kernel does not serve the model (the one-hot path, the LUT tiers).
NO_PLAN = TablePlan(*(0,) * len(TablePlan._fields))


def row_operand_dtype(dtype) -> jnp.dtype:
    """What the kernel's row block is in HBM for rows of `dtype`: uint8
    and int32 bins go in as they come, the tile widened in VMEM; any
    other integer is cast to int32 in XLA first."""
    dtype = jnp.dtype(dtype)
    return dtype if dtype in (jnp.uint8, jnp.int32) else jnp.dtype(jnp.int32)


def table_plan(
    n_trees_padded: int,
    max_depth: int,
    n_features: int,
    n_classes: int,
    tile_r: int | None = None,
    optional_operands: int = 2,
    row_dtype=jnp.int32,
) -> TablePlan:
    """The kernel's table blocks at this shape: G, the number of tree
    groups a grid step holds in VMEM, is the most whose double-buffered
    windows fit _VMEM_BUDGET_BYTES beside the row tile's windows and the
    working set, evened out over the blocks that takes (28 groups of
    which 9 fit are 4 blocks of 7, not 3 of 9 and one of 1 padded to 9),
    and every group where the whole ensemble fits. G = 0: not even one
    group fits (depth, features, classes and the optional operands decide
    that; the tree count never does). `optional_operands` counts the
    missing and categorical tables the ensemble carries (both, where the
    caller cannot say).

    And how the block's groups meet the class dot (module docstring,
    Class scatter): groups of whole rounds, `trees_per_group` = U =
    C (128 // C), sharing ONE dot a grid step (`class_dots_per_step` 1),
    where the MXU results of a row tile come to fewer that way, blocks x
    (G x `mxu_tiles_per_group` + the one dot's 6) against the 128-tree
    groups' blocks x G x (tiles + 6), each layout under its own blocks;
    else groups of 128 trees and a dot each (`class_dots_per_step` G),
    as in a block of one group, whose program is what it was. U = 128
    wherever C divides 128 and costs nothing; Covertype's 3,520 padded
    trees are 28 groups of 126 as of 128, 4 blocks of 7; 10 classes at
    depth 8 would pay 12% more groups for 4% of dots and keep 128. Read
    from C, the tree count and G; no knob.

    And the rows a grid step takes, `tile_rows` (`_step_rows`): decided
    AFTER G, which is fitted at 256 rows whatever the step turns out to
    be, so that no shape's G, blocks or `predict_pallas_fits` moves: 256
    where the step holds 256 MXU weight tiles or more (the 1000-tree
    model's 8 x 32, Covertype's 7 x 128), 512 from 128 tiles on, 1,024
    for a step of 64 or fewer (the CTR model's one group of 63). A
    `tile_r` the caller names is taken as it is, G fitted at it.

    And the two arrays that cross between XLA and the kernel, which no
    term above depends on: `row_operand_bytes`, the width of a bin of the
    row block in HBM for rows of `row_dtype` (`row_operand_dtype`), and
    `scores_class_major`, 1: the result is [C, rows]."""
    planned = tile_r is None
    if planned:
        tile_r = _DEFAULT_TILE_R
    rounds = TREE_GROUP // n_classes * n_classes      # 0: C > 128
    # G is capped by the groups there are, in either layout.
    most, cap = 0, -(-n_trees_padded // (rounds or TREE_GROUP))
    while most < cap and _vmem_bytes(
            most + 1, max_depth, n_features, n_classes, tile_r,
            optional_operands) <= _VMEM_BUDGET_BYTES:
        most += 1
    tiles = mxu_tiles_per_group(max_depth, n_features, optional_operands)
    packing = (nodes_per_tile(n_features, optional_operands), tiles,
               optional_operands,
               routes_in_tile(n_features, optional_operands))

    def blocks_of(per_group):
        """(groups, G, blocks) of groups of `per_group` trees."""
        n_tg = -(-n_trees_padded // per_group)
        blocks = -(-n_tg // max(most, 1))
        return n_tg, -(-n_tg // blocks), blocks

    n_tg, g, blocks = blocks_of(TREE_GROUP)
    interface = (row_operand_dtype(row_dtype).itemsize, 1)
    if most == 0:
        return TablePlan(n_tg, 0, 0, 0, tile_r, *packing, TREE_GROUP, 0,
                         *interface)
    dot = _CLASS_DOT_TILES * -(-n_classes // TREE_GROUP)
    per_group, shared = TREE_GROUP, False
    if rounds:
        whole = blocks_of(rounds)
        shared = (whole[2] * (whole[1] * tiles + dot)
                  < blocks * g * (tiles + dot))
        if shared:
            per_group, (n_tg, g, blocks) = rounds, whole
    nodes_bytes = 4 * TREE_GROUP * ((2 + optional_operands)
                                    * ((1 << max_depth) - 1)
                                    + (1 << max_depth))
    # The class one-hot: the [128, C] window the whole ensemble shares,
    # fetched once, or one a group.
    class_bytes = 4 * TREE_GROUP * n_classes * (1 if shared else blocks * g)
    if planned:
        tile_r = _step_rows(g, max_depth, n_features, n_classes,
                            optional_operands)
    return TablePlan(n_tg, g, blocks, blocks * g * nodes_bytes + class_bytes,
                     tile_r, *packing, per_group, 1 if shared else g,
                     *interface)


def predict_pallas_fits(
    max_depth: int,
    n_features: int,
    n_classes: int,
    tile_r: int | None = None,
    optional_operands: int = 2,
) -> bool:
    """Whether the traversal kernel's VMEM working set fits at this shape —
    the guard behind use_pallas=None auto-dispatch
    (`kernel_serves`, this layout's rule): the row tile, the working set and
    ONE tree group's table windows. The tree count is no term of it: the
    tables stream by blocks of as many groups as fit (`table_plan`)."""
    return table_plan(TREE_GROUP, max_depth, n_features, n_classes, tile_r,
                      optional_operands).groups_per_step > 0


def _traverse_kernel(x_ref, feat_ref, thr_ref, val_ref, coh_ref, *rest,
                     n_groups: int, groups_per_dot: int, n_blocks: int,
                     n_int: int, n_leaves: int, n_feat: int, pack: int,
                     folded: bool, missing_bin_value: int,
                     use_missing: bool, use_cat: bool):
    """One row tile against one block of tree groups: that block's share
    of every class's margin, fully in VMEM. `pack`: the plan's
    nodes_per_tile; `folded`: its routes_in_tile is not 0;
    `groups_per_dot`: G where the block's groups share one class dot
    (lane l holds class l % C in every group), 1 where each has its own.

    x_ref [TILE_R, F] uint8 or int32, as HBM holds the rows (in the last
    tile, whatever lies past row R: each row decides its own column of
    the out block, and those past R are not written back); feat/thr (+
    optional dl, cat) [G*Nint, 128] and val [G*W, 128], one plane a row;
    coh [8k, 128] a dot, class c on sublane c; out [C, TILE_R] f32,
    class-major, resident over the block axis (grid axis 1): written by
    the first block, added to by the others. Folded, the three planes
    are the prologue's h, delta and c (`_folded_routes`), all f32."""
    rest = list(rest)
    out_ref = rest.pop()
    dl_ref = rest.pop(0) if use_missing else None
    cat_ref = rest.pop(0) if use_cat else None
    tile_r = x_ref.shape[0]
    tg = TREE_GROUP

    def plane(ref, row, rows=tile_r):
        """Row `row` of a table over `rows` sublanes: a row load at lane
        offset 0, then a sublane broadcast."""
        return jnp.broadcast_to(ref[row:row + 1, :], (rows, tg))

    stride = _copy_stride(n_feat)
    # The tile as HBM holds it, uint8 or int32, widened once a step: the
    # int32 copy lives in VMEM alone.
    x = x_ref[:].astype(jnp.int32)
    if folded:
        # [2x | m | ones]: the bins (doubled with the categorical table,
        # whose match is a test of |w| against 0), the NaN-bin indicator
        # with the missing table and a block of ones with the categorical
        # one, each from a multiple of 8 K rows like the copies below.
        xf = x.astype(jnp.float32)
        parts = [xf * 2.0 if use_cat else xf]
        if use_missing:
            parts.append(jnp.where(x == missing_bin_value, 1.0, 0.0))
        if stride > n_feat:
            gap = jnp.zeros((tile_r, stride - n_feat), jnp.float32)
            parts = [a for part in parts for a in (part, gap)]
        if use_cat:
            parts.append(jnp.ones((tile_r, 8), jnp.float32))
            first_row = jax.lax.broadcasted_iota(jnp.int32, (8, tg), 0) == 0
        f_iota = jax.lax.broadcasted_iota(jnp.int32, (stride, tg), 0)
        xb = jnp.concatenate(parts, axis=1).astype(jnp.bfloat16)  # [T, K]
    elif pack == 1:
        xb = x.astype(jnp.bfloat16)                       # [T, F]
        f_iota = jax.lax.broadcasted_iota(jnp.int32, (n_feat, tg), 0)
    else:
        # Two copies of the row tile side by side along K, each from a
        # multiple of 8 (the one-hot's sublane tiles), the second times
        # 256, and where the weight tile has 8 rows to spare a block of
        # ones for the row that adds _MANTISSA inside the MXU.
        ones_in_tile = 2 * stride + 8 <= _MXU_ROWS
        xf = x.astype(jnp.float32)
        copies = [xf, xf * 256.0]
        if stride > n_feat:
            gap = jnp.zeros((tile_r, stride - n_feat), jnp.float32)
            copies = [xf, gap, xf * 256.0, gap]
        f_iota = jax.lax.broadcasted_iota(jnp.int32, (stride, tg), 0)
        mantissa_rows = []
        if ones_in_tile:
            copies.append(jnp.ones((tile_r, 8), jnp.float32))
            mantissa_rows = [jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (8, tg), 0) == 0,
                _MANTISSA, 0.0)]
        xb = jnp.concatenate(copies, axis=1).astype(jnp.bfloat16)  # [T, K]

    def tile_plane(g, nodes):
        """What one MXU weight tile returns for `nodes` of group g's
        trees, [T, 128]: the bin value of the node's feature at every
        (row, tree) as f32 (one node a tile), or the two siblings' values
        as the bytes 0 and 1 of an int32 (the root: byte 0) above
        _MANTISSA_BITS; folded, the node's w = 2b + delta m + c."""
        if folded:
            # The node's one-hot over the bins' rows, delta times it over
            # the indicator's, c in the first of the ones' rows.
            row = g * n_int + nodes[0]
            hot = plane(feat_ref, row, stride) == f_iota
            foh = [jnp.where(hot, 1.0, 0.0)]
            if use_missing:
                foh.append(jnp.where(hot, plane(dl_ref, row, stride), 0.0))
            if use_cat:
                foh.append(jnp.where(first_row, plane(cat_ref, row, 8), 0.0))
            foh = jnp.concatenate(foh, axis=0).astype(jnp.bfloat16)
        elif pack == 1:
            # Feature one-hot, TRANSPOSED: the node's feature row over F
            # sublanes against the per-feature iota (the hist_pallas
            # _hist_kernel_t trick). feat = -1 (pushed-down leaves)
            # matches no sublane -> colval 0 < thr(+BIG) -> always-left.
            foh = (plane(feat_ref, g * n_int + nodes[0], n_feat)
                   == f_iota).astype(jnp.bfloat16)        # [F, 128]
        else:
            foh = [jnp.where(plane(feat_ref, g * n_int + n, stride)
                             == f_iota, 1.0, 0.0) for n in nodes]
            if len(nodes) == 1:                           # the root
                foh.append(jnp.zeros((stride, tg), jnp.float32))
            foh = jnp.concatenate(foh + mantissa_rows,
                                  axis=0).astype(jnp.bfloat16)  # [K, 128]
        # bf16 operands (bins <= 255, their multiples of 256, the 0/1
        # one-hot and _MANTISSA are exact), f32 accumulator: the MXU
        # accumulates in 32 bits only, and the v5e's VPU has no bf16
        # compare. Every partial sum is an integer below 2^24: exact in
        # any order.
        colval = jax.lax.dot_general(
            xb, foh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [T, 128]
        if pack == 1:
            return colval
        if not ones_in_tile:
            colval = colval + _MANTISSA
        return pltpu.bitcast(colval, jnp.int32)

    def goes_right(row, n, colval):
        """Predicate [T, 128]: the row leaves heap node n (table row
        `row`), in each of its group's trees, to the right. `colval`:
        the node's tile_plane."""
        thr = plane(thr_ref, row)
        if folded:
            return (jnp.abs(colval) if use_cat else colval) > thr
        if pack == 2:
            # thr is the prologue's (thr + 1) << 8 * byte, on the tile's
            # top byte plus _MANTISSA_BITS: the top byte compares as the
            # whole word, the low one behind a mask.
            return (colval & 255 if n % 2 else colval) >= thr
        if not (use_cat or use_missing):
            return colval > thr
        # With the optional operands the bits are routed as int32 0/1,
        # never as a bool ARRAY: Mosaic keeps i1 only as a select's
        # predicate — a select BETWEEN bool arrays needs an i8 -> i1
        # truncation it does not have.
        comp = (colval > thr).astype(jnp.int32)
        if use_cat:
            # One-vs-rest nodes (pre-gated on eff_feat >= 0 in the
            # prologue): the matched bin goes left.
            cat = plane(cat_ref, row) != 0
            comp = jnp.where(cat, (colval != thr).astype(jnp.int32), comp)
        if use_missing:
            # Reserved-NaN-bin rows follow the learned direction;
            # pushed-down leaves have colval 0, never the reserved bin.
            miss = colval == jnp.float32(missing_bin_value)
            comp = jnp.where(miss, 1 - plane(dl_ref, row), comp)
        return comp != 0

    def leaf(g, n, planes):
        """Value [T, 128] of the leaf the row reaches below heap node n
        of group g's trees: the mux tree, depth-first (depth + 1 value
        planes live, and with two nodes a tile one tile_plane a level:
        the siblings' is computed at their parent and stays while the
        first one's subtree is walked). `planes`: node -> its tile_plane,
        for the nodes on the path whose tile is computed. Folded, a
        node's tile and predicate come AFTER its two subtrees: no
        predicate is held while they are walked (scoped VMEM 1.81 ->
        1.47 MiB at depth 6, 3.09 -> 2.12 at depth 10, and 0.3% of the
        step; compile check and my chip run, PR 32)."""
        if n >= n_int:
            return plane(val_ref, g * n_leaves + n - n_int)
        if folded:
            left, right = leaf(g, 2 * n + 1, {}), leaf(g, 2 * n + 2, {})
            return jnp.where(goes_right(g * n_int + n, n,
                                        tile_plane(g, (n,))), right, left)
        tiles = [(n,)] if pack == 1 or n == 0 else []
        if pack == 2 and 2 * n + 2 < n_int:
            tiles.append((2 * n + 1, 2 * n + 2))
        for nodes in tiles:
            planes = planes | dict.fromkeys(nodes, tile_plane(g, nodes))
        return jnp.where(goes_right(g * n_int + n, n, planes[n]),
                         leaf(g, 2 * n + 2, planes),
                         leaf(g, 2 * n + 1, planes))

    acc = None
    for d in range(n_groups // groups_per_dot):
        # The values the rows reach in the dot's groups, summed lane by
        # lane: one plane live across the groups, 32 vreg adds each.
        first = d * groups_per_dot
        vals = leaf(first, 0, {})
        for g in range(first + 1, first + groups_per_dot):
            vals = vals + leaf(g, 0, {})
        # Class scatter — the one-hot path's dot and precision, turned
        # over: both contract their lanes (the trees), so the classes
        # land on the sublanes and the tile's rows on the lanes.
        part = jax.lax.dot_general(
            coh_ref[:, d * tg:(d + 1) * tg], vals,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )                                                 # [8k, T]
        acc = part if acc is None else acc + part
    acc = acc[:out_ref.shape[0]]
    if n_blocks == 1:
        out_ref[:] = acc
        return
    block = pl.program_id(1)

    @pl.when(block == 0)
    def _():
        out_ref[:] = acc

    @pl.when(block > 0)
    def _():
        out_ref[:] += acc


def _folded_routes(eff_feat, eff_thr, eff_dl, eff_cat, missing_bin_value):
    """The folded routed form's per-node constants, [Tpad, Nint] f32 each:
    (h, delta or None, c or None), so that with b the bin of the node's
    feature and m = [b == missing_bin_value]

        goes_right = |2b + delta m + c| > h      with the categorical table
        goes_right =    b + delta m     > h      with the missing one alone

    is `_descend_comp`'s routing bit for every bin 0..255 (module
    docstring). Each constant is an integer that bfloat16 holds exactly:
    at most 256 in magnitude, or even and at most 512."""
    nan_bin = min(max(missing_bin_value, 0), 255)
    thr = jnp.clip(eff_thr, -1, 255).astype(jnp.int32)    # b > thr as it was
    if eff_dl is not None:
        eff_dl = eff_dl.astype(bool)
    if eff_cat is None:
        # The NaN bin lands under every threshold (-1) or over every one.
        delta = jnp.where(eff_dl, -(nan_bin + 1), 256)
        return thr.astype(jnp.float32), delta.astype(jnp.float32), None
    # One-vs-rest nodes, pre-gated on eff_feat >= 0 so pushed-down leaves
    # (colval 0, thr +BIG) stay always-left, exactly like _descend_comp:
    # the matched bin `cat` goes left, -1 where no bin matches. An ordinal
    # node that sends every bin right (thr < 0) is such a node too.
    is_cat = eff_cat.astype(bool) & (eff_feat >= 0)
    cat = jnp.where(is_cat & (eff_thr >= 0) & (eff_thr <= 255),
                    eff_thr, -1).astype(jnp.int32)
    is_cat |= thr < 0
    c = jnp.where(is_cat, -2 * cat, -thr)
    h = jnp.where(is_cat, 0, thr)
    delta = None
    if eff_dl is not None:
        # Left: w = 0 (a match) or -thr. Right: past every |w| a bin gives.
        delta = jnp.where(eff_dl, jnp.where(is_cat, 2 * (cat - nan_bin),
                                            -2 * nan_bin), 512)
        delta = delta.astype(jnp.float32)
    return h.astype(jnp.float32), delta, c.astype(jnp.float32)


def predict_effective_pallas(
    eff_feat: jax.Array,       # [Tpad, N] pushed-down features (int32)
    eff_thr: jax.Array,        # [Tpad, N] pushed-down thresholds
    bot_val: jax.Array,        # f32 [Tpad, 2^D] bottom-level values
    cls_oh: jax.Array,         # f32 [Tpad, C] class one-hot
    Xc: jax.Array,             # [R, F] integer bins
    *,
    max_depth: int,
    learning_rate,
    base,
    n_classes: int = 1,
    tree_chunk: int = 64,
    missing_bin_value: int = -1,
    eff_dl: jax.Array | None = None,
    eff_cat: jax.Array | None = None,
    tile_r: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas twin of ops/predict._predict_effective (binned data only).

    interpret=None auto-selects Pallas interpreter mode off-TPU (the CPU
    test suite exercises the identical kernel logic) — the same pattern
    as hist_pallas.build_histograms_pallas. Jit-safe: callable inside
    predict_raw / predict_raw_effective traces or standalone."""
    if interpret is None:
        interpret = device.platform() != "tpu"
    if not jnp.issubdtype(Xc.dtype, jnp.integer):
        raise ValueError(
            "the Pallas traversal kernel requires binned integer data; "
            "float (raw-threshold) scoring uses the one-hot path")
    R, F = Xc.shape
    C = n_classes
    if R == 0:
        out = jnp.full((0, C), base, jnp.float32)
        return out[:, 0] if C == 1 else out
    Tpad, N = eff_feat.shape
    if Tpad % tree_chunk != 0:
        raise ValueError(
            f"padded tree count {Tpad} is not a multiple of "
            f"tree_chunk={tree_chunk}")
    use_missing = eff_dl is not None
    use_cat = eff_cat is not None
    tg = TREE_GROUP
    plan = table_plan(Tpad, max_depth, F, C, tile_r, use_missing + use_cat,
                      Xc.dtype)
    if not interpret and not _plan_fits(plan):
        # Compiled dispatch past the budget means a VMEM OOM or a
        # pathological Mosaic trace on the chip — fail at the cause, read
        # off the plan the grid is built from. The auto path
        # (`kernel_serves`, asked once a model) never gets here; this
        # guards a forced predict_impl='pallas' at a monster shape.
        # Interpret mode (CPU tests) has no VMEM to protect.
        raise ValueError(
            f"predict shape (depth={max_depth}, F={F}, C={C}, "
            f"{use_missing + use_cat} optional operands) exceeds the "
            "Pallas VMEM budget; use the one-hot path")
    if tile_r is None:
        tile_r = plan.step_rows(R)
    # Interpreted past the budget: one block of every group.
    n_g = plan.groups_per_step or plan.table_groups
    n_blocks = plan.blocks or 1
    n_int = (1 << max_depth) - 1
    n_leaves = 1 << max_depth
    u = plan.trees_per_group
    groups_per_dot = n_g // (plan.class_dots_per_step or n_g)
    t_fill = n_blocks * n_g * u - Tpad

    def by_plane(a, dtype, fill=0):
        """[Tpad, width] -> [n_blocks, G*width, 128]: row g*width + n of
        block b holds column n of the U trees of the block's group g, in
        lanes 0..U-1. The trees that fill a group's last lanes, the last
        group and the last block's groups score 0 (feature -1 matches no
        one-hot row, value 0). Tiny arrays; the transpose is noise next
        to the row volume."""
        a = jnp.pad(a.astype(dtype), ((0, t_fill), (0, 0)),
                    constant_values=fill)
        width = a.shape[1]
        a = a.reshape(n_blocks, n_g, u, width)
        if u < tg:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, tg - u), (0, 0)),
                        constant_values=fill)
        return a.transpose(0, 1, 3, 2).reshape(n_blocks, n_g * width, tg)

    with traced_scope("predict:tables"):
        feat_pl = by_plane(eff_feat[:, :n_int], jnp.int32, fill=-1)
        folded = plan.routes_in_tile > 0
        extras = []
        if folded:
            h, *routes = _folded_routes(
                eff_feat[:, :n_int], eff_thr[:, :n_int],
                eff_dl[:, :n_int] if use_missing else None,
                eff_cat[:, :n_int] if use_cat else None, missing_bin_value)
            thr_pl = by_plane(h, jnp.float32)
            extras = [by_plane(a, jnp.float32) for a in routes
                      if a is not None]
        elif plan.nodes_per_tile == 1:
            thr_pl = by_plane(eff_thr[:, :n_int], jnp.float32)
        else:
            # The compare is made on the packed word, in integers: thr +
            # 1 (>= for >) shifted to the node's byte, and the tile's top
            # byte (even nodes, the root) carries the word's constant high
            # bits. A pushed-down leaf's +BIG becomes 255, which no byte
            # exceeds.
            node = jnp.arange(n_int)
            top = node % 2 == 0
            thr_i = jnp.clip(eff_thr[:, :n_int], -1, 255).astype(jnp.int32)
            thr_pl = by_plane(
                ((thr_i + 1) << jnp.where(top & (node > 0), 8, 0))
                + jnp.where(top, _MANTISSA_BITS, 0), jnp.int32)
        val_pl = by_plane(bot_val, jnp.float32)
        if groups_per_dot > 1:
            # Lane l is class l % C in every group of whole rounds.
            coh = jnp.pad(cls_oh[:u].astype(jnp.float32),
                          ((0, tg - u), (0, 0)))
        else:
            coh = jnp.pad(cls_oh.astype(jnp.float32),
                          ((0, t_fill), (0, 0))).reshape(n_blocks, n_g * tg, C)
        # ... and goes in turned over: the classes on the sublanes, padded
        # to whole ones, a group's trees on the lanes.
        coh = jnp.swapaxes(coh, -1, -2)
        coh = jax.lax.pad(coh, 0.0, [(0, 0, 0)] * (coh.ndim - 2)
                          + [(0, -C % 8, 0), (0, 0, 0)])
        if use_missing and not folded:
            extras.append(by_plane(eff_dl[:, :n_int], jnp.int32))
        if use_cat and not folded:
            # Pre-gate on eff_feat >= 0 so pushed-down leaves (colval 0,
            # thr +BIG) stay always-left, exactly like _descend_comp.
            cat_eff = eff_cat[:, :n_int].astype(bool) & (eff_feat[:, :n_int]
                                                         >= 0)
            extras.append(by_plane(cat_eff, jnp.int32))

    n_tiles = -(-R // tile_r)
    row_dtype = row_operand_dtype(Xc.dtype)
    if Xc.dtype != row_dtype:
        with traced_scope("predict:widen"):
            Xc = Xc.astype(row_dtype)

    kernel = functools.partial(
        _traverse_kernel, n_groups=n_g, groups_per_dot=groups_per_dot,
        n_blocks=n_blocks, n_int=n_int, n_leaves=n_leaves, n_feat=F,
        pack=plan.nodes_per_tile, folded=folded,
        missing_bin_value=missing_bin_value, use_missing=use_missing,
        use_cat=use_cat,
    )

    # The rows' two blocks, resident over the block axis: fetched (written
    # back) once a tile. The grid walks the UNPADDED rows, so the last
    # tile's blocks are ragged: rows are independent, and what the row
    # block holds past row R decides nothing that is written.
    rows_in = pl.BlockSpec((tile_r, F), lambda i, b: (i, 0),
                           memory_space=pltpu.VMEM)
    scores_out = pl.BlockSpec((C, tile_r), lambda i, b: (0, i),
                              memory_space=pltpu.VMEM)

    def table_block(rows, cols):
        """Block b of a table. One block: the index never moves, and the
        table is fetched once and stays, as a pinned window would."""
        return pl.BlockSpec((None, rows, cols), lambda i, b: (b, 0, 0),
                            memory_space=pltpu.VMEM)

    nodes = table_block(n_g * n_int, tg)
    in_specs = [
        rows_in,
        nodes,                                            # feat
        nodes,                                            # thr
        table_block(n_g * n_leaves, tg),                  # val
        # coh: a group's, or the one every group of every block shares
        # (the index never moves: fetched once).
        table_block(*coh.shape[1:]) if groups_per_dot == 1 else pl.BlockSpec(
            coh.shape, lambda i, b: (0, 0), memory_space=pltpu.VMEM),
    ] + [nodes] * len(extras)
    cost = pl.CostEstimate(
        flops=2 * n_tiles * tile_r * n_blocks * tg * (
            n_g * F * n_int + n_g // groups_per_dot * C),
        bytes_accessed=n_tiles * tile_r * (F * plan.row_operand_bytes + C * 4)
        + plan.table_bytes * (n_tiles if n_blocks > 1 else 1),
        transcendentals=0,
    )
    with traced_scope("predict:traverse"):
        acc = pl.pallas_call(
            kernel,
            grid=(n_tiles, n_blocks),
            in_specs=in_specs,
            out_specs=scores_out,
            out_shape=jax.ShapeDtypeStruct((C, R), jnp.float32),
            cost_estimate=cost,
            interpret=interpret,
        )(Xc, feat_pl, thr_pl, val_pl, coh, *extras)
    with traced_scope("predict:accumulate"):
        out = base + learning_rate * acc
        return out[0] if C == 1 else out.T


@costed("predict_pallas", phase="predict")
@functools.partial(
    jax.jit,
    static_argnames=("max_depth", "n_classes", "tree_chunk",
                     "missing_bin_value", "tile_r", "interpret"),
)
@op_scope("predict")
def predict_raw_pallas(
    feature: jax.Array,        # int32 [T, N]
    thr: jax.Array,            # [T, N] int32 bins
    is_leaf: jax.Array,        # bool [T, N]
    leaf_value: jax.Array,     # float32 [T, N]
    Xc: jax.Array,             # [R, F] integer bins
    max_depth: int,
    learning_rate: float,
    base: float,
    n_classes: int = 1,
    tree_chunk: int = 64,
    default_left: jax.Array | None = None,
    missing_bin_value: int = -1,
    cat_node: jax.Array | None = None,
    tile_r: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Standalone raw-arrays entry (tests): pushdown in-trace, then
    the Pallas core — the predict_raw contract with use_pallas forced."""
    from ddt_tpu.ops import predict as predict_ops

    T = feature.shape[0]
    C = n_classes
    n_tc = -(-T // tree_chunk)
    tpad = n_tc * tree_chunk - T

    def pad_t(a, fill=0):
        return jnp.pad(a, ((0, tpad), (0, 0)), constant_values=fill)

    ef, et, ev, _ = predict_ops._effective_arrays(
        pad_t(feature, -1), pad_t(thr), pad_t(is_leaf, True),
        pad_t(leaf_value), max_depth,
    )
    lo = (1 << max_depth) - 1
    cls = jnp.arange(n_tc * tree_chunk, dtype=jnp.int32) % C
    cls_oh = jax.nn.one_hot(cls, C, dtype=jnp.float32)
    return predict_effective_pallas(
        ef, et, ev[:, lo:], cls_oh, Xc,
        max_depth=max_depth, learning_rate=learning_rate, base=base,
        n_classes=C, tree_chunk=tree_chunk,
        missing_bin_value=missing_bin_value,
        eff_dl=pad_t(default_left) if default_left is not None else None,
        eff_cat=pad_t(cat_node) if cat_node is not None else None,
        tile_r=tile_r, interpret=interpret,
    )


# ---- the HEAP layout's entry (ops/predict.LAYOUTS) ----

def _plan_fits(plan: TablePlan) -> bool:
    """`predict_pallas_fits`, read off the plan a dispatcher has made for
    its grid: a block of it holds a tree group at all."""
    return plan.groups_per_step > 0


def kernel_serves(use_pallas, binned: bool, max_depth: int, n_features: int,
                  n_classes: int, optional_operands: int = 2) -> bool:
    """The heap layout's kernel-or-twin rule: ops/predict.resolve_use_pallas
    over this kernel's own budget predicate. `optional_operands` counts the
    missing and categorical tables the ensemble carries (both, where the
    caller cannot say). The tree count is no term of it."""
    from ddt_tpu.ops.predict import resolve_use_pallas

    return resolve_use_pallas(use_pallas, binned, lambda: predict_pallas_fits(
        max_depth, n_features, n_classes,
        optional_operands=optional_operands))


def _lut_program(ce, n_features: int, tier: str):
    """The quantized program at `tier` ("lut" = int8, "lut4" = int4
    bit-packed; ops/predict_lut.py), or None when the shape exceeds that
    kernel's budget (predict_lut_fits / predict_lut4_fits: the caller walks
    the ladder down). Tables quantize on the host once per model version
    (`ce.quantize()` memoizes); the error bound rides on the tables
    (docs/SERVING.md "Quantized serving")."""
    from ddt_tpu.ops import predict_lut
    from ddt_tpu.ops.predict import ScoringProgram

    if tier == "lut4":
        tables = ce.quantize(leaf_dtype="int4")
        packed = tables.pack_int4()
        if not predict_lut.predict_lut4_fits(
                tables.n_trees_padded, tables.tree_chunk,
                tables.max_depth, n_features, tables.n_classes_out,
                thr_packed=packed.thr_packed):
            return None
        host_ops = packed.ops
        static = packed.static_kwargs()
        core = predict_lut.predict_effective_lut4_ops
    else:
        tables = ce.quantize()
        if not predict_lut.predict_lut_fits(
                tables.n_trees_padded, tables.tree_chunk,
                tables.max_depth, n_features, tables.n_classes_out):
            return None
        host_ops = predict_lut.lut_device_operands(tables)
        static = dict(
            max_depth=tables.max_depth,
            learning_rate=tables.learning_rate,
            base=tables.base_score, n_classes=tables.n_classes_out,
            tree_chunk=tables.tree_chunk,
            n_trees_padded=tables.n_trees_padded,
            missing_bin_value=tables.missing_bin_value,
            use_missing=tables.eff_dl is not None,
            use_cat=tables.eff_cat is not None,
            use_scale=tables.leaf_scale is not None,
        )
        core = predict_lut.predict_effective_lut_ops

    def lut0(*args):
        *ops, Xc = args
        return core(tuple(ops), Xc, **static)

    return ScoringProgram(NO_PLAN, tuple(host_ops), jax.jit(lut0), None,
                          ce.n_classes_out, ce.n_classes_out, tier)


def scoring_program(ce, n_features: int, row_dtype, predict_impl: str,
                    link: bool):
    """The heap layout's entry (ops/predict.layout_entry): the program of a
    models/tree.CompiledEnsemble. predict_impl "lut": the int8 quantized
    tier where its VMEM budget takes the shape, else the f32 program;
    "lut4": the bit-packed int4 tier one rung up, degrading int4 -> int8 ->
    f32 down the same guards; the rung that serves is the record's `tier`.
    The f32 program's plan is `table_plan`'s where the traversal kernel
    serves (`kernel_serves`, asked here and bound as a bool), NO_PLAN where
    the one-hot form does. No heap model's link is taken on the device."""
    from ddt_tpu.ops import predict as predict_ops

    for tier in {"lut4": ("lut4", "lut"), "lut": ("lut",)}.get(
            predict_impl, ()):
        lut = _lut_program(ce, n_features, tier)
        if lut is not None:
            return lut
    use_missing = ce.eff_dl is not None
    use_cat = ce.eff_cat is not None
    classes, routes = ce.n_classes_out, use_missing + use_cat
    served = kernel_serves(predict_ops.USE_PALLAS[predict_impl], True,
                           ce.max_depth, n_features, classes, routes)
    plan = table_plan(ce.n_trees_padded, ce.max_depth, n_features, classes,
                      None, routes, row_dtype) if served else NO_PLAN
    static = dict(
        max_depth=ce.max_depth, learning_rate=ce.learning_rate,
        base=ce.base_score, n_classes=classes, tree_chunk=ce.tree_chunk,
        missing_bin_value=ce.missing_bin_value, use_pallas=served)

    def fn0(ef, et, bv, coh, *rest,
            entry=predict_ops.predict_raw_effective):
        *opt, Xc = rest
        opt = list(opt)
        dl = opt.pop(0) if use_missing else None
        cn = opt.pop(0) if use_cat else None
        return entry(ef, et, bv, coh, Xc, eff_dl=dl, eff_cat=cn, **static)

    return predict_ops.ScoringProgram(
        plan, ce.arrays(), fn0, predict_ops.predict_raw_effective, classes,
        classes)
