"""Pallas TPU kernel for the PATH-MATRIX form of batch scoring: node-list
ensembles (models/tree.NodeListEnsemble), binned data.

The heap kernel (ops/predict_pallas.py) resolves a tree by a multiplexer
over its full heap: 2^depth - 1 node planes traced, whatever the tree
holds. A leaf-wise tree of 255 leaves 13-20 levels deep has 254 nodes and
would ask for a heap of 2^21. This kernel resolves a tree by its PATH
MATRIX instead (ops/predict.py has the equations; Hummingbird's GEMM
strategy, OSDI 2020, re-read for the MXU): per tree and tile of rows

    v = x @ sel          [rows, W]   the bin of every node's feature
    s = v > thr ? +1 : -1            bf16, exact
    m = s @ P            [rows, W]   == len[l] for the one leaf reached
    acc += where(m == len, val, 0)   float32 leaf values, never bfloat16

so its cost is by the tree's NODES and LEAVES (W lanes of each, a multiple
of 128), not by its depth: `path_mxu_tiles_per_tree` MXU weight tiles a
tree, ceil(F/128) x ceil(W / P / 128) for v (P nodes a result lane, next
paragraph) and (W/128)^2 for m: 5 at 255 leaves and 28 columns (6 at P =
1), 18 at 512 lanes, 2 at 128, 20 at 968 columns; in the sub-tree form (THE
CHAIN, below) W/128 x E/128 more a sub-tree for the exits' table of E lanes
(`exit_mxu_tiles`), and the select's tiles by the SPANS of its lane tiles
(next but one paragraph): 13 a sub-tree of 256 lanes at 784 columns and 10
classes (3 + 4 of the select, 4 of the resolve, 2 of the exits' ONE lane
tile), 275 a tree of the MNIST forest's 21.12 sub-trees; 15 and 317 where
the class pieces and the chain's links have a lane tile each (4 + 2 + 2:
until PR 49 every model, since then those the one tile does not fit); with
every lane tile reading every K-block (dense spans) 7 x 2 of the select, 22
a sub-tree and 443 a tree of 20.15 before PR 48; and W/128 of the resolve
where (W/128)^2, the diagonal tiles alone, where the sub-trees are HALVED
(`resolve_mxu_tiles`; TWO HALVES, below): 5 a sub-tree of the XGBoost
Covertype model (1 packed select, 2 resolve, 2 exits; 7 before PR 51), 33 a
tree of its 6.55.

TWO NODES A RESULT LANE (`select_nodes_per_lane`, P: from F and W, nothing
else; the heap kernel's `nodes_per_tile` in this form). The select contracts
K = F of the weight tile's 128 rows and the kernel is bound by the NUMBER of
[rows, 128] results it asks the MXU for, so where two copies of the features
fit the tile (F <= 64) and the tree has 256 node lanes or more, the idle K
rows carry a second node: the sub-tile's left operand is [x | 256 x | ones]
(built once a sub-tile for the block's trees), the table `pack_select`'s
[K2, Wp] (Wp = W/2 up to whole 128 lanes; lane n the one-hot of node n over
the first copy's rows and of node Wp + n over the second's; 80 x 128 at 28
columns where [32, 256] was), and one matmul returns

    word[row, n] = M + bin(node n) + 256 bin(node Wp + n)

M = 1.5 x 2^23 (`_MANTISSA`: a K row of the table against the ones, or one
VPU add where the tile has no 8 rows to spare, F = 57..64) puts the integer
in the low bits of the f32's mantissa, so the bitcast reads the low byte
(`& 255`, compared as int32 against thr) without a convert, and the high
byte compares as the whole f32 word against a threshold `pack_select` has
moved to it: b > thr is word > M + 256 (thr + 1) - 1, and the NaN route's
b < up is word < M + 256 up. Both nodes of a lane on one feature is no
special case, each copy having its own K rows (257 is no bfloat16: hence
two copies). s comes out [rows, W] as before, the low bytes' lanes then the
high bytes'; `s @ P`, the accumulate and the fold are the unpacked form's.
The tables are packed on the host once a model (backends/tpu.py); F > 64 or
128 lanes is the unpacked program, instruction for instruction.

The select is K-BLOCKED: v = sum_k x_k @ sel_k over ceil(F/128) blocks of
128 columns (`select_k_blocks`: 1 at 28 columns, 8 at 968), each x_k cut
from the row tile as HBM holds it and widened uint8 -> bf16 in VMEM, once a
sub-tile, the last block's K padded to the tables' Fp (a multiple of 16).
One non-zero a column of sel and bins below 256: every partial product is
exact and so is their sum in any order.

... and K-BLOCK SPARSE in the sub-tree form (`Chain.select_spans`,
`select_mxu_tiles`) and, since PR 56, for an uncut tree with CATEGORY SETS
(`CatSets.spans`, below). A node's one K row lies in ONE K-block, so of the
ceil(F/128) weight tiles a 128-lane tile of the select asks, all but the
blocks its own nodes read are zeros; and which block a lane reads is decided
by the ORDER of the lanes alone, which is free. So the host numbers a
sub-tree's nodes by the K-block of their column (models/tree.
CompiledNodeList: lane tile j holds only nodes whose block lies in span j, a
contiguous range of K-blocks, the same for every entry; the cut holds a
bound a tile so that every part can be so numbered), and the kernel sums
lane tile j's `v` over span j's blocks alone: x_k @ sel[k, tile j] for k in
span j, [S, 128] results, unrolled and static. The skipped tiles are exactly
zero, so v is what it was. Spans that are all the same (dense: one K-block,
one lane tile, or a model the split buys nothing) are ONE matmul a K-block
over all the lanes: the program above, instruction for instruction. `sel`
stays [S, Fp, W] in HBM and its windows whole in VMEM (the DMA streams the
zero tiles, the MXU does not ask for them).

THE CHAIN: the SUB-TREE form (`class_lanes` > 0; ops/predict.py has the
equations, models/tree.CompiledNodeList the tables). A tree of more lanes
than one path matrix should hold (past 512: the resolve is quadratic in W),
or one whose leaves are VECTORS (an averaged forest: a class distribution a
leaf, the mean over the trees), is cut on the host into sub-trees of at most
256 lanes (models/tree.cut_subtrees), and a table entry is a SUB-TREE: the
grid's block axis walks blocks of G sub-trees, a tree's in a row, parents
first. A sub-tree's v, s and m are the tree's above; its "leaves" are its
EXITS, and what an exit means is a fourth table, `leaves` [W, E] bf16,
against which the exit one-hot e = (m == len) is multiplied ONCE, y = e @
leaves [rows, E]. The kernel never sees whether an entry is CONNECTED, and
since PR 53 it need not be: an entry holds one or SEVERAL connected pieces
of its tree, glued into one binary tree by copies of their lowest common
ancestors (a copy asks its node's question in a lane of its own and hangs no
exit, as a halved entry's spine copies do). Every row that reaches one of
the pieces passed those ancestors and answered them as the copies do, so of
an active entry's exits exactly one fires, the one the node walk takes; an
entry's pieces hang on EARLIER entries of the tree, possibly several, whose
links write the same activity lane (a row follows one chain: `a` stays 0 or
1). The cut FILLS its entries so (`pieces_per_subtree`,
`glue_copies_per_subtree` on the span): the entries' count is the other
factor of this kernel's time beside the tiles an entry.

A row of the table: a real leaf's float32 vector as THREE bfloat16 pieces in
lanes of their own (`class_dot_passes` 3; piece p's column c in lane p C +
c: exact, and one MXU tile serves 3 C <= 128), zeros for a link; a link from
the tree's sub-tree k to its sub-tree j a 1 in lane link0 + (j - k - 1),
zeros for a leaf. The activity `act` holds in lane h the sub-tree at hand
and in lane h + i the one i further on in the tree, every step shifts its
lanes down by one (XLU), and an entry that roots a tree (planes' row 4) sets
`act` to lane h alone. A row follows ONE chain of sub-trees, every other
sub-tree of the tree has a = 0, and the one real leaf it ends in adds its
pieces.

ONE LANE TILE OF EXITS (`Chain.shared`, `exit_mxu_tiles` W/128; PR 49), E =
128: where the 3 C lanes of pieces and the tree's chain fit a tile together,

    3 C + (most sub-trees a tree) - 1 <= 128

(models/tree.exit_table_lanes, THE RULE, stated there and nowhere else: the
builder lays the table out by it and `chain_of` reads the layout back from
the table's width), the links lie right behind the pieces, link0 = h = 3 C,
and the class sums and the activity are two [rows, 128] arrays that take the
SAME product:

    a   = act[:, 3C]                 this sub-tree's activity, 0 or 1
    ay  = a * y                      [rows, 128], once
    acc += ay                        lanes 0 .. 3C-1: the class dot
    act  = roll(act, -1) + ay        lanes 3C ..: the chain

No mask and no second operand: `acc`'s lanes from 3 C up hold sums of 0s and
1s that `fold_leaf_pieces` never reads, and `act`'s lanes below 3 C hold
class values that the shift moves DOWN, away from lane 3 C, round lane 0 to
lane 127 and down again a lane a sub-tree: back at lane 3 C, the only lane
the kernel reads, 129 - 3 C sub-trees after they were written at the
soonest, which is past the tree's last sub-tree exactly where its last link
(lane 3 C + n - 2) fits the tile, and the next tree's root clears every
lane. The MNIST forest: 30 lanes of pieces, 24 of links at most, two weight
tiles a sub-tree where four.

[V | L] (every model until PR 49; since then 3 C + n - 1 > 128: 85 classes,
128 classes, a 10-class tree of 100 sub-trees), E = CL + A: the pieces in CL
class lanes (whole 128s), the links in A activity lanes behind them (whole
128s, more than a tree's sub-trees, so no lane wraps into use), link0 = CL,
h = 0:

    a = act[:, 0]
    acc += a * y[:, :CL]             the class dot: CL class lanes
    act  = roll(act, -1) + a * y[:, CL:]     the chain: A activity lanes

that program and its tables are what they were, instruction for instruction.

TWO HALVES THAT SHARE THEIR SPINE (`Chain.halved`, `resolve_mxu_tiles` W/128;
PR 51). P[n, l] is non-zero only where node n lies on exit l's path, 12 of
256 rows a column in a depth-16 model, and which lane a node takes is the
host's to say. So the host numbers a sub-tree as two halves of 128 lanes
(models/tree.cut_subtrees, `halved`): the first k nodes of its pre-order in
lanes 0.., with the exits that hang on them in exit lanes 0..; from lane 128
COPIES of node k's ancestors (every later node's ancestors among the first k
are among them: the spine; a copy asks its node's question, the same K row
and threshold, and hangs no exit) and then the later nodes, with their exits
from exit lane 128. Every exit's whole path lies in the exit's own half, both
off-diagonal [128, 128] blocks of P are zeros, the table holds the two
diagonal ones side by side ([S, 128, 256] bf16, 64 KB an entry where 128:
172,032 B an entry of the XGBoost model where 237,568) and the resolve is

    m = [ s[:, :128] @ P[:, :128]  |  s[:, 128:] @ P[:, 128:] ]

two [rows, 128] x [128, 128] products where one [rows, 256] x [256, 256]:
the same integers, the same exit for every (row, sub-tree). Under the packed
select the halves are the low bytes' lanes and the high bytes', as they come.
The price is the copies' lanes and a cut that holds one bound more (an
entry's slots and those on its longest path at most 255 together: 7% more
sub-trees for the XGBoost model than its nodes alone would ask, 4.5 copies a
sub-tree since PR 53 fills the entries, 1.9 before); which models take it
is `models/tree.choose_select_spans`'s to say, by the fewest weight tiles a
tree: those whose select is one K-block (F <= 128), and none whose lanes are
ordered by K-blocks (the MNIST forest's select would be asked whole in both
halves: 18 tiles where 13). The kernel reads the layout back from the path
table's shape; a model that is not halved traces the program it did.

Either way `act` lives in a VMEM scratch [TILE_ROWS, A] over the block axis
(a tree's sub-trees may lie in several blocks; the table's first entry roots
a tree, so the scratch is never read before it is set), `acc` in the output
block [TILE_ROWS, CL], the rows on the sublanes; the three pieces are added
and divided by the tree count in XLA (`fold_leaf_pieces`: `predict:
accumulate`). Nothing of it is traced for a model whose trees one path
matrix holds with one output column: that program is instruction for
instruction what it was.

CATEGORY SETS (`CatSets`; LightGBM's categorical splits, models/tree.
CompiledNodeList has the tables and ops/predict.py the equations): a set
node goes LEFT iff the row's bin is in its set. The test is a column of the
select like any. Once a sub-tile, for the block's trees, the kernel makes
the ONE-HOT of every category column's bin, B K-blocks of 128 rows
(`cat_expand` says which column a K row reads: xe = x @ cat_expand[b] is
that column's bin in lane j, one small matmul a K-block and sub-tile, shared
by the block's G trees; `cat_bins` the bin the row stands for: hot = (xe ==
bin), one compare), and a tree's v is summed over the ordinal K-blocks (none
where every node asks a set: `sel` then has no such rows) and the B one-hot
blocks, against a `sel` whose set nodes' columns are MULTI-hot over their
sets' K rows: v = 1 in the set, 0 out of it, threshold 0, the node's row of P
negated. Compare, resolve, accumulate and fold are untouched. One node a
result lane (`select_nodes_per_lane` 1 whatever F).

The select of such a tree is K-BLOCK SPARSE too (`CatSets.spans`; PR 56): a
set node's K rows lie in one one-hot block (two for a column of more than
128 named ids), an ordinal node's in an ordinal one, and of a tree's 254
nodes few read any one block. So where a tree is two lane tiles the host
numbers its node lanes by the K-block they read (models/tree.
choose_set_spans, from the model alone: the blocks tied by a node that
reads two are a component, the ordinal blocks one more, each component read
by the first tile, by the second or by both, the fewest weight tiles under
which every tree fits), lays `sel`'s K-blocks in the order (the first
tile's own, the shared, the second's own: the ordinal rows then lie behind
`CatSets.ordinal_at` one-hot blocks, and `cat_expand` / `cat_bins` follow
their blocks), and the kernel sums lane tile j over span j's blocks alone,
as for a sub-tree. `planes` rows 0 and 3 and `paths`' rows follow the node
lanes; the leaf lanes keep their place, so `m = s @ P` sums the same
integers in another order and the scores are bit for bit those of the
dense numbering. The weight tiles a tree asks are the spans' sum and the
resolve's: the Allstate model (six one-hot blocks beside the ordinal one;
the ordinal block, 130 nodes a tree, in both tiles and each one-hot block
in one) 4 + 4 + 4 = 12 where an ordinal model of its shape asks 5
(`catset_mxu_tiles_per_tree` 7) and 7 x 2 + 4 = 18 before PR 56. Dense
spans (any other width than two lane tiles, more than eight components, a
model no cheaper assignment fits: eight categorical columns over five
blocks may ask 5 x 2 + 4 = 14, `catset_mxu_tiles_per_tree` 9) are ONE
matmul a K-block over all the lanes, the ordinal rows first: that program
and its tables, instruction for instruction. A model without a set traces
the program it did.

Learned NaN directions (`missing_routes`; models/tree.CompiledNodeList):
the NaN bin is the top bin, above every threshold, so `v > thr` alone is
the default-RIGHT route. A node that sends NaN LEFT stops answering right
at the NaN bin: s = (thr < v < up) ? +1 : -1, `up` (planes' row 3, one more
of the 8 rows the table already has) the NaN bin there and +BIG elsewhere.
Two thresholds, not a mask and a test for the NaN bin: one compare and one
AND a vreg of v more than the plain form, no new table. A model without
directions traces the one-compare program.

The HBM interface is the heap kernel's (ops/predict_pallas.py, PR 36): the
rows go in as the caller holds them (uint8 from api.predict; no int32 copy
of a chunk exists outside VMEM, where at 968 columns it would be 8 GB a
2M-row chunk), over a grid of cdiv(R, tile) row tiles whose last block is
ragged (rows are independent, and what a block holds past row R decides
nothing that is written), and the scores come out `f32[1, R]`, the rows on
the lanes: a sub-tile's [SUB_ROWS, W] accumulator is folded to 128 lanes,
turned over on the XLU and summed down its sublanes.

Layout strategy. The tables are 156 KB a tree at W = 256 (sel 20 packed,
16 at P = 1; planes 8, P 128), 80 MB for 500 trees: they do not stay in
VMEM, and streaming them for every 256-row tile as the heap kernel streams
its blocks would move 0.6 TB a 2M-row chunk. So the ROW TILE is thousands
of rows (`TILE_ROWS`) and the grid is (row tiles, table blocks): one step
holds a block of G trees' tables (Mosaic double-buffers the windows: the next
block's DMA runs under this block's matmuls) and walks the tile in
sub-tiles of `SUB_ROWS` rows (1024 where the select answers two nodes a
lane), each against the block's G trees in turn;
the [TILE_ROWS, 1] output stays resident over the block axis, zeroed by
the first block and added to by all. A row tile streams the tables once:
489 times a 2M-row chunk at 4096 rows, 39 GB against a second of MXU
time. G is not a knob (`path_plan`): the most trees whose windows fit the
VMEM budget beside the row tile's (64, the cap, at 255 leaves and 28
columns; 11 at 968 columns, where a tree's tables are 639 KB), or the
size down to half of that which leaves the last block the fewest filler
trees (500 trees: 10 blocks of 50, and 50 of 10). The other order (table blocks
outside, each fetched once a chunk) would revisit an output block across
grid steps that do not follow one another, which Pallas does not keep.

Exactness is the form's own: bins below 256, their multiples of 256, M,
+-1 and P are bfloat16 without rounding, the MXU accumulates in float32,
every partial sum of the resolve is an integer of at most 255 in magnitude
and of the select one below 2^16, plus M: below 2^24, exact in any order.
The leaf reached is the node walk's for every (row, tree); scores agree
with ops/predict._predict_paths to the float32 rounding of a sum in another
order (equal on dyadic leaf values).
Interpret mode auto-selects off-TPU, as in predict_pallas.py; the dispatch
rule is `kernel_serves`, below.
"""

from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddt_tpu.ops.predict_pallas import (
    _MANTISSA, _MXU_ROWS, _copy_stride, _window_bytes, row_operand_dtype)
from ddt_tpu.telemetry.annotations import traced_scope
from ddt_tpu.utils import device

# Rows a grid step holds (one walk of the tables), and rows a sub-tile:
# the [SUB_ROWS, W] float32 planes v, m and acc are what the VPU touches.
# Read on the v5e by a probe of the kernel alone (2M device-resident rows,
# 500 trees x 255 leaves, ms a call; PERF.md section 6, PR 33): tile 4096
# with sub-tiles of 256 / 512 rows under the default 16 MiB of scoped VMEM
# (8 / 3 trees a block) 1,172.9 / 1,189.0; tile 2048 (21 / 16 trees)
# 1,098.3 / 1,064.0; with `_VMEM_LIMIT_BYTES` (56 trees a block) tile 4096
# 1,066.9 / **1,024.6**; tile 8192 at 48 MiB (84 trees, sub-tiles of 256)
# 1,063.2. The MXU's own time is 1,008 (6 weight tiles x 64 cycles a tree
# and 256 rows at 1.5 GHz). What a grid step costs beside its trees (the
# lane reduction and the masked add into the [TILE_ROWS, 1] output, once a
# sub-tile and block) is what more trees a block buy back; a `fori_loop`
# over the block's trees in place of the unrolled loop: 1,579.8.
# With two nodes a lane of the select (5 weight tiles a tree: the MXU's
# time 833.3) the compiler schedules a sub-tile of 512 rows AT the MXU's
# rate, 16 bundles for every 4 vmatmul, so nothing hides what the hardware
# stalls on beside it; the same probe (PERF.md section 6, PR 38): sub-tiles
# of 256 / 512 / 1024 rows 1,085.0 / 893.5 / **862.0**; 2048 (its working
# set leaves 25 trees a block, so the float32 adds are grouped otherwise:
# not the unpacked form's bits) 853.2; tile 8192 with 1024: 863.7.
TILE_ROWS = 4096
SUB_ROWS = 512
_SUB_ROWS_PACKED = 1024
_LANES = 128
# Scoped VMEM the kernel asks of Mosaic (the default is 16 MiB of the
# v5e's 128), and what of it `path_plan` fills: the rest is the compiler's.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_VMEM_BUDGET_BYTES = _VMEM_LIMIT_BYTES - 4 * 1024 * 1024
# Trees a block at most: the kernel's trace is that many trees long.
_MAX_TREES_PER_STEP = 64
# Bytes a sub-tile's row keeps beside the windows: v, s, m, acc and the
# select's temporaries, [SUB_ROWS, W] each: 6 float32 planes a lane; and
# a widened bin of the sub-tile's K-blocks: the bf16 copy the trees of a
# block share and the float32 it is made from.
_SUB_ROW_LANE_BYTES = 24
_SUB_ROW_BIN_BYTES = 6


def select_k_blocks(n_features: int) -> int:
    """K-blocks of 128 columns the feature select is summed over."""
    return -(-n_features // _LANES)


def select_nodes_per_lane(n_features: int, lanes: int) -> int:
    """Nodes a result lane of the feature select answers for: 2 where two
    copies of the features fit the weight tile's 128 K rows (F <= 64, the
    heap kernel's `nodes_per_tile` rule) and the tree has at least 256
    node lanes (at 128 the select is one weight tile already), else 1.
    Read from the input; learned NaN directions do not decide it."""
    packs = 2 * _copy_stride(n_features) <= _MXU_ROWS and lanes >= 2 * _LANES
    return 2 if packs else 1


def _sub_rows(nodes_per_lane: int) -> int:
    """Rows a sub-tile of the kernel's walk holds (the constants above)."""
    return SUB_ROWS if nodes_per_lane == 1 else _SUB_ROWS_PACKED


def _mantissa_rows(n_features: int) -> int:
    """K rows of the packed table that carry _MANTISSA against the left
    operand's ones: 8 where two copies of the features leave the weight
    tile that many (F <= 56), else 0 and the VPU adds it."""
    return 8 if 2 * _copy_stride(n_features) + 8 <= _MXU_ROWS else 0


class CatSets(typing.NamedTuple):
    """The shape of a model's CATEGORY SETS (module docstring), as
    `path_plan` takes it: read from the tables (models/tree.
    CompiledNodeList.cat_expand and sel)."""

    blocks: int                # B: one-hot K-blocks of 128 rows
    select_rows: int           # K rows of `sel`: the ordinal ones (Fp, or
    #   0 where every node asks a set) and 128 B
    spans: tuple = ()          # (first, stop) K-blocks of `sel`, in its
    #   rows' order, that each 128-lane tile of a tree reads (models/tree.
    #   CompiledNodeList.select_spans); (): every tile reads every block
    ordinal_at: int = 0        # the one-hot blocks whose K rows lie before
    #   the ordinal ones in `sel`

    @property
    def ordinal_rows(self) -> int:
        return self.select_rows - _LANES * self.blocks


def _select_shape(lanes: int, n_features: int, nodes_per_lane: int,
                  cat: CatSets | None = None) -> tuple:
    """(K rows, lanes) of a tree's select table as the kernel takes it:
    [Fp, W], or packed (`pack_select`) [K2, Wp]: two copies of the features
    and, where they leave the tile 8 rows, the mantissa's, up to whole bf16
    sublane tiles, over the lanes of the first copy's nodes; with CATEGORY
    SETS the table's own K rows."""
    if cat:
        return cat.select_rows, lanes
    if nodes_per_lane == 1:
        return -(-n_features // 16) * 16, lanes
    k2 = 2 * _copy_stride(n_features) + _mantissa_rows(n_features)
    return -(-k2 // 16) * 16, _lane_pad(lanes // 2)


def select_mxu_tiles(lanes: int, n_features: int,
                     nodes_per_lane: int | None = None,
                     select_spans: tuple = (),
                     cat: CatSets | None = None) -> int:
    """MXU weight tiles of a tree's (a sub-tree's) feature select: ceil(F /
    128) K-blocks x ceil(W / nodes a lane / 128) lane tiles, or under
    `select_spans` (`Chain`) the K-blocks of each lane tile's own span.
    `nodes_per_lane` None: the kernel's own (`select_nodes_per_lane`).
    `cat`: the K-blocks the kernel asks of a model with CATEGORY SETS, the
    ordinal ones (if any node is ordinal) and the one-hot's, one node a
    lane: every block a lane tile, or under `cat.spans` each tile's own."""
    if cat:
        return sum(stop - start for start, stop in cat.spans) or (
            _cat_k_blocks(n_features, cat) * (lanes // _LANES))
    if nodes_per_lane is None:
        nodes_per_lane = select_nodes_per_lane(n_features, lanes)
    if select_spans and nodes_per_lane == 1:
        return sum(stop - start for start, stop in select_spans)
    return select_k_blocks(n_features) * -(-(lanes // _LANES)
                                           // nodes_per_lane)


def _cat_k_blocks(n_features: int, cat: CatSets) -> int:
    """K-blocks the select of a model with CATEGORY SETS is summed over."""
    return cat.blocks + (select_k_blocks(n_features)
                         if cat.ordinal_rows else 0)


def _cat_block_rows(n_features: int, cat: CatSets) -> list:
    """K rows of each K-block of such a model's `sel`, in its rows' order:
    128 a one-hot block, the ordinal blocks behind `cat.ordinal_at` of them
    (the last of those up to whole bf16 sublane tiles of the columns)."""
    ordinal = [min(k0 + _LANES, cat.ordinal_rows) - k0
               for k0 in range(0, n_features, _LANES)] * (cat.ordinal_rows > 0)
    return ([_LANES] * cat.ordinal_at + ordinal
            + [_LANES] * (cat.blocks - cat.ordinal_at))


def resolve_mxu_tiles(lanes: int, halved: bool = False) -> int:
    """MXU weight tiles of a tree's (a sub-tree's) path resolve: (W/128)^2,
    or of a HALVED sub-tree (`Chain.halved`) the W/128 diagonal ones."""
    w = lanes // _LANES
    return w if halved else w * w


def path_mxu_tiles_per_tree(lanes: int, n_features: int,
                            nodes_per_lane: int | None = None,
                            exit_lanes: int = 0,
                            select_spans: tuple = (),
                            halved: bool = False,
                            cat: CatSets | None = None) -> int:
    """MXU weight tiles (results [rows, 128]) a tree costs a tile of rows:
    the feature select's (`select_mxu_tiles`) and the path resolve's
    (`resolve_mxu_tiles`); of a SUB-TREE besides the exits' table's, W/128 x
    `exit_lanes`/128 (the class dot and the chain)."""
    return (select_mxu_tiles(lanes, n_features, nodes_per_lane, select_spans,
                             cat)
            + resolve_mxu_tiles(lanes, halved)
            + (lanes // _LANES) * (exit_lanes // _LANES))


def _resolve_rows(lanes: int, halved: bool) -> int:
    """Rows of a tree's path table: its node lanes, or HALVED one half's
    (the two diagonal blocks side by side)."""
    return lanes // 2 if halved else lanes


def _tree_bytes(lanes: int, n_features: int, nodes_per_lane: int = 1,
                exit_lanes: int = 0, halved: bool = False,
                cat: CatSets | None = None) -> int:
    """HBM bytes of one tree's (one sub-tree's) tables: sel bf16, planes
    f32, P bf16, the exits' bf16."""
    k, w = _select_shape(lanes, n_features, nodes_per_lane, cat)
    return (k * w * 2 + 8 * lanes * 4
            + _resolve_rows(lanes, halved) * lanes * 2
            + lanes * exit_lanes * 2)


class Chain(typing.NamedTuple):
    """The shape of a model in the sub-tree form, as `path_plan` and the
    kernel's caller take it (models/tree.CompiledNodeList has the tables)."""

    n_trees: int               # T: what a mean divides by
    leaf_columns: int          # C
    class_lanes: int           # CL: the lanes of the class sums (whole
    #   128s), three bfloat16 pieces of C columns in the first 3 C of them
    act_lanes: int             # A: the activity's lanes (whole 128s)
    select_spans: tuple = ()   # (first, stop) K-blocks of the select each
    #   128-lane tile of a sub-tree reads (models/tree.CompiledNodeList);
    #   (): every tile reads every block
    shared: bool = False       # the exits' table is ONE lane tile, the
    #   links behind the pieces in the class sums' own 128 lanes
    #   (models/tree.exit_table_lanes: THE RULE); else [V | L], CL + A wide
    halved: bool = False       # a sub-tree is two halves of 128 lanes that
    #   share their spine (models/tree.cut_subtrees): `paths` holds the two
    #   diagonal blocks alone, [S, 128, 256]

    @property
    def exit_lanes(self) -> int:
        """Lanes of the exits' table."""
        return self.class_lanes + (0 if self.shared else self.act_lanes)

    @property
    def at_hand(self) -> int:
        """The lane of the activity that is the sub-tree at hand (a link to
        the next sub-tree lies one lane above it in the exits' table's
        chain lanes): right behind the pieces, or the activity tiles'
        first."""
        return _LEAF_PIECES * self.leaf_columns if self.shared else 0


# bfloat16 pieces a float32 leaf value is held in (models/tree.
# split_bfloat16: three are exact).
_LEAF_PIECES = 3


def chain_of(n_trees: int, leaf_columns: int, exit_lanes: int,
             select_spans: tuple = (), path_shape: tuple = ()) -> Chain:
    """The Chain of a compiled model whose exits' table is `exit_lanes`
    wide and whose lanes are ordered under `select_spans`. The table's own
    width says which layout models/tree.exit_table_lanes (THE RULE) gave
    it: no wider than the class lanes, so the links share their tile (one
    tile: 3 C < 128); else the activity lanes are what lies behind them.
    `path_shape`, the path table's [S, rows, W], says in the same way
    whether the sub-trees are HALVED: fewer rows than lanes."""
    cl = _lane_pad(_LEAF_PIECES * leaf_columns)
    shared = exit_lanes == cl == _LANES
    if not shared and exit_lanes <= cl:
        raise ValueError(
            f"an exits' table of {exit_lanes} lanes holds no chain beside "
            f"the {cl} class lanes of {leaf_columns} leaf columns")
    return Chain(n_trees, leaf_columns, cl, _LANES if shared
                 else exit_lanes - cl, select_spans, shared,
                 bool(path_shape) and path_shape[-2] < path_shape[-1])


class PathPlan(typing.NamedTuple):
    """How a node-list ensemble's tables meet the kernel (`path_plan`)."""

    node_list: int             # 1: this form serves (0 in NO_PATH_PLAN)
    nodes_per_tree: int        # W, the padded lanes of nodes ...
    leaves_per_tree: int       # ... and of leaves
    deepest_leaf: int          # nodes on the model's longest path
    path_mxu_tiles_per_tree: int
    trees_per_step: int        # G: trees a table block; 0 = nothing fits
    table_blocks: int          # blocks a row tile walks
    table_bytes: int           # HBM bytes of all the blocks, read once
    tile_rows: int
    select_k_blocks: int = 1   # 128-column blocks the select is summed over
    missing_routes: int = 0    # 1: learned NaN directions in the compare
    row_operand_bytes: int = 1  # a bin of the row block as HBM holds it
    select_nodes_per_lane: int = 1  # 2: the select's lanes answer in pairs
    # The sub-tree form (module docstring, THE CHAIN); a tree that one path
    # matrix holds is one sub-tree of its own lanes, with no chain.
    subtrees_per_tree: float = 1.0  # table entries a tree, on average
    subtree_lanes: int = 0          # W of an entry
    leaf_columns: int = 1           # C, the output columns a leaf holds
    chain_mxu_tiles_per_tree: int = 0   # of path_mxu_tiles_per_tree: the
    #   exits against the activity lanes' OWN tiles (0 where the links
    #   share the class lanes' tile: `exit_mxu_tiles` counts that one)
    class_dot_passes: int = 0       # bfloat16 pieces of a float32 leaf value
    #   in the class dot's tile (0: leaf values added on the VPU)
    select_mxu_tiles: int = 0       # of a sub-tree's tiles: the feature
    #   select's (14 at 784 columns and 256 lanes; 7 where each lane tile
    #   reads its own K-blocks alone, `Chain.select_spans`)
    exit_mxu_tiles: int = 0         # of a sub-tree's tiles: the exits'
    #   table's (W/128 x its lane tiles: 2 at 256 lanes where the class
    #   pieces and the chain share ONE tile, `Chain.shared`; 4 or more
    #   where each has tiles of its own; 0 without a chain)
    # What the model's cut looks like beside its mean (`scoring_program`
    # fills them from models/tree.CompiledNodeList), and the link function the
    # program applies to the margins on the device ("none": the caller's).
    subtrees_per_tree_max: int = 1      # the largest tree's table entries
    single_subtree_trees: int = 0       # trees that are ONE entry
    link: str = "none"
    resolve_mxu_tiles: int = 0          # of a tree's (a sub-tree's) tiles:
    #   the path resolve's, (W/128)^2: 4 at 256 lanes; 2 where the sub-trees
    #   are HALVED (`Chain.halved`: the path matrix's diagonal blocks alone)
    spine_copies_per_subtree: float = 0.0   # ... and what that costs in
    #   lanes: the copies of a first half's nodes a second half holds, on
    #   average (the backend fills it; `subtrees_per_tree` says what the
    #   halves' bound cost the cut)
    pieces_per_subtree: float = 1.0     # connected pieces of its tree an
    #   entry holds, on average (1.0: nothing packed) ...
    glue_copies_per_subtree: float = 0.0    # ... and the lanes that hold a
    #   copy of the pieces' common ancestors (models/tree.cut_subtrees;
    #   the backend fills both)
    # CATEGORY SETS (module docstring; LightGBM's categorical splits)
    category_sets: int = 0              # 1: some node asks a set
    category_nodes: int = 0             # the nodes that do, and the bins of
    category_set_bits_max: int = 0      #   the widest set (the backend's)
    catset_mxu_tiles_per_tree: int = 0  # of path_mxu_tiles_per_tree: what
    #   the set test adds beside an ordinal model of the same F and W

    @property
    def blocks(self) -> int:
        """As TablePlan.blocks: past 1 a row tile streams `table_bytes`;
        one block's index never moves, so it is fetched once and stays."""
        return self.table_blocks

    def step_rows(self, rows: int) -> int:
        """As TablePlan.step_rows: this form's row tile whatever the
        program's rows (a shorter program is ONE tile either way)."""
        return self.tile_rows

    def span_counts(self) -> dict:
        return {k: getattr(self, k) for k in SPAN_COUNTS}

    def root_counts(self) -> dict:
        return {"routing_tables": self.missing_routes,
                "node_list": self.node_list,
                "path_mxu_tiles_per_tree": self.path_mxu_tiles_per_tree,
                "select_k_blocks": self.select_k_blocks,
                "select_nodes_per_lane": self.select_nodes_per_lane,
                **{k: getattr(self, k) for k in CHAIN_COUNTS + CAT_COUNTS}}


# What the `ddt:predict:ensemble` span says of a node-list model's plan, in
# the order it prints (docs/OBSERVABILITY.md); `cli predict` repeats all
# but `table_bytes` in `phases_ms`, as it does for the heap kernel's.
CHAIN_COUNTS = ("subtrees_per_tree", "subtrees_per_tree_max",
                "single_subtree_trees", "subtree_lanes", "leaf_columns",
                "link", "chain_mxu_tiles_per_tree", "class_dot_passes",
                "select_mxu_tiles", "exit_mxu_tiles", "resolve_mxu_tiles",
                "spine_copies_per_subtree", "pieces_per_subtree",
                "glue_copies_per_subtree")
CAT_COUNTS = ("category_sets", "category_nodes", "category_set_bits_max",
              "catset_mxu_tiles_per_tree")
SPAN_COUNTS = ("node_list", "nodes_per_tree", "leaves_per_tree",
               "deepest_leaf", "path_mxu_tiles_per_tree", "trees_per_step",
               "table_blocks", "table_bytes", "select_k_blocks",
               "missing_routes", "row_operand_bytes",
               "select_nodes_per_lane") + CAT_COUNTS + CHAIN_COUNTS
PHASES_COUNTS = tuple(k for k in SPAN_COUNTS if k != "table_bytes")


def _lane_pad(n: int) -> int:
    return -(-n // _LANES) * _LANES


def path_plan(n_trees: int, lanes: int, n_features: int,
              deepest_leaf: int = 0, served: bool = True,
              missing_routes: bool = False, row_dtype=jnp.uint8,
              chain: Chain | None = None,
              widest_tree: int = 0,
              cat: CatSets | None = None) -> PathPlan:
    """The kernel's table blocks at this shape: G trees a block, the most
    whose double-buffered windows fit _VMEM_BUDGET_BYTES beside what the
    kernel holds whatever G: the row tile's two windows at the rows' own
    width (`row_dtype`, as `row_operand_dtype` takes it: 4096 x 968 uint8
    is 4.2 MB a window where the int32 tile was 16.8), the [1, TILE_ROWS]
    output's, a sub-tile's widened K-blocks and its working set; then the
    size near it that leaves the fewest filler trees. `served` False: the
    plan of a model the jax.numpy form scores (its lanes and depth, no
    blocks). `missing_routes` rides along for the spans and decides
    nothing here. `chain`: the SUB-TREE form; `n_trees` then counts the
    table's entries, the sub-trees, G of which a block holds, whose windows
    have the exits' table among them, and beside which the kernel holds the
    [TILE_ROWS, CL] output's windows and the [TILE_ROWS, A] activity;
    `widest_tree` the lanes the widest tree would take uncut (what the
    spans call `nodes_per_tree`). `cat`: the model carries CATEGORY SETS
    (the uncut form alone): one node a lane, the select's K rows the
    table's own, the one-hot's tables and copies beside the windows."""
    # The jax.numpy form takes the select as the model compiles it.
    pack = select_nodes_per_lane(n_features, lanes) \
        if served and not cat else 1
    exit_lanes = chain.exit_lanes if chain else 0
    spans = chain.select_spans if chain else ()
    halved = bool(chain and chain.halved)
    tiles = path_mxu_tiles_per_tree(lanes, n_features, pack, exit_lanes,
                                    spans, halved, cat)
    row_bytes = row_operand_dtype(row_dtype).itemsize
    said = dict(select_k_blocks=_cat_k_blocks(n_features, cat) if cat
                else select_k_blocks(n_features),
                missing_routes=int(missing_routes),
                row_operand_bytes=row_bytes, select_nodes_per_lane=pack,
                subtree_lanes=lanes,
                select_mxu_tiles=select_mxu_tiles(lanes, n_features, pack,
                                                  spans, cat),
                resolve_mxu_tiles=resolve_mxu_tiles(lanes, halved))
    if cat:
        # beside the ordinal model of this shape, under the kernel's own
        # rule for it (two nodes a lane where they fit)
        said.update(category_sets=1, catset_mxu_tiles_per_tree=tiles
                    - path_mxu_tiles_per_tree(lanes, n_features))
    widest_tree = widest_tree or lanes
    if chain:
        per = n_trees / chain.n_trees
        own = exit_lanes - chain.class_lanes    # the links' own lane tiles
        said.update(subtrees_per_tree=round(per, 2),
                    leaf_columns=chain.leaf_columns,
                    chain_mxu_tiles_per_tree=round(
                        per * (lanes // _LANES) * (own // _LANES)),
                    class_dot_passes=_LEAF_PIECES,
                    exit_mxu_tiles=(lanes // _LANES) * (exit_lanes // _LANES))
        tiles = round(per * tiles)
    if not served:
        return PathPlan(1, widest_tree, widest_tree, deepest_leaf, tiles, 0,
                        0, 0, 0, **said)
    fp, sel_lanes = _select_shape(lanes, n_features, pack, cat)
    per_tree = (_window_bytes(fp, sel_lanes) // 2      # bf16: half of f32
                + _window_bytes(8, lanes)
                + _window_bytes(_resolve_rows(lanes, halved), lanes) // 2
                + _window_bytes(lanes, exit_lanes) // 2)
    # the scores' window: [1, TILE_ROWS], or the chain's [TILE_ROWS, CL]
    # with the activity scratch, a sub-tile's copies of both, and y and
    # a * y
    out = _window_bytes(1, TILE_ROWS) if not chain else (
        _window_bytes(TILE_ROWS, chain.class_lanes)
        + TILE_ROWS * chain.act_lanes * 4
        + _sub_rows(pack) * (chain.class_lanes + chain.act_lanes
                             + 2 * exit_lanes) * 4)
    fixed = (2 * TILE_ROWS * _lane_pad(n_features) * row_bytes
             + out
             + _sub_rows(pack) * (_lane_pad(fp) * _SUB_ROW_BIN_BYTES
                           + lanes * _SUB_ROW_LANE_BYTES))
    if cat:
        # the two small tables' windows; the sub-tile's bins are widened by
        # the columns (`fp` above counts the one-hot's K rows, as it should)
        fixed += cat.blocks * (
            _window_bytes(_select_shape(lanes, n_features, 1)[0],
                          _LANES) // 2 + _window_bytes(8, _LANES))
    most = min(n_trees, _MAX_TREES_PER_STEP,
               max(0, (_VMEM_BUDGET_BYTES - fixed) // per_tree))
    if most == 0:
        return PathPlan(1, widest_tree, widest_tree, deepest_leaf, tiles, 0,
                        0, 0, TILE_ROWS, **said)
    # Of the block sizes from `most` down to half of it, the one that
    # fills its last block best (filler trees cost what trees cost: 500
    # trees are 50 blocks of 10 where 46 of 11 would score 506), the
    # larger where two fill alike.
    g = min(range(most, max(most // 2, 1) - 1, -1),
            key=lambda g: (-(-n_trees // g) * g, -g))
    blocks = -(-n_trees // g)
    return PathPlan(1, widest_tree, widest_tree, deepest_leaf, tiles, g,
                    blocks,
                    blocks * g * _tree_bytes(lanes, n_features, pack,
                                             exit_lanes, halved, cat),
                    TILE_ROWS, **said)


def predict_paths_fits(lanes: int, n_features: int,
                       row_dtype=jnp.uint8,
                       chain: Chain | None = None,
                       cat: CatSets | None = None) -> bool:
    """Whether one tree's tables fit the kernel's VMEM budget beside a row
    tile: the guard behind use_pallas=None (`kernel_serves`, the rule).
    The tree count is no term of it. `chain`: a model in the sub-tree
    form; one sub-tree's tables beside the output's windows and the
    activity."""
    return path_plan(1, lanes, n_features, row_dtype=row_dtype,
                     chain=chain, cat=cat).trees_per_step > 0


def pack_select(sel, planes, n_features: int, xp=jnp) -> tuple:
    """The select table and the thresholds of `select_nodes_per_lane` 2,
    from the tables a model compiles (models/tree.CompiledNodeList): sel
    [T, K2, Wp] bf16 (`_select_shape`), lane n the one-hot of node n over
    the first copy's K rows and of node Wp + n over the second's, and where
    the tile has the 8 rows to spare _MANTISSA in the first of them; planes
    with the thresholds of the second copy's nodes (lanes Wp and up of rows
    0 and 3) moved to their byte of the word the matmul returns, M + bin_a +
    256 bin_b: b > thr is word > M + 256 (thr + 1) - 1, b < up is word <
    M + 256 up; +BIG stays +BIG. `xp` numpy: on the host, once a model
    (`scoring_program`, below); jax.numpy: inside the caller's program."""
    wp = _select_shape(sel.shape[2], n_features, 2)[1]
    stride = _copy_stride(n_features)

    def copy(a):        # [T, F, <= Wp] -> [T, stride, Wp]
        return xp.pad(a, ((0, 0), (0, stride - n_features),
                          (0, wp - a.shape[2])))

    parts = [copy(sel[:, :n_features, :wp]), copy(sel[:, :n_features, wp:])]
    if _mantissa_rows(n_features):
        parts.append(xp.pad(
            xp.full((sel.shape[0], 1, wp), _MANTISSA, sel.dtype),
            ((0, 0), (0, 7), (0, 0))))
    packed = xp.concatenate(parts, axis=1)
    packed = xp.pad(packed, ((0, 0), (0, -packed.shape[1] % 16), (0, 0)))

    def shifted(row, plus_one):
        at = xp.clip(row[:, :, wp:], -1.0, 256.0) + plus_one
        return xp.concatenate(
            [row[:, :, :wp], xp.where(row[:, :, wp:] < 2.0 ** 29,
                                      _MANTISSA + 256.0 * at - plus_one,
                                      row[:, :, wp:])], axis=2)

    planes = xp.concatenate(
        [shifted(planes[:, 0:1], 1.0), planes[:, 1:3],
         shifted(planes[:, 3:4], 0.0), planes[:, 4:]], axis=1)
    return packed, planes


def _paths_kernel(x_ref, sel_ref, planes_ref, paths_ref, *rest,
                  n_trees: int, n_feat: int, missing_routes: bool,
                  class_lanes: int = 0, select_spans: tuple = (),
                  at_hand: int = 0, cat_blocks: int = 0,
                  cat_ordinal_at: int = 0):
    """One row tile against one block of `n_trees` trees: the block's share
    of every row's margin. x_ref [TILE_ROWS, F] uint8 or int32, as HBM
    holds the rows (in the last tile, whatever lies past row R); sel
    [G, Fp, W] bf16, or `pack_select`'s [G, K2, Wp] (fewer lanes than the
    planes: two nodes a lane), planes [G, 8, W] f32, paths [G, W, W] bf16;
    out [1, TILE_ROWS] f32, the rows on the lanes, resident over the block
    axis (grid axis 1).

    `class_lanes` CL > 0, the SUB-TREE form: the block's entries are G
    sub-trees, `rest` is (leaves [G, W, E] bf16, out [TILE_ROWS, CL] f32,
    act [TILE_ROWS, A] f32 scratch): the output holds the rows on the
    sublanes and the leaf values' three pieces on the lanes, and the
    activity lives in VMEM over the block axis, a tree's sub-trees lying in
    one block or in several. A first entry of the whole table roots a tree,
    so what the scratch held before is never read. E = CL + A, [V | L], or
    E = CL = A = 128, the links behind the pieces in ONE tile (`Chain.
    shared`); `at_hand` the activity's lane of the sub-tree at hand
    (`Chain.at_hand`). `select_spans`: the
    K-blocks each 128-lane tile of the select reads (`Chain`, or `CatSets`
    of an uncut tree with category sets: the K-blocks in the order of sel's
    rows; the caller hands the packed select none); (): every block, one
    matmul a block over all the lanes.

    `cat_blocks` B > 0, CATEGORY SETS (module docstring; the uncut form
    alone): `rest` is (cat_expand [B, Fp, 128] bf16, cat_bins [B, 8, 128]
    f32, out); sel's K rows are the B one-hot blocks' with the ordinal
    ones (Fp, or none) behind the first `cat_ordinal_at` of them."""
    out_ref = rest[2 if cat_blocks else class_lanes > 0]
    tile_rows = x_ref.shape[0]
    fp, lanes = sel_ref.shape[1], planes_ref.shape[2]
    # the K rows of `sel` that read the bins themselves; the rows are
    # widened to whole bf16 sublane tiles of the columns either way
    ordinal_rows = fp - _LANES * cat_blocks
    if cat_blocks:
        fp = rest[0].shape[1]
    wp = sel_ref.shape[2]
    packed = wp < lanes
    half = paths_ref.shape[1]
    halved = half < lanes
    sub_rows = _sub_rows(2 if packed else 1)
    stride = _copy_stride(n_feat)
    ones_in_tile = bool(_mantissa_rows(n_feat))
    k_starts = range(0, n_feat, _LANES)
    # The select's lane groups (first lane, stop, first K-block, stop): a
    # run of lane tiles that read the same K-blocks is one matmul a block.
    groups = [(0, wp, 0, cat_blocks + len(k_starts) * (ordinal_rows > 0))]
    if len(set(select_spans)) > 1:
        groups = [(j * _LANES, (j + 1) * _LANES, *span)
                  for j, span in enumerate(select_spans)]

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    def sub_tile(j, carry):
        r0 = pl.multiple_of(j * sub_rows, sub_rows)
        # The sub-tile's K-blocks, widened once for the block's trees: the
        # bf16 copy lives in VMEM alone.
        xs = []
        for k0 in k_starts:
            k1, kp = min(k0 + _LANES, n_feat), min(k0 + _LANES, fp)
            xf = x_ref[pl.ds(r0, sub_rows), k0:k1].astype(
                jnp.int32).astype(jnp.float32)
            if packed:
                # [x | 256 x | ones]: the copies from the K rows of the
                # table's (a multiple of 8 each), the ones against the
                # row that adds _MANTISSA inside the MXU.
                gap = [jnp.zeros((sub_rows, stride - n_feat), jnp.float32)
                       ] * (stride > n_feat)
                ones = [jnp.ones((sub_rows, 8), jnp.float32)] * ones_in_tile
                xf = jnp.concatenate([xf, *gap, xf * 256.0, *gap, *ones],
                                     axis=1)
                k1 = 2 * stride + 8 * ones_in_tile
            if kp > k1:     # K to whole bf16 sublane tiles
                xf = jnp.concatenate(
                    [xf, jnp.zeros((sub_rows, kp - k1), jnp.float32)], axis=1)
            xs.append(xf.astype(jnp.bfloat16))            # [S, <= 128]
        # CATEGORY SETS: the one-hot of every category column's bin, a
        # K-block of 128 rows each, once for the block's trees: the bin of
        # the column a K row reads by one small matmul, one compare.
        hot = []
        expand_ref, bins_ref = rest[:2] if cat_blocks else (None, None)
        for b in range(cat_blocks):
            xe = None
            for k0, xk in zip(k_starts, xs):
                part = jax.lax.dot_general(
                    xk, expand_ref[b, k0:k0 + xk.shape[1], :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [S, 128]
                xe = part if xe is None else xe + part
            hot.append(jnp.where(xe == bins_ref[b, 0:1, :], 1.0, 0.0
                                 ).astype(jnp.bfloat16))
        # the select's K-blocks, in the order of sel's rows: (first K row
        # of sel, left operand)
        k_blocks = list(zip(k_starts, xs)) * (ordinal_rows > 0)
        if cat_blocks:
            at = cat_ordinal_at
            k_blocks = [(b * _LANES, h) for b, h in enumerate(hot[:at])] + [
                (at * _LANES + k0, xk) for k0, xk in k_blocks] + [
                (ordinal_rows + b * _LANES, h)
                for b, h in enumerate(hot[at:], at)]

        def tree(g, acc):
            """`acc` plus tree g's leaf value; of a sub-tree (`acc` None)
            its exits e [S, W] bf16, 1 at the exit the sub-tree's root
            leads this row to."""
            rows = planes_ref[g]                          # [8, W]
            # bf16 operands (bins <= 255, their multiples of 256, the 0/1
            # one-hot and _MANTISSA are exact), f32 accumulator: the v5e's
            # VPU has no bf16 compare.
            vs = []
            for l0, l1, first, stop in groups:
                v = None
                for k0, xk in k_blocks[first:stop]:
                    part = jax.lax.dot_general(
                        xk, sel_ref[g, k0:k0 + xk.shape[1], l0:l1],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)   # [S, Wp]
                    v = part if v is None else v + part
                vs.append(v)
            v = vs[0] if len(vs) == 1 else jnp.concatenate(vs, axis=1)
            if not packed:
                right = [v > rows[0:1, :]]
                if missing_routes:  # not at the NaN bin where NaN goes left
                    right[0] &= v < rows[3:4, :]
            else:
                # v = M + bin_a + 256 bin_b, the exact integer: node Wp + n
                # (b) compares as the whole word against its shifted
                # threshold, node n (a) as the low byte of the mantissa,
                # which the bitcast reads without a convert.
                if not ones_in_tile:        # no K row left for it
                    v = v + _MANTISSA
                low = pltpu.bitcast(v, jnp.int32) & 255
                high = v[:, :lanes - wp]
                right = [low > rows[0:1, :wp].astype(jnp.int32),
                         high > rows[0:1, wp:]]
                if missing_routes:
                    right[0] &= low < rows[3:4, :wp].astype(jnp.int32)
                    right[1] &= high < rows[3:4, wp:]
            if halved:
                # Every exit's path lies in its own half of the lanes (the
                # second half holds copies of the spine): the off-diagonal
                # blocks of P are zeros nobody stored, and the resolve is a
                # product a half.
                if not packed:      # (packed, `right` is the halves' already)
                    right = [right[0][:, :half], right[0][:, half:]]
                m = jnp.concatenate([jax.lax.dot_general(
                    jnp.where(r, 1.0, -1.0).astype(jnp.bfloat16),
                    paths_ref[g, :, j * half:(j + 1) * half],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    for j, r in enumerate(right)], axis=1)    # [S, W]
            else:
                s = jnp.concatenate(
                    [jnp.where(r, 1.0, -1.0) for r in right],
                    axis=1).astype(jnp.bfloat16)
                m = jax.lax.dot_general(
                    s, paths_ref[g], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [S, W]
            if acc is None:
                return jnp.where(m == rows[1:2, :], 1.0, 0.0).astype(
                    jnp.bfloat16)
            return acc + jnp.where(m == rows[1:2, :], rows[2:3, :], 0.0)

        if class_lanes:
            # THE CHAIN (module docstring). Lane `at_hand` of `act` is this
            # sub-tree's activity, lane `at_hand` + i that of the tree's
            # sub-tree i further on; an exit's row of the leaves' table adds
            # the leaf's pieces to the class lanes or 1 to the linked
            # sub-tree's lane.
            leaves_ref, _, act_ref = rest
            act_lanes = act_ref.shape[1]
            shared = leaves_ref.shape[2] == class_lanes
            act = act_ref[pl.ds(r0, sub_rows), :]
            acc = jnp.zeros((sub_rows, class_lanes), jnp.float32)
            first = jax.lax.broadcasted_iota(
                jnp.int32, (1, act_lanes), 1) == at_hand
            for g in range(n_trees):
                e = tree(g, None)
                y = jax.lax.dot_general(
                    e, leaves_ref[g], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [S, E]
                # (row 4 says it in every lane; W may be fewer than A)
                roots = jnp.concatenate(
                    [planes_ref[g, 4:5, :_LANES]] * (act_lanes // _LANES),
                    axis=1) > 0.0
                act = jnp.where(roots, jnp.where(first, 1.0, 0.0), act)
                a = act[:, at_hand:at_hand + 1]
                if shared:
                    # ONE tile: the pieces and the links take the same
                    # multiply and the same two adds. `acc`'s link lanes
                    # and `act`'s class lanes hold sums nobody reads; what
                    # the shift wraps round from lane 0 to lane 127 is
                    # cleared by the tree's root before it is back at lane
                    # `at_hand` (models/tree.exit_table_lanes: THE RULE).
                    ay = a * y
                    acc = acc + ay
                    act = pltpu.roll(act, act_lanes - 1, 1) + ay
                else:
                    acc = acc + a * y[:, :class_lanes]
                    act = pltpu.roll(act, act_lanes - 1, 1) \
                        + a * y[:, class_lanes:]
            act_ref[pl.ds(r0, sub_rows), :] = act
            out_ref[pl.ds(r0, sub_rows), :] += acc
            return carry

        # Unrolled: the compiler runs a tree's matmuls under the one
        # before's compares (a fori_loop here took 35% longer).
        acc = jnp.zeros((sub_rows, lanes), jnp.float32)
        for g in range(n_trees):
            acc = tree(g, acc)
        # The lanes summed with the rows on the lanes: fold to one vreg
        # column, turn it over, add down the sublanes.
        fold = acc[:, :_LANES]
        for l0 in range(_LANES, lanes, _LANES):
            fold = fold + acc[:, l0:l0 + _LANES]
        out_ref[:, pl.ds(r0, sub_rows)] += jnp.sum(fold.T, axis=0,
                                                   keepdims=True)
        return carry

    jax.lax.fori_loop(0, tile_rows // sub_rows, sub_tile, 0)


def predict_paths_pallas(
    sel: jax.Array,            # bf16 [T, Fp, W], or pack_select's
    planes: jax.Array,         # f32 [T, 8, W]      (then its planes too)
    paths: jax.Array,          # bf16 [T, W, W]; halved [S, W/2, W]
    Xc: jax.Array,             # [R, F] integer bins, uint8 as api.predict's
    *,
    learning_rate,
    base,
    missing_routes: bool = False,
    interpret: bool | None = None,
    leaves: jax.Array | None = None,   # bf16 [S, W, E]: the sub-tree form
    chain: Chain | None = None,        # ... and its shape
    mean: bool = False,
    cat: tuple | None = None,          # (cat_expand, cat_bins): CATEGORY SETS
    sets: CatSets | None = None,       # ... and their shape (its spans)
) -> jax.Array:
    """Raw margins [R]: Pallas twin of ops/predict._predict_paths. Jit-safe.
    interpret=None auto-selects the Pallas interpreter off-TPU. Where the
    select answers two nodes a lane (`select_nodes_per_lane`) a backend
    hands over `pack_select`'s tables, made once a model; the tables as
    the model compiles them are packed here, by every call's program.
    With `leaves` and `chain` the tables' entries are SUB-TREES and the
    answer is the sum over the trees of the reached leaves' vectors divided
    by the trees, float32 [R, C] (`mean`: vector leaves), or of scalar
    leaves the margin [R] as ever, or (`chain.leaf_columns` C > 1 without
    `mean`: softmax's round-major trees, a tree's leaves in its class's
    lanes alone) the margins [R, C]. `cat`: the model carries CATEGORY
    SETS (module docstring), `sel` as the model compiles it; `sets` says
    how its K-blocks lie and which of them each lane tile reads (models/
    tree.CompiledNodeList.select_spans and cat_ordinal_at; None: as the
    tables' shapes say, the ordinal rows first and every tile every
    block)."""
    if interpret is None:
        interpret = device.platform() != "tpu"
    T, _, lanes = planes.shape
    R, F = Xc.shape
    if cat and sets is None:
        sets = CatSets(cat[0].shape[0], sel.shape[1])
    if not cat and select_nodes_per_lane(F, lanes) == 2 \
            and sel.shape[2] == lanes:
        with traced_scope("predict:tables"):
            sel, planes = pack_select(sel, planes, F)
    fp, sel_lanes = sel.shape[1:]
    # The rows as the kernel takes them: uint8 and int32 as they come, any
    # other integer cast in XLA first (the heap kernel's rule).
    row_dtype = row_operand_dtype(Xc.dtype)
    with traced_scope("predict:widen"):
        rows = Xc if Xc.dtype == row_dtype else Xc.astype(row_dtype)
    plan = path_plan(T, lanes, F, row_dtype=row_dtype, chain=chain, cat=sets)
    if not _plan_fits(plan):    # (as `predict_paths_fits`: no block)
        if not interpret:
            raise ValueError(
                f"path-matrix shape ({lanes} lanes a tree, F={F}) exceeds "
                "the Pallas VMEM budget; use the jax.numpy form")
        # Interpreted past the budget: one block of every tree.
        plan = plan._replace(trees_per_step=T, table_blocks=1)
    g, n_blocks = plan.trees_per_step, plan.table_blocks
    # Trees that fill the last block: no node, and no leaf of any length
    # (-1), so they add 0. A backend hands the tables over whole blocks
    # long already (`scoring_program`'s fill: padded once a model, on
    # the host), and these pads are of no tree: no instruction.
    t_fill = ((0, n_blocks * g - T), (0, 0), (0, 0))
    with traced_scope("predict:tables"):
        sel_b, paths_b = jnp.pad(sel, t_fill), jnp.pad(paths, t_fill)
        planes_b = jnp.pad(planes, t_fill, constant_values=-1.0)
        tables = (sel_b, planes_b, paths_b) + (
            (jnp.pad(leaves, t_fill),) if chain else ()) + (cat or ())
    sub_rows = _sub_rows(plan.select_nodes_per_lane)
    tile_rows = min(TILE_ROWS, -(-R // sub_rows) * sub_rows)
    n_tiles = -(-R // tile_rows)

    def table_block(rows, cols):
        return pl.BlockSpec((g, rows, cols), lambda i, b: (b, 0, 0),
                            memory_space=pltpu.VMEM)

    def whole(a):       # a small table of the model: fetched once, stays
        return pl.BlockSpec(a.shape, lambda i, b: (0, 0, 0),
                            memory_space=pltpu.VMEM)

    exit_lanes = chain.exit_lanes if chain else 0
    resolve_rows = paths.shape[1]
    spans = sets.spans if sets else (
        chain.select_spans if chain and sel_lanes == lanes else ())
    # the select's weights the MXU is asked for: K rows x lanes, under
    # spans a lane tile's own K-blocks' rows
    k_rows = _cat_block_rows(F, sets) if sets else [
        min(k0 + _LANES, fp) - k0 for k0 in range(0, fp, _LANES)]
    select = fp * sel_lanes if not spans else sum(
        sum(k_rows[start:stop]) * _LANES for start, stop in spans)
    cost = pl.CostEstimate(
        flops=2 * n_tiles * tile_rows * n_blocks * g * (
            select + resolve_rows * lanes + lanes * exit_lanes),
        bytes_accessed=n_tiles * (
            tile_rows * (F * row_dtype.itemsize
                         + 4 * (chain.class_lanes if chain else 1))
            + plan.table_bytes),
        transcendentals=0,
    )
    # The scores' block, resident over the block axis: the rows on the
    # lanes, or (the sub-tree form) on the sublanes with the class lanes'
    # three pieces beside them and the activity in scratch.
    if chain:
        out_block, out_shape = (tile_rows, chain.class_lanes), (
            R, chain.class_lanes)
        out_index = lambda i, b: (i, 0)     # noqa: E731
    else:
        out_block, out_shape = (1, tile_rows), (1, R)
        out_index = lambda i, b: (0, i)     # noqa: E731
    with traced_scope("predict:traverse_paths"):
        acc = pl.pallas_call(
            functools.partial(_paths_kernel, n_trees=g, n_feat=F,
                              missing_routes=missing_routes,
                              class_lanes=chain.class_lanes if chain else 0,
                              select_spans=spans,
                              at_hand=chain.at_hand if chain else 0,
                              **({"cat_blocks": sets.blocks,
                                  "cat_ordinal_at": sets.ordinal_at}
                                 if cat else {})),
            # The grid walks the UNPADDED rows: the last tile's blocks are
            # ragged, as in the heap kernel.
            grid=(n_tiles, n_blocks),
            in_specs=[pl.BlockSpec((tile_rows, F), lambda i, b: (i, 0),
                                   memory_space=pltpu.VMEM),
                      table_block(fp, sel_lanes), table_block(8, lanes),
                      table_block(resolve_rows, lanes)]
            + [table_block(lanes, exit_lanes)] * bool(chain)
            + [whole(a) for a in cat or ()],
            out_specs=pl.BlockSpec(out_block, out_index,
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
            scratch_shapes=[pltpu.VMEM((tile_rows, chain.act_lanes),
                                       jnp.float32)] if chain else [],
            cost_estimate=cost,
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        )(rows, *tables)
    if chain:
        return fold_leaf_pieces(acc, chain, learning_rate, base, mean)
    with traced_scope("predict:accumulate"):
        return base + learning_rate * acc[0]


def fold_leaf_pieces(acc, chain: Chain, learning_rate, base, mean: bool):
    """[R, CL] sums of the leaf values' bfloat16 pieces, piece p's column c
    in lane p C + c, to the model's answer float32 [R, C]: the pieces added
    smallest first, then the mean over the trees or the margin's scale and
    shift (the sum, no division: [R] of one column, [R, C] of softmax's
    classes)."""
    c = chain.leaf_columns
    with traced_scope("predict:accumulate"):
        total = acc[:, (_LEAF_PIECES - 1) * c:_LEAF_PIECES * c]
        for p in range(_LEAF_PIECES - 2, -1, -1):
            total = total + acc[:, p * c:(p + 1) * c]
        if mean:
            return total / jnp.float32(chain.n_trees)
        margins = base + learning_rate * total
        return margins[:, 0] if c == 1 else margins


# ---- the NODE LIST's entry (ops/predict.LAYOUTS) ----

def _plan_fits(plan: PathPlan) -> bool:
    """`predict_paths_fits`, read off the plan the dispatcher has made for
    its grid: a block of it holds a tree at all."""
    return plan.trees_per_step > 0


def kernel_serves(use_pallas, lanes: int, n_features: int,
                  row_dtype=jnp.uint8, chain: Chain | None = None,
                  cat: CatSets | None = None) -> bool:
    """The node list's kernel-or-twin rule: ops/predict.resolve_use_pallas
    over this kernel's own budget predicate, for trees (sub-trees, with
    `chain`) of `lanes` lanes; `cat`: the model carries category sets. The
    tree count is no term of it."""
    from ddt_tpu.ops.predict import resolve_use_pallas

    return resolve_use_pallas(use_pallas, True, lambda: predict_paths_fits(
        lanes, n_features, row_dtype, chain, cat))


def scoring_program(ce, n_features: int, row_dtype, predict_impl: str,
                    link: bool):
    """The node list's entry (ops/predict.layout_entry): the program of a
    models/tree.CompiledNodeList, its path tables as they go up and
    ops/predict.predict_raw_effective_paths over them: the Pallas kernel
    where `kernel_serves` takes it, asked here and bound as a bool, else
    the jax.numpy form. `link`: the program ends in the model's link
    function (softmax's round-major trees). The quantized tiers have no
    node-list form: the f32 program serves them."""
    import numpy as np

    from ddt_tpu.ops import predict as predict_ops

    entry = predict_ops.predict_raw_effective_paths
    missing_routes = ce.missing_bin_value >= 0
    # The sub-tree form: the tables' entries are sub-trees, a fourth table
    # says what their exits are, and vector leaves (or softmax's
    # round-major trees) answer [rows, C].
    classes = ce.leaf_columns
    chain = chain_of(ce.n_trees, classes, ce.leaves.shape[2],
                     ce.select_spans, ce.paths.shape) if ce.chained else None
    # CATEGORY SETS (module docstring): the one-hot's K-blocks
    cat = CatSets(ce.cat_blocks, ce.sel.shape[1], ce.select_spans,
                  ce.cat_ordinal_at) if ce.cat_blocks else None
    served = kernel_serves(predict_ops.USE_PALLAS[predict_impl], ce.lanes,
                           n_features, row_dtype, chain, cat)
    entries = max(ce.n_subtrees, 1)
    plan = path_plan(
        ce.n_subtrees or ce.n_trees, ce.lanes, n_features, ce.deepest_leaf,
        served=served, missing_routes=missing_routes, row_dtype=row_dtype,
        chain=chain, widest_tree=ce.widest_tree, cat=cat)._replace(
            category_nodes=ce.category_nodes,
            category_set_bits_max=ce.category_set_bits_max,
            subtrees_per_tree_max=ce.subtrees_max,
            single_subtree_trees=ce.single_subtree_trees,
            link=ce.loss if link else "none",
            spine_copies_per_subtree=round(ce.spine_copies / entries, 2),
            pieces_per_subtree=round((ce.pieces or 1) / entries, 2),
            glue_copies_per_subtree=round(ce.glue_copies / entries, 2))
    # What every chunk's program would otherwise make of the tables is
    # made here, once a model: the select that answers two nodes a lane
    # with its shifted thresholds (`pack_select`), and the trees that fill
    # the kernel's last block (no node, no leaf of any length: they add 0).
    tables = ce.arrays()
    if plan.select_nodes_per_lane == 2:
        tables = (*pack_select(ce.sel, ce.planes, n_features, xp=np),
                  *tables[2:])
    short = max(0, plan.trees_per_step * plan.table_blocks - len(ce.sel))
    # (the two small tables of category sets are the model's, not a
    # tree's: they go up as they are)
    per_tree = 4 if chain else 3

    def fill(tables):
        """The fill goes on inside the put, a table at a time: a padded
        copy is gone when its transfer is."""
        pad = ((0, short), (0, 0), (0, 0))
        for i, (a, v) in enumerate(zip(tables, (0, -1.0, 0, 0, 0))):
            yield np.pad(a, pad, constant_values=v) \
                if short and i < per_tree else a

    # Bound here: fn0 outlives the build in the stage registry, and must
    # not hold the host copy of the path tables.
    static = dict(learning_rate=ce.learning_rate, base=ce.base_score,
                  use_pallas=served, missing_routes=missing_routes)
    if chain:
        static.update(n_trees=ce.n_trees, leaf_columns=classes,
                      mean=ce.mean, select_spans=chain.select_spans,
                      **({"link": plan.link} if link else {}))
    elif cat and cat.spans:
        static.update(select_spans=cat.spans, cat_ordinal_at=cat.ordinal_at)

    # (three functions: a program's HLO names its parameters)
    def fn0(sel, planes, paths, Xc, entry=entry):
        return entry(sel, planes, paths, Xc, **static)

    def fn0_chain(sel, planes, paths, leaves, Xc, entry=entry):
        return entry(sel, planes, paths, Xc, leaves=leaves, **static)

    def fn0_sets(sel, planes, paths, cat_expand, cat_bins, Xc, entry=entry):
        return entry(sel, planes, paths, Xc, cat_expand=cat_expand,
                     cat_bins=cat_bins, **static)

    # an averaged forest answers [rows, C] whatever C, one column too
    return predict_ops.ScoringProgram(
        plan, tables, fn0_chain if chain else fn0_sets if cat else fn0,
        entry, 2 if ce.mean or classes > 1 else 1, classes, fill=fill)
