"""Pallas TPU kernel for the OBLIVIOUS form of batch scoring: ensembles of
symmetric trees (models/tree.ObliviousEnsemble, CatBoost's own), binned
data.

A tree of depth D is D splits and 2^D leaf values, and a row's leaf is the
D-bit number of the splits' answers (ops/predict.py has the equations). The
heap kernel would trace 2^D - 1 node planes for the D splits there are, and
the path-matrix kernel a select of every one of those nodes; this kernel
serves the layout as it is. The trees go in GROUPS of 128, a tree a lane,
and a group's split d is one lane tile, so per group and tile of rows

    v_d  = x @ sel_d        [rows, 128]  the bin of split d's feature,
                                         d = 0 .. D-1: D lane tiles
    b_d  = v_d > thr_d                   the row's bit d, tree by lane
    leaf = mux(b_0 .. b_{D-1}, L)        L [2^D, 128]: the tree's leaf
                                         values down its lane; 2^D - 1
                                         selects on the VPU, bit 0 first
    acc += leaf                          float32, never bfloat16

VECTOR LEAVES (CatBoost's `MultiClass`: C values a leaf, PR 57) change the
last two lines alone: the select leaves ONE index a (row, tree) whatever C,
and the resolve takes it against class c's leaf rows for each c, L_c [2^D,
128]: rows c 2^D .. (c + 1) 2^D of the group's table, into row c of a
resident [C, TILE_ROWS] output (the HBM result `f32[C, R]`, class-major, the
heap kernel's interface). Where a class's leaves fill a vreg (`_gathered`:
C > 1 and D >= 3) the lookup is a SUBLANE GATHER (PR 58): leaf 8 h + s of
the class is sublane s of vreg h of L_c as HBM holds it, the index never
crosses lanes, and a v5e permutes a vreg along its sublanes by a per-element
index (`jnp.take_along_axis(.., axis=0)` on [8, 128] operands, Mosaic's
`tpu.dynamic_gather`, the VALU's `vperm.slane`, four a bundle like a
select), so for a strip of 8 rows, idx its packed indices,

    v_h    = take_along_axis(L_c[8h : 8h + 8], idx & 7, axis=0)
                                         h = 0 .. 2^(D-3) - 1: 2^(D-3)
                                         gathers (`resolve_gathers_per_tree`)
    leaf_c = mux(bits 3 .. D-1 of idx, v_0 .. v_{2^(D-3) - 1})
                                         2^(D-3) - 1 selects
    acc[c] += leaf_c                     c = 0 .. C-1: C (2^(D-2) - 1) VALU
                                         operations a (row, tree), gathers
                                         and selects alike
                                         (`resolve_selects_per_tree`)

the float32 values the multiplexer would pick, summed in its order: the
scores are its bits. At depth 6 and 7 classes that is 56 gathers and 49
selects, 105 operations where C multiplexers of all D bits took C (2^D - 1)
= 441 (and take them still for vector leaves under depth 3, fewer than a
vreg's 8 leaves: `leaf_c = mux(b_0 .. b_{D-1}, L_c)`); one column keeps its
multiplexer of 63 and traces the program it did before there were vector
leaves. At few columns the select is small (54 columns: ONE K-block, 6
weight tiles a group) and the C-fold resolve on the VPU sets the pace. Such
a step is not pipelined where C (2^D - 1) > 255 (`_pipelined`, a rule about
the multiplexer's trace that the gather left where it was; there is little
select to hide under): it packs a sub-tile's indices into one plane and
resolves it in blocks of 128 rows, a rolled loop: 627 bundles a block by
the gather (3.0 VALU operations a bundle) where the multiplexer took 2,348
(3.0 selects a bundle; whole [1024, 128] planes reached 2.2): a sub-tile
and group of 6,622 bundles where 20,867 (compile checks, PRs 57 and 58).

So the MXU is asked for D x ceil(F/128) weight tiles a group and 256 rows
(`oblivious_mxu_tiles_per_tree` = that over 128: 0.75 at depth 6 and 2000
columns, where the 63-node expansion through the path kernel asks 17) and
the leaf lookup costs no matmul: the bits of a lane are that tree's own,
so the index never crosses lanes. The multiplexer (63 selects a vreg of
rows against 96 weight-tile pushes) is work for the VPU while the MXUs do
the matmuls, but not of ONE sub-tile: its bits are the matmuls' results. So
the kernel is SOFTWARE-PIPELINED (PR 40): a sub-tile's SELECT (widen,
matmuls, compares) leaves the leaf index of every (row, tree) as one int32
plane in a VMEM scratch of two, and its RESOLVE (multiplexer, the plane
turned over, the add into the output) is issued with the select of the NEXT
sub-tile, in one basic block; a step's last sub-tile is resolved by the
next step on the group axis, against a copy of its leaf table, and a row
tile's last group resolves its own: 125 of a row tile's 126 resolves run
beside a select's matmuls (`resolves_under_select`). As PR 39 shipped it
(select and resolve of a sub-tile in one `fori_loop` body), the compiler's
final bundles held 6,259 of a body's 8,391 vector selects in one stretch
of 4,000 bundles with 472 of the 1,000 matmul pushes that keep the four
MXUs busy: 27,156 cycles a sub-tile against the MXU's 24,576.

The select is K-BLOCKED as the path kernel's is: v_d = sum_k x_k @ sel_d,k
over ceil(F/128) blocks of 128 columns (`select_k_blocks`: 16 at 2000
columns), each x_k cut from the row tile as HBM holds it and widened uint8
-> bf16 in VMEM once a sub-tile, for all D splits of the group. One
non-zero a column of sel and bins below 256: every partial product is exact
and so is their sum in any order.

The HBM interface is the other kernels' (ops/predict_pallas.py, PR 36): the
rows go in as the caller holds them (uint8 from api.predict), over a grid
of cdiv(R, tile) row tiles whose last block is ragged, and the scores come
out `f32[C, R]` (`f32[1, R]` of one column), the rows on the lanes.

Layout strategy. A group's tables are D x Fp x 128 bf16 of select (3.07 MB
at depth 6 and 2000 columns), 4 KB of thresholds and C x 2^D x 128 f32 of
leaf values (32 KB a class): 197 MB for 8000 trees. They stream: the grid
is (row tiles,
groups), one step holds ONE group's tables (Mosaic double-buffers the
windows: the next group's DMA runs under this group's matmuls) and walks
the row tile in sub-tiles of `SUB_ROWS`; the [C, TILE_ROWS] output stays
resident over the group axis, zeroed by the first group and added to by
all. A row tile streams the tables once.

Exactness is the form's own: bins below 256 and 0/1 are bfloat16 without
rounding, the MXU accumulates in float32 and every partial sum is a bin.
The leaf reached is the bit walk's for every (row, tree); scores agree with
ops/predict._predict_oblivious to the float32 rounding of a sum in another
order (equal on dyadic leaf values). Interpret mode auto-selects off-TPU,
as in predict_pallas.py; the dispatch rule is `kernel_serves`, below.
"""

from __future__ import annotations

import functools
import math
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddt_tpu.ops.predict_pallas import _window_bytes, row_operand_dtype
from ddt_tpu.ops.predict_paths import _lane_pad, select_k_blocks
from ddt_tpu.telemetry.annotations import traced_scope
from ddt_tpu.utils import device

# Rows a grid step holds (one walk of the tables), and rows a sub-tile: the
# [SUB_ROWS, 128] float32 planes v_d and the multiplexer's are what the VPU
# touches. Read on the v5e by a probe of the kernel alone (one
# device-resident chunk of 131,072 rows x 2000 columns, 8000 trees of depth
# 6, ms a call; PERF.md section 6, PR 39): tile 2048 with sub-tiles of 256
# / 512 / 1024 rows 152.4 / 149.4 / **147.9**; tile 4096 with 512: 149.0.
# The MXU's own time is 133.1 (96 weight tiles x 64 cycles a group and 256
# rows at 1.5 GHz): a weight tile serves a sub-tile's rows once loaded, so
# longer sub-tiles load fewer. The row tile widened ONCE into a bf16
# scratch by its first group (and not by each of the 63): 148.9 at 512,
# 146.8 at 1024, not worth its 8 MB of VMEM. Those are PR 39's readings,
# select and resolve of a sub-tile in one loop body; pipelined (PERF.md
# section 6, PR 40) the shipped form reads **142.1** where that one read
# 147.9 in the same call (a resolve's strips of 8 rows each tied to a
# matmul of the select it runs beside; a block of 128 rows to one: 144.5).
TILE_ROWS = 2048
SUB_ROWS = 1024
_LANES = 128
# Trees a group (models/tree.OBLIVIOUS_GROUP): a tree a lane.
GROUP = _LANES
# Scoped VMEM the kernel asks of Mosaic (the default is 16 MiB of the
# v5e's 128), and what of it `oblivious_plan` fills: the rest is the
# compiler's.
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
_VMEM_BUDGET_BYTES = _VMEM_LIMIT_BYTES - 8 * 1024 * 1024
# The longest resolve the kernel traces, in selects a (row, tree) of C
# multiplexers of 2^D - 1, `_mux_selects` (one column: depth 10; 7 classes:
# depth 7).
_MAX_SELECTS = 1023
# Bytes a sub-tile's row keeps beside the windows: a widened bin of its
# K-blocks (the bf16 copy the group's splits share and the float32 it is
# made from), and a lane's float32 planes (`_vmem_bytes`).
_SUB_ROW_BIN_BYTES = 6
# The longest resolve that is unrolled beside a select (`_pipelined`): one
# column's of depth 8.
_PIPELINED_SELECTS = 255
# Rows of a resolve's block that depend on ONE matmul of the select they are
# issued with (`_resolve_later`): a vreg of float32. A vreg's 8 sublanes are
# also the 8 leaves ONE sublane gather reaches (`_gathered`): the low three
# bits of the index.
_TIE_ROWS = 8
_GATHER_BITS = _TIE_ROWS.bit_length() - 1


def oblivious_mxu_tiles_per_tree(depth: int, n_features: int) -> float:
    """MXU weight tiles (results [rows, 128]) a TREE costs a tile of rows:
    a group's D lane tiles x ceil(F/128) K-blocks, over its 128 trees."""
    return round(depth * select_k_blocks(n_features) / GROUP, 4)


def _gathered(depth: int, n_cls: int) -> bool:
    """Whether the resolve looks a leaf up by a SUBLANE GATHER (the module's
    VECTOR LEAVES): vector leaves of at least a vreg's 8 leaves a class.
    THE RULE, a function of what the kernel sees in its tables; one column
    keeps the multiplexer and the program it traced (PERF.md section 7)."""
    return n_cls > 1 and depth >= _GATHER_BITS


def _mux_selects(depth: int, n_cls: int = 1) -> int:
    """Vector selects of C multiplexers of 2^D - 1 over the one index: what
    `_pipelined` and `predict_oblivious_fits` are rules about."""
    return n_cls * ((1 << depth) - 1)


def resolve_gathers(depth: int, n_cls: int = 1) -> int:
    """Sublane gathers the resolve costs a (row, tree): a class's 2^D leaves
    are 2^(D-3) vregs, one gather each; 0 where the multiplexer serves."""
    return n_cls << (depth - _GATHER_BITS) if _gathered(depth, n_cls) else 0


def resolve_selects(depth: int, n_cls: int = 1) -> int:
    """VALU operations the leaf lookup costs a (row, tree), gathers and
    selects alike: a multiplexer of 2^D - 1 a leaf column over the one
    index, or (`_gathered`) 2^(D-3) gathers and the 2^(D-3) - 1 selects
    among their results."""
    gathers = resolve_gathers(depth, n_cls)
    return 2 * gathers - n_cls if gathers else _mux_selects(depth, n_cls)


def _group_bytes(depth: int, n_features: int, n_cls: int = 1) -> int:
    """HBM bytes of one group's tables: sel bf16, thr and leaf f32."""
    fp = -(-n_features // 16) * 16
    return (depth * fp * GROUP * 2 + -(-depth // 8) * 8 * GROUP * 4
            + n_cls * (GROUP << depth) * 4)


class ObliviousPlan(typing.NamedTuple):
    """How an oblivious ensemble's tables meet the kernel
    (`oblivious_plan`); the PathPlan interface."""

    oblivious: int             # 1: this form serves the layout
    depth: int
    select_columns_per_tree: int   # D served natively, never 2^D - 1
    trees_per_lane_tile: float     # 128 trees over a group's D lane tiles
    select_k_blocks: int
    oblivious_mxu_tiles_per_tree: float
    trees_per_step: int        # a group; 0 = the jax.numpy form scores
    table_blocks: int          # groups a row tile walks
    table_bytes: int           # HBM bytes of all the groups, read once
    tile_rows: int
    row_operand_bytes: int = 1
    # Of a row tile's resolves (sub-tiles x groups), the share issued beside
    # a later select's matmuls: all but the last where the step is
    # pipelined, else 0.
    resolves_under_select: float = 0.0
    # Vector leaves: the columns C a leaf holds (the answer is [rows, C]),
    # the link the program ends in ("softmax": class probabilities, taken
    # on the device) and the VALU operations the lookup costs a (row, tree)
    # (`resolve_selects`), of which so many sublane gathers (PR 58; 0: the
    # multiplexer serves).
    leaf_columns: int = 1
    link: str = "none"
    resolve_selects_per_tree: int = 0
    resolve_gathers_per_tree: int = 0

    @property
    def blocks(self) -> int:
        """As TablePlan.blocks: past 1 a row tile streams `table_bytes`."""
        return self.table_blocks

    def step_rows(self, rows: int) -> int:
        """As TablePlan.step_rows: this form's row tile whatever the
        program's rows (a shorter program is ONE tile either way)."""
        return self.tile_rows

    def span_counts(self) -> dict:
        return {k: getattr(self, k) for k in SPAN_COUNTS}

    def root_counts(self) -> dict:
        return {"routing_tables": 0, "oblivious": self.oblivious,
                "select_columns_per_tree": self.select_columns_per_tree,
                "select_k_blocks": self.select_k_blocks,
                "leaf_columns": self.leaf_columns, "link": self.link,
                "resolve_selects_per_tree": self.resolve_selects_per_tree,
                "resolve_gathers_per_tree": self.resolve_gathers_per_tree}


# What the `ddt:predict:ensemble` span says of an oblivious model's plan, in
# the order it prints (docs/OBSERVABILITY.md); `cli predict` repeats all
# but `table_bytes` in `phases_ms`, as it does for the other kernels'.
SPAN_COUNTS = ("oblivious", "depth", "select_columns_per_tree",
               "trees_per_lane_tile", "select_k_blocks",
               "oblivious_mxu_tiles_per_tree", "trees_per_step",
               "table_blocks", "table_bytes", "row_operand_bytes",
               "leaf_columns", "link", "resolve_selects_per_tree",
               "resolve_gathers_per_tree", "resolves_under_select")
PHASES_COUNTS = tuple(k for k in SPAN_COUNTS if k != "table_bytes")


def _pipelined(depth: int, n_sub: int, n_cls: int = 1) -> bool:
    """Whether a step resolves a sub-tile beside the next one's select. It
    needs a next one; and its two unrolled resolves are 2 x C (2^D - 1) x
    128 vector selects in one basic block, past 2 x 255 x 128 of which the
    compiler's scheduler gives the order up (depth 10 at 28 columns:
    244,687 bundles a step where the rolled form's two sub-tiles take
    90,964; depth 9: 51,673 against 50,638, nothing gained; compile check,
    PR 40), where the select is a small part of the step anyway. One
    column: up to depth 8; 7 classes: up to depth 5."""
    return n_sub > 1 and _mux_selects(depth, n_cls) <= _PIPELINED_SELECTS


def _scratch_shapes(depth: int, n_sub: int, n_cls: int = 1) -> list:
    """The pipeline's VMEM scratch: the leaf indices of the sub-tile being
    selected and of the one being resolved, an int32 plane each (a
    sub-tile's D bits in 0.5 MB, where its D bit planes would be 3-5), and
    the group before's leaf table (32 KB a class at depth 6). A step of
    VECTOR leaves that is not pipelined keeps ONE plane of indices: its
    resolve walks the sub-tile in blocks of 128 rows (`_oblivious_kernel`);
    a one-column step past depth 8 keeps none, as before."""
    if not _pipelined(depth, n_sub, n_cls):
        return [pltpu.VMEM((1, SUB_ROWS, GROUP), jnp.int32)] * (n_cls > 1)
    return [pltpu.VMEM((2, SUB_ROWS, GROUP), jnp.int32),
            pltpu.VMEM((n_cls << depth, GROUP), jnp.float32)]


def _vmem_bytes(depth: int, n_features: int, row_bytes: int,
                n_cls: int = 1) -> int:
    """VMEM a grid step takes: the group's double-buffered table windows
    (the leaf table's C times one column's), the row tile's two at the
    rows' own width, the [C, TILE_ROWS] output's, a sub-tile's widened
    K-blocks and its float32 planes: v_d and b_d of every split and the
    multiplexer's stack, D deep (3 D + 2; the C multiplexers run one after
    another over the same bits); where a block of 128 rows is resolved at
    a time (the pipelined step, and every step of vector leaves), three
    planes fewer and the scratch: never more than the other form's, so no
    shape lost the kernel to the pipeline."""
    fp = -(-n_features // 16) * 16
    tables = (depth * _window_bytes(fp, GROUP) // 2     # bf16: half of f32
              + _window_bytes(depth, GROUP)
              + _window_bytes(n_cls << depth, GROUP))
    rows = (2 * TILE_ROWS * _lane_pad(n_features) * row_bytes
            + _window_bytes(n_cls, TILE_ROWS))
    scratch = sum(4 * math.prod(s.shape) for s in _scratch_shapes(
        depth, TILE_ROWS // SUB_ROWS, n_cls))
    planes = 3 * depth + 2 - (3 if scratch else 0)
    sub = SUB_ROWS * (_lane_pad(n_features) * _SUB_ROW_BIN_BYTES
                      + GROUP * 4 * planes)
    return tables + rows + sub + scratch


def oblivious_plan(n_trees: int, depth: int, n_features: int,
                   served: bool = True, row_dtype=jnp.uint8,
                   n_cls: int = 1, link: str = "none") -> ObliviousPlan:
    """The kernel's table blocks at this shape: one group of 128 trees a
    step. `served` False: the plan of a model the jax.numpy form scores
    (its depth and select, no blocks). `n_cls`: the columns of a leaf;
    `link`: what the program ends in."""
    row_bytes = row_operand_dtype(row_dtype).itemsize
    said = (1, depth, depth, round(GROUP / depth, 2),
            select_k_blocks(n_features),
            oblivious_mxu_tiles_per_tree(depth, n_features))
    leaves = dict(leaf_columns=n_cls, link=link,
                  resolve_selects_per_tree=resolve_selects(depth, n_cls),
                  resolve_gathers_per_tree=resolve_gathers(depth, n_cls))
    if not served:
        return ObliviousPlan(*said, 0, 0, 0, 0, row_bytes, **leaves)
    groups = max(1, -(-n_trees // GROUP))
    n_sub = TILE_ROWS // SUB_ROWS
    resolves = n_sub * groups
    return ObliviousPlan(*said, GROUP, groups,
                         groups * _group_bytes(depth, n_features, n_cls),
                         TILE_ROWS, row_bytes,
                         round((resolves - 1) / resolves, 4)
                         if _pipelined(depth, n_sub, n_cls) else 0.0,
                         **leaves)


def predict_oblivious_fits(depth: int, n_features: int,
                           row_dtype=jnp.uint8, n_cls: int = 1) -> bool:
    """Whether one group's tables fit the kernel's VMEM budget beside a row
    tile, and its C multiplexers the trace: the guard behind
    use_pallas=None (`kernel_serves`, this layout's rule). The tree count
    is no term of it."""
    # (the estimate is `_fits`, below the kernel, where the dispatcher's
    # refusal reads it too; this name is the rule's question)
    return _fits(depth, n_features, row_dtype, n_cls)


def _mux(bits: list, leaves: list, d: int, base: int):
    """The leaf value every (row, lane) reaches among leaves base ..
    base + 2^(d+1) - 1, by bits 0 .. d: depth-first, so at most d + 1
    values are alive."""
    if d < 0:
        return leaves[base]
    return jax.lax.select(bits[d],
                          _mux(bits, leaves, d - 1, base + (1 << d)),
                          _mux(bits, leaves, d - 1, base))


def _leaves(leaf_rows, rows: int) -> list:
    """The group's leaf table `leaf_rows [C 2^D, 128]`, a leaf (of a
    class) a plane of `rows` rows: the multiplexers' operands."""
    return [jnp.broadcast_to(leaf_rows[i:i + 1, :], (rows, _LANES))
            for i in range(leaf_rows.shape[0])]


def _into_output(leaf, out_ref, c: int, r0):
    """Class c's leaf values `leaf [rows, 128]` of a block of rows from r0,
    summed over the group's trees into row c of the output: the lanes
    summed with the rows on the lanes, so turn the block over and add down
    the sublanes."""
    out_ref[c:c + 1, pl.ds(r0, leaf.shape[0])] += jnp.sum(leaf.T, axis=0,
                                                          keepdims=True)


def _resolve(bits: list, leaves: list, out_ref, r0):
    """The RESOLVE of a block of rows whose D bit planes are `bits`: for
    each leaf column c the multiplexer over the group's `leaves` of that
    class (2^D of the C 2^D), the block turned over and added into row c
    of the output, the rows from r0."""
    per_class = 1 << len(bits)
    for c in range(len(leaves) // per_class):
        _into_output(_mux(bits, leaves, len(bits) - 1, c * per_class),
                     out_ref, c, r0)


def _gathered_leaves(idx, leaf_rows, depth: int):
    """The leaf lookup where `_gathered` says so: each class's leaf values
    `[rows, 128]` of the leaf indices `idx [rows, 128]`, class by class.
    Class c's 2^D leaves are 2^(D-3) vregs of `leaf_rows` (row c 2^D + leaf:
    leaf 8 h + s is sublane s of vreg h); a strip of 8 rows takes the
    sublane `idx & 7` of each (`vperm.slane`: the index never crosses lanes)
    and the multiplexer of bits 3 .. D-1 picks among the 2^(D-3) results:
    the float32 values the multiplexer of all D bits would pick."""
    n_vregs = 1 << (depth - _GATHER_BITS)
    strips = [jax.lax.slice_in_dim(idx, s0, s0 + _TIE_ROWS)
              for s0 in range(0, idx.shape[0], _TIE_ROWS)]
    low = [s & (_TIE_ROWS - 1) for s in strips]
    high = [[(s & (_TIE_ROWS << d)) != 0
             for d in range(depth - _GATHER_BITS)] for s in strips]
    for v0 in range(0, leaf_rows.shape[0], n_vregs * _TIE_ROWS):
        vregs = [leaf_rows[v0 + h * _TIE_ROWS:v0 + (h + 1) * _TIE_ROWS, :]
                 for h in range(n_vregs)]
        yield jnp.concatenate([
            _mux(bits, [jnp.take_along_axis(v, lo, axis=0,
                                            mode="promise_in_bounds")
                        for v in vregs], len(bits) - 1, 0)
            for lo, bits in zip(low, high)], axis=0)


def _resolve_later(idx_ref, slot: int, leaf_rows, out_ref, r0: int,
                   depth: int):
    """The resolve of the sub-tile whose leaf indices `idx_ref[slot]`
    holds (its first row the row tile's r0), as a function of a BLOCK of
    128 of its rows: `block(b0, after)`, the sub-tile's rows from b0;
    `after`: results of the matmuls over the sub-tile being SELECTED
    meanwhile. A strip of 8 rows of the block is made to depend on one of
    them (its indices are replaced where the result is NaN, and a sum of
    bins is none), because the compiler's critical-path scheduler moves a
    resolve that depends on nothing of the select into one stretch between
    two selects, where the MXUs wait for it (PERF.md section 6, PR 40):
    tied to the matmuls, the strips' selects are issued in the slots the
    next matmuls leave empty. The block's leaves are looked up by sublane
    gathers or by the multiplexer, as `_gathered` says of the table."""
    shape = (_LANES, _LANES)
    if _gathered(depth, leaf_rows.shape[0] >> depth):
        def lookup(idx, b0):
            for c, leaf in enumerate(_gathered_leaves(idx, leaf_rows, depth)):
                _into_output(leaf, out_ref, c, r0 + b0)
    else:
        leaves = _leaves(leaf_rows, _LANES)
        masks = [jnp.full(shape, 1 << d, jnp.int32) for d in range(depth)]
        zero = jnp.zeros(shape, jnp.int32)

        def lookup(idx, b0):
            # (the bits before `r0 + b0`: the order of the one-column trace,
            # whose Mosaic digests the compile check holds to the parent's)
            bits = [jax.lax.ne(jax.lax.bitwise_and(idx, m), zero)
                    for m in masks]
            _resolve(bits, leaves, out_ref, r0 + b0)
    zero_strip = jnp.zeros((_TIE_ROWS, _LANES), jnp.int32)

    def block(b0, after=()):
        idx = idx_ref[slot, pl.ds(b0, _LANES), :]
        if after:
            # (lax, not jnp: a step traces 2,000 of these)
            strips = []
            for i, s0 in enumerate(range(0, _LANES, _TIE_ROWS)):
                tie = jax.lax.slice_in_dim(
                    after[i * len(after) * _TIE_ROWS // _LANES],
                    b0 + s0, b0 + s0 + _TIE_ROWS)
                strips.append(jax.lax.select(
                    jax.lax.ne(tie, tie), zero_strip,
                    jax.lax.slice_in_dim(idx, s0, s0 + _TIE_ROWS)))
            idx = jax.lax.concatenate(strips, 0)
        lookup(idx, b0)

    return block


def _select(x_ref, sel_ref, thr_ref, r0, under=None, *, depth: int,
            n_feat: int, sub_rows: int) -> list:
    """The SELECT of the sub-tile at rows r0 against the step's group: the
    D splits' matmuls and compares, the D bit planes of every (row, tree).
    `under(b0, after)`: the resolve of another sub-tile to issue beside the
    matmuls, an even share of its blocks of 128 rows after each."""
    fp = sel_ref.shape[2]
    k_starts = range(0, n_feat, _LANES)
    n_dots, n_blocks = depth * len(k_starts), sub_rows // _LANES
    # The sub-tile's K-blocks, widened once for the group's D splits: the
    # bf16 copy lives in VMEM alone.
    xs = []
    for k0 in k_starts:
        k1, kp = min(k0 + _LANES, n_feat), min(k0 + _LANES, fp)
        xf = x_ref[pl.ds(r0, sub_rows), k0:k1].astype(
            jnp.int32).astype(jnp.float32)
        if kp > k1:     # K to whole bf16 sublane tiles
            xf = jnp.concatenate(
                [xf, jnp.zeros((sub_rows, kp - k1), jnp.float32)], axis=1)
        xs.append(xf.astype(jnp.bfloat16))            # [S, <= 128]
    bits, dot, recent = [], 0, []
    for d in range(depth):
        # bf16 operands (bins <= 255 and the 0/1 one-hot are exact), f32
        # accumulator: the v5e's VPU has no bf16 compare.
        v = None
        for k0, xk in zip(k_starts, xs):
            part = jax.lax.dot_general(
                xk, sel_ref[0, d, k0:k0 + xk.shape[1], :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # [S, 128]
            v = part if v is None else v + part
            if under is not None:
                recent.append(part)     # the matmuls since the last block
                for b in range(dot * n_blocks // n_dots,
                               (dot + 1) * n_blocks // n_dots):
                    under(b * _LANES, recent)
                    recent = [part]
            dot += 1
        bits.append(v > thr_ref[0, d:d + 1, :])
    return bits


def _leaf_index(bits: list):
    """The D bit planes of a sub-tile as ONE int32 plane of leaf indices."""
    return functools.reduce(jnp.bitwise_or, (
        jnp.where(b, 1 << d, 0) for d, b in enumerate(bits)))


def _in_blocks(block, sub_rows: int):
    """`block` (`_resolve_later`) over a sub-tile's blocks of 128 rows, in
    a rolled loop."""
    jax.lax.fori_loop(
        0, sub_rows // _LANES,
        lambda b, c: block(pl.multiple_of(b * _LANES, _LANES)), None)


def _oblivious_kernel(x_ref, sel_ref, thr_ref, leaf_ref, out_ref, *scratch,
                      depth: int, n_feat: int, sub_rows: int):
    """One row tile against one group of 128 trees: the group's share of
    every row's margin. x_ref [TILE_ROWS, F] uint8 or int32, as HBM holds
    the rows (in the last tile, whatever lies past row R); sel [1, D, Fp,
    128] bf16, thr [1, Dp, 128] f32, leaf [1, C 2^D, 128] f32; out [C,
    TILE_ROWS] f32, a leaf column a row, the rows on the lanes, resident
    over the group axis (grid axis 1). `scratch` (`_scratch_shapes`): idx
    [2, SUB_ROWS, 128] int32, the leaf indices of the sub-tile being
    selected and of the one being resolved; carry [C 2^D, 128] f32, the
    group before's leaf table.

    Software-pipelined (`_pipelined`): the resolve of a sub-tile is issued
    with the select of the NEXT one, in one basic block, and the step's
    last sub-tile is resolved by the next step on the group axis, against
    the carried leaf table (at a row tile's first group: a table of zeros
    and whatever indices the scratch holds, so it adds zeros); the last
    group resolves its own last sub-tile too, the one resolve a row tile's
    MXUs wait for. A row's sum still runs over the groups in grid order."""
    n_sub = x_ref.shape[0] // sub_rows
    n_cls = out_ref.shape[0]
    group, last = pl.program_id(1), pl.num_programs(1) - 1
    select = functools.partial(_select, x_ref, sel_ref, thr_ref, depth=depth,
                               n_feat=n_feat, sub_rows=sub_rows)

    @pl.when(group == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)
        for carry in scratch[1:]:       # the pipelined step's alone
            carry[:] = jnp.zeros_like(carry)

    if not _pipelined(depth, n_sub, n_cls):
        def sub_tile(j, carry):
            r0 = pl.multiple_of(j * sub_rows, sub_rows)
            bits = select(r0)
            if n_cls == 1:
                _resolve(bits, _leaves(leaf_ref.at[0], sub_rows), out_ref, r0)
                return carry
            # C multiplexers over whole [sub_rows, 128] planes keep their
            # values in VMEM (26,427 bundles a sub-tile at depth 6 and 7
            # classes, 44,085 of their operands spilled); over blocks of
            # 128 rows, the indices packed into one plane as the pipelined
            # step packs them, 20,867 (compile check, PR 57), and 6,622
            # with the leaves gathered from the plane (PR 58).
            scratch[0][0] = _leaf_index(bits)
            _in_blocks(_resolve_later(scratch[0], 0, leaf_ref.at[0], out_ref,
                                      r0, depth), sub_rows)
            return carry

        jax.lax.fori_loop(0, n_sub, sub_tile, 0)
        return
    idx_ref, carry_ref = scratch

    def later(j, leaf_rows):
        return _resolve_later(idx_ref, j % 2, leaf_rows, out_ref,
                              j * sub_rows, depth)

    for j in range(n_sub):
        # Beside sub-tile j's matmuls the resolve of the one before it: for
        # j = 0 the step before's last.
        bits = select(j * sub_rows, later(j - 1, leaf_ref.at[0]) if j
                      else later(n_sub - 1, carry_ref))
        idx_ref[j % 2] = _leaf_index(bits)
    carry_ref[:] = leaf_ref[0]

    @pl.when(group == last)
    def _():
        # Nothing to run under: a rolled loop, an eighth of the program.
        _in_blocks(later(n_sub - 1, leaf_ref.at[0]), sub_rows)


def predict_oblivious_pallas(
    sel: jax.Array,            # bf16 [G, D, Fp, 128]
    thr: jax.Array,            # f32 [G, Dp, 128]
    leaf: jax.Array,           # f32 [G, C 2^D, 128]
    Xc: jax.Array,             # [R, F] integer bins, uint8 as api.predict's
    *,
    scale,
    bias,
    interpret: bool | None = None,
) -> jax.Array:
    """Raw margins [R], or of vector leaves (`leaf` C 2^D rows a group,
    `bias` a tuple of C) [R, C]: Pallas twin of ops/predict.
    _predict_oblivious, over a model's compiled tables
    (models/tree.CompiledOblivious). Jit-safe. interpret=None auto-selects
    the Pallas interpreter off-TPU."""
    if interpret is None:
        interpret = device.platform() != "tpu"
    n_groups, depth, fp, _ = sel.shape
    n_cls = leaf.shape[1] >> depth
    R, F = Xc.shape
    # The rows as the kernel takes them: uint8 and int32 as they come, any
    # other integer cast in XLA first (the heap kernel's rule).
    row_dtype = row_operand_dtype(Xc.dtype)
    with traced_scope("predict:widen"):
        rows = Xc if Xc.dtype == row_dtype else Xc.astype(row_dtype)
    if not (interpret or _fits(depth, F, row_dtype, n_cls)):
        raise ValueError(
            f"oblivious shape (depth {depth}, F={F}, {n_cls} leaf "
            "column(s)) exceeds the Pallas VMEM budget or the resolve's "
            "trace; use the jax.numpy form")
    tile_rows = min(TILE_ROWS, -(-R // SUB_ROWS) * SUB_ROWS)
    n_tiles = -(-R // tile_rows)

    def table_block(*dims):
        return pl.BlockSpec((1, *dims), lambda i, b: (b,) + (0,) * len(dims),
                            memory_space=pltpu.VMEM)

    cost = pl.CostEstimate(
        flops=2 * n_tiles * tile_rows * n_groups * depth * fp * GROUP,
        bytes_accessed=n_tiles * (
            tile_rows * (F * row_dtype.itemsize + 4 * n_cls)
            + n_groups * _group_bytes(depth, F, n_cls)),
        transcendentals=0,
    )
    with traced_scope("predict:traverse_oblivious"):
        acc = pl.pallas_call(
            functools.partial(_oblivious_kernel, depth=depth, n_feat=F,
                              sub_rows=SUB_ROWS),
            # The grid walks the UNPADDED rows: the last tile's blocks are
            # ragged, as in the heap kernel.
            grid=(n_tiles, n_groups),
            in_specs=[pl.BlockSpec((tile_rows, F), lambda i, b: (i, 0),
                                   memory_space=pltpu.VMEM),
                      table_block(depth, fp, GROUP),
                      table_block(thr.shape[1], GROUP),
                      table_block(n_cls << depth, GROUP)],
            out_specs=pl.BlockSpec((n_cls, tile_rows), lambda i, b: (0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((n_cls, R), jnp.float32),
            scratch_shapes=_scratch_shapes(depth, tile_rows // SUB_ROWS,
                                           n_cls),
            cost_estimate=cost,
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        )(rows, sel, thr, leaf)
    with traced_scope("predict:accumulate"):
        if n_cls == 1:
            return bias + scale * acc[0]
        # class-major [C, R] as the kernel leaves it; the `.T` is the
        # layout's (a bitcast, as in the heap program)
        return (jnp.asarray(bias, jnp.float32)[:, None] + scale * acc).T


# ---- the OBLIVIOUS layout's entry (ops/predict.LAYOUTS) ----

def _fits(depth: int, n_features: int, row_dtype, n_cls: int) -> bool:
    """The kernel's budget estimate: one group's VMEM beside a row tile of
    `row_dtype` rows, and the multiplexers' selects the trace unrolls."""
    row_bytes = row_operand_dtype(row_dtype).itemsize
    return _mux_selects(depth, n_cls) <= _MAX_SELECTS and _vmem_bytes(
        depth, n_features, row_bytes, n_cls) <= _VMEM_BUDGET_BYTES


def kernel_serves(use_pallas, depth: int, n_features: int,
                  row_dtype=jnp.uint8, n_cls: int = 1) -> bool:
    """The oblivious layout's kernel-or-twin rule: ops/predict.
    resolve_use_pallas over this kernel's own budget predicate, for trees
    of `depth` splits and `n_cls` columns a leaf. The tree count is no term
    of it."""
    from ddt_tpu.ops.predict import resolve_use_pallas

    return resolve_use_pallas(
        use_pallas, True,
        lambda: predict_oblivious_fits(depth, n_features, row_dtype, n_cls))


def scoring_program(ce, n_features: int, row_dtype, predict_impl: str,
                    link: bool):
    """The oblivious layout's entry (ops/predict.layout_entry): the program
    of a models/tree.CompiledOblivious, its group tables as they are and
    ops/predict.predict_raw_effective_oblivious over them: the Pallas
    kernel where `kernel_serves` takes it, asked here and bound as a bool,
    else the jax.numpy form. `link`: the program ends in the model's link
    function (vector leaves' softmax). The quantized tiers have no
    oblivious form: the f32 program serves them."""
    from ddt_tpu.ops import predict as predict_ops

    entry = predict_ops.predict_raw_effective_oblivious
    classes = ce.n_classes_out
    served = kernel_serves(predict_ops.USE_PALLAS[predict_impl], ce.depth,
                           n_features, row_dtype, classes)
    plan = oblivious_plan(ce.n_trees, ce.depth, n_features, served=served,
                          row_dtype=row_dtype, n_cls=classes,
                          link=ce.loss if link else "none")
    # Bound here: fn0 outlives the build in the stage registry, and must
    # not hold the host copy of the tables.
    static = dict(scale=ce.scale, bias=ce.bias, use_pallas=served,
                  **({"link": plan.link} if link else {}))

    def fn0(sel, thr, leaf, Xc, entry=entry):
        return entry(sel, thr, leaf, Xc, **static)

    return predict_ops.ScoringProgram(plan, ce.arrays(), fn0, entry, classes,
                                      classes)
