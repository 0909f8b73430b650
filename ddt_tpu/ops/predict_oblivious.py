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

so the MXU is asked for D x ceil(F/128) weight tiles a group and 256 rows
(`oblivious_mxu_tiles_per_tree` = that over 128: 0.75 at depth 6 and 2000
columns, where the 63-node expansion through the path kernel asks 17) and
the leaf lookup costs no matmul: the bits of a lane are that tree's own,
so the index never crosses lanes and the 64-way multiplexer runs under the
select's matmuls (63 selects a vreg of rows against 96 weight-tile pushes).

The select is K-BLOCKED as the path kernel's is: v_d = sum_k x_k @ sel_d,k
over ceil(F/128) blocks of 128 columns (`select_k_blocks`: 16 at 2000
columns), each x_k cut from the row tile as HBM holds it and widened uint8
-> bf16 in VMEM once a sub-tile, for all D splits of the group. One
non-zero a column of sel and bins below 256: every partial product is exact
and so is their sum in any order.

The HBM interface is the other kernels' (ops/predict_pallas.py, PR 36): the
rows go in as the caller holds them (uint8 from api.predict), over a grid
of cdiv(R, tile) row tiles whose last block is ragged, and the scores come
out `f32[1, R]`, the rows on the lanes.

Layout strategy. A group's tables are D x Fp x 128 bf16 of select (3.07 MB
at depth 6 and 2000 columns), 4 KB of thresholds and 2^D x 128 f32 of leaf
values (32 KB): 197 MB for 8000 trees. They stream: the grid is (row tiles,
groups), one step holds ONE group's tables (Mosaic double-buffers the
windows: the next group's DMA runs under this group's matmuls) and walks
the row tile in sub-tiles of `SUB_ROWS`; the [1, TILE_ROWS] output stays
resident over the group axis, zeroed by the first group and added to by
all. A row tile streams the tables once.

Exactness is the form's own: bins below 256 and 0/1 are bfloat16 without
rounding, the MXU accumulates in float32 and every partial sum is a bin.
The leaf reached is the bit walk's for every (row, tree); scores agree with
ops/predict._predict_oblivious to the float32 rounding of a sum in another
order (equal on dyadic leaf values). Interpret mode auto-selects off-TPU,
as in predict_pallas.py; dispatch is ops/predict.resolve_use_pallas.
"""

from __future__ import annotations

import functools
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
# 146.8 at 1024, not worth its 8 MB of VMEM.
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
# The deepest tree the kernel traces: its multiplexer is 2^D - 1 selects
# long (1023 at depth 10).
_MAX_DEPTH = 10
# Bytes a sub-tile's row keeps beside the windows: a widened bin of its
# K-blocks (the bf16 copy the group's splits share and the float32 it is
# made from), and a lane's float32 planes: v_d and b_d of every split and
# the multiplexer's stack, D deep.
_SUB_ROW_BIN_BYTES = 6


def oblivious_mxu_tiles_per_tree(depth: int, n_features: int) -> float:
    """MXU weight tiles (results [rows, 128]) a TREE costs a tile of rows:
    a group's D lane tiles x ceil(F/128) K-blocks, over its 128 trees."""
    return round(depth * select_k_blocks(n_features) / GROUP, 4)


def _group_bytes(depth: int, n_features: int) -> int:
    """HBM bytes of one group's tables: sel bf16, thr and leaf f32."""
    fp = -(-n_features // 16) * 16
    return (depth * fp * GROUP * 2 + -(-depth // 8) * 8 * GROUP * 4
            + (GROUP << depth) * 4)


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

    @property
    def blocks(self) -> int:
        """As TablePlan.blocks: past 1 a row tile streams `table_bytes`."""
        return self.table_blocks

    def span_counts(self) -> dict:
        return {k: getattr(self, k) for k in SPAN_COUNTS}

    def root_counts(self) -> dict:
        return {"routing_tables": 0, "oblivious": self.oblivious,
                "select_columns_per_tree": self.select_columns_per_tree,
                "select_k_blocks": self.select_k_blocks}


# What the `ddt:predict:ensemble` span says of an oblivious model's plan, in
# the order it prints (docs/OBSERVABILITY.md); `cli predict` repeats all
# but `table_bytes` in `phases_ms`, as it does for the other kernels'.
SPAN_COUNTS = ("oblivious", "depth", "select_columns_per_tree",
               "trees_per_lane_tile", "select_k_blocks",
               "oblivious_mxu_tiles_per_tree", "trees_per_step",
               "table_blocks", "table_bytes", "row_operand_bytes")
PHASES_COUNTS = tuple(k for k in SPAN_COUNTS if k != "table_bytes")


def _vmem_bytes(depth: int, n_features: int, row_bytes: int) -> int:
    """VMEM a grid step takes: the group's double-buffered table windows,
    the row tile's two at the rows' own width, the [1, TILE_ROWS]
    output's, a sub-tile's widened K-blocks and its float32 planes."""
    fp = -(-n_features // 16) * 16
    tables = (depth * _window_bytes(fp, GROUP) // 2     # bf16: half of f32
              + _window_bytes(depth, GROUP) + _window_bytes(1 << depth, GROUP))
    rows = (2 * TILE_ROWS * _lane_pad(n_features) * row_bytes
            + _window_bytes(1, TILE_ROWS))
    sub = SUB_ROWS * (_lane_pad(n_features) * _SUB_ROW_BIN_BYTES
                      + GROUP * 4 * (3 * depth + 2))
    return tables + rows + sub


def oblivious_plan(n_trees: int, depth: int, n_features: int,
                   served: bool = True, row_dtype=jnp.uint8) -> ObliviousPlan:
    """The kernel's table blocks at this shape: one group of 128 trees a
    step. `served` False: the plan of a model the jax.numpy form scores
    (its depth and select, no blocks)."""
    row_bytes = row_operand_dtype(row_dtype).itemsize
    said = (1, depth, depth, round(GROUP / depth, 2),
            select_k_blocks(n_features),
            oblivious_mxu_tiles_per_tree(depth, n_features))
    if not served:
        return ObliviousPlan(*said, 0, 0, 0, 0, row_bytes)
    groups = max(1, -(-n_trees // GROUP))
    return ObliviousPlan(*said, GROUP, groups,
                         groups * _group_bytes(depth, n_features), TILE_ROWS,
                         row_bytes)


def predict_oblivious_fits(depth: int, n_features: int,
                           row_dtype=jnp.uint8) -> bool:
    """Whether one group's tables fit the kernel's VMEM budget beside a row
    tile, and its multiplexer the trace: the guard behind use_pallas=None
    (ops/predict.resolve_use_pallas), the ONE rule. The tree count is no
    term of it."""
    row_bytes = row_operand_dtype(row_dtype).itemsize
    return depth <= _MAX_DEPTH and _vmem_bytes(
        depth, n_features, row_bytes) <= _VMEM_BUDGET_BYTES


def _mux(bits: list, leaf_ref, d: int, base: int):
    """The leaf value every (row, lane) reaches among leaves base ..
    base + 2^(d+1) - 1, by bits 0 .. d: depth-first, so at most d + 1
    planes are alive."""
    if d < 0:
        return leaf_ref[0, base:base + 1, :]              # [1, 128]
    return jnp.where(bits[d], _mux(bits, leaf_ref, d - 1, base + (1 << d)),
                     _mux(bits, leaf_ref, d - 1, base))


def _oblivious_kernel(x_ref, sel_ref, thr_ref, leaf_ref, out_ref, *,
                      depth: int, n_feat: int, sub_rows: int):
    """One row tile against one group of 128 trees: the group's share of
    every row's margin. x_ref [TILE_ROWS, F] uint8 or int32, as HBM holds
    the rows (in the last tile, whatever lies past row R); sel [1, D, Fp,
    128] bf16, thr [1, Dp, 128] f32, leaf [1, 2^D, 128] f32; out [1,
    TILE_ROWS] f32, the rows on the lanes, resident over the group axis
    (grid axis 1)."""
    tile_rows = x_ref.shape[0]
    fp = sel_ref.shape[2]
    k_starts = range(0, n_feat, _LANES)

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    def sub_tile(j, carry):
        r0 = pl.multiple_of(j * sub_rows, sub_rows)
        # The sub-tile's K-blocks, widened once for the group's D splits:
        # the bf16 copy lives in VMEM alone.
        xs = []
        for k0 in k_starts:
            k1, kp = min(k0 + _LANES, n_feat), min(k0 + _LANES, fp)
            xf = x_ref[pl.ds(r0, sub_rows), k0:k1].astype(
                jnp.int32).astype(jnp.float32)
            if kp > k1:     # K to whole bf16 sublane tiles
                xf = jnp.concatenate(
                    [xf, jnp.zeros((sub_rows, kp - k1), jnp.float32)], axis=1)
            xs.append(xf.astype(jnp.bfloat16))            # [S, <= 128]
        bits = []
        for d in range(depth):
            # bf16 operands (bins <= 255 and the 0/1 one-hot are exact),
            # f32 accumulator: the v5e's VPU has no bf16 compare.
            v = None
            for k0, xk in zip(k_starts, xs):
                part = jax.lax.dot_general(
                    xk, sel_ref[0, d, k0:k0 + xk.shape[1], :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [S, 128]
                v = part if v is None else v + part
            bits.append(v > thr_ref[0, d:d + 1, :])
        leaf = jnp.broadcast_to(_mux(bits, leaf_ref, depth - 1, 0),
                                (sub_rows, _LANES))
        # The lanes summed with the rows on the lanes: turn the plane over,
        # add down the sublanes.
        out_ref[:, pl.ds(r0, sub_rows)] += jnp.sum(leaf.T, axis=0,
                                                   keepdims=True)
        return carry

    jax.lax.fori_loop(0, tile_rows // sub_rows, sub_tile, 0)


def predict_oblivious_pallas(
    sel: jax.Array,            # bf16 [G, D, Fp, 128]
    thr: jax.Array,            # f32 [G, Dp, 128]
    leaf: jax.Array,           # f32 [G, 2^D, 128]
    Xc: jax.Array,             # [R, F] integer bins, uint8 as api.predict's
    *,
    scale,
    bias,
    interpret: bool | None = None,
) -> jax.Array:
    """Raw margins [R]: Pallas twin of ops/predict._predict_oblivious, over
    a model's compiled tables (models/tree.CompiledOblivious). Jit-safe.
    interpret=None auto-selects the Pallas interpreter off-TPU."""
    if interpret is None:
        interpret = device.platform() != "tpu"
    n_groups, depth, fp, _ = sel.shape
    R, F = Xc.shape
    # The rows as the kernel takes them: uint8 and int32 as they come, any
    # other integer cast in XLA first (the heap kernel's rule).
    row_dtype = row_operand_dtype(Xc.dtype)
    with traced_scope("predict:widen"):
        rows = Xc if Xc.dtype == row_dtype else Xc.astype(row_dtype)
    if not (interpret or predict_oblivious_fits(depth, F, row_dtype)):
        raise ValueError(
            f"oblivious shape (depth {depth}, F={F}) exceeds the Pallas "
            "VMEM budget; use the jax.numpy form")
    tile_rows = min(TILE_ROWS, -(-R // SUB_ROWS) * SUB_ROWS)
    n_tiles = -(-R // tile_rows)

    def table_block(*dims):
        return pl.BlockSpec((1, *dims), lambda i, b: (b,) + (0,) * len(dims),
                            memory_space=pltpu.VMEM)

    cost = pl.CostEstimate(
        flops=2 * n_tiles * tile_rows * n_groups * depth * fp * GROUP,
        bytes_accessed=n_tiles * (
            tile_rows * (F * row_dtype.itemsize + 4)
            + n_groups * _group_bytes(depth, F)),
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
                      table_block(1 << depth, GROUP)],
            out_specs=pl.BlockSpec((1, tile_rows), lambda i, b: (0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((1, R), jnp.float32),
            cost_estimate=cost,
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        )(rows, sel, thr, leaf)
    with traced_scope("predict:accumulate"):
        return bias + scale * acc[0]
