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
tree, W/128 for v and (W/128)^2 for m, 6 at 255 leaves.

Layout strategy. The tables are 152 KB a tree at W = 256 (sel 16, planes
8, P 128), 76 MB for 500 trees: they do not stay in VMEM, and streaming
them for every 256-row tile as the heap kernel streams its blocks would
move 0.6 TB a 2M-row chunk. So the ROW TILE is thousands of rows
(`TILE_ROWS`) and the grid is (row tiles, table blocks): one step holds a
block of G trees' tables (Mosaic double-buffers the windows: the next
block's DMA runs under this block's matmuls) and walks the tile in
sub-tiles of `SUB_ROWS` rows, each against the block's G trees in turn;
the [TILE_ROWS, 1] output stays resident over the block axis, zeroed by
the first block and added to by all. A row tile streams the tables once:
489 times a 2M-row chunk at 4096 rows, 38 GB against a second of MXU
time. G is not a knob (`path_plan`): the most trees whose windows fit the
VMEM budget beside the row tile's (56 at 255 leaves: 500 trees in 9
blocks), evened out over the blocks. The other order (table blocks
outside, each fetched once a chunk) would revisit an output block across
grid steps that do not follow one another, which Pallas does not keep.

Exactness is the form's own: bins below 256, +-1 and P are bfloat16
without rounding, the MXU accumulates in float32, every partial sum is an
integer of at most 255 in magnitude. The leaf reached is the node walk's
for every (row, tree); scores agree with ops/predict._predict_paths to the
float32 rounding of a sum in another order (equal on dyadic leaf values).
Interpret mode auto-selects off-TPU, as in predict_pallas.py; dispatch is
ops/predict.resolve_use_pallas.
"""

from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddt_tpu.ops.predict_pallas import _window_bytes
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
TILE_ROWS = 4096
SUB_ROWS = 512
_LANES = 128
# Scoped VMEM the kernel asks of Mosaic (the default is 16 MiB of the
# v5e's 128), and what of it `path_plan` fills: the rest is the compiler's.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_VMEM_BUDGET_BYTES = _VMEM_LIMIT_BYTES - 4 * 1024 * 1024
# Trees a block at most: the kernel's trace is that many trees long.
_MAX_TREES_PER_STEP = 64
# Bytes a sub-tile's row keeps beside the windows: v, s, m, acc and the
# select's temporaries, [SUB_ROWS, W] each: 6 float32 planes a lane.
_SUB_ROW_LANE_BYTES = 24


def path_mxu_tiles_per_tree(lanes: int, n_features: int) -> int:
    """MXU weight tiles (results [rows, 128]) a tree costs a tile of rows:
    the feature select's, ceil(F/128) x W/128, and the path resolve's,
    (W/128)^2."""
    w = lanes // _LANES
    return -(-n_features // _LANES) * w + w * w


def _tree_bytes(lanes: int, n_features: int) -> int:
    """HBM bytes of one tree's tables: sel bf16, planes f32, P bf16."""
    fp = -(-n_features // 16) * 16
    return fp * lanes * 2 + 8 * lanes * 4 + lanes * lanes * 2


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

    @property
    def blocks(self) -> int:
        """As TablePlan.blocks: past 1 a row tile streams `table_bytes`;
        one block's index never moves, so it is fetched once and stays."""
        return self.table_blocks

    def span_counts(self) -> dict:
        return {k: getattr(self, k) for k in SPAN_COUNTS}

    def root_counts(self) -> dict:
        return {"routing_tables": 0, "node_list": self.node_list,
                "path_mxu_tiles_per_tree": self.path_mxu_tiles_per_tree}


# What the `ddt:predict:ensemble` span says of a node-list model's plan, in
# the order it prints (docs/OBSERVABILITY.md); `cli predict` repeats all
# but `table_bytes` in `phases_ms`, as it does for the heap kernel's.
SPAN_COUNTS = ("node_list", "nodes_per_tree", "leaves_per_tree",
               "deepest_leaf", "path_mxu_tiles_per_tree", "trees_per_step",
               "table_blocks", "table_bytes")
PHASES_COUNTS = tuple(k for k in SPAN_COUNTS if k != "table_bytes")


def path_plan(n_trees: int, lanes: int, n_features: int,
              deepest_leaf: int = 0, served: bool = True) -> PathPlan:
    """The kernel's table blocks at this shape: G trees a block, the most
    whose double-buffered windows fit _VMEM_BUDGET_BYTES beside the row
    tile's windows and a sub-tile's working set, evened out over the
    blocks. `served` False: the plan of a model the jax.numpy form scores
    (its lanes and depth, no blocks)."""
    tiles = path_mxu_tiles_per_tree(lanes, n_features)
    if not served:
        return PathPlan(1, lanes, lanes, deepest_leaf, tiles, 0, 0, 0, 0)
    fp = -(-n_features // 16) * 16
    per_tree = (_window_bytes(fp, lanes) // 2          # bf16: half of f32
                + _window_bytes(8, lanes)
                + _window_bytes(lanes, lanes) // 2)
    fixed = (_window_bytes(TILE_ROWS, n_features) + _window_bytes(TILE_ROWS, 1)
             + SUB_ROWS * lanes * _SUB_ROW_LANE_BYTES)
    most = min(n_trees, _MAX_TREES_PER_STEP,
               max(0, (_VMEM_BUDGET_BYTES - fixed) // per_tree))
    if most == 0:
        return PathPlan(1, lanes, lanes, deepest_leaf, tiles, 0, 0, 0,
                        TILE_ROWS)
    blocks = -(-n_trees // most)
    g = -(-n_trees // blocks)
    return PathPlan(1, lanes, lanes, deepest_leaf, tiles, g, blocks,
                    blocks * g * _tree_bytes(lanes, n_features), TILE_ROWS)


def predict_paths_fits(lanes: int, n_features: int) -> bool:
    """Whether one tree's tables fit the kernel's VMEM budget beside a row
    tile: the guard behind use_pallas=None (ops/predict.resolve_use_pallas).
    The tree count is no term of it."""
    return path_plan(1, lanes, n_features).trees_per_step > 0


def _paths_kernel(x_ref, sel_ref, planes_ref, paths_ref, out_ref, *,
                  n_trees: int, n_feat: int):
    """One row tile against one block of `n_trees` trees: the block's share
    of every row's margin. x_ref [TILE_ROWS, F] int32; sel [G, Fp, W] bf16,
    planes [G, 8, W] f32, paths [G, W, W] bf16; out [TILE_ROWS, 1] f32,
    resident over the block axis (grid axis 1)."""
    tile_rows = x_ref.shape[0]
    fp, lanes = sel_ref.shape[1], sel_ref.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    def sub_tile(j, carry):
        r0 = pl.multiple_of(j * SUB_ROWS, SUB_ROWS)
        xf = x_ref[pl.ds(r0, SUB_ROWS), :].astype(jnp.float32)
        if fp > n_feat:     # K to whole bf16 sublane tiles
            xf = jnp.concatenate(
                [xf, jnp.zeros((SUB_ROWS, fp - n_feat), jnp.float32)], axis=1)
        xb = xf.astype(jnp.bfloat16)                      # [S, Fp]

        def tree(g, acc):
            rows = planes_ref[g]                          # [8, W]
            # bf16 operands (bins <= 255 and the 0/1 one-hot are exact),
            # f32 accumulator: the v5e's VPU has no bf16 compare.
            v = jax.lax.dot_general(
                xb, sel_ref[g], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [S, W]
            s = jnp.where(v > rows[0:1, :], 1.0, -1.0).astype(jnp.bfloat16)
            m = jax.lax.dot_general(
                s, paths_ref[g], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [S, W]
            return acc + jnp.where(m == rows[1:2, :], rows[2:3, :], 0.0)

        # Unrolled: the compiler runs a tree's matmuls under the one
        # before's compares (a fori_loop here took 35% longer).
        acc = jnp.zeros((SUB_ROWS, lanes), jnp.float32)
        for g in range(n_trees):
            acc = tree(g, acc)
        out_ref[pl.ds(r0, SUB_ROWS), :] += jnp.sum(acc, axis=1,
                                                   keepdims=True)
        return carry

    jax.lax.fori_loop(0, tile_rows // SUB_ROWS, sub_tile, 0)


def predict_paths_pallas(
    sel: jax.Array,            # bf16 [T, Fp, W]
    planes: jax.Array,         # f32 [T, 8, W]
    paths: jax.Array,          # bf16 [T, W, W]
    Xi: jax.Array,             # int32 [R, F] bins
    *,
    learning_rate,
    base,
    interpret: bool | None = None,
) -> jax.Array:
    """Raw margins [R]: Pallas twin of ops/predict._predict_paths. Jit-safe.
    interpret=None auto-selects the Pallas interpreter off-TPU."""
    if interpret is None:
        interpret = device.platform() != "tpu"
    T, fp, lanes = sel.shape
    R, F = Xi.shape
    plan = path_plan(T, lanes, F)
    if not predict_paths_fits(lanes, F):
        if not interpret:
            raise ValueError(
                f"path-matrix shape ({lanes} lanes a tree, F={F}) exceeds "
                "the Pallas VMEM budget; use the jax.numpy form")
        # Interpreted past the budget: one block of every tree.
        plan = plan._replace(trees_per_step=T, table_blocks=1)
    g, n_blocks = plan.trees_per_step, plan.table_blocks
    # Trees that fill the last block: no node, and no leaf of any length
    # (-1), so they add 0. Rows that fill the last tile are cut off below.
    t_fill = ((0, n_blocks * g - T), (0, 0), (0, 0))
    with traced_scope("predict:tables"):
        sel_b, paths_b = jnp.pad(sel, t_fill), jnp.pad(paths, t_fill)
        planes_b = jnp.pad(planes, t_fill, constant_values=-1.0)
    tile_rows = min(TILE_ROWS, -(-R // SUB_ROWS) * SUB_ROWS)
    n_tiles = -(-R // tile_rows)
    with traced_scope("predict:widen"):
        Xt = jnp.pad(Xi, ((0, n_tiles * tile_rows - R), (0, 0)))

    def rows_of_tile(cols):
        return pl.BlockSpec((tile_rows, cols), lambda i, b: (i, 0),
                            memory_space=pltpu.VMEM)

    def table_block(rows, cols):
        return pl.BlockSpec((g, rows, cols), lambda i, b: (b, 0, 0),
                            memory_space=pltpu.VMEM)

    cost = pl.CostEstimate(
        flops=2 * n_tiles * tile_rows * n_blocks * g * lanes * (fp + lanes),
        bytes_accessed=n_tiles * (tile_rows * (F + 1) * 4
                                  + plan.table_bytes),
        transcendentals=0,
    )
    with traced_scope("predict:traverse_paths"):
        acc = pl.pallas_call(
            functools.partial(_paths_kernel, n_trees=g, n_feat=F),
            grid=(n_tiles, n_blocks),
            in_specs=[rows_of_tile(F), table_block(fp, lanes),
                      table_block(8, lanes), table_block(lanes, lanes)],
            out_specs=rows_of_tile(1),
            out_shape=jax.ShapeDtypeStruct((n_tiles * tile_rows, 1),
                                           jnp.float32),
            cost_estimate=cost,
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        )(Xt, sel_b, planes_b, paths_b)
    with traced_scope("predict:accumulate"):
        return base + learning_rate * acc[:R, 0]
