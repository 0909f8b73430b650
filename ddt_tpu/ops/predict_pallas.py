"""Pallas TPU traversal kernel for batch ensemble scoring — binned data.

Why this kernel exists (round-5 phase breakdown, docs/PERF.md): the pure-XLA
one-hot predict path is bound by the comparison matrix's HBM traffic — the
[row_chunk, tree_chunk, Nint] compare bits are ~33 MB per chunk pair, ~644 GB
total for the 10M x 1000 config against the v5e's ~820 GB/s, while the MXU
part of the matmul is ~0.2 ms of the 1.13 s P1 phase. Same disease the
histogram kernel had (ops/hist_pallas.py), same cure: build the per-tile
working set IN VMEM and never let it touch HBM. The only HBM traffic is the
binned input itself (R x F int32) plus the tiny tree tables and the [R, C]
scores — the comparison matrix, feature one-hots, and descent state live and
die inside one row tile's VMEM residency.

Layout strategy (one grid step = one tile of TILE_R rows; ALL tree tables are
pinned in VMEM for the whole kernel via constant index maps — a 1000-tree
depth-6 ensemble is ~1 MB):

    X     [TILE_R, F]        int32 bins, cast bf16 in-VMEM (the matmul
                             operand; its f32 result is what compares).
    feat  [n_tc, Nint*Tc]    NODE-MAJOR flattened effective features per
                             tree chunk (lane block n holds node n of all
                             Tc trees) — so every descent select is a
                             STATIC lane slice, no gathers anywhere.
    thr/dl/cat               same node-major layout.
    val   [n_tc, W*Tc]       bottom-level pushed-down leaf values.
    coh   [Tpad, C]          round-major class one-hot.

Per tree chunk (static Python loop, traced once):
    fohT [F, Nint*Tc] bf16 one-hot built on the VPU by SUBLANE-broadcasting
        the feature row against a lane iota (the hist_pallas transposed-
        kernel trick), then ONE MXU matmul with an f32 accumulator:
        colval = X @ fohT — the exact bin value at every (row, tree, node).
    comp = colval > thr (with categorical one-vs-rest and reserved-NaN-bin
        routing applied exactly as ops/predict._descend_comp).
    D-step indexed descent: k[r, t] starts 0; level d selects the path
        node's comparison bit by k-indexed predicated selects over the
        level's 2^d node planes (each plane a static lane slice) —
        sum(2^d) = Nint VPU selects per chunk, zero HBM traffic.
    Leaf select + class scatter: vals[r, t] by k-indexed select over the
        W bottom planes, then acc += vals @ class-one-hot (f32, HIGHEST —
        bit-stable, mirroring the one-hot path's accumulation order).

Contract: the SAME leaf per (row, tree) as ops/predict.predict_raw at the
same tree_chunk (missing-value routing, categorical one-vs-rest, softmax
round-major classes all preserved; the integer descent is identical). The
float accumulation is written term for term like the one-hot path's, but
the summation order inside a dot belongs to the compiler, so scores agree
to f32 rounding, not bitwise (tests/test_predict_pallas.py: equality on
dyadic leaf values, a 1e-6 tolerance on random ones).
Interpret mode auto-selects off-TPU (utils/device.platform), same pattern
as hist_pallas.py; dispatch lives in ops/predict.resolve_use_pallas (the
`use_pallas` flag on predict_raw / predict_raw_effective, one-hot fallback).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddt_tpu.telemetry.annotations import op_scope, traced_scope
from ddt_tpu.telemetry.costmodel import costed
from ddt_tpu.utils import device

# VMEM ceiling for auto-dispatch: the kernel's working set + the resident
# tree tables + Mosaic's double-buffered operand windows must fit the
# 16 MiB scoped-VMEM limit; 12 MB leaves the same headroom hist_pallas
# budgets.
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_DEFAULT_TILE_R = 256
# Working-set bytes per (row, lane) of a tree chunk's [TILE_R, Nint*Tc]
# colval (f32) and comparison bits (int32). NOT 8: the compiler streams
# both through vector registers plane by plane and spills little. Taken
# from the compiler's own account — AOT compiles for a described v5e with
# the scoped limit forced to 1 MiB, so that each reports its allocation
# (PERF.md, PR 21): <= 0.9 B per (row, lane) at every probed shape (tile
# 256 and 512, depth 4-8, with and without the missing/cat operands, 1
# and 7 classes), and tile 512 x depth 8 (8.4M row-lanes) compiled under
# the real limit where tile 1024 x depth 8 did not. 2 keeps every
# admitted shape inside what was shown to compile.
WORK_BYTES_PER_LANE = 2
# Static-unroll ceiling: the kernel traces n_tc * (Nint + W + ~4) ops;
# past this the trace (and Mosaic compile) grows pathological — the
# one-hot path is the right tool for such shapes anyway.
_MAX_TRACE_SELECTS = 32_768


def _window_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of one pipelined 32-bit operand window: padded to whole
    (8, 128) tiles, and double-buffered."""
    return 2 * (-(-rows // 8) * 8) * (-(-cols // 128) * 128) * 4


def predict_pallas_fits(
    n_trees_padded: int,
    tree_chunk: int,
    max_depth: int,
    n_features: int,
    n_classes: int,
    tile_r: int | None = None,
) -> bool:
    """Whether the traversal kernel's VMEM working set (and trace size)
    fits at this shape — the guard behind use_pallas=None auto-dispatch
    (ops/predict.resolve_use_pallas)."""
    if tile_r is None:
        tile_r = _DEFAULT_TILE_R
    if n_trees_padded % tree_chunk != 0:
        return False
    n_int = (1 << max_depth) - 1
    n_leaves = 1 << max_depth
    n_tc = n_trees_padded // tree_chunk
    if n_tc * (n_int + n_leaves) > _MAX_TRACE_SELECTS:
        return False
    lanes = n_int * tree_chunk
    work = tile_r * lanes * WORK_BYTES_PER_LANE
    # Resident tables, every one a whole-array window: feat i32, thr f32,
    # the optional dl and cat i32 (counted always — the guard is asked
    # before the operands exist), bottom values, class one-hot.
    trees = 4 * _window_bytes(n_tc, lanes)
    trees += _window_bytes(n_tc, n_leaves * tree_chunk)
    trees += _window_bytes(n_trees_padded, n_classes)
    x_tile = _window_bytes(tile_r, n_features)
    out = _window_bytes(tile_r, n_classes)
    return work + trees + x_tile + out <= _VMEM_BUDGET_BYTES


def _traverse_kernel(x_ref, feat_ref, thr_ref, val_ref, coh_ref, *rest,
                     n_tc: int, tc: int, n_int: int, n_leaves: int,
                     n_feat: int, max_depth: int, missing_bin_value: int,
                     use_missing: bool, use_cat: bool):
    """One row tile: margins for every class, all trees, fully in VMEM.

    x_ref [TILE_R, F] int32; feat/thr (+ optional dl, cat) [n_tc, Nint*Tc]
    node-major; val [n_tc, W*Tc]; coh [Tpad, C]; out [TILE_R, C] f32."""
    rest = list(rest)
    out_ref = rest.pop()
    dl_ref = rest.pop(0) if use_missing else None
    cat_ref = rest.pop(0) if use_cat else None
    tile_r = x_ref.shape[0]
    lanes = n_int * tc
    xb = x_ref[:].astype(jnp.bfloat16)                    # [T, F]
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (n_feat, lanes), 0)
    acc = jnp.zeros((tile_r, out_ref.shape[1]), jnp.float32)
    for c in range(n_tc):
        # Feature one-hot, TRANSPOSED: sublane-broadcast the feature row
        # (cheap row replication — the hist_pallas _hist_kernel_t trick)
        # against the per-feature iota. feat = -1 (pushed-down leaves)
        # matches no sublane -> colval 0 < thr(+BIG) -> always-left.
        feat = jnp.broadcast_to(feat_ref[c:c + 1, :], (n_feat, lanes))
        fohT = (feat == f_iota).astype(jnp.bfloat16)      # [F, Nint*Tc]
        # bf16 operands (bins <= 255 and the 0/1 one-hot are exact), f32
        # accumulator: the MXU accumulates in 32 bits only, and the v5e's
        # VPU has no bf16 compare, so colval stays f32 from here on.
        colval = jax.lax.dot_general(
            xb, fohT, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [T, Nint*Tc]
        thr = jnp.broadcast_to(thr_ref[c:c + 1, :], (tile_r, lanes))
        # Comparison bits live as int32 0/1, never as a bool ARRAY:
        # Mosaic keeps i1 only as a select's predicate — a select BETWEEN
        # bool arrays needs an i8 -> i1 truncation it does not have.
        comp = (colval > thr).astype(jnp.int32)
        if use_cat:
            # One-vs-rest nodes (pre-gated on eff_feat >= 0 in the
            # prologue): the matched bin goes left.
            cat = jnp.broadcast_to(
                cat_ref[c:c + 1, :], (tile_r, lanes)) != 0
            comp = jnp.where(cat, (colval != thr).astype(jnp.int32), comp)
        if use_missing:
            # Reserved-NaN-bin rows follow the learned direction;
            # pushed-down leaves have colval 0, never the reserved bin.
            miss = colval == jnp.float32(missing_bin_value)
            not_dl = 1 - jnp.broadcast_to(
                dl_ref[c:c + 1, :], (tile_r, lanes))
            comp = jnp.where(miss, not_dl, comp)
        # Indexed descent: k-select the path node's bit per level. Every
        # node plane is a STATIC lane slice of the node-major comp.
        k = jnp.zeros((tile_r, tc), jnp.int32)
        for d in range(max_depth):
            lo = (1 << d) - 1
            go = jnp.zeros((tile_r, tc), jnp.int32)
            for i in range(1 << d):
                n = lo + i
                go = jnp.where(k == i, comp[:, n * tc:(n + 1) * tc], go)
            k = 2 * k + go
        # Bottom-level leaf select (exact: k matches exactly one plane).
        vals = jnp.zeros((tile_r, tc), jnp.float32)
        for j in range(n_leaves):
            plane = jnp.broadcast_to(
                val_ref[c:c + 1, j * tc:(j + 1) * tc], (tile_r, tc))
            vals = jnp.where(k == j, plane, vals)
        # Class scatter — the same dot, precision, and per-chunk add order
        # as the one-hot path's scan body (bit-stable mirror).
        acc = acc + jax.lax.dot_general(
            vals, coh_ref[c * tc:(c + 1) * tc, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    out_ref[:] = acc


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
    if tile_r is None:
        tile_r = _DEFAULT_TILE_R
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
    if not interpret and not predict_pallas_fits(
            Tpad, tree_chunk, max_depth, F, C, tile_r):
        # Compiled dispatch past the budget means a VMEM OOM or a
        # pathological Mosaic trace on the chip — fail at the cause. The
        # auto path (ops/predict.resolve_use_pallas) never gets here;
        # this guards a forced predict_impl='pallas' at a monster shape.
        # Interpret mode (CPU tests) has no VMEM to protect.
        raise ValueError(
            f"predict shape (trees_padded={Tpad}, tree_chunk={tree_chunk}, "
            f"depth={max_depth}, F={F}, C={C}) exceeds the Pallas "
            "VMEM/trace budget; use the one-hot path")
    n_tc = Tpad // tree_chunk
    n_int = (1 << max_depth) - 1
    n_leaves = 1 << max_depth

    def node_major(a, width, dtype):
        """[Tpad, width] -> [n_tc, width*Tc] with lane block n holding
        node n of every tree in the chunk (tiny arrays; the transpose is
        noise next to the row volume)."""
        return (a.astype(dtype)
                .reshape(n_tc, tree_chunk, width)
                .transpose(0, 2, 1)
                .reshape(n_tc, width * tree_chunk))

    feat_nm = node_major(eff_feat[:, :n_int], n_int, jnp.int32)
    thr_nm = node_major(eff_thr[:, :n_int], n_int, jnp.float32)
    val_nm = node_major(bot_val, n_leaves, jnp.float32)
    use_missing = eff_dl is not None
    use_cat = eff_cat is not None
    extras = []
    if use_missing:
        extras.append(node_major(eff_dl[:, :n_int], n_int, jnp.int32))
    if use_cat:
        # Pre-gate on eff_feat >= 0 so pushed-down leaves (colval 0,
        # thr +BIG) stay always-left, exactly like _descend_comp.
        cat_eff = eff_cat[:, :n_int].astype(bool) & (eff_feat[:, :n_int]
                                                     >= 0)
        extras.append(node_major(cat_eff, n_int, jnp.int32))

    Xi = Xc.astype(jnp.int32)
    n_tiles = -(-R // tile_r)
    rpad = n_tiles * tile_r - R
    if rpad:
        Xi = jnp.pad(Xi, ((0, rpad), (0, 0)))

    lanes = n_int * tree_chunk
    kernel = functools.partial(
        _traverse_kernel, n_tc=n_tc, tc=tree_chunk, n_int=n_int,
        n_leaves=n_leaves, n_feat=F, max_depth=max_depth,
        missing_bin_value=missing_bin_value, use_missing=use_missing,
        use_cat=use_cat,
    )
    pinned = pl.BlockSpec((n_tc, lanes), lambda i: (0, 0),
                          memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((tile_r, F), lambda i: (i, 0),
                     memory_space=pltpu.VMEM),
        pinned,                                           # feat
        pinned,                                           # thr
        pl.BlockSpec((n_tc, n_leaves * tree_chunk), lambda i: (0, 0),
                     memory_space=pltpu.VMEM),            # val
        pl.BlockSpec((Tpad, C), lambda i: (0, 0),
                     memory_space=pltpu.VMEM),            # coh
    ] + [pinned] * len(extras)
    cost = pl.CostEstimate(
        flops=2 * n_tiles * tile_r * (F * n_tc * lanes + Tpad * C),
        bytes_accessed=n_tiles * tile_r * (F + C) * 4
        + n_tc * lanes * 8 + Tpad * C * 4,
        transcendentals=0,
    )
    with traced_scope("predict"):
        with traced_scope("predict:traverse"):
            acc = pl.pallas_call(
                kernel,
                grid=(n_tiles,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((tile_r, C), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((n_tiles * tile_r, C),
                                               jnp.float32),
                cost_estimate=cost,
                interpret=interpret,
            )(Xi, feat_nm, thr_nm, val_nm,
              cls_oh.astype(jnp.float32), *extras)
        with traced_scope("predict:accumulate"):
            out = base + learning_rate * acc[:R]
    return out[:, 0] if C == 1 else out


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
    """Standalone raw-arrays entry (tests/bench): pushdown in-trace, then
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
