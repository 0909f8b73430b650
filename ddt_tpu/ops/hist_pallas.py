"""Pallas TPU kernel for HistogramBuilder — VMEM-accumulating, hand-tiled.

Why this kernel exists (measured on TPU v5e, 1M rows x 28 feat x 255 bins):
the pure-XLA one-hot-matmul path materialises the [rows, F*B] bin one-hot in
HBM — ~29 GB of write+read traffic per build — and runs HBM-bound at
~26 M-rows/s with the MXU nearly idle. The first Pallas form (rounds 1-5)
built the bin one-hot tile-by-tile in VMEM but still materialised the
WEIGHTED NODE ONE-HOT `A [R, 2N]` (plus an int32 copy of the binned input)
in an XLA prologue: ~250 MB of avoidable HBM write+read per build at the
headline shape, re-streamed per feature slab when chunked — the roofline
observatory's `ddt:hist` verdict stayed "hbm".

This rewrite streams only the RAW operands and synthesises everything else
on-chip:

    inputs per grid step (one tile of TILE_R rows):
      Xb  [TILE_R, F]  uint8  binned features (cast int32 in-VMEM — the
                       only row-sized HBM read, 1 byte/feature/row)
      g,h [1, TILE_R]  f32    gradient/hessian rows (one block of the
                       [n_tiles, 1, TILE_R] fold: Mosaic wants a block's
                       last two dims divisible by (8, 128) or equal to the
                       array's, and (1, TILE_R) of [n_tiles, TILE_R] is
                       neither)
      ni  [1, TILE_R]  i32    level-local node index, -1 = frozen
    on-chip per tile (VPU):
      A   [TILE_R, 2N]   node one-hot weighted by g (cols 0..N-1) and by
                         h (cols N..2N-1); ni = -1 matches no column, so
                         frozen rows vanish without a masking prologue.
      OH  [TILE_R, F*Bp] per-feature bin one-hot, Bp = padded lanes/bins.
    accumulate (MXU):
      acc [2N, F*Bp] f32 VMEM SCRATCH += A^T @ OH — ONE dot_general per
      tile; the scratch lives across the whole row-tile grid loop and is
      flushed to the output block (ONE HBM write per feature slab) at the
      final grid step.

HBM traffic per build: R x F uint8 + (2 * grad itemsize + 4) bytes/row
of g/h/ni + the [N, F, B, 2] output — nothing else. No prologue
materialisation, no per-slab re-stream of row-sized state (chunked
slabs re-read only g/h/ni). The g/h itemsize is DTYPE-PARAMETERIZED
(ISSUE 14): f32 gradients stream 12 B/row of g/h/ni; quantized int16
streams 8 B/row and int8 6 B/row — the pallas_fits budget and the
CostEstimate read the actual operand dtypes, never a hard-coded 12.

INTEGER ACCUMULATION (cfg.grad_dtype, docs/PERF.md "Quantized
gradients"): when g/h arrive QUANTIZED (int8/int16 from
ops/grad.quantize_gradients), the whole kernel runs in the integer
domain — A and the bin one-hot are built in the gradient dtype, the
dot_general accumulates with preferred_element_type=int32 into an int32
VMEM scratch (s8 x s8 -> s32 is MXU-native), and the flushed output is
the RAW int32 histogram. Integer adds commute, so the result is
bitwise independent of tile order, feature chunking, sibling
subtraction, and shard merge order; the caller dequantizes exactly once
(hist * scale) after the last merge. The scratch/output itemsize is
unchanged (int32 == f32 at 4 B), so the VMEM budget arithmetic is
shared with the f32 path.

Two kernel forms (dispatch on the padded bin width, sweep-9/10 measured):
row-major (`_hist_kernel`, bins_pad >= 256) builds OH [T, F*Bp] with bins
on LANES; the transposed form (`_hist_kernel_t`, bins_pad <= 128) builds
OH [F*Bp, T] with bins on SUBLANES — x broadcasts along sublanes as cheap
row replication, ~1.5x the row-major form at 64 bins. Since round 6 the
64-bin layout is promoted to automatic dispatch: n_bins <= 64 pads to Bp
= 64 sublanes (half the OH footprint and half the MXU columns of the old
128-lane padding).

Contract identical to ops/histogram.py: returns [n_nodes, F, n_bins, 2]
f32. Tests run this kernel in Pallas interpret mode on CPU
(tests/test_hist_pallas.py, tests/test_hist_fused.py) and lower its
compiled form for a TPU (tests/test_tpu_lowering.py); chip_smoke.py runs
it on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddt_tpu.telemetry.annotations import traced_scope
from ddt_tpu.telemetry.costmodel import costed
from ddt_tpu.utils import device

LANE = 128

# VMEM working-set ceiling for auto-selection: the one-hot tile
# [tile_r, F*Bp] + the scratch accumulator AND its HBM-flush output block
# (both [2N, F*Bp] f32) + pipeline buffers must fit ~16 MB/core. 12 MB
# leaves headroom for Mosaic's double-buffered input windows.
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_DEFAULT_TILE_R = 512
# The transposed kernel's default row tile: tiles 1024-2048 measure
# identically (~73 Mrows/s at 64 bins, min-of-8; sweep 10 A/B) and 512
# was never faster — 1024 keeps the VMEM working set modest.
_DEFAULT_TILE_R_T = 1024


def _default_tile_r(n_bins: int) -> int:
    """The row tile the dispatcher will actually run with: the transposed
    kernel (n_bins <= 128) uses the larger tile (sweep-10 A/B). The ONE
    home of this rule — pallas_fits/feature_chunks_for must size VMEM for
    the same tile the kernel allocates."""
    return _DEFAULT_TILE_R_T if _bins_pad(n_bins) <= LANE \
        else _DEFAULT_TILE_R


def _bins_pad(n_bins: int) -> int:
    """Padded one-hot width per feature. n_bins <= 64 pads to 64 SUBLANES
    (the promoted 64-bin layout — bins ride the transposed kernel's
    sublane axis, where 64 is tile-aligned for both bf16 and f32);
    n_bins <= 128 pads to one 128 tile and still routes transposed; wider
    bin counts pad to 256 LANES for the row-major kernel."""
    if n_bins <= 64:
        return 64
    if n_bins <= LANE:
        return LANE
    return max(2 * LANE, ((n_bins + LANE - 1) // LANE) * LANE)


def pallas_fits(
    n_nodes: int,
    n_features: int,
    n_bins: int,
    tile_r: int | None = None,
    input_bytes: int = 2,
    grad_bytes: int = 4,
    acc_bytes: int = 4,
) -> bool:
    """Whether the kernel's VMEM working set fits at this shape (the shape
    guard behind hist_impl='auto' — ops/histogram.resolve_hist_impl).
    tile_r=None sizes for the tile the dispatcher will actually run.

    The budget is computed from the ACTUAL operand itemsizes, never
    hard-coded f32 (ISSUE 14): `input_bytes` is the one-hot/A operand
    itemsize (2 bf16, 4 f32; 1/2 on the quantized int8/int16 path),
    `grad_bytes` the streamed g/h row itemsize (4 f32, 2 int16, 1 int8),
    `acc_bytes` the scratch/output accumulator itemsize (4 for both f32
    and the quantized path's int32 — asserted, not assumed)."""
    assert acc_bytes == 4, (
        "the VMEM accumulator is f32 or int32 — both 4 B; a new "
        "accumulator dtype must re-derive this budget")
    if tile_r is None:
        tile_r = _default_tile_r(n_bins)
    fbp = n_features * _bins_pad(n_bins)
    oh_bytes = tile_r * fbp * input_bytes
    # Streamed per-tile row operands (g, h, ni blocks) — tiny next to
    # the one-hot, but dtype-parameterized like everything else.
    row_bytes = tile_r * (2 * grad_bytes + 4)
    # Scratch accumulator + the output block it flushes into: both live
    # in VMEM for the whole kernel.
    acc_total = 2 * (2 * n_nodes * fbp * acc_bytes)
    return oh_bytes + row_bytes + acc_total <= _VMEM_BUDGET_BYTES


def _weighted_node_onehot(ni, g, h, n_nodes: int, input_dtype):
    """A [T, 2N]: node one-hot weighted by g then h, built on the VPU.
    ni = -1 (frozen / pad rows) matches no column — the masking prologue
    the old kernel needed is free here. Dtype-generic: on the quantized
    path g/h are int8/int16 and A stays in that dtype (the weights fit
    by the |q| <= qmax construction), so the dot runs integer."""
    tile_r = ni.shape[0]
    noh = ni[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (tile_r, n_nodes), 1)
    if jnp.issubdtype(g.dtype, jnp.integer):
        # The row -> column relayout ([T] on lanes to [T, 1] on sublanes)
        # exists in Mosaic for 32-bit elements only; int8/int16 rows
        # widen first (exact) and A narrows back after the select.
        g = g.astype(jnp.int32)
        h = h.astype(jnp.int32)
    zero = jnp.zeros((), g.dtype)
    return jnp.concatenate(
        [jnp.where(noh, g[:, None], zero), jnp.where(noh, h[:, None], zero)],
        axis=1,
    ).astype(input_dtype)                                 # [T, 2N]


def _acc_dtype(input_dtype):
    """Accumulator dtype for an operand dtype: int32 on the quantized
    integer path (exact adds), f32 otherwise (the MXU's native form)."""
    return (jnp.int32 if jnp.issubdtype(jnp.dtype(input_dtype), jnp.integer)
            else jnp.float32)


def _hist_kernel(xb_ref, g_ref, h_ref, ni_ref, out_ref, acc_ref, *,
                 n_nodes: int, n_feat: int, bins_pad: int, input_dtype):
    """One row tile, row-major form: acc += A^T @ OH, all built in VMEM.

    xb_ref [TILE_R, F] uint8; g/h [1, TILE_R] f32; ni [1, TILE_R] i32;
    acc_ref [2N, F*Bp] f32 VMEM scratch (lives across the grid);
    out_ref same shape — written ONCE at the final grid step."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = xb_ref[:].astype(jnp.int32)                       # [T, F]
    tile_r = x.shape[0]
    A = _weighted_node_onehot(ni_ref[0, :], g_ref[0, :], h_ref[0, :],
                              n_nodes, input_dtype)
    bin_iota = jax.lax.broadcasted_iota(
        jnp.int32, (tile_r, bins_pad), 1
    )
    # Per-feature one-hot slabs, concatenated to [T, F * Bp]. The Python
    # loop unrolls at trace time (F is static).
    slabs = [
        (x[:, f][:, None] == bin_iota).astype(input_dtype)
        for f in range(n_feat)
    ]
    oh = jnp.concatenate(slabs, axis=1)                   # [T, F*Bp]

    acc_ref[:] += jax.lax.dot_general(
        A, oh,
        (((0,), (0,)), ((), ())),                         # contract rows
        preferred_element_type=_acc_dtype(input_dtype),
    )

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]                           # ONE HBM write


def _hist_kernel_t(xt_ref, g_ref, h_ref, ni_ref, out_ref, acc_ref, *,
                   n_nodes: int, n_feat: int, bins_pad: int, input_dtype):
    """TRANSPOSED row tile (bins_pad <= 128, i.e. n_bins <= 128):
    acc[F*Bp, 2N] += OH[F*Bp, T] @ A[T, 2N].

    Why a second form exists (sweeps 9 and 10, earlier host, round 5):
    the row-major kernel is bound by per-feature [T, 1] -> [T, Bp] LANE
    broadcasts (cost flat in Bp — shrinking bins bought nothing), while
    this form broadcasts x rows along SUBLANES ((bin_iota[Bp, 1] ==
    x[1, T])), which Mosaic executes as cheap row replication. At 64 bins
    it measures ~72 Mrows/s vs ~48 row-major, and the promoted Bp = 64
    sublane layout (n_bins <= 64) halves the OH footprint again. At
    Bp = 256 the transposed form loses its edge (more sublane tiles per
    slab), so the row-major kernel keeps the 255-bin contract.
    """
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    xt = xt_ref[:].astype(jnp.int32)                      # [F, T]
    tile_r = xt.shape[1]
    A = _weighted_node_onehot(ni_ref[0, :], g_ref[0, :], h_ref[0, :],
                              n_nodes, input_dtype)
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (bins_pad, tile_r), 0)
    slabs = [
        (xt[f, :][None, :] == bin_iota).astype(input_dtype)   # [Bp, T]
        for f in range(n_feat)
    ]
    oh = jnp.concatenate(slabs, axis=0)                   # [F*Bp, T]
    acc_ref[:] += jax.lax.dot_general(
        oh, A,
        (((1,), (0,)), ((), ())),                         # contract rows
        preferred_element_type=_acc_dtype(input_dtype),
    )

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]                           # ONE HBM write


def feature_chunks_for(n_nodes: int, n_features: int, n_bins: int,
                       tile_r: int | None = None,
                       input_bytes: int = 2,
                       grad_bytes: int = 4) -> int | None:
    """Smallest number of feature chunks whose per-chunk working set fits
    the kernel's VMEM budget, or None if even one feature does not fit
    (then the caller must use the matmul path). input_bytes is the one-hot
    operand's itemsize (2 bfloat16, 4 float32, 1/2 quantized int8/int16);
    grad_bytes the g/h row itemsize (see pallas_fits)."""
    if tile_r is None:
        tile_r = _default_tile_r(n_bins)
    for k in range(1, n_features + 1):
        if pallas_fits(n_nodes, -(-n_features // k), n_bins, tile_r,
                       input_bytes, grad_bytes):
            return k
    return None


def build_histograms_pallas(
    Xb: jax.Array,
    g: jax.Array,
    h: jax.Array,
    node_index: jax.Array,
    n_nodes: int,
    n_bins: int,
    tile_r: int | None = None,
    interpret: bool | None = None,
    input_dtype=jnp.bfloat16,
) -> jax.Array:
    """Pallas HistogramBuilder: [n_nodes, F, n_bins, 2] float32 — or RAW
    int32 when g/h arrive quantized (int8/int16; the caller dequantizes
    once after the last merge — see the module docstring's integer
    section).

    interpret=None auto-selects Pallas interpreter mode off-TPU (CPU tests
    exercise the identical kernel logic; the compiled path needs a real
    chip). input_dtype is the A/one-hot operand dtype: bfloat16 rides the MXU
    at full rate; float32 buys exact accumulation at reduced rate (same knob
    as the matmul path — cfg.matmul_input_dtype). Quantized g/h OVERRIDE it
    with their own dtype (s8/s16 operands, s32 accumulation — exact).

    Shapes whose VMEM working set overflows the budget (deep levels:
    n_nodes >= 32 at 255 bins) are feature-CHUNKED: one pallas_call per
    column slab, outputs concatenated — exact (columns are independent),
    and since the rewrite a slab re-reads only its own Xb columns plus
    2 * grad-itemsize + 4 bytes/row of g/h/ni, so chunking stays far
    above the matmul fallback.
    """
    if interpret is None:
        interpret = device.platform() != "tpu"
    if tile_r is None:
        tile_r = _default_tile_r(n_bins)
    quant = jnp.issubdtype(jnp.dtype(g.dtype), jnp.integer)
    dt = jnp.dtype(g.dtype) if quant else jnp.dtype(input_dtype)
    F = Xb.shape[1]
    grad_bytes = dt.itemsize if quant else 4
    k = feature_chunks_for(n_nodes, F, n_bins, tile_r, dt.itemsize,
                           grad_bytes)
    if k is None:
        raise ValueError(
            f"histogram shape (n_nodes={n_nodes}, n_bins={n_bins}) exceeds "
            "the Pallas VMEM budget even at one feature per call; use the "
            "matmul implementation"
        )
    return _build_histograms_pallas(
        Xb, g, h, node_index, n_nodes, n_bins, tile_r, interpret, dt, k,
    )


@costed("hist_pallas", phase="hist")
@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "tile_r", "interpret",
                     "input_dtype", "n_chunks"),
)
def _build_histograms_pallas(
    Xb: jax.Array,          # uint8 [R, F]
    g: jax.Array,           # float32 [R]
    h: jax.Array,           # float32 [R]
    node_index: jax.Array,  # int32 [R], -1 = frozen
    n_nodes: int,
    n_bins: int,
    tile_r: int = _DEFAULT_TILE_R,
    interpret: bool = False,
    input_dtype=jnp.bfloat16,
    n_chunks: int = 1,      # feature slabs (one pallas_call each); slabs
                            # share the streamed g/h/ni rows
) -> jax.Array:
    R, F = Xb.shape
    bins_pad = _bins_pad(n_bins)
    quant = jnp.issubdtype(jnp.dtype(input_dtype), jnp.integer)
    acc_dtype = _acc_dtype(input_dtype)

    # Stream prologue (XLA, cheap): pad rows to a tile multiple and fold
    # the per-row vectors to [n_tiles, 1, tile_r] blocks. Pad rows carry
    # ni = -1, so they match no node column in-kernel — no weighted
    # one-hot, no int32 input copy, nothing row-sized materialises.
    # Quantized g/h keep their narrow dtype on the stream (the whole
    # point: 1-2 bytes/row instead of 4 per channel).
    n_tiles = -(-R // tile_r)
    pad = n_tiles * tile_r - R
    Xp = Xb
    gz = g if quant else g.astype(jnp.float32)
    hz = h if quant else h.astype(jnp.float32)
    ni = node_index.astype(jnp.int32)
    if pad:
        Xp = jnp.pad(Xp, ((0, pad), (0, 0)))
        gz = jnp.pad(gz, (0, pad))
        hz = jnp.pad(hz, (0, pad))
        ni = jnp.pad(ni, (0, pad), constant_values=-1)
    g2 = gz.reshape(n_tiles, 1, tile_r)
    h2 = hz.reshape(n_tiles, 1, tile_r)
    ni2 = ni.reshape(n_tiles, 1, tile_r)

    # Leading dim squeezed: the kernel sees [1, tile_r] refs, and the
    # block's last two dims EQUAL the array's (module docstring).
    row_spec = pl.BlockSpec((None, 1, tile_r), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    def slab(Xs):
        Fs = Xs.shape[1]
        # bytes_accessed from the ACTUAL operand dtypes: uint8 Xb, g/h at
        # their streamed itemsize (4 f32, 2 int16, 1 int8), int32 ni, and
        # the 4 B/entry (f32 or int32) output — never a hard-coded 12.
        row_bytes = 2 * jnp.dtype(gz.dtype).itemsize + 4
        cost = pl.CostEstimate(
            flops=2 * 2 * n_nodes * Fs * bins_pad * n_tiles * tile_r,
            bytes_accessed=R * Fs + R * row_bytes
            + 2 * n_nodes * Fs * bins_pad * 4,
            transcendentals=0,
        )
        if bins_pad <= LANE:
            # Transposed kernel (n_bins <= 128): sublane-broadcast one-hot
            # build — ~1.5x the row-major form at 64 bins (sweep 10).
            with traced_scope("hist:stream"):
                out = pl.pallas_call(
                    functools.partial(_hist_kernel_t, n_nodes=n_nodes,
                                      n_feat=Fs, bins_pad=bins_pad,
                                      input_dtype=input_dtype),
                    grid=(n_tiles,),
                    in_specs=[
                        pl.BlockSpec((Fs, tile_r), lambda i: (0, i),
                                     memory_space=pltpu.VMEM),
                        row_spec, row_spec, row_spec,
                    ],
                    out_specs=pl.BlockSpec(
                        (Fs * bins_pad, 2 * n_nodes), lambda i: (0, 0),
                        memory_space=pltpu.VMEM,
                    ),
                    out_shape=jax.ShapeDtypeStruct(
                        (Fs * bins_pad, 2 * n_nodes), acc_dtype),
                    scratch_shapes=[
                        pltpu.VMEM((Fs * bins_pad, 2 * n_nodes),
                                   acc_dtype),
                    ],
                    cost_estimate=cost,
                    interpret=interpret,
                )(Xs.T, g2, h2, ni2)
            with traced_scope("hist:flush"):
                # [Fs*Bp, 2N] -> [N, Fs, B, 2]
                out = out.reshape(Fs, bins_pad, 2, n_nodes)[:, :n_bins]
                return out.transpose(3, 0, 1, 2)
        with traced_scope("hist:stream"):
            out = pl.pallas_call(
                functools.partial(_hist_kernel, n_nodes=n_nodes, n_feat=Fs,
                                  bins_pad=bins_pad,
                                  input_dtype=input_dtype),
                grid=(n_tiles,),
                in_specs=[
                    pl.BlockSpec(
                        (tile_r, Fs), lambda i: (i, 0),
                        memory_space=pltpu.VMEM,
                    ),
                    row_spec, row_spec, row_spec,
                ],
                out_specs=pl.BlockSpec(
                    (2 * n_nodes, Fs * bins_pad), lambda i: (0, 0),
                    memory_space=pltpu.VMEM,
                ),
                out_shape=jax.ShapeDtypeStruct((2 * n_nodes, Fs * bins_pad),
                                               acc_dtype),
                scratch_shapes=[
                    pltpu.VMEM((2 * n_nodes, Fs * bins_pad), acc_dtype),
                ],
                cost_estimate=cost,
                interpret=interpret,
            )(Xs, g2, h2, ni2)
        with traced_scope("hist:flush"):
            # [2N, Fs*Bp] -> [N, Fs, B, 2]
            out = out.reshape(2, n_nodes, Fs, bins_pad)[..., :n_bins]
            return out.transpose(1, 2, 3, 0)

    if n_chunks == 1:
        return slab(Xp)
    fc = -(-F // n_chunks)
    return jnp.concatenate(
        [slab(Xp[:, i:i + fc]) for i in range(0, F, fc)], axis=1)
