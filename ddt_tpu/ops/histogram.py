"""HistogramBuilder: per-(node, feature, bin) gradient/hessian histograms.

THE hot kernel (SURVEY.md §2/§3 "HOT LOOP #1", the benchmark metric:
M-rows/sec/chip). Contract (identical to the NumPy oracle
reference/numpy_trainer.build_histograms): given binned uint8 features
Xb [R, F], gradients g/h [R] float32 and a per-row level-local node index
(int32, -1 for rows frozen at an earlier leaf), return float32
[n_nodes, F, n_bins, 2] with (g, h) sums per (node, feature, bin).

TPU realisation — XLA hates random-access scatter, so three interchangeable
implementations (SURVEY.md §7 "hard parts (a)"):

- "pallas": VMEM-accumulating tiled kernel (ops/hist_pallas.py): raw
  g/h/node-index rows stream in tiles, the weighted node one-hot AND the
  bin one-hot are synthesised on-chip, per-(feature-slab, node) bin
  accumulators live in VMEM scratch across the row-tile grid, and each
  slab performs exactly ONE HBM write — nothing but the uint8 Xb, 12
  bytes/row of g/h/ni, and the output ever touches HBM. The TPU default
  for shapes whose working set fits VMEM (hist_pallas.pallas_fits).
- "matmul": one-hot outer-product accumulation on the MXU. Per feature f the
  histogram is A^T @ Bf where A [R, 2N] stacks node-one-hot weighted by g and
  by h, and Bf [R, B] is the bin one-hot. Chunked over rows with lax.scan so
  the one-hot never materialises more than `row_chunk` rows at once — but XLA
  still round-trips it through HBM, which bounds throughput (~29 GB/build at
  the Higgs-1M shape). The TPU fallback for shapes too large for the Pallas
  kernel's VMEM accumulator, and the non-TPU accelerator default.
- "segment": `jax.ops.segment_sum` over combined (node*B + bin) keys, vmapped
  over features. Lowers to scatter-add; the fast path on CPU, slow on TPU.

All return bit-identical shapes and (up to float addition order) the same
values; parity vs the NumPy oracle is tests/test_ops.py.

QUANTIZED INTEGER PATH (cfg.grad_dtype, docs/PERF.md "Quantized
gradients"): int8/int16 g/h (ops/grad.quantize_gradients) dispatch the
same three implementations in the INTEGER domain — int32 accumulators,
s8/s16 operands on the MXU path — and return the RAW int32 histogram.
Integer adds commute, so all three impls are bitwise IDENTICAL to each
other (not merely up to addition order) and to any chunked/sharded
merge of themselves; the caller dequantizes exactly once (hist * scale)
after its last merge. Overflow is impossible by the quantizer's
sum-cap construction plus its enforced row ceiling
(ops/grad.GRAD_SUM_CAP / GRAD_ROW_LIMIT).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ddt_tpu.telemetry.annotations import op_scope
from ddt_tpu.telemetry.costmodel import costed
from ddt_tpu.utils import device


def _mask_inactive(
    g: jax.Array, h: jax.Array, node_index: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Zero out frozen rows (node_index < 0) and clamp their index to 0.
    Dtype-preserving on the quantized integer path (int8/int16 g/h stay
    narrow — the whole point of the stream); floats normalize to f32."""
    active = node_index >= 0
    idx = jnp.where(active, node_index, 0).astype(jnp.int32)
    if jnp.issubdtype(g.dtype, jnp.integer):
        zero = jnp.zeros((), g.dtype)
        return jnp.where(active, g, zero), jnp.where(active, h, zero), idx
    gz = jnp.where(active, g, 0.0).astype(jnp.float32)
    hz = jnp.where(active, h, 0.0).astype(jnp.float32)
    return gz, hz, idx


# --------------------------------------------------------------------------- #
# segment_sum implementation (scatter path; CPU fast path / TPU fallback)
# --------------------------------------------------------------------------- #

@costed("hist", phase="hist")
@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
@op_scope("hist")
def build_histograms_segment(
    Xb: jax.Array,          # uint8 [R, F]
    g: jax.Array,           # float32 [R]
    h: jax.Array,           # float32 [R]
    node_index: jax.Array,  # int32 [R], -1 = frozen
    n_nodes: int,
    n_bins: int,
) -> jax.Array:
    gz, hz, idx = _mask_inactive(g, h, node_index)
    if jnp.issubdtype(gz.dtype, jnp.integer):
        # Quantized path: widen to the int32 accumulator FIRST (a
        # segment_sum in int8/int16 would wrap) — the scatter-adds are
        # then exact and order-independent; output is the RAW int32
        # histogram the caller dequantizes after its last merge.
        gz = gz.astype(jnp.int32)
        hz = hz.astype(jnp.int32)
    keys = idx[:, None] * n_bins + Xb.astype(jnp.int32)       # [R, F]
    num = n_nodes * n_bins

    def per_feature(k):
        gs = jax.ops.segment_sum(gz, k, num_segments=num)
        hs = jax.ops.segment_sum(hz, k, num_segments=num)
        return jnp.stack([gs, hs], axis=-1)                   # [N*B, 2]

    out = jax.vmap(per_feature, in_axes=1)(keys)              # [F, N*B, 2]
    F = Xb.shape[1]
    return out.reshape(F, n_nodes, n_bins, 2).transpose(1, 0, 2, 3)


# --------------------------------------------------------------------------- #
# one-hot matmul implementation (MXU path; TPU default)
# --------------------------------------------------------------------------- #

def _hist_chunk_matmul(
    Xb_c: jax.Array,    # [r, F] uint8
    gz: jax.Array,      # [r] float32 (already masked)
    hz: jax.Array,
    idx: jax.Array,     # [r] int32 in [0, n_nodes)
    n_nodes: int,
    n_bins: int,
    input_dtype: jnp.dtype,
) -> jax.Array:
    """One row-chunk's histogram via outer-product matmuls: [F, 2N, B]
    f32 — int32 on the quantized integer path (exact adds; the caller
    dequantizes after its last merge)."""
    if jnp.issubdtype(gz.dtype, jnp.integer):
        # Quantized path: A and the bin one-hot in the gradient dtype
        # (|q| <= qmax fits), dot with an int32 accumulator — exact and
        # order-independent where the f32 form was ULP-tolerant. The
        # input_dtype/bf16-emulation knobs are float-path concerns.
        qdt = gz.dtype
        noh = (idx[:, None]
               == jnp.arange(n_nodes, dtype=jnp.int32)[None, :])
        zero = jnp.zeros((), qdt)
        A = jnp.concatenate(
            [jnp.where(noh, gz[:, None], zero),
             jnp.where(noh, hz[:, None], zero)], axis=1)      # [r, 2N]

        def per_feature_q(xcol):                              # [r] uint8
            bins_oh = (
                xcol[:, None]
                == jnp.arange(n_bins, dtype=jnp.uint8)[None, :]
            ).astype(qdt)                                     # [r, B]
            return jax.lax.dot_general(
                A, bins_oh,
                (((0,), (0,)), ((), ())),                     # contract rows
                preferred_element_type=jnp.int32,
            )                                                 # [2N, B] i32

        return jax.vmap(per_feature_q, in_axes=1)(Xb_c)       # [F, 2N, B]
    node_oh = jax.nn.one_hot(idx, n_nodes, dtype=jnp.float32)     # [r, N]
    # A stacks g-weighted and h-weighted node one-hots: [r, 2N].
    A = jnp.concatenate(
        [node_oh * gz[:, None], node_oh * hz[:, None]], axis=1
    ).astype(input_dtype)
    # CPU XLA has no BF16 x BF16 = F32 dot thunk; emulate EXACTLY by
    # rounding the inputs to bf16 and contracting in f32 — bf16 values are
    # exact in f32 and their products fit f32, and the MXU accumulates in
    # f32 anyway, so this reproduces the TPU path's numerics (used by the
    # bf16-vs-f32 training-quality tests, tests/test_numerics.py).
    emulate_bf16 = (
        input_dtype == jnp.bfloat16 and device.platform() == "cpu"
    )
    if emulate_bf16:
        A = A.astype(jnp.float32)
    # TPU default matmul precision is bf16 passes even for f32 operands;
    # when the caller asked for f32 inputs they want exact accumulation.
    prec = (
        jax.lax.Precision.HIGHEST
        if input_dtype == jnp.float32 or emulate_bf16
        else jax.lax.Precision.DEFAULT
    )

    def per_feature(xcol):                                        # [r] uint8
        bins_oh = (
            xcol[:, None] == jnp.arange(n_bins, dtype=jnp.uint8)[None, :]
        ).astype(input_dtype)                                     # [r, B]
        if emulate_bf16:
            bins_oh = bins_oh.astype(jnp.float32)
        return jax.lax.dot_general(
            A, bins_oh,
            (((0,), (0,)), ((), ())),                             # contract rows
            preferred_element_type=jnp.float32,
            precision=prec,
        )                                                         # [2N, B]

    return jax.vmap(per_feature, in_axes=1)(Xb_c)                 # [F, 2N, B]


@costed("hist", phase="hist")
@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "row_chunk", "input_dtype"),
)
@op_scope("hist")
def build_histograms_matmul(
    Xb: jax.Array,          # uint8 [R, F]
    g: jax.Array,
    h: jax.Array,
    node_index: jax.Array,
    n_nodes: int,
    n_bins: int,
    row_chunk: int = 32_768,
    input_dtype: jnp.dtype = jnp.bfloat16,
) -> jax.Array:
    R, F = Xb.shape
    gz, hz, idx = _mask_inactive(g, h, node_index)
    acc_dtype = (jnp.int32 if jnp.issubdtype(gz.dtype, jnp.integer)
                 else jnp.float32)

    if R <= row_chunk:
        out = _hist_chunk_matmul(Xb, gz, hz, idx, n_nodes, n_bins, input_dtype)
    else:
        # Pad R to a chunk multiple; padded rows carry g=h=0 so they add 0.
        n_chunks = -(-R // row_chunk)
        pad = n_chunks * row_chunk - R
        Xb_p = jnp.pad(Xb, ((0, pad), (0, 0)))
        gz_p = jnp.pad(gz, (0, pad))
        hz_p = jnp.pad(hz, (0, pad))
        idx_p = jnp.pad(idx, (0, pad))

        def body(acc, args):
            xc, gc, hc, ic = args
            return acc + _hist_chunk_matmul(
                xc, gc, hc, ic, n_nodes, n_bins, input_dtype
            ), None

        acc0 = jnp.zeros((F, 2 * n_nodes, n_bins), acc_dtype)
        out, _ = jax.lax.scan(
            body,
            acc0,
            (
                Xb_p.reshape(n_chunks, row_chunk, F),
                gz_p.reshape(n_chunks, row_chunk),
                hz_p.reshape(n_chunks, row_chunk),
                idx_p.reshape(n_chunks, row_chunk),
            ),
        )

    # [F, 2N, B] -> [N, F, B, 2]
    out = out.reshape(F, 2, n_nodes, n_bins)
    return out.transpose(2, 0, 3, 1)


# --------------------------------------------------------------------------- #
# dispatch
# --------------------------------------------------------------------------- #

def resolve_hist_impl(
    hist_impl: str,
    platform: str | None = None,
    n_nodes: int | None = None,
    n_features: int | None = None,
    n_bins: int | None = None,
    input_bytes: int = 2,
    grad_bytes: int = 4,
) -> str:
    """'auto' -> the right implementation for the platform (and shape).

    CPU: segment (scatter is fine there). TPU: the Pallas VMEM kernel when
    the shape fits its accumulator budget (hist_pallas.pallas_fits), else the
    chunked matmul. Other accelerators: matmul (the Pallas kernel is
    TPU-only; off-TPU it would silently run interpreted, orders of magnitude
    slower). Shape args omitted -> optimistic TPU answer ("pallas").
    `input_bytes`/`grad_bytes` are the one-hot operand and g/h row
    itemsizes (pallas_fits' budget terms): build_histograms passes the
    ACTUAL gradient dtype's sizes, so quantized int8/int16 shapes chunk
    against their own — smaller — working set instead of the f32
    defaults silently forcing the matmul fallback at deep levels.
    """
    if hist_impl != "auto":
        return hist_impl
    if platform is None:
        platform = device.platform()
    if platform == "cpu":
        return "segment"
    if platform != "tpu":
        return "matmul"
    if n_nodes is not None and n_features is not None and n_bins is not None:
        from ddt_tpu.ops.hist_pallas import feature_chunks_for

        # The kernel feature-chunks itself for deep levels. Since the
        # VMEM-streaming rewrite a slab re-reads only its own uint8
        # columns plus 2 * grad-itemsize + 4 bytes/row of g/h/ni — 12
        # for f32 gradients, 8/6 for quantized int16/int8 (the old form
        # re-streamed the [R, 2N] weighted one-hot per slab, which
        # capped k at 4) — so chunking stays ahead of the matmul
        # fallback until the slab count itself is pathological.
        k = feature_chunks_for(n_nodes, n_features, n_bins,
                               input_bytes=input_bytes,
                               grad_bytes=grad_bytes)
        if k is None or k > 8:
            return "matmul"
    return "pallas"


def build_histograms(
    Xb: jax.Array,
    g: jax.Array,
    h: jax.Array,
    node_index: jax.Array,
    n_nodes: int,
    n_bins: int,
    impl: str = "auto",
    row_chunk: int = 32_768,
    input_dtype: jnp.dtype = jnp.bfloat16,
) -> jax.Array:
    """Dispatching HistogramBuilder; see module docstring for impls."""
    quant = jnp.issubdtype(jnp.dtype(g.dtype), jnp.integer)
    gb = jnp.dtype(g.dtype).itemsize if quant else 4
    impl = resolve_hist_impl(
        impl, n_nodes=n_nodes, n_features=Xb.shape[1], n_bins=n_bins,
        # Quantized one-hot operands are built in the gradient dtype
        # (1/2 B); the f32 path's one-hot rides cfg.matmul_input_dtype
        # (bf16 = 2 B, the historical resolver assumption).
        input_bytes=gb if quant else 2, grad_bytes=gb,
    )
    if impl == "segment":
        return build_histograms_segment(Xb, g, h, node_index, n_nodes, n_bins)
    if impl == "matmul":
        return build_histograms_matmul(
            Xb, g, h, node_index, n_nodes, n_bins,
            row_chunk=row_chunk, input_dtype=input_dtype,
        )
    if impl == "pallas":
        from ddt_tpu.ops.hist_pallas import build_histograms_pallas
        return build_histograms_pallas(
            Xb, g, h, node_index, n_nodes, n_bins, input_dtype=input_dtype
        )
    raise ValueError(f"unknown hist impl {impl!r}")
