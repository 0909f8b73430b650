"""One-home collectives for split finding: the single spelling of
psum/reduce_scatter/all_gather (+ compressed payloads).

Every cross-device byte the trainer moves funnels through this module —
the ddtlint `one-home-collective` rule flags raw `jax.lax.psum`/
`reduce_scatter`/`all_gather` anywhere else in ddt_tpu/, so changing a
collective's algorithm, payload dtype, or instrumentation is a one-file
edit and the `hist_allreduce_bytes` counter's payload model
(telemetry/counters.py) cannot silently drift from the wire.

Three concerns live here (ISSUE 10, docs/PERF.md "Histogram comms"):

- **The collectives.** `psum`/`pmax`/`pmin`/`all_gather` are thin
  wrappers (identity when `axis_name` is None, so single-device traces
  share the callers' code path). `reduce_scatter` is
  `jax.lax.psum_scatter(tiled=True)`, which lowers to a true
  `reduce-scatter` HLO, tuple (hosts, rows) axes included.

- **Reduce-scatter split finding** (`cfg.split_comms`): instead of
  psumming the full `[n, F, B, 2]` level histogram to every device and
  having every device run the same argmax, `hist_reduce(...,
  mode="reduce_scatter")` hands each of the P row shards one merged
  F/P-feature slab; the caller runs split finding on its slab and
  `combine_shard_winners` all_gathers the tiny per-shard (gain, feat,
  bin, direction) tuples — O(F·B/P) + O(P · n_level) per device where
  the allreduce moved O(F·B). The cross-shard tie-break is by GLOBAL
  flattened candidate index (direction block, then feature, then bin),
  so the combined winner is exactly the single-device argmax's pick —
  including the missing-bin RIGHT-block-first rule — regardless of
  which shard owns which slab. On a 2D (rows x features) mesh the
  scatter runs over the row axes WITHIN each feature slab (per-device
  slab F/(Pr·Pf)) and the winner combine gathers over BOTH axes — the
  tie-break key is layout-independent, so composition needs no new
  rule (ROADMAP item 2).

- **Compressed collective payloads** (`cfg.hist_comms_dtype`, opt-in):
  `bf16` halves the wire bytes at ~2^-9 relative rounding per partial;
  `int32_fixed` quantizes each partial onto a shared fixed-point grid
  (global scale from a pmax of the local max-abs) and reduces in int32
  — integer addition commutes EXACTLY, so an N-partition merge is
  bit-stable under any reduction order where f32 psum order was not.
  `comms_error_bound` computes the worst-case per-entry error either
  mode can introduce; the split-agreement contract tests
  (tests/test_comms.py) hold the trained trees to it.

Named scopes: every collective opens a `ddt:comms:<kind>` traced scope
(compress/decompress included) so profiler captures attribute the wire
time this module exists to shrink (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ddt_tpu.telemetry.annotations import traced_scope
from ddt_tpu.utils import device

#: cfg.split_comms values (config.py validates; backends resolve "auto").
SPLIT_COMMS = ("auto", "allreduce", "reduce_scatter")
#: cfg.hist_comms_dtype values — the histogram collective's wire dtype.
COMMS_DTYPES = ("f32", "bf16", "int32_fixed")

#: Wire bytes per histogram entry under each comms dtype (the
#: hist_allreduce_bytes payload model reads this — one home).
COMMS_DTYPE_BYTES = {"f32": 4, "bf16": 2, "int32_fixed": 4}

#: int32_fixed headroom: the per-partial quantized magnitude cap is
#: (2^30 - 1) // P so the P-way integer sum can never overflow int32
#: (sum bounded by P * cap < 2^30 << 2^31 - 1).
_FIXED_CAP = (1 << 30) - 1


# --------------------------------------------------------------------- #
# axis helpers (tuple row axes — the (hosts, rows) pod mesh — welcome)
# --------------------------------------------------------------------- #

def axis_size(axis_name) -> int:
    """Static total extent of `axis_name` (product over a tuple of
    axes) — trace-time python int."""
    if axis_name is None:
        return 1
    if isinstance(axis_name, tuple):
        n = 1
        for a in axis_name:
            n *= jax.lax.axis_size(a)
        return n
    return jax.lax.axis_size(axis_name)


def flat_axis_index(axis_name):
    """This shard's flattened index over `axis_name` (row-major over a
    tuple of axes, matching psum_scatter's slab ordering and the
    backends' global-row-offset convention)."""
    if axis_name is None:
        return jnp.int32(0)
    if isinstance(axis_name, tuple):
        idx = jax.lax.axis_index(axis_name[0])
        for a in axis_name[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx.astype(jnp.int32)
    return jax.lax.axis_index(axis_name).astype(jnp.int32)


# --------------------------------------------------------------------- #
# the collectives (identity when axis_name is None)
# --------------------------------------------------------------------- #

def psum(x, axis_name):
    if axis_name is None:
        return x
    with traced_scope("comms:allreduce"):
        return jax.lax.psum(x, axis_name)


def pmax(x, axis_name):
    if axis_name is None:
        return x
    with traced_scope("comms:allreduce"):
        return jax.lax.pmax(x, axis_name)


def pmin(x, axis_name):
    if axis_name is None:
        return x
    with traced_scope("comms:allreduce"):
        return jax.lax.pmin(x, axis_name)


def all_gather(x, axis_name, axis: int = 0, tiled: bool = False):
    if axis_name is None:
        return x if tiled else x[None]
    with traced_scope("comms:allgather"):
        return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, dim: int):
    """Sum `x` over `axis_name` and hand each shard its contiguous
    1/P block of dimension `dim` (shard i gets block i in flattened
    axis order). `x.shape[dim]` must be a multiple of the axis size —
    callers pad (see `pad_to_multiple`)."""
    if axis_name is None:
        return x
    P = axis_size(axis_name)
    if x.shape[dim] % P:
        raise ValueError(
            f"reduce_scatter dim {dim} extent {x.shape[dim]} not a "
            f"multiple of the axis size {P}; pad first")
    with traced_scope("comms:reduce_scatter"):
        return jax.lax.psum_scatter(
            x, axis_name, scatter_dimension=dim, tiled=True)


def pad_to_multiple(x, dim: int, multiple: int):
    """Zero-pad dimension `dim` of `x` up to a multiple (identity when
    already aligned) — the reduce_scatter callers' F-axis alignment."""
    extent = x.shape[dim]
    rem = extent % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[dim] = (0, multiple - rem)
    return jnp.pad(x, pad)


# --------------------------------------------------------------------- #
# compressed histogram reduction
# --------------------------------------------------------------------- #

def _reduce(x, axis_name, mode: str, scatter_dim: int):
    if mode == "reduce_scatter":
        return reduce_scatter(x, axis_name, scatter_dim)
    return psum(x, axis_name)


def hist_reduce(hist, axis_name, *, mode: str = "allreduce",
                comms_dtype: str = "f32", scatter_dim: int = 1):
    """The histogram collective: merge per-shard partial histograms over
    `axis_name`, replicated (`mode="allreduce"`) or slab-sharded along
    `scatter_dim` (`mode="reduce_scatter"`; callers pre-pad that dim to
    the axis size). `comms_dtype` down-converts the payload on the wire:

    - "f32": the exact baseline.
    - "bf16": 2 bytes/entry; each shard's partial rounds to bf16 before
      the reduce (accumulation stays f32 via an upcast — psum of bf16
      operands would also round every partial SUM).
    - "int32_fixed": 4 bytes/entry, but the reduction is an INTEGER sum
      on a shared fixed-point grid (scale = pmax of the local max-abs),
      so the merged histogram is bitwise independent of reduction order
      — N-partition merges become bit-stable where f32 psum order was
      not. An all-zero histogram short-circuits exactly (scale guard).
      The scale is derived from THIS call's tensor: slab-pipelined
      callers (ops/grow.level_histograms) therefore quantize each slab
      on its own — tighter — grid, so int32_fixed values depend on the
      slab count (deterministic, inside comms_error_bound, not bitwise
      vs the monolithic call; f32/bf16 are elementwise and slab-
      invariant).

    INTEGER partials (the quantized-gradient path, cfg.grad_dtype —
    ops/grad.py): int32 histograms already live on ONE shared
    fixed-point grid (the scale is derived from psum'd/pmax'd global
    stats before quantization), so the merge is a plain integer psum /
    reduce-scatter — order-independent bit-stable WITHOUT int32_fixed's
    per-collective scale carve-out, and overflow-free by the quantizer's
    sum-cap construction. Compression is REFUSED for them rather than
    silently double-quantizing (config.py raises at TrainConfig
    construction; this is the backstop for direct callers).

    Single-shard traces (axis_name None) skip compression entirely —
    there is no wire, so there must be no rounding."""
    if comms_dtype not in COMMS_DTYPES:
        raise ValueError(
            f"comms_dtype must be one of {COMMS_DTYPES}, got {comms_dtype!r}")
    if jnp.issubdtype(hist.dtype, jnp.integer):
        if comms_dtype != "f32":
            raise ValueError(
                f"hist_comms_dtype={comms_dtype!r} cannot compress integer "
                "(quantized-gradient) histogram partials: they already "
                "live on one shared fixed-point grid, so re-quantizing "
                "per collective would DOUBLE-quantize and void the "
                "grad_quant error bound; keep hist_comms_dtype='f32' "
                "(the integer merge is already bit-stable and needs no "
                "compression for order-independence)")
        return _reduce(hist, axis_name, mode, scatter_dim)
    if axis_name is None or comms_dtype == "f32":
        return _reduce(hist, axis_name, mode, scatter_dim)
    if comms_dtype == "bf16":
        with traced_scope("comms:compress"):
            x = hist.astype(jnp.bfloat16).astype(jnp.float32)
        return _reduce(x, axis_name, mode, scatter_dim)
    # int32_fixed: shared scale from the global max-abs; quantized
    # partials bounded by cap = _FIXED_CAP // P so the int32 sum cannot
    # overflow. round-half-away rounding matches the NumPy twin in
    # tests; dequantize AFTER the integer reduce.
    P = axis_size(axis_name)
    cap = _FIXED_CAP // P
    m = pmax(jnp.max(jnp.abs(hist)), axis_name)
    scale = jnp.where(m > 0, m / cap, jnp.float32(1.0))
    with traced_scope("comms:compress"):
        q = jnp.round(hist / scale).astype(jnp.int32)
    q = _reduce(q, axis_name, mode, scatter_dim)
    with traced_scope("comms:decompress"):
        return q.astype(jnp.float32) * scale


def comms_error_bound(comms_dtype: str, partitions: int,
                      max_abs: float) -> float:
    """Worst-case ABSOLUTE per-entry error the compressed merge can add
    to a histogram whose partials are bounded by `max_abs`, vs the exact
    f32 merge. The split-agreement contract tests hold measured
    deviations (and the gains derived from them) under this bound.

    - bf16: each of the P partials rounds once, relative error
      <= 2^-9 (8 mantissa bits + implicit) of that partial.
    - int32_fixed: each partial lands within half a grid step of its
      value (grid step = scale = max_abs / cap), plus the single f32
      rounding of the dequantized result (`int_sum * scale`), which is
      bounded by eps_f32 times the merged magnitude (<= P * max_abs)."""
    if comms_dtype == "f32":
        return 0.0
    if comms_dtype == "bf16":
        return partitions * max_abs * 2.0 ** -9
    if comms_dtype == "int32_fixed":
        cap = _FIXED_CAP // max(1, partitions)
        return (0.5 * partitions * max_abs / cap
                + partitions * max_abs * 2.0 ** -23)
    raise ValueError(f"unknown comms_dtype {comms_dtype!r}")


# --------------------------------------------------------------------- #
# split-winner combine (the reduce-scatter epilogue)
# --------------------------------------------------------------------- #

def combine_shard_winners(gains, feats, bins, dls, axis_name, *,
                          n_features: int, n_bins: int,
                          missing_bin: bool = False):
    """Combine per-shard best-split tuples into the global winner.

    Each shard ran the argmax over its own feature slab; `feats` are
    already GLOBAL indices. The payload is tiny — 4 x [n_level] per
    shard — and the tie-break is exact: maximum gain, ties broken by the
    smallest GLOBAL flattened candidate index (direction block first
    when missing_bin — RIGHT before LEFT — then feature, then bin),
    which is precisely jnp.argmax's first-occurrence rule on the
    single-device flattened gain table. Shard slab layout therefore
    cannot perturb split selection, interleaved slabs included."""
    if axis_name is None:
        return gains, feats, bins, dls
    with traced_scope("comms:winners"):
        ga = all_gather(gains, axis_name)          # [P, n_level]
        fa = all_gather(feats, axis_name)
        ba = all_gather(bins, axis_name)
        da = all_gather(dls, axis_name)
        # Global flattened candidate index (the single-device tie-break
        # key). int32 is safe: F < 2^19 and B <= 512 by the routing-pack
        # contract => 2*F*B < 2^29.
        flat = fa * n_bins + ba
        if missing_bin:
            flat = flat + da.astype(jnp.int32) * (n_features * n_bins)
        # Shards with a -inf slab winner (fully masked slab) must never
        # win; park their key past every real candidate.
        live = jnp.isfinite(ga)
        flat = jnp.where(live, flat, jnp.int32(2 ** 30))
        best_gain = jnp.max(ga, axis=0)
        tied = ga == best_gain[None, :]
        key = jnp.where(tied, flat, jnp.int32(2 ** 30))
        kmin = jnp.min(key, axis=0)
        # First axis-0 row matching the winning key (rows are distinct
        # per shard except exact candidate collisions, which cannot
        # happen: flat indices are globally unique per candidate).
        w = jnp.argmax(key == kmin[None, :], axis=0)
        take = lambda a: jnp.take_along_axis(a, w[None], axis=0)[0]  # noqa: E731
        return take(ga), take(fa), take(ba), take(da)


# --------------------------------------------------------------------- #
# resolution (the cfg.split_comms seam)
# --------------------------------------------------------------------- #

def resolve_split_comms(flag: str, *, distributed: bool,
                        feature_partitions: int = 1,
                        row_shards: "int | None" = None) -> str:
    """cfg.split_comms -> "allreduce" | "reduce_scatter" for this mesh.

    Since the 2D (rows x features) mesh landed (ROADMAP item 2),
    reduce-scatter split finding COMPOSES with a sharded feature axis:
    the scatter runs over the ROW axes *within* each feature slab (each
    of the Pr x Pf devices ends up with an F/(Pr*Pf) sub-slab) and the
    winner combine all_gathers over both axes — grow_tree wires it, so
    the old feature-sharded refusal is gone. `feature_partitions` is
    kept for signature compatibility; it no longer changes the answer.

    "auto" picks reduce_scatter exactly when a ROW wire exists —
    `row_shards` > 1 when the caller knows the row-axis extent (the
    hosts x rows product), else `distributed` as the legacy proxy. A
    pure feature mesh (Pr=1, Pf>1) has no row wire to scatter, so it
    resolves to allreduce (a size-1-axis scatter is an identity with
    extra ceremony). Forcing "reduce_scatter" without a row wire
    degrades to allreduce the same way."""
    if flag not in SPLIT_COMMS:
        raise ValueError(
            f"split_comms must be one of {SPLIT_COMMS}, got {flag!r}")
    if flag == "allreduce":
        return "allreduce"
    has_row_wire = (distributed if row_shards is None else row_shards > 1)
    return "reduce_scatter" if has_row_wire else "allreduce"


#: Auto slab count for the pipelined build+collective loop: enough
#: in-flight collectives to hide one DCN round-trip behind the next
#: slab's VPU work, few enough that per-slab kernels stay fat.
_AUTO_SLABS = 4


def resolve_comms_slabs(flag: int, *, distributed: bool,
                        platform: str | None = None) -> int:
    """cfg.hist_comms_slabs (0 = auto) -> the static slab count for the
    level loop's pipelined build+collective. Auto pipelines only on a
    real TPU mesh: that is where a wire exists to hide, and keeping the
    CPU suites on the monolithic path leaves their fixed-seed artifacts
    untouched (the phasing is bit-identical by construction — tested —
    but compile time isn't free). Explicit N >= 1 forces N everywhere
    (tests pipeline on the CPU mesh this way)."""
    if flag < 0:
        raise ValueError(f"hist_comms_slabs must be >= 0, got {flag}")
    if flag >= 1:
        return flag
    if not distributed:
        return 1
    if platform is None:
        platform = device.platform()
    return _AUTO_SLABS if platform == "tpu" else 1
