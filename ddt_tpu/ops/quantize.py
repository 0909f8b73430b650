"""Device-side binning: the quantizer's transform as an XLA op.

Host binning (`BinMapper.transform`, NumPy searchsorted) costs seconds at
the 10M-row configs and serialises on one core. On device the same
semantics are a compare+sum: `searchsorted(edges, v, side='left')` equals
the count of edges strictly below v, so the device compute is sub-second
— but the f32 upload is 4 bytes/cell, so this path wins only when the
raw matrix is already device-side or the host link is fast enough
that the 4x upload does not dominate (on the chip: not measured).
Formula:

    bin = clip( sum_e [edges[f, e] < v], 0, n_value_bins - 1 )

with NaN routed to the reserved bin (missing_policy="learn") or bin 0 —
BIT-IDENTICAL to the host transform, including +/-inf, duplicate-edge
runs (dup bins are simply never produced by either form) and identity
(categorical) columns. Rows are processed in blocks via lax.map so the
[block, F, n_edges] compare stays a fused VMEM-resident transient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ddt_tpu.telemetry.annotations import op_scope


@functools.partial(
    jax.jit, static_argnames=("n_bins", "missing_bin", "row_block")
)
@op_scope("quantize")
def transform_binned(
    X: jax.Array,           # float32 [R, F] raw features (NaN allowed)
    edges: jax.Array,       # float32 [F, n_bins - 1] (trailing cols +inf)
    n_bins: int,
    missing_bin: bool = False,
    row_block: int = 8192,
) -> jax.Array:
    """uint8 [R, F] bin indices; device twin of BinMapper.transform."""
    R, F = X.shape
    nv = n_bins - 1 if missing_bin else n_bins
    e = edges[:, : nv - 1]                         # [F, nv-1]
    nan_bin = n_bins - 1 if missing_bin else 0

    def block(Xb):
        cmp = e[None, :, :] < Xb[:, :, None]       # [blk, F, nv-1]
        b = jnp.clip(cmp.sum(-1).astype(jnp.int32), 0, nv - 1)
        b = jnp.where(jnp.isnan(Xb), nan_bin, b)
        return b.astype(jnp.uint8)

    if R <= row_block:
        return block(X)
    pad = -R % row_block
    Xp = jnp.pad(X, ((0, pad), (0, 0))) if pad else X
    out = jax.lax.map(block, Xp.reshape(-1, row_block, F))
    return out.reshape(-1, F)[:R]


def transform_device(mapper, X: np.ndarray) -> np.ndarray:
    """Bin a float matrix on the default device; returns host uint8.
    Semantics identical to mapper.transform (tests assert bit-equality)."""
    X = np.asarray(X, np.float32)
    if X.ndim != 2 or X.shape[1] != mapper.n_features:
        raise ValueError(
            f"X must be [rows, {mapper.n_features}], got {X.shape}"
        )
    out = transform_binned(
        jnp.asarray(X), jnp.asarray(mapper.edges),
        n_bins=mapper.n_bins, missing_bin=mapper.missing_bin,
    )
    return np.asarray(out)
