"""Sweep round 9 (round-2 verdict item 2): int8 one-hot operands and a
reduced-bin lane-packed variant, measured on the real chip.

Hypotheses under test:

1. **int8 one-hot**: the v5e MXU's int8 rate is 2x bf16. The bin one-hot
   is exactly representable in int8; if the [T, F*Bp] operand rides the
   int8 path while A keeps the f32/bf16 gradient weights, the dot gets
   cheaper. Suspicion: the MXU has no mixed int8 x bf16 mode — XLA will
   convert int8 -> bf16 first (extra VPU work, same dot). A pure
   int8 x int8 variant (A = UNWEIGHTED node one-hot; counts-only, NOT the
   kernel contract) bounds the best case the int8 path could ever give.

2. **Reduced-bin lane packing**: the kernel is VPU-bound on the one-hot
   build (2 ops x F x Bp per row; docs/PERF.md). The shipped padding rule
   pads Bp to >= 256 lanes even for small bin counts; at n_bins <= 128 a
   Bp = 128 layout halves the VPU work per row — the candidate opt-in
   speed knob for a 64-bin contract.

Run on the real TPU:  python experiments/hist_sweep9.py
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from ddt_tpu.utils.device import device_sync  # noqa: E402

R, F, N = 1_000_000, 28, 32
TILE_R = 512


def _kernel(xb_ref, a_ref, out_ref, *, n_feat, bins_pad, oh_dtype,
            acc_dtype):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    x = xb_ref[:]
    tile_r = x.shape[0]
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (tile_r, bins_pad), 1)
    slabs = [
        (x[:, f][:, None] == bin_iota).astype(oh_dtype)
        for f in range(n_feat)
    ]
    oh = jnp.concatenate(slabs, axis=1)
    out_ref[:] += jax.lax.dot_general(
        a_ref[:], oh, (((0,), (0,)), ((), ())),
        preferred_element_type=acc_dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "bins_pad", "oh_dtype", "a_dtype"))
def variant(Xb, g, h, ni, n_bins, bins_pad, oh_dtype, a_dtype):
    acc_dtype = jnp.int32 if a_dtype == jnp.int8 else jnp.float32
    active = ni >= 0
    idx = jnp.where(active, ni, 0).astype(jnp.int32)
    gz = jnp.where(active, g, 0.0)
    hz = jnp.where(active, h, 0.0)
    noh = jax.nn.one_hot(idx, N, dtype=jnp.float32)
    if a_dtype == jnp.int8:
        # counts-only bound: A is the unweighted node one-hot twice
        A = jnp.concatenate([noh, noh], axis=1).astype(jnp.int8)
    else:
        A = jnp.concatenate(
            [noh * gz[:, None], noh * hz[:, None]], axis=1
        ).astype(a_dtype)
    Xi = Xb.astype(jnp.int32)
    n_tiles = R // TILE_R
    out = pl.pallas_call(
        functools.partial(_kernel, n_feat=F, bins_pad=bins_pad,
                          oh_dtype=oh_dtype, acc_dtype=acc_dtype),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((TILE_R, F), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE_R, 2 * N), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((2 * N, F * bins_pad), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((2 * N, F * bins_pad), acc_dtype),
        interpret=jax.default_backend() != "tpu",
    )(Xi, A)
    return out


def run(name, n_bins, bins_pad, oh_dtype, a_dtype, iters=10, reps=5):
    rng = np.random.default_rng(0)
    # device_put ONCE — numpy inputs would re-upload ~40 MB per call
    # and time the H2D link instead of the kernel.
    Xb = jax.device_put(rng.integers(0, n_bins, (R, F), dtype=np.uint8))
    g = jax.device_put(rng.standard_normal(R).astype(np.float32))
    h = jax.device_put(rng.random(R).astype(np.float32))
    ni = jax.device_put(rng.integers(0, N, R).astype(np.int32))
    try:
        out = variant(Xb, g, h, ni, n_bins, bins_pad, oh_dtype, a_dtype)
        device_sync(out)
        dt = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = variant(Xb, g, h, ni, n_bins, bins_pad, oh_dtype,
                              a_dtype)
            device_sync(out)
            dt = min(dt, (time.perf_counter() - t0) / iters)
        print(f"{name:42s} {R / dt / 1e6:8.1f} Mrows/s   "
              f"{dt * 1e3:7.2f} ms")
    except Exception as e:
        print(f"{name:42s} FAILED: {type(e).__name__}: {str(e)[:120]}")


if __name__ == "__main__":
    print(f"platform={jax.default_backend()}  shape {R}x{F}, N={N}")
    run("dense 255b Bp=256 bf16 (shipped)", 255, 256, jnp.bfloat16,
        jnp.bfloat16)
    run("dense 255b Bp=256 OH=int8 A=bf16", 255, 256, jnp.int8,
        jnp.bfloat16)
    run("dense 255b Bp=256 int8xint8 (counts bound)", 255, 256, jnp.int8,
        jnp.int8)
    run("64b Bp=256 bf16 (shipped padding)", 64, 256, jnp.bfloat16,
        jnp.bfloat16)
    run("64b Bp=128 bf16 (lane-packed knob)", 64, 128, jnp.bfloat16,
        jnp.bfloat16)
    run("64b Bp=128 int8xint8 (counts bound)", 64, 128, jnp.int8,
        jnp.int8)
    run("32b Bp=128 bf16", 32, 128, jnp.bfloat16, jnp.bfloat16)
