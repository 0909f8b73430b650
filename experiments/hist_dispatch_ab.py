"""Paired A/B: is the headline hist metric's band swing dispatch jitter?

The round-4 verdict's standing complaint: the headline 255-bin number
swung 40-64 Mrows/s across run-to-run bands on the earlier host, so
the captured artifact is "band luck". The bench already amortizes
dispatch (10 async dispatches, one sync), but each dispatch is still a
host round-trip.
Hypothesis to kill or confirm: a ONE-dispatch variant — K kernel
invocations inside a single jitted lax.fori_loop, two round-trips total
— removes per-dispatch jitter; if its per-rep spread is much tighter
than the dispatch-loop's IN THE SAME WINDOW, the band story is partly
dispatch-side and a band-stable headline metric exists; if the spreads
match, the bands are device/runtime execution-rate variance and the
sealed diagnosis stands with direct evidence.

Method: interleaved reps (A, B, A, B, ...) of
  A: bench-style loop of K async dispatches + one device_sync;
  B: jit(fori_loop(K, hist ∘ perturb)) + one device_sync
with a data dependence (g advanced by a tiny function of the previous
histogram) so XLA cannot hoist the loop body. Same inputs, same shapes
as bench.py's headline arm (1M x 28, 255 bins, 32 nodes).

Usage: python experiments/hist_dispatch_ab.py [reps] [K]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402

from ddt_tpu.backends.tpu import (                  # noqa: E402
    enable_persistent_compile_cache)
from ddt_tpu.ops import histogram as hist_ops       # noqa: E402


def main():
    enable_persistent_compile_cache()
    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 12
    K = int(sys.argv[2]) if len(sys.argv) > 2 else 10
    R, F, B, N = 1_000_000, 28, 255, 32

    rng = np.random.default_rng(0)
    Xb = jnp.asarray(rng.integers(0, B, (R, F), np.uint8))
    g0 = jnp.asarray(rng.standard_normal(R).astype(np.float32))
    h = jnp.asarray((rng.random(R) + 0.5).astype(np.float32))
    ni = jnp.asarray(rng.integers(0, N, R).astype(np.int32))

    def hist(g):
        return hist_ops.build_histograms(Xb, g, h, ni, N, B)

    one = jax.jit(hist)

    @jax.jit
    def k_in_one(g):
        def body(_, carry):
            g2, acc = carry
            out = hist_ops.build_histograms(Xb, g2, h, ni, N, B)
            s = out[0, 0, 0, 0] * jnp.float32(1e-30)   # cheap dependence
            return g2 + s, acc + s
        return jax.lax.fori_loop(0, K, body, (g, jnp.float32(0.0)))[1]

    # Warm both programs.
    float(jnp.sum(one(g0)))
    float(k_in_one(g0))

    rows_a, rows_b = [], []
    for rep in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(K):
            out = one(g0)
        float(jnp.sum(out))
        dt_a = (time.perf_counter() - t0) / K

        t0 = time.perf_counter()
        float(k_in_one(g0))
        dt_b = (time.perf_counter() - t0) / K

        a, b = R / dt_a / 1e6, R / dt_b / 1e6
        rows_a.append(a)
        rows_b.append(b)
        print(f"rep {rep:02d}  dispatch-loop {a:6.1f} Mrows/s   "
              f"one-dispatch {b:6.1f} Mrows/s", flush=True)

    def stats(v):
        v = np.array(v)
        return dict(median=round(float(np.median(v)), 2),
                    q1=round(float(np.percentile(v, 25)), 2),
                    q3=round(float(np.percentile(v, 75)), 2),
                    spread_pct=round(100 * (v.max() - v.min())
                                     / np.median(v), 1))

    rec = {"dispatch_loop": stats(rows_a), "one_dispatch": stats(rows_b),
           "reps": reps, "K": K}
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
