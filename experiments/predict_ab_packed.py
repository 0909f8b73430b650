"""Paired A/B: packed-bitword descent vs the shipped one-hot descent.

Candidate from the round-4 verdict's predict formulation round: the
shipped binned descent (ops/predict._descend_comp) selects the path bit
per level with a [R, Tc, 2^d] one-hot compare + AND + any — ~3*(2^D - 1)
VPU ops per (row, tree) across the levels. The candidate packs each
level's comparison bits into ONE uint32 lane per (row, tree) (2^d <= 32
bits for depth <= 6), then descends with a shift+mask per level:
~(2^D - 1) packing ops + 2*D bit ops — roughly a third of the VPU work,
same exact semantics (bit-identical leaf indices, asserted before
timing).

Both arms time the FULL 10M x 1000 volume with a scalar on-device
reduction (no D2H — the fetch is identical either way and would only
dilute the compute ratio this A/B exists to measure), under the paired
per-rep-ratio protocol (experiments/paired_protocol.py — the
statistic that survives run-to-run bands).

Usage: python experiments/predict_ab_packed.py [rows_millions] [reps]
"""

from __future__ import annotations

import functools
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402

from ddt_tpu.backends.tpu import enable_persistent_compile_cache  # noqa: E402
from ddt_tpu.ops.predict import (                   # noqa: E402
    _descend_comp, _effective_arrays)
from ddt_tpu.utils.device import device_sync        # noqa: E402
from experiments.paired_protocol import paired_ab   # noqa: E402
from experiments.predict_phases import (            # noqa: E402
    B, DEPTH, F, N, N_INT, ROW_CHUNK, T, TREE_CHUNK, build_model,
    device_batch)


def _comp_matrix(eff_feat, eff_thr, Xc):
    """The shared bf16 comparison-matrix precompute (ops/predict P1)."""
    Tc = eff_feat.shape[0]
    foh = (
        eff_feat[:, :N_INT, None]
        == jnp.arange(F, dtype=jnp.int32)[None, None, :]
    ).astype(jnp.bfloat16)
    colval = jax.lax.dot_general(
        Xc.astype(jnp.bfloat16), foh.reshape(Tc * N_INT, F),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.bfloat16,
    ).reshape(Xc.shape[0], Tc, N_INT)
    return colval > eff_thr[None, :, :N_INT].astype(jnp.bfloat16)


def _descend_packed(eff_feat, eff_thr, Xc, max_depth):
    """Candidate: per-level bitword packing + shift/mask descent."""
    comp = _comp_matrix(eff_feat, eff_thr, Xc)
    R, Tc = comp.shape[:2]
    words = []
    for d in range(max_depth):
        lo, w = (1 << d) - 1, 1 << d
        c = comp[:, :, lo:lo + w].astype(jnp.uint32)
        word = jnp.zeros((R, Tc), jnp.uint32)
        for n in range(w):
            word = word | (c[:, :, n] << np.uint32(n))
        words.append(word)
    k = jnp.zeros((R, Tc), jnp.uint32)
    for d in range(max_depth):
        bit = (words[d] >> k) & jnp.uint32(1)
        k = 2 * k + bit
    return k.astype(jnp.int32)


def volume_fn(descend, fd, td, ld, vd):
    """Full-volume scorer with `descend` plugged in; scalar output."""
    from experiments.predict_phases import _padded_effective

    featp, thrp, valp = _padded_effective(fd, td, ld, vd)

    @jax.jit
    def run(Xd):
        Xp = Xd.astype(jnp.int32).reshape(-1, ROW_CHUNK, F)

        def row_body(acc_r, xrc):
            def tree_body(acc, args):
                f, t, v = args
                k = descend(f, t, xrc, DEPTH)
                W = v.shape[1]
                noh = (k[:, :, None]
                       == jnp.arange(W, dtype=jnp.int32)[None, None, :])
                vals = jnp.sum(jnp.where(noh, v[None, :, :], 0.0), axis=-1)
                return acc + vals.sum(), None

            acc, _ = jax.lax.scan(tree_body, jnp.float32(0),
                                  (featp, thrp, valp))
            return acc_r + acc, None

        out, _ = jax.lax.scan(row_body, jnp.float32(0), Xp)
        return out

    return run


def main():
    enable_persistent_compile_cache()
    rows_m = float(sys.argv[1]) if len(sys.argv) > 1 else 10.0
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    rows = int(rows_m * 1e6) // ROW_CHUNK * ROW_CHUNK
    feature, thr, is_leaf, leaf_value = build_model()
    fd, td = jax.device_put(feature), jax.device_put(thr)
    ld, vd = jax.device_put(is_leaf), jax.device_put(leaf_value)
    Xd = device_batch(rows)
    print(f"# rows={rows} platform={jax.default_backend()}", flush=True)

    # Exactness gate before any timing: identical leaf indices on a chunk.
    ef, et, _, _ = _effective_arrays(fd, td, ld, vd, DEPTH)
    xc = Xd[:ROW_CHUNK].astype(jnp.int32)
    ka = _descend_comp(ef[:TREE_CHUNK], et[:TREE_CHUNK], xc, DEPTH)
    kb = _descend_packed(ef[:TREE_CHUNK], et[:TREE_CHUNK], xc, DEPTH)
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))
    print("# exactness: packed == one-hot descent, bitwise", flush=True)

    run_a = volume_fn(_descend_comp, fd, td, ld, vd)
    run_b = volume_fn(_descend_packed, fd, td, ld, vd)
    device_sync(run_a(Xd))
    device_sync(run_b(Xd))

    import time

    def bout(run):
        def f():
            t0 = time.perf_counter()
            device_sync(run(Xd))
            return time.perf_counter() - t0
        return f

    res = paired_ab(bout(run_a), bout(run_b), name_a="onehot",
                    name_b="packed", reps=reps, sleep_s=8.0,
                    scale=rows / 1e6, unit="Mrows/s")
    print(json.dumps({"rows": rows, "median_ratio_onehot_over_packed":
                      res["median"], "q1": res["q1"], "q3": res["q3"]}),
          flush=True)


if __name__ == "__main__":
    main()
