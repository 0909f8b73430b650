"""Phase breakdown of the resident 10M x 1000-tree scoring config.

Round-4 verdict item 1: predict (BASELINE config 4) never had a perf
round — no phase breakdown, no formulation A/B under the paired
protocol. This script produces the breakdown that decides where any
optimisation effort goes:

  P1 comp-matrix : per (row-chunk, tree-chunk), the bf16 one-hot matmul
                   colval = Xc . onehot(feat) and the > threshold compare
                   (ops/predict._descend_comp's precompute)
  P2 descent     : + the 6-level one-hot path-bit selection
  P3 leaf-select : + bottom-level one-hot leaf-value select
  P4 full-compute: the real predict_raw, result REDUCED on device (no
                   vector fetch) — adds the class-scatter matmul + scan
                   plumbing over P3
  P5 full+D2H    : predict_raw with the [10M] f32 scores fetched to host
                   (the bench's resident arm) — P5 - P4 is the D2H
                   share, the part no kernel work can move

Each phase program runs the whole 10M x 1000 volume (row chunks x tree
chunks under lax.scan, identical chunking to predict_raw) and returns a
scalar, so inter-phase deltas isolate the added stage. The input batch
is GENERATED ON DEVICE (random bins — traversal cost is data-blind):
uploading 280 MB would add time and nothing else. Timings are
min-of-reps with device_sync; phase RATIOS within one run share the
band, so the
breakdown is meaningful even when absolute Mrows/s drifts.

Usage: python experiments/predict_phases.py [rows_millions]
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402

from ddt_tpu.backends.tpu import enable_persistent_compile_cache  # noqa: E402
from ddt_tpu.ops.predict import (                   # noqa: E402
    _descend_comp, _effective_arrays, predict_raw)
from ddt_tpu.utils.device import device_sync        # noqa: E402

T, DEPTH, F, B = 1000, 6, 28, 255
TREE_CHUNK, ROW_CHUNK = 64, 8192
N = 2 ** (DEPTH + 1) - 1
N_INT = (1 << DEPTH) - 1


def build_model(seed=0):
    rng = np.random.default_rng(seed)
    feature = rng.integers(0, F, size=(T, N)).astype(np.int32)
    thr = rng.integers(0, B - 1, size=(T, N)).astype(np.int32)
    is_leaf = np.zeros((T, N), bool)
    is_leaf[:, N // 2:] = True
    leaf_value = rng.standard_normal((T, N)).astype(np.float32)
    return feature, thr, is_leaf, leaf_value


def device_batch(rows, seed=0):
    """Random binned batch generated ON device (skips the upload)."""
    @jax.jit
    def gen(key):
        return jax.random.randint(key, (rows, F), 0, B, dtype=jnp.int32
                                  ).astype(jnp.uint8)
    x = gen(jax.random.PRNGKey(seed))
    device_sync(x)
    return x


def _padded_effective(feature, thr, is_leaf, leaf_value):
    """predict_raw's tree padding (all-leaf value-0 trees to a TREE_CHUNK
    multiple) + leaf pushdown, reshaped into tree chunks."""
    T_ = feature.shape[0]
    n_tc = -(-T_ // TREE_CHUNK)
    tpad = n_tc * TREE_CHUNK - T_

    def pad_t(a, fill=0):
        return jnp.pad(a, ((0, tpad), (0, 0)), constant_values=fill)

    ef, et, ev, _ = _effective_arrays(
        pad_t(feature, -1), pad_t(thr), pad_t(is_leaf, True),
        pad_t(leaf_value), DEPTH)
    featp = ef.reshape(n_tc, TREE_CHUNK, -1)
    thrp = et.reshape(n_tc, TREE_CHUNK, -1)
    valp = ev[:, N_INT:].reshape(n_tc, TREE_CHUNK, -1)
    return featp, thrp, valp


@functools.partial(jax.jit, static_argnames=("stage",))
def staged(feature, thr, is_leaf, leaf_value, Xc, *, stage):
    """predict_raw's exact chunking with the per-tree-chunk body cut at
    `stage`; returns a f32 scalar so nothing row-sized leaves the chip."""
    Xc = Xc.astype(jnp.int32)
    R = Xc.shape[0]
    featp, thrp, valp = _padded_effective(feature, thr, is_leaf,
                                          leaf_value)
    n_rc = R // ROW_CHUNK
    Xp = Xc.reshape(n_rc, ROW_CHUNK, F)

    def row_body(acc_r, xrc):
        def tree_body(acc, args):
            f, t, v = args
            if stage == "comp":
                foh = (f[:, :N_INT, None] == jnp.arange(
                    F, dtype=jnp.int32)[None, None, :]).astype(jnp.bfloat16)
                colval = jax.lax.dot_general(
                    xrc.astype(jnp.bfloat16),
                    foh.reshape(TREE_CHUNK * N_INT, F),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.bfloat16,
                ).reshape(ROW_CHUNK, TREE_CHUNK, N_INT)
                comp = colval > t[None, :, :N_INT].astype(jnp.bfloat16)
                return acc + comp.sum(dtype=jnp.float32), None
            k = _descend_comp(f, t, xrc, DEPTH)
            if stage == "descend":
                return acc + k.sum().astype(jnp.float32), None
            W = v.shape[1]
            noh = (k[:, :, None]
                   == jnp.arange(W, dtype=jnp.int32)[None, None, :])
            vals = jnp.sum(jnp.where(noh, v[None, :, :], 0.0), axis=-1)
            return acc + vals.sum(), None            # stage == "leaf"

        acc, _ = jax.lax.scan(tree_body, jnp.float32(0),
                              (featp, thrp, valp))
        return acc_r + acc, None

    out, _ = jax.lax.scan(row_body, jnp.float32(0), Xp)
    return out


def timed(fn, reps=5):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        device_sync(out)
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    enable_persistent_compile_cache()
    rows_m = float(sys.argv[1]) if len(sys.argv) > 1 else 10.0
    rows = int(rows_m * 1e6) // ROW_CHUNK * ROW_CHUNK
    feature, thr, is_leaf, leaf_value = build_model()
    fd = jax.device_put(feature)
    td = jax.device_put(thr)
    ld = jax.device_put(is_leaf)
    vd = jax.device_put(leaf_value)
    Xd = device_batch(rows)
    print(f"# rows={rows} trees={T} depth={DEPTH} "
          f"platform={jax.default_backend()}", flush=True)

    full = functools.partial(
        predict_raw, fd, td, ld, vd, Xd, max_depth=DEPTH,
        learning_rate=0.1, base=0.0, n_classes=1,
        tree_chunk=TREE_CHUNK, row_chunk=ROW_CHUNK)

    @jax.jit
    def full_nofetch(x):
        return predict_raw(fd, td, ld, vd, x, max_depth=DEPTH,
                           learning_rate=0.1, base=0.0, n_classes=1,
                           tree_chunk=TREE_CHUNK, row_chunk=ROW_CHUNK).sum()

    phases = {}
    # warm every program first (compiles), then time coldest-first
    for name in ("comp", "descend", "leaf"):
        device_sync(staged(fd, td, ld, vd, Xd, stage=name))
    device_sync(full_nofetch(Xd))
    np.asarray(full())

    for name in ("comp", "descend", "leaf"):
        phases[name] = timed(
            lambda n=name: staged(fd, td, ld, vd, Xd, stage=n))
    phases["full_nofetch"] = timed(lambda: full_nofetch(Xd))
    phases["full_d2h"] = timed(lambda: np.asarray(full()), reps=3)

    rec = {"rows": rows, "trees": T,
           **{k: round(v, 3) for k, v in phases.items()},
           "mrows_resident": round(rows / phases["full_d2h"] / 1e6, 2),
           "d2h_share": round(
               1 - phases["full_nofetch"] / phases["full_d2h"], 3)}
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
