"""Paired A/B: does bagging tax the streamed pipeline at scale?

Round 5 made fit_streaming accept sampling (stateless counter masks
computed ON DEVICE per chunk). The expected marginal cost is ~zero —
one uint32 hash + f32 multiply per row against a histogram matmul —
and only the paired per-rep-ratio protocol can prove a null effect. Each bout trains the full config-5
miniature (5M x 64 pre-binned shards, device chunk cache ON, 2 trees
depth 3) end to end; arms differ ONLY in cfg.subsample.

Usage: python -u experiments/stream_bagged_ab.py [rows_millions] [reps]
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax  # noqa: E402

from ddt_tpu.backends.tpu import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

from ddt_tpu.backends import get_backend  # noqa: E402
from ddt_tpu.config import TrainConfig  # noqa: E402
from ddt_tpu.data import chunks as chunks_mod  # noqa: E402
from ddt_tpu.streaming import fit_streaming  # noqa: E402
from experiments.paired_protocol import paired_ab  # noqa: E402

FEATURES, N_CHUNKS, BINS, TREES, DEPTH = 64, 10, 63, 2, 3
WORK = "/tmp/ddt_stream_bagged_ab"


def main() -> None:
    rows = int(float(sys.argv[1]) * 1e6) if len(sys.argv) > 1 else 5_000_000
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    print(f"platform={jax.default_backend()} rows={rows}", flush=True)
    shard_dir = os.path.join(WORK, "shards")
    shutil.rmtree(shard_dir, ignore_errors=True)
    chunks_mod.shard_stress_chunks(shard_dir, rows, N_CHUNKS,
                                   n_features=FEATURES, seed=7,
                                   n_bins=BINS)
    src = chunks_mod.directory_chunks(shard_dir)

    def bout_for(subsample):
        cfg = TrainConfig(n_trees=TREES, max_depth=DEPTH, n_bins=BINS,
                          backend="tpu", subsample=subsample, seed=3)
        be = get_backend(cfg)

        def bout():
            t0 = time.perf_counter()
            ens = fit_streaming(src, src.n_chunks, cfg, backend=be,
                                device_chunk_cache=True)
            dt = time.perf_counter() - t0
            assert ens.n_trees == TREES
            return dt

        bout()                           # warm: compiles + fills cache
        return bout

    det = bout_for(1.0)
    bag = bout_for(0.8)
    res = paired_ab(det, bag, name_a="det", name_b="bagged", reps=reps,
                    sleep_s=5.0, scale=rows * (DEPTH + 1) * TREES / 1e6,
                    unit="Mrow-visits/s")
    print(json.dumps({"rows": rows,
                      "median_ratio_det_over_bagged": res["median"],
                      "q1": res["q1"], "q3": res["q3"]}), flush=True)


if __name__ == "__main__":
    main()
