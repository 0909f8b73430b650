"""Config-5 at this box's full capacity: a MEASURED out-of-core run
(round-3 verdict item 3). 20M rows x 64 features of pre-binned uint8
shards (1.28 GB on disk — 5.1 GB as the float32 matrix the in-memory
path would need) trained end to end with fit_streaming over
directory_chunks on the real chip, reporting:

  - streamed throughput per pass (rows/s of data visited) and s/tree
  - peak RSS vs the post-import baseline (the O(chunk) claim, witnessed
    at 20M rows; the 5M-row suite twin with hard assertions is
    tests/test_stream_scale.py)

Where the pipeline is transfer-bound the absolute rate measures the
LINK, not the kernels (which of the two binds on the chip: not measured).

Run: python -u experiments/stream_scale.py [rows] [features] [off]
(third arg "off" disables the device chunk cache — the round-4 A/B).
"""

import json
import os
import resource
import shutil
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax  # noqa: E402

from ddt_tpu.backends.tpu import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

import numpy as np  # noqa: E402

from ddt_tpu.backends import get_backend  # noqa: E402
from ddt_tpu.config import TrainConfig  # noqa: E402
from ddt_tpu.data import chunks as chunks_mod  # noqa: E402
from ddt_tpu.streaming import fit_streaming  # noqa: E402

ROWS = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000_000
FEATURES = int(sys.argv[2]) if len(sys.argv) > 2 else 64
N_CHUNKS, BINS, TREES, DEPTH = 40, 63, 2, 3
WORK = "/tmp/ddt_stream_scale"


def rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def main() -> None:
    print(f"platform={jax.default_backend()}  {ROWS}x{FEATURES}, "
          f"{N_CHUNKS} chunks, {TREES} trees depth {DEPTH}", flush=True)
    jax.devices()
    base = rss_mb()

    shard_dir = os.path.join(WORK, "shards")
    shutil.rmtree(shard_dir, ignore_errors=True)
    t0 = time.perf_counter()
    chunks_mod.shard_stress_chunks(shard_dir, ROWS, N_CHUNKS,
                                   n_features=FEATURES, seed=7,
                                   n_bins=BINS)
    t_shard = time.perf_counter() - t0
    print(f"sharded {ROWS * FEATURES / 1e9:.2f} GB in {t_shard:.0f}s "
          f"(rss {rss_mb():.0f} MB)", flush=True)

    cache = (sys.argv[3] if len(sys.argv) > 3 else "on") != "off"
    cfg = TrainConfig(n_trees=TREES, max_depth=DEPTH, n_bins=BINS,
                      backend="tpu")
    be = get_backend(cfg)
    src = chunks_mod.directory_chunks(shard_dir)
    t0 = time.perf_counter()
    ens = fit_streaming(src, src.n_chunks, cfg, backend=be,
                        device_chunk_cache=cache)
    t_train = time.perf_counter() - t0

    # Data visits per tree: one histogram pass per level + the leaf pass
    # (the round-start pred-update is folded into the first pass).
    passes = TREES * (DEPTH + 1)
    visited = passes * ROWS
    rec = {
        "rows": ROWS, "features": FEATURES, "n_chunks": N_CHUNKS,
        "bins": BINS, "trees": TREES, "depth": DEPTH,
        "device_chunk_cache": cache,
        "shard_s": round(t_shard, 1),
        "train_s": round(t_train, 1),
        "s_per_tree": round(t_train / TREES, 1),
        "passes": passes,
        "mrows_per_sec_per_pass": round(visited / t_train / 1e6, 3),
        "effective_h2d_mb_s": round(
            visited * FEATURES / t_train / 1e6, 1),
        "rss_baseline_mb": round(base, 1),
        "rss_peak_mb": round(rss_mb(), 1),
        "dataset_binned_mb": round(ROWS * FEATURES / 1e6, 1),
        "n_trees_grown": ens.n_trees,
    }
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
