"""Paired A/B: overlapped per-chunk D2H vs the old serial end fetch.

experiments/predict_phases.py measured the resident 10M x 1000 scoring
config at ~65% device->host fetch (the [10M] f32 score vector, on the
earlier host) paid SERIALLY after all compute. The round-5 predict path
(backends/tpu.py predict_raw, single-chip branch) starts every chunk's
host copy asynchronously so the link drains while later chunks compute.
This script times OLD (device-side concatenate + one blocking fetch)
against NEW (the shipped overlapped path) under the paired per-rep-ratio
protocol. Identical outputs are asserted before timing.

Usage: python experiments/predict_fetch_ab.py [rows_millions] [reps]
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

from ddt_tpu.backends import get_backend            # noqa: E402
from ddt_tpu.backends.tpu import (                  # noqa: E402
    enable_persistent_compile_cache)
from ddt_tpu.config import TrainConfig              # noqa: E402
from ddt_tpu.models.tree import empty_ensemble      # noqa: E402
from experiments.paired_protocol import paired_ab   # noqa: E402
from experiments.predict_phases import (            # noqa: E402
    B, DEPTH, F, N, T, build_model, device_batch)


def main():
    enable_persistent_compile_cache()
    rows_m = float(sys.argv[1]) if len(sys.argv) > 1 else 10.0
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    rows = int(rows_m * 1e6)
    feature, thr, is_leaf, leaf_value = build_model()
    ens = empty_ensemble(T, DEPTH, F, 0.1, 0.0, "logloss")
    ens.feature[:] = feature
    ens.threshold_bin[:] = thr
    ens.is_leaf[:] = is_leaf
    ens.leaf_value[:] = leaf_value
    Xd = device_batch(rows)
    be = get_backend(TrainConfig(backend="tpu", n_bins=B))
    chunk = be.PREDICT_ROW_CHUNK
    print(f"# rows={rows} chunk={chunk} platform={jax.default_backend()}",
          flush=True)

    fn, ens_dev = be._predict_fn(ens)

    def old_path():
        outs = [fn(*ens_dev, Xd[i:i + chunk])
                for i in range(0, rows, chunk)]
        return np.asarray(jnp.concatenate(outs))[:rows]

    new = be.predict_raw(ens, Xd)                   # warm + reference
    old = old_path()
    np.testing.assert_array_equal(old, new)
    print("# exactness: overlapped fetch == serial fetch, bitwise",
          flush=True)

    def bout(f):
        def g():
            t0 = time.perf_counter()
            f()
            return time.perf_counter() - t0
        return g

    res = paired_ab(bout(old_path), bout(lambda: be.predict_raw(ens, Xd)),
                    name_a="serial", name_b="overlap", reps=reps,
                    sleep_s=6.0, scale=rows / 1e6, unit="Mrows/s")
    print(json.dumps({"rows": rows,
                      "median_ratio_serial_over_overlap": res["median"],
                      "q1": res["q1"], "q3": res["q3"]}), flush=True)


if __name__ == "__main__":
    main()
