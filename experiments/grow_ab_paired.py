"""Paired-ratio A/B: fused grow_tree dispatch at 255 vs 64 bins.

Round 3's interleaved grow A/B (grow_ab_bins.py) measured ~1.3x for the
64-bin opt-in at the whole-tree dispatch level; round 4's sweep-11
epilogue showed that protocol can still compare arms across
persistent wallclock bands. This re-measures the claim with
the amended protocol (docs/PERF.md round-4 addendum): per-rep PAIRED
ratios, arm order alternating every rep, pairs spread over minutes,
median reported (scaffolding: experiments/paired_protocol.py).

Measured 2026-07-30 (24 pairs): median 255b/64b = 1.281,
IQR [1.150, 1.407] — round-3's ~1.3x fused-dispatch claim CONFIRMED.

Run: python -u experiments/grow_ab_paired.py
"""
import functools
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from ddt_tpu.backends.tpu import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

import numpy as np  # noqa: E402

from experiments.paired_protocol import paired_ab  # noqa: E402
from ddt_tpu.backends import get_backend  # noqa: E402
from ddt_tpu.config import TrainConfig  # noqa: E402
from ddt_tpu.utils.device import device_sync  # noqa: E402

R, REPS, ITERS = 1_000_000, 24, 4


def main() -> None:
    rng = np.random.default_rng(0)
    g = rng.standard_normal(R).astype(np.float32)
    h = rng.random(R).astype(np.float32)
    arms = {}
    for bins in (255, 64):
        cfg = TrainConfig(n_trees=1, max_depth=6, n_bins=bins,
                          backend="tpu")
        be = get_backend(cfg)
        Xb = rng.integers(0, bins, (R, 28), dtype=np.uint8)
        args = (be.upload(Xb), be._put_rows(g), be._put_rows(h))
        _, delta = be.grow_tree(*args)
        device_sync(delta)                       # compile + first run
        arms[bins] = (be, args)

    def bout(bins):
        be, args = arms[bins]
        t0 = time.perf_counter()
        for _ in range(ITERS):
            _, delta = be.grow_tree(*args)
        device_sync(delta)
        return (time.perf_counter() - t0) / ITERS

    paired_ab(
        functools.partial(bout, 255), functools.partial(bout, 64),
        name_a="255b", name_b="64b", reps=REPS,
    )


if __name__ == "__main__":
    main()
