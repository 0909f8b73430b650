"""The round-4 on-chip A/B protocol, as a shared harness.

Where wallclock sits in bands that persist across whole timing windows,
per-arm minimums — even interleaved — can compare arms across bands and
reverse a conclusion run to run. The robust procedure: time the arms as PAIRS with the order
alternating every rep, spread the pairs over minutes (sleep between so
the band state evolves), and report the MEDIAN of per-rep ratios — a
statistic invariant to any band state shared within a pair.

Every A/B experiment in this directory routes through paired_ab() so a
future protocol amendment lands in exactly one place.
"""

from __future__ import annotations

import time

import numpy as np


def paired_ab(
    bout_a,
    bout_b,
    *,
    name_a: str = "A",
    name_b: str = "B",
    reps: int = 24,
    sleep_s: float = 4.0,
    scale: float | None = None,
    unit: str = "ms",
) -> dict:
    """Run `reps` order-alternating (bout_a, bout_b) pairs; print per-rep
    times and ratios; return {"ratios", "median", "q1", "q3"}.

    Each bout_* is a zero-arg callable returning the measured seconds for
    one timing bout (the caller owns iters-per-bout and device syncs).
    `scale` renders times as scale/seconds (e.g. rows -> Mrows/s via
    scale=rows/1e6); None prints milliseconds. The reported ratio is
    time_a / time_b (>1 means B is faster)."""
    ratios = []
    for rep in range(reps):
        order = ((name_a, bout_a), (name_b, bout_b))
        if rep % 2:
            order = order[::-1]
        ts = {}
        for name, bout in order:
            ts[name] = bout()
        ratios.append(ts[name_a] / ts[name_b])

        def fmt(t):
            return (f"{scale / t:8.1f} {unit}" if scale is not None
                    else f"{t * 1e3:7.1f} ms")
        print(f"rep {rep:02d}  {name_a} {fmt(ts[name_a])}  "
              f"{name_b} {fmt(ts[name_b])}  "
              f"ratio({name_a}/{name_b}) {ratios[-1]:.3f}", flush=True)
        if rep + 1 < reps:
            time.sleep(sleep_s)
    med = float(np.median(ratios))
    q1, q3 = (float(q) for q in np.percentile(ratios, [25, 75]))
    verdict = (f"{name_b} faster" if med > 1.02
               else f"{name_a} faster" if med < 0.98 else "parity")
    print(f"\nmedian paired ratio {name_a}/{name_b} = {med:.3f}  "
          f"IQR [{q1:.3f}, {q3:.3f}]  ({verdict})", flush=True)
    return {"ratios": ratios, "median": med, "q1": q1, "q3": q3}
