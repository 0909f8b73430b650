"""Tracing/profiling (SURVEY.md §5): phase breakdown + XLA profiler capture.

Two layers:
- PhaseTimer: lightweight host-side wallclock breakdown of the training
  phases the reference cares about (hist / allreduce / gain / predict). On
  TPU each phase must end with a device sync to be meaningful — pass
  utils/device.device_sync (bound to the phase's output) as the `sync`
  callable.
- trace(): context manager around jax.profiler.trace producing a
  TensorBoard/Perfetto trace directory with Pallas kernel timelines.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Callable


class PhaseTimer:
    """Accumulate wallclock per named phase; report ms + share."""

    def __init__(self, sync: Callable | None = None):
        self.totals: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self._sync = sync

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        if self._sync is not None:
            self._sync()
        self.totals[name] += time.perf_counter() - t0
        self.counts[name] += 1

    def as_json(self) -> list[dict]:
        """Stable JSON form of the phase breakdown, embedded verbatim in
        the telemetry run log's `phase_timings.phases` field
        (docs/OBSERVABILITY.md). The keys — phase, ms_total, ms_per_call,
        calls, share — are a COMPATIBILITY CONTRACT with external log
        consumers; extend, never rename."""
        total = sum(self.totals.values()) or 1.0
        return [
            {
                "phase": k,
                "ms_total": round(v * 1e3, 2),
                "ms_per_call": round(v * 1e3 / max(1, self.counts[k]), 3),
                "calls": self.counts[k],
                "share": round(v / total, 3),
            }
            for k, v in sorted(
                self.totals.items(), key=lambda kv: -kv[1]
            )
        ]

    def report(self) -> list[dict]:
        """Human-consumption twin of as_json() (same records; kept as the
        logging-oriented name the Driver has always exposed)."""
        return self.as_json()

    def log_report(self, logger) -> None:
        """The INFO-level phase table — one formatting home for every
        trainer that prints a breakdown (Driver, fit_streaming)."""
        for rec in self.as_json():
            logger.info("phase %-12s %8.2f ms total  %7.3f ms/call  "
                        "x%-5d %5.1f%%", rec["phase"], rec["ms_total"],
                        rec["ms_per_call"], rec["calls"],
                        100 * rec["share"])


@contextlib.contextmanager
def trace(log_dir: str):
    """jax.profiler capture: `with trace("/tmp/prof"): step()` then open in
    TensorBoard (or xprof) — shows XLA op + Pallas kernel timelines."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
