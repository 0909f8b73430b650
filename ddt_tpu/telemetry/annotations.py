"""Host/device phase annotations sharing ONE naming scheme: `ddt:<phase>`.

Two halves of the Perfetto-alignment story (docs/OBSERVABILITY.md):

- phase_span(name, **counts): the ONE host-side span. Entering it enters
  a jax.profiler.TraceAnnotation `ddt:<name>` (seen in a profiler
  capture that has the host tracer on) AND records the span in memory:
  name, start and end by time.perf_counter_ns(), its own id, the id of
  the span that caused it (the enclosing span of the same thread; a
  span opened on another thread is a root), the id of its root, and a
  small dict of counts. Finished spans go into one process-wide ring
  (SPAN_RING entries, oldest dropped); recent_spans() / root_spans()
  read it. Nothing is written to disk, no option switches it: like
  telemetry.counters it is always on and bounded. The Driver enters it
  around each PhaseTimer phase (`ddt:grow`, `ddt:eval`, ...), the scorer
  around each step of TPUDevice.predict_raw (`ddt:predict`,
  `ddt:predict:upload`, ...; the table is in docs/OBSERVABILITY.md).
- traced_scope(name): jax.named_scope for use INSIDE traced code. The
  ops kernels wrap their hist/allreduce/gain/route/leaf/predict stages,
  which names the lowered XLA ops — the device timeline then carries
  the same `ddt:` prefixes and lines up under the host spans.

Both work without jax (the cpu-backend CLI contract: spans are still
recorded, the profiler half is skipped). What a span costs is measured,
not guessed: PERF.md section 6, PR 25.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import threading
import time

try:
    import jax
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except ImportError:               # jax-less host: annotations are no-ops
    jax = None
    _TraceAnnotation = None

PREFIX = "ddt:"


#: Finished spans kept, process-wide. Three 50-chunk scoring calls are
#: about 320 spans; a serving call records five.
SPAN_RING = 8192
#: (time.perf_counter_ns(), time.time_ns()) taken together at import: a
#: reader turns any span into wall-clock time with
#: `span.start - SPAN_ANCHOR[0] + SPAN_ANCHOR[1]`.
SPAN_ANCHOR = (time.perf_counter_ns(), time.time_ns())

_ring: collections.deque = collections.deque(maxlen=SPAN_RING)
_ids = itertools.count(1)          # next() is one bytecode: GIL-atomic
_open = threading.local()          # .stack: this thread's open spans


class Span:
    """One host span; use through phase_span(). `counts` may be filled
    while the span is open (`with phase_span("x") as s: s.counts[...]`)."""

    __slots__ = ("name", "id", "cause", "root", "start", "end", "counts",
                 "_annotation")

    def __init__(self, name: str, counts: dict):
        self.name = PREFIX + name
        self.counts = counts

    def __enter__(self) -> "Span":
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        self.id = next(_ids)
        if stack:
            cause = stack[-1]
            self.cause, self.root = cause.id, cause.root
        else:
            self.cause, self.root = None, self.id
        stack.append(self)
        if _TraceAnnotation is None:
            self._annotation = None
        else:
            self._annotation = _TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _open.stack.pop()
        _ring.append(self)
        return False

    def as_dict(self) -> dict:
        return {"name": self.name, "id": self.id, "cause": self.cause,
                "root": self.root, "start": self.start, "end": self.end,
                "counts": dict(self.counts)}


def phase_span(name: str, **counts) -> Span:
    """Host span `ddt:<name>`: a profiler annotation and an in-memory
    record in one context manager (see the module docstring)."""
    return Span(name, counts)


def recent_spans() -> list:
    """The ring's finished spans as plain dicts, oldest first by start
    (a span lands in the ring when it ENDS, so children precede their
    parents there). Times are time.perf_counter_ns()."""
    return sorted((s.as_dict() for s in list(_ring)),
                  key=lambda d: d["start"])


def root_spans(name: str) -> list:
    """Finished root spans named `ddt:<name>` (`root_spans("predict")`:
    one per TPUDevice.predict_raw call still in the ring), oldest first;
    each dict gains "spans": every finished span of that root, itself
    included."""
    spans = recent_spans()
    by_root: dict = {}
    for d in spans:
        by_root.setdefault(d["root"], []).append(d)
    return [dict(d, spans=by_root[d["id"]]) for d in spans
            if d["id"] == d["root"] and d["name"] == PREFIX + name]


def traced_scope(name: str):
    """Named scope `ddt:<name>` for code under jit (no-op without jax)."""
    if jax is None:
        return contextlib.nullcontext()
    return jax.named_scope(PREFIX + name)


def op_scope(name: str):
    """Whole-function traced_scope as a decorator — the canonical fix for
    ddtlint's `named-scope` rule on op ENTRY POINTS whose entire body is
    one pipeline stage (a `with` block would just re-indent the full
    function). Composes under jit: place it BELOW the @jit/@partial(jax.
    jit, ...) decorator; functools.wraps preserves the signature, so
    static_argnames keep resolving. Trace-time-only indirection — the
    lowered HLO carries `ddt:<name>` metadata and the runtime never sees
    the wrapper again after compilation."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with traced_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def phase_ctx(timer):
    """Phase-context factory — the ONE home of the PhaseTimer +
    phase_span pairing, shared by the Driver's granular and fused loops
    and both streaming loops (keeping span naming/ordering from
    diverging between trainers). `timer` is a utils.profiling.PhaseTimer
    or None; with None the factory returns bare nullcontexts so
    disabled-telemetry hot loops stay unannotated."""
    if timer is None:
        def ph(name):
            return contextlib.nullcontext()
    else:
        def ph(name):
            stack = contextlib.ExitStack()
            stack.enter_context(phase_span(name))
            stack.enter_context(timer.phase(name))
            return stack
    return ph
