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
- account(root): where one root's time went, by span name, summing to the
  root's duration exactly; note_root(span, shape): what the owner of a
  root calls when it has ended (the scorer does, for `ddt:predict`), and
  slow_calls(): the noted roots that took both SLOW_SHARE and SLOW_NS
  longer than the median of the last SLOW_HISTORY calls of their shape,
  each kept with its account and logged as one warning
  (docs/OBSERVABILITY.md "Call records").
- traced_scope(name): jax.named_scope for use INSIDE traced code. The
  ops kernels wrap their hist/allreduce/gain/route/leaf/predict stages,
  which writes `ddt:<name>` into the `op_name` of every HLO instruction
  traced under it. A device timeline does NOT carry that name: on the
  chip an `XLA Ops` event is named by its instruction (`%fusion.1`,
  `%copy.5`; benchmark/tracefile.py). The bridge from scope to event is
  the instruction's NAME, and device_stages() is it: for each scoring
  program this process built, `{instruction: {"stage", "source", "op"}}`
  read from the optimized HLO of the executable that runs. The program
  registers how to get that text (stage_program) when it builds a
  model's scoring function; the map is made only when asked, once.

Both work without jax (the cpu-backend CLI contract: spans are still
recorded, the profiler half is skipped). What a span costs is measured,
not guessed: PERF.md section 6, PR 25.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import json
import logging
import os
import re
import statistics
import threading
import time

from ddt_tpu.telemetry.counters import HOST_COUNTERS

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

#: A noted root (note_root) is slow when it took BOTH this share and this
#: long more than the median of the last SLOW_HISTORY calls of its shape: a
#: serving micro-batch's jitter never reaches 20 ms, a 40 s call's never 5%.
SLOW_SHARE = 0.05
SLOW_NS = 20_000_000
SLOW_HISTORY = 8
#: Slow calls kept, and shapes whose history is (the oldest shape goes).
SLOW_RING = 64
SLOW_SHAPES = 256

log = logging.getLogger(__name__)

_ring: collections.deque = collections.deque(maxlen=SPAN_RING)
_slow: collections.deque = collections.deque(maxlen=SLOW_RING)
_history: dict = {}                # shape -> deque of its last durations
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


# ------------------------------------------------------------------ #
# a root's account, and the slow call kept
# ------------------------------------------------------------------ #

UNNAMED = "unnamed"


def account(root: dict) -> dict:
    """Where one root's time went: `{"name", "duration_ns", "self_ns":
    {span name without the prefix: ns}, "counts"}` of one root_spans()
    entry. Every instant of the root belongs to ONE span, the innermost
    open at it (of two siblings that overlap, the one that started last):
    for spans that nest, a span's duration minus what its children cover.
    The root's own share is UNNAMED, last: Python between the spans, and
    any pause that fell there. The values are whole nanoseconds and sum to
    `duration_ns` exactly. Pure: it reads the dict it is given."""
    lo, hi = root["start"], root["end"]
    by_id = {s["id"]: s for s in root["spans"]}
    depth: dict = {root["id"]: 0}

    def depth_of(s) -> int:
        if s["id"] not in depth:
            above = by_id.get(s["cause"])
            depth[s["id"]] = 1 if above is None else depth_of(above) + 1
        return depth[s["id"]]

    events = []                     # (time, opens, key); closes sort first
    names = {}
    for s in root["spans"]:
        a, b = max(s["start"], lo), min(s["end"], hi)
        if b > a or s["id"] == root["id"]:
            key = (depth_of(s), s["start"], s["id"])
            names[key] = (UNNAMED if s["id"] == root["id"]
                          else s["name"].removeprefix(PREFIX))
            events += [(a, 1, key), (b, 0, key)]
    events.sort()
    self_ns: dict = {}
    open_now: set = set()
    at = lo
    for t, opens, key in events:
        if t > at and open_now:
            name = names[max(open_now)]
            self_ns[name] = self_ns.get(name, 0) + t - at
        at = max(at, t)
        (open_now.add if opens else open_now.discard)(key)
    self_ns[UNNAMED] = self_ns.pop(UNNAMED, 0)          # last
    return {"name": root["name"], "duration_ns": hi - lo,
            "self_ns": self_ns, "counts": dict(root["counts"])}


def slow_calls() -> list:
    """The slow calls still kept (SLOW_RING), oldest first: each `{"name",
    "id", "start", "ms", "median_ms", "excess_ms", "shape", "account_ms",
    "longest", "pauses"}`, what the warning of its end printed."""
    return list(_slow)


def note_root(span: Span, shape: tuple) -> None:
    """A root has ended (its owner calls this after the `with` block):
    hold it to the last calls of its name and `shape`, the names of the
    root's counts that make two calls the same work; keep and log it if it
    is slow, and count it among them. The first call of a shape (it
    compiles) is neither compared nor counted, nor is a span that is not a
    root (the call ran inside another's span: the account is that one's)."""
    if span.cause is not None:
        return
    key = (span.name, *(span.counts.get(k) for k in shape))
    past = _history.get(key)
    if past is None:
        if len(_history) >= SLOW_SHAPES:    # tuple(): whole under the GIL
            _history.pop(tuple(_history)[0], None)
        _history[key] = collections.deque(maxlen=SLOW_HISTORY)
        return
    took = span.end - span.start
    if past:
        median = statistics.median(past)
        if took - median > max(SLOW_NS, SLOW_SHARE * median):
            _keep_slow(span, shape, median)
    past.append(took)


def _keep_slow(span: Span, shape: tuple, median: float) -> None:
    spans = [s.as_dict() for s in list(_ring) if s.root == span.id]
    root = dict(span.as_dict(), spans=spans)
    found = account(root)
    longest = sorted((s for s in spans if s["id"] != span.id),
                     key=lambda s: s["start"] - s["end"])[:3]
    took = span.end - span.start
    rec = {
        "name": span.name, "id": span.id, "start": span.start,
        "ms": took / 1e6, "median_ms": median / 1e6,
        "excess_ms": (took - median) / 1e6,
        "shape": {k: span.counts.get(k) for k in shape},
        "account_ms": {k: v / 1e6 for k, v in found["self_ns"].items()},
        "longest": [{"name": s["name"].removeprefix(PREFIX),
                     "ms": (s["end"] - s["start"]) / 1e6,
                     **{k: s["counts"][k] for k in ("chunk", "piece")
                        if k in s["counts"]}} for s in longest],
        # what tells a pause of the process from a wait for the link or
        # the device: the host's pauses over the call
        "pauses": {k: span.counts[k] for k in HOST_COUNTERS
                   if k in span.counts}}
    _slow.append(rec)
    log.warning("slow call %s", json.dumps(rec))


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


# ------------------------------------------------------------------ #
# device stages: which stage of the program an HLO instruction belongs to
# ------------------------------------------------------------------ #

#: The stage of an instruction whose `op_name` holds no `ddt:<phase>:<stage>`
#: scope: parameters, constants, the copies the compiler adds, and whatever a
#: later change traces outside every stage (chip_smoke.py fails on that).
UNSCOPED = "unscoped"
#: In a program's map, the entry of EVERY instruction of a program that is
#: one stage as a whole (the chunk loop's slice and reshape programs).
WHOLE_PROGRAM = "*"

# program name -> a callable giving its optimized HLO text, or the ready
# map of a whole-program stage; the newest registration of a name wins
_stage_programs: dict = {}
_stage_maps: dict = {}             # program name -> its map, once made

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))) + os.sep
_STAGE = re.compile(r"(?:^|/)" + PREFIX + r"(\w+:[^/]+)")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[^\s=]+) = .*?[\s)]([a-z][\w-]*\([^)]{0,48})")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^\s(]+) \(.*\{$")
_TABLE_ROW = re.compile(r"^(\d+) (.*)$")
_METADATA = re.compile(r' metadata=\{((?:[^"}]|"[^"]*")*)\}')


def stage_program(name: str, hlo=None, *, stage: str | None = None,
                  source: str = "") -> None:
    """Name the device stages of program `name` (`jit_<function>`, as a
    device trace's `XLA Modules` line spells it). Either `hlo`, a
    zero-argument callable that returns the optimized HLO text of the
    executable that runs (`jitted.lower(...).compile().as_text()`): it is
    called when device_stages() is first asked, never before; or `stage`,
    for a program that is one stage as a whole. A dict write: nothing is
    lowered here."""
    _stage_programs[name] = hlo if stage is None else {
        WHOLE_PROGRAM: {"stage": stage, "source": source, "op": "program"}}
    _stage_maps.pop(name, None)


def device_stages() -> dict:
    """`{program: {instruction: {"stage", "source", "op"}}}` for every
    program named through stage_program(): `stage` is the innermost
    `ddt:<phase>:<stage>` scope of the instruction's `op_name` without the
    prefix (`predict:widen`) or UNSCOPED, `source` the `file:line` that
    traced it (relative to the checkout; "" where the compiler made the
    instruction), `op` its opcode and the start of its operands
    (`copy(%Xc.1)`). A fusion is what its own metadata says.
    Made on the first call that finds the program registered (a lowering,
    a compile that jit's own cache or the persistent one serves, a parse)
    and kept until the name is registered again."""
    for name, hlo in list(_stage_programs.items()):
        if name not in _stage_maps:
            _stage_maps[name] = hlo if isinstance(hlo, dict) \
                else stages_of_hlo(hlo())
    return dict(_stage_maps)


def stages_of_hlo(text: str) -> dict:
    """device_stages()'s map of one program from its HLO text. The text
    names a source either inline (`source_file=... source_line=...`) or by
    `stack_frame_id`, an index into the tables at its head. The scalar
    computation a reduction applies (`to_apply=`) is part of its reduce
    instruction, never an operation of its own: left out."""
    tables: dict = {}              # "FileNames" -> {id: the row's text}
    table = None
    applied = set(re.findall(r"to_apply=(%[^\s,)]+)", text))
    skip = False                   # inside an applied computation
    out = {}
    for line in text.splitlines():
        if table is not None:      # inside one of the head's tables
            row = _TABLE_ROW.match(line)
            if row:
                table[int(row.group(1))] = row.group(2)
                continue
            table = None
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = tables[line] = {}
            continue
        found = _INSTRUCTION.match(line)
        if not found:
            opens = _COMPUTATION.match(line)
            if opens:
                skip = opens.group(1) in applied
            continue
        if skip:
            continue
        meta = _METADATA.search(line)
        meta = meta.group(1) if meta else ""
        scopes = _STAGE.findall(_field(meta, "op_name"))
        out[found.group(1)] = {
            "stage": scopes[-1] if scopes else UNSCOPED,
            "source": _source(meta, tables), "op": found.group(2) + ")"}
    return out


def _field(meta: str, key: str) -> str:
    found = re.search(r"(?<!\w)" + key + r'=(?:"([^"]*)"|(\d+))', meta)
    return (found.group(1) or found.group(2) or "") if found else ""


def _source(meta: str, tables: dict) -> str:
    name, line = _field(meta, "source_file"), _field(meta, "source_line")
    frame = _field(meta, "stack_frame_id")
    if not name and frame:
        try:
            location = _field(tables["StackFrames"][int(frame)],
                              "file_location_id")
            where = tables["FileLocations"][int(location)]
            name = tables["FileNames"][int(_field(where, "file_name_id"))]
            name, line = name.strip('"'), _field(where, "line")
        except (KeyError, ValueError):
            return ""
    if not name:
        return ""
    if name.startswith(_REPO):
        name = name[len(_REPO):]
    return f"{name}:{line}"


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
