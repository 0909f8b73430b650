"""Device-truth cost observatory: XLA's own cost model, wired to the run log.

The telemetry stack answers *where* time goes (host wall-clock per phase,
per-partition skew); this module answers *why*: a slow `ddt:hist` round
could be HBM-bandwidth-bound, recompile-thrashed, or padding-bloated, and
a host clock alone cannot distinguish them. XLA's compiled-executable
`cost_analysis()` (FLOPs, bytes accessed) and `memory_analysis()`
(argument/output/temp HBM bytes) are the ground truth for what a compiled
program actually costs — GPU tree-boosting work (arXiv:1706.08359) shows
histogram building lives or dies on achieved memory bandwidth, and the
TPU compilation literature (arXiv:1810.09868) treats XLA's analyses as
the authoritative cost model. This module pulls those numbers at compile
time, joins them against the measured phase wall-times, and renders a
roofline verdict per phase: compute-bound / HBM-bound / recompile / host.

Three pieces:

- **costed(op, phase)** — a transparent wrapper for jit entry points
  (`CostedFn`). When a collector is ACTIVE (a run log is attached), the
  first top-level call with a new argument signature AOT-lowers and
  compiles the program (`fn.lower(*args).compile()` — the executable
  the call itself then reuses), records FLOPs / bytes-accessed /
  HBM-byte breakdown, and counts subsequent calls per signature. When no
  collector is active the wrapper is ONE module-global read per call —
  the hot path never lowers, never compiles, never syncs (guard-tested
  with the rest of the disabled-telemetry invariant). Calls made while
  tracing (the op riding inside a larger jit/shard_map program) are
  skipped: the enclosing program's own entry point carries the cost.
- **Collector / activate() / flush_into()** — per-run capture state. The
  trainers activate on telemetry runs, and `finish_run_log` flushes one
  schema-v3 `cost_analysis` event per (op, signature) — per-call FLOPs
  and bytes plus the observed call count — into the run log's epilogue.
- **roofline_table()** — the read side: join cost events against the
  run's `phase_timings` and the compile-time counters, compute achieved
  GFLOP/s and GB/s against the device's peak ceilings, and attach a
  bound-by verdict. Pure host math, no jax — a log reports anywhere
  (the report CLI contract).

Verdict semantics (documented, deliberately coarse): a phase whose
device utilization is visible (>= HOST_BOUND_UTIL on either axis) is
"compute" or "hbm" by which roofline axis it sits closer to; a phase the
device barely noticed is "host" (dispatch / host work dominated) —
upgraded to "recompile" when the run's cumulative backend-compile
wall-time (`counters.jit_compile_seconds`) claims more than
RECOMPILE_WALL_SHARE of the run, since compiles bill their wall time to
whichever phase first hit the fresh shape.
"""

from __future__ import annotations

try:
    import jax
except ImportError:               # jax-less host: capture never activates
    jax = None

#: Roofline ceilings by `device_kind` (as jax.devices()[0].device_kind
#: reports it): peak GFLOP/s, HBM GB/s and, for the comms row, per-chip
#: collective GB/s. "TPU v5 lite" is the v5e: 197 TFLOP/s bf16 and
#: 819 GB/s of HBM per chip (Google Cloud documentation, "TPU v5e");
#: `coll_gbs` is an order of magnitude for its ICI (the comms verdict asks
#: whether the wire binds at all, not which wire). "cpu" is NOT a
#: measurement of any host: a nominal row so that the CPU test suite's
#: logs render a table. A device that is not here has no peaks to divide
#: by — peaks_for raises; add the row with its source.
PEAK_CEILINGS: dict[str, dict] = {
    "TPU v5 lite": {"gflops": 197_000.0, "gbs": 819.0, "coll_gbs": 90.0},
    "cpu": {"gflops": 150.0, "gbs": 30.0, "coll_gbs": 10.0},
}

#: Below this utilization on BOTH roofline axes the device was mostly
#: idle during the phase — the phase is host/dispatch-bound.
HOST_BOUND_UTIL = 0.02
#: Run-level compile share above which idle-device phases are attributed
#: to recompilation rather than plain host work.
RECOMPILE_WALL_SHARE = 0.25

# ------------------------------------------------------------------ #
# collection
# ------------------------------------------------------------------ #

_active: "Collector | None" = None


class Collector:
    """Capture state for ONE telemetry run: (op, signature) -> record."""

    def __init__(self):
        self.records: dict[tuple, dict] = {}

    def on_call(self, op: str, phase: str, fn, args, kwargs) -> None:
        if not _host_context(args):
            return                      # riding inside a traced program
        key = (op, _signature(args, kwargs))
        rec = self.records.get(key)
        if rec is not None:
            rec["calls"] += 1
            return
        rec = {"op": op, "phase": phase, "calls": 1,
               "signature": _sig_str(key[1])}
        rec.update(_capture(fn, args, kwargs))
        self.records[key] = rec

    def events(self) -> list[dict]:
        """Flushable cost_analysis payloads, op-sorted for stable logs."""
        return [dict(r) for r in sorted(
            self.records.values(),
            key=lambda r: (r["op"], r["signature"]))]


def activate() -> "Collector | None":
    """Install a fresh collector (telemetry-run prologue). Returns None
    on a jax-less host — every costed entry point is device code, so
    there is nothing to collect."""
    global _active
    if jax is None:
        return None
    _active = Collector()
    return _active


def deactivate(collector: "Collector | None") -> None:
    """Remove `collector` if it is still the active one (trainer
    epilogues/ownership shims call this in `finally`, so a crashed run
    cannot leak capture work into the next — possibly telemetry-less —
    run in the same process)."""
    global _active
    if collector is not None and _active is collector:
        _active = None


def flush_into(run_log, collector: "Collector | None") -> None:
    """Emit one `cost_analysis` event per captured (op, signature) —
    the finish_run_log epilogue's cost section."""
    if run_log is None or collector is None:
        return
    for rec in collector.events():
        run_log.emit("cost_analysis", **rec)


def _host_context(args) -> bool:
    """True when we are NOT inside a jax trace (lowering from within a
    trace is invalid, and an op called under an enclosing jit bills its
    cost to that program's entry point, not its own)."""
    try:
        if not jax.core.trace_state_clean():
            return False
    except AttributeError:      # older/newer jax: fall back to arg probe
        pass
    return not any(isinstance(a, jax.core.Tracer) for a in args)


def _sig_of(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("a", tuple(x.shape), str(x.dtype))
    if isinstance(x, (bool, int, float, str, type(None))):
        return ("v", x)
    return ("o", type(x).__name__)


def _signature(args, kwargs) -> tuple:
    return (tuple(_sig_of(a) for a in args),
            tuple(sorted((k, _sig_of(v)) for k, v in kwargs.items())))


def _sig_str(sig: tuple) -> str:
    """Human/JSON form of a signature: shapes only, the part a reader
    can act on ("hist at [1000000, 28] uint8 ...")."""
    parts = []
    for s in sig[0]:
        parts.append(f"{list(s[1])}:{s[2]}" if s[0] == "a" else str(s[1]))
    for k, s in sig[1]:
        parts.append(
            f"{k}={list(s[1])}:{s[2]}" if s[0] == "a" else f"{k}={s[1]}")
    return "(" + ", ".join(parts) + ")"


def _capture(fn, args, kwargs) -> dict:
    """AOT-lower + compile `fn` at `args` and extract XLA's cost and
    memory analyses. One extra backend compile per (op, signature),
    paid only on telemetry runs; failures degrade to a zeroed record
    carrying the error — cost capture must never fail a training run."""
    try:
        # This compile IS the program's one counted compile: jit keeps
        # the executable, and the call that follows finds it (no second
        # backend compile, so jit_compiles matches a telemetry-less
        # run's). Its wall time lands in the enclosing phase span, like
        # any first compile.
        compiled = fn.lower(*args, **kwargs).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = ca or {}
        rec = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            **_device_fields(),
        }
        try:
            ma = compiled.memory_analysis()
        except (NotImplementedError, RuntimeError, AttributeError):
            ma = None
        if ma is not None:
            for field, key in (("argument_size_in_bytes", "arg_bytes"),
                               ("output_size_in_bytes", "output_bytes"),
                               ("temp_size_in_bytes", "temp_bytes")):
                v = getattr(ma, field, None)
                if v is not None:
                    rec[key] = int(v)
        return rec
    except (TypeError, ValueError, RuntimeError, NotImplementedError,
            AttributeError, KeyError, OSError) as e:
        return {"flops": 0.0, "bytes_accessed": 0.0, **_device_fields(),
                "error": f"{type(e).__name__}: {e}"[:300]}


def _device_fields() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind}


class CostedFn:
    """Transparent cost-capturing wrapper around a jit entry point.

    Call semantics are untouched — the wrapped function runs exactly as
    before; attribute access (``.lower``, ``.clear_cache``, ...) passes
    through to the underlying jit object. The ONLY added work per call
    is one module-global read when no collector is active, or a dict
    lookup + integer add when one is."""

    __slots__ = ("_fn", "op", "phase", "__wrapped__")

    def __init__(self, fn, op: str, phase: str):
        self._fn = fn
        self.op = op
        self.phase = phase
        self.__wrapped__ = fn

    def __call__(self, *args, **kwargs):
        col = _active
        if col is not None:
            col.on_call(self.op, self.phase, self._fn, args, kwargs)
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_fn"), name)

    def __repr__(self):
        return f"CostedFn({self.op!r}, {self._fn!r})"


def costed(op: str, phase: str | None = None):
    """Decorator/wrapper factory: ``costed("hist", phase="hist")(jitted)``.
    `op` names the program in cost_analysis events; `phase` (default:
    `op`) is the run-log phase_timings name the roofline join keys on."""
    def wrap(fn):
        return CostedFn(fn, op, phase if phase is not None else op)

    return wrap


def analyze(fn, *args, **kwargs) -> dict:
    """One-shot explicit cost analysis of `fn` at `args`. `fn` may be a jit object (has .lower) or a
    plain traceable callable (jitted here). Returns the _capture record
    ({flops, bytes_accessed, platform, ...})."""
    if jax is None:
        return {"flops": 0.0, "bytes_accessed": 0.0, "platform": None,
                "device_kind": None, "error": "jax unavailable"}
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    return _capture(fn, args, kwargs)


# ------------------------------------------------------------------ #
# the read side: roofline join (pure host math — no jax)
# ------------------------------------------------------------------ #

def peaks_for(device_kind: str | None) -> dict:
    """PEAK_CEILINGS row of a device kind. An unknown (or unstamped)
    device is an error, never another device's peaks."""
    try:
        return PEAK_CEILINGS[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAK_CEILINGS)} (telemetry/costmodel.PEAK_CEILINGS "
            "— add the row with its source)") from None


def roofline_table(phases: list[dict], cost_events: list[dict],
                   counters: dict | None = None,
                   wallclock_s: float | None = None) -> list[dict]:
    """Join `phase_timings` records against `cost_analysis` events into
    roofline rows: achieved GFLOP/s and GB/s per phase vs the peak
    ceilings of the device the events name (`device_kind`; a log whose
    cost events name none, or one PEAK_CEILINGS lacks, raises), with a
    bound-by verdict.

    `phases` is PhaseTimer.as_json() (the run log's phase_timings);
    `cost_events` the run's cost_analysis records. Phases without cost
    data still get a row (verdict "host" — no device program was
    registered under that name; e.g. the streamed gain phase, which is
    NumPy split selection by design). The fused path's `grow_block`
    dispatch is async, so its row folds in the `fetch_tree` barrier that
    carries the block's device wallclock (and fetch_tree's own row is
    dropped)."""
    ms_by_phase = {p["phase"]: p for p in phases}
    ev_by_phase: dict[str, list] = {}
    device_kind = None
    for e in cost_events:
        ev_by_phase.setdefault(e.get("phase", e.get("op")), []).append(e)
        device_kind = device_kind or e.get("device_kind")
    peaks = peaks_for(device_kind)
    compile_s = float((counters or {}).get("jit_compile_seconds") or 0.0)
    compile_share = (compile_s / wallclock_s
                     if wallclock_s and wallclock_s > 0 else 0.0)

    rows = []
    for p in phases:
        name = p["phase"]
        if name == "fetch_tree" and "grow_block" in ms_by_phase:
            continue                      # folded into the grow_block row
        wall_ms = p["ms_total"]
        if name == "grow_block" and "fetch_tree" in ms_by_phase:
            wall_ms += ms_by_phase["fetch_tree"]["ms_total"]
        evs = ev_by_phase.get(name, [])
        flops = sum(e.get("flops", 0.0) * e.get("calls", 1) for e in evs)
        byts = sum(e.get("bytes_accessed", 0.0) * e.get("calls", 1)
                   for e in evs)
        row = {"phase": name, "ms": round(wall_ms, 1),
               "calls": p.get("calls"), "n_programs": len(evs)}
        if not evs or wall_ms <= 0 or (flops <= 0 and byts <= 0):
            row.update(gflops=None, gbs=None, flops_util=None,
                       hbm_util=None,
                       verdict="recompile"
                       if evs and compile_share > RECOMPILE_WALL_SHARE
                       else "host")
            rows.append(row)
            continue
        wall_s = wall_ms / 1e3
        gflops = flops / wall_s / 1e9
        gbs = byts / wall_s / 1e9
        uc = gflops / peaks["gflops"]
        ub = gbs / peaks["gbs"]
        if max(uc, ub) < HOST_BOUND_UTIL:
            verdict = ("recompile" if compile_share > RECOMPILE_WALL_SHARE
                       else "host")
        elif ub >= uc:
            verdict = "hbm"
        else:
            verdict = "compute"
        row.update(gflops=round(gflops, 2), gbs=round(gbs, 2),
                   flops_util=round(uc, 4), hbm_util=round(ub, 4),
                   verdict=verdict)
        rows.append(row)
    # Comms roofline row (ISSUE 10, docs/PERF.md "Histogram comms"): the
    # run's EFFECTIVE collective payload (counters.collective_bytes_est —
    # post-compression, post-scatter, subtraction-halved) against the
    # interconnect ceiling, attributed to the phase whose programs carry
    # the collective. Verdict "comms" when the wire's utilization rivals
    # or beats the carrying phase's HBM leg (the wire binds); else
    # "overlapped" — the latency is hidden behind compute, which is the
    # state the comms-lean split finding exists to reach.
    coll_bytes = float((counters or {}).get("collective_bytes_est") or 0.0)
    if coll_bytes > 0:
        carrier = next((r for r in rows
                        if r["phase"] in ("grow_block", "grow", "hist")
                        and r["ms"] > 0), None)
        if carrier is not None:
            gbs = coll_bytes / (carrier["ms"] / 1e3) / 1e9
            cu = gbs / peaks.get("coll_gbs", peaks["gbs"])
            verdict = ("comms"
                       if cu >= HOST_BOUND_UTIL
                       and cu >= (carrier.get("hbm_util") or 0.0)
                       else "overlapped")
            rows.append({
                "phase": "comms", "ms": carrier["ms"], "calls": None,
                "n_programs": 0, "gflops": None, "gbs": round(gbs, 2),
                "flops_util": None, "hbm_util": None,
                "coll_util": round(cu, 4), "verdict": verdict})
    rows.sort(key=lambda r: -r["ms"])
    return rows
