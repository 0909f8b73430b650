"""Process-wide device counters behind the run log's `counters` event.

Four counters, each chosen because the literature says it is the silent
TPU perf killer the host wallclock alone cannot see:

- `jit_compiles` — every program XLA's backend BUILT OR LOADED FROM THE
  PERSISTENT CACHE, counted by a jax.monitoring listener on the
  `/jax/core/compile/backend_compile_duration` event: in this jax
  (0.9.0) the event wraps `compile_or_get_cached`, so it fires on a
  persistent-cache hit too, and `compile_cache_hits` (event
  `/jax/compilation_cache/cache_hits`) says how many of them were
  loads (recompiles from shape churn are the classic hidden cost:
  arXiv:1810.09868). The listeners install lazily
  (install_jax_listener) so a process that never attaches telemetry
  never registers them; once installed they are a host add per
  COMPILE — nothing per dispatch. The SAME listener accumulates
  `jit_compile_seconds` (cumulative wall time of that event: compile,
  or cache read and load) so the run log carries recompile COST, not
  just count — the roofline verdict's "recompile" leg reads it
  (telemetry/costmodel.py) — and `jit_trace_seconds` /
  `jit_lower_seconds`: tracing to a jaxpr and lowering to an MLIR
  module, which no cache skips (what a process's first call of a
  program pays even when `compile_cache_hits` covers every program).
- `h2d_bytes` / `d2h_bytes` — host↔device transfer bytes recorded at
  the backends' upload/fetch funnels (TPUDevice._put / fetch_tree and
  the fused tree-fetch). Approximate by design: scalar metric
  readbacks (~bytes) are not counted, the row-matrix and tree traffic
  that actually loads the host<->device link is.
- `collective_bytes_est` — ESTIMATED allreduce payload per round
  (hist_allreduce_bytes), recorded by the Driver only on distributed
  meshes. An estimate because the psum lives inside a fused device
  program where the host cannot observe the wire; the histogram shapes
  are static per config, so the estimate is exact up to XLA's own
  reduction scheduling.

All counters are monotonic process-wide integers; consumers take a
snapshot() at run start and publish delta() at run end, so concurrent
runs in one process each see their own traffic plus any overlap —
documented, not hidden (docs/OBSERVABILITY.md).

THE HOST'S PAUSES (HOST_COUNTERS, host_pauses()) are NOT in that registry:
they are read at the two ends of one call and their movement rides on the
call's root span (TPUDevice.predict_raw), to tell a pause of the process
from a wait for the link or the device. `cpu_ns`: the CPU time of all the
process's threads (time.process_time_ns(); against a call's wall it shows
the host relayout threads' work, and a process that was not running);
`gc_pause_ns`, `gc_collections`, `gc_gen2_collections`: counted by ONE
gc.callbacks hook (two perf_counter_ns a collection; every thread waits
for a collection, the interpreter's lock is held). The registry is what
statusd scrapes, and a scrape changes nothing and reads the same twice:
a clock, or a count the collector moves between two scrapes, has no place
in it. A thread's run-queue wait, the machine's CPU pressure and
getrusage's switches and faults are not read at all: the hosts the
benchmark runs on have neither /proc file and report the three as 0
(PERF.md section 6, PR 52).

`device_peak_bytes()` reads the accelerator's high-water mark from
device.memory_stats() where the platform exposes one (TPU/GPU; CPU XLA
returns None).
"""

from __future__ import annotations

import gc
import time

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# duration event -> the float counter that accumulates its seconds
_DURATION_COUNTERS = {
    _COMPILE_EVENT: "jit_compile_seconds",
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower_seconds",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# Monotonic process-wide counters (plain ints: the GIL makes += atomic
# enough for counting; these feed reports, not invariants).
_c = {
    "jit_compiles": 0,
    # Cumulative backend-compile WALL TIME (seconds, float) from the same
    # jax.monitoring listener: the recompile COUNT says the silent killer
    # is present, the seconds say what it costs — a run whose compile
    # seconds rival a phase's wall time is recompile-bound no matter how
    # healthy its kernels are (the roofline verdict in
    # telemetry/costmodel.py reads exactly this).
    "jit_compile_seconds": 0.0,
    # Tracing and lowering, which the persistent cache cannot skip, and
    # how many of `jit_compiles` were loads from it (module docstring).
    "jit_trace_seconds": 0.0,
    "jit_lower_seconds": 0.0,
    "compile_cache_hits": 0,
    "h2d_bytes": 0,
    "d2h_bytes": 0,
    "collective_bytes_est": 0,
    # Device-resident CompiledEnsemble cache hits (TPUDevice._predict_fn):
    # a hit skips the per-call pushdown + ensemble re-upload (a miss is
    # the `ddt:predict:ensemble` span: 18 ms for 1000 trees of depth 6 on
    # the v5e, 0.08% of a 100M-row call — PERF.md section 5). Zero hits
    # across a many-call scoring run means the cache is thrashing (more
    # live models than the LRU holds) or the model is being rebuilt
    # between calls.
    "compiled_ensemble_cache_hits": 0,
    # Robustness substrate (docs/ROBUSTNESS.md): failed attempts the
    # retry seams recovered from (utils/retry.py — each also emits a
    # `fault` event with the seam) and histogram OOM degradations (the
    # backend stepped down the hist-impl ladder after RESOURCE_EXHAUSTED
    # — backends/tpu.py). Nonzero values in a "healthy" run's counters
    # line are the signal the infrastructure is limping.
    "fault_retries": 0,
    "hist_oom_degrades": 0,
    # Serving tier (ddt_tpu/serve/, schema v4): requests completed,
    # micro-batches dispatched, and zero-downtime hot swaps. The ratio
    # requests/batches is the process-lifetime mean coalesce width — a
    # serving process whose ratio sits at ~1.0 under load has lost
    # admission batching (per-window quantiles live in the
    # serve_latency events, not here: quantiles are not monotonic).
    "serve_requests": 0,
    "serve_batches": 0,
    "serve_hot_swaps": 0,
    # Requests the express lane dispatched synchronously (ISSUE 12) —
    # serve_express/serve_requests is the lifetime share of traffic
    # that skipped the admission window (== idle-regime traffic).
    "serve_express": 0,
    # Fleet tenancy (ddt_tpu/serve/fleet.py, ISSUE 15): LRU demotions
    # of cold models to their AOT artifacts, and reloads of previously
    # evicted models on their next request. A fleet whose reloads track
    # its evictions 1:1 is thrashing (max_resident too small for the
    # live working set); per-model attribution lives in the
    # fault(kind=fleet_eviction/fleet_reload) events, not here.
    "fleet_evictions": 0,
    "fleet_reloads": 0,
    # SLO burn-rate breach transitions (serve/fleet.py, ISSUE 17): the
    # number of times a model's rolling burn rate crossed INTO breach
    # (latched — a model burning continuously counts once until it
    # recovers below a 1.0 burn and breaches again). Each transition
    # also emits a fault(kind=slo_breach) event with the model, burn
    # rate, and objective; this counter is the process-lifetime total
    # the /metrics exposition and report diff read.
    "slo_breaches": 0,
    # EFFECTIVE per-round g/h HBM stream bytes (grad_stream_bytes below;
    # recorded by the Driver and the streaming trainers every round) —
    # the quantized-gradient win's in-process witness: an f32 run and an
    # int8 run of the same shape record 4x different values here, read
    # back from their run logs' counters events (ISSUE 14).
    "grad_stream_bytes_est": 0,
    # Rounds that ran the quantized-gradient path (scale derivation +
    # stochastic rounding) — nonzero iff cfg.grad_dtype != "f32"
    # actually armed (the "is the integer path live" observability
    # counter; the per-round scales themselves are in-trace values, so
    # they surface via debug logs, not counters).
    "grad_quant_rounds": 0,
    # Drift alert transitions (serve/drift.py, ISSUE 19): the number of
    # times a model's rolling-window feature divergence (max per-feature
    # PSI vs the artifact's training reference histogram) crossed INTO
    # alert (latched — a model drifting continuously counts once until
    # it recovers below threshold and alerts again). Each transition
    # also emits a `drift` event with the model, divergence scores, and
    # worst feature; this counter is the process-lifetime total the
    # /metrics exposition and report diff read.
    "drift_alerts": 0,
    # Training rounds completed process-wide (ISSUE 20): one tick per
    # boosted round across every trainer path (Driver granular + fused,
    # streamed host + device loops). The live-ops plane's primary
    # liveness signal — statusd's /metrics renders it as
    # ddt_train_rounds_total, and the smoke harness asserts it strictly
    # advances between two mid-run scrapes.
    "train_rounds": 0,
    # train_heartbeat events emitted (ISSUE 20): one per checkpoint
    # cadence boundary on runs with a run log — the post-mortem
    # liveness trail a SIGKILLed run leaves behind (report progress).
    "train_heartbeats": 0,
}
_listener_installed = False

#: The host's pauses, in the order a call's root span carries them.
HOST_COUNTERS = ("cpu_ns", "gc_pause_ns", "gc_collections",
                 "gc_gen2_collections")
_gc = {"gc_pause_ns": 0, "gc_collections": 0, "gc_gen2_collections": 0}
_gc_hooked = False
_gc_started = 0


def _on_gc(phase: str, info: dict) -> None:
    global _gc_started
    if phase == "start":
        _gc_started = time.perf_counter_ns()
    elif _gc_started:
        _gc["gc_pause_ns"] += time.perf_counter_ns() - _gc_started
        _gc["gc_collections"] += 1
        _gc["gc_gen2_collections"] += info.get("generation") == 2


def install_gc_hook() -> None:
    """Count the collector's pauses from here on (idempotent): called by
    install_jax_listener and by the first host_pauses()."""
    global _gc_hooked
    if not _gc_hooked:
        _gc_hooked = True
        gc.callbacks.append(_on_gc)


def host_pauses(since: dict | None = None) -> dict:
    """The host's pauses (HOST_COUNTERS; the module docstring says what
    each is evidence of) as they stand now, or their movement since an
    earlier reading: what the two ends of a scoring call take."""
    install_gc_hook()
    now = {"cpu_ns": time.process_time_ns(), **_gc}
    if since is None:
        return now
    return {k: now[k] - since[k] for k in now}


def install_jax_listener() -> None:
    """Register the recompile-counting jax.monitoring listener (idempotent;
    no-op when jax is absent — the cpu-backend CLI must run without it)."""
    global _listener_installed
    if _listener_installed:
        return
    try:
        from jax import monitoring
    except ImportError:
        return

    def _on_duration(event, duration_secs=None, **kw) -> None:
        name = _DURATION_COUNTERS.get(event)
        if name is not None:
            _c[name] += float(duration_secs or 0.0)
            if event == _COMPILE_EVENT:
                _c["jit_compiles"] += 1

    def _on_event(event, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            _c["compile_cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _listener_installed = True
    install_gc_hook()


def record_h2d(nbytes: int) -> None:
    _c["h2d_bytes"] += int(nbytes)


def record_d2h(nbytes: int) -> None:
    _c["d2h_bytes"] += int(nbytes)


def record_collective(nbytes: int) -> None:
    _c["collective_bytes_est"] += int(nbytes)


def record_compiled_ensemble_hit() -> None:
    _c["compiled_ensemble_cache_hits"] += 1


def record_fault_retry() -> None:
    _c["fault_retries"] += 1


def record_hist_oom_degrade() -> None:
    _c["hist_oom_degrades"] += 1


def record_serve_requests(n: int) -> None:
    _c["serve_requests"] += int(n)


def record_serve_batch() -> None:
    _c["serve_batches"] += 1


def record_serve_hot_swap() -> None:
    _c["serve_hot_swaps"] += 1


def record_serve_express() -> None:
    _c["serve_express"] += 1


def record_fleet_eviction() -> None:
    _c["fleet_evictions"] += 1


def record_fleet_reload() -> None:
    _c["fleet_reloads"] += 1


def record_slo_breach() -> None:
    _c["slo_breaches"] += 1


def record_drift_alert() -> None:
    _c["drift_alerts"] += 1


def record_grad_stream(nbytes: int) -> None:
    _c["grad_stream_bytes_est"] += int(nbytes)


def record_grad_quant_round(n: int = 1) -> None:
    _c["grad_quant_rounds"] += int(n)


def record_train_round(n: int = 1) -> None:
    _c["train_rounds"] += int(n)


def record_train_heartbeat() -> None:
    _c["train_heartbeats"] += 1


def snapshot() -> dict:
    """Point-in-time copy of the monotonic counters."""
    return dict(_c)


def delta(start: dict, end: dict | None = None) -> dict:
    """Counter movement since `start` (a snapshot()); `end` defaults to
    now. Float counters (compile seconds) are rounded to keep the run
    log's JSON readable; integer counters pass through exact."""
    end = end if end is not None else snapshot()
    out = {k: end[k] - start.get(k, 0) for k in _c}
    for name in _DURATION_COUNTERS.values():
        out[name] = round(out[name], 4)
    return out


def device_peak_bytes() -> int | None:
    """Accelerator memory high-water mark, or None where the platform
    exposes no memory_stats (CPU XLA, some runtimes)."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except (ImportError, RuntimeError, IndexError, AttributeError,
            NotImplementedError):
        return None
    if not stats:
        return None
    for key in ("peak_bytes_in_use", "bytes_in_use"):
        if key in stats:
            return int(stats[key])
    return None


def host_peak_rss_bytes() -> int | None:
    """Peak HOST resident-set size of this process (resource.getrusage
    ru_maxrss), or None where the resource module is unavailable
    (non-POSIX). The host-side twin of device_peak_bytes: the streaming
    trainers' O(chunk) host contract and the predict sink's bounded
    residency are claims about THIS number, so the run log records it
    next to the device high-water mark. Linux reports ru_maxrss in KiB,
    macOS in bytes — normalised to bytes here."""
    try:
        import resource
        import sys
    except ImportError:
        return None
    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(ru) if sys.platform == "darwin" else int(ru) * 1024


def hist_allreduce_bytes_by_level(
        max_depth: int, n_features: int, n_bins: int,
        *, partitions: int = 1, mode: str = "allreduce",
        subtraction: bool = False, comms_dtype: str = "f32",
        feature_partitions: int = 1,
        grad_dtype: str = "f32") -> "list[int]":
    """Per-LEVEL effective collective payload (levels 0..max_depth-1;
    the leaf-aggregate term is hist_allreduce_bytes' extra). The
    quantized-gradient acceptance contract reads this form: under
    integer hists subtraction is unconditionally exact, so every level
    >= 1 moves exactly HALF the f32-with-subtraction-off baseline's
    entries — a per-level >= 2x wire reduction the counters witness
    (docs/PERF.md "Quantized gradients"; whole-tree the ratio
    asymptotes to 2 from below because depth 0 has no parent).
    `grad_dtype` != "f32" means int32 partials on the wire (4 B/value —
    same as f32; the win is the halved entry count plus bit-stable
    merges without int32_fixed) and refuses compressed comms_dtype like
    the wire itself does (parallel/comms.hist_reduce)."""
    from ddt_tpu.parallel.comms import COMMS_DTYPE_BYTES

    if grad_dtype != "f32" and comms_dtype != "f32":
        raise ValueError(
            f"grad_dtype={grad_dtype!r} with comms_dtype={comms_dtype!r}: "
            "integer histogram partials refuse compression (the "
            "double-quantization hazard — config.py/comms.hist_reduce)")
    # int32 partials and f32 both move 4 B/value; the dict keeps the
    # spelling honest if a narrower integer wire ever lands.
    val_bytes = 4 if grad_dtype != "f32" else COMMS_DTYPE_BYTES[comms_dtype]
    per_entry = val_bytes * 2                            # (g, h) pairs
    P = max(1, partitions)
    Pf = max(1, feature_partitions)
    f_dev = -(-n_features // Pf)
    out = []
    for d in range(max_depth):
        nodes = 1 << d
        if subtraction and d >= 1:
            nodes //= 2                   # left children only
        if mode == "reduce_scatter":
            f_pad = -(-f_dev // P) * P
            total = nodes * (f_pad // P) * n_bins * per_entry
            # Winner combine: gain/feat/bin/dl x [n_level] from every
            # shard that owns a distinct slab (Pr row shards x Pf
            # feature shards on the 2D mesh).
            total += P * Pf * (1 << d) * 4 * 4
        else:
            total = nodes * f_dev * n_bins * per_entry
            if Pf > 1:
                # Column-sharded allreduce mode still combines winners
                # across the feature axis (tiny tuples per level).
                total += Pf * (1 << d) * 4 * 4
        out.append(total)
    return out


def hist_allreduce_bytes(max_depth: int, n_features: int, n_bins: int,
                         *, partitions: int = 1, mode: str = "allreduce",
                         subtraction: bool = False,
                         comms_dtype: str = "f32",
                         feature_partitions: int = 1,
                         grad_dtype: str = "f32") -> int:
    """EFFECTIVE per-device collective payload estimate for ONE tree's
    histogram phases (parallel/comms.py is the wire this models; the
    two must change together).

    Baseline (positional args only — the historical estimate): the
    [n_level, F, n_bins, 2] f32 histogram psum'd at every level plus the
    final level's [2^d, 2] leaf-aggregate reduction. The keyword knobs
    mirror the resolved comms configuration
    (TPUDevice.collective_bytes_per_tree passes them):

    - `subtraction` — sibling-subtraction levels (>= 1) move only LEFT
      children: half the level's entries.
    - `mode="reduce_scatter"` — each device receives its merged
      F_pad/P slab instead of the full table (F pads to the shard
      count), plus the split-winner combine's all_gather: 4 int/f32
      [n_level] vectors from each of the P shards.
    - `comms_dtype` — wire bytes per histogram value (f32/int32_fixed 4,
      bf16 2; parallel/comms.COMMS_DTYPE_BYTES).
    - `feature_partitions` — the 2D (rows x features) mesh's second
      axis (Pf): each device histograms only its F/Pf column slab, so
      the row-axis collective carries F/Pf columns per device —
      composed with reduce_scatter the per-device slab is F/(Pf·Pr),
      i.e. <= 1/(Pr·Pf) of the replicated-feature allreduce baseline
      (plus the O(Pr·Pf·nodes) winner term, which then gathers over
      both axes).

    - `grad_dtype` — the quantized-gradient path (int8/int16): partials
      ride the wire as int32 (4 B/value, like f32 — the wire win there
      is the unconditionally-exact subtraction halving every level >= 1
      plus bit-stable merges with no int32_fixed carve-out); the
      per-level form (hist_allreduce_bytes_by_level) is the acceptance
      contract's witness surface. Leaf aggregates stay 4 B/value
      either way (f32 psum or exact int32 psum).

    An estimate because the collective lives inside a fused device
    program where the host cannot observe the wire; shapes are static
    per config, so it is exact up to XLA's own reduction scheduling."""
    levels = hist_allreduce_bytes_by_level(
        max_depth, n_features, n_bins, partitions=partitions, mode=mode,
        subtraction=subtraction, comms_dtype=comms_dtype,
        feature_partitions=feature_partitions, grad_dtype=grad_dtype)
    return sum(levels) + (1 << max_depth) * 4 * 2   # leaf aggregates: psum


def grad_stream_bytes(rows: int, max_depth: int,
                      grad_dtype: str = "f32") -> int:
    """EFFECTIVE per-tree g/h HBM stream estimate: every histogram pass
    (max_depth levels + the leaf pass) re-reads both gradient rows at
    their STORED itemsize — 8 B/row/pass for f32, 4 for int16, 2 for
    int8 (ops/grad.GRAD_ITEMSIZE is the one home; node_index's 4 B/row
    is dtype-invariant and excluded so the ratio is the g/h story).
    The Driver and streaming trainers record this per round into
    `grad_stream_bytes_est` — the quantized path's >= 2x (int16) / 4x
    (int8) per-level byte cut, witnessed in-process from run-log
    counters rather than merely computed (ISSUE 14)."""
    from ddt_tpu.ops.grad import GRAD_ITEMSIZE

    return (max_depth + 1) * rows * 2 * GRAD_ITEMSIZE[grad_dtype]
