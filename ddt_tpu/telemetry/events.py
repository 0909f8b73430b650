"""Run-log events: schema-versioned JSONL records + in-memory ring buffer.

Every record is one JSON object per line with a fixed envelope
(`event`, `schema`, `t`, `seq`) plus the event type's required fields
(EVENT_FIELDS) and any optional extras. The schema is validated at EMIT
time (a malformed event is a bug at the producer, not something for the
report CLI to limp around) and again at READ time (report.read_events),
so a log that loads is a log every consumer can trust.

Writes are line-buffered appends of complete lines — a run killed mid-
round (the fault-injection story) loses at most its final partial line,
which read-side validation then skips with a warning rather than
discarding the run.
"""

from __future__ import annotations

import collections
import hashlib
import json
import time

# v2 (the distributed flight recorder): adds the per-partition events
# `partition_phases` / `partition_skew` and the `run_id` / `host`
# manifest extras the cross-host merge keys on.
# v3 (the device-truth cost observatory): adds the `cost_analysis` event
# (XLA compiled-executable cost/memory analysis per jit entry point —
# telemetry/costmodel.py) and the manifest's optional `xprof_dir` /
# `xprof_rounds` extras (telemetry/profiler.py capture windows).
# v4 (the low-latency serving tier): adds the `serve_latency` event
# (per-window request latency quantiles + admission-batching counters
# from ServeEngine — ddt_tpu/serve/engine.py) and the `hot_swap` fault
# kind. v1-v3 logs remain readable (no required field of an existing
# event ever changed — the back-compat contract tests/test_observatory.
# py and tests/test_serve.py pin).
# v5 (the AOT export + model registry): adds the `artifact` event
# (registry push/load/serve-publish records carrying the content
# digest, name@version, and the training run_id — ddt_tpu/registry/),
# plus the optional `artifact_digest` extra on serve_latency and the
# `old_artifact`/`new_artifact` extras on hot_swap faults. v1-v4 logs
# remain readable (tests/test_registry.py pins the v4 round trip).
# ISSUE 15 extras (schema-ADDITIVE, no version bump — the fleet tier):
# `model_name` on serve_latency and hot_swap faults (the multi-model
# dimension `report`'s fleet rollup groups on; absent on single-model
# logs, which render exactly as before), the fleet lifecycle fault
# kinds fleet_eviction / fleet_reload / fleet_remove (model_name +
# artifact_digest + running eviction/reload counts as extras), and the
# fleet_evictions / fleet_reloads process counters.
# ISSUE 17 extras (schema-ADDITIVE, no version bump — the serve-side
# operations plane): the `serve_trace` event (a flushed per-model ring
# of per-request timing breakdowns — trace id, accept→admit, queue/
# window wait, gate hold, device call, wake; flushed on demand via
# `GET /debug/requests?emit=1` or automatically on SLO breach), the
# `slo_breach` fault kind (burn_rate + objective_ms + window_s extras),
# the `slo_p99_ms` objective extra on serve_latency windows, and the
# slo_breaches process counter. Pre-SLO logs remain readable and render
# exactly as before (tests/test_fleet.py pins the mixed-era report).
# ISSUE 19 extras (schema-ADDITIVE, no version bump — the drift
# observatory): the `drift` event (a latched per-model alert transition
# when a model's rolling-window feature divergence against its
# artifact's training reference histogram crosses the PSI threshold —
# ddt_tpu/serve/drift.py; psi_max required, per-feature attribution +
# Jensen-Shannon score + window shape as extras), the drift_alerts
# process counter, and the drift/shadow extras on serve_latency windows
# (drift_psi_max, drift_js_max, shadow_model, shadow_mean_abs_diff,
# shadow_ms_p50 — how `report drift` recovers per-model drift and
# champion/challenger comparison from a log). Pre-drift logs remain
# readable and render exactly as before (tests/test_drift.py pins the
# mixed-era report).
# ISSUE 20 extras (schema-ADDITIVE, no version bump — the training
# operations plane): the `train_heartbeat` event, emitted by every
# trainer path at checkpoint cadence when a run log is attached (round
# required; total_rounds, checkpoint_round, ms_per_round, rows_per_s as
# extras) so a SIGKILLed run is diagnosable from its log's last
# heartbeat (`report progress`), plus the train_rounds /
# train_heartbeats process counters statusd's live /metrics exposition
# reads. Pre-heartbeat logs remain readable and render exactly as
# before (tests/test_statusd.py pins the mixed-era report).
SCHEMA_VERSION = 5

#: event type -> REQUIRED payload fields (extras are allowed and common:
#: e.g. `round` records carry `valid_<metric>` keys named by the run's
#: metric, and nullable fields like train_loss simply hold null).
EVENT_FIELDS: dict[str, set] = {
    # One per run, first record: what trained, on what, from where.
    # Since schema v2 manifests also carry `run_id` (deterministic config
    # digest, identical on every host of a pod run — the cross-host merge
    # key) and `host` (jax.process_index) as extras.
    "run_manifest": {"trainer", "backend", "loss", "n_trees", "max_depth",
                     "rows", "features"},
    # One per boosting round (the Driver.history record, as an event).
    "round": {"round", "ms_per_round"},
    # PhaseTimer.as_json() embedded verbatim under "phases".
    "phase_timings": {"phases"},
    # Per-partition attribution for ONE round (or fused block) of a mesh
    # run: `partitions` is [{device, phases: {name: ms}, rows?,
    # hist_allreduce_bytes}] — per-device completion wall times observed
    # by the host-side shard probe (PartitionRecorder).
    "partition_phases": {"round", "partitions"},
    # End-of-run straggler reduction over the partition_phases stream:
    # `phases` is [{phase, ms_max, ms_median, skew, max_device}]
    # (partition_skew_summary's exact output — tests recompute it
    # offline from the partition_phases events and compare).
    "partition_skew": {"phases"},
    # The early-stopping decision, when one fires.
    "early_stop": {"round", "best_round", "best_score", "metric"},
    # Fault/recovery events. Kinds (extras per kind; the catalog table
    # lives in docs/OBSERVABILITY.md): checkpoint_resume,
    # checkpoint_corrupt, checkpoint_fallback, checkpoint_unrecoverable
    # (utils/checkpoint.py); retry / retry_exhausted / retry_deadline
    # (utils/retry.py, with seam + attempt); injected (the chaos
    # harness, robustness/faultplan.py, with site); hist_oom_degrade
    # (backends/tpu.py); straggler_detected / repartition
    # (robustness/watchdog.py via the trainers); hot_swap
    # (serve/engine.py + fleet retag, with old/new tokens and the
    # ISSUE 15 model_name extra); fleet_eviction / fleet_reload /
    # fleet_remove (serve/fleet.py, with model_name + artifact_digest);
    # slo_breach (serve/fleet.py burn-rate tracker, with model_name +
    # burn_rate + objective_ms + window_s + requests).
    "fault": {"kind"},
    # Device-counter deltas over the run (telemetry.counters).
    "counters": {"jit_compiles", "h2d_bytes", "d2h_bytes",
                 "collective_bytes_est"},
    # XLA's own cost model for one jit-compiled op entry point at one
    # argument signature (telemetry/costmodel.py): per-call FLOPs and
    # bytes accessed from compile().cost_analysis(), plus extras —
    # phase (the phase_timings name the roofline join keys on), calls,
    # platform, arg/output/temp HBM bytes from memory_analysis(),
    # signature. Emitted in the run epilogue, one per (op, signature).
    "cost_analysis": {"op", "flops", "bytes_accessed"},
    # Registry provenance (schema v5, ddt_tpu/registry/): one per
    # artifact lifecycle step — action in {push, load}, digest = the
    # 16-hex content address. Extras: name, version, kind, the training
    # run_id (the cross-reference `report`'s registry section joins on),
    # model_token, and mode (the loader's restore ladder: aot-f32 /
    # aot-lut / aot-lut4 / tables-fallback / rebuild).
    "artifact": {"action", "digest"},
    # Serving-tier SLO window (schema v4, ddt_tpu/serve/engine.py): one
    # per emitted latency window — per-request latency quantiles
    # (p50/p99; extras p999_ms, max_ms), admission-batching shape
    # (batches, coalesce_mean/max, queue_depth_max), window_s, and the
    # served model's content-digest token. Additive ISSUE 12 extras:
    # `predict_impl` (the quantization tier ACTUALLY serving the window
    # — "lut4"/"lut"/"f32"; a silent VMEM-guard fallback is visible
    # here, not only in debug logs) and `express` (requests the express
    # lane dispatched without an admission window). Additive ISSUE 15
    # extra: `model_name` (the fleet tier emits one window per resident
    # model — `report`'s fleet rollup groups on it; absent on
    # single-model logs). Consumed by `report`'s serving section.
    "serve_latency": {"requests", "p50_ms", "p99_ms"},
    # Serve-side request traces (ISSUE 17, schema-additive): one flushed
    # per-model ring of completed per-request timing breakdowns —
    # `traces` is [{trace_id, rows, express, handler_ms, queue_ms,
    # gate_ms, device_ms, wake_ms, total_ms}] (serve/batcher.py
    # trace_breakdown is the one shape home). Flushed on demand
    # (GET /debug/requests?emit=1) or on SLO breach, with the model
    # dimension and the flush reason as extras. Absent from pre-trace
    # logs; report ignores unknown-to-it events by construction.
    "serve_trace": {"traces"},
    # Drift alert transition (ISSUE 19, schema-additive): one per
    # latched crossing of a model's rolling-window feature divergence
    # into alert — psi_max is the worst per-feature population
    # stability index vs the artifact's training reference histogram
    # (serve/drift.py is the one divergence home). Extras carry the
    # model dimension, the worst feature, the companion Jensen-Shannon
    # score, and the window shape so the report can rank breaches.
    # Absent from pre-drift logs; report ignores unknown-to-it events
    # by construction.
    "drift": {"psi_max"},
    # Training-liveness heartbeat (ISSUE 20, schema-additive): one per
    # checkpoint cadence boundary on runs with a run log, from every
    # trainer path (Driver granular + fused, streamed host + device).
    # `round` is the 1-based count of completed rounds at emit time;
    # extras carry the configured total, the latest checkpoint round,
    # and the rolling rate — the post-mortem trail `report progress`
    # rolls up when a run dies between heartbeats.
    "train_heartbeat": {"round"},
    # Last record of a completed run.
    "run_end": {"completed_rounds", "wallclock_s"},
}

#: event type -> DECLARED optional extras (fnmatch globs allowed:
#: `round` records carry `valid_<metric>` keys named by the run's
#: metric). Extras stay runtime-optional — validate_event does not
#: require them — but they are no longer informal: ddtlint's
#: telemetry-contract pass (tools/ddtlint/telemetrycontract.py) checks
#: every literal emit-site keyword against this catalog, and
#: docs/OBSERVABILITY.md embeds the derived contract. Growing this dict
#: is the schema-ADDITIVE move (no version bump); growing a kind's
#: REQUIRED set is not (event-schema-additivity).
EVENT_EXTRAS: dict[str, tuple] = {
    "run_manifest": (
        # v1 shape facts + v2 merge keys.
        "n_bins", "n_classes", "seed", "distributed", "run_id", "host",
        # Streaming runs (n_chunks) and the resolved comms config
        # (comms_manifest_fields — ISSUE 10/11/14 extras).
        "n_chunks", "grad_dtype", "split_comms", "hist_comms_dtype",
        "hist_comms_slabs", "mesh_layout",
        # v3 xprof cross-reference (telemetry/profiler.py).
        "xprof_dir", "xprof_rounds",
        # The device the run used, as JAX reports it (backend.
        # device_stamp — PR 21): a log's numbers name their device.
        "platform", "device_kind", "n_devices",
    ),
    "round": ("train_loss", "valid_*"),
    "phase_timings": (),
    "partition_phases": ("rounds",),
    "partition_skew": ("n_partitions",),
    "early_stop": (),
    # The union of every fault kind's extras — the catalog table mapping
    # kind -> extras lives in docs/OBSERVABILITY.md; report reads them
    # per kind, the schema only promises they are declared names.
    "fault": (
        "round", "rotation", "device", "skew", "streak",      # stragglers
        "seam", "attempt", "error", "message", "deadline_s",  # retries
        "site",                                               # injected
        "from_impl", "to_impl", "row_chunk",                  # OOM degrade
        "old", "new", "old_artifact", "new_artifact",         # hot swap
        "model_name", "artifact_digest", "evictions",         # fleet
        "reloads", "failed_requests",
        "candidate", "reason",                                # checkpoints
        "burn_rate", "objective_ms", "window_s", "requests",  # slo_breach
    ),
    # Everything counters.delta() / the finish_run_log epilogue may
    # publish beyond the required four — kept in sync with the `_c`
    # registry by the undeclared-event-extra cross-check.
    "counters": (
        "jit_compile_seconds", "jit_trace_seconds", "jit_lower_seconds",
        "compile_cache_hits", "compiled_ensemble_cache_hits",
        "fault_retries", "hist_oom_degrades",
        "serve_requests", "serve_batches", "serve_hot_swaps",
        "serve_express", "fleet_evictions", "fleet_reloads",
        "slo_breaches", "drift_alerts",
        "grad_stream_bytes_est", "grad_quant_rounds",
        "train_rounds", "train_heartbeats",
        "device_peak_bytes", "host_peak_rss_bytes",
    ),
    "cost_analysis": ("phase", "calls", "platform", "device_kind",
                      "signature", "arg_bytes", "output_bytes",
                      "temp_bytes"),
    "artifact": ("name", "version", "kind", "run_id", "model_token",
                 "mode"),
    "serve_latency": ("batches", "window_s", "p999_ms", "max_ms",
                      "coalesce_mean", "coalesce_max", "queue_depth_max",
                      "express", "model_token", "model_name",
                      "predict_impl", "artifact_digest", "slo_p99_ms",
                      # ISSUE 19 drift/shadow extras: the window's
                      # divergence scores and, on a shadowed champion,
                      # the challenger's comparison stats — the signals
                      # `report drift` joins per model.
                      "drift_psi_max", "drift_js_max", "drift_alerting",
                      "shadow_model", "shadow_rows",
                      "shadow_mean_abs_diff", "shadow_ms_p50",
                      "shadow_dropped"),
    "serve_trace": ("model_name", "model_token", "reason", "count"),
    # Training heartbeats (ISSUE 20): the run's configured round total,
    # the last checkpoint boundary crossed, and the rolling rate at
    # emit time — everything `report progress` needs to place a
    # mid-run death between two cadence marks.
    "train_heartbeat": ("total_rounds", "checkpoint_round",
                        "ms_per_round", "rows_per_s"),
    # Drift alert transitions (ISSUE 19): the model dimension, worst-
    # feature attribution, companion Jensen-Shannon score, window shape,
    # and the alert threshold that was crossed.
    "drift": ("model_name", "feature", "js_max", "psi_mean",
              "window_rows", "window_s", "threshold", "alerts"),
    "run_end": (),
}

#: every `fault` event kind any emitter may use — the undeclared-event-
#: kind rule checks literal kinds against this tuple, so a typo'd kind
#: is a lint finding, not a fault event report silently cannot group.
#: The per-kind extras table lives in docs/OBSERVABILITY.md.
FAULT_KINDS = (
    "checkpoint_resume", "checkpoint_corrupt", "checkpoint_fallback",
    "checkpoint_unrecoverable",
    "retry", "retry_exhausted", "retry_deadline",
    "injected", "hist_oom_degrade",
    "straggler_detected", "repartition",
    "hot_swap", "fleet_eviction", "fleet_reload", "fleet_remove",
    "slo_breach",
)

ENVELOPE_FIELDS = ("event", "schema", "t", "seq")


def validate_event(rec: dict) -> None:
    """Raise ValueError unless `rec` is a well-formed run-log record."""
    if not isinstance(rec, dict):
        raise ValueError(f"run-log record must be an object, got "
                         f"{type(rec).__name__}")
    missing = [k for k in ENVELOPE_FIELDS if k not in rec]
    if missing:
        raise ValueError(f"run-log record missing envelope fields {missing}")
    if not isinstance(rec["schema"], int) or isinstance(rec["schema"], bool):
        # A corrupt/hand-edited line must surface as the reader's clean
        # ValueError, not a TypeError from the comparison below.
        raise ValueError(
            f"run-log schema must be an integer, got {rec['schema']!r}")
    if rec["schema"] > SCHEMA_VERSION:
        raise ValueError(
            f"run-log schema {rec['schema']} is newer than this reader "
            f"(schema {SCHEMA_VERSION}); upgrade ddt_tpu to report on it")
    ev = rec["event"]
    if ev not in EVENT_FIELDS:
        raise ValueError(
            f"unknown run-log event {ev!r}; have {sorted(EVENT_FIELDS)}")
    missing = [k for k in EVENT_FIELDS[ev] if k not in rec]
    if missing:
        raise ValueError(f"{ev} record missing required fields {missing}")


class RunLog:
    """Append-only JSONL run log + bounded in-memory ring buffer.

    `path=None` keeps events in the ring only (tests, library callers).
    The file handle opens lazily on the first emit and is line-buffered;
    `close()` (or context-manager exit) releases it. Emission never
    touches the device — every field is host data the trainer already
    had in hand.
    """

    def __init__(self, path: str | None = None, ring_size: int = 4096):
        self.path = path
        self.ring: collections.deque = collections.deque(maxlen=ring_size)
        self._fh = None
        self._seq = 0
        # Bound by the trainer that derives it (Driver, fit_streaming) —
        # callers that only hold the log (the CLI's streaming save path)
        # read the run's identity here; survives close().
        self.run_id: str | None = None

    @classmethod
    def coerce(cls, run_log) -> "RunLog | None":
        """None | path-str | RunLog -> RunLog | None (the api.train /
        fit_streaming argument convention)."""
        if run_log is None or isinstance(run_log, cls):
            return run_log
        return cls(str(run_log))

    def emit(self, event: str, **fields) -> dict:
        rec = {"event": event, "schema": SCHEMA_VERSION,
               "t": time.time(), "seq": self._seq, **fields}
        validate_event(rec)
        self._seq += 1
        self.ring.append(rec)
        if self.path is not None:
            if self._fh is None:
                self._fh = open(self.path, "a", buffering=1,
                                encoding="utf-8")
            self._fh.write(json.dumps(rec, sort_keys=False) + "\n")
        return rec

    def events(self, event: str | None = None) -> list[dict]:
        """Ring-buffer contents (oldest first), optionally one type."""
        return [r for r in self.ring if event is None or r["event"] == event]

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "RunLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def emit_early_stop(run_log: "RunLog | None", stop_round: int, metric,
                    best_round: int, best_score) -> None:
    """The early_stop event, one emit site for the Driver's granular and
    fused loops and both streaming loops (rounds are 1-based here)."""
    if run_log is None:
        return
    run_log.emit("early_stop", round=stop_round, metric=metric,
                 best_round=best_round, best_score=best_score)


def finish_run_log(run_log: "RunLog | None", timer, counters_start,
                   completed_rounds: int, wallclock_s: float,
                   partitions: "PartitionRecorder | None" = None,
                   costs=None) -> None:
    """Run-log epilogue — [partition_skew +] [cost_analysis... +]
    phase_timings + counters + run_end — shared by Driver._finish_run
    and fit_streaming's _finish so the trainers' terminal records cannot
    drift. `timer` is a PhaseTimer or None; `counters_start` a
    telemetry.counters.snapshot() (or None); `partitions` the mesh run's
    PartitionRecorder (or None); `costs` the run's costmodel.Collector
    (or None). Closing path-owned logs is the trainers' ownership shims'
    job (Driver.fit / fit_streaming), which also covers the exception
    paths this helper never sees."""
    if run_log is None:
        return
    from ddt_tpu.telemetry import counters as tele_counters

    if partitions is not None:
        partitions.emit_skew()
    if costs is not None:
        from ddt_tpu.telemetry import costmodel

        costmodel.flush_into(run_log, costs)
    if timer is not None and timer.totals:
        run_log.emit("phase_timings", phases=timer.as_json())
    d = tele_counters.delta(counters_start or {})
    d["device_peak_bytes"] = tele_counters.device_peak_bytes()
    d["host_peak_rss_bytes"] = tele_counters.host_peak_rss_bytes()
    run_log.emit("counters", **d)
    run_log.emit("run_end", completed_rounds=completed_rounds,
                 wallclock_s=wallclock_s)


def emit_train_heartbeat(run_log, *, rnd, total_rounds,
                         checkpoint_round=None, ms_per_round=None,
                         rows_per_s=None) -> None:
    """One heartbeat at a checkpoint-cadence boundary — the ONE emit
    home shared by every trainer path (Driver granular + fused,
    streamed host + device loops) so the record shape cannot drift.
    `rnd` is 0-based (the loop variable); the event's `round` is the
    1-based completed count, matching `round` records. No-op without a
    run log (the disabled-telemetry contract)."""
    if run_log is None:
        return
    from ddt_tpu.telemetry import counters as tele_counters

    tele_counters.record_train_heartbeat()
    extras = {}
    if checkpoint_round is not None:
        extras["checkpoint_round"] = checkpoint_round
    if ms_per_round is not None:
        extras["ms_per_round"] = round(float(ms_per_round), 3)
    if rows_per_s is not None:
        extras["rows_per_s"] = round(float(rows_per_s), 1)
    run_log.emit("train_heartbeat", round=rnd + 1,
                 total_rounds=total_rounds, **extras)


def comms_manifest_fields(backend) -> dict:
    """run_manifest extras describing the RESOLVED split-finding comms
    configuration (ISSUE 10; schema extras only, no version bump —
    absent on single-device backends and in every pre-existing log, and
    report treats them as optional). The one home the Driver's and the
    streaming trainers' manifests share. ISSUE 14 extra: `grad_dtype`
    appears whenever the quantized-gradient path is armed (absent =
    f32), single-device runs included — the effective-bytes counters'
    byte model keys on it."""
    out = {}
    cfg = getattr(backend, "cfg", None)
    if cfg is not None and getattr(cfg, "grad_dtype", "f32") != "f32":
        out["grad_dtype"] = cfg.grad_dtype
    if not getattr(backend, "distributed", False):
        return out
    return {
        **out,
        "split_comms": getattr(backend, "split_comms", "allreduce"),
        "hist_comms_dtype": backend.cfg.hist_comms_dtype,
        "hist_comms_slabs": int(getattr(backend, "comms_slabs", 1)),
        # ISSUE 11 extra: the LIVE mesh's (row shards, feature shards)
        # pair — the second axis the partition_phases lanes and the
        # comms roofline's effective-bytes model account for. Named
        # mesh_LAYOUT, not mesh_shape: row_shards folds host_partitions
        # in (hosts x rows), so this is NOT replayable as
        # cfg.mesh_shape on pod runs. Schema extra like the rest:
        # absent in pre-2D logs, optional to report.
        "mesh_layout": [int(getattr(backend, "row_shards", 1)),
                        int(getattr(backend, "feature_partitions", 1))],
    }


def device_manifest_fields(backend) -> dict:
    """run_manifest extras naming the device the run used (platform,
    device_kind, n_devices — backend.device_stamp). The one home the
    Driver's and the streaming trainers' manifests share; empty for a
    duck-typed backend without the method."""
    stamp = getattr(backend, "device_stamp", None)
    return stamp() if stamp is not None else {}


def derive_run_id(**fields) -> str:
    """Deterministic 12-hex run id from the run's config facts. Every
    host of a multi-host run derives the IDENTICAL id from its (identical
    by SPMD construction) config — the key telemetry.merge joins per-host
    logs on. Same config rerun -> same id; the merge additionally keys on
    file identity, so that is a feature (retry logs join), not a
    collision."""
    blob = json.dumps(fields, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def partition_skew_summary(totals: dict) -> list[dict]:
    """Host-side straggler reduction: {lane: {phase: ms}} accumulated
    per-lane phase wall times -> [{phase, ms_max, ms_median, skew,
    max_device}], phases sorted by ms_max descending. A lane key is a
    device id (single-host collection) or a (host, device) tuple (the
    report's cross-host recompute — the record then also carries
    max_host). `skew` is max/median (1.0 = perfectly balanced); the ONE
    reduction home — PartitionRecorder emits it and the tests recompute
    it offline from the partition_phases events, so the two cannot
    drift."""
    phases: dict[str, dict] = {}
    for lane, per_phase in totals.items():
        for name, ms in per_phase.items():
            # one value per (lane, phase) by construction — assign
            phases.setdefault(name, {})[lane] = ms
    out = []
    for name, by_lane in phases.items():
        vals = sorted(by_lane.values())
        n = len(vals)
        median = (vals[n // 2] if n % 2 else
                  (vals[n // 2 - 1] + vals[n // 2]) / 2.0)
        # max over sorted keys -> the SMALLEST lane wins exact ties
        # (deterministic for int and tuple keys alike)
        max_lane = max(sorted(by_lane), key=lambda k: by_lane[k])
        ms_max = by_lane[max_lane]
        rec = {
            "phase": name,
            "ms_max": round(ms_max, 3),
            "ms_median": round(median, 3),
            "skew": round(ms_max / median, 3) if median > 0 else None,
        }
        if isinstance(max_lane, tuple):
            rec["max_host"] = int(max_lane[0])
            rec["max_device"] = int(max_lane[1])
        else:
            rec["max_device"] = int(max_lane)
        out.append(rec)
    out.sort(key=lambda r: -r["ms_max"])
    return out


class PartitionRecorder:
    """Per-partition phase attribution for mesh runs (the distributed
    flight recorder's collection half).

    Protocol: at an instrumented phase boundary the trainer hands the
    phase's device OUTPUT handle plus the phase's host start time to
    observe(); the backend's shard probe (TPUDevice.partition_ready_ms,
    riding parallel.mesh.shard_ready_times) reports, per addressable
    device, the host-clock moment that device's shard of the output
    completed. The per-device wall time is that completion offset — the
    honest host-observable per-partition signal: inside a psum'd program
    every shard completes only after the collective, so what this
    measures is COMPLETION skew (a straggling partition delays its own
    shard's availability and shows up as the max lane).

    Cost: one device barrier per observed phase — paid ONLY on
    distributed runs with a run log attached. Single-device runs,
    host backends, and disabled telemetry construct an inactive recorder
    whose observe()/flush_round() are attribute checks (no probe, no
    sync, no allocation) — the PR-2 zero-overhead invariant, extended
    (tests/test_telemetry.py guard).

    Emits one `partition_phases` event per flushed round (per fused
    block on the fused path, with the block's first round and a
    `rounds` extra) and, via emit_skew() at run end, one
    `partition_skew` event reducing the whole run
    (partition_skew_summary)."""

    def __init__(self, run_log: "RunLog | None", backend,
                 bytes_per_round: int = 0):
        probe = getattr(backend, "partition_ready_ms", None)
        self.active = (run_log is not None and probe is not None
                       and bool(getattr(backend, "distributed", False)))
        self.run_log = run_log
        self._probe = probe
        self.bytes_per_round = int(bytes_per_round)
        # device -> phase -> ms, current round / whole run
        self._round: dict[int, dict[str, float]] = {}
        self._totals: dict[int, dict[str, float]] = {}

    def observe(self, phase: str, handle, t0: float) -> None:
        """Record the per-device wall time of one phase from its output
        handle (`t0` = the phase's host start, time.perf_counter())."""
        if not self.active:
            return
        ready = self._probe(handle)
        if not ready:
            return
        for dev, t_ready in ready:
            ms = max(0.0, (t_ready - t0) * 1e3)
            self._round.setdefault(dev, {})
            self._round[dev][phase] = self._round[dev].get(phase, 0.0) + ms

    def flush_round(self, rnd: int, n_rounds: int = 1) -> "dict | None":
        """Emit the round's partition_phases event (rnd is 0-based here;
        the event carries the 1-based round like every other record).
        `n_rounds` > 1 on the fused path: the event covers a whole
        block. Returns the flushed {device: {phase: ms}} dict — the
        straggler watchdog's per-round feed (robustness/watchdog.py) —
        or None when inactive/empty."""
        if not self.active or not self._round:
            return None
        # Chaos-harness straggler seam (robustness/faultplan.py): an
        # active plan may inflate one lane's observed time — a
        # DETERMINISTIC straggler (no real sleeping) that flows into the
        # event stream, the skew summary, and the watchdog exactly like
        # a slow device would. One module-global read per device when no
        # plan is active.
        from ddt_tpu.robustness import faultplan

        for dev in self._round:
            extra = faultplan.perturb_ms("straggler", device=int(dev),
                                         round=rnd + 1)
            if extra:
                self._round[dev]["straggler_injected"] = (
                    self._round[dev].get("straggler_injected", 0.0) + extra)
        parts = []
        for dev in sorted(self._round):
            phases = {k: round(v, 3) for k, v in self._round[dev].items()}
            parts.append({
                "device": int(dev), "phases": phases,
                "hist_allreduce_bytes": self.bytes_per_round * n_rounds,
            })
            tot = self._totals.setdefault(dev, {})
            for k, v in self._round[dev].items():
                tot[k] = tot.get(k, 0.0) + v
        self.run_log.emit("partition_phases", round=rnd + 1,
                          rounds=n_rounds, partitions=parts)
        flushed, self._round = self._round, {}
        return flushed

    def emit_skew(self) -> None:
        """End-of-run partition_skew event (finish_run_log calls this
        before the terminal phase_timings/counters/run_end triplet)."""
        if not self.active or not self._totals:
            return
        self.run_log.emit(
            "partition_skew", phases=partition_skew_summary(self._totals),
            n_partitions=len(self._totals))


class RoundRecorder:
    """Per-round history record + run-log event + progress log line — the
    ONE home of the round-record shape, shared by the Driver's granular
    and fused loops (it replaced Driver._record_round) and mirrored by
    the streaming trainer's round events.

    Semantics preserved from the Driver: train loss at `log_every`
    cadence only (the loss thunk may cost a device sync; off-cadence
    records carry train_loss=None so the schema stays uniform), eval
    metric EVERY round — the per-round series (sklearn evals_result_)
    must not depend on the logging knob. ms_per_round is the caller's
    number: real per-round wallclock on the granular path, the block
    average on the fused path (per-round wallclock does not exist there
    — that is the point of fusing).
    """

    def __init__(self, history: list, run_log: RunLog | None,
                 log_every: int, n_rounds: int, metric_name: str | None,
                 logger):
        self.history = history
        self.run_log = run_log
        self.log_every = log_every
        self.n_rounds = n_rounds
        self.metric_name = metric_name
        self.log = logger

    @staticmethod
    def make_record(r: int, ms: float, train_loss,
                    metric_name=None, val_score=None) -> dict:
        """THE round-record dict shape ({round, train_loss, ms_per_round
        [, valid_<metric>]}) — also used by the streaming trainer's round
        events so the two emitters cannot drift."""
        rec = {"round": r + 1, "train_loss": train_loss,
               "ms_per_round": ms}
        if val_score is not None:
            rec[f"valid_{metric_name}"] = val_score
        return rec

    def record(self, r: int, ms: float, val_score, loss_fn) -> None:
        on_cadence = (r + 1) % self.log_every == 0 or r == self.n_rounds - 1
        if not on_cadence and val_score is None and self.run_log is None:
            return                       # nothing records this round
        loss = loss_fn() if on_cadence else None
        rec = self.make_record(r, ms, loss, self.metric_name, val_score)
        if on_cadence or val_score is not None:
            self.history.append(rec)
        if self.run_log is not None:
            self.run_log.emit("round", **rec)
        if on_cadence:
            self.log.info(
                "round %4d/%d  loss=%.6f  %.1f ms/round%s",
                r + 1, self.n_rounds, loss, ms,
                f"  valid_{self.metric_name}={val_score:.6f}"
                if val_score is not None else "",
            )
