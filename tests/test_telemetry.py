"""Telemetry subsystem (ddt_tpu/telemetry, docs/OBSERVABILITY.md):
schema validation of every event type, the zero-overhead disabled path
(no device syncs, no file I/O — asserted, not assumed), run-log
round-trips through the report CLI, and the streaming trainer's phase
timing. CPU platform, tier-1."""

import importlib.util
import json
import os

import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.config import TrainConfig
from ddt_tpu.telemetry import counters as tele_counters
from ddt_tpu.telemetry import report
from ddt_tpu.telemetry.events import (
    EVENT_FIELDS, RunLog, validate_event)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _binary(rows, features=7, bins=29, seed=0):
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    y = (Xb[:, 0] > bins // 2).astype(np.float32)
    return Xb, y


# --------------------------------------------------------------------- #
# schema
# --------------------------------------------------------------------- #
def test_runlog_emits_and_round_trips_every_event_type(tmp_path):
    """One emission per schema event type, written to JSONL and read back
    through the validating reader — EVENT_FIELDS is covered exhaustively,
    so a new event type without a test fails here."""
    path = str(tmp_path / "run.jsonl")
    payloads = {
        "run_manifest": dict(trainer="driver", backend="cpu",
                             loss="logloss", n_trees=2, max_depth=3,
                             rows=10, features=4),
        "round": dict(round=1, ms_per_round=1.5, train_loss=None,
                      valid_logloss=0.6),
        "phase_timings": dict(phases=[{"phase": "grow", "ms_total": 1.0,
                                       "ms_per_call": 0.5, "calls": 2,
                                       "share": 1.0}]),
        "early_stop": dict(round=2, best_round=1, best_score=0.59,
                           metric="logloss"),
        "fault": dict(kind="checkpoint_resume", round=1),
        "counters": dict(jit_compiles=1, jit_compile_seconds=0.25,
                         h2d_bytes=10, d2h_bytes=5,
                         collective_bytes_est=0, device_peak_bytes=None,
                         host_peak_rss_bytes=123456),
        # Schema v3 (device-truth cost observatory): XLA's cost model for
        # one op entry point at one signature.
        "cost_analysis": dict(op="hist", flops=2.5e9, bytes_accessed=1e9,
                              phase="hist", calls=12, platform="cpu",
                              device_kind="cpu",
                              arg_bytes=1000, output_bytes=200,
                              temp_bytes=50,
                              signature="([1000, 7]:uint8)"),
        "partition_phases": dict(
            round=1, rounds=1,
            partitions=[{"device": 0, "phases": {"grow": 1.5},
                         "hist_allreduce_bytes": 64},
                        {"device": 1, "phases": {"grow": 2.0},
                         "hist_allreduce_bytes": 64}]),
        "partition_skew": dict(
            phases=[{"phase": "grow", "ms_max": 2.0, "ms_median": 1.75,
                     "skew": 1.143, "max_device": 1}],
            n_partitions=2),
        # Schema v5 (AOT export + model registry): one artifact
        # lifecycle step (registry push / loader restore).
        "artifact": dict(action="push", digest="a1b2c3d4e5f60718",
                         name="higgs", version=3, kind="servable",
                         run_id="58226c4d64f0", mode=None),
        # Schema v4 (low-latency serving tier): one SLO window from
        # ServeEngine.emit_latency.
        "serve_latency": dict(requests=100, p50_ms=0.8, p99_ms=2.5,
                              p999_ms=4.0, max_ms=4.2, batches=13,
                              coalesce_mean=7.7, coalesce_max=16,
                              queue_depth_max=3, window_s=1.0,
                              model_token="cafe" * 10),
        # Schema v5-additive (ISSUE 17 operations plane): one flushed
        # request-trace ring (breakdown per trace_breakdown's shape).
        "serve_trace": dict(
            traces=[{"trace_id": "ab12cd34ef56-00000001", "rows": 1,
                     "express": True, "handler_ms": 0.012,
                     "queue_ms": 0.0, "gate_ms": 0.21,
                     "device_ms": 3.1, "wake_ms": 0.05,
                     "total_ms": 3.37}],
            count=1, model_name="higgs", model_token="cafe" * 10,
            reason="on_demand"),
        # Schema v5-additive (ISSUE 19 drift observatory): one latched
        # divergence-alert transition from serve.drift.DriftTracker.
        "drift": dict(psi_max=0.41, model_name="higgs", feature=3,
                      js_max=0.22, psi_mean=0.11, window_rows=512,
                      window_s=300.0, threshold=0.25, alerts=1),
        # Schema v5-additive (ISSUE 20 training operations plane): one
        # checkpoint-cadence progress heartbeat from the train loops.
        "train_heartbeat": dict(round=6, total_rounds=12,
                                checkpoint_round=6, ms_per_round=375.1,
                                rows_per_s=14776.0),
        "run_end": dict(completed_rounds=2, wallclock_s=0.1),
    }
    assert set(payloads) == set(EVENT_FIELDS)   # exhaustive by contract
    with RunLog(path) as rl:
        for ev, fields in payloads.items():
            rl.emit(ev, **fields)
        assert [r["event"] for r in rl.events()] == list(payloads)
    back = report.read_events(path)
    assert [r["event"] for r in back] == list(payloads)
    assert [r["seq"] for r in back] == list(range(len(payloads)))
    for r in back:
        validate_event(r)                       # idempotent on valid recs


def test_validate_event_rejects_malformed():
    ok = {"event": "round", "schema": 1, "t": 0.0, "seq": 0,
          "round": 1, "ms_per_round": 2.0}
    validate_event(ok)
    with pytest.raises(ValueError, match="unknown run-log event"):
        validate_event({**ok, "event": "nonsense"})
    bad = dict(ok)
    del bad["ms_per_round"]
    with pytest.raises(ValueError, match="missing required fields"):
        validate_event(bad)
    bad = dict(ok)
    del bad["seq"]
    with pytest.raises(ValueError, match="envelope"):
        validate_event(bad)
    with pytest.raises(ValueError, match="newer than this reader"):
        validate_event({**ok, "schema": 999})
    # Corrupt/hand-edited logs must surface as the reader's clean
    # ValueError, never a TypeError from the version comparison.
    with pytest.raises(ValueError, match="schema must be an integer"):
        validate_event({**ok, "schema": "1"})
    with pytest.raises(ValueError, match="must be an object"):
        validate_event(["not", "a", "dict"])


def test_runlog_rejects_bad_emit_at_the_producer():
    rl = RunLog()                               # ring-only
    with pytest.raises(ValueError):
        rl.emit("round")                        # missing required fields
    with pytest.raises(ValueError):
        rl.emit("no_such_event", x=1)
    assert rl.events() == []                    # nothing half-recorded


# --------------------------------------------------------------------- #
# driver integration
# --------------------------------------------------------------------- #
def test_driver_e2e_run_log_counters_and_eval_curve(tmp_path):
    """The acceptance round trip at API level: a TPU-backend (XLA-on-CPU)
    train with eval_set produces a schema-valid log holding per-phase
    timings, per-round eval metrics, and a NONZERO jit-recompile count
    (unique shapes force fresh compiles even in a shared process)."""
    Xb, y = _binary(2113)
    Xv, yv = _binary(431, seed=1)
    path = str(tmp_path / "run.jsonl")
    with RunLog(path) as rl:
        api.train(Xb, y, binned=True, n_trees=4, max_depth=3, n_bins=29,
                  backend="tpu", eval_set=(Xv, yv),
                  eval_metric="logloss", run_log=rl)
    events = report.read_events(path)
    by_type = {}
    for e in events:
        by_type.setdefault(e["event"], []).append(e)
    assert {"run_manifest", "round", "phase_timings", "counters",
            "run_end"} <= set(by_type)

    man = by_type["run_manifest"][0]
    assert (man["trainer"], man["backend"]) == ("driver", "tpu")
    assert (man["rows"], man["features"]) == (2113, 7)
    # The log names its device, as JAX reports it (8 virtual CPUs here).
    assert (man["platform"], man["device_kind"], man["n_devices"]) == \
        ("cpu", "cpu", 8)

    rounds = by_type["round"]
    assert [r["round"] for r in rounds] == [1, 2, 3, 4]
    assert all("valid_logloss" in r for r in rounds)   # metric EVERY round
    assert all(r["ms_per_round"] > 0 for r in rounds)

    c = by_type["counters"][-1]
    assert c["jit_compiles"] > 0                       # the silent killer
    assert c["h2d_bytes"] >= Xb.nbytes                 # data plane counted
    assert c["d2h_bytes"] > 0                          # tree fetches

    phases = by_type["phase_timings"][-1]["phases"]
    assert phases and {"phase", "ms_total", "ms_per_call", "calls",
                       "share"} <= set(phases[0])
    assert by_type["run_end"][-1]["completed_rounds"] == 4


def test_disabled_path_no_syncs_no_file_io(monkeypatch, tmp_path):
    """With telemetry off (run_log=None, profile=False) the hot loop must
    add ZERO device syncs — counted on the backend's sync callable — and
    perform no run-log file I/O, asserted by making any RunLog
    construction or emission explode."""
    from ddt_tpu.backends.tpu import TPUDevice
    from ddt_tpu.parallel import mesh as mesh_lib
    import ddt_tpu.telemetry.events as ev_mod

    def _boom(*a, **k):
        raise AssertionError("telemetry touched while disabled")

    monkeypatch.setattr(ev_mod.RunLog, "__init__", _boom)
    monkeypatch.setattr(ev_mod.RunLog, "emit", _boom)
    # Flight-recorder collectors (schema v2) are held to the same bar:
    # no shard probes while telemetry is off (the probe is a barrier).
    monkeypatch.setattr(mesh_lib, "shard_ready_times", _boom)
    # Cost observatory (schema v3), same bar: no collector install and —
    # the acceptance criterion — no compile()/re-lowering on the hot
    # path while telemetry is off (_capture is the only lowering site).
    from ddt_tpu.telemetry import costmodel

    monkeypatch.setattr(costmodel, "activate", _boom)
    monkeypatch.setattr(costmodel, "_capture", _boom)

    cfg = TrainConfig(n_trees=3, max_depth=3, n_bins=29, backend="tpu")
    be = TPUDevice(cfg)
    calls = {"sync": 0}
    real_sync = be.sync

    def counting_sync(x):
        calls["sync"] += 1
        return real_sync(x)

    monkeypatch.setattr(be, "sync", counting_sync)
    Xb, y = _binary(977)
    res = api.train(Xb, y, cfg, binned=True, backend=be)
    assert res.ensemble.n_trees == 3
    assert calls["sync"] == 0


def test_early_stop_event_and_driver_history_unchanged(tmp_path):
    """Granular CPU path: the early-stop decision lands in the log with
    best-round attribution, and Driver.history keeps its shape (the
    sklearn evals_result_ surface must not change under telemetry)."""
    Xb, y = _binary(1201, seed=2)
    rng = np.random.default_rng(3)
    Xv = rng.integers(0, 29, size=(301, 7), dtype=np.uint8)
    yv = rng.integers(0, 2, size=301).astype(np.float32)  # noise: stops
    rl = RunLog()                                         # ring-only
    res = api.train(Xb, y, binned=True, n_trees=40, max_depth=3,
                    n_bins=29, backend="cpu", eval_set=(Xv, yv),
                    early_stopping_rounds=2, run_log=rl)
    stops = rl.events("early_stop")
    assert len(stops) == 1
    es = stops[0]
    assert es["metric"] == "logloss"
    assert es["best_round"] == res.best_round + 1
    assert es["best_score"] == pytest.approx(res.best_score)
    assert res.ensemble.n_trees == res.best_round + 1
    # history round records match the run log's round events 1:1 here
    # (eval every round -> every round recorded in both).
    assert len(rl.events("round")) == len(res.history)


def test_checkpoint_resume_emits_fault_event(tmp_path):
    """Resume-from-checkpoint is the recovery story — the run log records
    it as a fault event carrying the resume round."""
    Xb, y = _binary(1301, seed=4)
    ck = str(tmp_path / "ck")
    api.train(Xb, y, binned=True, n_trees=2, max_depth=3, n_bins=29,
              backend="cpu", checkpoint_dir=ck)
    rl = RunLog()
    res = api.train(Xb, y, binned=True, n_trees=4, max_depth=3, n_bins=29,
                    backend="cpu", checkpoint_dir=ck, run_log=rl)
    faults = rl.events("fault")
    assert faults and faults[0]["kind"] == "checkpoint_resume"
    assert faults[0]["round"] == 2
    assert res.ensemble.n_trees == 4
    assert rl.events("run_end")[-1]["completed_rounds"] == 4


def test_owned_run_log_closed_when_fit_raises(tmp_path, monkeypatch):
    """A run log built from a PATH is Driver-owned: mid-run exceptions
    (here the NaN-eval guard) must still close the file handle — a
    long-lived process retrying failing fits must not leak fds. close()
    is observed directly (reading the file back would pass even with a
    leaked handle on POSIX)."""
    import ddt_tpu.telemetry.events as ev_mod

    closed = []
    real_close = ev_mod.RunLog.close

    def recording_close(self):
        closed.append(self.path)
        real_close(self)

    monkeypatch.setattr(ev_mod.RunLog, "close", recording_close)
    Xb, y = _binary(601, seed=7)
    Xv = np.zeros((50, 7), np.uint8)
    yv = np.zeros(50, np.float32)          # single-class: auc -> error
    log_path = str(tmp_path / "fail.jsonl")
    with pytest.raises(ValueError):
        api.train(Xb, y, binned=True, n_trees=5, max_depth=3, n_bins=29,
                  backend="cpu", eval_set=(Xv, yv), eval_metric="auc",
                  early_stopping_rounds=2, run_log=log_path)
    assert log_path in closed              # the ownership shim fired
    # The manifest got out before the failure: complete lines only.
    events = report.read_events(log_path)
    assert events[0]["event"] == "run_manifest"


# --------------------------------------------------------------------- #
# streaming integration
# --------------------------------------------------------------------- #
def test_streaming_host_run_log_and_phase_timer(tmp_path):
    from ddt_tpu.streaming import fit_streaming

    Xb, y = _binary(900, seed=5)
    bounds = [0, 300, 600, 900]

    def chunk_fn(c):
        return Xb[bounds[c]:bounds[c + 1]], y[bounds[c]:bounds[c + 1]]

    Xv, yv = _binary(200, seed=6)

    def valid_fn(c):
        return Xv, yv

    cfg = TrainConfig(n_trees=3, max_depth=3, n_bins=29, backend="cpu")
    rl = RunLog(str(tmp_path / "stream.jsonl"))
    history = []
    ens = fit_streaming(chunk_fn, 3, cfg, valid_chunk_fn=valid_fn,
                        n_valid_chunks=1, history=history, run_log=rl)
    rl.close()
    assert ens.n_trees == 3
    events = report.read_events(str(tmp_path / "stream.jsonl"))
    by_type = {}
    for e in events:
        by_type.setdefault(e["event"], []).append(e)
    man = by_type["run_manifest"][0]
    assert man["trainer"] == "streaming_host"
    assert man["n_chunks"] == 3
    assert man["device_kind"] == "host"       # the NumPy streaming loop
    rounds = by_type["round"]
    assert [r["round"] for r in rounds] == [1, 2, 3]
    assert all("valid_logloss" in r for r in rounds)
    # PhaseTimer wired into fit_streaming (satellite): the streamed hot
    # loop's phases appear in the embedded breakdown.
    phases = {p["phase"] for p in by_type["phase_timings"][-1]["phases"]}
    assert {"hist", "gain", "leaf", "eval"} <= phases
    assert by_type["run_end"][-1]["completed_rounds"] == 3
    # history (the _StreamEval surface) is unchanged by telemetry
    assert [h["round"] for h in history] == [1, 2, 3]


# --------------------------------------------------------------------- #
# report round trip (CLI) + smoke
# --------------------------------------------------------------------- #
def test_report_cli_round_trips_a_training_run(tmp_path, capsys):
    """The acceptance criterion end to end through the CLI: train with
    --run-log, then `report` renders it — phase timings, metric curve,
    and a nonzero recompile counter all present."""
    from ddt_tpu.cli import main

    log = str(tmp_path / "run.jsonl")
    model = str(tmp_path / "ens.npz")
    rc = main([
        "train", "--backend=tpu", "--dataset=higgs", "--rows=2357",
        "--trees=3", "--depth=3", "--bins=23", "--valid-frac=0.2",
        f"--run-log={log}", f"--out={model}",
    ])
    assert rc == 0
    train_out = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert train_out["run_log"] == log

    rc = main(["report", "--log", log, "--json"])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["counters"]["jit_compiles"] > 0
    assert summary["phases"]                     # per-phase timings
    assert summary["metric"] == "logloss"
    assert [c["round"] for c in summary["metric_curve"]] == [1, 2, 3]
    assert summary["completed_rounds"] == 3

    rc = main(["report", "--log", log])          # human rendering
    assert rc == 0
    text = capsys.readouterr().out
    assert "phases (host wallclock):" in text
    assert "jit_compiles=" in text
    assert "valid_logloss:" in text


def test_read_events_tolerates_torn_tail_keeps_records_pure(tmp_path):
    """A run killed mid-write tears only the FINAL line (append-only,
    line-buffered writes): the reader drops it, keeps everything above,
    and injects no out-of-schema marker keys into surviving records."""
    p = tmp_path / "torn.jsonl"
    with RunLog(str(p)) as rl:
        rl.emit("run_manifest", trainer="driver", backend="cpu",
                loss="logloss", n_trees=2, max_depth=3, rows=5, features=2)
        rl.emit("round", round=1, ms_per_round=1.0, train_loss=None)
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"event": "round", "schema": 1, "t":')   # torn mid-write
    events = report.read_events(str(p))
    assert [e["event"] for e in events] == ["run_manifest", "round"]
    for e in events:
        validate_event(e)
        assert "truncated_tail" not in e
    report.summarize(events)                              # still renders


def test_report_cli_fails_loudly_on_garbage(tmp_path):
    from ddt_tpu.cli import main

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event": "nonsense", "schema": 1, "t": 0, "seq": 0}\n'
                   '{"event": "run_end"}\n')
    with pytest.raises(SystemExit, match="unknown run-log event"):
        main(["report", "--log", str(bad)])
    with pytest.raises(SystemExit, match="report:"):
        main(["report", "--log", str(tmp_path / "missing.jsonl")])


def test_telemetry_smoke_script():
    """`make report`'s smoke, run in-process: 2 rounds on synthetic data,
    run log in a tmpdir, report on it (tier-1-safe)."""
    spec = importlib.util.spec_from_file_location(
        "telemetry_smoke", os.path.join(REPO, "scripts",
                                        "telemetry_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0


# --------------------------------------------------------------------- #
# counters unit behavior
# --------------------------------------------------------------------- #
def test_counter_snapshots_delta_and_estimate():
    c0 = tele_counters.snapshot()
    tele_counters.record_h2d(100)
    tele_counters.record_d2h(40)
    tele_counters.record_collective(7)
    d = tele_counters.delta(c0)
    assert (d["h2d_bytes"], d["d2h_bytes"], d["collective_bytes_est"]) \
        == (100, 40, 7)
    # depth-2, 3 features, 4 bins: levels 1+2 nodes of [F, bins, 2] f32
    # pairs + 4 leaf-aggregate pairs.
    assert tele_counters.hist_allreduce_bytes(2, 3, 4) \
        == (1 + 2) * 3 * 4 * 8 + 4 * 8
