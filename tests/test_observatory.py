"""Device-truth cost observatory (schema v3 — docs/OBSERVABILITY.md):
XLA cost-analysis capture + roofline verdicts, programmatic xprof
capture windows, and run-log diffing. CPU platform, tier-1.

Layers covered:
- cost_analysis event round trip through a REAL training run, and the
  report CLI's roofline table with bound-by verdicts for the hist, gain,
  and predict phases (the acceptance criterion, end to end);
- v1/v2 run logs still parse through report / merge / perfetto (the
  back-compat contract SCHEMA_VERSION bumps must keep);
- `report diff` flags a synthetic +30% gain-phase regression — with the
  right phase and counter named — and stays quiet on identical logs;
- the disabled path compiles/lowers nothing (extends the PR-2 zero-
  overhead guard; the run-side half lives in tests/test_telemetry.py);
- roofline verdict math on controlled synthetic inputs;
- the profiler capture window's parsing/block-capping and the
  profile-smoke script (`make profile-smoke`) in-process.
"""

import copy
import importlib.util
import json
import os

import numpy as np
import pytest

from ddt_tpu.telemetry import costmodel, diffing, perfetto, report
from ddt_tpu.telemetry import merge as tele_merge
from ddt_tpu.telemetry.events import RunLog
from ddt_tpu.telemetry.profiler import CaptureWindow, parse_rounds

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _binary(rows, features=7, bins=23, seed=0):
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    y = (Xb[:, 0] > bins // 2).astype(np.float32)
    return Xb, y


# --------------------------------------------------------------------- #
# capture: a real run emits cost_analysis; the roofline joins it
# --------------------------------------------------------------------- #
def _streaming_cli_log(tmp_path, capsys) -> str:
    """One real 2-round streamed train through the CLI with a run log —
    the log every acceptance assertion below reads."""
    from ddt_tpu.cli import main

    log = str(tmp_path / "stream.jsonl")
    model = str(tmp_path / "ens.npz")
    rc = main([
        "train", "--backend=tpu", "--dataset=higgs", "--rows=900",
        "--trees=2", "--depth=3", "--bins=23", "--stream-chunks=2",
        "--valid-frac=0.25", f"--run-log={log}", f"--out={model}",
    ])
    assert rc == 0
    capsys.readouterr()
    return log


def test_cost_events_and_roofline_on_real_run(tmp_path, capsys):
    """Acceptance: a real 2-round run log carries cost_analysis events
    for the streamed device programs, and `report` renders a roofline
    row WITH a bound-by verdict for at least hist, gain, and predict."""
    from ddt_tpu.cli import main

    log = _streaming_cli_log(tmp_path, capsys)
    events = report.read_events(log)
    cost = [e for e in events if e["event"] == "cost_analysis"]
    assert cost, "telemetry run emitted no cost_analysis events"
    by_op = {e["op"]: e for e in cost}
    # The streamed device loop's programs registered their cost.
    assert "stream_hist" in by_op
    assert "stream_update" in by_op          # the predict-phase scorer
    for e in cost:
        assert e["calls"] >= 1
        assert e["flops"] >= 0 and e["bytes_accessed"] >= 0
        assert e["platform"] == "cpu" and e["device_kind"] == "cpu"
        # memory_analysis landed (CPU XLA supports it on this jax).
        assert "signature" in e

    summary = report.summarize(events)
    roof = summary["roofline"]
    assert roof is not None
    rows = {r["phase"]: r for r in roof}
    verdicts = {"compute", "hbm", "recompile", "host"}
    for phase in ("hist", "gain", "predict"):
        assert phase in rows, (phase, sorted(rows))
        assert rows[phase]["verdict"] in verdicts
    # hist/predict carried device cost; gain is NumPy split selection by
    # design — no device program, so its verdict is host-side.
    assert rows["hist"]["gflops"] is not None
    assert rows["predict"]["gflops"] is not None
    assert rows["gain"]["verdict"] in ("host", "recompile")

    rc = main(["report", "--log", log])
    assert rc == 0
    text = capsys.readouterr().out
    assert "roofline (XLA cost model" in text
    for phase in ("hist", "gain", "predict"):
        # the phase's roofline row (not its phases-table row) carries
        # the "-> <verdict>" column
        assert any(ln.strip().startswith(phase) and "-> " in ln
                   for ln in text.splitlines()), (phase, text)
    assert "compiling)" in text              # compile-seconds satellite


def test_costed_wrapper_counts_calls_and_signatures():
    """CostedFn: one capture per (op, signature), a call count per
    signature, and full passthrough of the wrapped function."""
    import jax
    import jax.numpy as jnp

    calls = {"n": 0}

    @costmodel.costed("toy", phase="toyphase")
    @jax.jit
    def f(x):
        calls["n"] += 1              # traced: counts compiles, not calls
        return x * 2.0

    col = costmodel.activate()
    try:
        a = jnp.ones(8)
        b = jnp.ones(16)
        np.testing.assert_allclose(f(a), np.full(8, 2.0))
        f(a)
        f(b)
        evs = sorted(col.events(), key=lambda e: -e["calls"])
        assert [(e["op"], e["phase"], e["calls"]) for e in evs] == \
            [("toy", "toyphase", 2), ("toy", "toyphase", 1)]
        for e in evs:
            assert e["flops"] >= 0
            assert e["platform"] == "cpu"
    finally:
        costmodel.deactivate(col)
    # Wrapper passthrough: the underlying jit surface stays reachable.
    assert hasattr(f, "lower")


def test_analysis_compile_does_not_inflate_recompile_counters():
    """The capture's AOT analysis compile must not bill itself to the
    jit_compiles/jit_compile_seconds counters it exists to explain: one
    costed call = ONE counted compile, exactly like a telemetry-less
    run (a 2x-counters observatory would flag itself in report diff)."""
    import jax

    from ddt_tpu.telemetry import counters as tele_counters

    tele_counters.install_jax_listener()

    @costmodel.costed("toy3")
    @jax.jit
    def f(x):
        return x * 3.0

    col = costmodel.activate()
    try:
        c0 = tele_counters.snapshot()
        f(np.float32(2.0))               # fresh shape: capture + compile
        d = tele_counters.delta(c0)
        assert len(col.events()) == 1    # the capture DID run
        assert d["jit_compiles"] == 1, d
    finally:
        costmodel.deactivate(col)


def test_costmodel_analyze_sees_real_flops():
    import jax.numpy as jnp

    x = jnp.ones((64, 64), jnp.float32)
    rec = costmodel.analyze(lambda a: a @ a, x)
    assert rec.get("error") is None
    assert rec["flops"] > 64 * 64 * 64       # ~2*N^3 matmul flops
    assert rec["bytes_accessed"] > 0


def test_disabled_path_never_captures(monkeypatch):
    """No collector active -> a costed call must not lower, compile, or
    allocate capture state (the module-global read is the whole cost)."""
    import jax

    def _boom(*a, **k):
        raise AssertionError("capture ran while telemetry disabled")

    monkeypatch.setattr(costmodel, "_capture", _boom)

    @costmodel.costed("toy2")
    @jax.jit
    def f(x):
        return x + 1

    assert costmodel._active is None
    assert int(f(np.int32(1))) == 2          # plain call, no capture


def test_deactivate_only_removes_its_own_collector():
    c1 = costmodel.activate()
    c2 = costmodel.activate()                # replaces c1
    costmodel.deactivate(c1)                 # stale handle: no-op
    assert costmodel._active is c2
    costmodel.deactivate(c2)
    assert costmodel._active is None


# --------------------------------------------------------------------- #
# roofline verdict math (synthetic, controlled)
# --------------------------------------------------------------------- #
def _phase(name, ms, calls=1):
    return {"phase": name, "ms_total": ms,
            "ms_per_call": ms / calls, "calls": calls,
            "share": 1.0}


def _cost(phase, flops, byts, calls=1, device_kind="cpu"):
    return {"op": phase, "phase": phase, "flops": flops,
            "bytes_accessed": byts, "calls": calls, "platform": "cpu",
            "device_kind": device_kind}


def test_roofline_verdicts():
    peaks = costmodel.PEAK_CEILINGS["cpu"]   # 150 GFLOP/s, 30 GB/s
    # 100 ms wall: 50% compute util, negligible bytes -> compute-bound.
    compute = _cost("a", 0.5 * peaks["gflops"] * 1e9 * 0.1, 1e3)
    # 100 ms wall: 50% HBM util, negligible flops -> hbm-bound.
    hbm = _cost("b", 1e3, 0.5 * peaks["gbs"] * 1e9 * 0.1)
    # device barely touched, low compile share -> host.
    idle = _cost("c", 1e3, 1e3)
    rows = costmodel.roofline_table(
        [_phase("a", 100.0), _phase("b", 100.0), _phase("c", 100.0)],
        [compute, hbm, idle],
        counters={"jit_compile_seconds": 0.0}, wallclock_s=10.0)
    verdict = {r["phase"]: r["verdict"] for r in rows}
    assert verdict == {"a": "compute", "b": "hbm", "c": "host"}
    util = {r["phase"]: r for r in rows}
    assert util["a"]["flops_util"] == pytest.approx(0.5, rel=1e-3)
    assert util["b"]["hbm_util"] == pytest.approx(0.5, rel=1e-3)


def test_roofline_recompile_verdict_and_growblock_fold():
    # Idle device + compile time over the wall-share threshold ->
    # recompile; grow_block's row folds in the fetch_tree barrier.
    rows = costmodel.roofline_table(
        [_phase("grow_block", 400.0), _phase("fetch_tree", 600.0)],
        [_cost("grow_block", 1e3, 1e3)],
        counters={"jit_compile_seconds": 3.0}, wallclock_s=10.0)
    assert len(rows) == 1                    # fetch_tree folded away
    assert rows[0]["phase"] == "grow_block"
    assert rows[0]["ms"] == pytest.approx(1000.0)
    assert rows[0]["verdict"] == "recompile"


def test_roofline_unknown_device_raises():
    """A device PEAK_CEILINGS does not list has no peaks to divide by:
    an error, never another device's row (and "tpu" is a platform, not a
    device kind)."""
    for kind in ("TPU v9", "tpu", None):
        with pytest.raises(ValueError, match="no roofline peaks"):
            costmodel.roofline_table(
                [_phase("hist", 100.0)],
                [_cost("hist", 1e9, 1e9, device_kind=kind)])
    assert costmodel.peaks_for("TPU v5 lite")["gbs"] == 819.0


def test_roofline_phase_without_cost_is_host():
    rows = costmodel.roofline_table(
        [_phase("gain", 50.0), _phase("hist", 100.0)],
        [_cost("hist", 1e9, 1e9)])
    by = {r["phase"]: r for r in rows}
    assert by["gain"]["verdict"] == "host"
    assert by["gain"]["gflops"] is None


# --------------------------------------------------------------------- #
# schema back-compat: v1/v2 logs through report / merge / perfetto
# --------------------------------------------------------------------- #
def _v1_log(tmp_path, name="v1.jsonl"):
    """A minimal schema-1 log exactly as the PR-2 writer shaped it."""
    recs = [
        {"event": "run_manifest", "schema": 1, "t": 100.0, "seq": 0,
         "trainer": "driver", "backend": "tpu", "loss": "logloss",
         "n_trees": 2, "max_depth": 3, "rows": 100, "features": 4},
        {"event": "round", "schema": 1, "t": 101.0, "seq": 1,
         "round": 1, "ms_per_round": 9.0, "train_loss": 0.6},
        {"event": "round", "schema": 1, "t": 102.0, "seq": 2,
         "round": 2, "ms_per_round": 8.0, "train_loss": 0.5},
        {"event": "phase_timings", "schema": 1, "t": 102.5, "seq": 3,
         "phases": [{"phase": "grow", "ms_total": 17.0,
                     "ms_per_call": 8.5, "calls": 2, "share": 1.0}]},
        {"event": "counters", "schema": 1, "t": 102.6, "seq": 4,
         "jit_compiles": 2, "h2d_bytes": 400, "d2h_bytes": 60,
         "collective_bytes_est": 0},
        {"event": "run_end", "schema": 1, "t": 102.7, "seq": 5,
         "completed_rounds": 2, "wallclock_s": 2.7},
    ]
    p = tmp_path / name
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(p)


def _v2_log(tmp_path, host, name=None):
    """A schema-2 flight-recorder log (run_id/host manifest extras +
    partition events) — no v3 fields anywhere."""
    recs = [
        {"event": "run_manifest", "schema": 2, "t": 100.0 + host,
         "seq": 0, "trainer": "driver", "backend": "tpu",
         "loss": "logloss", "n_trees": 1, "max_depth": 3, "rows": 100,
         "features": 4, "run_id": "cafe01234567", "host": host},
        {"event": "partition_phases", "schema": 2, "t": 101.0 + host,
         "seq": 1, "round": 1, "rounds": 1, "partitions": [
             {"device": 0, "phases": {"grow": 5.0},
              "hist_allreduce_bytes": 64},
             {"device": 1, "phases": {"grow": 7.0},
              "hist_allreduce_bytes": 64}]},
        {"event": "partition_skew", "schema": 2, "t": 101.5 + host,
         "seq": 2, "phases": [
             {"phase": "grow", "ms_max": 7.0, "ms_median": 6.0,
              "skew": 1.167, "max_device": 1}], "n_partitions": 2},
        {"event": "run_end", "schema": 2, "t": 102.0 + host, "seq": 3,
         "completed_rounds": 1, "wallclock_s": 2.0},
    ]
    p = tmp_path / (name or f"v2_h{host}.jsonl")
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(p)


def test_v1_log_still_reads_summarizes_and_traces(tmp_path):
    p = _v1_log(tmp_path)
    events = report.read_events(p)           # validates every record
    summary = report.summarize(events)
    assert summary["completed_rounds"] == 2
    assert summary["roofline"] is None       # no v3 events -> no table
    assert summary["cost_events"] == []
    text = report.render(summary)
    assert "roofline" not in text            # renders exactly as before
    out = tmp_path / "v1_trace.json"
    n = perfetto.write_trace(events, str(out))
    trace = json.loads(out.read_text())
    assert len(trace["traceEvents"]) == n > 0


def test_v2_logs_still_merge_and_report(tmp_path):
    p0, p1 = _v2_log(tmp_path, 0), _v2_log(tmp_path, 1)
    merged = tele_merge.merge_paths([p0, p1])
    assert len(merged) == 8
    summary = report.summarize(merged)
    assert summary["hosts"] == [0, 1]
    assert summary["partition_skew"]         # cross-host recompute ran
    assert summary["roofline"] is None
    n = perfetto.write_trace(merged, str(tmp_path / "v2_trace.json"))
    assert n > 0


def test_v3_diff_reads_v1_logs_too(tmp_path):
    """The differ runs on pre-v3 logs (no cost events): phases and
    counters still align."""
    a = report.summarize(report.read_events(_v1_log(tmp_path, "a.jsonl")))
    b = report.summarize(report.read_events(_v1_log(tmp_path, "b.jsonl")))
    d = diffing.diff_summaries(a, b)
    assert d["flagged"] == []
    assert d["cost"] == []


# --------------------------------------------------------------------- #
# report diff
# --------------------------------------------------------------------- #
def _perturb_log(src_path: str, dst_path: str, gain_factor: float,
                 h2d_factor: float) -> None:
    """Clone a run log with the gain phase slowed by `gain_factor` and
    the H2D transfer counter inflated — the synthetic regression.
    (h2d_bytes rather than jit_compiles: the upload counter is nonzero
    on EVERY run, while a warm jit cache can legitimately leave the
    baseline's recompile count at 0 — and a zero baseline is exactly
    the case the differ declines to band.)"""
    with open(src_path, encoding="utf-8") as f:
        lines = f.read().splitlines()
    out = []
    for line in lines:
        rec = json.loads(line)
        if rec["event"] == "phase_timings":
            rec = copy.deepcopy(rec)
            for p in rec["phases"]:
                if p["phase"] == "gain":
                    p["ms_total"] = round(p["ms_total"] * gain_factor, 3)
                    p["ms_per_call"] = round(
                        p["ms_per_call"] * gain_factor, 4)
        if rec["event"] == "counters":
            rec = dict(rec, h2d_bytes=int(rec["h2d_bytes"] * h2d_factor))
        out.append(json.dumps(rec))
    with open(dst_path, "w", encoding="utf-8") as f:
        f.write("\n".join(out) + "\n")


def test_report_diff_flags_synthetic_gain_regression(tmp_path, capsys):
    """Acceptance: diff attributes a synthetic +30%-past-band gain-phase
    regression to the right phase AND counter, and stays quiet on
    identical logs."""
    from ddt_tpu.cli import main

    log_a = _streaming_cli_log(tmp_path, capsys)
    log_b = str(tmp_path / "regressed.jsonl")
    # +30% on the gain phase (the ISSUE's synthetic regression) plus a
    # 4x transfer-bytes jump; the absolute floor is dropped because this
    # micro-run's real gain timings are sub-millisecond.
    _perturb_log(log_a, log_b, gain_factor=1.30001, h2d_factor=4.0)

    rc = main(["report", "diff", log_a, log_b, "--json",
               "--abs-floor-ms=0"])
    assert rc == 0
    d = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert any(f.startswith("gain +") for f in d["flagged"]), d["flagged"]
    assert any(f.startswith("h2d_bytes ") for f in d["flagged"])
    gain = next(p for p in d["phases"] if p["phase"] == "gain")
    assert gain["flag"] == "slower"
    hist = next(p for p in d["phases"] if p["phase"] == "hist")
    assert hist["flag"] is None              # regression stays attributed

    # Identical logs: quiet, and --check exits 0.
    rc = main(["report", "diff", log_a, log_a, "--check"])
    assert rc == 0
    text = capsys.readouterr().out
    assert "no adverse excursions" in text

    # --check turns a flagged diff into exit 1 (CI mode).
    rc = main(["report", "diff", log_a, log_b, "--check",
               "--abs-floor-ms=0"])
    capsys.readouterr()
    assert rc == 1


def test_diff_directionality_and_structure():
    """Unit checks on the band logic: favorable moves never flag, new /
    gone phases are marked, cache-hit counter flags on DECREASE."""
    a = {"phases": [_phase("hist", 1000.0), _phase("old", 100.0)],
         "counters": {"jit_compiles": 10,
                      "compiled_ensemble_cache_hits": 50},
         "cost_events": [_cost("hist", 1e9, 2e9)],
         "completed_rounds": 2, "wallclock_s": 2.0}
    b = {"phases": [_phase("hist", 500.0), _phase("new", 100.0)],
         "counters": {"jit_compiles": 11,
                      "compiled_ensemble_cache_hits": 0},
         "cost_events": [_cost("hist", 1e9, 2e9)],
         "completed_rounds": 2, "wallclock_s": 1.5}
    d = diffing.diff_summaries(a, b)
    by = {p["phase"]: p for p in d["phases"]}
    assert by["hist"]["flag"] is None        # 2x FASTER: never flagged
    assert by["old"]["flag"] == "gone"
    assert by["new"]["flag"] == "new"
    assert any("compiled_ensemble_cache_hits" in f for f in d["flagged"])
    # jit_compiles 10 -> 11 is inside the 20% band: not flagged.
    jc = next(c for c in d["counters"] if c["counter"] == "jit_compiles")
    assert jc["flag"] is None
    # hist cost identical: no bytes-bloat.
    assert all(c["flag"] is None for c in d["cost"])


def test_diff_unknown_and_neutral_counter_directions():
    """ISSUE 16 satellite: a counter missing from COUNTER_DIRECTIONS is
    reported with a loud direction=? marker (text AND --json record) but
    never flagged; a declared-"neutral" counter is banded in NO
    direction — a 10x workload-shape move stays quiet."""
    a = {"phases": [], "counters": {"mystery_counter": 10,
                                    "serve_requests": 10},
         "cost_events": [], "completed_rounds": 1, "wallclock_s": 1.0}
    b = {"phases": [], "counters": {"mystery_counter": 100,
                                    "serve_requests": 100},
         "cost_events": [], "completed_rounds": 1, "wallclock_s": 1.0}
    d = diffing.diff_summaries(a, b)
    by = {c["counter"]: c for c in d["counters"]}
    assert by["mystery_counter"]["direction"] == "?"
    assert by["mystery_counter"]["flag"] is None
    assert by["serve_requests"]["direction"] == "neutral"
    assert by["serve_requests"]["flag"] is None
    assert d["flagged"] == []
    text = diffing.render_diff(d)
    assert "mystery_counter" in text and "serve_requests" in text
    # exactly one marker: the unregistered counter, not the neutral one
    assert text.count("direction=?") == 1


# --------------------------------------------------------------------- #
# profiler capture window
# --------------------------------------------------------------------- #
def test_parse_rounds():
    assert parse_rounds("5:8") == (5, 8)
    assert parse_rounds("4") == (4, 4)
    with pytest.raises(ValueError, match="LO:HI"):
        parse_rounds("a:b")
    with pytest.raises(ValueError, match="empty or starts"):
        parse_rounds("8:5")
    with pytest.raises(ValueError, match="empty or starts"):
        parse_rounds("0:3")


def test_block_cap_aligns_blocks_to_window_edges(tmp_path):
    w = CaptureWindow(str(tmp_path), "5:8")
    # block [0, 10) must break at round 4 (0-based start edge lo-1=4).
    assert w.block_cap(0, 10) == 4
    # block [4, 10) must break at the stop edge hi=8.
    assert w.block_cap(4, 10) == 4
    # blocks fully inside or outside the window pass through.
    assert w.block_cap(4, 4) == 4
    assert w.block_cap(8, 10) == 10
    assert w.block_cap(0, 3) == 3


def test_capture_window_manifest_fields_and_close(tmp_path):
    w = CaptureWindow(str(tmp_path / "xp"), "1:2")
    w.bind("deadbeef0123")
    m = w.manifest_fields()
    assert m["xprof_rounds"] == [1, 2]
    assert os.path.basename(m["xprof_dir"]) == "run_deadbeef0123"
    # closing an unopened window is safe and terminal.
    w.close()
    assert not w.active
    w.round_start(0)                         # done: never restarts
    assert not w.active


def test_profile_smoke_script():
    """`make profile-smoke`, in-process (tier-1, non-slow): 2-round CPU
    capture-window train; asserts the manifest cross-reference fields
    and the written trace."""
    spec = importlib.util.spec_from_file_location(
        "profile_smoke", os.path.join(REPO, "scripts",
                                      "profile_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0
