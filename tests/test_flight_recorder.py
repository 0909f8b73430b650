"""Distributed flight recorder (docs/OBSERVABILITY.md): per-partition
attribution on a CPU mesh, the cross-host run-log merge and the Perfetto
trace-event export. CPU platform, tier-1; the 8-virtual-device mesh comes from conftest."""

import copy
import json
import os

import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.config import TrainConfig
from ddt_tpu.telemetry import merge, perfetto, report
from ddt_tpu.telemetry.events import (
    PartitionRecorder, RunLog, partition_skew_summary)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _binary(rows, features=7, bins=29, seed=0):
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    y = (Xb[:, 0] > bins // 2).astype(np.float32)
    return Xb, y


def _recompute_skew(events):
    """Offline recompute of the skew reduction from the raw
    partition_phases stream — the acceptance contract: the emitted
    partition_skew must equal this."""
    totals = {}
    for e in events:
        if e["event"] != "partition_phases":
            continue
        for part in e["partitions"]:
            d = totals.setdefault(part["device"], {})
            for ph, ms in part["phases"].items():
                d[ph] = d.get(ph, 0.0) + ms
    return partition_skew_summary(totals)


# --------------------------------------------------------------------- #
# per-partition attribution (tentpole part 1)
# --------------------------------------------------------------------- #
def test_mesh_dryrun_partition_skew_matches_offline_recompute(tmp_path):
    """The acceptance criterion: a 4-partition CPU-mesh run produces a
    log whose partition_skew matches per-partition timings recomputed
    offline from the partition_phases events."""
    Xb, y = _binary(2048)
    path = str(tmp_path / "mesh.jsonl")
    with RunLog(path) as rl:
        api.train(Xb, y, binned=True, n_trees=4, max_depth=3, n_bins=29,
                  backend="tpu", n_partitions=4, run_log=rl)
    events = report.read_events(path)
    pp = [e for e in events if e["event"] == "partition_phases"]
    assert pp, "mesh run with a run log must emit partition_phases"
    for e in pp:
        devs = [p["device"] for p in e["partitions"]]
        assert devs == sorted(devs) and len(devs) == 4
        for p in e["partitions"]:
            assert p["hist_allreduce_bytes"] > 0
            assert all(ms >= 0 for ms in p["phases"].values())
    skew = [e for e in events if e["event"] == "partition_skew"]
    assert len(skew) == 1
    assert skew[-1]["n_partitions"] == 4
    recomputed = _recompute_skew(events)
    emitted = skew[-1]["phases"]
    assert [p["phase"] for p in emitted] == [p["phase"]
                                             for p in recomputed]
    for a, b in zip(emitted, recomputed):
        assert a["ms_max"] == pytest.approx(b["ms_max"], abs=0.01)
        assert a["ms_median"] == pytest.approx(b["ms_median"], abs=0.01)
        assert a["max_device"] == b["max_device"]
    # the manifest carries the v2 merge keys
    man = events[0]
    assert man["event"] == "run_manifest"
    assert len(man["run_id"]) == 12 and man["host"] == 0
    # ...and the report renders a straggler table from it
    summary = report.summarize(events)
    assert summary["n_partitions"] == 4
    assert summary["partition_skew"] == emitted
    assert "partitions (4 lanes" in report.render(summary)


def test_streaming_mesh_run_emits_partition_lanes(tmp_path):
    """The streaming device trainer's chunk passes carry per-partition
    lanes too (hist/leaf/roundstart phases)."""
    from ddt_tpu.streaming import fit_streaming

    Xb, y = _binary(960, seed=3)
    bounds = [0, 480, 960]

    def chunk_fn(c):
        return Xb[bounds[c]:bounds[c + 1]], y[bounds[c]:bounds[c + 1]]

    cfg = TrainConfig(n_trees=2, max_depth=3, n_bins=29, backend="tpu",
                      n_partitions=2)
    path = str(tmp_path / "stream.jsonl")
    with RunLog(path) as rl:
        fit_streaming(chunk_fn, 2, cfg, run_log=rl)
    events = report.read_events(path)
    pp = [e for e in events if e["event"] == "partition_phases"]
    assert len(pp) == 2                       # one per round
    phases = {ph for e in pp for p in e["partitions"]
              for ph in p["phases"]}
    assert "hist" in phases and "leaf" in phases
    assert "roundstart" in phases             # round 2's fused start pass
    skew = [e for e in events if e["event"] == "partition_skew"]
    assert skew and skew[-1]["n_partitions"] == 2
    assert _recompute_skew(events)[0]["phase"] == \
        skew[-1]["phases"][0]["phase"]


def test_disabled_telemetry_never_probes_shards(monkeypatch):
    """PR-2 invariant extended to the new collectors: with no run log, a
    DISTRIBUTED fit must never touch the shard probe (the probe is a
    device barrier) nor construct partition events."""
    from ddt_tpu.parallel import mesh as mesh_lib

    def _boom(*a, **k):
        raise AssertionError("shard probe touched with telemetry off")

    monkeypatch.setattr(mesh_lib, "shard_ready_times", _boom)
    Xb, y = _binary(1024, seed=5)
    res = api.train(Xb, y, binned=True, n_trees=2, max_depth=3,
                    n_bins=29, backend="tpu", n_partitions=2)
    assert res.ensemble.n_trees == 2


def test_partition_recorder_inert_without_mesh_or_log():
    class Backend:
        distributed = True

        def partition_ready_ms(self, h):      # pragma: no cover
            raise AssertionError("probed")

    # no run log -> inactive even on a distributed backend
    rec = PartitionRecorder(None, Backend())
    assert not rec.active
    rec.observe("grow", object(), 0.0)        # no probe, no error
    rec.flush_round(0)
    rec.emit_skew()
    # run log but single-device backend -> inactive
    class Single:
        distributed = False

        def partition_ready_ms(self, h):      # pragma: no cover
            raise AssertionError("probed")

    rec = PartitionRecorder(RunLog(), Single())
    assert not rec.active


def test_partition_skew_summary_reduction():
    totals = {0: {"grow": 10.0, "eval": 1.0},
              1: {"grow": 30.0, "eval": 1.0},
              2: {"grow": 20.0, "eval": 4.0}}
    out = partition_skew_summary(totals)
    assert [p["phase"] for p in out] == ["grow", "eval"]   # by ms_max
    grow = out[0]
    assert grow["ms_max"] == 30.0 and grow["max_device"] == 1
    assert grow["ms_median"] == 20.0
    assert grow["skew"] == pytest.approx(1.5)
    ev = out[1]
    assert ev["ms_max"] == 4.0 and ev["max_device"] == 2
    assert ev["ms_median"] == 1.0 and ev["skew"] == 4.0


# --------------------------------------------------------------------- #
# cross-host merge (tentpole part 3)
# --------------------------------------------------------------------- #
def _fabricate_two_hosts(tmp_path, offset_s=5.25):
    """One real single-host run log + a fabricated host-1 twin whose
    clock runs `offset_s` ahead and whose rounds interleave."""
    Xb, y = _binary(1200, seed=7)
    p0 = str(tmp_path / "host0.jsonl")
    with RunLog(p0) as rl:
        api.train(Xb, y, binned=True, n_trees=3, max_depth=3, n_bins=29,
                  backend="cpu", run_log=rl)
    ev0 = report.read_events(p0)
    p1 = str(tmp_path / "host1.jsonl")
    with open(p1, "w", encoding="utf-8") as f:
        for e in ev0:
            e2 = copy.deepcopy(e)
            e2["t"] += offset_s                # skewed wall clock
            e2["host"] = 1
            if e2["event"] == "round":         # a straggling host
                e2["ms_per_round"] += 1.0
            f.write(json.dumps(e2) + "\n")
    return p0, p1, ev0


def test_two_host_merge_offset_and_deterministic_order(tmp_path):
    p0, p1, ev0 = _fabricate_two_hosts(tmp_path)
    merged = merge.merge_paths([p0, p1])
    assert len(merged) == 2 * len(ev0)
    # clock offset estimated away: both manifests land at (near) the
    # same adjusted time, far closer than the fabricated 5.25 s skew
    mans = [e for e in merged if e["event"] == "run_manifest"]
    assert len(mans) == 2
    assert abs(mans[0]["t"] - mans[1]["t"]) < 1e-6
    # deterministic: argument order cannot change the merged stream
    key = [(e["event"], e["host"], round(e["t"], 6), e["seq"])
           for e in merged]
    swapped = merge.merge_paths([p1, p0])
    assert key == [(e["event"], e["host"], round(e["t"], 6), e["seq"])
                   for e in swapped]
    # times are monotone and rounds interleave host 0/1 adjacently
    ts = [e["t"] for e in merged]
    assert ts == sorted(ts)
    rounds = [(e["round"], e["host"]) for e in merged
              if e["event"] == "round"]
    assert rounds == [(r, h) for r in (1, 2, 3) for h in (0, 1)]


def test_merge_refuses_mismatched_run_ids(tmp_path):
    p0, p1, _ = _fabricate_two_hosts(tmp_path)
    other = str(tmp_path / "other.jsonl")
    evs = report.read_events(p1)
    with open(other, "w", encoding="utf-8") as f:
        for e in evs:
            e2 = dict(e)
            if e2["event"] == "run_manifest":
                e2["run_id"] = "feedfeedfeed"
            f.write(json.dumps(e2) + "\n")
    with pytest.raises(ValueError, match="different runs"):
        merge.merge_paths([p0, other])


def test_merged_report_single_segment_and_one_curve(tmp_path, capsys):
    from ddt_tpu.cli import main

    p0, p1, _ = _fabricate_two_hosts(tmp_path)
    rc = main(["report", "--log", p0, "--log", p1, "--json"])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["n_runs_in_log"] == 1      # two hosts, ONE run
    assert summary["hosts"] == [0, 1]
    assert summary["n_round_records"] == 3    # one lane's curve, not 6
    rc = main(["report", "--log", p0, "--log", p1])
    assert rc == 0
    assert "hosts: 2 merged" in capsys.readouterr().out


def test_merged_straggler_table_covers_every_host(tmp_path):
    """On a merged pod log each host's partition_skew covers only its
    own devices — the report must recompute the table over EVERY host's
    partition_phases lanes, so a straggler on host 0 stays visible (and
    the fused `rounds` extra counts rounds, not events)."""
    def host_log(path, host, t0, grow_ms):
        with open(path, "w", encoding="utf-8") as f:
            recs = [
                {"event": "run_manifest", "trainer": "driver",
                 "backend": "tpu", "loss": "logloss", "n_trees": 3,
                 "max_depth": 3, "rows": 64, "features": 4,
                 "run_id": "aaaabbbbcccc", "host": host},
                {"event": "partition_phases", "round": 1, "rounds": 3,
                 "partitions": [
                     {"device": host * 2 + d,
                      "phases": {"grow_block": grow_ms[d]},
                      "hist_allreduce_bytes": 128} for d in (0, 1)]},
                {"event": "partition_skew", "n_partitions": 2,
                 "phases": [{"phase": "grow_block",
                             "ms_max": max(grow_ms),
                             "ms_median": sum(grow_ms) / 2,
                             "skew": 1.0,
                             "max_device": host * 2}]},
                {"event": "run_end", "completed_rounds": 3,
                 "wallclock_s": 1.0},
            ]
            for i, r in enumerate(recs):
                f.write(json.dumps({"schema": 2, "t": t0 + i * 0.1,
                                    "seq": i, **r}) + "\n")

    p0 = str(tmp_path / "h0.jsonl")
    p1 = str(tmp_path / "h1.jsonl")
    host_log(p0, 0, 100.0, [50.0, 90.0])      # host 0 holds the straggler
    host_log(p1, 1, 104.5, [10.0, 20.0])
    summary = report.summarize(merge.merge_paths([p0, p1]))
    assert summary["n_partitions"] == 4       # all lanes, both hosts
    assert summary["partition_rounds_observed"] == 3   # rounds, not events
    row = summary["partition_skew"][0]
    assert row["phase"] == "grow_block"
    assert row["ms_max"] == 90.0
    assert (row["max_host"], row["max_device"]) == (0, 1)
    assert row["ms_median"] == pytest.approx(35.0)     # median of 4 lanes
    text = report.render(summary)
    assert "@h0/dev1" in text


def test_single_log_from_nonzero_host_keeps_partition_rounds(tmp_path):
    """A lone pod host's UN-merged log (manifest host=N, events carry no
    host field) must still count its partition rounds and use its own
    skew event verbatim."""
    p = str(tmp_path / "h2.jsonl")
    with open(p, "w", encoding="utf-8") as f:
        recs = [
            {"event": "run_manifest", "trainer": "driver",
             "backend": "tpu", "loss": "logloss", "n_trees": 2,
             "max_depth": 3, "rows": 64, "features": 4,
             "run_id": "aaaabbbbcccc", "host": 2},
            {"event": "partition_phases", "round": 1, "rounds": 2,
             "partitions": [{"device": 4, "phases": {"grow_block": 5.0},
                             "hist_allreduce_bytes": 64},
                            {"device": 5, "phases": {"grow_block": 7.0},
                             "hist_allreduce_bytes": 64}]},
            {"event": "partition_skew", "n_partitions": 2,
             "phases": [{"phase": "grow_block", "ms_max": 7.0,
                         "ms_median": 6.0, "skew": 1.167,
                         "max_device": 5}]},
            {"event": "run_end", "completed_rounds": 2,
             "wallclock_s": 1.0},
        ]
        for i, r in enumerate(recs):
            f.write(json.dumps({"schema": 2, "t": 10.0 + i, "seq": i,
                                **r}) + "\n")
    summary = report.summarize(report.read_events(p))
    assert summary["hosts"] == [2]
    assert summary["partition_rounds_observed"] == 2
    assert summary["n_partitions"] == 2
    assert summary["partition_skew"][0]["max_device"] == 5


def test_merge_hostless_v1_logs_stays_deterministic(tmp_path):
    """Pre-v2 logs (no host/run_id stamps): host labels come from
    manifest-time rank, so swapping the file arguments cannot change
    the merged stream."""
    def v1_log(path, t0):
        with open(path, "w", encoding="utf-8") as f:
            recs = [
                {"event": "run_manifest", "trainer": "driver",
                 "backend": "cpu", "loss": "logloss", "n_trees": 1,
                 "max_depth": 3, "rows": 8, "features": 2},
                {"event": "round", "round": 1, "ms_per_round": 2.0,
                 "train_loss": None},
                {"event": "run_end", "completed_rounds": 1,
                 "wallclock_s": 0.1},
            ]
            for i, r in enumerate(recs):
                f.write(json.dumps({"schema": 1, "t": t0 + i * 0.1,
                                    "seq": i, **r}) + "\n")
    pa = str(tmp_path / "a.jsonl")
    pb = str(tmp_path / "b.jsonl")
    v1_log(pa, 50.0)
    v1_log(pb, 57.0)
    key = [(e["event"], e["host"], round(e["t"], 6), e["seq"])
           for e in merge.merge_paths([pa, pb])]
    assert key == [(e["event"], e["host"], round(e["t"], 6), e["seq"])
                   for e in merge.merge_paths([pb, pa])]
    # the earlier-manifest log is host 0 either way
    assert key[0][1] == 0


def test_same_host_restart_still_two_segments(tmp_path):
    """A preemptible restart appends a second segment with the SAME
    config-deterministic run_id on the SAME host — that must stay two
    segments, not collapse into a pod merge."""
    Xb, y = _binary(800, seed=11)
    path = str(tmp_path / "restart.jsonl")
    for _ in range(2):
        with RunLog(path) as rl:
            api.train(Xb, y, binned=True, n_trees=2, max_depth=3,
                      n_bins=29, backend="cpu", run_log=rl)
    summary = report.summarize(report.read_events(path))
    assert summary["n_runs_in_log"] == 2
    assert summary["n_round_records"] == 2    # last segment only


# --------------------------------------------------------------------- #
# perfetto export (tentpole part 2)
# --------------------------------------------------------------------- #
_PH_KNOWN = {"X", "i", "M"}


def _validate_trace(trace):
    """The trace-event field contract ui.perfetto.dev's importer needs:
    JSON object form, every record fully typed."""
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    assert trace["displayTimeUnit"] == "ms"
    assert trace["traceEvents"], "empty trace"
    for rec in trace["traceEvents"]:
        assert isinstance(rec["name"], str) and rec["name"]
        assert rec["ph"] in _PH_KNOWN
        assert isinstance(rec["ts"], (int, float)) and rec["ts"] >= 0
        assert isinstance(rec["pid"], int)
        assert isinstance(rec["tid"], int)
        if rec["ph"] == "X":
            assert isinstance(rec["dur"], (int, float)) and rec["dur"] >= 0
        if rec["ph"] == "M":
            assert rec["name"] in ("process_name", "thread_name")
            assert isinstance(rec["args"]["name"], str)


def test_trace_export_mesh_run_has_partition_lanes(tmp_path):
    Xb, y = _binary(2048, seed=13)
    path = str(tmp_path / "mesh.jsonl")
    with RunLog(path) as rl:
        api.train(Xb, y, binned=True, n_trees=3, max_depth=3, n_bins=29,
                  backend="tpu", n_partitions=4, run_log=rl)
    events = report.read_events(path)
    trace = perfetto.to_trace_events(events)
    _validate_trace(trace)
    recs = trace["traceEvents"]
    # round slices on tid 0, partition lanes on tids 1..4
    assert any(r["ph"] == "X" and r["tid"] == 0
               and r["name"].startswith("round ") for r in recs)
    lane_tids = {r["tid"] for r in recs
                 if r["ph"] == "X" and r["name"].startswith("ddt:")}
    assert lane_tids == {1, 2, 3, 4}
    lanes = [r for r in recs if r["ph"] == "X"
             and r["name"].startswith("ddt:")]
    assert all(r["args"]["hist_allreduce_bytes"] > 0 for r in lanes)
    # durations in the lanes equal the logged per-phase ms (µs scale)
    pp = [e for e in events if e["event"] == "partition_phases"][0]
    dev0 = pp["partitions"][0]
    got = [r for r in lanes if r["args"]["device"] == 0
           and r["args"]["round"] == pp["round"]]
    assert sorted(r["dur"] for r in got) == pytest.approx(
        sorted(ms * 1e3 for ms in dev0["phases"].values()))


def test_trace_cli_merged_two_hosts_parses(tmp_path, capsys):
    from ddt_tpu.cli import main

    p0, p1, _ = _fabricate_two_hosts(tmp_path)
    out = str(tmp_path / "trace.json")
    rc = main(["trace", "--log", p0, "--log", p1, "--out", out])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["out"] == out and line["trace_events"] > 0
    with open(out, encoding="utf-8") as f:
        trace = json.load(f)                  # asserts it parses
    _validate_trace(trace)
    pids = {r["pid"] for r in trace["traceEvents"]}
    assert pids == {0, 1}                     # one process per host
    names = {r["args"]["name"] for r in trace["traceEvents"]
             if r["ph"] == "M" and r["name"] == "process_name"}
    assert len(names) == 2


def test_trace_cli_fails_loudly_on_garbage(tmp_path):
    from ddt_tpu.cli import main

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event": "nonsense", "schema": 1, "t": 0, "seq": 0}\n')
    with pytest.raises(SystemExit, match="trace:"):
        main(["trace", "--log", str(bad), "--out",
              str(tmp_path / "t.json")])


def test_trace_smoke_script():
    """`make trace-smoke` run in-process: mesh train -> merge -> export
    -> parse (tier-1-safe; conftest's 8-device mesh covers the 2 the
    script asks for)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_smoke", os.path.join(REPO, "scripts", "trace_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0


# --------------------------------------------------------------------- #
# host RSS (satellite)
# --------------------------------------------------------------------- #
def test_host_rss_counter_recorded_and_rendered(tmp_path):
    from ddt_tpu.telemetry import counters as tele_counters

    rss = tele_counters.host_peak_rss_bytes()
    assert rss is None or rss > 1 << 20       # a python process is >1 MiB
    Xb, y = _binary(700, seed=17)
    path = str(tmp_path / "rss.jsonl")
    with RunLog(path) as rl:
        api.train(Xb, y, binned=True, n_trees=2, max_depth=3, n_bins=29,
                  backend="cpu", run_log=rl)
    events = report.read_events(path)
    c = [e for e in events if e["event"] == "counters"][-1]
    assert "host_peak_rss_bytes" in c
    assert c["host_peak_rss_bytes"] is None \
        or c["host_peak_rss_bytes"] > 1 << 20
    text = report.render(report.summarize(events))
    assert "host_rss_peak=" in text
