"""readers/host_account.py on hand-made span records: `test_call_anatomy`'s
root of three chunks around the recorded device operations, given a second
piece whose upload holds the wait for the first, and a model's build before
it."""

import json
import os

import pytest

from readers import host_account
from test_call_anatomy import call, context, device  # noqa: F401 (fixture)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = {"score_upload_wait_ms": ("host_account", "upload_wait"),
       "score_host_unnamed_ms": ("host_account", "unnamed"),
       "setup_ensemble_ms": ("host_account", "setup_ensemble")}
ROUTED = ("score_upload_wait_ms", "score_host_unnamed_ms")


def span(spans, name, start, end, cause, root, **counts):
    """One more span; `root` None: a root of its own."""
    own = max(x["id"] for x in spans) + 50
    s = {"name": "ddt:" + name, "id": own, "cause": cause,
         "root": own if root is None else root, "start": start, "end": end,
         "counts": counts}
    spans.append(s)
    return s


def waited_call(execs, first_id=1, gc_pause=None):
    """`call()`'s root with a second piece: its upload after the last
    dispatch, 1 us of wait for piece 0 inside; (spans, wall)."""
    spans, wall = call(execs, first_id=first_id)
    root = spans.pop()                  # (and last again below)
    t0 = root["start"]
    first = next(s for s in spans if s["name"] == "ddt:predict:upload")
    first["counts"]["piece"] = 0
    later = span(spans, "predict:upload", t0 + 148_200, t0 + 149_800,
                 root["id"], root["id"], piece=1, bytes=286_720)
    span(spans, "predict:upload:wait", t0 + 148_500, t0 + 149_500,
         later["id"], root["id"], piece=0, bytes=573_440)
    if gc_pause is not None:
        root["counts"]["gc_pause_ns"] = gc_pause
    return spans + [root], wall


def set_up(before_ns):
    """A model's build, ended before `before_ns`: a root of its own, as a
    job's set-up makes."""
    t = before_ns - 10_000_000
    spans = [{"name": "ddt:predict:ensemble", "id": 9001, "cause": None,
              "root": 9001, "start": t + 3_000_000, "end": t + 4_500_000,
              "counts": {"bytes": 1024, "trees": 3}}]
    span(spans, "predict:ensemble:compile", t + 3_100_000, t + 3_900_000,
         9001, 9001, trees=3, nodes=24)
    span(spans, "predict:ensemble:pack", t + 3_900_000, t + 4_000_000,
         9001, 9001, bytes=1024)
    span(spans, "predict:ensemble:upload", t + 4_000_000, t + 4_400_000,
         9001, 9001, bytes=1024)
    return spans


def value(ctx, name):
    return host_account.read(ctx, {"value": NEW[name][1]})


def test_each_value_from_hand_made_spans(device, capsys):
    trace, execs = device
    spans, wall = waited_call(execs, gc_pause=250_000)
    ctx = context(trace, set_up(spans[-1]["start"]) + spans, wall)
    assert value(ctx, "score_upload_wait_ms") == pytest.approx(0.001)
    assert value(ctx, "setup_ensemble_ms") == pytest.approx(1.5)
    # the root's own: its extent minus what its spans cover
    root = spans[-1]
    covered = sum(s["end"] - s["start"] for s in spans
                  if s["cause"] == root["id"])
    assert value(ctx, "score_host_unnamed_ms") == pytest.approx(
        (root["end"] - root["start"] - covered) / 1e6)
    said = capsys.readouterr().out
    # the table: every name's self-time, `unnamed` last, summing to the
    # root that window_roots matched
    line, = [ln for ln in said.splitlines()
             if ln.startswith(f"host_account: call root {root['id']}:")]
    assert "apart by 0 ns" in line
    assert line.index("predict:upload:wait 0.001") < line.index("unnamed ")
    assert "unnamed" in line.split(" (sum")[0].split(" + ")[-1]
    assert "pauses: gc_pause_ns=250000" in line
    # piece 0's arrival beside call_anatomy's upload exposed, the link's
    # rate, the build's stages, the idle gaps with the host span open
    assert "piece 0 had landed" in said
    assert "piece 0: 573440 B" in said and "GB/s" in said
    assert "predict:ensemble:compile 0.800 ms" in said
    assert "its own 0.200 ms" in said
    assert "idle " in said and "host in predict:" in said


def test_two_calls_are_read_a_call(device):
    trace, execs = device
    later = [(a + 5_000_000, b + 5_000_000) for a, b in execs]
    one, wall = waited_call(execs)
    two, _ = waited_call(later, first_id=201)
    ctx = context(trace, one + two, wall)
    ctx.update(jobs=2, walls=ctx["walls"] * 2)
    ctx.pop("trace")                    # no value needs a device trace
    assert value(ctx, "score_upload_wait_ms") == pytest.approx(0.001)
    root = one[-1]
    covered = sum(s["end"] - s["start"] for s in one
                  if s["cause"] == root["id"])
    assert value(ctx, "score_host_unnamed_ms") == pytest.approx(
        (root["end"] - root["start"] - covered) / 1e6)


def test_a_program_without_the_span_reads_nothing(device):
    """The parent of PR 52: no wait span, and no `predict:ensemble` span
    before this window."""
    trace, execs = device
    spans, wall = waited_call(execs)
    old = [s for s in spans if s["name"] != "ddt:predict:upload:wait"]
    ctx = context(trace, old, wall)
    assert value(ctx, "score_upload_wait_ms") is None    # 2 pieces, no wait
    assert value(ctx, "setup_ensemble_ms") is None
    # one piece a call: nothing to wait for, whatever the program
    spans, wall = call(execs)
    assert value(context(trace, spans, wall), "score_upload_wait_ms") == 0.0
    # no spans at all, roots that are not the harness's jobs
    none = {"trace": trace, "walls": [0.001], "span": 0.001, "jobs": 1,
            "program_spans": []}
    other = context(trace, spans, wall, edge_ns=int(0.02 * wall))
    for name in NEW:
        assert value(none, name) is None
        assert value(other, name) is None


def test_a_program_without_account_leaves_unnamed_out(device, monkeypatch):
    trace, execs = device
    spans, wall = waited_call(execs)
    monkeypatch.setattr(host_account, "program_calls", lambda: (None, None))
    ctx = context(trace, spans, wall)
    assert value(ctx, "score_host_unnamed_ms") is None
    assert value(ctx, "score_upload_wait_ms") == pytest.approx(0.001)


def test_a_dispatch_that_blocked_is_printed(device, capsys):
    trace, execs = device
    spans, wall = waited_call(execs)
    first = next(s for s in spans if s["name"] == "ddt:predict:dispatch"
                 and s["counts"]["chunk"] == 0)
    first["start"] -= 100_000           # 108 us where the others take 8
    ctx = context(trace, spans, wall)
    ctx.pop("trace")
    host_account.read(ctx, {"value": "upload_wait"})
    assert ("1 of 3 dispatches blocked (over 10 x the fastest, 0.008 ms), "
            "the first at chunk 0, 0.108 ms together") \
        in capsys.readouterr().out


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_new_metric_files_are_named_by_the_manifest(name):
    manifest = json.load(open(os.path.join(os.path.dirname(HERE),
                                           "BENCHMARK.json")))
    cells = {w["name"] for w in manifest["workloads"]}
    for full in (name, name + ".routed")[:1 + (name in ROUTED)]:
        spec = json.load(open(os.path.join(HERE, "layer_metrics",
                                           full + ".json")))
        assert spec["reader"] == NEW[name][0]
        assert NEW[name][1] in spec["args"].values()
        entry, = [m for m in manifest["per_layer"] if m["name"] == full]
        assert entry["workloads"] and set(entry["workloads"]) <= cells
        assert entry["layer"] == "backends/tpu.py predict_raw chunk loop"
        moved, = [m for m in manifest["end_to_end"]
                  if m["name"] == entry["moves"]]
        assert set(entry["workloads"]) <= set(
            moved.get("workloads", cells))
        # the copy's file is its original's, byte for byte (PR 46's rule)
        assert open(os.path.join(HERE, "layer_metrics",
                                 full + ".json")).read() == open(
            os.path.join(HERE, "layer_metrics", name + ".json")).read()


def test_every_new_metric_file_has_its_entry_and_the_xgb_cell_is_left_out():
    """No file under layer_metrics/ that no entry names; and no new metric
    lists the XGBoost cell (`test_correct_xgb.py` holds that cell to the
    sixteen metrics PR 50 listed it on)."""
    manifest = json.load(open(os.path.join(os.path.dirname(HERE),
                                           "BENCHMARK.json")))
    named = {m["name"] for m in manifest["per_layer"]}
    files = {f.removesuffix(".json")
             for f in os.listdir(os.path.join(HERE, "layer_metrics"))}
    assert files == named
    mine = [m for m in manifest["per_layer"]
            if m["name"].removesuffix(".routed") in NEW]
    assert len(mine) == len(NEW) + len(ROUTED) == 5
    assert manifest["per_layer"][-5:] == mine       # appended, last
    assert not [m["name"] for m in mine
                if "covtype-xgb-d16-score-1chip" in m["workloads"]]
