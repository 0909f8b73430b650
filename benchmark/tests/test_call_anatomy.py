"""readers/call_anatomy.py and readers/first_call_extra.py on the scoring call
of the trace recorded on the chip (`recorded_trace.json.gz`, PR 24: one
execution of `jit_predict_raw_effective`), repeated three times as three
chunks, plus hand-written span records built around those device operations
on a host clock that runs a second ahead of the profile's."""

import gzip
import json
import os

import pytest

import tracefile
from readers import call_anatomy, first_call_extra

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_trace.json.gz")
D = 1_000_000_000            # host clock = profile clock + 1 s
STEP = 400_000               # ns between the chunks' executions
LATENCY = (30_000, 20_000, 50_000)   # fetch[k] ends this long after chunk k


@pytest.fixture(scope="module")
def device():
    """(trace of three executions, [(start, end)] of each on the profile's
    clock): the recorded scoring execution and its operations, three times."""
    with gzip.open(RECORDED, "rt") as f:
        plane, = json.load(f)
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    module, = [e for e in lines["XLA Modules"]
               if e[0].startswith("jit_predict_raw_effective")]
    ops = [e for e in lines["XLA Ops"]
           if module[1] <= e[1] and e[1] + e[2] <= module[1] + module[2]]
    shifted = [{"name": name, "events": [[n, s + k * STEP, d]
                                         for k in range(3) for n, s, d in ev]}
               for name, ev in (("XLA Modules", [module]), ("XLA Ops", ops))]
    trace = tracefile.from_planes([{"name": plane["name"],
                                    "lines": shifted}])
    assert module[2] < STEP
    return trace, [(module[1] + k * STEP, module[1] + module[2] + k * STEP)
                   for k in range(3)]


def call(execs, first_id=1, latency=LATENCY, d=D):
    """One root `ddt:predict` of three chunks around `execs`, on the host's
    clock; returns (spans, its wall in ns)."""
    ids = iter(range(first_id, first_id + 100))
    root_id = next(ids)
    t0 = execs[0][0] + d - 900_000          # the call starts
    spans = []

    def span(name, start, end, **counts):
        spans.append({"name": "ddt:" + name, "id": next(ids),
                      "cause": root_id, "root": root_id, "start": start,
                      "end": end, "counts": counts})

    span("predict:token", t0 + 1_000, t0 + 60_000)
    span("predict:upload", t0 + 100_000, t0 + 110_000, bytes=573_440)
    for k in range(3):
        span("predict:dispatch", t0 + 120_000 + 10_000 * k,
             t0 + 128_000 + 10_000 * k, chunk=k)
    at = t0 + 150_000
    for k in range(3):
        end = execs[k][1] + d + latency[k]
        span("predict:fetch", at, end, chunk=k, bytes=27_307)
        at = end + 1_000
    span("predict:concat", at, at + 40_000, bytes=81_920)
    t1 = at + 45_000
    spans.append({"name": "ddt:predict", "id": root_id, "cause": None,
                  "root": root_id, "start": t0, "end": t1,
                  "counts": {"rows": 20480, "chunks": 3, "branch": "chunks",
                             "jit_trace_seconds": 0.0}})
    return spans, t1 - t0


def context(trace, spans, wall_ns, edge_ns=10_000):
    wall = (wall_ns + edge_ns) / 1e9        # the harness sees a little more
    return {"trace": trace, "walls": [wall], "span": wall, "jobs": 1,
            "program_spans": spans}


def segment(ctx, name):
    return call_anatomy.read(ctx, {"segment": name})


def test_the_five_segments_sum_to_span_minus_busy(device):
    trace, execs = device
    spans, wall = call(execs)
    ctx = context(trace, spans, wall)
    assert segment(ctx, "prologue") == pytest.approx(0.1)
    found = ctx["_call_anatomy"]
    assert set(found) == set(call_anatomy.SEGMENTS)
    assert found["outside"] == pytest.approx(0.010)
    assert found["interior_idle"] > 0
    assert sum(found.values()) == pytest.approx(
        (ctx["span"] - trace.busy_s) * 1e3, abs=1e-9)


def test_a_clock_a_second_ahead_is_recovered_by_the_causal_anchor(device):
    trace, execs = device
    spans, wall = call(execs)
    ctx = context(trace, spans, wall)
    # true values on the host's clock; the anchor is the tightest fetch,
    # so it is high by that chunk's latency (20 us) and by no more
    first_op = min(o.start for o in trace.ops[0]) + D
    last_op = max(o.start + o.dur for o in trace.ops[0]) + D
    root = spans[-1]
    upload = next(s for s in spans if s["name"] == "ddt:predict:upload")
    want_up = (first_op - upload["start"]) / 1e6
    want_tail = (root["end"] - last_op) / 1e6
    assert segment(ctx, "upload_exposed") == pytest.approx(want_up + 0.020)
    assert segment(ctx, "fetch_tail") == pytest.approx(want_tail - 0.020)
    # the same spans on a clock another second ahead read the same
    later = [dict(s, start=s["start"] + D, end=s["end"] + D) for s in spans]
    ctx2 = context(trace, later, wall)
    assert segment(ctx2, "upload_exposed") == pytest.approx(want_up + 0.020)


def test_a_fetch_that_ends_before_its_device_work_aligns_nothing(device,
                                                                capsys):
    trace, execs = device
    # chunk 1's fetch "ends" 2 ms before its execution does: under the
    # offset that allows, chunk 0's device work would start before its
    # dispatch
    spans, wall = call(execs, latency=(30_000, -2_000_000, 50_000))
    ctx = context(trace, spans, wall)
    assert segment(ctx, "upload_exposed") is None
    assert segment(ctx, "fetch_tail") is None
    assert segment(ctx, "prologue") == pytest.approx(0.1)   # host clock only
    said = capsys.readouterr().out
    assert "NOT aligned" in said and "chunk 1" in said and "chunk 0" in said


def test_roots_that_are_not_the_harness_jobs_read_nothing(device):
    trace, execs = device
    spans, wall = call(execs)
    slow = context(trace, spans, wall, edge_ns=int(0.02 * wall))
    assert segment(slow, "prologue") is None      # 2% longer than the root
    two = context(trace, spans, wall)
    two.update(jobs=2, walls=two["walls"] * 2)
    assert segment(two, "prologue") is None       # one root for two jobs
    odd = context(trace, [s for s in spans
                          if s["counts"].get("chunk") != 2], wall)
    assert segment(odd, "upload_exposed") is None  # 2 chunks, 3 executions


def test_a_program_without_spans_reads_nothing(device):
    """The parent of PR 25 records none: every new metric is left out."""
    trace, _ = device
    ctx = {"trace": trace, "walls": [0.001], "span": 0.001, "jobs": 1,
           "program_spans": []}
    assert segment(ctx, "prologue") is None
    assert first_call_extra.read(ctx, {}) is None


def test_first_call_extra_is_the_first_root_minus_the_window_median(device):
    trace, execs = device

    def later(ms):
        return [(a + ms * 10**6, b + ms * 10**6) for a, b in execs]

    warm_up, _ = call(execs, first_id=1)
    warm_up[-1]["end"] += 3_000_000             # the first call: 3 ms more
    second, _ = call(later(10), first_id=101)
    second[-1]["end"] += 1_000_000
    steady, wall = call(later(20), first_id=201)
    ctx = context(trace, warm_up + second + steady, wall)
    assert first_call_extra.read(ctx, {}) == pytest.approx(3.0)
    # the ring has dropped the first call: its oldest root is the second,
    # which must not be read as the first
    ctx = context(trace, second + steady, wall)
    assert first_call_extra.read(ctx, {}) is None
    # a window that IS the first call has no warm-up to compare with
    ctx = context(trace, warm_up, wall + 3_000_000)
    assert first_call_extra.read(ctx, {}) is None


def test_the_readers_find_the_programs_own_ring():
    """Without `program_spans` the spans come from ddt_tpu.telemetry."""
    from ddt_tpu.telemetry import annotations

    with annotations.phase_span("t:reader"):
        pass
    mine = call_anatomy.program_spans({})
    assert mine[-1]["name"] == "ddt:t:reader"
    assert set(mine[-1]) == {"name", "id", "cause", "root", "start", "end",
                             "counts"}
