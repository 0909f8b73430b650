"""readers/device_stage_ms.py on hand-written device events and a hand-written
stage map: two calls of two chunks, each chunk a slice program and a scoring
program of four operations, the first chunk of a call after a reshape
program. Durations are whole microseconds, so every expected value is exact.
"""

import sys
import types

import pytest

import tracefile
from readers import device_stage_ms

SCORING = "jit_predict_raw_effective(123)"
# (instruction, its HLO text after the name, ns)
CHUNK = [("%copy.5", "u8[8,4]{1,0} copy(%Xc.1)", 30_000),
         ("%pad_convert_fusion", "s32[8,4]{1,0} fusion(%copy.5)", 100_000),
         ("%ddt_predict_traverse.1", "f32[8,1]{1,0} custom-call(...)",
          4_000_000),
         ("%fusion.1", "f32[8]{0} fusion(%ddt_predict_traverse.1)", 70_000)]
SLICE = ("%constant_dynamic-slice_fusion", "u8[8,4]{1,0} fusion(%p)", 10_000)
RESHAPE = ("%reshape.1", "u8[16,4]{1,0} reshape(%p)", 4_000)
GAP = 5_000                 # ns between two programs: idle, in no stage

STAGES = {
    "jit_predict_raw_effective": {
        "%copy.5": {"stage": "unscoped", "source": "", "op": "copy(%Xc.1)"},
        "%pad_convert_fusion": {
            "stage": "predict:widen", "op": "fusion(%copy.5)",
            "source": "ddt_tpu/ops/predict_pallas.py:872"},
        "%ddt_predict_traverse.1": {
            "stage": "predict:traverse", "op": "custom-call(...)",
            "source": "ddt_tpu/ops/predict_pallas.py:913"},
        "%fusion.1": {
            "stage": "predict:accumulate", "op": "fusion(...)",
            "source": "ddt_tpu/ops/predict_pallas.py:924"},
        # the scoring program's own %reshape.1 is another instruction than
        # the reshape program's: the lookup is by (program, instruction)
        "%reshape.1": {"stage": "predict:tables", "op": "reshape(...)",
                       "source": "ddt_tpu/ops/predict_pallas.py:822"}},
    "jit_dynamic_slice": {"*": {"stage": "predict:slice", "op": "program",
                                "source": "ddt_tpu/backends/tpu.py:1588"}},
    "jit_reshape": {"*": {"stage": "predict:unflatten", "op": "program",
                          "source": "ddt_tpu/backends/tpu.py:1588"}},
}
CALLS, CHUNKS = 2, 2
# ms a call
WIDEN, ACCUMULATE, KERNEL, COPY = 0.2, 0.14, 8.0, 0.06
SLICES, UNFLATTEN = 0.02, 0.004


def planes(scoring=SCORING, extra=()):
    """One device plane: the calls' programs back to back, GAP apart; with
    `extra` operations (name, text, ns) appended to every scoring
    execution."""
    modules, ops = [], []
    at = 1_000.0

    def run(program, instructions):
        nonlocal at
        start = at
        for name, text, ns in instructions:
            ops.append([f"{name} = {text}", at, float(ns)])
            at += ns
        modules.append([program, start, at - start])
        at += GAP

    for _ in range(CALLS):
        run("jit_reshape(7)", [RESHAPE])
        for _ in range(CHUNKS):
            run("jit_dynamic_slice(9)", [SLICE])
            run(scoring, CHUNK + list(extra))
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Modules", "events": modules},
                       {"name": "XLA Ops", "events": ops}]}]


def stage_context(trace_planes, **more):
    trace = tracefile.from_planes(trace_planes)
    return dict({"trace": trace, "jobs": CALLS, "walls": [1.0] * CALLS,
                 "span": 2.0, "divisors": {"jobs": CALLS, "calls": CALLS},
                 "device_stages": STAGES}, **more)


METRICS = [
    ("widen", {"stage": "^predict:widen$"}, WIDEN),
    ("accumulate", {"stage": "^predict:accumulate$"}, ACCUMULATE),
    ("traverse", {"stage": "^predict:traverse"}, KERNEL),
    ("unscoped", {"stage": "^unscoped$"}, COPY),
    ("slice", {"stage": "^predict:slice$"}, SLICES),
    ("unflatten", {"stage": "^predict:unflatten$"}, UNFLATTEN),
    ("other", {"not": "^predict:traverse"},
     WIDEN + ACCUMULATE + COPY + SLICES + UNFLATTEN),
    ("tables-none", {"stage": "^predict:tables$"}, 0.0),
]


@pytest.mark.parametrize("name,args,want", METRICS,
                         ids=[m[0] for m in METRICS])
def test_a_stage_reads_its_operations_a_call(name, args, want):
    ctx = stage_context(planes())
    got = device_stage_ms.read(ctx, dict(args, per="calls"))
    assert got == pytest.approx(want, abs=1e-9)


def test_the_stages_sum_to_the_busy_time_and_one_table_is_printed(capsys):
    ctx = stage_context(planes())
    other = device_stage_ms.read(ctx, {"not": "^predict:traverse"})
    kernel = device_stage_ms.read(ctx, {"stage": "^predict:traverse"})
    busy = ctx["trace"].busy_s * 1e3 / CALLS
    assert other + kernel == pytest.approx(busy, abs=1e-9)
    said = capsys.readouterr().out
    # four metrics, one table: the second read printed nothing
    assert said.count("the stages sum to") == 1
    assert f"sum to {busy:.3f} ms a call" in said
    assert "apart by +0.000" in said
    assert "NOT in the map" not in said
    # a stage's line: ms a call, events, the largest instruction and where
    widen, = [ln for ln in said.splitlines() if "predict:widen" in ln]
    assert f"{WIDEN:12.3f} {CALLS * CHUNKS:7d}" in widen
    assert "%pad_convert_fusion 0.200 fusion(%copy.5) " \
        "ddt_tpu/ops/predict_pallas.py:872" in widen
    unscoped, = [ln for ln in said.splitlines()
                 if ln.startswith("device_stage_ms: unscoped")]
    assert "%copy.5 0.060 copy(%Xc.1) no source line" in unscoped
    whole, = [ln for ln in said.splitlines() if "predict:unflatten" in ln]
    assert "%reshape.1 0.004 in jit_reshape " \
        "ddt_tpu/backends/tpu.py:1588" in whole


def test_the_divisor_is_the_jobs(capsys):
    ctx = stage_context(planes())
    ctx["divisors"] = {"jobs": CALLS, "calls": CALLS, "chunks": CALLS * 2}
    assert device_stage_ms.read(
        ctx, {"stage": "^predict:widen$", "per": "chunks"}) \
        == pytest.approx(WIDEN / 2, abs=1e-9)


FITS = [
    # an instruction the scoring program's map lacks: a new operation
    # traced under no stage, or another executable of the same name
    ("instruction", SCORING, [("%copy.77", "f32[8]{0} copy(%x)", 6_000)],
     "0.012 ms a call of 1 instructions in jit_predict_raw_effective (the "
     "map of this name was made from another executable): %copy.77", 0.012),
    # a program the map does not name at all
    ("program", "jit_other(5)", [],
     "of 4 instructions in jit_other (a program the map does not name)",
     WIDEN + ACCUMULATE + KERNEL),
]


@pytest.mark.parametrize("what,scoring,extra,says,more", FITS,
                         ids=[f[0] for f in FITS])
def test_a_map_that_does_not_fit_reads_unscoped_and_says_so(
        what, scoring, extra, says, more, capsys):
    ctx = stage_context(planes(scoring, extra))
    got = device_stage_ms.read(ctx, {"stage": "^unscoped$"})
    assert got == pytest.approx(COPY + more, abs=1e-9)
    said = capsys.readouterr().out
    assert "NOT in the map, read as unscoped" in said
    assert says in said
    # nothing is lost: the stages still sum to the busy time
    assert "apart by +0.000" in said


def test_a_program_without_the_function_reads_nothing(monkeypatch, capsys):
    """The parent of PR 35: `annotations` has no `device_stages`."""
    ctx = stage_context(planes())
    del ctx["device_stages"]
    monkeypatch.setitem(sys.modules, "ddt_tpu.telemetry.annotations",
                        types.ModuleType("ddt_tpu.telemetry.annotations"))
    for args in ({"stage": "^predict:widen$"}, {"not": "^predict:traverse"}):
        assert device_stage_ms.read(ctx, args) is None
    said = capsys.readouterr().out
    assert said.count("the program names no device stages") == 1


def test_a_map_without_the_scoring_program_reads_nothing(capsys):
    """A model served by a program the backend does not map (a LUT tier):
    the two small programs alone are no map of the call."""
    ctx = stage_context(planes(), device_stages={
        k: v for k, v in STAGES.items() if k != "jit_predict_raw_effective"})
    assert device_stage_ms.read(ctx, {"stage": "^unscoped$"}) is None
    assert "names no device stages for a program matching " \
        "'^jit_predict_raw_effective'" in capsys.readouterr().out


def test_the_map_comes_from_the_program_when_no_test_gives_it(monkeypatch):
    from ddt_tpu.telemetry import annotations

    asked = []
    monkeypatch.setattr(annotations, "device_stages",
                        lambda: asked.append(1) or STAGES, raising=False)
    ctx = stage_context(planes())
    del ctx["device_stages"]
    assert device_stage_ms.read(ctx, {"stage": "^predict:widen$"}) \
        == pytest.approx(WIDEN, abs=1e-9)
    assert device_stage_ms.read(ctx, {"stage": "^unscoped$"}) \
        == pytest.approx(COPY, abs=1e-9)
    assert asked == [1]                 # asked once for the run's metrics


def test_the_metric_files_name_this_reader():
    """The four metrics this reader serves, as BENCHMARK.json lists them:
    each once for the cells that report `score_mrows_per_s` and once, as
    `<name>.routed` with the same reader and arguments, for the routed cell,
    whose rate is a metric of its own (PR 46)."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(os.path.dirname(here), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    rate_of = {cell: m["name"] for m in manifest["end_to_end"]
               if m["name"] != "setup_s" for cell in m["workloads"]}
    assert sorted(rate_of) == sorted(w["name"] for w in manifest["workloads"])
    mine = {}
    for m in manifest["per_layer"]:
        with open(os.path.join(here, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "device_stage_ms":
            mine[m["name"]] = (m, spec["args"])
    want = {"score_widen_ms": WIDEN, "score_accumulate_ms": ACCUMULATE,
            "score_unscoped_device_ms": COPY,
            "score_other_device_ms":
                WIDEN + ACCUMULATE + COPY + SLICES + UNFLATTEN}
    assert sorted(mine) == sorted(
        [*want, *(name + ".routed" for name in want)])
    ctx = stage_context(planes())
    cells = []
    for name, (m, args) in mine.items():
        assert (m["unit"], m["better"], m["source"]) == (
            "ms", "lower", "device_trace")
        assert m["layer"] == "ops/predict.py scoring program around the kernel"
        # a metric lists cells of ONE rate, the one it moves
        assert {rate_of[cell] for cell in m["workloads"]} == {m["moves"]}
        assert args == mine[name.removesuffix(".routed")][1]
        assert device_stage_ms.read(ctx, args) \
            == pytest.approx(want[name.removesuffix(".routed")], abs=1e-9)
        cells += m["workloads"]
    # and every cell reads each of the four, under one of its two names
    assert sorted(cells) == sorted(4 * list(rate_of))
