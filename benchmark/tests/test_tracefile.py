"""tracefile.py and the three readers, on synthetic events and on a trace
recorded on the chip (TPU v5 lite, PR 24, `recorded_trace.json.gz`: two
2-tree builds of 20,480 x 28 rows, then one 64-tree scoring call of the same
rows, device planes only)."""

import os

import pytest

import tracefile
from readers import roofline_share, trace_scope_ms, wall_minus_busy

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_trace.json.gz")
SCORE_SHAPES = dict(rows=20480, features=28, n_bins=255, max_depth=6,
                    n_trees=64)
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def toy():
    return tracefile.from_planes([{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules",
         "events": [["jit_a(1)", 0, 100], ["jit_b(2)", 200, 100]]},
        {"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while(...)", 0, 100],
            ["%k.1 = f32[8] custom-call(...)", 10, 30],
            ["%f.2 = f32[8] fusion(...)", 50, 40],
            ["%g.3 = f32[8] fusion(...)", 200, 100]]}]}])


def test_leaves_drop_the_enclosing_while_and_keep_programs():
    tr = toy()
    assert [(o.name, o.module) for o in tr.ops[0]] == [
        ("%k.1", "jit_a(1)"), ("%f.2", "jit_a(1)"), ("%g.3", "jit_b(2)")]


def test_busy_is_the_union_of_leaf_intervals():
    assert toy().busy_s == pytest.approx(170e-9)
    assert tracefile.union_ns([(0, 10), (5, 20), (30, 40)]) == 30


def test_scope_sum_minus_and_program_filter():
    tr = toy()
    assert tr.matched_s("^%k") == (pytest.approx(30e-9), 1)
    assert tr.matched_s(".", minus="^%k") == (pytest.approx(140e-9), 2)
    assert tr.matched_s(".", module="^jit_b") == (pytest.approx(100e-9), 1)
    assert tr.matched_s("^%nothing") == (0.0, 0)


def test_idle_gaps_are_named_by_their_two_sides():
    gaps = toy().idle_gaps()
    assert gaps[0] == ["after jit_a before jit_b", pytest.approx(110e-9)]
    assert gaps[1][0] == "in jit_a: after %k.1 before %f.2"


def test_readers_on_toy_events():
    ctx = {"trace": toy(), "walls": [1.0, 1.0], "span": 2.5, "jobs": 2,
           "divisors": {"jobs": 2, "trees": 4}, "shapes": {}, "peaks": PEAKS}
    assert trace_scope_ms.read(ctx, {"match": "^%k", "per": "trees"}) \
        == pytest.approx(30e-6 / 4)
    assert trace_scope_ms.read(ctx, {"match": "^%nothing"}) is None
    assert wall_minus_busy.read(ctx, {}) == pytest.approx(
        (2.5 - 170e-9) * 1e3 / 2)


def test_a_share_over_100_percent_fails_the_run():
    ctx = {"trace": toy(), "walls": [1.0], "jobs": 1, "peaks": PEAKS,
           "divisors": {"jobs": 1}, "shapes": SCORE_SHAPES}
    with pytest.raises(RuntimeError, match="> 100 %"):
        roofline_share.read(ctx, {"match": "^%k", "opcount": "traverse_call"})


@pytest.fixture(scope="module")
def recorded():
    return tracefile.load_recorded(RECORDED)


def test_recorded_trace_kernels_by_name(recorded):
    # 2 builds x 2 trees x 6 levels of the histogram kernel, one traversal
    hist_s, n_hist = recorded.matched_s("^%ddt_hist_", module="^jit_rounds")
    assert n_hist == 24 and hist_s > 0
    other_s, n_other = recorded.matched_s(".", "^%ddt_hist_", "^jit_rounds")
    all_s, n_all = recorded.matched_s(".", module="^jit_rounds")
    assert n_other + n_hist == n_all
    assert hist_s + other_s == pytest.approx(all_s)
    assert recorded.matched_s("^%ddt_predict_traverse")[1] == 1
    assert not any(o.name.startswith("%while") for o in recorded.ops[0])


def test_recorded_trace_busy_and_breakdown(recorded):
    programs = sum(d for _, _, d in recorded.modules[0]) / 1e9
    assert 0 < recorded.busy_s <= programs * (1 + 1e-9)
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1] > 0
    assert any(name.startswith("after jit_rounds before")
               for name, _ in b["idle_gaps"])


def test_recorded_trace_roofline_is_a_share(recorded):
    ctx = {"trace": recorded, "walls": [0.01], "jobs": 1,
           "divisors": {"jobs": 1, "calls": 1}, "shapes": SCORE_SHAPES,
           "peaks": PEAKS}
    share = roofline_share.read(
        ctx, {"match": "^%ddt_predict_traverse", "opcount": "traverse_call"})
    assert 0 < share < 100
