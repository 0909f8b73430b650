"""The routed job kind (`jobs/score_routed.py`): `correct` has to come out
FALSE for each control of the configuration (every learned direction flipped,
category nodes compared ordinally, bfloat16 leaves, the one-hot path forced by
`--set`), for a sample in which a route is dead, and for broken scores; TRUE
when sound. And `opcount_routed.py` against hand numbers.

The whole-run cases drive run.py but for the look for a chip (`--rehearse`:
CPU, the configuration's "rehearse" sizes, kernels interpreted) and read the
verdict it prints; the control cases put the control's answer in the
program's place and ask the job's own `check` which line fails. The controls'
readings at the cell's own size are in the configuration's file and in
PERF.md.
"""

import json
import os

import numpy as np
import pytest

import opcount_routed
import reference_mc
import reference_routed
import run
from test_correct import break_score, cell_of, verdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GAP, ROUTE = "vs the float64 reference", "least share of the sample"


def test_correct_separates_sound_from_broken(capsys, monkeypatch):
    assert verdict(capsys, "score_routed") is True
    # the XLA one-hot path scores right and is not what the cell measures
    assert verdict(capsys, "score_routed", "--set",
                   'predict_impl="onehot"') is False
    break_score(monkeypatch)
    assert verdict(capsys, "score_routed") is False


@pytest.fixture(scope="module")
def job():
    """The cell's job at its rehearsal size, set up once, with the sound
    answer of one call."""
    import jax

    from jobs import score_routed

    jax.config.update("jax_platforms", "cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = run.resolve_cell(manifest, cell_of("score_routed"))
    j = score_routed.Job(cell, seed=4000000007, rehearse=True, control={})
    j.setup()
    j.sound = j.one_job()
    return j


def failed(checks: list) -> list:
    return [what for what, _, _, ok in checks if not ok]


def reference_with(job, tables=None, cat_features=None):
    """The reference's answer over the whole batch, with other node tables
    or other categorical columns where given, as the program's float32
    [rows]."""
    s, m = job.shapes, job.cell["config"]["model"]
    return reference_routed.raw_scores(
        job.tables if tables is None else tables, s["max_depth"],
        m["learning_rate"], m["base_score"], job.Xb, job.missing_bin,
        job.cat if cat_features is None else cat_features).astype(np.float32)


def test_sound_answer_passes_every_line(job):
    assert failed(job.check([job.sound], job.sound)) == []
    # and the reference itself, in float32, is inside the score limit
    assert failed(job.check([reference_with(job)] * 2,
                            reference_with(job))) == []


@pytest.mark.parametrize("control", [
    "default_left_flipped", "category_nodes_ordinal", "bfloat16_leaves"])
def test_control_fails_the_score_limit_alone(job, control):
    tables = job.tables
    answer = {
        "default_left_flipped": lambda: reference_with(job, tables=dict(
            tables, default_left=~tables["default_left"])),
        "category_nodes_ordinal": lambda: reference_with(
            job, cat_features=()),
        "bfloat16_leaves": lambda: reference_with(job, tables=dict(
            tables, leaf_value=reference_mc.bfloat16(tables["leaf_value"]))),
    }[control]()
    lines = failed(job.check([answer], answer))
    assert len(lines) == 1 and GAP in lines[0]
    gap = np.abs(answer.astype(np.float64) - job.sound).max()
    assert gap > 10 * job.limits["score_atol"]


def test_a_dead_route_cannot_pass(job, monkeypatch):
    """Rows with no NaN bin: the scores agree with the reference and the
    sample is refused, because two of the four routes decide nothing."""
    Xb = job.Xb.copy()
    Xb[Xb == job.missing_bin] = 0
    monkeypatch.setattr(job, "Xb", Xb)
    answer = reference_with(job)
    lines = failed(job.check([answer], answer))
    assert len(lines) == 1 and ROUTE in lines[0] and "NaN bin" in lines[0]


def test_another_form_of_the_kernel_is_refused(job, monkeypatch):
    """The same model without its categorical table is served by the
    kernel too, with one routing table: not this cell's form."""
    monkeypatch.setattr(job.ens, "cat_features", None)
    lines = failed(job._what_ran())
    assert len(lines) == 1 and "routed traversal kernel" in lines[0]
    assert "'routing_tables': 1" in str(job._what_ran()[-1][1])


def config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(ROOT, files["criteo-ctr-100t-d6"])) as f:
        return json.load(f)


def test_configuration_keeps_the_sources_widths():
    """docs/CONFIGS.md section 3: 100 trees, depth 6, 13 numeric and 26
    categorical columns, 255 bins; `rows` is the one key that differs."""
    cfg = config()
    s = cfg["shapes"]
    assert (s["n_trees"], s["max_depth"], s["features"], s["n_bins"]) == (
        100, 6, 39, 255)
    assert s["cat_features"] == list(range(13, 39))
    assert s["numeric_features"] == 13
    assert list(cfg["reduced"]) == ["rows"]
    assert s["rows"] % 2_000_000 == 0        # whole PREDICT_ROW_CHUNK chunks
    assert 0 < cfg["check"]["route_share_min"] <= 0.01


def test_traverse_call_routed_criteo_100m_rows():
    shapes = dict(config()["shapes"], rows=10 ** 8)
    ops, nbytes = opcount_routed.traverse_call_routed(shapes)
    # 2 x 1e8 x 39 x 100 x 63 = 4.914e13: 0.249 s of matmul at 197 TFLOP/s
    assert ops == 2.0 * 1e8 * 39 * 100 * 63
    assert ops == pytest.approx(4.914e13, rel=1e-4)
    assert ops / 197e12 == pytest.approx(0.2494, rel=1e-3)
    # 39 B a row in, 4 B a row out, 100 x 127 nodes x 15 B
    assert nbytes == 10 ** 8 * 43 + 100 * 127 * 15
    # bound by compute: 4.3 GB at 819 GB/s is 5.25 ms
    assert nbytes / 819e9 < 0.03 * ops / 197e12
    # this formulation's ceiling: K = 39 of the MXU tile's 128 rows, 100 of
    # the 128 trees of the one group
    assert 39 / 128 * 100 / 128 == pytest.approx(0.238, rel=2e-3)


def test_traverse_call_routed_scales_and_ignores_the_layout():
    shapes = config()["shapes"]
    one = opcount_routed.traverse_call_routed(shapes)
    assert opcount_routed.traverse_call_routed(
        dict(shapes, rows=2 * shapes["rows"]))[0] == 2 * one[0]
    assert opcount_routed.traverse_call_routed(
        dict(shapes, n_trees=50))[0] == one[0] / 2
    # nothing of the kernel's grouping, padding or routing is a term
    assert opcount_routed.traverse_call_routed(
        dict(shapes, tree_group=64, routing_tables=0)) == one


def test_click_log_bins_are_the_stated_distributions():
    import datagen_routed

    s = config()["shapes"]
    Xb = datagen_routed.click_log_bins(300_000, s["numeric_features"],
                                       s["features"], s["n_bins"], 5)
    again = datagen_routed.click_log_bins(300_000, s["numeric_features"],
                                          s["features"], s["n_bins"], 5)
    np.testing.assert_array_equal(Xb, again)         # the seed is the data
    nan = (Xb == s["n_bins"] - 1).mean(axis=0)
    np.testing.assert_allclose(nan[:13], datagen_routed.MISSING_SHARE,
                               atol=0.004)
    assert (nan[13:] == 0).all()                     # no NaN category
    # numeric values that are there: uniform over bins 0..253
    present = Xb[:, 1]
    assert present.max() == 253 and abs(present.mean() - 126.5) < 0.5
    # categories: the power law floor(254 u^3), rank 0 at (1/254)^(1/3)
    assert abs((Xb[:, 20] == 0).mean() - 254 ** (-1 / 3)) < 0.004
    assert Xb[:, 13:].max() == 253


# ---- PR 46: the cell's rate is a metric of its own ----------------------- #

RATE, ROUTED_RATE = "score_mrows_per_s", "score_routed_mrows_per_s"
# What each cell read at PR 42, the parent of PR 46 (a cell may read more).
SHARED = ["traverse_kernel_ms_per_call", "score_prologue_ms",
          "score_upload_exposed_ms", "score_fetch_tail_ms",
          "score_first_call_extra_ms", "score_tables_streamed_mb",
          "score_widen_ms", "score_accumulate_ms", "score_other_device_ms",
          "score_unscoped_device_ms"]
PARENT_PER_LAYER = {
    "score1000t-100m-1chip": SHARED + ["traverse_roofline",
                                       "score_offdevice_ms"],
    "covtype3500t-d8-score-1chip": SHARED + ["traverse_mc_roofline"],
    "criteo100t-d6-score-1chip": SHARED + ["traverse_routed_roofline",
                                           "score_routing_tables"],
    "higgs-lgbm500t-255l-score-1chip": SHARED + [
        "traverse_paths_roofline", "score_path_mxu_tiles_per_tree",
        "score_select_k_blocks"],
    "bosch-lgbm500t-255l-score-1chip": SHARED + [
        "score_routing_tables", "traverse_paths_roofline",
        "score_path_mxu_tiles_per_tree", "score_select_k_blocks"],
    "epsilon-catboost8000t-d6-score-1chip": SHARED + [
        "score_select_k_blocks", "traverse_oblivious_roofline",
        "score_select_columns_per_tree"],
}


def _manifest() -> dict:
    return run.load_json(ROOT, "BENCHMARK.json")


def _names(manifest, group, cell):
    return [m["name"] for m in run.metrics_of(manifest, group, cell)]


def test_the_cell_reports_one_rate_and_setup(job):
    cell, manifest = cell_of("score_routed"), _manifest()
    assert _names(manifest, "end_to_end", cell) == [ROUTED_RATE, "setup_s"]
    # the job answers with that one name, and the number is `score.Job`'s:
    # all the rows of the calls that finished over the whole span
    win = {"walls": [1.25, 1.5, 1.25], "span": 4.125}
    assert job.end_to_end(win) == {
        ROUTED_RATE: 3 * job.shapes["rows"] / 4.125 / 1e6}
    (rate,), (routed,) = ([m for m in manifest["end_to_end"]
                           if m["name"] == name]
                          for name in (RATE, ROUTED_RATE))
    assert routed["workloads"] == [cell] and cell not in rate["workloads"]
    assert [routed[k] for k in ("unit", "better", "source")] == [
        rate[k] for k in ("unit", "better", "source")]
    # the other cells' bound is not this cell's to loosen; its own is a
    # multiple of 0.005 under the contract's 0.1
    assert rate["bound"] == 0.01
    assert rate["bound"] < routed["bound"] <= 0.1
    assert round(routed["bound"] / 0.005, 9).is_integer()


def test_a_whole_run_prints_that_rate_and_no_other(capsys):
    assert run.main(["--workload", cell_of("score_routed"),
                     "--seed", "4600000007", "--seconds", "0.1",
                     "--trace", "0", "--rehearse"]) == 0
    out = capsys.readouterr().out
    (line,) = [ln for ln in out.splitlines()
               if ln.startswith("rehearsal line: ")]
    line = json.loads(line.partition(": ")[2])
    assert line["correct"] is True
    assert sorted(line["metrics"]) == [ROUTED_RATE, "setup_s"]
    assert line["metrics"][ROUTED_RATE]["unit"] == "Mrows/s"
    assert line["metrics"][ROUTED_RATE]["value"] > 0


@pytest.mark.parametrize("cell", sorted(
    set(PARENT_PER_LAYER) - {"criteo100t-d6-score-1chip"}))
def test_the_other_cells_metrics_are_the_parents(cell):
    manifest = _manifest()
    assert _names(manifest, "end_to_end", cell) == [RATE, "setup_s"]
    listed = run.metrics_of(manifest, "per_layer", cell)
    assert set(PARENT_PER_LAYER[cell]) <= {m["name"] for m in listed}
    assert not [m["name"] for m in listed if m["name"].endswith(".routed")]
    assert {m["moves"] for m in listed} == {RATE, "setup_s"}


def test_the_cell_reads_every_per_layer_metric_the_parent_read():
    """Under its own name where the cell alone reads it, and as
    `<name>.routed`, the same reader with the same arguments, where other
    cells read it too: a metric moves ONE end-to-end metric, and its cells
    all report that one."""
    cell, manifest = cell_of("score_routed"), _manifest()
    listed = run.metrics_of(manifest, "per_layer", cell)
    assert set(PARENT_PER_LAYER[cell]) <= {
        m["name"].removesuffix(".routed") for m in listed}
    assert {m["moves"] for m in listed} == {ROUTED_RATE, "setup_s"}
    same = ("unit", "better", "source", "layer")
    for m in listed:
        if m["name"].endswith(".routed"):
            assert m["workloads"] == [cell]
            (twin,) = [t for t in manifest["per_layer"]
                       if t["name"] == m["name"].removesuffix(".routed")]
            assert cell not in twin["workloads"] and twin["moves"] == RATE
            assert [m[k] for k in same] == [twin[k] for k in same]
            assert run.load_json(run.HERE, "layer_metrics",
                                 m["name"] + ".json") \
                == run.load_json(run.HERE, "layer_metrics",
                                 twin["name"] + ".json")


def test_no_chip_no_result_line(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", cell_of("score_routed"), "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc == 1
    assert not any(line.startswith("{") for line in out.splitlines())
