"""The NaN-routed leaf-wise job kind (`jobs/score_leafwise_nan.py`): `correct`
has to come out FALSE for each control of the configuration (every default
direction flipped, the NaN bin compared as an ordinary bin, `<` for `<=`,
bfloat16 leaf values), whether the control's answer is put in the program's
place or the program is handed the control's tables (`--set patched_table`),
for a sample that misses the leaf coverage, the depth limit or a route, and
for broken scores; TRUE when sound. And `opcount_leafwise.py` at 968 columns
against the hand number, `datagen_bosch.py` against its own contract.

The whole-run cases drive run.py but for the look for a chip (`--rehearse`:
CPU, the configuration's "rehearse" sizes, kernels interpreted) and read the
verdict it prints. The controls' readings at the cell's own size are in the
configuration's file and in PERF.md.
"""

import json
import os

import numpy as np
import pytest

import datagen_bosch
import opcount_leafwise
import reference_leafwise_nan
import run
from test_correct import break_score, cell_of, verdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = "score_leafwise_nan"
GAP, SHARE, DEEP, NAN_ROUTE, ORDINAL, SAID = (
    "vs the float64 reference", "share of the ensemble's",
    "deepest path a sampled row takes", "NaN route decides to one side",
    "an ordinal compare decides", "the program's record says")


def test_correct_separates_sound_from_broken_and_patched(capsys, monkeypatch):
    assert verdict(capsys, TRAFFIC) is True
    # the program handed a control's tables, the answer held to the right
    # ones: a CONTROL run, never a result line
    for control in ("flipped_default_left", "strict_less"):
        assert verdict(capsys, TRAFFIC, "--set",
                       f'patched_table="{control}"') is False
    break_score(monkeypatch)
    assert verdict(capsys, TRAFFIC) is False


@pytest.fixture(scope="module")
def leafwise_nan_job():
    """The cell's job at its rehearsal size, set up once, with the sound
    answer of one call. (A name of its own: tests/test_benchmark_suite.py
    gathers every module's fixtures into one namespace.)"""
    import jax

    from jobs import score_leafwise_nan

    jax.config.update("jax_platforms", "cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = run.resolve_cell(manifest, cell_of(TRAFFIC))
    j = score_leafwise_nan.Job(cell, seed=4000000007, rehearse=True,
                               control={})
    j.setup()
    j.sound = j.one_job()
    return j


def failed(checks: list) -> list:
    return [what for what, _, _, ok in checks if not ok]


def reference_with(job, control=None, Xb=None, tables=None):
    """The reference's answer over the whole batch (with ONE thing changed
    where `control` names it), as the program's float32 [rows]."""
    m = job.cell["config"]["model"]
    return reference_leafwise_nan.raw_scores(
        job.tables if tables is None else tables, m["learning_rate"],
        m["base_score"], job.Xb if Xb is None else Xb, job.nan_bin,
        control=control)[0].astype(np.float32)


def test_sound_answer_passes_every_line(leafwise_nan_job):
    job = leafwise_nan_job
    assert failed(job.check([job.sound], job.sound)) == []
    # and the reference itself, in float32, is inside the score limit
    assert failed(job.check([reference_with(job)] * 2,
                            reference_with(job))) == []


@pytest.mark.parametrize("control", reference_leafwise_nan.CONTROLS)
def test_control_fails_the_score_limit_alone(leafwise_nan_job, control):
    job = leafwise_nan_job
    answer = reference_with(job, control)
    lines = failed(job.check([answer], answer))
    assert len(lines) == 1 and GAP in lines[0]
    gap = np.abs(answer.astype(np.float64) - job.sound).max()
    assert gap > 10 * job.limits["score_atol"]
    # the same control as a patch of the tables is the same wrong model
    patched = reference_leafwise_nan.patched(job.tables, control)
    np.testing.assert_array_equal(
        reference_with(job, tables=patched), answer)


def test_a_dead_subtree_cannot_pass(leafwise_nan_job, monkeypatch):
    """Rows that all sit in one corner of the bin box, nothing missing: the
    scores agree with the reference and the sample is refused, because it
    reaches a few leaves of each tree and the NaN route decides nothing."""
    job = leafwise_nan_job
    Xb = np.zeros_like(job.Xb)
    monkeypatch.setattr(job, "Xb", Xb)
    answer = reference_with(job, Xb=Xb)
    lines = failed(job.check([answer], answer))
    assert any(SHARE in line for line in lines)
    assert any(NAN_ROUTE in line for line in lines)
    assert not any(GAP in line for line in lines)


def test_a_table_with_nothing_present_cannot_pass(leafwise_nan_job,
                                                  monkeypatch):
    """Every cell missing: no ordinal compare is ever made."""
    job = leafwise_nan_job
    Xb = np.full_like(job.Xb, job.nan_bin)
    monkeypatch.setattr(job, "Xb", Xb)
    answer = reference_with(job, Xb=Xb)
    lines = failed(job.check([answer], answer))
    assert any(ORDINAL in line for line in lines)
    assert not any(GAP in line for line in lines)


def test_a_model_a_heap_could_hold_cannot_pass(leafwise_nan_job,
                                               monkeypatch):
    """Shallow trees (the same drawing procedure stopped at 8 leaves): the
    scores agree, but no row goes deeper than 10 nodes."""
    job = leafwise_nan_job
    s = job.shapes
    tables = datagen_bosch.leafwise_nan_trees(
        s["n_trees"], 8, s["features"], s["n_bins"], job.seed, job.missing)
    monkeypatch.setattr(job, "tables", tables)
    answer = reference_with(job)
    lines = failed(job.check([answer], answer))
    assert len(lines) == 1 and DEEP in lines[0]


def test_the_question_is_asked_before_any_row_is_drawn(leafwise_nan_job,
                                                       monkeypatch):
    """A program whose span does not say missing_routes 1, and one whose
    node list takes no directions at all: SystemExit out of `setup`, and
    `sparse_bins` never called."""
    from ddt_tpu.models import tree
    from jobs import score_leafwise_nan

    job = leafwise_nan_job
    monkeypatch.setattr(datagen_bosch, "sparse_bins", lambda *a: pytest.fail(
        "rows drawn before the what-ran question was answered"))
    fresh = score_leafwise_nan.Job(job.cell, seed=5, rehearse=False,
                                   control={})
    monkeypatch.setattr(fresh, "_what_ran", lambda: [
        ("the program's record says a node-list form with the NaN route "
         "serves", {"node_list": 1, "missing_routes": None}, True, False)])
    with pytest.raises(SystemExit, match="no Pallas kernel serves"):
        fresh.setup()

    def old_constructor(**kw):
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'default_left'")

    monkeypatch.setattr(tree, "NodeListEnsemble", old_constructor)
    with pytest.raises(SystemExit, match="takes no learned NaN directions"):
        score_leafwise_nan.Job(job.cell, seed=5, rehearse=False,
                               control={}).setup()
    # and it asks nothing about tiling
    said = str(job._what_ran())
    assert "missing_routes" in said and SAID in said
    for tiling in ("path_mxu_tiles_per_tree", "trees_per_step",
                   "table_blocks", "select_k_blocks"):
        assert tiling not in said.split("ddt:predict:ensemble")[0]


def test_a_control_run_keeps_its_key_out_of_the_programs_config(
        leafwise_nan_job):
    from jobs import score_leafwise_nan

    control = {"patched_table": "nan_as_ordinary_bin"}
    j = score_leafwise_nan.Job(leafwise_nan_job.cell, seed=6, rehearse=True,
                               control=control)
    assert j.patch == "nan_as_ordinary_bin" and control   # run.py's is whole
    assert not hasattr(j.cfg, "patched_table")
    j.setup()       # a patched job is not stopped by the what-ran question
    assert j.ens.default_left is None
    assert [ok for what, *_, ok in j.what_ran if SAID in what] == [False]
    with pytest.raises(ValueError, match="unknown control"):
        reference_leafwise_nan.patched(j.tables, "no_such_control")


def config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(ROOT, files["bosch-lgbm-500t-255l"])) as f:
        return json.load(f)


def test_configuration_keeps_the_sources_widths():
    """LightGBM's GPU comparison, data set Bosch: 500 trees, 255 leaves, 968
    columns, 255 bins, learning rate 0.1; `rows` is the one key that
    differs: the training and the test file together."""
    cfg = config()
    s = cfg["shapes"]
    assert (s["n_trees"], s["n_leaves"], s["features"], s["n_bins"]) == (
        500, 255, 968, 255)
    assert cfg["model"]["learning_rate"] == 0.1
    assert list(cfg["reduced"]) == ["rows"]
    assert s["rows"] == 1_183_747 + 1_183_748
    lim = cfg["check"]
    assert 0.8 <= lim["leaf_share_min"] < 1 and lim["deep_leaf_min"] == 10
    assert 0.25 <= lim["nan_route_share_min"] < 0.4
    assert lim["ordinal_share_min"] == 0.05
    assert "TO BE FILLED" not in lim["readings"]


def test_traverse_call_paths_bosch():
    """The hand number at 968 columns (ISSUE 37): 2 x 2,367,495 x 500 x 254
    x (968 + 255) = 7.354e14 operations, 3.73 s at 197 TFLOP/s; the
    strategy's ceiling on 128-wide MXU tiles, 20 a tree."""
    ops, nbytes = opcount_leafwise.traverse_call_paths(config()["shapes"])
    assert ops == 2.0 * 2_367_495 * 500 * 254 * 1223
    assert ops == pytest.approx(7.354e14, rel=1e-3)
    assert ops / 197e12 == pytest.approx(3.733, rel=1e-3)
    assert nbytes == 2_367_495 * (968 + 4) + 500 * (254 * 16 + 255 * 4)
    assert nbytes / 819e9 < 0.01 * ops / 197e12       # bound by compute
    assert 254 * 1223 / (20 * 128 * 128) == pytest.approx(0.948, rel=1e-3)


def test_bosch_inputs_are_the_seeds_and_every_leaf_is_reachable():
    s = config()["shapes"]
    F, B = s["features"], s["n_bins"]
    q = datagen_bosch.missing_bytes(F, 4000000007)
    np.testing.assert_array_equal(q, datagen_bosch.missing_bytes(
        F, 4000000007))
    assert q.shape == (F,) and q.max() <= 255
    assert abs((q / 256).mean() - datagen_bosch.MISSING_MEAN) < 0.005
    # station-like runs: neighbours far closer than columns at random
    assert np.abs(np.diff(q.astype(int))).mean() < 0.5 * np.abs(
        q.astype(int) - np.random.default_rng(0).permutation(q)).mean()
    Xb = datagen_bosch.sparse_bins(40_000, F, B, 4000000007, q)
    assert Xb.dtype == np.uint8 and Xb.shape == (40_000, F)
    # a longer batch of the same seed starts with the same rows
    np.testing.assert_array_equal(
        Xb[:20_000], datagen_bosch.sparse_bins(20_000, F, B, 4000000007, q))
    assert not np.array_equal(Xb[:100], datagen_bosch.sparse_bins(
        100, F, B, 5, q))
    nan = Xb == B - 1
    assert abs(nan.mean() - (q / 256).mean()) < 0.002
    assert np.abs(nan.mean(axis=0) - q / 256).max() < 0.02
    assert set(np.unique(Xb[~nan])) == set(range(B - 1))   # every value bin

    a = datagen_bosch.leafwise_nan_trees(6, s["n_leaves"], F, B, 11, q)
    b = datagen_bosch.leafwise_nan_trees(6, s["n_leaves"], F, B, 11, q)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])        # the seed is the data
    assert a["default_left"].dtype == bool
    assert 0.35 < a["default_left"].mean() < 0.65
    assert a["threshold_bin"].min() >= 0 and a["threshold_bin"].max() < B - 2
    L = s["n_leaves"]
    for t in range(6):
        refs = np.concatenate([a["left_child"][t], a["right_child"][t]])
        assert sorted(~refs[refs < 0]) == list(range(L))
        assert sorted(refs[refs >= 0]) == list(range(1, L - 1))
        # every leaf keeps a value bin of every feature it was split on:
        # walk down from the root narrowing the value ranges
        stack = [(0, {}, 0)]
        depths = []
        while stack:
            ref, box, d = stack.pop()
            if ref < 0:
                depths.append(d)
                continue
            f, thr = a["feature"][t][ref], a["threshold_bin"][t][ref]
            lo, hi = box.get(f, (0, B - 2))
            assert lo <= thr < hi                 # both sides keep a bin
            stack.append((a["left_child"][t][ref], {**box, f: (lo, thr)},
                          d + 1))
            stack.append((a["right_child"][t][ref],
                          {**box, f: (thr + 1, hi)}, d + 1))
        assert len(depths) == L and max(depths) > 10     # leaf-wise: deep


def test_no_chip_no_result_line(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", cell_of(TRAFFIC), "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc == 1
    assert not any(line.startswith("{") for line in out.splitlines())
