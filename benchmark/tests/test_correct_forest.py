"""The averaged-forest job kind (`jobs/score_forest.py`): `correct` has to
come out FALSE for each control of the configuration (`<` for `<=`, two
class columns exchanged, the sum not divided by the trees, bfloat16 leaf
vectors, the chain's links dropped), whether the control's answer is put in
the program's place or the program is handed the control's tables (`--set
patched_table`), for a sample that misses the leaf coverage, the path
length or the depth limit, and for broken scores; TRUE when sound. And
`opcount_forest.py` against the hand number, `datagen_forest.py` against
its own contract, the configuration's file against its source.

The whole-run cases drive run.py but for the look for a chip (`--rehearse`:
CPU, the configuration's "rehearse" sizes, kernels interpreted) and read the
verdict it prints. The controls' readings at the cell's own size are in the
configuration's file and in PERF.md.
"""

import json
import os

import numpy as np
import pytest

import datagen_forest
import opcount_forest
import reference_forest
import run
from test_correct import break_score, cell_of, verdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = "score_forest"
FOREST_GAP, FOREST_SHARE, FOREST_DEEP, FOREST_PASSED, FOREST_SAID = (
    "vs the float64 reference", "share of the forest's",
    "deepest path a sampled row takes", "passes in a tree, on average",
    "the program's record says")


def test_forest_correct_separates_sound_from_broken_and_patched(
        capsys, monkeypatch):
    assert verdict(capsys, TRAFFIC) is True
    # the program handed a control's tables, the answer held to the right
    # ones: a CONTROL run, never a result line
    for control in ("bfloat16_scores", "dropped_chain"):
        assert verdict(capsys, TRAFFIC, "--set",
                       f'patched_table="{control}"') is False
    break_score(monkeypatch)
    assert verdict(capsys, TRAFFIC) is False


@pytest.fixture(scope="module")
def forest_job():
    """The cell's job at its rehearsal size, set up once, with the sound
    answer of one call. (A name of its own: tests/test_benchmark_suite.py
    gathers every module's fixtures into one namespace.)"""
    import jax

    from jobs import score_forest

    jax.config.update("jax_platforms", "cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = run.resolve_cell(manifest, cell_of(TRAFFIC))
    j = score_forest.Job(cell, seed=4700000007, rehearse=True, control={})
    j.setup()
    j.sound = j.one_job()
    return j


def forest_failed(checks: list) -> list:
    return [what for what, _, _, ok in checks if not ok]


def forest_reference(job, control=None, Xb=None, tables=None):
    """The reference's answer over the whole batch (of the tables with ONE
    thing wrong where `control` names it), as the program's float32
    [rows, classes]."""
    tables = job.tables if tables is None else tables
    return reference_forest.class_scores(
        reference_forest.patched(tables, control),
        job.Xb if Xb is None else Xb)[0].astype(np.float32)


def test_forest_sound_answer_passes_every_line(forest_job):
    job = forest_job
    assert forest_failed(job.check([job.sound], job.sound)) == []
    # and the reference itself, in float32, is inside the score limit
    assert forest_failed(job.check([forest_reference(job)] * 2,
                                   forest_reference(job))) == []
    assert job.shapes["skeleton"] == datagen_forest.skeleton(job.tables)


@pytest.mark.parametrize("control", reference_forest.CONTROLS)
def test_forest_control_fails_the_score_limit_alone(forest_job, control):
    job = forest_job
    answer = forest_reference(job, control)
    lines = forest_failed(job.check([answer], answer))
    assert len(lines) == 1 and FOREST_GAP in lines[0]
    gap = np.abs(answer.astype(np.float64) - job.sound).max()
    assert gap > 10 * job.limits["score_atol"]


def test_forest_a_dead_subtree_cannot_pass(forest_job, monkeypatch):
    """Rows that all sit in one corner of the bin box: the scores agree with
    the reference and the sample is refused, because it reaches one leaf of
    each tree."""
    job = forest_job
    Xb = np.zeros_like(job.Xb)
    monkeypatch.setattr(job, "Xb", Xb)
    answer = forest_reference(job, Xb=Xb)
    lines = forest_failed(job.check([answer], answer))
    assert any(FOREST_SHARE in line for line in lines)
    assert not any(FOREST_GAP in line for line in lines)


def test_forest_a_model_a_heap_could_hold_cannot_pass(forest_job,
                                                      monkeypatch):
    """Shallow trees (the same drawing with leaves that close at a
    thousandth of the root's mass): the scores agree, but no row goes
    deeper than 15 nodes and a row passes fewer than 10 a tree."""
    job = forest_job
    s = job.shapes
    drawing = dict(job.cell["config"]["assumed"]["drawing"],
                   purity_mass=3000.0)
    tables = datagen_forest.grown_forest(
        s["n_trees"], s["features"], s["n_bins"], s["n_classes"],
        s["forest_seed"], **drawing)
    assert tables["n_leaves"].max() < 100
    monkeypatch.setattr(job, "tables", tables)
    answer = forest_reference(job)
    lines = forest_failed(job.check([answer], answer))
    assert len(lines) == 2
    assert any(FOREST_DEEP in line for line in lines)
    assert any(FOREST_PASSED in line for line in lines)


def test_forest_the_question_is_asked_before_any_row_is_drawn(forest_job,
                                                              monkeypatch):
    """A program whose span does not say sub-trees and class vectors, and
    one whose node list holds no vector leaves at all: SystemExit out of
    `setup`, and `uniform_pixels` never called; the older program is turned
    away before the forest is drawn."""
    from ddt_tpu.models import tree
    from jobs import score_forest

    job = forest_job
    monkeypatch.setattr(datagen_forest, "uniform_pixels", lambda *a: (
        pytest.fail("rows drawn before the what-ran question was answered")))
    fresh = score_forest.Job(job.cell, seed=5, rehearse=False, control={})
    monkeypatch.setattr(fresh, "_what_ran", lambda: [
        ("the program's record says a node-list form serves trees cut into "
         "sub-trees", {"node_list": 1, "subtrees_per_tree": None}, True,
         False)])
    with pytest.raises(SystemExit, match="no Pallas kernel serves"):
        fresh.setup()

    monkeypatch.delattr(tree.NodeListEnsemble, "vector_leaves")
    monkeypatch.setattr(datagen_forest, "grown_forest", lambda *a, **k: (
        pytest.fail("the forest drawn for a program that cannot hold it")))
    with pytest.raises(SystemExit, match="holds no vector leaves"):
        score_forest.Job(job.cell, seed=5, rehearse=False,
                         control={}).setup()
    monkeypatch.undo()
    # and it asks nothing about tiling
    said = str(job._what_ran())
    assert "subtrees_per_tree" in said and FOREST_SAID in said
    for tiling in ("path_mxu_tiles_per_tree", "trees_per_step",
                   "table_blocks", "select_k_blocks", "subtree_lanes"):
        assert tiling not in said.split("ddt:predict:ensemble")[0]


def test_forest_a_control_run_keeps_its_key_out_of_the_programs_config(
        forest_job):
    from jobs import score_forest

    control = {"patched_table": "swapped_classes"}
    j = score_forest.Job(forest_job.cell, seed=6, rehearse=True,
                         control=control)
    assert j.patch == "swapped_classes" and control     # run.py's is whole
    assert not hasattr(j.cfg, "patched_table")
    j.setup()
    np.testing.assert_array_equal(j.ens.leaf_value[..., 3],
                                  j.tables["leaf_value"][..., 8])
    answer = j.one_job()
    assert FOREST_GAP in forest_failed(j.check([answer], answer))[0]
    with pytest.raises(ValueError, match="unknown control"):
        reference_forest.patched(j.tables, "no_such_control")


def forest_config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(ROOT, files["mnist-rf-100t-full"])) as f:
        return json.load(f)


def test_forest_configuration_keeps_the_sources_widths():
    """scikit-learn's defaults on MNIST: 100 trees, no depth limit, 784
    columns of 256 values, 10 classes, the mean over the trees; `rows` is
    the one key that differs, and it is raised."""
    cfg = forest_config()
    s = cfg["shapes"]
    assert (s["n_trees"], s["features"], s["n_bins"], s["n_classes"]) == (
        100, 784, 256, 10)
    assert (cfg["model"]["loss"], cfg["model"]["learning_rate"],
            cfg["model"]["base_score"]) == ("mean", 1.0, 0.0)
    assert list(cfg["reduced"]) == ["rows"] and s["rows"] == 3_000_000
    assert cfg["assumed"]["drawing"] == datagen_forest.DEFAULTS
    lim = cfg["check"]
    assert lim["sample_rows"] == 50_000 and 0 < lim["score_atol"] <= 1e-4
    assert (lim["leaf_share_min"], lim["path_nodes_min"],
            lim["deep_leaf_min"]) == (0.5, 10, 15)
    for text in (cfg["reduced"]["rows"], cfg["assumed"]["ensemble"],
                 lim["readings"], cfg["deployment"]):
        assert "TO BE FILLED" not in text


def test_traverse_call_forest_by_hand():
    """A forest of two trees by hand: 5 and 2 internal nodes, 6 and 3
    leaves, path entries 3+3+2+2+3+3 and 1+2+2, C = 10, F = 784, 1,000
    rows: 2 x 1000 x (784 x 7 + 21 + 9 x 10); and the cell's own forest."""
    sk = {"nodes": 7, "leaves": 9, "path_entries": 21}
    ops, nbytes = opcount_forest.traverse_call_forest(
        {"rows": 1000, "features": 784, "n_classes": 10, "skeleton": sk})
    assert ops == 2.0 * 1000 * (784 * 7 + 21 + 90)
    assert nbytes == 1000 * 784 + 4 * 1000 * 10 + 7 * 16 + 9 * 10 * 4
    s = dict(forest_config()["shapes"])
    tables = datagen_forest.grown_forest(
        s["n_trees"], s["features"], s["n_bins"], s["n_classes"],
        s["forest_seed"], **forest_config()["assumed"]["drawing"])
    s["skeleton"] = datagen_forest.skeleton(tables)
    assert s["skeleton"] == {"nodes": 396889, "leaves": 396989,
                             "path_entries": 5482575, "deepest_leaf": 26}
    ops, nbytes = opcount_forest.traverse_call_forest(s)
    assert ops == pytest.approx(1.9237e15, rel=1e-4)
    assert ops / 197e12 == pytest.approx(9.765, rel=1e-3)
    assert nbytes / 819e9 < 0.01 * ops / 197e12       # bound by compute
    # the select is 97% of it: what the padding of K rows and lanes costs
    assert 2.0 * s["rows"] * 784 * 396889 / ops == pytest.approx(0.9705,
                                                                 abs=1e-3)


def test_forest_inputs_are_the_seeds_and_every_leaf_is_reachable():
    a = datagen_forest.grown_forest(3, 784, 256, 10, 47)
    b = datagen_forest.grown_forest(3, 784, 256, 10, 47)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])        # the seed is the data
    assert not np.array_equal(
        a["n_leaves"], datagen_forest.grown_forest(3, 784, 256, 10, 48)[
            "n_leaves"])
    T, N = a["feature"].shape
    assert a["leaf_value"].shape == (T, a["n_leaves"].max(), 10)
    assert a["leaf_value"].dtype == np.float32
    assert 3000 < a["n_leaves"].min() and a["n_leaves"].max() < 5500
    live = np.arange(N)[None, :] < (a["n_leaves"] - 1)[:, None]
    assert (a["feature"][live] >= 0).all() and (a["feature"][~live] < 0).all()
    has = a["leaf_depth"] >= 0
    assert has.sum() == a["n_leaves"].sum()
    # a leaf's vector: a distribution, most of it on one class
    vec = a["leaf_value"][has]
    np.testing.assert_allclose(vec.sum(axis=1), 1.0, atol=1e-6)
    assert (vec.max(axis=1) >= 0.8).all() and (vec.min(axis=1) > 0).all()
    # every child reference names a node or a leaf of its tree, once
    for t in range(T):
        n_int = a["n_leaves"][t] - 1
        refs = np.concatenate([a["left_child"][t, :n_int],
                               a["right_child"][t, :n_int]])
        assert sorted(refs[refs >= 0]) == list(range(1, n_int))
        assert sorted(~refs[refs < 0]) == list(range(a["n_leaves"][t]))
    # uniform rows reach most leaves, each at the depth the drawing says
    Xb = datagen_forest.uniform_pixels(20_000, 784, 4700000007)
    assert Xb.dtype == np.uint8 and Xb.shape == (20_000, 784)
    np.testing.assert_array_equal(
        Xb[:5_000], datagen_forest.uniform_pixels(5_000, 784, 4700000007))
    assert not np.array_equal(Xb[:100],
                              datagen_forest.uniform_pixels(100, 784, 5))
    assert set(np.unique(Xb[:2000])) == set(range(256))
    visited = np.zeros(a["leaf_depth"].shape, bool)
    _, deepest, passed = reference_forest.class_scores(a, Xb, visited)
    assert visited.sum() / has.sum() > 0.8 and not visited[~has].any()
    assert deepest <= a["leaf_depth"].max() and 12 < passed < 16
    leaf, depth = reference_forest.leaf_of_rows(
        a["feature"][0], a["threshold_bin"][0], a["left_child"][0],
        a["right_child"][0], Xb[:500])
    np.testing.assert_array_equal(depth, a["leaf_depth"][0][leaf])
