"""The XGBoost multiclass job kind (`jobs/score_xgb.py`): `correct` has to
come out FALSE for each control of the configuration (`<=` for `<`, the
classes taken column-major, bfloat16 leaf values, the margins for the
probabilities, the chain's links dropped, `eta` applied twice), whether the
control's answer is put in the program's place or the program is handed the
control's model (`--set patched_table`), for a sample that misses the leaf
coverage, the path length or the depth limit, and for broken answers; TRUE
when sound. And `opcount_xgb.py` against the hand number, `datagen_xgb.py`
against its own contract and the library's schema, the configuration's file
against its source.

The whole-run cases drive run.py but for the look for a chip (`--rehearse`:
CPU, the configuration's "rehearse" sizes, kernels interpreted) and read the
verdict it prints. The controls' readings at the cell's own size are in the
configuration's file and in PERF.md.
"""

import json
import os
import sys

import numpy as np
import pytest

import datagen_xgb
import opcount_xgb
import reference_xgb
import run
from test_correct import break_score, cell_of, verdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = "score_xgb"
XGB_GAP, XGB_SHARE, XGB_DEEP, XGB_PASSED, XGB_SAID = (
    "vs the float64 reference", "share of the model's",
    "deepest path a sampled row takes", "passes in one of the",
    "the program's record says")


def test_xgb_correct_separates_sound_from_broken_and_patched(
        capsys, monkeypatch):
    assert verdict(capsys, TRAFFIC) is True
    # the program handed a control's model, the answer held to the right
    # one: a CONTROL run, never a result line
    assert verdict(capsys, TRAFFIC, "--set",
                   'patched_table="not_strict"') is False
    break_score(monkeypatch)
    assert verdict(capsys, TRAFFIC) is False


@pytest.fixture(scope="module")
def xgb_job():
    """The cell's job at its rehearsal size, set up once, with the sound
    answer of one call. (A name of its own: tests/test_benchmark_suite.py
    gathers every module's fixtures into one namespace.)"""
    import jax

    from jobs import score_xgb

    jax.config.update("jax_platforms", "cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = run.resolve_cell(manifest, cell_of(TRAFFIC))
    j = score_xgb.Job(cell, seed=5000000011, rehearse=True, control={})
    j.setup()
    j.sound = j.one_job()
    return j


def xgb_failed(checks: list) -> list:
    return [what for what, _, _, ok in checks if not ok]


def xgb_reference(job, control=None, X=None, model=None):
    """The reference's answer over the whole batch (of the model with ONE
    thing wrong where `control` names it), as the program's float32
    [rows, classes]."""
    return reference_xgb.answer(
        job.model if model is None else model,
        job.X if X is None else X, control,
        job.cell["config"]["assumed"]["drawing"]["eta"]).astype(np.float32)


def test_xgb_sound_answer_passes_every_line(xgb_job):
    job = xgb_job
    assert xgb_failed(job.check([job.sound], job.sound)) == []
    np.testing.assert_allclose(job.sound.sum(axis=1), 1.0, atol=1e-6)
    # and the reference itself, in float32, is inside the limit
    assert xgb_failed(job.check([xgb_reference(job)] * 2,
                                xgb_reference(job))) == []
    assert job.shapes["skeleton"] == datagen_xgb.skeleton(job.model)
    # the model went the user's way: imported, its thresholds ranked
    assert job.ens.loss == "softmax" and job.ens.n_classes == 7
    assert not job.ens.missing_routes and job.mapper.n_bins == 256
    assert job.Xb.dtype == np.uint8


@pytest.mark.parametrize("control", reference_xgb.CONTROLS)
def test_xgb_control_fails_the_probability_limit_alone(xgb_job, control):
    job = xgb_job
    answer = xgb_reference(job, control)
    lines = xgb_failed(job.check([answer], answer))
    assert len(lines) == 1 and XGB_GAP in lines[0]
    gap = np.abs(answer.astype(np.float64) - job.sound).max()
    # (a rehearsal sums TWO rounds a class: bfloat16 leaves read 3.7e-4
    # here and 6.85e-3 at the cell's 350; the configuration's readings)
    assert gap > 5 * job.limits["proba_atol"]


def test_xgb_a_dead_subtree_cannot_pass(xgb_job, monkeypatch):
    """Rows that all sit in one corner of the box: the answers agree with
    the reference and the sample is refused, because it reaches one leaf of
    each tree."""
    job = xgb_job
    X = np.zeros_like(job.X)
    monkeypatch.setattr(job, "X", X)
    answer = xgb_reference(job, X=X)
    lines = xgb_failed(job.check([answer], answer))
    assert any(XGB_SHARE in line for line in lines)
    assert not any(XGB_GAP in line for line in lines)


def test_xgb_a_model_a_heap_could_hold_cannot_pass(xgb_job, monkeypatch):
    """Shallow trees (the same drawing over a set of 2,000 rows: a root's
    hessian mass a 290th): the answers agree, but no row goes deeper than
    12 nodes and a row passes fewer than 8 a tree."""
    job = xgb_job
    s, cfg = job.shapes, job.cell["config"]
    model = datagen_xgb.drawn_model(
        s["rounds"], s["features"], s["model_seed"],
        **{**cfg["assumed"]["drawing"], **s["drawing"], "rows": 2000})
    sk = datagen_xgb.skeleton(model)
    assert sk["deepest_leaf"] <= 12 and sk["leaves_a_tree"][2] < 400
    monkeypatch.setattr(job, "model", model)
    monkeypatch.setitem(job.shapes, "skeleton", sk)
    answer = xgb_reference(job)
    lines = xgb_failed(job.check([answer], answer))
    assert len(lines) == 2
    assert any(XGB_DEEP in line for line in lines)
    assert any(XGB_PASSED in line for line in lines)


def test_xgb_the_question_is_asked_before_any_row_is_drawn(xgb_job,
                                                          monkeypatch):
    """A program whose span does not say softmax's trees and the link, and
    one that has no XGBoost import at all: SystemExit out of `setup`, and
    `rows_and_bins` never called; the older program is turned away before
    the model is drawn."""
    from jobs import score_xgb

    job = xgb_job
    monkeypatch.setattr(datagen_xgb, "rows_and_bins", lambda *a: (
        pytest.fail("rows drawn before the what-ran question was answered")))
    fresh = score_xgb.Job(job.cell, seed=5, rehearse=False, control={})
    fresh.shapes.update(rounds=2, drawing=job.shapes["drawing"])
    monkeypatch.setattr(fresh, "_what_ran", lambda: [
        ("the program's record says a node-list form serves softmax's "
         "round-major trees", {"node_list": 1, "link": None}, True, False)])
    with pytest.raises(SystemExit, match="no Pallas kernel serves"):
        fresh.setup()

    import ddt_tpu.models

    monkeypatch.setitem(sys.modules, "ddt_tpu.models.xgboost_io", None)
    monkeypatch.delattr(ddt_tpu.models, "xgboost_io")
    monkeypatch.setattr(datagen_xgb, "drawn_model", lambda *a, **k: (
        pytest.fail("the model drawn for a program that cannot import it")))
    with pytest.raises(SystemExit, match="imports no XGBoost model"):
        score_xgb.Job(job.cell, seed=5, rehearse=False, control={}).setup()
    monkeypatch.undo()
    # and it asks nothing about tiling
    said = str(job._what_ran())
    assert "single_subtree_trees" in said and XGB_SAID in said
    for tiling in ("path_mxu_tiles_per_tree", "trees_per_step",
                   "table_blocks", "select_nodes_per_lane", "subtree_lanes"):
        assert tiling not in said


def test_xgb_a_control_run_keeps_its_key_out_of_the_programs_config(xgb_job):
    from jobs import score_xgb

    control = {"patched_table": "eta_twice"}
    j = score_xgb.Job(xgb_job.cell, seed=6, rehearse=True, control=control)
    assert j.patch == "eta_twice" and control       # run.py's is whole
    assert not hasattr(j.cfg, "patched_table")
    j.setup()
    ratio = j.ens.leaf_value[0, :5] / xgb_job.ens.leaf_value[0, :5]
    np.testing.assert_allclose(ratio, 0.3, rtol=1e-6)
    answer = j.one_job()
    assert XGB_GAP in xgb_failed(j.check([answer], answer))[0]
    # the margins for the probabilities: the CALL's one wrong thing
    raw = score_xgb.Job(xgb_job.cell, seed=5000000011, rehearse=True,
                        control={"patched_table": "no_link"})
    raw.setup()
    answer = raw.one_job()
    np.testing.assert_allclose(
        reference_xgb.softmax(answer.astype(np.float64)), xgb_job.sound,
        atol=1e-6)
    assert XGB_GAP in xgb_failed(raw.check([answer], answer))[0]
    with pytest.raises(ValueError, match="unknown control"):
        reference_xgb.patched(j.model, "no_such_control")


def xgb_config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(ROOT, files["covtype-xgb-softprob-d16"])) as f:
        return json.load(f)


def test_xgb_configuration_keeps_the_sources_widths():
    """GPUTreeShap's covtype-large: 7 classes, max_depth 16, 54 columns, 256
    bins, eta 0.3, the set's own 581,012 rows; `rounds` is the one key that
    differs, the first of the source's 1,000, a multiple of 50 and at least
    100."""
    cfg = xgb_config()
    s, m = cfg["shapes"], cfg["model"]
    assert (s["rows"], s["features"], s["n_bins"], s["n_classes"],
            s["max_depth"]) == (581_012, 54, 256, 7, 16)
    assert (m["objective"], m["loss"], m["eta"], m["base_score"],
            m["max_bin"]) == ("multi:softprob", "softmax", 0.3, 0.5, 256)
    assert list(cfg["reduced"]) == ["rounds", "rows"]
    assert "NOT changed" in cfg["reduced"]["rows"]
    assert s["rounds"] % 50 == 0 and 100 <= s["rounds"] <= 1000
    assert s["n_trees"] == 7 * s["rounds"]
    assert cfg["assumed"]["drawing"] == datagen_xgb.DEFAULTS
    assert cfg["architecture"] is None
    lim = cfg["check"]
    assert lim["sample_rows"] == 50_000 and 0 < lim["proba_atol"] <= 1e-4
    assert (lim["deep_leaf_min"], lim["subtrees_max_min"]) == (12, 20)
    for text in (cfg["reduced"]["rounds"], cfg["assumed"]["ensemble"],
                 lim["readings"], cfg["deployment"]):
        assert "TO BE FILLED" not in text


def test_xgb_metrics_name_their_readers_and_the_cell_alone():
    """The three per-layer metrics this cell brings: a file each, a reader
    that is there, the new cell their one workload; and the cell's name on
    every accepted metric it reports, appended last."""
    import importlib

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = cell_of(TRAFFIC)
    here = os.path.join(ROOT, "benchmark")
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m.get("workloads") == [cell]}
    assert sorted(mine) == ["score_link_ms", "score_single_subtree_trees",
                            "traverse_xgb_roofline"]
    for name, m in mine.items():
        with open(os.path.join(here, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert callable(importlib.import_module(
            "readers." + spec["reader"]).read)
        assert m["moves"] == "score_mrows_per_s"
    with open(os.path.join(here, "layer_metrics", "score_link_ms.json")) as f:
        assert json.load(f)["args"]["stage"] == "^predict:link$"
    listed = [m["name"] for m in manifest["per_layer"]
              if cell in m.get("workloads", ())]
    assert len(listed) == 16 and all(
        m["workloads"][-1] == cell for m in manifest["per_layer"]
        if cell in m.get("workloads", ()))
    # the stage reader reads the table device_stage_ms makes
    from readers import device_stage_ms, stage_ms
    assert stage_ms.read is device_stage_ms.read


def test_traverse_call_xgb_by_hand():
    """A model of two trees by hand: 5 and 2 internal nodes, 6 and 3
    leaves, path entries 3+3+2+2+3+3 and 1+2+2, F = 54, 7 classes, 1,000
    rows: 2 x 1000 x (54 x 7 + 21 + 9): a leaf holds ONE value; padding and
    the other six classes' zeros count nothing."""
    sk = {"nodes": 7, "leaves": 9, "path_entries": 21}
    ops, nbytes = opcount_xgb.traverse_call_xgb(
        {"rows": 1000, "features": 54, "n_classes": 7, "skeleton": sk})
    assert ops == 2.0 * 1000 * (54 * 7 + 21 + 9)
    assert nbytes == 1000 * 54 + 4 * 1000 * 7 + 7 * 16 + 9 * 4


def test_xgb_model_is_the_librarys_schema_and_every_leaf_is_reachable():
    a = datagen_xgb.drawn_model(3, 54, 50, fade=0.1)
    b = datagen_xgb.drawn_model(3, 54, 50, fade=0.1)
    assert json.dumps(a) == json.dumps(b)            # the seed is the model
    assert json.dumps(a) != json.dumps(datagen_xgb.drawn_model(
        3, 54, 51, fade=0.1))
    assert json.loads(json.dumps(a)) == a            # plain JSON, no arrays
    learner = a["learner"]
    assert learner["objective"]["name"] == "multi:softprob"
    assert learner["learner_model_param"] == {
        "base_score": "5.000000E-01", "num_class": "7", "num_feature": "54",
        "num_target": "1"}
    held = reference_xgb.booster(a)
    assert held["tree_info"] == [0, 1, 2, 3, 4, 5, 6] * 3
    units = datagen_xgb.units(54)
    for tree in held["trees"]:
        left = np.asarray(tree["left_children"])
        right = np.asarray(tree["right_children"])
        inner = left >= 0
        assert ((left < 0) == (right < 0)).all()
        # every node but the root is some node's child, once, after it
        kids = np.concatenate([left[inner], right[inner]])
        assert sorted(kids) == list(range(1, len(left)))
        assert (left[inner] > np.nonzero(inner)[0]).all()
        assert reference_xgb.node_depths(left, right).max() <= 16
        # an internal node's condition is a cut of its column
        col = np.asarray(tree["split_indices"])[inner]
        cut = np.asarray(tree["split_conditions"], np.float32)[inner]
        k = cut / units[col]
        assert (k == np.round(k)).all() and (k >= 1).all()
        assert (k[col >= 10] == 1).all() and (k[col < 10] <= 255).all()
    sizes = datagen_xgb.skeleton(a)["leaves_a_tree"]
    assert sizes[0] < 100 and sizes[2] > 5000       # tens beside thousands
    # rows: uniform over each column's bins, half of the continuous ON a cut
    X, b = datagen_xgb.rows_and_bins(20_000, 54, 5000000011)
    assert X.dtype == np.float32 and b.dtype == np.uint8
    np.testing.assert_array_equal(
        X[:5_000], datagen_xgb.rows_and_bins(5_000, 54, 5000000011)[0])
    assert set(np.unique(b[:, :10])) == set(range(256))
    assert set(np.unique(b[:, 10:])) == {0, 1}
    on = X[:, :10] == b[:, :10] * units[:10]
    assert 0.48 < on.mean() < 0.52
    np.testing.assert_array_equal(X[:, 10:], b[:, 10:])
    # uniform rows reach most leaves of the large trees
    visited: list = []
    _, deepest, passed = reference_xgb.margins(a, X, visited)
    leaves = sum(int((np.asarray(t["left_children"]) < 0).sum())
                 for t in held["trees"])
    assert sum(int(v.sum()) for v in visited) / leaves > 0.5
    assert deepest == 16 and 10 < passed < 16
