"""The category-set job kind (`jobs/score_leafwise_cat.py`): `correct` has to
come out FALSE for each control of the configuration (the set test read as
`id <= threshold`, one category dropped from one set a tree, the unnamed ids
sent left, bfloat16 leaf values, every numeric threshold one bin off), for a
model of one-vs-rest nodes only (one the heap import's chains could have
served), for a sample that misses the leaves, and for broken scores; TRUE
when sound. And `datagen_leafwise_cat.py` and `reference_leafwise_cat.py`
against their own contracts, the configuration against its source's widths,
and the cell's per-layer metrics BY NAME.

The whole-run cases drive run.py but for the look for a chip (`--rehearse`:
CPU, the configuration's "rehearse" sizes, kernels interpreted) and read the
verdict it prints; the control cases put the control's answer in the
program's place and ask the job's own `check` which line fails, and ask a
CONTROL run (`--set patched_table=...`) of the sound program to fail every
control's line. The controls' readings at the cell's own size are in the
configuration's file and in PERF.md.

NO POSITION in any list of `BENCHMARK.json` is pinned here: the cell, its
configuration and its metrics are found by NAME, so that a later PR can list
the cell on a new metric, or add a cell or a metric after it, without
editing this file.
"""

import importlib
import json
import os

import numpy as np
import pytest

import datagen_leafwise_cat
import opcount_leafwise
import reference_leafwise_cat
import run
from test_correct import break_score

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = "score_leafwise_cat"
CONFIG = {"name": "allstate-lgbm-500t-255l-cat",
          "file": "benchmark/configs/allstate-lgbm-500t-255l-cat.json"}
CELL = {"name": "allstate-lgbm500t-255l-cat-score-1chip",
        "config": CONFIG["name"], "traffic": TRAFFIC, "chips": 1}
GAP, SHARE, WIDEST = ("vs the float64 reference", "share of the ensemble's",
                      "ids in the model's widest set")


def listed() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cat_verdict(capsys) -> bool:
    assert run.main(["--workload", CELL["name"], "--seed", "2147483659",
                     "--seconds", "0.1", "--trace", "0", "--rehearse"]) == 0
    out = capsys.readouterr().out
    assert "REHEARSAL complete" in out
    assert not out.rstrip().splitlines()[-1].startswith("{")   # no result
    return "correct=True" in out


def test_cat_correct_separates_sound_from_broken(capsys, monkeypatch):
    assert cat_verdict(capsys) is True
    break_score(monkeypatch)
    assert cat_verdict(capsys) is False


@pytest.fixture(scope="module")
def leafwise_cat_job():
    """The cell's job at its rehearsal size, set up once, with the sound
    answer of one call. (A name of its own: tests/test_benchmark_suite.py
    gathers every module's fixtures into one namespace.)"""
    import jax

    from jobs import score_leafwise_cat

    jax.config.update("jax_platforms", "cpu")
    cell = run.resolve_cell(listed(), CELL["name"])
    j = score_leafwise_cat.Job(cell, seed=4000000007, rehearse=True,
                               control={})
    j.setup()
    j.sound = j.one_job()
    return j


def failed_lines(checks: list) -> list:
    return [what for what, _, _, ok in checks if not ok]


def cat_reference_with(job, control=None):
    """The reference's answer over the whole batch of RAW rows (with ONE
    thing changed where `control` names it), as the program's float32."""
    X = job.raw_rows(np.arange(job.shapes["rows"]))
    return reference_leafwise_cat.raw_scores(
        job.text, X, control=control, seed=job.seed)[0].astype(np.float32)


def test_cat_sound_answer_passes_every_line(leafwise_cat_job):
    job = leafwise_cat_job
    assert failed_lines(job.check([job.sound], job.sound)) == []
    ref = cat_reference_with(job)
    assert failed_lines(job.check([ref] * 2, ref)) == []
    # the rows hold what the rule is about: ids the model never names, and
    # the three values that go right whatever the set
    X = job.raw_rows(np.arange(job.shapes["rows"]))
    assert np.isnan(X).any() and (X < 0).any() and (X == 100000.0).any()
    named = len(job.mapper.category_ids[5][0])
    assert (job.Xb[:, 5] == named).mean() > 0.01
    # ... in the categorical columns alone: a numeric column holds its
    # bins' own values and no NaN
    numeric = [c for c in range(X.shape[1])
               if c not in job.mapper.category_ids]
    assert len(numeric) == 16 and not np.isnan(X[:, numeric]).any()
    assert X[:, numeric].min() == 0 and X[:, numeric].max() == 254


@pytest.mark.parametrize("control", reference_leafwise_cat.CONTROLS)
def test_cat_control_fails_the_score_limit_alone(leafwise_cat_job, control):
    job = leafwise_cat_job
    answer = cat_reference_with(job, control)
    lines = failed_lines(job.check([answer], answer))
    assert len(lines) == 1 and GAP in lines[0]
    gap = np.abs(answer.astype(np.float64) - job.sound).max()
    assert gap > 2 * job.limits["score_atol"]


@pytest.mark.parametrize("patch", ["every", "threshold_bin_off"])
def test_cat_control_run_fails_every_control_line(leafwise_cat_job,
                                                  monkeypatch, patch):
    """A CONTROL run (`--set patched_table='"every"'`): the sound program's
    scores against the reference with one thing wrong, a line a control,
    each FAILED; the window's other lines stay ok."""
    job = leafwise_cat_job
    monkeypatch.setattr(job, "patch", patch)
    checks = job.check([job.sound], job.sound)
    controls = [c for c in checks if "CONTROL" in c[0]]
    assert [c[0].split("CONTROL ")[1].split("'")[0] for c in controls] == \
        list(reference_leafwise_cat.CONTROLS if patch == "every"
             else (patch,))
    assert not any(ok for *_, ok in controls)
    assert all(ok for what, *_, ok in checks if "CONTROL" not in what)


def test_cat_a_model_of_one_vs_rest_nodes_cannot_pass(leafwise_cat_job,
                                                      monkeypatch):
    """The same drawing with `max_cat_to_onehot` past every column: every
    set ONE id, which the heap import's chain expansion holds node for
    node: the scores agree and the widest-set line refuses the model."""
    job = leafwise_cat_job
    s = dict(job.shapes, max_cat_to_onehot=10_000)
    text = datagen_leafwise_cat.drawn_model(
        s, job.cell["config"]["assumed"]["drawing"],
        job.cell["config"]["model"]["learning_rate"])
    monkeypatch.setattr(job, "text", text)
    answer = cat_reference_with(job)
    lines = failed_lines(job.check([answer], answer))
    assert any(WIDEST in line for line in lines)
    assert not any(GAP in line for line in lines)


def test_cat_a_sample_that_misses_the_leaves_cannot_pass(leafwise_cat_job,
                                                         monkeypatch):
    """Rows that all hold ONE id a column: the scores agree with the
    reference and the sample is refused, a leaf a tree being all it
    reaches."""
    job = leafwise_cat_job
    monkeypatch.setattr(job, "codes", np.zeros_like(job.codes))
    monkeypatch.setattr(job, "Xb", job.mapper.transform(
        job.raw_rows(np.arange(job.shapes["rows"]))))
    answer = cat_reference_with(job)
    lines = failed_lines(job.check([answer], answer))
    assert lines and all(SHARE in line or "deepest" in line
                         or "set nodes" in line for line in lines)
    assert any(SHARE in line for line in lines)


def test_cat_the_question_is_asked_before_any_row_is_drawn(leafwise_cat_job,
                                                           monkeypatch):
    """A program whose span does not say category_sets 1: SystemExit out of
    `setup`, and no row drawn; the question names no tiling."""
    job = leafwise_cat_job
    from jobs import score_leafwise_cat

    fresh = score_leafwise_cat.Job(job.cell, seed=5, rehearse=False,
                                   control={})
    monkeypatch.setattr(fresh, "_what_ran", lambda: [
        ("the program's record says a node-list form serves the category "
         "sets", {"node_list": 1, "category_sets": None}, True, False)])
    monkeypatch.setattr(datagen_leafwise_cat, "drawn_codes",
                        lambda *a: pytest.fail(
                            "rows drawn before the what-ran question was "
                            "answered"))
    with pytest.raises(SystemExit, match="no Pallas kernel serves"):
        fresh.setup()
    assert score_leafwise_cat.SAID == ("node_list", "category_sets")
    said = str(job._what_ran())
    for tiling in ("path_mxu_tiles_per_tree", "trees_per_step",
                   "select_k_blocks", "catset_mxu_tiles_per_tree"):
        assert tiling not in said


def cat_config():
    with open(os.path.join(ROOT, CONFIG["file"])) as f:
        return json.load(f)


def test_cat_configuration_keeps_the_sources_widths():
    """LightGBM's Allstate experiment: 500 trees, 255 leaves, 32 columns (16
    categorical, 16 numeric) that one-hot coding makes the page's 4,228,
    255 bins, sets of at most 32, one-vs-rest up to 4, learning rate 0.1;
    `rows` is the one key that differs, whole chunks of it, not under the
    source's 7 (the file's `reduced` says what a call's peak reads there,
    against the memory floor)."""
    cfg = cat_config()
    s, d = cfg["shapes"], cfg["assumed"]["drawing"]
    assert (s["n_trees"], s["n_leaves"], s["features"], s["n_bins"],
            s["max_cat_threshold"], s["max_cat_to_onehot"]) == (
        500, 255, 32, 255, 32, 4)
    assert cfg["model"]["learning_rate"] == 0.1
    card = d["cardinalities"]
    assert len(card) == len(d["names"]) == len(d["exponents"]) == 32
    assert sum(1 for k in card if k) == 16
    assert sum(card) + 16 == 4228
    assert all(n == min(k, 254) for n, k in zip(d["named"], card))
    assert max(card) > 2 ** 8 > max(d["named"])      # uint16 codes, uint8 bins
    assert list(cfg["reduced"]) == ["rows"]
    assert s["rows"] % 2_000_000 == 0
    assert s["rows"] // 2_000_000 >= -(-13_184_290 // 2_000_000) == 7
    lim = cfg["check"]
    assert lim["sample_rows"] == 50_000 and 0 < lim["score_atol"] <= 1e-3
    assert lim["widest_set_min"] == s["max_cat_to_onehot"]
    assert lim["deep_leaf_min"] == 11           # HEAP_MAX_DEPTH
    assert lim["set_nodes_min"] >= 3 and lim["ordinal_nodes_min"] >= 3
    for text in (cfg["reduced"]["rows"], lim["readings"], cfg["source"]):
        assert "PLACEHOLDER" not in text
    assert "REMEMBERED, NOT READ" in cfg["source"]
    for key in ("source", "reduced", "assumed", "guarantees", "check"):
        assert cfg[key]


NEEDED = ("traverse_kernel_ms_per_call", "traverse_paths_roofline",
          "score_path_mxu_tiles_per_tree", "score_select_k_blocks",
          "score_catset_mxu_tiles_per_tree",
          "score_tables_streamed_mb", "score_prologue_ms",
          "score_upload_exposed_ms", "score_fetch_tail_ms",
          "score_first_call_extra_ms", "score_widen_ms",
          "score_accumulate_ms", "score_other_device_ms",
          "score_unscoped_device_ms", "score_upload_wait_ms",
          "score_host_unnamed_ms", "setup_ensemble_ms")


def test_cat_metrics_are_counted_by_name(leafwise_cat_job):
    """The per-layer metrics the cell needs, by NAME and by no position:
    each is in the benchmark with a file and a reader that is there, and
    lists the cell (a later PR may list it on more); the cell reports
    `score_mrows_per_s` and `setup_s`; its configuration and its one entry
    of `workloads` are found by name. The root span of a call carries what
    the counts' readers read, and the roofline's count is the cell's own
    shapes'."""
    from ddt_tpu.telemetry.annotations import recent_spans
    from readers import root_count

    manifest = listed()
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    here = os.path.join(ROOT, "benchmark", "layer_metrics")
    cell = CELL["name"]
    for name in NEEDED:
        assert cell in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] in ("score_mrows_per_s", "setup_s")
        with open(os.path.join(here, name + ".json")) as f:
            spec = json.load(f)
        assert callable(importlib.import_module(
            "readers." + spec["reader"]).read)
    end_to_end = {m["name"] for m in run.metrics_of(manifest, "end_to_end",
                                                    cell)}
    assert end_to_end == {"score_mrows_per_s", "setup_s"}
    mine, = [w for w in manifest["workloads"] if w["name"] == cell]
    assert {k: mine[k] for k in CELL} == CELL
    config, = [c for c in manifest["configs"] if c["name"] == CONFIG["name"]]
    assert config["file"] == CONFIG["file"]
    assert config["reduced"] == ["rows"]
    own = per_layer["score_catset_mxu_tiles_per_tree"]
    assert (own["unit"], own["better"], own["source"]) == (
        "tiles", "lower", "program_counter")
    with open(os.path.join(here, own["name"] + ".json")) as f:
        assert json.load(f) == {"reader": "root_count", "args": {
            "count": "catset_mxu_tiles_per_tree"}}
    assert callable(root_count.read)
    leafwise_cat_job.one_job()
    root = [sp for sp in recent_spans() if sp["name"] == "ddt:predict"][-1]
    counts = root["counts"]
    assert counts["category_sets"] == 1
    # what the set test adds beside an ordinal model of 32 columns and 256
    # lanes, which asks 5 (the packed select)
    assert counts["catset_mxu_tiles_per_tree"] == \
        counts["path_mxu_tiles_per_tree"] - 5 > 0
    assert counts["path_mxu_tiles_per_tree"] == \
        2 * counts["select_k_blocks"] + 4
    # the roofline's count is of the model's shape: F = 32, the columns a
    # row has, a set test counted as the one compare an ordinal node costs
    s = cat_config()["shapes"]
    ops, nbytes = opcount_leafwise.traverse_call_paths(s)
    assert ops == 2.0 * s["rows"] * 500 * 254 * (32 + 255)
    assert nbytes == s["rows"] * 36 + 500 * (254 * 16 + 255 * 4)
    # at 18 weight tiles a tree the share cannot pass 25%
    assert 254 * 287 / (18 * 128 * 128) == pytest.approx(0.247, abs=2e-3)


def test_cat_drawn_model_is_the_model_seeds_and_follows_the_rule():
    cfg = cat_config()
    s = dict(cfg["shapes"], n_trees=6)
    d = cfg["assumed"]["drawing"]
    text = datagen_leafwise_cat.drawn_model(s, d, 0.1)
    assert text == datagen_leafwise_cat.drawn_model(s, d, 0.1)
    assert text != datagen_leafwise_cat.drawn_model(
        dict(s, model_seed=s["model_seed"] + 1), d, 0.1)
    trees = reference_leafwise_cat.parse(text)
    assert len(trees) == 6
    names = reference_leafwise_cat.named_ids(trees, 32)
    weights = datagen_leafwise_cat.column_weights(
        d["cardinalities"], d["exponents"], s["model_seed"])
    for c, (w, n) in enumerate(zip(weights, d["named"])):
        # a model names only the `named` most frequent ids of a column,
        # and no id of a numeric one
        assert names[c] <= (set() if w is None else set(
            np.argsort(-w, kind="stable")[:n].tolist()))
    for tree in trees:
        L = tree["num_leaves"]
        sets = (tree["decision_type"] & 1).astype(bool)
        assert L == 255 and set(tree["decision_type"]) == {2, 9}
        # a set node on a categorical column, a threshold on a numeric
        # one, both kinds in every tree
        assert (np.asarray(d["cardinalities"])[tree["split_feature"]] > 0
                ).tolist() == sets.tolist()
        assert 30 < sets.sum() < 224
        assert (tree["threshold"][sets] == np.arange(sets.sum())).all()
        thr = tree["threshold"][~sets]
        assert (thr % 1 == 0.5).all() and 0 < thr.min() and thr.max() < 254
        refs = np.concatenate([tree["left_child"], tree["right_child"]])
        assert sorted(~refs[refs < 0]) == list(range(L))
        assert sorted(refs[refs >= 0]) == list(range(1, L - 1))
        bits = np.unpackbits(tree["cat_threshold"].astype("<u4").view(
            np.uint8), bitorder="little")
        sizes = np.add.reduceat(bits, 32 * tree["cat_boundaries"][:-1])
        assert 1 <= sizes.min() and sizes.max() <= 32
        assert sizes.max() > 4                   # multi-id sets


def test_cat_reference_follows_the_librarys_rule():
    """One hand-written tree: a set {1, 34} over column 0 (two words), then
    a numerical node; NaN right under missing type NaN, as id 0 under
    None."""
    def text(decision):
        return "\n".join([
            "Tree=0", "num_leaves=3", "num_cat=1", "split_feature=0 1",
            "threshold=0 2.5", f"decision_type={decision} 0",
            "left_child=1 -1", "right_child=-3 -2",
            "leaf_value=1 2 4", "cat_boundaries=0 2", "cat_threshold=2 4",
            "", "end of trees"])

    X = np.asarray([[1, 0], [34, 3], [2, 0], [64, 0], [-1, 0],
                    [np.nan, 0], [1.9, 9], [0, 0]], np.float64)
    got, facts = reference_leafwise_cat.raw_scores(text(9), X)
    assert got.tolist() == [1, 2, 4, 4, 4, 4, 2, 4]
    assert facts["widest_set"] == 2 and facts["deepest"] == 2
    zero = text(1).replace("cat_threshold=2 4", "cat_threshold=3 4")
    got, _ = reference_leafwise_cat.raw_scores(zero, X)
    assert got.tolist() == [1, 2, 4, 4, 4, 1, 2, 1]      # NaN as id 0
    with pytest.raises(ValueError, match="unknown control"):
        reference_leafwise_cat.raw_scores(text(9), X, control="nope")
    # the ordinal side's control moves the numerical node's threshold
    # alone: [34, 3] passes 3 <= 3.5 and goes left
    got, _ = reference_leafwise_cat.raw_scores(
        text(9), X, control="threshold_bin_off")
    assert got.tolist() == [1, 1, 4, 4, 4, 4, 2, 4]
    # a tree without a set node writes no bitset
    plain = "\n".join([
        "Tree=0", "num_leaves=2", "num_cat=0", "split_feature=1",
        "threshold=2.5", "decision_type=2", "left_child=-1",
        "right_child=-2", "leaf_value=1 2", "", "end of trees"])
    got, facts = reference_leafwise_cat.raw_scores(plain, X)
    assert got.tolist() == [1, 2, 1, 1, 1, 1, 2, 1]
    assert facts["widest_set"] == 0 and facts["ordinal_nodes"] == 1.0


def test_cat_no_chip_no_result_line(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL["name"], "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc == 1
    assert not any(line.startswith("{") for line in out.splitlines())
