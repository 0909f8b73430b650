"""The leaf-wise job kind (`jobs/score_leafwise.py`): `correct` has to come
out FALSE for each control of the configuration (bfloat16 leaf values, `<` for
`<=` at every node, left and right exchanged at one node a tree, the sibling
leaf for 1% of the (row, tree) pairs), for a sample that misses the leaf
coverage or the depth limit, for the jax.numpy form forced by `--set`, and for
broken scores; TRUE when sound. And `opcount_leafwise.py` against hand
numbers, `datagen_leafwise.py` against its own contract.

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

import datagen_leafwise
import opcount_leafwise
import reference_leafwise
import run
from test_correct import break_score, cell_of, verdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GAP, SHARE, DEEP = ("vs the float64 reference", "share of the ensemble's",
                    "deepest path a sampled row takes")


def test_correct_separates_sound_from_broken(capsys, monkeypatch):
    assert verdict(capsys, "score_leafwise") is True
    # the jax.numpy form scores right and is not what the cell measures:
    # its span says node_list 1 too, so on a CPU (which lowers no kernel
    # either way) the rehearsal passes it; the chip's question is
    # tpu_custom_call (PERF.md section 2)
    break_score(monkeypatch)
    assert verdict(capsys, "score_leafwise") is False


@pytest.fixture(scope="module")
def leafwise_job():
    """The cell's job at its rehearsal size, set up once, with the sound
    answer of one call. (A name of its own: tests/test_benchmark_suite.py
    gathers every module's fixtures into one namespace.)"""
    import jax

    from jobs import score_leafwise

    jax.config.update("jax_platforms", "cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = run.resolve_cell(manifest, cell_of("score_leafwise"))
    j = score_leafwise.Job(cell, seed=4000000007, rehearse=True, control={})
    j.setup()
    j.sound = j.one_job()
    return j


def failed(checks: list) -> list:
    return [what for what, _, _, ok in checks if not ok]


def reference_with(job, control=None, Xb=None):
    """The reference's answer over the whole batch (with ONE thing changed
    where `control` names it), as the program's float32 [rows]."""
    m = job.cell["config"]["model"]
    return reference_leafwise.raw_scores(
        job.tables, m["learning_rate"], m["base_score"],
        job.Xb if Xb is None else Xb, control=control,
        seed=job.seed)[0].astype(np.float32)


def test_sound_answer_passes_every_line(leafwise_job):
    job = leafwise_job
    assert failed(job.check([job.sound], job.sound)) == []
    # and the reference itself, in float32, is inside the score limit
    assert failed(job.check([reference_with(job)] * 2,
                            reference_with(job))) == []


@pytest.mark.parametrize("control", reference_leafwise.CONTROLS)
def test_control_fails_the_score_limit_alone(leafwise_job, control):
    job = leafwise_job
    answer = reference_with(job, control)
    lines = failed(job.check([answer], answer))
    assert len(lines) == 1 and GAP in lines[0]
    gap = np.abs(answer.astype(np.float64) - job.sound).max()
    assert gap > 10 * job.limits["score_atol"]


def test_a_sample_that_misses_the_leaves_cannot_pass(leafwise_job,
                                                     monkeypatch):
    """Rows that all sit in one corner of the bin box: the scores agree
    with the reference and the sample is refused, because it reaches a few
    leaves of each tree and none of the deep ones need be among them."""
    job = leafwise_job
    Xb = np.zeros_like(job.Xb)
    monkeypatch.setattr(job, "Xb", Xb)
    answer = reference_with(job, Xb=Xb)
    lines = failed(job.check([answer], answer))
    assert any(SHARE in line for line in lines)
    assert all(SHARE in line or DEEP in line for line in lines)


def test_a_model_a_heap_could_hold_cannot_pass(leafwise_job, monkeypatch):
    """Shallow trees (the same drawing procedure stopped at 8 leaves): every
    leaf is reached and the scores agree, but no row goes deeper than 10
    nodes."""
    job = leafwise_job
    s = job.shapes
    tables = datagen_leafwise.leafwise_trees(s["n_trees"], 8, s["features"],
                                             s["n_bins"], job.seed)
    monkeypatch.setattr(job, "tables", tables)
    answer = reference_with(job)
    lines = failed(job.check([answer], answer))
    assert len(lines) == 1 and DEEP in lines[0]


def test_the_question_is_asked_before_any_row_is_drawn(leafwise_job,
                                                       monkeypatch):
    """A program whose span does not say node_list 1: SystemExit out of
    `setup`, and `uniform_bins` never called."""
    job = leafwise_job
    import datagen
    from jobs import score_leafwise

    fresh = score_leafwise.Job(job.cell, seed=5, rehearse=False, control={})
    monkeypatch.setattr(fresh, "_what_ran", lambda: [
        ("the program's record says a node-list form serves", None, 1,
         False)])
    monkeypatch.setattr(datagen, "uniform_bins", lambda *a: pytest.fail(
        "rows drawn before the what-ran question was answered"))
    with pytest.raises(SystemExit, match="no Pallas kernel serves"):
        fresh.setup()
    # and it asks nothing about tiling
    said = str(job._what_ran())
    assert "node_list" in said
    for tiling in ("path_mxu_tiles_per_tree", "trees_per_step",
                   "table_blocks"):
        assert tiling not in said.split("ddt:predict:ensemble")[0]


def config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(ROOT, files["higgs-lgbm-500t-255l"])) as f:
        return json.load(f)


def test_configuration_keeps_the_sources_widths():
    """LightGBM's Higgs experiment: 500 trees, 255 leaves, 28 features, 255
    bins, learning rate 0.1; `rows` is the one key that differs."""
    cfg = config()
    s = cfg["shapes"]
    assert (s["n_trees"], s["n_leaves"], s["features"], s["n_bins"]) == (
        500, 255, 28, 255)
    assert cfg["model"]["learning_rate"] == 0.1
    assert list(cfg["reduced"]) == ["rows"]
    assert s["rows"] % 2_000_000 == 0        # whole PREDICT_ROW_CHUNK chunks
    assert 0.9 <= cfg["check"]["leaf_share_min"] < 1
    assert cfg["check"]["deep_leaf_min"] == 10


def test_traverse_call_paths_three_leaf_tree():
    """One tree of 3 leaves (2 internal nodes) over 4 features, 10 rows:
    the select is 2 x 10 x 1 x 2 x 4 = 160 operations, the resolve
    2 x 10 x 1 x 2 x 3 = 120."""
    ops, nbytes = opcount_leafwise.traverse_call_paths(
        dict(rows=10, features=4, n_trees=1, n_leaves=3))
    assert ops == 160 + 120
    # 4 B a row in, 4 B a row out, 2 nodes x 16 B, 3 leaf values x 4 B
    assert nbytes == 10 * 4 + 10 * 4 + 2 * 16 + 3 * 4


def test_traverse_call_paths_higgs_100m_rows():
    ops, nbytes = opcount_leafwise.traverse_call_paths(config()["shapes"])
    # 2 x 1e8 x 500 x 254 x (28 + 255) = 7.188e15: 36.5 s at 197 TFLOP/s
    assert ops == 2.0 * 1e8 * 500 * 254 * 283
    assert ops / 197e12 == pytest.approx(36.49, rel=1e-3)
    assert nbytes == 10 ** 8 * 32 + 500 * (254 * 16 + 255 * 4)
    assert nbytes / 819e9 < 0.01 * ops / 197e12       # bound by compute
    # the strategy's ceiling on 128-wide MXU tiles: 28 of 128 K rows in the
    # select's 2 tiles, 254 of 256 nodes and 255 of 256 leaves in the
    # resolve's 4
    assert 254 * 283 / (6 * 128 * 128) == pytest.approx(0.731, rel=2e-3)
    # nothing of a kernel's padding, tiling or blocking is a term
    assert opcount_leafwise.traverse_call_paths(
        dict(config()["shapes"], trees_per_step=8, tile_rows=4096)) == (
            ops, nbytes)


def test_leafwise_trees_are_the_seeds_and_every_leaf_is_reachable():
    s = config()["shapes"]
    a = datagen_leafwise.leafwise_trees(20, s["n_leaves"], s["features"],
                                        s["n_bins"], 4000000007)
    b = datagen_leafwise.leafwise_trees(20, s["n_leaves"], s["features"],
                                        s["n_bins"], 4000000007)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])        # the seed is the data
    other = datagen_leafwise.leafwise_trees(20, s["n_leaves"], s["features"],
                                            s["n_bins"], 5)
    assert not np.array_equal(a["feature"], other["feature"])
    assert a["feature"].shape == (20, 254)
    assert a["leaf_value"].shape == (20, 255)
    L = s["n_leaves"]
    for t in range(20):
        # 255 leaves and 253 nodes below the root, each referenced once
        refs = np.concatenate([a["left_child"][t], a["right_child"][t]])
        assert sorted(~refs[refs < 0]) == list(range(L))
        assert sorted(refs[refs >= 0]) == list(range(1, L - 1))
        # every leaf's box is non-empty: walk down from the root narrowing
        # the bin ranges, as a row would
        stack = [(0, np.zeros(s["features"], int),
                  np.full(s["features"], s["n_bins"] - 1))]
        depths = []
        while stack:
            ref, lo, hi, *d = stack.pop()
            d = d[0] if d else 0
            if ref < 0:
                assert (lo <= hi).all()
                depths.append(d)
                continue
            f, thr = a["feature"][t][ref], a["threshold_bin"][t][ref]
            assert lo[f] <= thr < hi[f]           # both sides keep a bin
            left_hi, right_lo = hi.copy(), lo.copy()
            left_hi[f], right_lo[f] = thr, thr + 1
            stack.append((a["left_child"][t][ref], lo, left_hi, d + 1))
            stack.append((a["right_child"][t][ref], right_lo, hi, d + 1))
        assert len(depths) == L and max(depths) > 10     # leaf-wise: deep


def test_no_chip_no_result_line(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", cell_of("score_leafwise"), "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc == 1
    assert not any(line.startswith("{") for line in out.splitlines())
