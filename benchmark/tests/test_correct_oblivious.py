"""The oblivious job kind (`jobs/score_oblivious.py`): `correct` has to come
out FALSE for each control of the configuration (bfloat16 leaf values, `>=`
for `>`, the index built high-bit-first, one split a tree on the next
column), whether the control's answer is put in the program's place or the
program is handed the control's tables (`--set patched_table`), for a sample
with a dead bit or a corner of the bin box, and for broken scores; TRUE when
sound. And `opcount_oblivious.py` against the hand number,
`datagen_oblivious.py` against its own contract.

The whole-run cases drive run.py but for the look for a chip (`--rehearse`:
CPU, the configuration's "rehearse" sizes, kernels interpreted) and read the
verdict it prints. The controls' readings at the cell's own size are in the
configuration's file and in PERF.md.
"""

import json
import os

import numpy as np
import pytest

import datagen_oblivious
import opcount_oblivious
import reference_oblivious
import run
from test_correct import break_score, cell_of, verdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = "score_oblivious"
GAP, SHARE, BITS, SAID = (
    "vs the float64 reference", "share of the ensemble's",
    "one of the bit positions is set", "the program's record says")


def test_correct_separates_sound_from_broken_and_patched(capsys, monkeypatch):
    assert verdict(capsys, TRAFFIC) is True
    # the program handed a control's tables, the answer held to the right
    # ones: a CONTROL run, never a result line
    for control in ("high_bit_first", "greater_equal"):
        assert verdict(capsys, TRAFFIC, "--set",
                       f'patched_table="{control}"') is False
    break_score(monkeypatch)
    assert verdict(capsys, TRAFFIC) is False


@pytest.fixture(scope="module")
def oblivious_job():
    """The cell's job at its rehearsal size, set up once, with the sound
    answer of one call. (A name of its own: tests/test_benchmark_suite.py
    gathers every module's fixtures into one namespace.)"""
    import jax

    from jobs import score_oblivious

    jax.config.update("jax_platforms", "cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = run.resolve_cell(manifest, cell_of(TRAFFIC))
    j = score_oblivious.Job(cell, seed=4000000007, rehearse=True, control={})
    j.setup()
    j.sound = j.one_job()
    return j


def failed(checks: list) -> list:
    return [what for what, _, _, ok in checks if not ok]


def reference_with(job, control=None, Xb=None, tables=None):
    """The reference's answer over the whole batch (with ONE thing changed
    where `control` names it), as the program's float32 [rows]."""
    m = job.cell["config"]["model"]
    return reference_oblivious.raw_scores(
        job.tables if tables is None else tables, m["scale"], m["bias"],
        job.Xb if Xb is None else Xb, control=control)[0].astype(np.float32)


def test_sound_answer_passes_every_line(oblivious_job):
    job = oblivious_job
    assert failed(job.check([job.sound], job.sound)) == []
    # and the reference itself, in float32, is inside the score limit
    assert failed(job.check([reference_with(job)] * 2,
                            reference_with(job))) == []


@pytest.mark.parametrize("control", reference_oblivious.CONTROLS)
def test_control_fails_the_score_limit_alone(oblivious_job, control):
    job = oblivious_job
    answer = reference_with(job, control)
    lines = failed(job.check([answer], answer))
    assert len(lines) == 1 and GAP in lines[0]
    gap = np.abs(answer.astype(np.float64) - job.sound).max()
    # the routing controls miss by whole leaves; bfloat16 leaves by their
    # rounding, which at the rehearsal's 130 trees is an 8th of what the
    # cell's 8000 sum to (the configuration's file has that reading)
    assert gap > (1.0 if control == "bfloat16_leaves" else 100.0
                  ) * job.limits["score_atol"]
    # the same control as a patch of the tables is the same wrong model
    patched = reference_oblivious.patched(job.tables, control,
                                          job.shapes["features"])
    np.testing.assert_array_equal(
        reference_with(job, tables=patched), answer)


def test_a_dead_bit_cannot_pass(oblivious_job, monkeypatch):
    """A model whose last split no bin passes (border rank 254: `bin > 254`
    never holds): the scores agree with the reference of that model and the
    sample is refused, because bit 5 is never set and half of every tree's
    leaves are out of reach."""
    job = oblivious_job
    tables = dict(job.tables)
    tables["split_bin"] = job.tables["split_bin"].copy()
    tables["split_bin"][:, -1] = job.shapes["n_bins"] - 1
    monkeypatch.setattr(job, "tables", tables)
    answer = reference_with(job)
    lines = failed(job.check([answer], answer))
    assert any(BITS in line for line in lines)
    assert any(SHARE in line for line in lines)
    assert not any(GAP in line for line in lines)


def test_a_corner_of_the_bin_box_cannot_pass(oblivious_job, monkeypatch):
    """Rows that all sit in bin 0: no bit is ever set and every tree is
    held to its leaf 0 alone."""
    job = oblivious_job
    Xb = np.zeros_like(job.Xb)
    monkeypatch.setattr(job, "Xb", Xb)
    answer = reference_with(job, Xb=Xb)
    lines = failed(job.check([answer], answer))
    assert any(BITS in line for line in lines)
    assert any(SHARE in line for line in lines)
    assert not any(GAP in line for line in lines)


def test_the_question_is_asked_before_any_row_is_drawn(oblivious_job,
                                                       monkeypatch):
    """A program whose span does not say select_columns_per_tree 6 (an
    expansion says 63), and one with no oblivious layout at all:
    SystemExit out of `setup`, and `uniform_bins` never called."""
    import datagen
    from ddt_tpu.models import tree
    from jobs import score_oblivious

    job = oblivious_job
    monkeypatch.setattr(datagen, "uniform_bins", lambda *a: pytest.fail(
        "rows drawn before the what-ran question was answered"))
    fresh = score_oblivious.Job(job.cell, seed=5, rehearse=False, control={})
    monkeypatch.setattr(fresh, "_what_ran", lambda: [
        ("the program's record says the oblivious form serves the layout as "
         "it is", {"oblivious": None, "select_columns_per_tree": 63}, True,
         False)])
    with pytest.raises(SystemExit, match="no Pallas kernel serves"):
        fresh.setup()

    monkeypatch.delattr(tree, "ObliviousEnsemble")
    with pytest.raises(SystemExit, match="has no oblivious layout"):
        score_oblivious.Job(job.cell, seed=5, rehearse=False,
                            control={}).setup()
    # and it asks nothing about tiling
    said = str(job._what_ran())
    assert "select_columns_per_tree" in said and SAID in said
    for tiling in ("oblivious_mxu_tiles_per_tree", "trees_per_step",
                   "table_blocks", "select_k_blocks"):
        assert tiling not in said.split("ddt:predict:ensemble")[0]


def test_a_control_run_keeps_its_key_out_of_the_programs_config(
        oblivious_job):
    from jobs import score_oblivious

    control = {"patched_table": "next_feature"}
    j = score_oblivious.Job(oblivious_job.cell, seed=6, rehearse=True,
                            control=control)
    assert j.patch == "next_feature" and control      # run.py's is whole
    assert not hasattr(j.cfg, "patched_table")
    j.setup()
    moved = j.ens.split_feature[:, 0] != j.tables["split_feature"][:, 0]
    assert moved.all()
    assert np.array_equal(j.ens.split_feature[:, 1:],
                          j.tables["split_feature"][:, 1:])
    with pytest.raises(ValueError, match="unknown control"):
        reference_oblivious.patched(j.tables, "no_such_control")


def config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(ROOT, files["epsilon-catboost-8000t-d6"])) as f:
        return json.load(f)


def test_configuration_keeps_the_sources_widths():
    """The CatBoost paper's applier comparison on Epsilon: 8000 trees,
    depth 6, 2000 columns, 254 borders (255 bins); `rows` is the one key
    that differs, and it is raised."""
    cfg = config()
    s = cfg["shapes"]
    assert (s["n_trees"], s["depth"], s["features"], s["n_bins"]) == (
        8000, 6, 2000, 255)
    assert list(cfg["reduced"]) == ["rows"]
    assert s["rows"] >= 1_200_000 and s["rows"] % 100_000 == 0
    lim = cfg["check"]
    assert (lim["bit_share_min"], lim["bit_share_max"]) == (0.25, 0.75)
    assert 0.5 < lim["leaf_share_min"] < 0.95
    assert lim["sample_rows"] == 50_000
    assert "TO BE FILLED" not in lim["readings"]
    assert "PEAK_BYTES" not in cfg["reduced"]["rows"]


def test_traverse_call_oblivious():
    """The hand number (ISSUE 39): 2 x 1,200,000 x 8000 x 6 x (2000 + 64) =
    2.378e14 operations, 1.207 s at 197 TFLOP/s; the shipped kernel's own
    matmuls (8064 lanes of trees, 2048 columns, no resolve dot) are 99.98%
    of that, the highest share it can read."""
    shapes = config()["shapes"]
    ops, nbytes = opcount_oblivious.traverse_call_oblivious(shapes)
    R = shapes["rows"]
    assert ops == 2.0 * R * 8000 * 6 * 2064
    assert ops / R == pytest.approx(2.378e14 / 1.2e6, rel=1e-3)
    assert nbytes == R * (2000 + 4) + 8000 * (6 * 8 + 64 * 4)
    assert nbytes / 819e9 < 0.01 * ops / 197e12       # bound by compute
    kernel = 2.0 * R * (63 * 128) * 6 * (16 * 128)
    assert kernel / ops == pytest.approx(1.0002, abs=1e-4)
    assert ops / kernel < 1.0
    small = {"rows": 10, "features": 3, "n_trees": 2, "depth": 1,
             "n_bins": 255}
    assert opcount_oblivious.traverse_call_oblivious(small) == (
        2.0 * 10 * 2 * 1 * 5, 10 * 3 + 40 + 2 * (8 + 8))


def test_oblivious_trees_are_the_seeds_and_a_trees_questions_differ():
    a = datagen_oblivious.oblivious_trees(400, 6, 2000, 255, 4000000007, 0.5)
    b = datagen_oblivious.oblivious_trees(400, 6, 2000, 255, 4000000007, 0.5)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])        # the seed is the data
    c = datagen_oblivious.oblivious_trees(400, 6, 2000, 255, 5, 0.5)
    assert not np.array_equal(a["split_feature"], c["split_feature"])
    assert a["split_feature"].dtype == a["split_bin"].dtype == np.int32
    assert a["leaf_value"].dtype == np.float32
    assert a["leaf_value"].shape == (400, 64)
    assert 0 <= a["split_feature"].min() and a["split_feature"].max() < 2000
    # 254 borders a column: ranks 0 .. 253, so every bit can go both ways
    assert 0 <= a["split_bin"].min() and a["split_bin"].max() <= 253
    assert abs(a["leaf_value"].std() - 0.5) < 0.01
    # few features, few borders: repeats are drawn again until a tree's 6
    # questions differ
    d = datagen_oblivious.oblivious_trees(300, 6, 3, 4, 7, 1.0)
    pair = d["split_feature"].astype(np.int64) * 4 + d["split_bin"]
    assert all(len(set(p)) == 6 for p in pair.tolist())


def test_no_chip_no_result_line(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", cell_of(TRAFFIC), "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc == 1
    assert not any(line.startswith("{") for line in out.splitlines())
