"""The multiclass job kind (`jobs/score_mc.py`): `correct` has to come out
FALSE for swapped class columns, for bfloat16 leaves, and for the one-hot path
forced by `--set`; TRUE when sound. And `opcount_mc.py` against hand numbers.

Each whole-run case drives run.py but for the look for a chip (`--rehearse`:
CPU, the configuration's "rehearse" sizes, kernels interpreted) and reads the
verdict it prints. The controls' readings on the chip, at the cell's own size,
are in the configuration's file and in PERF.md.
"""

import json
import os

import numpy as np
import pytest

import opcount_mc
import run
from test_correct import cell_of, verdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def swap_classes(monkeypatch):
    """Every score right, in the wrong column: classes 0 and 1 swapped."""
    from ddt_tpu import api

    real = api.predict

    def predict(*a, **kw):
        out = np.array(real(*a, **kw))
        out[:, [0, 1]] = out[:, [1, 0]]
        return out

    monkeypatch.setattr(api, "predict", predict)


@pytest.mark.slow
def test_correct_separates_sound_from_broken(capsys, monkeypatch):
    assert verdict(capsys, "score_mc") is True
    # the XLA one-hot path scores right and is not what the cell measures
    assert verdict(capsys, "score_mc", "--set",
                   'predict_impl="onehot"') is False
    swap_classes(monkeypatch)
    assert verdict(capsys, "score_mc") is False


def config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(ROOT, files["covtype-3500t-d8"])) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [3, 2147483659, 4000000007])
def test_bfloat16_leaves_fail_the_score_limit(seed):
    """The control of the configuration's float32 leaves: the reference with
    its leaf values rounded to bfloat16, put in the program's place, misses
    the float64 reference by more than `score_atol` in EVERY class (the
    configuration's own ensemble; 2,000 sampled rows here, 50,000 in the
    configuration's readings)."""
    import datagen
    import reference_mc

    cfg = config()
    s, m = cfg["shapes"], cfg["model"]
    tables = datagen.random_full_trees(s["n_trees"], s["max_depth"],
                                       s["features"], s["n_bins"], seed)
    Xb = datagen.uniform_bins(2000, s["features"], s["n_bins"], seed)
    args = (s["max_depth"], m["learning_rate"], m["base_score"],
            s["n_classes"], Xb)
    want = reference_mc.raw_scores(tables, *args)
    assert want.shape == (2000, 7) and want.dtype == np.float64
    low = reference_mc.raw_scores(
        dict(tables, leaf_value=reference_mc.bfloat16(tables["leaf_value"])),
        *args)
    assert (np.abs(low - want).max(axis=0)
            > 10 * cfg["check"]["score_atol"]).all()
    # float32 arithmetic on float32 leaves, the stated precision, holds it
    assert np.abs(want.astype(np.float32) - want).max() \
        < cfg["check"]["score_atol"]
    # one class's trees in another's column is a different answer
    assert np.abs(np.roll(want, 1, axis=1) - want).max() > 1.0


def test_reference_mc_is_round_major():
    """Tree t scores class t % C: three hand-made stumps, two classes."""
    import reference_mc

    tables = {
        "feature": np.array([[0, -1, -1]] * 3, np.int32),
        "threshold_bin": np.array([[4, 0, 0]] * 3, np.int32),
        "is_leaf": np.array([[False, True, True]] * 3),
        "leaf_value": np.array([[0, 1, 2], [0, 10, 20], [0, 100, 200]],
                               np.float32),
    }
    Xb = np.array([[4], [5]], np.uint8)               # left, right
    got = reference_mc.raw_scores(tables, 1, 0.5, 1.0, 2, Xb)
    np.testing.assert_array_equal(got, [[1 + 0.5 * 101, 1 + 0.5 * 10],
                                        [1 + 0.5 * 202, 1 + 0.5 * 20]])


def test_traverse_call_mc_covertype_36m_rows():
    shapes = dict(config()["shapes"], rows=36 * 10 ** 6)
    ops, nbytes = opcount_mc.traverse_call_mc(shapes)
    # 2 x 36e6 x 54 x 3500 x 255 = 3.470e15: 17.6 s of matmul at 197 TFLOP/s
    assert ops == 2.0 * 36e6 * 54 * 3500 * 255
    assert ops == pytest.approx(3.470e15, rel=1e-3)
    assert ops / 197e12 == pytest.approx(17.61, rel=1e-3)
    # 54 B a row in, 28 B a row out, 3500 x 511 nodes x 13 B
    assert nbytes == 36 * 10 ** 6 * (54 + 28) + 3500 * 511 * 13
    # this formulation's ceiling: K = 54 of the MXU tile's 128 rows, 3500 of
    # the 3584 trees of 28 groups
    assert 54 / 128 * 3500 / 3584 == pytest.approx(0.412, rel=1e-3)


def test_traverse_call_mc_scales_and_ignores_the_layout():
    shapes = config()["shapes"]
    one = opcount_mc.traverse_call_mc(shapes)
    assert opcount_mc.traverse_call_mc(
        dict(shapes, rows=2 * shapes["rows"]))[0] == 2 * one[0]
    # nothing of the kernel's grouping, padding or table blocks is a term
    assert set(shapes) >= {"rows", "features", "n_trees", "n_classes",
                           "max_depth"}
    assert opcount_mc.traverse_call_mc(
        dict(shapes, groups_per_step=1, tree_group=64)) == one


def test_no_chip_no_result_line(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", cell_of("score_mc"), "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc == 1
    assert not any(line.startswith("{") for line in out.splitlines())
