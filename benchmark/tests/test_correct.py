"""`correct` has to come out FALSE when the timed path is broken underneath,
and for the lower-precision control of each job kind; TRUE when sound.

Each case drives run.py's whole run but for the look for a chip (`--rehearse`:
CPU, 1/100 of the rows, kernels interpreted) and reads the verdict it prints.
The controls' readings on the chip, at the cells' own sizes, are in PERF.md.
"""

import json
import os
import re

import numpy as np
import pytest

import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cell_of(traffic: str) -> str:
    """The first cell of the benchmark that runs this traffic."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        for w in json.load(f)["workloads"]:
            if w["traffic"] == traffic:
                return w["name"]
    raise LookupError(traffic)


def verdict(capsys, traffic: str, *extra: str) -> bool:
    assert run.main(["--workload", cell_of(traffic),
                     "--seed", "2147483659", "--seconds", "0.1",
                     "--trace", "0", "--rehearse", *extra]) == 0
    out = capsys.readouterr().out
    assert "REHEARSAL complete" in out
    assert not out.rstrip().splitlines()[-1].startswith("{")   # no result
    return re.search(r"correct=(True|False)", out).group(1) == "True"


def break_score(monkeypatch):
    """Answers altered where they are produced: one row in seven is off by
    0.01."""
    from ddt_tpu import api

    real = api.predict

    def predict(*a, **kw):
        out = np.array(real(*a, **kw))
        out[::7] += np.float32(0.01)
        return out

    monkeypatch.setattr(api, "predict", predict)


@pytest.mark.slow
@pytest.mark.parametrize("traffic,breaker,control", [
    ("score", break_score, 'predict_impl="lut4"'),
])
def test_correct_separates_sound_from_broken(capsys, monkeypatch, traffic,
                                             breaker, control):
    assert verdict(capsys, traffic) is True
    assert verdict(capsys, traffic, "--set", control) is False
    breaker(monkeypatch)
    assert verdict(capsys, traffic) is False


@pytest.mark.parametrize("seed", [3, 2147483659, 4000000007])
def test_bfloat16_leaves_fail_the_score_limit(seed):
    """The control of the configuration's float32 leaves: the reference with
    its leaf values rounded to bfloat16, put in the program's place, misses
    the float64 reference by more than `score_atol` (the configuration's own
    ensemble; 4,000 sampled rows here, 50,000 in PERF.md's readings)."""
    import datagen
    import reference

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cfg_file = json.load(f)["configs"][0]["file"]
    with open(os.path.join(ROOT, cfg_file)) as f:
        cfg = json.load(f)
    s, m = cfg["shapes"], cfg["model"]
    tables = datagen.random_full_trees(s["n_trees"], s["max_depth"],
                                       s["features"], s["n_bins"], seed)
    Xb = datagen.uniform_bins(4000, s["features"], s["n_bins"], seed)
    want = reference.raw_scores(tables, s["max_depth"], m["learning_rate"],
                                m["base_score"], Xb)
    bits = tables["leaf_value"].view(np.uint32)
    bf16 = ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)                                  # round to nearest even
    got = reference.raw_scores(dict(tables, leaf_value=bf16), s["max_depth"],
                               m["learning_rate"], m["base_score"], Xb)
    assert np.max(np.abs(got - want)) > 10 * cfg["check"]["score_atol"]
    # and float32 arithmetic on float32 leaves, the stated precision, holds it
    f32 = reference.raw_scores(tables, s["max_depth"], m["learning_rate"],
                               m["base_score"], Xb).astype(np.float32)
    assert np.max(np.abs(f32 - want)) < cfg["check"]["score_atol"]


def test_no_chip_no_result_line(capsys, monkeypatch):
    """Without a TPU the command exits non-zero and prints no result."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", cell_of("score"), "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc == 1
    assert not any(line.startswith("{") for line in out.splitlines())
