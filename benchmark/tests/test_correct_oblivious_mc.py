"""The oblivious multiclass job kind (`jobs/score_oblivious_mc.py`): `correct`
has to come out FALSE for each control of the configuration (bfloat16 leaf
values, `leaf_values` read class-major, `>=` for `>`, the index built
high-bit-first, one class's bias dropped, the margins handed back for the
probabilities), whether the control's answer is put in the program's place
or the program is handed the control's model (`--set patched_table`), for a
sample with a dead bit, a corner of the bin box or a dead class column, and
for broken answers; TRUE when sound. And `opcount_oblivious_mc.py` against
the hand number, `datagen_oblivious_mc.py` against its own contract, and the
cell's metrics counted BY NAME (no position and no list length pinned: a
later PR appends after them).

The whole-run cases drive run.py but for the look for a chip (`--rehearse`:
CPU, the configuration's "rehearse" sizes, kernels interpreted) and read the
verdict it prints. The controls' readings at the cell's own size are in the
configuration's file and in PERF.md.
"""

import importlib
import json
import os

import numpy as np
import pytest

import datagen_oblivious_mc
import opcount_oblivious_mc
import reference_oblivious_mc
import run
from test_correct import break_score, cell_of, verdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = "score_oblivious_mc"
CELL = {"name": "covtype-catboost1000t-d6-mc-score-1chip",
        "config": "covtype-catboost-1000t-d6-mc",
        "traffic": "score_oblivious_mc", "chips": 1}
CONFIG = {"name": "covtype-catboost-1000t-d6-mc",
          "file": "benchmark/configs/covtype-catboost-1000t-d6-mc.json"}
GAP, SUM, SHARE, BITS, WINS, SAID = (
    "vs the float64 reference", "class probabilities summed",
    "share of the model's", "one of the bit positions is set",
    "is the argmax of the call's answer", "the program's record says")
# the per-layer metrics the cell reports, and which end-to-end metric each
# moves
NEEDED = {
    "traverse_kernel_ms_per_call", "score_prologue_ms",
    "score_upload_exposed_ms", "score_fetch_tail_ms",
    "score_tables_streamed_mb", "score_widen_ms", "score_accumulate_ms",
    "score_other_device_ms", "score_unscoped_device_ms",
    "score_select_k_blocks", "score_select_columns_per_tree",
    "score_link_ms", "score_upload_wait_ms", "score_host_unnamed_ms",
    "traverse_oblivious_mc_roofline", "score_resolve_selects_per_tree"}
NEEDED_SETUP = {"score_first_call_extra_ms", "setup_ensemble_ms"}


def listed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_correct_separates_sound_from_broken_and_patched(capsys, monkeypatch):
    assert cell_of(TRAFFIC) == CELL["name"]
    # the Epsilon cell's traffic still finds its ONE cell
    assert [w["name"] for w in listed()["workloads"]
            if w["traffic"] == "score_oblivious"] == [
                "epsilon-catboost8000t-d6-score-1chip"]
    assert verdict(capsys, TRAFFIC) is True
    # the program handed a control's model (or asked for the margins), the
    # answer held to the right one: a CONTROL run, never a result line
    for control in ("class_major_leaves", "no_link", "drop_bias"):
        assert verdict(capsys, TRAFFIC, "--set",
                       f'patched_table="{control}"') is False
    break_score(monkeypatch)
    assert verdict(capsys, TRAFFIC) is False


@pytest.fixture(scope="module")
def oblivious_mc_job():
    """The cell's job at its rehearsal size, set up once, with the sound
    answer of one call. (A name of its own: tests/test_benchmark_suite.py
    gathers every module's fixtures into one namespace.)"""
    import jax

    from jobs import score_oblivious_mc

    jax.config.update("jax_platforms", "cpu")
    cell = run.resolve_cell(listed(), CELL["name"])
    j = score_oblivious_mc.Job(cell, seed=4000000007, rehearse=True,
                               control={})
    j.setup()
    j.sound = j.one_job()
    return j


def failed(checks: list) -> list:
    return [what for what, _, _, ok in checks if not ok]


def reference_with(job, control=None, Xb=None, model=None):
    """The reference's answer over the whole batch (with ONE thing changed
    where `control` names it), as the program's float32 [rows, classes]."""
    model = reference_oblivious_mc.patched(
        job.model if model is None else model, control)
    m, _ = reference_oblivious_mc.margins(model,
                                          job.Xb if Xb is None else Xb)
    return (m if control == "no_link"
            else reference_oblivious_mc.softmax(m)).astype(np.float32)


def test_sound_answer_passes_every_line(oblivious_mc_job):
    job = oblivious_mc_job
    assert job.sound.shape == (job.shapes["rows"], 7)
    assert failed(job.check([job.sound], job.sound)) == []
    # and the reference itself, in float32, is inside the limits
    assert failed(job.check([reference_with(job)] * 2,
                            reference_with(job))) == []


@pytest.mark.parametrize("control", reference_oblivious_mc.CONTROLS)
def test_control_fails_the_probability_limit(oblivious_mc_job, control):
    job = oblivious_mc_job
    answer = reference_with(job, control)
    lines = failed(job.check([answer], answer))
    assert any(GAP in line for line in lines)
    # the margins for the probabilities do not sum to 1 either; no control
    # fails a line of the SAMPLE's (its bits and leaves are the right
    # model's; at the rehearsal's 130 trees a wrong model may starve a
    # class of wins besides)
    assert (control == "no_link") == any(SUM in line for line in lines)
    assert not any(BITS in line or SHARE in line for line in lines)
    assert len(lines) <= 2 + (control == "no_link")
    gap = np.abs(answer.astype(np.float64) - job.sound).max()
    # the routing controls miss by whole leaves; bfloat16 leaves by their
    # rounding, which at the rehearsal's 130 trees is a third of what the
    # cell's 1000 sum to (the configuration's file has that reading)
    assert gap > (1.0 if control == "bfloat16_leaves" else 100.0
                  ) * job.limits["proba_atol"]


def test_the_importer_reads_what_the_reference_walks(oblivious_mc_job):
    """The dict the program's importer was handed is the dict the reference
    walks: the host walk of the imported model gives the reference's
    margins, and a class-major reading of `leaf_values` does not."""
    job = oblivious_mc_job
    want, _ = reference_oblivious_mc.margins(job.model, job.Xb)
    got = job.ens.predict_raw(job.Xb, binned=True)
    assert got.shape == want.shape == (job.shapes["rows"], 7)
    assert np.abs(got - want).max() < 1e-5
    assert job.ens.loss == "softmax" and job.ens.n_classes == 7
    L = 1 << job.shapes["depth"]
    first = np.asarray(job.model["oblivious_trees"][0]["leaf_values"])
    assert np.array_equal(job.ens.leaf_value[0],
                          first.reshape(L, 7).astype(np.float32))
    wrong = reference_oblivious_mc.patched(job.model, "class_major_leaves")
    assert np.array_equal(
        np.asarray(wrong["oblivious_trees"][0]["leaf_values"]).reshape(L, 7),
        first.reshape(L, 7).T.reshape(L, 7))
    with pytest.raises(ValueError, match="unknown control"):
        reference_oblivious_mc.patched(job.model, "no_such_control")
    assert reference_oblivious_mc.patched(job.model, None) is job.model


def test_a_dead_bit_cannot_pass(oblivious_mc_job, monkeypatch):
    """A model whose last split no row passes (its border the column's
    last, and rows that stop below it): the answers agree with the
    reference of that model and the sample is refused, because bit 5 is
    never set and half of every tree's leaves are out of reach."""
    job = oblivious_mc_job
    model = json.loads(json.dumps(job.model))
    top = datagen_oblivious_mc.border_of(job.shapes["n_bins"] - 2)
    for tree in model["oblivious_trees"]:
        tree["splits"][-1]["border"] = float(top)
    Xb = np.minimum(job.Xb, job.shapes["n_bins"] - 2)
    monkeypatch.setattr(job, "model", model)
    monkeypatch.setattr(job, "Xb", Xb)
    answer = reference_with(job, Xb=Xb, model=model)
    lines = failed(job.check([answer], answer))
    assert any(BITS in line for line in lines)
    assert any(SHARE in line for line in lines)
    assert not any(GAP in line for line in lines)


def test_a_corner_of_the_bin_box_cannot_pass(oblivious_mc_job, monkeypatch):
    """Rows that all sit in bin 0: no bit is ever set, every tree is held
    to its leaf 0 alone, and one class wins every row."""
    job = oblivious_mc_job
    Xb = np.zeros_like(job.Xb)
    monkeypatch.setattr(job, "Xb", Xb)
    answer = reference_with(job, Xb=Xb)
    lines = failed(job.check([answer], answer))
    assert any(BITS in line for line in lines)
    assert any(SHARE in line for line in lines)
    assert any(WINS in line for line in lines)
    assert not any(GAP in line for line in lines)


def test_a_dead_class_column_cannot_pass(oblivious_mc_job):
    """An answer whose last class never wins (its column pushed down, the
    rows renormalised) fails the argmax floor beside the gap."""
    job = oblivious_mc_job
    answer = reference_with(job).astype(np.float64)
    answer[:, -1] *= 1e-6
    answer = (answer / answer.sum(axis=1, keepdims=True)).astype(np.float32)
    lines = failed(job.check([answer], answer))
    assert any(WINS in line for line in lines)
    assert any(GAP in line for line in lines)
    assert not any(SUM in line for line in lines)


def test_the_question_is_asked_before_any_row_is_drawn(oblivious_mc_job,
                                                       monkeypatch):
    """A program whose span does not say leaf_columns 7 and link softmax,
    and one whose importer turns vector leaves away: SystemExit out of
    `setup`, and `uniform_bins` never called."""
    import datagen
    from ddt_tpu.models import catboost_io
    from jobs import score_oblivious_mc

    job = oblivious_mc_job
    monkeypatch.setattr(datagen, "uniform_bins", lambda *a: pytest.fail(
        "rows drawn before the what-ran question was answered"))
    fresh = score_oblivious_mc.Job(job.cell, seed=5, rehearse=False,
                                   control={})
    monkeypatch.setattr(fresh, "_what_ran", lambda: [
        ("the program's record says the oblivious form serves the vector "
         "leaves as they are", {"oblivious": 1, "leaf_columns": None,
                                "link": None,
                                "select_columns_per_tree": 6}, True, False)])
    with pytest.raises(SystemExit, match="no Pallas kernel serves"):
        fresh.setup()

    def refuse(*a, **kw):
        raise ValueError("from_catboost_json: the model carries vector "
                         "leaves")
    monkeypatch.setattr(catboost_io, "from_catboost_json", refuse)
    with pytest.raises(SystemExit, match="imports no CatBoost model of "
                                         "vector leaves"):
        score_oblivious_mc.Job(job.cell, seed=5, rehearse=False,
                               control={}).setup()
    # and it asks nothing about tiling, or about the unit that resolves
    said = str(job._what_ran())
    assert "leaf_columns" in said and SAID in said
    for tiling in ("oblivious_mxu_tiles_per_tree", "trees_per_step",
                   "table_blocks", "select_k_blocks",
                   "resolve_selects_per_tree", "resolves_under_select"):
        assert tiling not in said.split("ddt:predict:ensemble")[0]


def test_a_control_run_keeps_its_key_out_of_the_programs_config(
        oblivious_mc_job):
    from jobs import score_oblivious_mc

    control = {"patched_table": "high_bit_first"}
    j = score_oblivious_mc.Job(oblivious_mc_job.cell, seed=6, rehearse=True,
                               control=control)
    assert j.patch == "high_bit_first" and control      # run.py's is whole
    assert not hasattr(j.cfg, "patched_table")
    j.setup()
    right = score_oblivious_mc.Job(oblivious_mc_job.cell, seed=6,
                                   rehearse=True, control={})
    right.setup()
    assert np.array_equal(j.ens.split_feature,
                          right.ens.split_feature[:, ::-1])
    assert np.array_equal(j.ens.leaf_value, right.ens.leaf_value)


def config():
    files = {c["name"]: c["file"] for c in listed()["configs"]}
    with open(os.path.join(ROOT, files[CONFIG["name"]])) as f:
        return json.load(f)


def test_configuration_keeps_the_sources_widths():
    """The library's documented defaults over Covertype: 1000 trees, depth
    6, 254 borders (255 bins), 54 columns, 7 classes; `rows` is the one key
    that differs, and it is raised."""
    cfg = config()
    s = cfg["shapes"]
    assert (s["n_trees"], s["depth"], s["features"], s["n_bins"],
            s["n_classes"]) == (1000, 6, 54, 255, 7)
    assert list(cfg["reduced"]) == ["rows"]
    assert s["rows"] >= 32_000_000 and s["rows"] % 2_000_000 == 0
    lim = cfg["check"]
    assert (lim["bit_share_min"], lim["bit_share_max"]) == (0.25, 0.75)
    assert 0.5 < lim["leaf_share_min"] < 0.95
    assert 0 < lim["argmax_share_min"] < 1 / 7
    assert lim["sample_rows"] == 50_000
    assert 1e-6 <= lim["proba_atol"] <= 1e-4
    assert "TO BE FILLED" not in lim["readings"]
    assert "TO BE FILLED" not in cfg["reduced"]["rows"]
    assert cfg["train_config"] == {"n_bins": 255, "backend": "tpu"}


def test_traverse_call_oblivious_mc():
    """The hand number (ISSUE 57): 2 x (6 x (54 + 64) + 64 x 7) = 2,312
    operations a (row, tree); 32M rows x 1000 trees: 7.398e13, 0.376 s at
    197 TFLOP/s, and the bytes (1.73 GB in, 0.90 GB out, 1.8 MB of model)
    3.2 ms at 819 GB/s: bound by compute."""
    shapes = config()["shapes"]
    ops, nbytes = opcount_oblivious_mc.traverse_call_oblivious_mc(shapes)
    R = shapes["rows"]
    assert ops == 2.0 * R * 1000 * (6 * 118 + 448)
    assert ops / R / 1000 == 2312
    assert nbytes == R * 54 + 4 * R * 7 + 1000 * (48 + 4 * 7 * 64)
    assert nbytes / 819e9 < 0.02 * ops / 197e12       # bound by compute
    # the Epsilon count's rule plus the leaf product of a vector leaf
    import opcount_oblivious

    one = dict(shapes, n_classes=0)
    assert opcount_oblivious_mc.traverse_call_oblivious_mc(one)[0] == \
        opcount_oblivious.traverse_call_oblivious(shapes)[0]
    small = {"rows": 10, "features": 3, "n_trees": 2, "depth": 1,
             "n_classes": 3, "n_bins": 255}
    assert opcount_oblivious_mc.traverse_call_oblivious_mc(small) == (
        2.0 * 10 * 2 * (5 + 6), 10 * 3 + 120 + 2 * (8 + 24))
    # the ceiling of the docstring: 441 selects at four a cycle
    assert 441 == 7 * 63
    assert 1024 * 4 * 1.5e9 / 441 / (197e12 / 2312) == pytest.approx(
        0.163, abs=0.002)


def test_the_drawn_model_is_the_seeds_and_in_the_exports_layout():
    draw = datagen_oblivious_mc.drawn_model
    a = draw(40, 6, 54, 255, 7, 4000000007, 0.5, 0.25)
    assert a == draw(40, 6, 54, 255, 7, 4000000007, 0.5, 0.25)
    c = draw(40, 6, 54, 255, 7, 5, 0.5, 0.25)
    assert a["oblivious_trees"] != c["oblivious_trees"]
    assert json.loads(json.dumps(a)) == a               # plain JSON
    assert a["model_info"]["params"]["loss_function"]["type"] == "MultiClass"
    assert len(a["features_info"]["float_features"]) == 54
    borders = a["features_info"]["float_features"][53]["borders"]
    assert borders == [k + 0.5 for k in range(254)]
    scale, bias = a["scale_and_bias"]
    assert scale == 1.0 and len(bias) == 7
    leaves = np.array([t["leaf_values"] for t in a["oblivious_trees"]])
    assert leaves.shape == (40, 64 * 7)
    assert abs(leaves.std() - 0.5) < 0.02
    assert np.array_equal(leaves, leaves.astype(np.float32))  # float32 values
    for t in a["oblivious_trees"]:
        assert len(t["splits"]) == 6 and len(t["leaf_weights"]) == 64
        pairs = {(sp["float_feature_index"], sp["border"])
                 for sp in t["splits"]}
        assert len(pairs) == 6
        for sp in t["splits"]:
            assert sp["split_type"] == "FloatFeature"
            assert 0 <= sp["float_feature_index"] < 54
            assert sp["border"] in borders
    # few features, few borders: repeats are drawn again until a tree's
    # questions differ
    d = draw(300, 6, 3, 4, 2, 7, 1.0, 1.0)
    for t in d["oblivious_trees"]:
        assert len({(sp["float_feature_index"], sp["border"])
                    for sp in t["splits"]}) == 6


def test_mc_metrics_are_counted_by_name(oblivious_mc_job):
    """The per-layer metrics the cell needs, by NAME and by no position:
    each is in the benchmark with a file and a reader that is there, and
    lists the cell (a later PR may list it on more); the cell reports
    `score_mrows_per_s` and `setup_s`; its configuration and its one entry
    of `workloads` are found by name. The root span of a call carries what
    the counts' readers read, and the roofline's count is the cell's own
    shapes'."""
    from ddt_tpu.telemetry.annotations import recent_spans

    manifest = listed()
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    here = os.path.join(ROOT, "benchmark", "layer_metrics")
    cell = CELL["name"]
    for name in NEEDED | NEEDED_SETUP:
        assert cell in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == (
            "setup_s" if name in NEEDED_SETUP else "score_mrows_per_s")
        with open(os.path.join(here, name + ".json")) as f:
            spec = json.load(f)
        assert callable(importlib.import_module(
            "readers." + spec["reader"]).read)
    reported = {m["name"] for m in run.metrics_of(manifest, "per_layer",
                                                  cell)}
    assert NEEDED | NEEDED_SETUP <= reported
    # its count has no class product: not the Epsilon cell's roofline
    assert "traverse_oblivious_roofline" not in reported
    end_to_end = {m["name"] for m in run.metrics_of(manifest, "end_to_end",
                                                    cell)}
    assert end_to_end == {"score_mrows_per_s", "setup_s"}
    mine, = [w for w in manifest["workloads"] if w["name"] == cell]
    assert {k: mine[k] for k in CELL} == CELL
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG["name"]]
    assert entry["file"] == CONFIG["file"] and entry["reduced"] == ["rows"]
    assert len(entry["source"]) <= 200 and len(mine["why"]) <= 200
    own = per_layer["score_resolve_selects_per_tree"]
    assert (own["unit"], own["better"], own["source"], own["workloads"]) == (
        "selects", "lower", "program_counter", [cell])
    with open(os.path.join(here, own["name"] + ".json")) as f:
        assert json.load(f) == {"reader": "root_count", "args": {
            "count": "resolve_selects_per_tree"}}
    roof = per_layer["traverse_oblivious_mc_roofline"]
    assert (roof["unit"], roof["better"], roof["source"],
            roof["workloads"]) == ("%", "higher", "device_trace", [cell])
    with open(os.path.join(here, roof["name"] + ".json")) as f:
        assert json.load(f) == {"reader": "roofline_share", "args": {
            "match": "^%ddt_predict_traverse",
            "opcount_module": "opcount_oblivious_mc",
            "opcount": "traverse_call_oblivious_mc"}}
    oblivious_mc_job.one_job()
    root = [sp for sp in recent_spans() if sp["name"] == "ddt:predict"][-1]
    counts = root["counts"]
    assert counts["oblivious"] == 1 and counts["leaf_columns"] == 7
    assert counts["link"] == "softmax" and counts["classes"] == 7
    assert counts["resolve_selects_per_tree"] == 441
    assert counts["select_columns_per_tree"] == 6
    assert counts["select_k_blocks"] == 1


def test_no_chip_no_result_line(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL["name"], "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc == 1
    assert not any(line.startswith("{") for line in out.splitlines())
