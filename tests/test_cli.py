"""CLI round-trips (layer L8) on the CPU platform."""

import json
import os

import numpy as np
import pytest

from ddt_tpu.cli import main


def _run(capsys, argv):
    rc = main(argv)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_cli_train_predict_roundtrip(tmp_path, capsys):
    model = str(tmp_path / "ens.npz")
    rec = _run(capsys, [
        "train", "--backend=cpu", "--dataset=higgs", "--rows=2000",
        "--trees=4", "--depth=3", "--bins=31", f"--out={model}",
    ])
    assert rec["trees"] == 4 and rec["backend"] == "cpu"
    assert rec["final_train_loss"] < 0.693  # below chance for logloss
    # Results name their device: the NumPy backend computes on the host.
    host = {"platform": "cpu", "device_kind": "host", "n_devices": 1}
    assert {k: rec[k] for k in host} == host

    scores = str(tmp_path / "scores.npy")
    rec = _run(capsys, [
        "predict", "--backend=cpu", f"--model={model}",
        "--dataset=higgs", "--rows=500", "--bins=31", f"--out={scores}",
    ])
    assert rec["rows"] == 500
    assert {k: rec[k] for k in host} == host
    s = np.load(scores)
    assert s.shape == (500,) and (0 <= s).all() and (s <= 1).all()


def test_cli_train_tpu_backend_with_partitions(tmp_path, capsys):
    """The [BASELINE] flag surface: same command, different --backend, and
    a 4-partition run on the virtual device mesh."""
    model = str(tmp_path / "ens.npz")
    rec = _run(capsys, [
        "train", "--backend=tpu", "--dataset=higgs", "--rows=2000",
        "--trees=3", "--depth=3", "--bins=31", "--partitions=4",
        f"--out={model}",
    ])
    assert rec["backend"] == "tpu"
    # ... and the XLA backend's stamp is JAX's own account (8 virtual CPU
    # devices here, conftest) — a CPU run can never read as a chip run.
    assert (rec["platform"], rec["device_kind"], rec["n_devices"]) == \
        ("cpu", "cpu", 8)


def test_cli_train_feature_partitions_and_early_stop(tmp_path, capsys):
    out = str(tmp_path / "m.npz")
    rc = main([
        "train", "--backend=tpu", "--dataset=higgs", "--rows=2000",
        "--bins=31", "--trees=12", "--depth=3", "--partitions=2",
        "--feature-partitions=2", "--out", out,
        "--valid-frac=0.2", "--metric=auc", "--early-stop=8",
    ])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["best_round"] >= 1
    assert 0.5 < rec["best_score"] <= 1.0
    assert rec["trees"] <= 12


def test_cli_covertype_softmax(tmp_path, capsys):
    model = str(tmp_path / "cov.npz")
    rec = _run(capsys, [
        "train", "--backend=cpu", "--dataset=covertype", "--rows=1500",
        "--trees=2", "--depth=3", "--bins=31", f"--out={model}",
    ])
    from ddt_tpu.models.tree import TreeEnsemble

    ens = TreeEnsemble.load(model)
    assert ens.loss == "softmax" and ens.n_classes == 7
    assert ens.n_trees == 2 * 7  # rounds x classes


def test_cli_criteo_categoricals(tmp_path, capsys):
    rec = _run(capsys, [
        "train", "--backend=cpu", "--dataset=criteo", "--rows=2000",
        "--trees=2", "--depth=3", "--bins=100",
        f"--out={tmp_path / 'c.npz'}",
    ])
    assert rec["final_train_loss"] < 0.60  # ~25% CTR base rate entropy

def test_cli_fpga_backend_fails_loudly(tmp_path):
    with pytest.raises(NotImplementedError, match="FPGA"):
        main([
            "train", "--backend=fpga", "--dataset=higgs", "--rows=100",
            "--trees=1", "--depth=2", "--bins=15",
            f"--out={tmp_path / 'x.npz'}",
        ])


def test_cli_inspect(tmp_path, capsys):
    model = str(tmp_path / "ens.npz")
    _run(capsys, [
        "train", "--backend=cpu", "--dataset=higgs", "--rows=2000",
        "--trees=4", "--depth=3", "--bins=31", f"--out={model}",
    ])
    rc = main(["inspect", f"--model={model}", "--tree=0",
               "--importance=gain"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[0])
    assert rec["n_trees"] == 4 and rec["n_splits"] > 0
    assert rec["top_features_by_gain"]
    # The tree dump follows: root line mentions a feature split or a leaf.
    assert out[1].startswith(("f", "leaf="))


def test_cli_train_streaming(tmp_path, capsys):
    """--stream-chunks trains via the streaming path (BASELINE config 5
    from the CLI): streamed quantizer fit + per-chunk accumulation, model
    artifact complete (mapper included), trees identical to an in-memory
    run on the same mapper's bins."""
    from ddt_tpu import api
    from ddt_tpu.backends import get_backend
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.driver import Driver

    model = str(tmp_path / "s.npz")
    rec = _run(capsys, [
        "train", "--backend=cpu", "--rows=4000", "--trees=4", "--depth=3",
        "--bins=31", "--stream-chunks=4", f"--out={model}",
    ])
    assert rec["streamed_chunks"] == 4 and rec["trees"] == 4
    b = api.load_model(model)
    assert b.mapper is not None

    # identical to in-memory training on the streamed mapper's bins
    from ddt_tpu.data.datasets import synthetic_binary

    X, y = synthetic_binary(4000, seed=0)
    cfg = TrainConfig(n_trees=4, max_depth=3, n_bins=31, backend="cpu")
    full = Driver(get_backend(cfg), cfg, log_every=10**9).fit(
        b.mapper.transform(X), y)
    np.testing.assert_array_equal(full.feature, b.ensemble.feature)

    # guard: early stopping still needs a validation split
    with pytest.raises(SystemExit, match="valid-frac"):
        main(["train", "--backend=cpu", "--rows=1000", "--trees=2",
              "--stream-chunks=2", "--early-stop=2"])


def test_cli_train_streaming_validation(tmp_path, capsys):
    """--stream-chunks composes with --valid-frac/--early-stop (round-2
    verdict item 3): held-out rows streamed as validation chunks, metric
    per round, best_round/best_score in the summary."""
    model = str(tmp_path / "s.npz")
    rec = _run(capsys, [
        "train", "--backend=cpu", "--rows=3000", "--trees=25", "--depth=3",
        "--bins=31", "--stream-chunks=3", "--valid-frac=0.25",
        "--metric=auc", "--early-stop=3", "--lr=0.9", f"--out={model}",
    ])
    assert rec["best_round"] >= 1
    assert 0.5 < rec["best_score"] <= 1.0
    # early stop truncated: trees == best_round (binary: 1 tree/round)
    assert rec["trees"] == rec["best_round"]
    assert rec["trees"] < 25


def test_cli_config_file(tmp_path, capsys):
    """--config overlays TrainConfig fields from YAML/JSON onto the flag-
    built config (file wins for fields it names; unknown keys fail)."""
    from ddt_tpu.config import TrainConfig

    yml = tmp_path / "c.yaml"
    yml.write_text("n_trees: 5\nmax_depth: 3\nreg_lambda: 2.5\n")
    model = str(tmp_path / "m.npz")
    rec = _run(capsys, [
        "train", "--backend=cpu", "--rows=1000", "--trees=99", "--bins=31",
        f"--config={yml}", f"--out={model}",
    ])
    assert rec["trees"] == 5 and rec["depth"] == 3   # file beat --trees=99

    js = tmp_path / "c.json"
    js.write_text('{"n_trees": 4, "learning_rate": 0.2}')
    rec = _run(capsys, [
        "train", "--backend=cpu", "--rows=1000", "--bins=31",
        f"--config={js}", f"--out={model}",
    ])
    assert rec["trees"] == 4

    bad = tmp_path / "bad.json"
    bad.write_text('{"n_treez": 4}')
    with pytest.raises(SystemExit, match="n_treez"):
        main(["train", "--backend=cpu", "--rows=500", f"--config={bad}"])
    with pytest.raises(SystemExit, match="config"):
        main(["train", "--backend=cpu", "--rows=500",
              "--config=/nonexistent.yaml"])

    # the library surface
    c = TrainConfig.from_file(str(yml))
    assert (c.n_trees, c.max_depth, c.reg_lambda) == (5, 3, 2.5)


def test_cli_config_file_syncs_pipeline_fields(tmp_path, capsys):
    """File-set fields that feed dataset loading / guards apply BEFORE the
    load: backend is reported truthfully, and file-set bagging streams
    (round 5 — the old streaming-vs-sampling rejection is gone; the
    counter-based masks made the combination exact)."""
    js = tmp_path / "c.json"
    js.write_text('{"backend": "cpu", "n_trees": 3, "seed": 7}')
    model = str(tmp_path / "m.npz")
    rec = _run(capsys, [
        "train", "--backend=tpu", "--rows=800", "--bins=31",
        f"--config={js}", f"--out={model}",
    ])
    assert rec["backend"] == "cpu"      # the file's backend, not the flag

    bag = tmp_path / "bag.yaml"
    bag.write_text("subsample: 0.5\nn_trees: 3\n")
    model2 = str(tmp_path / "bagged.npz")
    rec = _run(capsys, [
        "train", "--backend=cpu", "--rows=800", "--bins=31",
        "--stream-chunks=2", f"--config={bag}", f"--out={model2}",
    ])
    assert rec["streamed_chunks"] == 2
    assert os.path.exists(model2)
    # --profile composes with streaming since the telemetry PR
    # (fit_streaming wires its own PhaseTimer); the XLA trace capture
    # remains in-memory-only.
    with pytest.raises(SystemExit, match="trace-dir"):
        main(["train", "--backend=cpu", "--rows=800", "--bins=31",
              "--stream-chunks=2", "--trace-dir", str(tmp_path / "tr")])
    model3 = str(tmp_path / "profiled.npz")
    rec = _run(capsys, [
        "train", "--backend=cpu", "--rows=800", "--bins=31",
        "--stream-chunks=2", "--profile", f"--config={bag}",
        f"--out={model3}",
    ])
    assert rec["streamed_chunks"] == 2
    assert os.path.exists(model3)
