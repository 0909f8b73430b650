"""Backend-parity + Driver/API tests (SURVEY.md §4 "Backend parity").

The DeviceBackend contract test: CPUDevice and TPUDevice produce identical
ensembles on fixed seeds, driven through the SAME Driver. Also covers the
registry flag, the FPGA stub, checkpoint/resume, and the api.train surface.
"""

import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.backends import FPGADevice, get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.data import datasets
from ddt_tpu.data.quantizer import quantize
from ddt_tpu.driver import Driver


def _small_problem(rows=2000, loss="logloss", seed=0, bins=31):
    if loss == "softmax":
        X, y = datasets.synthetic_multiclass(rows, n_features=12, seed=seed)
    elif loss == "mse":
        X, y = datasets.synthetic_regression(rows, n_features=8, seed=seed)
    else:
        X, y = datasets.synthetic_binary(rows, n_features=10, seed=seed)
    Xb, mapper = quantize(X, n_bins=bins, seed=seed)
    return Xb, y, mapper


def _fit(backend_flag, Xb, y, **cfg_kw):
    cfg = TrainConfig(
        n_trees=5, max_depth=4, n_bins=31, backend=backend_flag, **cfg_kw
    )
    be = get_backend(cfg)
    return Driver(be, cfg, log_every=10**9).fit(Xb, y), cfg


@pytest.mark.parametrize("loss,extra", [
    ("logloss", {}),
    ("mse", {}),
    ("softmax", {"n_classes": 7}),
])
def test_backend_parity_cpu_vs_tpu(loss, extra):
    """The DeviceBackend contract: identical trees from both backends."""
    Xb, y, _ = _small_problem(loss=loss)
    ens_cpu, _ = _fit("cpu", Xb, y, loss=loss, **extra)
    ens_tpu, _ = _fit("tpu", Xb, y, loss=loss, **extra)

    np.testing.assert_array_equal(ens_cpu.feature, ens_tpu.feature)
    np.testing.assert_array_equal(ens_cpu.threshold_bin, ens_tpu.threshold_bin)
    np.testing.assert_array_equal(ens_cpu.is_leaf, ens_tpu.is_leaf)
    np.testing.assert_allclose(
        ens_cpu.leaf_value, ens_tpu.leaf_value, rtol=2e-4, atol=2e-5
    )


def test_backend_registry_flag():
    cfg = TrainConfig(backend="cpu")
    assert get_backend(cfg).name == "cpu"
    cfg = TrainConfig(backend="tpu")
    assert get_backend(cfg).name == "tpu"
    with pytest.raises(NotImplementedError, match="FPGA"):
        get_backend(TrainConfig(backend="fpga"))
    with pytest.raises(ValueError):
        TrainConfig(backend="cuda")


def test_granular_kernel_contract_via_backend():
    """build_histograms/best_splits through the L4 interface match the
    oracle — on both backends, including node_index -1 masking."""
    from ddt_tpu.reference import numpy_trainer as ref

    rng = np.random.default_rng(3)
    R, F, B, N = 512, 6, 16, 4
    Xb = rng.integers(0, B, size=(R, F), dtype=np.uint8)
    g = rng.standard_normal(R).astype(np.float32)
    h = rng.random(R).astype(np.float32)
    ni = rng.integers(-1, N, size=R).astype(np.int32)

    want_h = ref.build_histograms(Xb, g, h, ni, N, B)
    want_s = ref.best_splits(want_h, 1.0, 1e-3)

    for flag in ("cpu", "tpu"):
        be = get_backend(TrainConfig(backend=flag, n_bins=B))
        data = be.upload(Xb)
        got_h = np.asarray(be.build_histograms(data, g, h, ni, N))
        np.testing.assert_allclose(got_h, want_h, rtol=1e-5, atol=1e-5)
        gains, feats, bins = be.best_splits(got_h)
        np.testing.assert_array_equal(np.asarray(feats), want_s[1])
        np.testing.assert_array_equal(np.asarray(bins), want_s[2])


def test_api_train_predict_roundtrip(tmp_path):
    X, y = datasets.synthetic_binary(3000, n_features=10, seed=1)
    res = api.train(X, y, n_trees=10, max_depth=4, n_bins=31,
                    backend="tpu", log_every=10**9)
    assert res.ensemble.n_trees == 10
    assert res.ensemble.has_raw_thresholds

    p_np = api.predict(res.ensemble, X, mapper=res.mapper)
    auc_inputs = p_np[y == 1].mean() - p_np[y == 0].mean()
    assert auc_inputs > 0.1  # learned something

    # device predict path agrees with the NumPy oracle scorer
    be = get_backend(TrainConfig(backend="tpu", n_bins=31))
    Xb = res.mapper.transform(X)
    p_dev = api.predict(res.ensemble, Xb, binned=True, backend=be)
    np.testing.assert_allclose(p_dev, p_np, rtol=2e-4, atol=2e-5)

    # save/load roundtrip
    path = str(tmp_path / "ens.npz")
    res.ensemble.save(path)
    from ddt_tpu.models.tree import TreeEnsemble

    loaded = TreeEnsemble.load(path)
    np.testing.assert_array_equal(loaded.feature, res.ensemble.feature)


def test_checkpoint_resume_matches_uninterrupted(tmp_path):
    """SURVEY.md §5 fault tolerance: train 10 trees straight vs 6 + resume 4;
    the ensembles must match."""
    Xb, y, _ = _small_problem(rows=1500)
    cfg = TrainConfig(n_trees=10, max_depth=4, n_bins=31, backend="tpu")

    be = get_backend(cfg)
    full = Driver(be, cfg, log_every=10**9).fit(Xb, y)

    ck = str(tmp_path / "ck")
    # Phase 1: "crash" after 6 rounds (simulated by only running 6).
    be1 = get_backend(cfg.replace(n_trees=6))
    Driver(be1, cfg.replace(n_trees=6), log_every=10**9,
           checkpoint_dir=ck, checkpoint_every=3).fit(Xb, y)
    # Phase 2: resume with the full config.
    be2 = get_backend(cfg)
    resumed = Driver(be2, cfg, log_every=10**9,
                     checkpoint_dir=ck, checkpoint_every=5).fit(Xb, y)

    np.testing.assert_array_equal(full.feature, resumed.feature)
    np.testing.assert_array_equal(full.threshold_bin, resumed.threshold_bin)
    np.testing.assert_allclose(full.leaf_value, resumed.leaf_value,
                               rtol=2e-4, atol=2e-5)
    # split_gain must survive resume too (round-1 verdict: trees trained
    # before the checkpoint silently lost their gains, corrupting
    # feature_importances("gain") after any resume).
    np.testing.assert_allclose(full.split_gain, resumed.split_gain,
                               rtol=2e-4, atol=2e-5)
    assert np.any(resumed.split_gain[:6] > 0)


def test_checkpoint_resume_with_sampling_matches_uninterrupted(tmp_path):
    """Round 5: bagging/colsample masks are STATELESS counter draws
    (ops/sampling) — there is no RNG stream to lose in a crash, so a
    resumed run recomputes the IDENTICAL masks for the rounds it
    continues (the fused path rebuilds them in-scan from first_round).
    6-then-resume-to-10 must equal straight-10, like the deterministic
    resume contract above."""
    Xb, y, _ = _small_problem(rows=1500)
    cfg = TrainConfig(n_trees=10, max_depth=4, n_bins=31, backend="tpu",
                      subsample=0.75, colsample_bytree=0.7, seed=11)

    be = get_backend(cfg)
    full = Driver(be, cfg, log_every=10**9).fit(Xb, y)

    ck = str(tmp_path / "ck")
    be1 = get_backend(cfg.replace(n_trees=6))
    Driver(be1, cfg.replace(n_trees=6), log_every=10**9,
           checkpoint_dir=ck, checkpoint_every=3).fit(Xb, y)
    be2 = get_backend(cfg)
    resumed = Driver(be2, cfg, log_every=10**9,
                     checkpoint_dir=ck, checkpoint_every=5).fit(Xb, y)

    np.testing.assert_array_equal(full.feature, resumed.feature)
    np.testing.assert_array_equal(full.threshold_bin,
                                  resumed.threshold_bin)
    np.testing.assert_array_equal(full.is_leaf, resumed.is_leaf)
    np.testing.assert_allclose(full.leaf_value, resumed.leaf_value,
                               rtol=2e-4, atol=2e-5)
    # Gains must survive a sampled resume too (the deterministic resume
    # test added this for a real round-1 regression; the fused masked
    # scan is a different writer and deserves the same tripwire).
    np.testing.assert_allclose(full.split_gain, resumed.split_gain,
                               rtol=2e-4, atol=2e-5)
    assert np.any(resumed.split_gain[:6] > 0)


def test_streaming_checkpoint_resume_with_sampling(tmp_path):
    """The streamed twin: a bagged streaming run interrupted at round 4
    resumes to the straight run's exact trees (per-chunk device masks
    re-derive from (seed, round, global row id) — nothing to replay)."""
    from ddt_tpu.streaming import fit_streaming

    Xb, y, _ = _small_problem(rows=2000)
    cfg = TrainConfig(n_trees=8, max_depth=3, n_bins=31, backend="tpu",
                      subsample=0.8, colsample_bytree=0.7, seed=5)

    def cf(c):
        return Xb[c * 500:(c + 1) * 500], y[c * 500:(c + 1) * 500]

    full = fit_streaming(cf, 4, cfg)
    ck = str(tmp_path / "ck")
    fit_streaming(cf, 4, cfg.replace(n_trees=4), checkpoint_dir=ck,
                  checkpoint_every=2)
    resumed = fit_streaming(cf, 4, cfg, checkpoint_dir=ck,
                            checkpoint_every=4)
    np.testing.assert_array_equal(full.feature, resumed.feature)
    np.testing.assert_array_equal(full.threshold_bin,
                                  resumed.threshold_bin)
    np.testing.assert_array_equal(full.leaf_value, resumed.leaf_value)


def test_checkpoint_config_mismatch_refuses(tmp_path):
    Xb, y, _ = _small_problem(rows=500)
    ck = str(tmp_path / "ck")
    cfg = TrainConfig(n_trees=4, max_depth=3, n_bins=31, backend="cpu")
    Driver(get_backend(cfg), cfg, log_every=10**9,
           checkpoint_dir=ck, checkpoint_every=2).fit(Xb, y)
    bad = cfg.replace(max_depth=5)
    with pytest.raises(ValueError, match="incompatible"):
        Driver(get_backend(bad), bad, log_every=10**9,
               checkpoint_dir=ck).fit(Xb, y)


def test_driver_history_logging():
    Xb, y, _ = _small_problem(rows=800)
    cfg = TrainConfig(n_trees=6, max_depth=3, n_bins=31, backend="tpu")
    d = Driver(get_backend(cfg), cfg, log_every=2)
    d.fit(Xb, y)
    assert len(d.history) == 3
    assert d.history[-1]["round"] == 6
    losses = [r["train_loss"] for r in d.history]
    assert losses == sorted(losses, reverse=True)  # loss decreases


@pytest.mark.parametrize("depth,bins,loss", [
    (1, 2, "logloss"),      # stumps on binary bins
    (2, 3, "mse"),
    (8, 17, "logloss"),     # deep + few bins: most nodes become leaves
    (3, 256, "logloss"),    # full uint8 range
    (2, 63, "softmax"),
])
def test_backend_parity_edge_configs(depth, bins, loss):
    """CPU and TPU grow identical trees across uncommon shapes."""
    from ddt_tpu import api
    from ddt_tpu.data.datasets import synthetic_binary, synthetic_multiclass
    from ddt_tpu.data.quantizer import quantize

    if loss == "softmax":
        X, y = synthetic_multiclass(1200, n_features=5, n_classes=3, seed=7)
        extra = dict(loss="softmax", n_classes=3)
    else:
        X, y = synthetic_binary(1200, n_features=5, seed=7)
        if loss == "mse":
            y = y + 0.1 * np.random.default_rng(0).standard_normal(len(y))
        extra = dict(loss=loss)
    Xb, _ = quantize(X, n_bins=bins, seed=7)
    kw = dict(n_trees=3, max_depth=depth, n_bins=bins, seed=7, **extra)
    ec = api.train(Xb, y, TrainConfig(backend="cpu", **kw),
                   binned=True, log_every=10 ** 9).ensemble
    et = api.train(Xb, y, TrainConfig(backend="tpu", **kw),
                   binned=True, log_every=10 ** 9).ensemble
    np.testing.assert_array_equal(ec.feature, et.feature)
    np.testing.assert_array_equal(ec.threshold_bin, et.threshold_bin)
    np.testing.assert_array_equal(ec.is_leaf, et.is_leaf)
    np.testing.assert_allclose(ec.leaf_value, et.leaf_value,
                               rtol=2e-4, atol=2e-5)


def test_api_predict_accepts_model_bundle(tmp_path):
    """api.predict(load_model(path), X) scores with the training-time
    mapper automatically (the complete-artifact contract end to end)."""
    from ddt_tpu import api
    from ddt_tpu.data.datasets import synthetic_binary

    X, y = synthetic_binary(1500, n_features=6, seed=2)
    res = api.train(X, y, n_trees=4, max_depth=3, n_bins=31,
                    backend="cpu", log_every=10**9)
    p = str(tmp_path / "m.npz")
    res.save(p)
    bundle = api.load_model(p)
    got = api.predict(bundle, X)
    want = api.predict(res.ensemble, X, mapper=res.mapper)
    np.testing.assert_array_equal(got, want)


def test_predict_backend_row_chunking_identity(monkeypatch):
    """The backend-level row-chunked scoring path (R > PREDICT_ROW_CHUNK;
    overlapped per-chunk D2H since round 5) equals the host oracle and
    the unchunked path exactly — including a non-multiple final chunk."""
    from ddt_tpu.backends.tpu import TPUDevice

    Xb, y, _ = _small_problem()
    cfg = TrainConfig(n_trees=6, max_depth=4, n_bins=31, backend="tpu")
    be = get_backend(cfg)
    ens = Driver(be, cfg, log_every=10**9).fit(Xb, y)
    want = be.predict_raw(ens, Xb)                   # single dispatch
    monkeypatch.setattr(TPUDevice, "PREDICT_ROW_CHUNK", 96)
    assert Xb.shape[0] % 96 != 0                     # ragged tail chunk
    got = be.predict_raw(ens, Xb)                    # chunked + async D2H
    np.testing.assert_array_equal(want, got)
    np.testing.assert_allclose(
        got, ens.predict_raw(Xb, binned=True), rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("block_rounds", [3, 4])
def test_fused_block_cap_multi_block_identity(block_rounds):
    """Long configs split into multiple fused dispatches
    (cfg.fused_block_rounds caps single-dispatch runtime). Block boundaries must not change results: a 10-round run forced
    through small blocks (both even and uneven final blocks) equals the
    single-block run and the CPU oracle exactly."""
    Xb, y, _ = _small_problem()

    def fit(backend, fused_block_rounds=100):
        cfg = TrainConfig(n_trees=10, max_depth=4, n_bins=31,
                          backend=backend,
                          fused_block_rounds=fused_block_rounds)
        return Driver(get_backend(cfg), cfg, log_every=10**9).fit(Xb, y)

    one_block = fit("tpu")
    multi_block = fit("tpu", fused_block_rounds=block_rounds)
    cpu = fit("cpu")
    for k in ("feature", "threshold_bin", "is_leaf", "leaf_value",
              "split_gain", "default_left"):
        a, b = getattr(one_block, k), getattr(multi_block, k)
        if a is None or b is None:          # default_left on non-missing
            assert a is b, k                # models: None on BOTH sides
        else:
            np.testing.assert_array_equal(a, b, err_msg=k)
    np.testing.assert_array_equal(cpu.feature, multi_block.feature)
