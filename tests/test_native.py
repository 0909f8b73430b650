"""Native C++ kernel parity vs the NumPy oracle (exact — same f32 op order).

The native kernels are the honest CPU-reference baseline (BASELINE.md);
skipped cleanly when the toolchain can't build them.
"""

import numpy as np
import pytest

try:
    from ddt_tpu import native
except (ImportError, OSError) as _e:
    # ImportError: no toolchain. OSError: ctypes.CDLL on a corrupt/
    # wrong-arch lib or a DDT_NATIVE_LIB sanitizer build without its
    # runtime preloaded — skip, don't error. Other exception types are
    # real binding bugs and must propagate (round-5 advisor finding).
    pytest.skip(f"native kernels unavailable: {_e}",
                allow_module_level=True)

from ddt_tpu.config import TrainConfig  # noqa: E402
from ddt_tpu.reference import numpy_trainer as ref  # noqa: E402


# Bit-exactness vs the row-order NumPy oracle holds only on the serial
# kernel path; tests/conftest.py pins the whole suite to one OpenMP
# thread (rationale there). Multi-thread behavior is covered explicitly
# by test_native_multithread_allclose_deterministic below.


@pytest.mark.parametrize("R,F,B,N", [
    (1000, 6, 31, 1),
    (2048, 4, 255, 8),
    (777, 3, 16, 32),     # odd row count
])
def test_native_histogram_exact(R, F, B, N):
    rng = np.random.default_rng(1)
    Xb = rng.integers(0, B, size=(R, F), dtype=np.uint8)
    g = rng.standard_normal(R).astype(np.float32)
    h = rng.random(R).astype(np.float32)
    ni = rng.integers(-1, N, size=R).astype(np.int32)
    want = ref.build_histograms(Xb, g, h, ni, N, B)
    got = native.histogram_native(Xb, g, h, ni, N, B)
    # Same accumulation order (row-major) → bit-exact.
    np.testing.assert_array_equal(want, got)


def test_native_traverse_matches_ensemble():
    from ddt_tpu.models.tree import empty_ensemble

    rng = np.random.default_rng(2)
    R, F, B, depth, T = 3000, 8, 63, 5, 12
    Xb = rng.integers(0, B, size=(R, F), dtype=np.uint8)
    ens = empty_ensemble(T, depth, F, 0.1, 0.0, "logloss")
    N = ens.feature.shape[1]
    ens.feature[:] = rng.integers(0, F, size=(T, N))
    ens.threshold_bin[:] = rng.integers(0, B - 1, size=(T, N))
    # Random early leaves + all-leaf last level.
    ens.is_leaf[:] = rng.random((T, N)) < 0.15
    ens.is_leaf[:, (1 << depth) - 1:] = True
    want = ens._traverse_np(Xb, binned=True)
    got = native.traverse_native(
        Xb, ens.feature, ens.threshold_bin, ens.is_leaf, depth
    )
    np.testing.assert_array_equal(want, got)


def test_cpu_backend_uses_native():
    """CPUDevice should pick the native kernels up automatically."""
    from ddt_tpu.backends.cpu import CPUDevice
    from ddt_tpu.config import TrainConfig

    be = CPUDevice(TrainConfig(backend="cpu", n_bins=31))
    assert be._native is not None
    assert be._native_split is not None
    assert be._native_traverse is not None


@pytest.mark.parametrize("reg_lambda,mcw,seed", [
    (1.0, 1e-3, 0),
    (0.0, 0.0, 1),      # NaN-masking path (0/0 gains)
    (5.0, 2.0, 2),      # min_child_weight pruning
])
def test_native_split_gain_exact(reg_lambda, mcw, seed):
    rng = np.random.default_rng(seed)
    N, F, B = 8, 5, 31
    hist = rng.standard_normal((N, F, B, 2)).astype(np.float32)
    hist[..., 1] = np.abs(hist[..., 1])          # hessians >= 0
    hist[2] = 0.0                                # empty node (no valid split)
    # Duplicate a feature to force exact bf16 ties → first-index tie-break.
    hist[:, 3] = hist[:, 1]
    want = ref.best_splits(hist, reg_lambda, mcw)[:3]
    got = native.split_gain_native(hist, reg_lambda, mcw)
    for w, g_ in zip(want, got):
        np.testing.assert_array_equal(w, g_)


def test_native_trainer_identical_to_numpy_trainer():
    """Full CPU training with native kernels == pure-NumPy oracle training,
    tree for tree (the bit-parity contract that makes the native path a
    legitimate drop-in)."""
    from ddt_tpu.backends.cpu import CPUDevice
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.data.datasets import synthetic_binary
    from ddt_tpu.data.quantizer import quantize
    from ddt_tpu.driver import Driver

    X, y = synthetic_binary(3000, n_features=8, seed=13)
    Xb, _ = quantize(X, n_bins=63, seed=13)
    cfg = TrainConfig(n_trees=6, max_depth=4, n_bins=63, backend="cpu")
    e_native = Driver(
        CPUDevice(cfg, use_native=True), cfg, log_every=10**9).fit(Xb, y)
    e_numpy = Driver(
        CPUDevice(cfg, use_native=False), cfg, log_every=10**9).fit(Xb, y)
    np.testing.assert_array_equal(e_native.feature, e_numpy.feature)
    np.testing.assert_array_equal(e_native.threshold_bin,
                                  e_numpy.threshold_bin)
    np.testing.assert_array_equal(e_native.is_leaf, e_numpy.is_leaf)
    np.testing.assert_array_equal(e_native.leaf_value, e_numpy.leaf_value)


def test_native_predict_matches_numpy_predict():
    from ddt_tpu.backends.cpu import CPUDevice
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.data.datasets import synthetic_multiclass
    from ddt_tpu.data.quantizer import quantize
    from ddt_tpu.driver import Driver

    X, y = synthetic_multiclass(1500, n_features=6, n_classes=3, seed=4)
    Xb, _ = quantize(X, n_bins=31, seed=4)
    cfg = TrainConfig(n_trees=4, max_depth=3, n_bins=31, backend="cpu",
                      loss="softmax", n_classes=3)
    be = CPUDevice(cfg, use_native=True)
    ens = Driver(be, cfg, log_every=10**9).fit(Xb, y)
    np.testing.assert_allclose(
        be.predict_raw(ens, Xb), ens.predict_raw(Xb, binned=True),
        rtol=1e-6, atol=1e-6)


def test_cpu_backend_histogram_exact():
    """be.build_histograms through the backend (not the raw kernel) is
    bit-exact vs the NumPy oracle."""
    from ddt_tpu.backends.cpu import CPUDevice
    from ddt_tpu.config import TrainConfig

    be = CPUDevice(TrainConfig(backend="cpu", n_bins=31), use_native=True)
    rng = np.random.default_rng(3)
    Xb = rng.integers(0, 31, size=(500, 4), dtype=np.uint8)
    g = rng.standard_normal(500).astype(np.float32)
    h = rng.random(500).astype(np.float32)
    ni = rng.integers(0, 4, size=500).astype(np.int32)
    got = be.build_histograms(be.upload(Xb), g, h, ni, 4)
    want = ref.build_histograms(Xb, g, h, ni, 4, 31)
    np.testing.assert_array_equal(want, got)


def test_split_gain_full_matches_oracle_fuzz():
    """ddt_split_gain_full == reference.best_splits EXACTLY across the
    full contract grid: feature masks, missing_bin direction scoring,
    categorical one-vs-rest, zero/nonzero reg_lambda and
    min_child_weight (bf16 argmax tie-breaks included)."""
    native = pytest.importorskip("ddt_tpu.native")

    rng = np.random.default_rng(0)
    for trial in range(50):
        n = int(rng.integers(1, 5))
        F = int(rng.integers(2, 7))
        B = int(rng.integers(3, 20))
        hist = rng.standard_normal((n, F, B, 2)).astype(np.float32)
        hist[..., 1] = np.abs(hist[..., 1])
        lam = float(rng.choice([0.0, 0.5, 1.0]))
        mcw = float(rng.choice([0.0, 1e-3, 0.7]))
        fm = rng.random(F) < 0.7 if rng.random() < 0.5 else None
        if fm is not None and not fm.any():
            fm[0] = True
        missing = bool(rng.random() < 0.5)
        cm = (rng.random(F) < 0.4) if rng.random() < 0.5 else None
        want = ref.best_splits(hist, lam, mcw, fm, missing_bin=missing,
                               cat_mask=cm)
        got = native.split_gain_full_native(hist, lam, mcw, fm, missing, cm)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(
                np.asarray(w, np.float64), np.asarray(g, np.float64),
                err_msg=f"trial {trial} lam={lam} mcw={mcw} "
                        f"missing={missing}")


def test_native_traverse_cat_routing_matches_numpy():
    """v3 traversal's one-vs-rest routing == TreeEnsemble's NumPy scorer
    on a trained categorical model (the native predict path no longer
    gates cat models off)."""
    pytest.importorskip("ddt_tpu.native")
    from ddt_tpu import api
    from ddt_tpu.backends.cpu import CPUDevice
    from ddt_tpu.data.categorical import fit_categorical_encoder
    from ddt_tpu.data.datasets import synthetic_ctr
    from ddt_tpu.data.quantizer import fit_bin_mapper

    Xn, Xc, y = synthetic_ctr(2000, seed=0)
    enc = fit_categorical_encoder(Xc, n_bins=63)
    X = np.concatenate([Xn, enc.transform(Xc).astype(np.float32)], axis=1)
    cat = tuple(range(Xn.shape[1], X.shape[1]))
    m = fit_bin_mapper(X, n_bins=63, cat_features=cat)
    res = api.train(X, y, mapper=m, cat_features=cat, n_trees=5,
                    max_depth=4, n_bins=63, backend="cpu",
                    log_every=10**9)
    Xb = m.transform(X)
    be = CPUDevice(TrainConfig(backend="cpu", n_bins=63,
                               cat_features=cat), use_native=True)
    assert be._native_traverse is not None
    want = res.ensemble.predict_raw(Xb, binned=True)
    got = be.predict_raw(res.ensemble, Xb)
    np.testing.assert_array_equal(want, got)
    used = res.ensemble.feature[(~res.ensemble.is_leaf)
                                & (res.ensemble.feature >= 0)]
    assert np.isin(used, cat).any()


def test_cpu_backend_uses_native_full_split_missing_colsample():
    """The native full-contract SplitGain drives CPU training for
    missing+colsample configs (no silent NumPy fallback), growing trees
    identical to a native-disabled run. (Cat composes with
    missing_policy='zero' only — covered separately below.)"""
    pytest.importorskip("ddt_tpu.native")
    from ddt_tpu.backends.cpu import CPUDevice
    from ddt_tpu.driver import Driver

    rng = np.random.default_rng(5)
    X = rng.standard_normal((3000, 8)).astype(np.float32)
    X[rng.random(X.shape) < 0.1] = np.nan
    y = (X[:, 0] > 0.2).astype(np.int64)
    y[np.isnan(X[:, 0])] = rng.integers(0, 2, np.isnan(X[:, 0]).sum())
    from ddt_tpu.data.quantizer import fit_bin_mapper

    m = fit_bin_mapper(X, n_bins=31, missing_policy="learn")
    Xb = m.transform(X)
    cfg = TrainConfig(n_trees=4, max_depth=4, n_bins=31, backend="cpu",
                      missing_policy="learn", colsample_bytree=0.75)
    be_n = CPUDevice(cfg, use_native=True)
    assert be_n._native_split_full is not None
    be_0 = CPUDevice(cfg, use_native=False)
    e_n = Driver(be_n, cfg, log_every=10**9).fit(Xb, y)
    e_0 = Driver(be_0, cfg, log_every=10**9).fit(Xb, y)
    np.testing.assert_array_equal(e_n.feature, e_0.feature)
    np.testing.assert_array_equal(e_n.threshold_bin, e_0.threshold_bin)
    np.testing.assert_array_equal(e_n.default_left, e_0.default_left)
    np.testing.assert_allclose(e_n.leaf_value, e_0.leaf_value, rtol=1e-6)


def test_cpu_backend_uses_native_full_split_cat_training():
    """Driver-level categorical training through the native full-contract
    SplitGain equals a native-disabled run (cat wiring of the
    split_full path through grow_tree)."""
    pytest.importorskip("ddt_tpu.native")
    from ddt_tpu.backends.cpu import CPUDevice
    from ddt_tpu.data.categorical import fit_categorical_encoder
    from ddt_tpu.data.datasets import synthetic_ctr
    from ddt_tpu.data.quantizer import fit_bin_mapper
    from ddt_tpu.driver import Driver

    Xn, Xc, y = synthetic_ctr(2500, seed=2)
    enc = fit_categorical_encoder(Xc, n_bins=63)
    X = np.concatenate([Xn, enc.transform(Xc).astype(np.float32)], axis=1)
    cat = tuple(range(Xn.shape[1], X.shape[1]))
    m = fit_bin_mapper(X, n_bins=63, cat_features=cat)
    Xb = m.transform(X)
    cfg = TrainConfig(n_trees=4, max_depth=4, n_bins=63, backend="cpu",
                      cat_features=cat)
    be_n = CPUDevice(cfg, use_native=True)
    assert be_n._native_split_full is not None
    e_n = Driver(be_n, cfg, log_every=10**9).fit(Xb, y)
    e_0 = Driver(CPUDevice(cfg, use_native=False), cfg,
                 log_every=10**9).fit(Xb, y)
    np.testing.assert_array_equal(e_n.feature, e_0.feature)
    np.testing.assert_array_equal(e_n.threshold_bin, e_0.threshold_bin)
    np.testing.assert_allclose(e_n.leaf_value, e_0.leaf_value, rtol=1e-6)
    used = e_n.feature[(~e_n.is_leaf) & (e_n.feature >= 0)]
    assert np.isin(used, cat).any()


def test_csv_parse_native_matches_loadtxt(tmp_path):
    """The native CSV parser (csv_loader.cpp) vs np.loadtxt on the exact
    subset load_file uses: comments, blank lines, headers skipped by
    physical count, \\r\\n endings, exponents, max_rows."""
    from ddt_tpu.native import csv_parse_native

    text = (
        "colA,colB,colC\n"            # header (skip_rows=1)
        "1.5,2,-3e2\r\n"
        "# a full-line comment\n"
        "\n"
        "4,5.25,6 # trailing comment\n"
        "-0.125,1e-3,+7\n"
    )
    p = tmp_path / "t.csv"
    p.write_text(text)
    want = np.loadtxt(str(p), delimiter=",", skiprows=1)
    got = csv_parse_native(text.encode(), skip_rows=1)
    np.testing.assert_array_equal(got, want)

    got2 = csv_parse_native(text.encode(), skip_rows=1, max_rows=2)
    np.testing.assert_array_equal(got2, want[:2])


def test_csv_parse_native_rejects_malformed():
    from ddt_tpu.native import csv_parse_native

    with pytest.raises(ValueError, match="line 2.*expected"):
        csv_parse_native(b"1,2,3\n4,5\n")
    with pytest.raises(ValueError, match="unparseable"):
        csv_parse_native(b"1,2\n3,x\n")
    with pytest.raises(ValueError, match="empty"):
        csv_parse_native(b"1,,3\n")
    assert csv_parse_native(b"").shape == (0, 0)


def test_load_file_csv_native_equals_fallback(tmp_path, monkeypatch):
    """load_file's CSV branch: native parse == np.loadtxt fallback."""
    from ddt_tpu.data import datasets as ds

    rng = np.random.default_rng(3)
    M = rng.standard_normal((200, 5)).round(4)
    M[:, 0] = rng.integers(0, 2, 200)
    p = tmp_path / "d.csv"
    np.savetxt(str(p), M, delimiter=",", fmt="%.6g")

    Xn, yn = ds.load_file(str(p))
    # Force the fallback by making the native import fail.
    import builtins
    real_import = builtins.__import__

    def block(name, *a, **k):
        if name == "ddt_tpu.native":
            raise ImportError("blocked for fallback test")
        return real_import(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", block)
    Xf, yf = ds.load_file(str(p))
    np.testing.assert_array_equal(Xn, Xf)
    np.testing.assert_array_equal(yn, yf)


def test_native_multithread_allclose_deterministic():
    """The multi-thread kernel contract (and the TSan soak's parallel
    workout — native/Makefile): at a fixed team size >1 the histogram
    reduction is (a) deterministic run-to-run, (b) equal to the serial
    oracle up to float32 reassociation (~1e-6 relative), and (c) node/bin
    placement-exact (a race would corrupt placement or drop rows, moving
    sums far beyond reassociation noise). CSV parsing writes row-disjoint
    output, so it stays bit-exact at any team size."""
    rng = np.random.default_rng(7)
    R, F, B, N = 20_000, 8, 63, 16
    Xb = rng.integers(0, B, size=(R, F), dtype=np.uint8)
    g = rng.standard_normal(R).astype(np.float32)
    h = rng.random(R).astype(np.float32)
    ni = rng.integers(-1, N, size=R).astype(np.int32)
    want = ref.build_histograms(Xb, g, h, ni, N, B)

    with native.omp_threads(4):
        a = native.histogram_native(Xb, g, h, ni, N, B)
        b = native.histogram_native(Xb, g, h, ni, N, B)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, want, rtol=2e-5, atol=2e-5)

        M = rng.standard_normal((2_000, 6))
        text = "\n".join(",".join(f"{v:.6f}" for v in row) for row in M)
        got = native.csv_parse_native((text + "\n").encode())
        np.testing.assert_array_equal(got, np.round(M, 6))

        # split_gain + traversal parallelize over nodes/trees with
        # per-item serial scans and disjoint outputs: bit-exact at ANY
        # team size (no reassociation), so the oracle comparison is exact.
        hist = want + 0.0
        hist[..., 1] = np.abs(hist[..., 1])
        sw = ref.best_splits(hist, 1.0, 1e-3)[:3]
        sg = native.split_gain_native(hist, 1.0, 1e-3)
        for w_, g_ in zip(sw, sg):
            np.testing.assert_array_equal(w_, g_)

        from ddt_tpu.models.tree import empty_ensemble
        depth, T = 5, 12
        ens = empty_ensemble(T, depth, F, 0.1, 0.0, "logloss")
        NN = ens.feature.shape[1]
        ens.feature[:] = rng.integers(0, F, size=(T, NN))
        ens.threshold_bin[:] = rng.integers(0, B - 1, size=(T, NN))
        ens.is_leaf[:] = rng.random((T, NN)) < 0.15
        ens.is_leaf[:, (1 << depth) - 1:] = True
        np.testing.assert_array_equal(
            ens._traverse_np(Xb, binned=True),
            native.traverse_native(Xb, ens.feature, ens.threshold_bin,
                                   ens.is_leaf, depth))

        # Composed kernels under real interleaving (the shapes a single
        # kernel call can't produce): a full CPU Driver training at team
        # size 4 — histogram -> split_gain_full -> traversal per level,
        # every round. Gains here sit above the reassociation noise
        # floor, so tree STRUCTURE matches the serial run; leaf sums may
        # differ at float32 reassociation level only.
        from ddt_tpu.backends.cpu import CPUDevice
        from ddt_tpu.data.datasets import synthetic_binary
        from ddt_tpu.data.quantizer import quantize
        from ddt_tpu.driver import Driver

        X4, y4 = synthetic_binary(5000, n_features=8, seed=21)
        Xb4, _ = quantize(X4, n_bins=63, seed=21)
        cfg = TrainConfig(n_trees=3, max_depth=4, n_bins=63, backend="cpu")
        e4 = Driver(CPUDevice(cfg, use_native=True), cfg,
                    log_every=10**9).fit(Xb4, y4)
    e1 = Driver(CPUDevice(cfg, use_native=True), cfg,
                log_every=10**9).fit(Xb4, y4)      # serial (suite pin)
    np.testing.assert_array_equal(e4.feature, e1.feature)
    np.testing.assert_array_equal(e4.threshold_bin, e1.threshold_bin)
    np.testing.assert_allclose(e4.leaf_value, e1.leaf_value,
                               rtol=1e-5, atol=1e-6)
