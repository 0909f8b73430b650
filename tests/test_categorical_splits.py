"""Categorical one-vs-rest splits (round-1 verdict item 9, SURVEY.md §2
"one-hot-gain variant"): features listed in cfg.cat_features split as
"bin == k goes left" with one-hot gain, instead of ordinal "bin <= t" on
the frequency-ranked bins. The split type derives from the model's
cat_features metadata — no per-node storage.
"""

import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.data.categorical import fit_categorical_encoder
from ddt_tpu.data.datasets import synthetic_ctr
from ddt_tpu.data.quantizer import fit_bin_mapper
from ddt_tpu.driver import Driver
from ddt_tpu.reference import numpy_trainer as ref


def _ctr_matrix(rows=4000, bins=63, seed=0):
    """(X float32 incl. encoded cat columns, y, cat feature indices)."""
    Xn, Xc, y = synthetic_ctr(rows, seed=seed)
    enc = fit_categorical_encoder(Xc, n_bins=bins)
    X = np.concatenate([Xn, enc.transform(Xc).astype(np.float32)], axis=1)
    return X, y, tuple(range(Xn.shape[1], X.shape[1]))


# ------------------------------------------------------------------ #
# kernel twins
# ------------------------------------------------------------------ #

def test_onehot_gain_matches_oracle_kernel():
    from ddt_tpu.ops.split import best_splits as jx_best

    rng = np.random.default_rng(3)
    hist = rng.standard_normal((4, 6, 16, 2)).astype(np.float32)
    hist[..., 1] = np.abs(hist[..., 1])
    cat = np.zeros(6, bool)
    cat[[1, 4]] = True
    want = ref.best_splits(hist, 1.0, 1e-3, cat_mask=cat)
    got = jx_best(hist, 1.0, 1e-3, cat_mask=cat)
    np.testing.assert_array_equal(np.asarray(got[1]), want[1])
    np.testing.assert_array_equal(np.asarray(got[2]), want[2])
    np.testing.assert_allclose(np.asarray(got[0]), want[0],
                               rtol=1e-2, atol=1e-2)


def test_onehot_gain_hand_computed():
    """One cat feature, 3 bins, best candidate = isolating the LAST
    category — expressible only as one-vs-rest (ordinal splits exclude
    the last bin and can only cut {0} | {1,2})."""
    hist = np.zeros((1, 1, 3, 2), np.float32)
    hist[0, 0, :, 0] = [1.0, 1.0, -4.0]    # category 2 carries the signal
    hist[0, 0, :, 1] = [1.0, 1.0, 2.0]
    cat = np.ones(1, bool)
    gains, feats, bins, _ = ref.best_splits(hist, 1.0, 0.0, cat_mask=cat)
    # one-vs-rest candidates (G=-2, H=4, parent=4/5):
    #   k=0: 0.5*(1/2 + 9/4 - 0.8)  = 0.975
    #   k=1: same by symmetry        = 0.975
    #   k=2: 0.5*(16/3 + 4/3 - 0.8) = 2.933   <- winner
    assert bins[0] == 2
    np.testing.assert_allclose(
        gains[0], 0.5 * (16 / 3 + 4 / 3 - 4 / 5), rtol=1 / 128)
    # Ordinal on the same histogram cannot isolate category 2.
    _, _, b_ord, _ = ref.best_splits(hist, 1.0, 0.0)
    assert b_ord[0] != 2


# ------------------------------------------------------------------ #
# end-to-end
# ------------------------------------------------------------------ #

def _fit(backend, Xb, y, cat_features, **kw):
    cfg = TrainConfig(n_trees=5, max_depth=4, n_bins=63, backend=backend,
                      cat_features=cat_features, **kw)
    be = get_backend(cfg)
    return Driver(be, cfg, log_every=10**9).fit(Xb, y)


def test_backend_parity_with_cat_splits():
    X, y, cat = _ctr_matrix()
    m = fit_bin_mapper(X, n_bins=63, cat_features=cat)
    Xb = m.transform(X)
    ec = _fit("cpu", Xb, y, cat)
    et = _fit("tpu", Xb, y, cat)
    np.testing.assert_array_equal(ec.feature, et.feature)
    np.testing.assert_array_equal(ec.threshold_bin, et.threshold_bin)
    np.testing.assert_array_equal(ec.is_leaf, et.is_leaf)
    np.testing.assert_allclose(ec.leaf_value, et.leaf_value,
                               rtol=2e-4, atol=2e-5)
    # Some categorical split was actually chosen.
    used = ec.feature[(~ec.is_leaf) & (ec.feature >= 0)]
    assert np.isin(used, cat).any()


def test_partitioned_cat_training_identical():
    X, y, cat = _ctr_matrix()
    m = fit_bin_mapper(X, n_bins=63, cat_features=cat)
    Xb = m.transform(X)
    e1 = _fit("tpu", Xb, y, cat)
    e8 = _fit("tpu", Xb, y, cat, n_partitions=8)
    np.testing.assert_array_equal(e1.feature, e8.feature)
    np.testing.assert_array_equal(e1.threshold_bin, e8.threshold_bin)


def test_onehot_beats_ordinal_on_ctr():
    """The verdict's acceptance bar: AUC improvement over ordinal splits
    on a CTR task whose signal is EXACT-CATEGORY effects: a handful of
    specific categories (scattered across the frequency ranking) carry
    the label. One one-vs-rest split isolates each; ordinal needs several
    cuts per category and burns depth."""
    from ddt_tpu.utils.metrics import evaluate

    rng = np.random.default_rng(11)
    rows = 12000
    Xn = rng.standard_normal((rows, 4)).astype(np.float32)
    ids = rng.integers(0, 40, size=(rows, 2))
    hot = np.isin(ids[:, 0], [7, 23, 31]) | np.isin(ids[:, 1], [4, 18])
    score = 1.8 * hot + 0.4 * Xn[:, 0] + rng.standard_normal(rows) * 0.8
    y = (score > np.quantile(score, 0.7)).astype(np.int32)
    enc = fit_categorical_encoder(ids, n_bins=63)
    X = np.concatenate([Xn, enc.transform(ids).astype(np.float32)], axis=1)
    cat = (4, 5)
    tr, va = slice(0, 9000), slice(9000, None)
    kw = dict(n_trees=30, max_depth=4, n_bins=63, backend="cpu",
              log_every=10**9)
    r_one = api.train(X[tr], y[tr], cat_features=cat, **kw)
    r_ord = api.train(X[tr], y[tr], **kw)
    auc_one = evaluate("auc", y[va], api.predict(
        r_one.ensemble, X[va], mapper=r_one.mapper, raw=True))
    auc_ord = evaluate("auc", y[va], api.predict(
        r_ord.ensemble, X[va], mapper=r_ord.mapper, raw=True))
    assert auc_one > auc_ord + 0.002, (auc_one, auc_ord)


def test_predict_paths_agree_with_cat_splits():
    X, y, cat = _ctr_matrix(rows=3000)
    res = api.train(X, y, n_trees=6, max_depth=4, n_bins=63, backend="cpu",
                    cat_features=cat, log_every=10**9)
    ens, mapper = res.ensemble, res.mapper
    Xb = mapper.transform(X)
    want = ens.predict_raw(Xb, binned=True)          # NumPy oracle

    be_t = get_backend(TrainConfig(backend="tpu", n_bins=63,
                                   cat_features=cat))
    got_dev = be_t.predict_raw(ens, Xb)
    np.testing.assert_allclose(got_dev, want, rtol=2e-4, atol=2e-5)

    # CPU backend (gated off the native traversal for cat models).
    be_c = get_backend(TrainConfig(backend="cpu", n_bins=63,
                                   cat_features=cat))
    np.testing.assert_allclose(be_c.predict_raw(ens, Xb), want,
                               rtol=1e-6, atol=1e-6)


def test_cat_model_artifact_roundtrip(tmp_path):
    X, y, cat = _ctr_matrix(rows=1000)
    res = api.train(X, y, n_trees=3, max_depth=3, n_bins=63, backend="cpu",
                    cat_features=cat, log_every=10**9)
    p = str(tmp_path / "m.npz")
    res.save(p)
    b = api.load_model(p)
    np.testing.assert_array_equal(b.ensemble.cat_features, list(cat))
    p1 = api.predict(res.ensemble, X, mapper=res.mapper)
    p2 = api.predict(b.ensemble, X, mapper=b.mapper)
    np.testing.assert_array_equal(p1, p2)


def test_cat_mapper_identity_edges():
    """Categorical columns pass through binning unchanged (no quantile
    merging of category ids)."""
    X, y, cat = _ctr_matrix(rows=2000, bins=31)
    m = fit_bin_mapper(X, n_bins=31, cat_features=cat)
    Xb = m.transform(X)
    for f in cat:
        np.testing.assert_array_equal(Xb[:, f], X[:, f].astype(np.uint8))


def test_cli_criteo_onehot(tmp_path, capsys):
    import json

    from ddt_tpu.cli import main

    model = str(tmp_path / "c.npz")
    rc = main(["train", "--backend=cpu", "--dataset=criteo", "--rows=2000",
               "--trees=3", "--depth=3", "--bins=63", "--cat-splits=onehot",
               f"--out={model}"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["final_train_loss"] < 0.60
    b = api.load_model(model)
    assert b.ensemble.cat_features is not None
    assert b.ensemble.cat_features[0] == 13


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_streaming_cat_matches_inmemory(backend):
    """Streamed training with categorical one-vs-rest splits grows trees
    bit-identical to the in-memory Driver (host and device stream paths
    route 'bin == k' semantics per chunk)."""
    from ddt_tpu.streaming import fit_streaming

    X, y, cat = _ctr_matrix(rows=2048)
    m = fit_bin_mapper(X, n_bins=63, cat_features=cat)
    Xb = m.transform(X)
    cfg = TrainConfig(n_trees=4, max_depth=4, n_bins=63, backend=backend,
                      cat_features=cat)
    full = Driver(get_backend(cfg), cfg, log_every=10**9).fit(Xb, y)

    def chunk_fn(c):
        s = c * 512
        return Xb[s:s + 512], y[s:s + 512]

    streamed = fit_streaming(chunk_fn, 4, cfg)
    np.testing.assert_array_equal(full.feature, streamed.feature)
    np.testing.assert_array_equal(full.threshold_bin,
                                  streamed.threshold_bin)
    np.testing.assert_array_equal(full.is_leaf, streamed.is_leaf)
    np.testing.assert_allclose(full.leaf_value, streamed.leaf_value,
                               rtol=2e-4, atol=2e-5)
    used = full.feature[(~full.is_leaf) & (full.feature >= 0)]
    assert np.isin(used, cat).any()    # a cat split was actually exercised


def test_cat_eval_set_and_early_stopping():
    """The Driver's incremental validation traversal honors one-vs-rest
    routing (a mis-routed val set would corrupt early stopping)."""
    X, y, cat = _ctr_matrix(rows=4000)
    cfg = TrainConfig(n_trees=12, max_depth=4, n_bins=63, backend="cpu",
                      cat_features=cat)
    from ddt_tpu.data.quantizer import fit_bin_mapper as _fbm

    m = _fbm(X, n_bins=63, cat_features=cat)
    Xb = m.transform(X)
    be = get_backend(cfg)
    d = Driver(be, cfg, log_every=1)
    ens = d.fit(Xb[:3000], y[:3000], eval_set=(Xb[3000:], y[3000:]),
                eval_metric="auc")
    # The recorded validation AUC must equal scoring the truncated
    # ensemble with the (cat-aware) oracle at the same round.
    from ddt_tpu.utils.metrics import evaluate

    last = d.history[-1]
    part = ens.truncate(last["round"])
    want = evaluate("auc", y[3000:], part.predict_raw(Xb[3000:], binned=True))
    np.testing.assert_allclose(last["valid_auc"], want, rtol=1e-6)


def test_cat_config_guards():
    with pytest.raises(ValueError, match="missing_policy"):
        TrainConfig(cat_features=(1,), missing_policy="learn")
    cfg = TrainConfig(cat_features=[])        # list normalizes to tuple
    assert cfg.cat_features == ()
    with pytest.raises(ValueError, match="out of range"):
        X, y, _ = _ctr_matrix(rows=200)
        from ddt_tpu.data.quantizer import quantize as _q

        Xb, _ = _q(X, n_bins=63)
        _fit("cpu", Xb, y, (X.shape[1] + 3,))


def test_feature_sharded_cat_training_identical():
    """The feature-axis cat path (cat_vec_g sliced to the shard's columns,
    global cat-ness recomputed after the all_gather winner combine) must
    grow the same tree as unsharded training."""
    X, y, cat = _ctr_matrix()
    m = fit_bin_mapper(X, n_bins=63, cat_features=cat)
    Xb = m.transform(X)
    # Pad to a column count divisible by the shard count, keeping the cat
    # indices untouched (pad columns are constant -> never chosen).
    F = Xb.shape[1]
    fp = 4
    pad = (-F) % fp
    if pad:
        Xb = np.concatenate(
            [Xb, np.zeros((Xb.shape[0], pad), np.uint8)], axis=1)
    e1 = _fit("tpu", Xb, y, cat)
    eF = _fit("tpu", Xb, y, cat, feature_partitions=fp)
    np.testing.assert_array_equal(e1.feature, eF.feature)
    np.testing.assert_array_equal(e1.threshold_bin, eF.threshold_bin)
    np.testing.assert_array_equal(e1.is_leaf, eF.is_leaf)


def test_mapper_without_identity_cat_bins_rejected():
    """A user-supplied mapper fitted WITHOUT cat_features quantile-merges
    category ids; train and predict must fail loudly, not silently train
    on corrupted categories (round-2 review finding)."""
    X, y, cat = _ctr_matrix(rows=600)
    m_plain = fit_bin_mapper(X, n_bins=63)                   # no identity
    with pytest.raises(ValueError, match="identity-binned"):
        api.train(X, y, mapper=m_plain, cat_features=cat,
                  n_trees=2, max_depth=3, n_bins=63, backend="cpu",
                  log_every=10**9)
    m_cat = fit_bin_mapper(X, n_bins=63, cat_features=cat)
    res = api.train(X, y, mapper=m_cat, cat_features=cat,
                    n_trees=2, max_depth=3, n_bins=63, backend="cpu",
                    log_every=10**9)
    with pytest.raises(ValueError, match="identity-bin"):
        api.predict(res.ensemble, X, mapper=m_plain)
    # The training-time mapper round-trips through save/load and scores.
    m_rt = type(m_cat).load(m_cat.save())
    assert m_rt.cat_features == m_cat.cat_features
    p = api.predict(res.ensemble, X, mapper=m_rt)
    assert p.shape[0] == X.shape[0]
    # A LEGACY artifact (saved before the cat_features field existed) whose
    # edges ARE identity must still be accepted: the guard checks the
    # edges, not the metadata.
    legacy = {k: v for k, v in m_cat.save().items() if k != "cat_features"}
    m_legacy = type(m_cat).load(legacy)
    assert m_legacy.cat_features == ()
    p2 = api.predict(res.ensemble, X, mapper=m_legacy)
    np.testing.assert_allclose(p2, p, rtol=1e-6)


def test_cat_eval_set_device_path():
    """The DEVICE-side eval traversal (TPUDevice.eval_round) honors
    one-vs-rest routing — twin of test_cat_eval_set_and_early_stopping,
    which exercises the host path."""
    X, y, cat = _ctr_matrix(rows=4000)
    cfg = TrainConfig(n_trees=12, max_depth=4, n_bins=63, backend="tpu",
                      cat_features=cat)
    from ddt_tpu.data.quantizer import fit_bin_mapper as _fbm

    m = _fbm(X, n_bins=63, cat_features=cat)
    Xb = m.transform(X)
    be = get_backend(cfg)
    d = Driver(be, cfg, log_every=1)
    ens = d.fit(Xb[:3000], y[:3000], eval_set=(Xb[3000:], y[3000:]),
                eval_metric="auc")
    from ddt_tpu.utils.metrics import evaluate

    last = d.history[-1]
    part = ens.truncate(last["round"])
    want = evaluate("auc", y[3000:], part.predict_raw(Xb[3000:], binned=True))
    # The recorded score now comes from the binned-rank DEVICE auc twin
    # (round 5 - auc rides the fused path); 5e-5 is its documented
    # within-bin tie tolerance vs the f64 host auc
    # (utils/metrics.DEVICE_AUC_BINS).
    np.testing.assert_allclose(last["valid_auc"], want, atol=5e-5)


def test_config3_partitioned_at_reduced_scale():
    """Reduced-size twin of the config-3 at-scale witness (1M rows on
    the earlier host, round 5): Criteo-shaped
    categorical training over 4 row partitions upholds the scale
    contract (tree_compare.assert_prefix_identity_mod_ties — ONE home,
    shared with the witness): bitwise-identical tree prefix, any
    first-divergence root cause a PROVABLE bf16-boundary tie (the
    cross-partition psum-order seam), later trees quality-equivalent
    (holdout AUC). At this size divergence usually doesn't occur at all
    and the whole run is bitwise."""
    from tree_compare import assert_prefix_identity_mod_ties

    X, y, cat = _ctr_matrix(rows=200_000, seed=5)
    m = fit_bin_mapper(X, n_bins=63, cat_features=cat)
    Xb = m.transform(X)
    ens = {}
    for parts in (1, 4):
        cfg = TrainConfig(n_trees=6, max_depth=5, n_bins=63,
                          backend="tpu", n_partitions=parts,
                          min_split_gain=1e-3, cat_features=cat)
        ens[parts] = Driver(get_backend(cfg), cfg,
                            log_every=10**9).fit(Xb, y)

    assert_prefix_identity_mod_ties(ens[1], ens[4], 1e-3)
    from ddt_tpu.utils.metrics import auc

    a1 = auc(y, ens[1].predict_raw(Xb, binned=True))
    a4 = auc(y, ens[4].predict_raw(Xb, binned=True))
    assert abs(a1 - a4) < 1e-3, (a1, a4)
    assert np.isin(ens[4].feature[~ens[4].is_leaf], list(cat)).any()
