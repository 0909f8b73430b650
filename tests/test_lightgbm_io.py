"""LightGBM model.txt interop round-trips (round-2 verdict item 8):
export -> re-parse with the repo's own loader -> identical predictions.
LightGBM itself is not installed here; the format is validated
structurally and semantically via the independent parser."""

import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.data import datasets
from ddt_tpu.models.tree import TreeEnsemble


def _train(loss="logloss", **kw):
    if loss == "softmax":
        X, y = datasets.synthetic_multiclass(1500, n_features=8,
                                             n_classes=3, seed=11)
        kw.setdefault("n_classes", 3)
    elif loss == "mse":
        X, y = datasets.synthetic_regression(1500, seed=11)
    else:
        X, y = datasets.synthetic_binary(1500, n_features=8, seed=11)
    res = api.train(X, y, n_trees=4, max_depth=3, n_bins=31, loss=loss,
                    backend="cpu", log_every=10**9, **kw)
    return res, X


@pytest.mark.parametrize("loss", ["logloss", "mse", "softmax"])
def test_roundtrip_predictions(loss):
    res, X = _train(loss)
    txt = res.ensemble.to_lightgbm_text()
    assert txt.startswith("tree\nversion=v3")
    assert "end of trees" in txt
    back = TreeEnsemble.from_lightgbm_text(txt)
    assert back.loss == loss
    assert back.n_features == res.ensemble.n_features
    want = res.ensemble.predict_raw(X, binned=False)
    got = back.predict_raw(X, binned=False)
    # base-score fold + shrinkage pre-multiplication reorder float adds:
    # ULP-level, not structural.
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_roundtrip_missing_default_directions():
    """NaN routing survives: decision_type carries the NaN missing type
    and the learned default-left bit."""
    X, y = datasets.synthetic_binary(2000, n_features=6, seed=3)
    X = X.copy()
    X[::7, 2] = np.nan
    res = api.train(X, y, n_trees=4, max_depth=3, n_bins=31,
                    backend="cpu", missing_policy="learn",
                    log_every=10**9)
    txt = res.ensemble.to_lightgbm_text()
    back = TreeEnsemble.from_lightgbm_text(txt)
    want = res.ensemble.predict_raw(X, binned=False)
    got = back.predict_raw(X, binned=False)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert back.default_left is not None


def test_export_validates():
    from ddt_tpu.models.tree import empty_ensemble

    bare = empty_ensemble(2, 3, 5, 0.1, 0.0, "logloss")
    with pytest.raises(ValueError, match="raw-value thresholds"):
        bare.to_lightgbm_text()


def _train_categorical(seed=0, **kw):
    """A model with real one-vs-rest cat splits (criteo-shaped data)."""
    from ddt_tpu.data.categorical import fit_categorical_encoder
    from ddt_tpu.data.datasets import synthetic_ctr

    Xn, Xc, y = synthetic_ctr(4000, seed=seed)
    enc = fit_categorical_encoder(Xc, n_bins=63)
    X = np.concatenate([Xn, enc.transform(Xc).astype(np.float32)], axis=1)
    cat = tuple(range(Xn.shape[1], X.shape[1]))
    res = api.train(X, y, n_trees=5, max_depth=4, n_bins=63,
                    backend="cpu", cat_features=cat, log_every=10**9, **kw)
    return res, X, cat


def test_roundtrip_categorical():
    """Cat one-vs-rest splits export as LightGBM categorical nodes
    (single-bit cat_threshold bitsets) and parse back to identical
    predictions — the Criteo-config model family is no longer excluded
    from the tree-diff validation path (round-3 verdict item 6)."""
    res, X, cat = _train_categorical()
    ens = res.ensemble
    assert ens.has_cat_splits
    txt = ens.to_lightgbm_text()
    blocks = [b for b in txt.split("Tree=") if "num_cat" in b]
    n_cat_total = sum(
        int(b.split("num_cat=")[1].splitlines()[0]) for b in blocks)
    assert n_cat_total > 0, "model grew no cat splits; test data too easy"
    assert "cat_boundaries=" in txt and "cat_threshold=" in txt

    back = TreeEnsemble.from_lightgbm_text(txt)
    assert back.cat_features is not None
    assert set(back.cat_features) <= set(cat)
    want = ens.predict_raw(X, binned=False)
    got = back.predict_raw(X, binned=False)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def _lgbm_oracle_raw(txt: str, X: np.ndarray) -> np.ndarray:
    """Independent NumPy evaluator of LightGBM model.txt semantics (slow
    per-row walk, no shared code with models/lightgbm_io.py): numerical
    `v <= thr goes left`, categorical `int(v) in bitset goes left`, NaN
    follows decision_type's default-left bit. Leaf values are final
    contributions; returns the raw margin sum per row."""
    lines = txt.splitlines()
    blocks, cur = [], None
    for ln in lines:
        if ln.startswith("Tree="):
            cur = {}
            blocks.append(cur)
        elif cur is not None and "=" in ln and ln.strip():
            k, _, v = ln.partition("=")
            cur[k] = v
        elif cur is not None and not ln.strip():
            cur = None

    out = np.zeros(X.shape[0], np.float64)
    for blk in blocks:
        lv = [float(v) for v in blk["leaf_value"].split()]
        if int(blk["num_leaves"]) == 1:
            out += lv[0]
            continue
        sf = [int(v) for v in blk["split_feature"].split()]
        th = [float(v) for v in blk["threshold"].split()]
        dt = [int(float(v)) for v in blk["decision_type"].split()]
        lc = [int(v) for v in blk["left_child"].split()]
        rc = [int(v) for v in blk["right_child"].split()]
        cb = ct = None
        if int(blk.get("num_cat", "0")) != 0:
            cb = [int(v) for v in blk["cat_boundaries"].split()]
            ct = [int(v) for v in blk["cat_threshold"].split()]
        for r in range(X.shape[0]):
            ref = 0
            while ref >= 0:
                v = X[r, sf[ref]]
                if np.isnan(v):
                    left = bool(dt[ref] & 2)
                elif dt[ref] & 1:          # categorical bitset
                    ci = int(th[ref])
                    words = ct[cb[ci]:cb[ci + 1]]
                    k = int(v)
                    left = (k // 32 < len(words)
                            and bool(words[k // 32] >> (k % 32) & 1))
                else:
                    left = v <= th[ref]
                ref = lc[ref] if left else rc[ref]
            out[r] += lv[~ref]
    return out


def test_multibit_categorical_import():
    """Externally-trained LightGBM models with MULTI-category bitsets
    (round-4 verdict item 5) import via one-vs-rest chain expansion and
    score identically to an independent LightGBM-semantics oracle —
    including bitsets spanning two uint32 words, NaN rows, and an empty
    bitset whose decision_type demands NaN-default-LEFT (the one case an
    empty bitset cannot collapse: no category matches but NaN rows still
    exit left — caught by review, sentinel link in bits_of)."""
    # Hand-built model: f0 numeric, f1 categorical with 40 categories.
    # Tree 0's root sends categories {1, 5, 33, 38} left (2-word bitset);
    # its left child is numeric, right child a 1-bit cat node. Tree 1
    # has an EMPTY bitset at the root with decision_type=11
    # (categorical | default-left | NaN missing): real values all go
    # right, NaN goes LEFT.
    def words(cats):
        w = [0, 0]
        for c in cats:
            w[c // 32] |= 1 << (c % 32)
        return w

    w0 = words([1, 5, 33, 38])
    txt = "\n".join([
        "tree", "version=v3", "num_class=1", "num_tree_per_iteration=1",
        "label_index=0", "max_feature_idx=1",
        "objective=binary sigmoid:1", "feature_names=Column_0 Column_1",
        "feature_infos=[-inf:inf] [-inf:inf]", "",
        "Tree=0", "num_leaves=4", "num_cat=2",
        "split_feature=1 0 1",
        "split_gain=9 4 2",
        "threshold=0 0.35 1",
        "decision_type=1 0 1",
        "left_child=1 -1 -3",
        "right_child=2 -2 -4",
        "leaf_value=0.5 -0.25 0.125 -0.75",
        "leaf_weight=0 0 0 0", "leaf_count=0 0 0 0",
        "internal_value=0 0 0", "internal_weight=0 0 0",
        "internal_count=0 0 0",
        f"cat_boundaries=0 2 3",
        f"cat_threshold={w0[0]} {w0[1]} {1 << 7}",
        "is_linear=0", "shrinkage=1", "",
        "Tree=1", "num_leaves=2", "num_cat=1",
        "split_feature=1",
        "split_gain=1",
        "threshold=0",
        "decision_type=11",
        "left_child=-1",
        "right_child=-2",
        "leaf_value=100.0 0.0625",
        "leaf_weight=0 0", "leaf_count=0 0",
        "internal_value=0", "internal_weight=0", "internal_count=0",
        "cat_boundaries=0 1",
        "cat_threshold=0",
        "is_linear=0", "shrinkage=1", "",
        "end of trees", "", "pandas_categorical:null", "",
    ])
    back = TreeEnsemble.from_lightgbm_text(txt)
    assert back.cat_features is not None and 1 in set(back.cat_features)
    # 4-bit chain under a depth-1 subtree: expanded depth 4+1 = 5
    assert back.max_depth == 5
    rng = np.random.default_rng(7)
    X = np.stack([
        rng.random(400).astype(np.float32),
        rng.integers(0, 40, size=400).astype(np.float32),
    ], axis=1)
    X[::11, 1] = np.nan            # NaN in the categorical column
    X[::13, 0] = np.nan            # NaN in the numeric column
    want = _lgbm_oracle_raw(txt, X)
    got = back.predict_raw(X, binned=False)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # The empty-bitset default-left tree: real rows all went right
    # (0.0625), NaN-in-f1 rows exited LEFT into the 100 leaf.
    nan_f1 = np.isnan(X[:, 1])
    assert got[~nan_f1].max() < 50
    assert (got[nan_f1] > 50).all()

    # Gain-sum importances count each original split once, not once per
    # chain link or subtree copy: f0 keeps gain 4 (one copy counted),
    # f1 keeps 9 + 2 + 1 (first links, incl. the sentinel link standing
    # in for the empty-bitset NaN split) -> normalized [4/16, 12/16].
    imp = back.feature_importances("gain")
    np.testing.assert_allclose(imp, [4 / 16, 12 / 16], rtol=1e-6)


def test_multibit_roundtrip_of_doctored_export():
    """A doctored two-extra-bit bitset on a REAL exported model parses
    (no longer rejected) and scores per LightGBM semantics."""
    res, X, cat = _train_categorical()
    txt = res.ensemble.to_lightgbm_text()
    lines = txt.splitlines()
    for i, ln in enumerate(lines):
        if ln.startswith("cat_threshold="):
            words = ln.split("=")[1].split()
            words[0] = str(int(words[0]) | (1 << 31) | 1)
            lines[i] = "cat_threshold=" + " ".join(words)
            break
    doctored = "\n".join(lines)
    back = TreeEnsemble.from_lightgbm_text(doctored)
    want = _lgbm_oracle_raw(doctored, X)
    got = back.predict_raw(X, binned=False)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_malformed_cat_node_rejected():
    """Categorical decision_type with num_cat=0 (foreign/corrupt input)
    fails with a precise ValueError, not a NoneType subscript."""
    txt = "\n".join([
        "tree", "version=v3", "num_class=1", "num_tree_per_iteration=1",
        "label_index=0", "max_feature_idx=0",
        "objective=binary sigmoid:1", "feature_names=Column_0",
        "feature_infos=[-inf:inf]", "",
        "Tree=0", "num_leaves=2", "num_cat=0",
        "split_feature=0", "split_gain=1", "threshold=0",
        "decision_type=1", "left_child=-1", "right_child=-2",
        "leaf_value=1 0", "leaf_weight=0 0", "leaf_count=0 0",
        "internal_value=0", "internal_weight=0", "internal_count=0",
        "is_linear=0", "shrinkage=1", "",
        "end of trees", "", "pandas_categorical:null", "",
    ])
    with pytest.raises(ValueError, match="num_cat=0"):
        TreeEnsemble.from_lightgbm_text(txt)


def test_cat_missing_export_warns():
    """Exporting cat splits together with learned NaN directions warns
    about the cross-tool NaN-routing difference (round-4 advisor)."""
    from ddt_tpu.models.tree import empty_ensemble

    ens = empty_ensemble(1, 2, 3, 0.1, 0.0, "logloss",
                         missing_bin=True, n_bins=31, cat_features=(1,))
    ens.feature[0, 0] = 1
    ens.threshold_bin[0, 0] = 2
    ens.threshold_raw[0, 0] = 2.0
    ens.is_leaf[0, 1:3] = True
    ens.has_raw_thresholds = True
    with pytest.warns(UserWarning, match="NaN"):
        ens.to_lightgbm_text()


def test_categorical_bitset_validation():
    """Mixed cat/ordinal feature use is unrepresentable and must fail
    loudly, not silently misroute."""
    res, X, cat = _train_categorical()
    txt = res.ensemble.to_lightgbm_text()

    # Doctor a cat node's feature to collide with an ordinal feature.
    lines = txt.splitlines()
    for i, ln in enumerate(lines):
        if ln.startswith("decision_type="):
            dts = [int(v) for v in ln.split("=")[1].split()]
            if not any(d & 1 for d in dts):
                continue
            cat_pos = next(j for j, d in enumerate(dts) if d & 1)
            ord_pos = next((j for j, d in enumerate(dts) if not d & 1), None)
            if ord_pos is None:
                continue
            sf_line = i - 3          # split_feature precedes decision_type
            assert lines[sf_line].startswith("split_feature=")
            sfs = lines[sf_line].split("=")[1].split()
            sfs[cat_pos] = sfs[ord_pos]
            lines[sf_line] = "split_feature=" + " ".join(sfs)
            break
    with pytest.raises(ValueError, match="both categorical and numerical"):
        TreeEnsemble.from_lightgbm_text("\n".join(lines))


def test_header_fields_and_leaf_encoding():
    res, _ = _train()
    txt = res.ensemble.to_lightgbm_text(
        feature_names=[f"f{i}" for i in range(8)])
    lines = dict(
        ln.partition("=")[::2] for ln in txt.splitlines() if "=" in ln)
    assert lines["num_class"] == "1"
    assert lines["objective"] == "binary sigmoid:1"
    assert lines["max_feature_idx"] == "7"
    assert "feature_names=f0 f1 f2 f3 f4 f5 f6 f7" in txt
    # leaf references are negative (~leaf_idx), internals non-negative
    lc = [int(v) for v in lines["left_child"].split()]
    rc = [int(v) for v in lines["right_child"].split()]
    n_leaves = int(lines["num_leaves"])
    refs = lc + rc
    assert sum(1 for r in refs if r < 0) == n_leaves
    assert all(-n_leaves <= r < n_leaves - 1 for r in refs)


def _leafwise_nan_text(n_trees=4, n_leaves=255, n_features=12, seed=21):
    """A LightGBM text as `use_missing` writes it for leaf-wise trees of 255
    leaves: every node's missing type NaN, default-left and default-right
    nodes mixed (a fair coin); distinct thresholds a feature well under
    253."""
    from ddt_tpu.models.tree import random_node_list

    rng = np.random.default_rng(seed)
    src = random_node_list(rng, n_trees, n_leaves, n_features, missing=True,
                           learning_rate=1.0, base_score=0.0, loss="logloss",
                           has_raw_thresholds=True, has_bin_thresholds=False)
    live = src.live_nodes
    src.threshold_raw[live] = rng.integers(-90, 90, int(live.sum())) / 8.0
    return src, src.to_lightgbm_text()


def test_leafwise_nan_directions_import_as_a_node_list_and_round_trip():
    """255 leaves with NaN default-left and default-right nodes: imported as
    a NODE LIST with its directions (it was refused before PR 37), scored
    as the independent LightGBM walk scores it, NaN rows and all, and
    written back to the same text."""
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.models import lightgbm_io
    from ddt_tpu.models.tree import NodeListEnsemble

    src, txt = _leafwise_nan_text()
    dts = {int(v) for ln in txt.splitlines() if ln.startswith(
        "decision_type=") for v in ln.split("=")[1].split()}
    assert dts == {8, 10}           # NaN missing type; default right / left
    back = TreeEnsemble.from_lightgbm_text(txt)
    assert isinstance(back, NodeListEnsemble) and back.missing_routes
    assert back.deepest_leaf > lightgbm_io.HEAP_MAX_DEPTH
    np.testing.assert_array_equal(back.default_left, src.default_left)
    assert back.to_lightgbm_text() == txt
    rng = np.random.default_rng(22)
    X = (rng.integers(-100, 100, (300, src.n_features)) / 8.0).astype(
        np.float32)
    X[rng.random(X.shape) < 0.6] = np.nan
    want = _lgbm_oracle_raw(txt, X)
    np.testing.assert_allclose(back.predict_raw(X), want, rtol=1e-5,
                               atol=1e-6)
    # ... and on the device path, through the model's own NaN-aware mapper
    mapper = lightgbm_io.threshold_bin_mapper(back)
    assert mapper.missing_bin and back.n_bins == 255
    Xb = mapper.transform(X)
    assert (Xb[np.isnan(X)] == 254).all() and Xb[~np.isnan(X)].max() < 254
    got = api.predict(back, X, mapper=mapper, raw=True,
                      cfg=TrainConfig(backend="tpu", predict_impl="pallas"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)


def test_threshold_mapper_keeps_the_top_bin_for_nan():
    """With directions a feature holds 253 distinct thresholds (bins
    0..253 for values, 254 NaN's) and the 254th is refused by name; a
    node whose missing type is not NaN sends NaN right, as the heap import
    does; the heap import of a shallow NaN-routed text gets the same
    mapper."""
    from ddt_tpu.models import lightgbm_io
    from ddt_tpu.models.tree import node_list_from_trees

    def chain(n):
        nodes = [(0, 0, float(k), 0.0, (k + 1) if k < n - 1 else ~0,
                  ~(k + 1), k % 2 == 0) for k in range(n)]
        return node_list_from_trees(
            [(nodes, list(np.arange(n + 1.0)))], n_features=2,
            learning_rate=1.0, base_score=0.0, loss="mse",
            has_raw_thresholds=True, has_bin_thresholds=False,
            missing_bin=True)

    ok = chain(253)
    mapper = lightgbm_io.threshold_bin_mapper(ok)
    assert mapper.missing_bin and mapper.n_value_bins == 254
    assert int(ok.threshold_bin[0].max()) == 252
    X = np.array([[np.nan, 0.0], [-1.0, 0.0], [251.5, 0.0], [1e9, 0.0]],
                 np.float32)
    assert mapper.transform(X)[:, 0].tolist() == [254, 0, 252, 253]
    np.testing.assert_array_equal(ok.predict_raw(mapper.transform(X),
                                                 binned=True),
                                  ok.predict_raw(X))
    with pytest.raises(ValueError, match="254 distinct thresholds.*NaN"):
        lightgbm_io.threshold_bin_mapper(chain(254))
    # missing type none on some nodes: NaN goes right there
    txt = chain(40).to_lightgbm_text().replace(
        "decision_type=10 8 10", "decision_type=2 8 10")
    back = TreeEnsemble.from_lightgbm_text(txt)
    assert not back.default_left[0, 0] and back.default_left[0, 2]
    # a shallow text stays a heap, and its mapper keeps the NaN bin too
    heap = TreeEnsemble.from_lightgbm_text(chain(5).to_lightgbm_text())
    assert isinstance(heap, TreeEnsemble) and heap.missing_bin
    hm = lightgbm_io.threshold_bin_mapper(heap)
    assert hm.missing_bin and heap.n_bins == 255
    Xs = np.array([[np.nan, 0.0], [2.5, 0.0]], np.float32)
    np.testing.assert_array_equal(
        heap.predict_raw(hm.transform(Xs), binned=True), heap.predict_raw(Xs))


# ------------------------------------------------------------------ #
# category sets in a node list (PR 55)
# ------------------------------------------------------------------ #

def _set_model_text(seed: int, missing_type: int) -> str:
    """A small LightGBM text: 3 trees of 6 leaves, multi-bit category sets
    on columns 1 and 3 (ids up to 70: three words), numerical splits on
    the others; a set node's missing type as given (2 NaN, 0 None)."""
    rng = np.random.default_rng(seed)
    lines = ["tree", "version=v3", "num_class=1",
             "num_tree_per_iteration=1", "label_index=0",
             "max_feature_idx=3", "objective=regression",
             "feature_names=a b c d", "feature_infos=none none none none",
             ""]
    for t in range(3):
        feat = [1, 0, 3, 2, 1]
        left, right = [1, 2, -1, -2, -3], [3, 4, -4, -5, -6]
        # a balanced-ish tree: node 0 -> (1, 3), 1 -> (2, 4), leaves below
        left, right = [1, 2, ~0, ~1, ~2], [3, 4, ~3, ~4, ~5]
        thr, dec, bounds, words = [], [], [0], []
        for n, f in enumerate(feat):
            if f in (1, 3):
                ids = sorted(rng.choice(71, int(rng.integers(2, 6)),
                                        replace=False).tolist())
                run = [0] * (max(ids) // 32 + 1)
                for i in ids:
                    run[i >> 5] |= 1 << (i & 31)
                thr.append(float(len(bounds) - 1))
                words += run
                bounds.append(len(words))
                dec.append(1 | (missing_type << 2))
            else:
                thr.append(float(rng.integers(0, 50)) + 0.5)
                dec.append(missing_type << 2)
        vals = (rng.integers(-16, 17, 6) / 8.0).tolist()
        lines += [f"Tree={t}", "num_leaves=6", f"num_cat={len(bounds) - 1}",
                  "split_feature=" + " ".join(map(str, feat)),
                  "split_gain=" + " ".join(["1"] * 5),
                  "threshold=" + " ".join(f"{v:.17g}" for v in thr),
                  "decision_type=" + " ".join(map(str, dec)),
                  "left_child=" + " ".join(map(str, left)),
                  "right_child=" + " ".join(map(str, right)),
                  "leaf_value=" + " ".join(f"{v:.17g}" for v in vals),
                  "cat_boundaries=" + " ".join(map(str, bounds)),
                  "cat_threshold=" + " ".join(map(str, words)),
                  "is_linear=0", "shrinkage=1", ""]
    return "\n".join(lines + ["end of trees", ""])


def _deepened(text: str, missing_type: int) -> str:
    """The same model past `HEAP_MAX_DEPTH`: one more tree, a chain of 12
    numerical nodes on column 0 whose 13 leaves are all 0.0, so the import
    yields a NODE LIST and no score moves."""
    n = 12
    block = [
        "Tree=3", f"num_leaves={n + 1}", "num_cat=0",
        "split_feature=" + " ".join(["0"] * n),
        "split_gain=" + " ".join(["1"] * n),
        "threshold=" + " ".join(f"{i + 0.5}" for i in range(n)),
        "decision_type=" + " ".join([str(missing_type << 2)] * n),
        "left_child=" + " ".join(str(~i) for i in range(n)),
        "right_child=" + " ".join(
            [str(i + 1) for i in range(n - 1)] + [str(~n)]),
        "leaf_value=" + " ".join(["0"] * (n + 1)),
        "is_linear=0", "shrinkage=1", "", "end of trees", ""]
    return text.replace("end of trees\n", "\n".join(block))


def _set_rows(seed: int, with_nan: bool) -> np.ndarray:
    """Raw rows with named, unseen, negative and past-the-bitset ids (and
    NaN) in the category columns."""
    rng = np.random.default_rng(seed)
    X = rng.integers(0, 72, (400, 4)).astype(np.float32)
    X[rng.random(400) < 0.1, 1] = -3.0
    X[rng.random(400) < 0.1, 3] = 5000.0          # past every bitset
    X[rng.random(400) < 0.1, 1] = 96.0            # inside the last word's
    if with_nan:                                  # span, never named
        X[rng.random(400) < 0.15, 3] = np.nan
        X[rng.random(400) < 0.15, 0] = np.nan
    return X


@pytest.mark.parametrize("missing_type,with_nan", [(2, True), (2, False),
                                                   (0, False)],
                         ids=["nan-type", "nan-type-no-nan", "none-type"])
def test_category_sets_tie_the_node_list_to_the_heap(missing_type, with_nan,
                                                     tmp_path):
    """ONE model, two layouts: the heap import expands a k-id set into a
    chain of k one-vs-rest nodes; the same text with a 12-level tree of
    zero leaves beside it (`_deepened`) is past the heap and imports as a
    node list, which keeps the bitsets; both score alike, NaN, negative, unseen and past-the-bitset ids among the
    rows. The round trip text -> node list -> text -> node list keeps every
    bitset, and so does save / load; binned rows through the model's own
    mapper score as raw ones."""
    from ddt_tpu.models.lightgbm_io import (from_lightgbm_text,
                                            threshold_bin_mapper,
                                            to_lightgbm_text)
    from ddt_tpu.models.tree import NodeListEnsemble, TreeEnsemble
    from ddt_tpu.reference import numpy_predict

    text = _set_model_text(91 + missing_type, missing_type)
    heap = from_lightgbm_text(text)
    assert isinstance(heap, TreeEnsemble) and heap.has_cat_splits
    ens = from_lightgbm_text(_deepened(text, missing_type))
    assert isinstance(ens, NodeListEnsemble) and ens.has_cat_splits
    assert int(ens.cat_nodes.sum()) == 9
    X = _set_rows(92, with_nan)
    want = ens.predict_raw(X)
    np.testing.assert_array_equal(heap.predict_raw(X), want)
    # the round trip keeps every bitset, word for word
    again = from_lightgbm_text(to_lightgbm_text(ens))
    assert isinstance(again, NodeListEnsemble)
    np.testing.assert_array_equal(again.cat_threshold, ens.cat_threshold)
    np.testing.assert_array_equal(again.cat_boundaries, ens.cat_boundaries)
    np.testing.assert_array_equal(again.cat_nan_as_zero, ens.cat_nan_as_zero)
    np.testing.assert_array_equal(again.predict_raw(X), want)
    # binned by the model's own mapper: the sets over bins
    mapper = threshold_bin_mapper(ens, n_bins=255)
    assert sorted(mapper.category_ids) == [1, 3]
    Xb = mapper.transform(X)
    np.testing.assert_array_equal(
        numpy_predict.predict_raw_node_list(ens, Xb), want)
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig

    for impl in ("onehot", "pallas"):
        np.testing.assert_array_equal(api.predict(
            ens, X, mapper=mapper, raw=True, cfg=TrainConfig(
                backend="tpu", n_bins=255, predict_impl=impl)), want)
    # save / load keeps the sets and the mapper's category tables
    path = str(tmp_path / "sets.npz")
    api.save_model(path, ens, mapper=mapper)
    bundle = api.load_model(path)
    np.testing.assert_array_equal(bundle.ensemble.cat_threshold,
                                  ens.cat_threshold)
    np.testing.assert_array_equal(bundle.ensemble.cat_bin_sets,
                                  ens.cat_bin_sets)
    np.testing.assert_array_equal(bundle.mapper.transform(X), Xb)
    np.testing.assert_array_equal(api.predict(bundle, X, raw=True), want)


def test_category_set_mapper_refuses_by_name():
    from ddt_tpu.models.lightgbm_io import (from_lightgbm_text,
                                            threshold_bin_mapper)

    text = _deepened(_set_model_text(93, 2), 2)
    both = text.replace("split_feature=1 0 3 2 1", "split_feature=1 0 3 1 1")
    with pytest.raises(ValueError, match="by ordinal"):
        threshold_bin_mapper(from_lightgbm_text(both))
    mixed = text.replace("decision_type=9 8 9 8 9", "decision_type=9 8 9 8 1")
    with pytest.raises(ValueError, match="disagree on the missing type"):
        threshold_bin_mapper(from_lightgbm_text(mixed))
    ens = from_lightgbm_text(text)
    with pytest.raises(ValueError, match="name .* ids, more than"):
        threshold_bin_mapper(ens, n_bins=8)


def _wide_set_text(num_class: int) -> str:
    """`num_class` trees of 3 leaves: a set of 14 ids at the root (a chain
    of 14 one-vs-rest nodes in a heap: depth 15), a numerical node under
    it."""
    ids = [1, 2, 3, 5, 8, 13, 21, 34, 40, 41, 47, 55, 60, 63]
    words = [sum(1 << (i & 31) for i in ids if i >> 5 == w) for w in (0, 1)]
    multi = num_class > 1
    lines = ["tree", "version=v3", f"num_class={num_class}",
             f"num_tree_per_iteration={num_class}", "label_index=0",
             "max_feature_idx=1",
             "objective=" + (f"multiclass num_class:{num_class}" if multi
                             else "regression"),
             "feature_names=a b", "feature_infos=none none", ""]
    for t in range(num_class):
        lines += [f"Tree={t}", "num_leaves=3", "num_cat=1",
                  "split_feature=0 1", "split_gain=1 1",
                  "threshold=0 20.5", "decision_type=9 8",
                  "left_child=1 -1", "right_child=-3 -2",
                  f"leaf_value={t + 1} {t + 2.5} {-t - 0.25}",
                  "cat_boundaries=0 2",
                  "cat_threshold=" + " ".join(map(str, words)),
                  "is_linear=0", "shrinkage=1", ""]
    return "\n".join(lines + ["end of trees", ""])


@pytest.mark.parametrize("num_class", [1, 3], ids=["one-column", "softmax"])
def test_a_set_model_the_parent_held_as_a_deep_heap_is_a_node_list(num_class):
    """PR 55 changed the layout of ONE class of model, pinned here: a text
    with category sets whose chain expansion is 12 to 30 levels deep (here
    15) imported as a HEAP before (up to 2^27 slots) and is a node list
    with its bitsets now. One output column: the path kernel scores it.
    Softmax's round-major trees: the sub-tree form has no set tables, so
    the XLA backend refuses it BY NAME at the build and the host backend
    walks it (ROADMAP M13(a), docs/API.md)."""
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.models.lightgbm_io import (from_lightgbm_text,
                                            threshold_bin_mapper)
    from ddt_tpu.models.tree import NodeListEnsemble

    ens = from_lightgbm_text(_wide_set_text(num_class))
    assert isinstance(ens, NodeListEnsemble) and ens.has_cat_splits
    assert int(ens.cat_nodes.sum()) == num_class
    X = _set_rows(95, True)[:, :2]
    want = ens.predict_raw(X)
    in_set = np.isin(X[:, 0], [1, 2, 3, 5, 8, 13, 21, 34, 40, 41, 47, 55,
                               60, 63])
    tree0 = np.where(in_set, np.where(X[:, 1] <= 20.5, 1.0, 2.5), -0.25)
    np.testing.assert_array_equal(
        want if num_class == 1 else want[:, 0], tree0.astype(np.float32))
    mapper = threshold_bin_mapper(ens, n_bins=255)

    def predict(backend):
        return api.predict(ens, X, mapper=mapper, raw=True, cfg=TrainConfig(
            backend=backend, n_bins=255))

    np.testing.assert_array_equal(predict("cpu"), want)
    if num_class == 1:
        np.testing.assert_array_equal(predict("tpu"), want)
    else:
        with pytest.raises(ValueError, match="SUB-TREE form"):
            predict("tpu")
