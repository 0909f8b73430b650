"""The averaged forest: a node list with VECTOR LEAVES whose trees are cut
into chained sub-trees (models/tree.cut_subtrees, CompiledNodeList's
sub-tree form; ops/predict_paths.py's chain and class dot, interpreted;
ops/predict._predict_chain, its jax.numpy twin) held to the walk of the
UNCUT tree (reference/numpy_predict.predict_proba_node_list), and
scikit-learn's own forests through models/sklearn_io.py. Seeded; the lane
count of a sub-tree is lowered to one tile (128) so that trees of a few
hundred leaves make chains three sub-trees deep."""

import json

import numpy as np
import pytest

from ddt_tpu import api, cli
from ddt_tpu.config import TrainConfig
from ddt_tpu.models import tree
from ddt_tpu.models.tree import (NodeListEnsemble, cut_subtrees,
                                 ensemble_from_dict, random_node_list,
                                 split_bfloat16)
from ddt_tpu.ops import predict_paths
from ddt_tpu.reference.numpy_predict import (leaf_of_rows_node_list,
                                             predict_proba_node_list)

BINS, F = 64, 12


@pytest.fixture(autouse=True)
def one_tile_subtrees(monkeypatch):
    monkeypatch.setattr(tree, "SUBTREE_LANES", 128)


def forest(seed, n_trees, leaves, columns, missing=False, dyadic=False,
           features=F):
    return random_node_list(
        np.random.default_rng(seed), n_trees, leaves, features, n_bins=BINS,
        dyadic=dyadic, missing=missing, leaf_columns=columns)


def rows_of(seed, n, features=F):
    return np.random.default_rng(seed).integers(
        0, BINS, (n, features)).astype(np.uint8)


def scored(ens, Xb, impl):
    return api.predict(ens, Xb, binned=True, raw=True, cfg=TrainConfig(
        backend="tpu", predict_impl=impl, n_bins=BINS))


def chain_depth(ens, cut, t):
    """Sub-trees on the longest chain of tree t (1: the tree is uncut)."""
    parent = ens._parents()[0][t]
    depth = {}
    for n in np.argsort(cut.subtree[t])[(cut.subtree[t] < 0).sum():]:
        if cut.root[t, n]:
            up = parent[n]
            depth[cut.subtree[t, n]] = 1 + (
                depth[cut.subtree[t, up]] if up >= 0 else 0)
    return max(depth.values(), default=1)


# ---------------------------------------------------------------------- #
# the cut
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("lanes", [8, 32, 128])
def test_the_cut_is_a_partition_into_connected_subtrees(lanes):
    ens = forest(1, 6, (1, 700), 3)
    cut = cut_subtrees(ens, lanes)
    parent = ens._parents()[0]
    live = ens.live_nodes
    assert (cut.subtree[live] >= 0).all() and (cut.subtree[~live] < 0).all()
    for t in range(ens.n_trees):
        n_int = int(ens.n_leaves[t]) - 1
        if n_int == 0:
            assert cut.n_subtrees[t] == 1
            continue
        sub, lane, root = cut.subtree[t, :n_int], cut.lane[t, :n_int], \
            cut.root[t, :n_int]
        assert root[0] and sub[0] == 0
        assert sorted(set(sub)) == list(range(cut.n_subtrees[t]))
        # one root a sub-tree, lane 0; every other node hangs on a node of
        # its own sub-tree (connected), every root on another's (a link's
        # target is a root, and a parent is numbered before its child)
        assert np.bincount(sub[root]).tolist() == [1] * cut.n_subtrees[t]
        assert (lane[root] == 0).all()
        inner = np.nonzero(~root)[0]
        assert (sub[parent[t, inner]] == sub[inner]).all()
        hung = np.nonzero(root)[0][1:]
        assert (sub[parent[t, hung]] < sub[hung]).all()
        # lanes: a sub-tree's nodes are numbered 0.. without a gap, and
        # its exits (nodes + 1) fit the lane count
        for k in range(cut.n_subtrees[t]):
            mine = np.sort(lane[sub == k])
            assert mine.tolist() == list(range(len(mine)))
            assert len(mine) + 1 <= lanes
    # the fewest parts a bound allows is no fewer than nodes / bound
    assert (cut.n_subtrees >= np.ceil(
        (ens.n_leaves - 1) / (lanes - 1))).all()
    if lanes == 128:
        assert max(chain_depth(ens, cut, t) for t in range(6)) >= 3


def test_a_cycle_and_a_stray_child_are_refused():
    ens = forest(2, 2, 40, 1)
    ens.left_child[0, 5] = 99
    with pytest.raises(ValueError, match="outside its tree"):
        cut_subtrees(ens, 8)


def test_three_bfloat16_pieces_hold_a_float32_exactly():
    rng = np.random.default_rng(3)
    v = np.concatenate([rng.standard_normal(5000) * 10.0 ** rng.integers(
        -6, 6, 5000), [0.0, 1.0, 1 / 3, 0.1, 255.0, 1e-30]]).astype(
        np.float32)
    pieces = [p.astype(np.float32) for p in split_bfloat16(v)]
    np.testing.assert_array_equal((pieces[2] + pieces[1]) + pieces[0], v)


def tables_scores(ce, Xb):
    """The compiled tables' own equations in NumPy (ops/predict.py, "The
    chain"): every sub-tree's contribution, summed."""
    sel, paths, leaves = (a.astype(np.float32) for a in (
        ce.sel, ce.paths, ce.leaves))
    C = ce.leaf_columns
    cl = -(-3 * C // 128) * 128
    X = np.pad(Xb.astype(np.float32), ((0, 0), (0, sel.shape[1] - F)))
    acc = np.zeros((len(Xb), cl), np.float32)
    act = np.zeros((len(Xb), leaves.shape[2] - cl), np.float32)
    for k in range(ce.n_subtrees):
        v = X @ sel[k]
        right = v > ce.planes[k, 0]
        if ce.missing_bin_value >= 0:
            right &= v < ce.planes[k, 3]
        e = (np.where(right, 1.0, -1.0).astype(np.float32) @ paths[k]
             == ce.planes[k, 1]).astype(np.float32)
        assert (e.sum(axis=1) == 1).all()       # one exit a row, always
        if ce.planes[k, 4, 0] > 0:
            act[:], act[:, 0] = 0.0, 1.0
        y, a = e @ leaves[k], act[:, :1].copy()
        acc += a * y[:, :cl]
        act = np.roll(act, -1, axis=1) + a * y[:, cl:]
    return (acc[:, 2 * C:3 * C] + acc[:, C:2 * C] + acc[:, :C]) / ce.n_trees


@pytest.mark.parametrize("columns,missing", [(1, False), (3, True),
                                             (10, False)])
def test_the_subtrees_contributions_sum_to_the_uncut_walk(columns, missing):
    ens = forest(4 + columns, 5, (1, 600), columns, missing=missing)
    ce = ens.compile()
    assert ce.chained and ce.mean and ce.n_subtrees > ens.n_trees
    assert ce.leaves.shape[1] == ce.lanes == 128
    assert ce.deepest_leaf == ens.deepest_leaf
    Xb = rows_of(5, 300)
    np.testing.assert_allclose(
        tables_scores(ce, Xb), predict_proba_node_list(ens, Xb),
        atol=2e-6 * np.abs(ens.leaf_value).max())


# ---------------------------------------------------------------------- #
# kernel (interpreted), twin, reference
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("columns,leaves,missing", [
    (1, (1, 100), False),          # every tree one sub-tree
    (3, (100, 250), True),         # one or two
    (10, (1, 700), False),         # many, chains three deep
    (10, (300, 700), True),
])
def test_kernel_twin_and_reference_agree(columns, leaves, missing):
    ens = forest(10 + columns, 7, leaves, columns, missing=missing)
    Xb = rows_of(11, 333)          # a ragged last sub-tile
    want = predict_proba_node_list(ens, Xb)
    kernel, twin = scored(ens, Xb, "pallas"), scored(ens, Xb, "onehot")
    for got in (kernel, twin):
        assert got.shape == (333, columns) and got.dtype == np.float32
        np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(kernel, twin, atol=3e-7)
    np.testing.assert_allclose(ens.predict_raw(Xb, binned=True), want,
                               atol=1e-6)
    # every (row, tree) reaches the walk's leaf: a forest of one-hot
    # leaves counts them (the sum of 7 trees' one-hots is exact)
    marks = NodeListEnsemble(**{**vars(ens), "leaf_value": np.eye(
        ens.leaf_value.shape[1], dtype=np.float32)[None].repeat(7, 0)})
    hits = scored(marks, Xb, "pallas") * 7
    for t in range(7):
        leaf = leaf_of_rows_node_list(ens, t, Xb)
        assert (hits[np.arange(333), leaf] >= 1).all()
    np.testing.assert_array_equal(hits.sum(axis=1), np.full(333, 7.0))


def test_blocks_that_cut_through_a_tree_and_ragged_row_tiles(monkeypatch):
    """Three sub-trees a block and row tiles of 512: a tree's chain runs
    over several grid steps (the activity is kept in scratch over the block
    axis), the last block holds filler entries, the last row tile is ragged."""
    monkeypatch.setattr(predict_paths, "_MAX_TREES_PER_STEP", 3)
    monkeypatch.setattr(predict_paths, "TILE_ROWS", 512)
    ens = forest(21, 8, (1, 500), 3, dyadic=True)
    ce = ens.compile()
    plan = predict_paths.path_plan(
        ce.n_subtrees, 128, F, chain=predict_paths.chain_of(
            8, 3, ce.leaves.shape[2]))
    assert plan.trees_per_step in (2, 3) and plan.table_blocks > 4
    Xb = rows_of(22, 1100)
    want = predict_proba_node_list(ens, Xb).astype(np.float32)
    # dyadic leaf values, 8 trees: every sum and the mean are exact
    np.testing.assert_array_equal(scored(ens, Xb, "pallas"), want)
    np.testing.assert_array_equal(scored(ens, Xb, "onehot"), want)


def test_a_tall_scalar_tree_is_cut_and_keeps_its_margin(monkeypatch):
    """One output column and no mean: a tree of more lanes than one path
    matrix should hold takes the sub-tree form and answers the margin [R]
    (base + learning_rate x sum); a tree within the bound keeps today's
    uncut form."""
    rng = np.random.default_rng(31)
    meta = dict(learning_rate=0.1, base_score=0.5, loss="logloss")
    tall = random_node_list(rng, 3, 700, F, n_bins=BINS, **meta)
    short = random_node_list(rng, 3, 500, F, n_bins=BINS, **meta)
    assert tall.compile().chained and not tall.compile().mean
    assert not short.compile().chained
    assert short.compile().lanes == tree.PATH_UNCUT_LANES == 512
    Xb = rows_of(32, 200)
    for impl in ("pallas", "onehot"):
        got = scored(tall, Xb, impl)
        assert got.shape == (200,)
        np.testing.assert_allclose(got, tall.predict_raw(Xb, binned=True),
                                   atol=2e-6)


def test_the_plan_says_what_serves_and_the_rule_refuses_what_cannot_build():
    chain = predict_paths.chain_of(100, 10, 256)
    assert chain == predict_paths.Chain(100, 10, 128, 128)
    plan = predict_paths.path_plan(2015, 256, 784, 26, chain=chain,
                                   widest_tree=4864)
    # the MNIST forest's shape: 7 K-blocks x 2 + 4 + 2 + 2 tiles a sub-tree
    assert predict_paths.path_mxu_tiles_per_tree(256, 784, 1, 256) == 22
    assert (plan.path_mxu_tiles_per_tree, plan.subtrees_per_tree,
            plan.subtree_lanes, plan.leaf_columns, plan.class_dot_passes,
            plan.chain_mxu_tiles_per_tree, plan.select_k_blocks,
            plan.nodes_per_tree) == (443, 20.15, 256, 10, 3, 40, 7, 4864)
    assert plan.trees_per_step >= 4 and plan.table_blocks > 1
    assert plan.table_bytes == plan.trees_per_step * plan.table_blocks * (
        784 * 256 * 2 + 8 * 256 * 4 + 256 * 256 * 2 + 256 * 256 * 2)
    assert set(predict_paths.CHAIN_COUNTS) <= set(plan.root_counts())
    # one column and the widest leaf the rule takes; 128 columns are three
    # class tiles whose output windows do not fit beside a row tile: the
    # guard says so and `auto` takes the jax.numpy form
    assert predict_paths.predict_paths_fits(
        256, 28, chain=predict_paths.chain_of(1, 85, 256 + 128))
    assert not predict_paths.predict_paths_fits(
        256, 28, chain=predict_paths.chain_of(1, 128, 384 + 128))
    # an uncut model's plan says a tree is a sub-tree of its own
    flat = predict_paths.path_plan(500, 256, 28)
    assert (flat.subtrees_per_tree, flat.subtree_lanes, flat.leaf_columns,
            flat.chain_mxu_tiles_per_tree, flat.class_dot_passes) == (
                1.0, 256, 1, 0, 0)


def test_the_spans_say_the_subtree_form(monkeypatch):
    from ddt_tpu.backends import get_backend
    from ddt_tpu.telemetry import annotations as an

    ens = forest(41, 4, (300, 600), 10)
    be = get_backend(TrainConfig(backend="tpu", n_bins=BINS,
                                 predict_impl="pallas"))
    out = be.predict_raw(ens, rows_of(42, 50))
    assert out.shape == (50, 10)
    root = an.root_spans("predict")[-1]
    built = [s for s in root["spans"]
             if s["name"] == "ddt:predict:ensemble"][0]["counts"]
    assert list(built)[-5:] == list(predict_paths.CHAIN_COUNTS)
    assert built["subtrees_per_tree"] > 1 and built["subtree_lanes"] == 128
    assert built["leaf_columns"] == root["counts"]["classes"] == 10
    assert built["class_dot_passes"] == 3
    assert built["chain_mxu_tiles_per_tree"] == round(
        built["subtrees_per_tree"])
    for k in predict_paths.CHAIN_COUNTS + ("node_list",
                                           "path_mxu_tiles_per_tree"):
        assert root["counts"][k] == built[k]


# ---------------------------------------------------------------------- #
# scikit-learn's own forests
# ---------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def digits():
    from sklearn.datasets import load_digits

    X, y = load_digits(return_X_y=True)
    return X.astype(np.float32), y


def test_a_sklearn_classifier_agrees_with_its_own_predict_proba(digits):
    from sklearn.ensemble import RandomForestClassifier

    from ddt_tpu.models.lightgbm_io import threshold_bin_mapper
    from ddt_tpu.models.sklearn_io import from_sklearn

    X, y = digits
    rf = RandomForestClassifier(n_estimators=12, random_state=0).fit(
        X[:1200], y[:1200])
    ens = from_sklearn(rf)
    assert ens.loss == "mean" and ens.leaf_value.shape[2] == 10
    assert ens.n_trees == 12 and ens.n_features == 64
    assert not ens.has_bin_thresholds
    assert int(ens.n_leaves.max()) > 128         # cut, at one tile a sub-tree
    want = rf.predict_proba(X)
    np.testing.assert_allclose(ens.predict_raw(X), want, atol=1e-6)
    with pytest.raises(ValueError, match="raw thresholds only"):
        ens.compile()
    mapper = threshold_bin_mapper(ens, n_bins=256)
    for impl in ("pallas", "onehot"):
        proba = api.predict(ens, X, mapper=mapper, cfg=TrainConfig(
            backend="tpu", predict_impl=impl, n_bins=256))
        assert proba.shape == (len(X), 10) and proba.dtype == np.float32
        np.testing.assert_allclose(proba, want, atol=1e-6)
        np.testing.assert_array_equal(rf.classes_[proba.argmax(axis=1)],
                                      rf.predict(X))
    np.testing.assert_allclose(
        predict_proba_node_list(ens, mapper.transform(X)), want, atol=1e-6)


def test_a_sklearn_regressor_is_one_column_and_a_single_tree_a_forest(
        digits):
    from sklearn.ensemble import RandomForestRegressor
    from sklearn.tree import DecisionTreeClassifier

    from ddt_tpu.models.lightgbm_io import threshold_bin_mapper
    from ddt_tpu.models.sklearn_io import from_sklearn

    X, y = digits
    rr = RandomForestRegressor(n_estimators=5, random_state=1).fit(
        X[:800], y[:800])
    ens = from_sklearn(rr)
    assert ens.leaf_value.shape[2] == 1 and ens.n_classes == 1
    mapper = threshold_bin_mapper(ens, n_bins=256)
    out = api.predict(ens, X, mapper=mapper, cfg=TrainConfig(
        backend="tpu", predict_impl="pallas", n_bins=256))
    assert out.shape == (len(X), 1)
    np.testing.assert_allclose(out[:, 0], rr.predict(X), rtol=1e-6)
    one = from_sklearn(DecisionTreeClassifier(max_depth=4).fit(X, y))
    assert one.n_trees == 1 and one.leaf_value.shape[2] == 10

    class TwoOutputs:
        estimators_, n_outputs_, n_features_in_ = rr.estimators_, 2, 64

    with pytest.raises(ValueError, match="multi-output forest"):
        from_sklearn(TwoOutputs())
    with pytest.raises(ValueError, match="not a fitted scikit-learn"):
        from_sklearn(RandomForestRegressor())


def test_thresholds_round_down_to_float32():
    from ddt_tpu.models.sklearn_io import _float32_below

    t = np.array([0.5, 1 / 3, 0.1, 1e-8 + 1.0, -2 / 3, 16777217.0])
    below = _float32_below(t)
    assert below.dtype == np.float32 and (below.astype(np.float64) <= t).all()
    assert (np.nextafter(below, np.float32(np.inf)).astype(np.float64)
            > t).all()


# ---------------------------------------------------------------------- #
# round trips and refusals
# ---------------------------------------------------------------------- #

def test_round_trips_keep_the_vectors(tmp_path):
    ens = forest(51, 3, (1, 60), 4)
    back = ensemble_from_dict(ens.to_dict())
    assert isinstance(back, NodeListEnsemble) and back.loss == "mean"
    np.testing.assert_array_equal(back.leaf_value, ens.leaf_value)
    assert back.cache_token() == ens.cache_token()
    path = str(tmp_path / "forest.npz")
    ens.save(path)
    loaded = tree.TreeEnsemble.load(path)
    assert loaded.cache_token() == ens.cache_token()
    Xb = rows_of(52, 40)
    np.testing.assert_array_equal(loaded.predict(Xb, binned=True),
                                  ens.predict_raw(Xb, binned=True))
    # the token follows one entry of one leaf's vector, and the columns
    token = ens.cache_token()
    ens.leaf_value[1, 0, 2] += 1.0
    assert ens.cache_token() != token
    # the same bytes as [T, L, 4] and as [T, 2 L, 2] are two models
    flat = forest(53, 2, 9, 4)
    folded = NodeListEnsemble(**{**vars(flat), "n_classes": 2,
                                 "leaf_value": flat.leaf_value.reshape(
                                     2, 18, 2)})
    assert folded.cache_token() != flat.cache_token()
    assert "leaf=[" in ens.dump_text(0)


def test_cli_inspect_and_predict_read_a_saved_forest(tmp_path, capsys,
                                                     digits):
    from sklearn.ensemble import RandomForestClassifier

    from ddt_tpu.models.lightgbm_io import threshold_bin_mapper
    from ddt_tpu.models.sklearn_io import from_sklearn

    X, y = digits
    rf = RandomForestClassifier(n_estimators=4, random_state=2).fit(
        X[:600], y[:600])
    ens = from_sklearn(rf)
    mapper = threshold_bin_mapper(ens, n_bins=256)
    model, out = str(tmp_path / "f.npz"), str(tmp_path / "p.npy")
    api.save_model(model, ens, mapper)
    assert cli.main(["inspect", "--model", model, "--tree", "0"]) == 0
    said = capsys.readouterr().out
    head = json.loads(said.splitlines()[0])
    assert head["loss"] == "mean" and head["n_trees"] == 4
    assert head["max_depth"] == ens.deepest_leaf and head["n_classes"] == 10
    assert "leaf=[" in said
    data = str(tmp_path / "x.npz")
    np.savez(data, X=X[:200], y=y[:200])
    assert cli.main(["predict", "--model", model, "--data", data, "--out",
                     out, "--backend", "tpu"]) == 0
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert said["phases_ms"]["node_list"] == 1
    assert said["phases_ms"]["leaf_columns"] == 10
    got = np.load(out)
    np.testing.assert_allclose(got, rf.predict_proba(X[:200]), atol=1e-6)
    np.testing.assert_array_equal(rf.classes_[got.argmax(axis=1)],
                                  rf.predict(X[:200]))


def test_what_stays_refused_is_refused_by_name():
    ens = forest(71, 2, 9, 3)
    parts = vars(ens)
    with pytest.raises(ValueError, match="averaged forest"):
        NodeListEnsemble(**{**parts, "loss": "mse"})
    with pytest.raises(ValueError, match="averaged forest"):
        NodeListEnsemble(**{**parts, "learning_rate": 0.1})
    with pytest.raises(ValueError, match="averaged forest"):
        NodeListEnsemble(**{**parts, "leaf_value": ens.leaf_value[:, :, 0]})
    with pytest.raises(ValueError, match="softmax"):
        NodeListEnsemble(**{**parts, "loss": "softmax"})
    with pytest.raises(ValueError, match="vector leaves"):
        ens.to_lightgbm_text()
    with pytest.raises(ValueError, match="round-major trees"):
        tree._refuse_routes("from_heap", categories=False, classes=True)
    with pytest.raises(ValueError, match="category-set"):
        tree._refuse_routes("from_heap", categories=True, classes=False)
    with pytest.raises(ValueError, match="binned"):
        api.predict(ens, rows_of(72, 8).astype(np.float32), cfg=TrainConfig(
            backend="tpu", n_bins=BINS))
