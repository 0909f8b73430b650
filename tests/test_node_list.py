"""The NODE-LIST ensemble layout and the path-matrix scoring form, held to the
plain walk of `ddt_tpu/reference/numpy_predict.py` on seeded random leaf-wise
trees (CPU, small sizes, the Pallas kernel interpreted)."""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.models import lightgbm_io
from ddt_tpu.models.tree import (NodeListEnsemble, TreeEnsemble,
                                 empty_ensemble, node_list_from_trees,
                                 random_node_list)
from ddt_tpu.reference import numpy_predict


def leafwise_ensemble(seed, n_trees, n_leaves, n_features, dyadic=True,
                      learning_rate=0.5):
    return random_node_list(
        np.random.default_rng(seed), n_trees, n_leaves, n_features,
        dyadic=dyadic, learning_rate=learning_rate, base_score=0.25,
        loss="logloss")


def rows(seed, n, n_features, n_bins=255):
    return np.random.default_rng(seed).integers(
        0, n_bins, (n, n_features)).astype(np.uint8)


# ------------------------------------------------------------------ #
# the reference walk
# ------------------------------------------------------------------ #

def hand_built():
    """n0: f0 <= 3 ? n1 : n2;  n1: f1 <= 5 ? L0 : n3;  n2: f0 <= 7 ? L1 : L2;
    n3: f2 <= 1 ? L3 : L4        (5 leaves, the deepest 3 nodes down)"""
    nodes = [(0, 3, 0.0, 0.0, 1, 2), (1, 5, 0.0, 0.0, ~0, 3),
             (0, 7, 0.0, 0.0, ~1, ~2), (2, 1, 0.0, 0.0, ~3, ~4)]
    return node_list_from_trees(
        [(nodes, [10.0, 20.0, 30.0, 40.0, 50.0])], n_features=3,
        learning_rate=0.1, base_score=1.0, loss="mse")


def test_reference_walk_on_a_hand_built_tree():
    ens = hand_built()
    Xb = np.array([[3, 5, 9], [0, 6, 1], [3, 6, 2], [4, 0, 0], [8, 0, 0],
                   [7, 200, 200]], np.uint8)
    want_leaf = [0, 3, 4, 1, 2, 1]
    assert list(numpy_predict.leaf_of_rows_node_list(ens, 0, Xb)) == want_leaf
    want = 1.0 + 0.1 * np.array([10.0, 40.0, 50.0, 20.0, 30.0, 20.0])
    for dtype in (np.float32, np.float64):
        got = numpy_predict.predict_raw_node_list(ens, Xb, dtype)
        assert got.dtype == dtype
        np.testing.assert_allclose(got, want, rtol=1e-6)
    # the model's own walk is the same walk
    np.testing.assert_array_equal(ens._leaf_np(Xb, True)[0], want_leaf)
    assert ens.deepest_leaf == 3 and ens.n_splits == 4


def test_a_tree_of_one_leaf_scores_its_leaf():
    ens = node_list_from_trees(
        [([], [2.5]), ([(0, 4, 0.0, 0.0, ~0, ~1)], [1.0, -1.0])],
        n_features=2, learning_rate=1.0, base_score=0.0, loss="mse")
    Xb = np.array([[4, 0], [5, 0]], np.uint8)
    want = np.array([3.5, 1.5], np.float32)
    np.testing.assert_array_equal(
        numpy_predict.predict_raw_node_list(ens, Xb), want)
    np.testing.assert_array_equal(ens.predict_raw(Xb, binned=True), want)
    cfg = TrainConfig(backend="tpu", predict_impl="pallas")
    np.testing.assert_array_equal(
        api.predict(ens, Xb, binned=True, raw=True, cfg=cfg), want)


# ------------------------------------------------------------------ #
# the path matrix
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("n_leaves", [2, 15, 255])
def test_path_matrix_hits_one_leaf_a_row(n_leaves):
    """m = s @ P equals len for exactly one leaf a (row, tree): the one the
    walk reaches."""
    ens = leafwise_ensemble(3, 4, n_leaves, 9)
    Xb = rows(4, 300, 9)
    P, plen = ens.path_matrix()
    assert set(np.unique(P)) <= {-1, 0, 1}
    assert (plen[:, :n_leaves] >= 1).all()
    for t in range(ens.n_trees):
        v = Xb[:, ens.feature[t]].astype(np.int64)             # [R, N]
        s = np.where(v > ens.threshold_bin[t][None, :], 1, -1)
        m = s @ P[t].astype(np.int64)                           # [R, L]
        hit = m == plen[t][None, :]
        assert (hit.sum(axis=1) == 1).all()
        np.testing.assert_array_equal(
            hit.argmax(axis=1),
            numpy_predict.leaf_of_rows_node_list(ens, t, Xb))
    # its compiled tables: padded lanes never hit, padded nodes never right
    ce = ens.compile()
    assert ce.lanes == -(-n_leaves // 128) * 128
    assert (np.asarray(ce.planes[:, 1, n_leaves:]) == -1).all()
    assert (np.asarray(ce.planes[:, 0, n_leaves - 1:]) > 255).all()
    assert ce.deepest_leaf == ens.deepest_leaf == int(plen.max())


def test_a_broken_node_list_is_refused():
    ens = hand_built()
    ens.left_child[0, 3] = ~0            # leaf 0 gets a second parent
    with pytest.raises(ValueError, match="exactly one parent"):
        ens.path_matrix()
    ens = hand_built()
    ens.right_child[0, 3] = 0            # back at the root
    with pytest.raises(ValueError, match="outside its tree"):
        ens.path_matrix()


# ------------------------------------------------------------------ #
# api.predict: the kernel (interpreted) and the jax.numpy form
# ------------------------------------------------------------------ #

SHAPES = [(1, 2, 3), (9, 15, 28), (130, 255, 70)]


@pytest.mark.parametrize("impl", ["pallas", "onehot"])
@pytest.mark.parametrize("dyadic", [True, False],
                         ids=["dyadic", "random_leaves"])
@pytest.mark.parametrize("n_trees,n_leaves,n_features", SHAPES)
def test_api_predict_agrees_with_the_reference(n_trees, n_leaves,
                                               n_features, dyadic, impl):
    """predict_impl="pallas" demands the kernel (interpreted off-TPU),
    "onehot" refuses it: the jax.numpy form. Dyadic leaf values sum
    without rounding in any order: equality; random ones to 1e-6 of the
    score's scale."""
    ens = leafwise_ensemble(11, n_trees, n_leaves, n_features, dyadic)
    Xb = rows(12, 700, n_features)
    want = numpy_predict.predict_raw_node_list(ens, Xb, np.float64)
    cfg = TrainConfig(backend="tpu", predict_impl=impl)
    got = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    assert got.dtype == np.float32 and got.shape == (700,)
    if dyadic:
        np.testing.assert_array_equal(got, want.astype(np.float32))
    else:
        assert np.abs(got - want).max() <= 1e-6 * max(
            1.0, np.abs(want).max())
    # probabilities through the same path
    prob = api.predict(ens, Xb, binned=True, cfg=cfg)
    np.testing.assert_allclose(prob, 1 / (1 + np.exp(-got)), rtol=1e-6)
    # the NumPy backend walks
    np.testing.assert_allclose(
        api.predict(ens, Xb, binned=True, raw=True,
                    cfg=TrainConfig(backend="cpu")), want, atol=2e-5)


def test_rows_past_one_tile_and_the_compiled_cache():
    """More rows than a row tile (several grid steps over the rows, the
    last one padded), and a second call served from the compiled-ensemble
    cache."""
    from ddt_tpu.ops import predict_paths
    from ddt_tpu.telemetry import counters

    ens = leafwise_ensemble(21, 5, 40, 11)
    Xb = rows(22, predict_paths.TILE_ROWS + 300, 11)
    cfg = TrainConfig(backend="tpu", predict_impl="pallas")
    want = numpy_predict.predict_raw_node_list(ens, Xb)
    got = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    np.testing.assert_array_equal(got, want)
    c0 = counters.snapshot()
    again = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    assert counters.delta(c0)["compiled_ensemble_cache_hits"] == 1
    np.testing.assert_array_equal(again, got)
    # a changed leaf value is another model
    ens.leaf_value[0, 0] += 1.0
    assert not np.array_equal(
        api.predict(ens, Xb, binned=True, raw=True, cfg=cfg), got)


def test_a_full_heap_ensemble_scores_the_same_as_a_node_list():
    """Ties the new form to the old: the same full trees through the heap
    kernel and, converted, through the path form."""
    rng = np.random.default_rng(5)
    T, depth, F = 20, 5, 28
    heap = empty_ensemble(T, depth, F, 0.5, 0.125, "logloss")
    n_int = 2 ** depth - 1
    heap.feature[:, :n_int] = rng.integers(0, F, (T, n_int))
    heap.threshold_bin[:, :n_int] = rng.integers(0, 254, (T, n_int))
    heap.is_leaf[:, n_int:] = True
    heap.leaf_value[:, n_int:] = rng.integers(-16, 17, (T, n_int + 1)) / 8.0
    nl = NodeListEnsemble.from_heap(heap)
    assert (nl.n_leaves == 2 ** depth).all() and nl.deepest_leaf == depth
    Xb = rows(6, 900, F)
    cfg = TrainConfig(backend="tpu", predict_impl="pallas")
    by_heap = api.predict(heap, Xb, binned=True, raw=True, cfg=cfg)
    by_list = api.predict(nl, Xb, binned=True, raw=True, cfg=cfg)
    np.testing.assert_array_equal(by_list, by_heap)
    np.testing.assert_array_equal(by_list,
                                  heap.predict_raw(Xb, binned=True))
    # an early-stopped heap (a leaf above the bottom level) converts too
    heap.is_leaf[0, 1] = True
    heap.leaf_value[0, 1] = 3.0
    nl = NodeListEnsemble.from_heap(heap)
    assert nl.n_leaves[0] == 2 ** (depth - 1) + 1
    np.testing.assert_array_equal(
        api.predict(nl, Xb, binned=True, raw=True, cfg=cfg),
        heap.predict_raw(Xb, binned=True))


# ------------------------------------------------------------------ #
# LightGBM text, and the mapper from the model's own thresholds
# ------------------------------------------------------------------ #

def leafwise_raw_model(seed=31, n_trees=6, n_leaves=60, n_features=8):
    """A leaf-wise model as LightGBM would hand it over: raw thresholds,
    final leaf values, a chain down one side so the deepest leaf is far
    past what a heap import takes."""
    rng = np.random.default_rng(seed)
    ens = random_node_list(rng, n_trees, n_leaves, n_features,
                           learning_rate=1.0, base_score=0.0, loss="logloss",
                           has_raw_thresholds=True, has_bin_thresholds=False)
    # tree 0: a chain of 14 nodes, each one's right child a leaf
    chain = np.arange(14)
    ens.n_leaves[0] = 15
    ens.feature[0, :14] = rng.integers(n_features, size=14)
    ens.left_child[0, :14] = np.where(chain < 13, chain + 1, ~0)
    ens.right_child[0, :14] = ~(chain + 1)
    ens.feature[0, 14:], ens.leaf_value[0, 15:] = -1, 0.0   # unused slots
    ens.left_child[0, 14:] = ens.right_child[0, 14:] = 0
    live = ens.live_nodes
    ens.threshold_raw[live] = rng.standard_normal(int(live.sum())).round(2)
    return ens


def test_lightgbm_text_round_trip_and_the_threshold_mapper():
    src = leafwise_raw_model()
    assert src.deepest_leaf > 12
    text = src.to_lightgbm_text()
    ens = TreeEnsemble.from_lightgbm_text(text)
    assert isinstance(ens, NodeListEnsemble)
    assert ens.deepest_leaf == src.deepest_leaf > lightgbm_io.HEAP_MAX_DEPTH
    for k in ("feature", "left_child", "right_child", "n_leaves",
              "threshold_raw", "leaf_value"):
        np.testing.assert_array_equal(getattr(ens, k), getattr(src, k))
    assert ens.to_lightgbm_text() == text
    X = np.random.default_rng(32).standard_normal((800, 8)).astype(np.float32)
    X[:50, 0] = src.threshold_raw[1, 0]         # rows ON a threshold
    on_host = ens.predict_raw(X)                 # the walk over raw values
    np.testing.assert_array_equal(on_host, src.predict_raw(X))
    # binned scoring is refused until the thresholds are ranked
    with pytest.raises(ValueError, match="raw thresholds only"):
        ens.predict_raw(np.zeros((2, 8), np.uint8), binned=True)
    mapper = lightgbm_io.threshold_bin_mapper(ens)
    assert mapper.n_bins == 255 and ens.has_bin_thresholds
    Xb = mapper.transform(X)
    # x <= t  is  bin(x) <= rank(t), exactly: the same leaf everywhere
    np.testing.assert_array_equal(ens._leaf_np(Xb, True),
                                  ens._leaf_np(X, False))
    for impl in ("pallas", "onehot"):
        cfg = TrainConfig(backend="tpu", predict_impl=impl)
        got = api.predict(ens, X, mapper=mapper, raw=True, cfg=cfg)
        assert np.abs(got - on_host).max() <= 1e-6 * max(
            1.0, np.abs(on_host).max())


def test_threshold_mapper_refuses_more_thresholds_than_bins():
    ens = leafwise_raw_model()
    with pytest.raises(ValueError, match="distinct thresholds"):
        lightgbm_io.threshold_bin_mapper(ens, n_bins=4)


def test_a_shallow_import_stays_a_heap():
    """The shape decides: what the heap kernel serves imports as a heap."""
    from ddt_tpu.ops import predict_pallas

    d = lightgbm_io.HEAP_MAX_DEPTH
    assert predict_pallas.predict_pallas_fits(d, 500, 1, None, 0)
    assert not predict_pallas.predict_pallas_fits(d + 1, 3, 1, None, 0)
    src = leafwise_ensemble(41, 3, 6, 5)
    src.has_raw_thresholds = True
    src.threshold_raw[:] = src.threshold_bin
    ens = TreeEnsemble.from_lightgbm_text(src.to_lightgbm_text())
    assert isinstance(ens, TreeEnsemble) and ens.max_depth <= 5
    X = rows(42, 200, 5).astype(np.float32)
    np.testing.assert_allclose(ens.predict_raw(X), src.predict_raw(X),
                               rtol=1e-6)


def test_routed_and_multiclass_node_lists_are_refused_by_name():
    heap = empty_ensemble(2, 2, 4, 0.1, 0.0, "logloss", missing_bin=True,
                          n_bins=255)
    heap.is_leaf[:, 0] = True
    with pytest.raises(ValueError, match="default directions for missing"):
        NodeListEnsemble.from_heap(heap)
    heap = empty_ensemble(2, 2, 4, 0.1, 0.0, "logloss", cat_features=(1,))
    heap.is_leaf[:, 0] = True
    with pytest.raises(ValueError, match="category-set"):
        NodeListEnsemble.from_heap(heap)
    heap = empty_ensemble(3, 2, 4, 0.1, 0.0, "softmax", n_classes=3)
    heap.is_leaf[:, 0] = True
    with pytest.raises(ValueError, match="several classes"):
        NodeListEnsemble.from_heap(heap)
    src = hand_built()
    with pytest.raises(ValueError, match="several classes"):
        dataclasses.replace(src, loss="softmax", n_classes=3)
    # a LightGBM text too deep for any heap, with NaN default directions
    deep = leafwise_raw_model()
    chain = 40
    nodes = [(0, 0, float(k), 0.0, (k + 1) if k < chain - 1 else ~0,
              ~(k + 1)) for k in range(chain)]
    deep = node_list_from_trees(
        [(nodes, [0.0] * (chain + 1))], n_features=2, learning_rate=1.0,
        base_score=0.0, loss="logloss", has_raw_thresholds=True)
    text = deep.to_lightgbm_text().replace(
        "decision_type=" + " ".join(["0"] * chain),
        "decision_type=" + " ".join(["10"] * chain))
    with pytest.raises(ValueError, match="default directions for missing"):
        TreeEnsemble.from_lightgbm_text(text)


# ------------------------------------------------------------------ #
# the normal path: save, load, cli
# ------------------------------------------------------------------ #

def test_save_load_and_cli_predict(tmp_path, capsys):
    from ddt_tpu.cli import main

    ens = leafwise_raw_model()
    mapper = lightgbm_io.threshold_bin_mapper(ens)
    path = str(tmp_path / "lgbm.npz")
    api.save_model(path, ens, mapper=mapper)
    bundle = api.load_model(path)
    assert isinstance(bundle.ensemble, NodeListEnsemble)
    assert bundle.ensemble.cache_token() == ens.cache_token()
    assert isinstance(TreeEnsemble.load(path), NodeListEnsemble)
    X = np.random.default_rng(7).standard_normal((300, 8)).astype(np.float32)
    cfg = TrainConfig(backend="tpu", predict_impl="pallas")
    np.testing.assert_array_equal(
        api.predict(bundle, X, raw=True, cfg=cfg),
        api.predict(ens, X, mapper=mapper, raw=True, cfg=cfg))
    data = str(tmp_path / "rows.npz")
    np.savez(data, X=X, y=np.zeros(len(X), np.float32))
    assert main(["predict", "--backend=tpu", f"--model={path}",
                 f"--data={data}"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["rows"] == 300 and rec["trees"] == ens.n_trees
    # a CPU's auto dispatch takes the jax.numpy form: the model's lanes and
    # depth, no table block
    assert rec["phases_ms"]["node_list"] == 1
    assert rec["phases_ms"]["deepest_leaf"] == ens.deepest_leaf
    assert rec["phases_ms"]["trees_per_step"] == 0
    assert main(["inspect", f"--model={path}", "--tree=0"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.splitlines()[0])["n_splits"] == ens.n_splits
    assert "leaf=" in out


def test_backend_entry_says_which_kernel_serves():
    """What the benchmark's job asks first: the ensemble span of a node
    list says node_list 1, and the forced kernel's plan has blocks."""
    from ddt_tpu.telemetry.annotations import recent_spans

    ens = leafwise_ensemble(51, 20, 255, 28)
    be = get_backend(TrainConfig(backend="tpu", predict_impl="pallas"))
    be._predict_fn(ens)
    counts = [sp for sp in recent_spans()
              if sp["name"] == "ddt:predict:ensemble"][-1]["counts"]
    assert counts["node_list"] == 1 and counts["trees"] == 20
    assert counts["nodes_per_tree"] == counts["leaves_per_tree"] == 256
    assert counts["path_mxu_tiles_per_tree"] == 6
    assert counts["trees_per_step"] * counts["table_blocks"] >= 20
    assert counts["table_bytes"] >= 20 * 256 * 256 * 2
