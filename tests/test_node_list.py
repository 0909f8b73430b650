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


def test_category_and_multiclass_node_lists_are_refused_by_name():
    """What a node list still cannot carry is refused with the mechanism
    named; learned NaN directions are NOT among them any more, and since
    PR 50 neither are several classes (tests/test_xgboost.py)."""
    heap = empty_ensemble(2, 2, 4, 0.1, 0.0, "logloss", missing_bin=True,
                          n_bins=255)
    heap.is_leaf[:, 0] = True
    assert NodeListEnsemble.from_heap(heap).n_trees == 2
    # a heap's one-vs-rest category node is a set of ONE bit (PR 55) ...
    heap = empty_ensemble(2, 2, 4, 0.1, 0.0, "logloss", cat_features=(1,),
                          n_bins=255)
    heap.is_leaf[:, 1:3] = True
    heap.feature[:, 0], heap.threshold_bin[:, 0] = 1, 7
    heap.leaf_value[:, 1], heap.leaf_value[:, 2] = 1.0, -1.0
    one_bit = NodeListEnsemble.from_heap(heap)
    assert one_bit.has_cat_splits and one_bit.cat_nodes.sum() == 2
    assert one_bit.cat_set_bits().sum(axis=1).tolist() == [1, 1]
    Xc = np.zeros((3, 4), np.uint8)
    Xc[:, 1] = (7, 8, 254)
    np.testing.assert_array_equal(one_bit.predict_raw(Xc, binned=True),
                                  heap.predict_raw(Xc, binned=True))
    # ... and one that sends NaN LEFT is refused by its new name
    routed_cat = empty_ensemble(2, 2, 4, 0.1, 0.0, "logloss",
                                cat_features=(1,), missing_bin=True,
                                n_bins=255)
    routed_cat.is_leaf[:, 1:3] = True
    routed_cat.feature[:, 0] = 1
    routed_cat.default_left[:, 0] = True
    with pytest.raises(ValueError, match="sends NaN LEFT"):
        NodeListEnsemble.from_heap(routed_cat)
    heap = empty_ensemble(3, 2, 4, 0.1, 0.0, "softmax", n_classes=3)
    heap.is_leaf[:, 0] = True
    assert NodeListEnsemble.from_heap(heap).leaf_columns == 3
    src = hand_built()
    assert dataclasses.replace(src, loss="softmax",
                               n_classes=3).predict_raw(
        np.zeros((2, src.n_features), np.uint8), binned=True).shape == (2, 3)
    with pytest.raises(ValueError, match="no class count"):
        dataclasses.replace(src, loss="softmax", n_classes=1)
    # directions without the bin count they route by
    with pytest.raises(ValueError, match="learned NaN directions need"):
        dataclasses.replace(src, missing_bin=True,
                            default_left=np.zeros((1, 4), bool))
    with pytest.raises(ValueError, match="learned NaN directions need"):
        dataclasses.replace(src, missing_bin=True, n_bins=255,
                            default_left=np.zeros((1, 3), bool))
    # a LightGBM text too deep for any heap, with a category node: a node
    # list with the set as it is (PR 55; refused before), scored as the
    # reference walk does
    chain = 40
    nodes = [(0, 0, float(k), 0.0, (k + 1) if k < chain - 1 else ~0,
              ~(k + 1)) for k in range(chain)]
    deep = node_list_from_trees(
        [(nodes, [0.0] * (chain + 1))], n_features=2, learning_rate=1.0,
        base_score=0.0, loss="logloss", has_raw_thresholds=True)
    text = deep.to_lightgbm_text()
    cat = text.replace(
        "decision_type=" + " ".join(["0"] * chain),
        "decision_type=" + " ".join(["1"] + ["0"] * (chain - 1))).replace(
            "num_cat=0", "num_cat=1").replace(
                "is_linear=0", "cat_boundaries=0 1\ncat_threshold=6\n"
                "is_linear=0").replace(
                    "split_feature=" + " ".join(["0"] * chain),
                    "split_feature=" + " ".join(["1"] + ["0"] * (chain - 1)))
    kept = TreeEnsemble.from_lightgbm_text(cat)
    assert isinstance(kept, NodeListEnsemble) and kept.cat_nodes.sum() == 1
    assert lightgbm_io._set_ids(kept, 0).tolist() == [1, 2]     # word 6
    mapper = lightgbm_io.threshold_bin_mapper(kept)
    Xr = np.asarray([[0.0, 1.0], [5.0, 2.0], [7.5, 3.0], [3.0, np.nan],
                     [50.0, -1.0], [0.0, 99.0], [39.0, 2.0]], np.float32)
    want = numpy_predict.predict_raw_node_list(kept, mapper.transform(Xr))
    np.testing.assert_array_equal(kept.predict_raw(Xr), want)
    np.testing.assert_array_equal(api.predict(
        kept, Xr, mapper=mapper, raw=True,
        cfg=TrainConfig(backend="tpu", predict_impl="pallas")), want)
    # ... and the same text with NaN default directions is a node list now
    routed = TreeEnsemble.from_lightgbm_text(text.replace(
        "decision_type=" + " ".join(["0"] * chain),
        "decision_type=" + " ".join(["10", "8"] * (chain // 2))))
    assert isinstance(routed, NodeListEnsemble) and routed.missing_routes
    assert routed.default_left[0].tolist() == [True, False] * (chain // 2)


# ------------------------------------------------------------------ #
# learned NaN directions
# ------------------------------------------------------------------ #

NAN = 254


def hand_built_routed():
    """`hand_built` with directions: NaN goes left at n0 and n3, right at
    n1 and n2."""
    src = hand_built()
    return dataclasses.replace(
        src, missing_bin=True, n_bins=255,
        default_left=np.array([[True, False, False, True]]))


def test_reference_walk_follows_the_nan_directions():
    ens = hand_built_routed()
    Xb = np.array([[NAN, 5, 9],        # n0 NaN -> left n1; 5 <= 5 -> L0
                   [NAN, NAN, NAN],    # n1 NaN -> right n3; n3 NaN -> L3
                   [NAN, NAN, 2],      # n3: 2 > 1 -> L4
                   [4, 0, 0],          # n0 right n2; 4 <= 7 -> L1
                   [3, NAN, 0],        # left n1, NaN right n3, 0 <= 1 -> L3
                   [200, 0, 0]],       # n2: 200 > 7 -> L2
                  np.uint8)
    want_leaf = [0, 3, 4, 1, 3, 2]
    assert list(numpy_predict.leaf_of_rows_node_list(ens, 0, Xb)) == want_leaf
    assert list(ens._leaf_np(Xb, True)[0]) == want_leaf
    # n2's default is right: a NaN there is leaf 2, where the plain compare
    # of an unrouted model sends it too (the NaN bin is above 7)
    x = np.array([[NAN, 0, 0]], np.uint8)
    ens.default_left[0, 0] = False
    assert numpy_predict.leaf_of_rows_node_list(ens, 0, x)[0] == 2
    assert numpy_predict.leaf_of_rows_node_list(hand_built(), 0, x)[0] == 2
    # without `missing_bin` the directions are not read (the heap's rule)
    off = dataclasses.replace(hand_built_routed(), missing_bin=False)
    assert not off.missing_routes and off.missing_bin_value == -1
    np.testing.assert_array_equal(
        numpy_predict.predict_raw_node_list(off, Xb),
        numpy_predict.predict_raw_node_list(hand_built(), Xb))
    # raw rows: NaN itself follows the direction
    raw = dataclasses.replace(hand_built_routed(), has_raw_thresholds=True)
    raw.threshold_raw[:] = raw.threshold_bin
    Xf = np.array([[np.nan, 5.0, 9.0], [np.nan, np.nan, np.nan]], np.float32)
    assert list(raw._leaf_np(Xf, False)[0]) == [0, 3]


@pytest.mark.parametrize("n_leaves", [2, 15, 255])
def test_path_matrix_with_the_folded_nan_route(n_leaves):
    """`thr < bin < up` (the compiled tables' two thresholds) is the
    walk's answer at every node, and the path matrix then picks the
    walk's leaf, alone."""
    ens = random_node_list(np.random.default_rng(9), 3, n_leaves, 5,
                           dyadic=True, missing=True, learning_rate=0.5,
                           base_score=0.0, loss="logloss")
    Xb = rows(10, 400, 5)
    Xb[np.random.default_rng(11).random(Xb.shape) < 0.5] = NAN
    P, plen = ens.path_matrix()
    ce = ens.compile()
    assert ce.missing_bin_value == NAN
    N = ens.feature.shape[1]
    for t in range(3):
        thr, up = ce.planes[t, 0, :N], ce.planes[t, 3, :N]
        np.testing.assert_array_equal(
            up == NAN, ens.default_left[t] & ens.live_nodes[t])
        v = Xb[:, np.maximum(ens.feature[t], 0)].astype(np.float32)
        s = np.where((v > thr) & (v < up), 1, -1)             # [R, N]
        hit = (s @ P[t].astype(np.int64)) == plen[t][None, :]
        assert (hit.sum(axis=1) == 1).all()
        np.testing.assert_array_equal(
            hit.argmax(axis=1),
            numpy_predict.leaf_of_rows_node_list(ens, t, Xb))
    # a threshold in the NaN bin cannot be told from the route: refused
    ens.threshold_bin[0, 0] = NAN
    with pytest.raises(ValueError, match="the NaN bin"):
        ens.compile()


ROUTE_SHAPES = [(F, R) for F in (28, 129, 300) for R in (1, 257, 1000)]


@pytest.mark.parametrize("missing", [False, True], ids=["plain", "nan"])
@pytest.mark.parametrize("n_features,n_rows", ROUTE_SHAPES)
def test_kernel_and_twin_agree_with_the_walk(n_features, n_rows, missing):
    """The interpreted kernel and the jax.numpy form at 1, 2 and 3 K-blocks
    of the select (the last one partial), with and without the NaN route,
    against the plain walk: bit-equal on dyadic leaf values."""
    rng = np.random.default_rng(n_features + n_rows)
    ens = random_node_list(rng, 7, 40, n_features, dyadic=True,
                           missing=missing, learning_rate=0.5,
                           base_score=0.25, loss="logloss")
    Xb = rows(13, n_rows, n_features, 254)
    Xb[rng.random(Xb.shape) < 0.5] = NAN     # a bin like any, unrouted
    want = numpy_predict.predict_raw_node_list(
        ens, Xb, np.float64).astype(np.float32)
    np.testing.assert_array_equal(ens.predict_raw(Xb, binned=True), want)
    for impl in ("pallas", "onehot"):
        got = api.predict(ens, Xb, binned=True, raw=True,
                          cfg=TrainConfig(backend="tpu", predict_impl=impl))
        np.testing.assert_array_equal(got, want, err_msg=impl)


def test_int32_rows_score_as_uint8_rows_do():
    """The kernel takes the rows at the width they come in."""
    import jax.numpy as jnp

    from ddt_tpu.ops import predict_paths

    ens = random_node_list(np.random.default_rng(3), 5, 30, 140,
                           dyadic=True, missing=True, learning_rate=0.5,
                           base_score=0.0, loss="logloss")
    ce = ens.compile()
    Xb = rows(14, 600, 140)
    outs = [np.asarray(predict_paths.predict_paths_pallas(
        *map(jnp.asarray, ce.arrays()), jnp.asarray(Xb.astype(dt)),
        learning_rate=0.5, base=0.0, missing_routes=True))
        for dt in (np.uint8, np.int32, np.int16)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
    np.testing.assert_array_equal(
        outs[0], numpy_predict.predict_raw_node_list(ens, Xb))


def test_a_missing_routed_heap_converts_with_its_directions():
    """heap -> node list of a model with learned NaN directions: the same
    scores as the heap's own reference and the heap kernel."""
    rng = np.random.default_rng(5)
    T, depth, F = 12, 4, 9
    heap = empty_ensemble(T, depth, F, 0.5, 0.125, "logloss",
                          missing_bin=True, n_bins=255)
    n_int = 2 ** depth - 1
    heap.feature[:, :n_int] = rng.integers(0, F, (T, n_int))
    heap.threshold_bin[:, :n_int] = rng.integers(0, 253, (T, n_int))
    heap.default_left[:, :n_int] = rng.random((T, n_int)) < 0.5
    heap.is_leaf[:, n_int:] = True
    heap.leaf_value[:, n_int:] = rng.integers(-16, 17, (T, n_int + 1)) / 8.0
    heap.is_leaf[0, 2] = True                 # an early leaf
    heap.leaf_value[0, 2] = 1.5
    nl = NodeListEnsemble.from_heap(heap)
    assert nl.missing_routes and nl.missing_bin_value == NAN
    Xb = rows(6, 900, F, 254)
    Xb[rng.random(Xb.shape) < 0.4] = NAN
    want = numpy_predict.predict_raw(heap, Xb)
    np.testing.assert_array_equal(
        numpy_predict.predict_raw_node_list(nl, Xb), want)
    cfg = TrainConfig(backend="tpu", predict_impl="pallas")
    np.testing.assert_array_equal(
        api.predict(nl, Xb, binned=True, raw=True, cfg=cfg),
        api.predict(heap, Xb, binned=True, raw=True, cfg=cfg))
    # flipping one direction is another model: token and scores
    token = nl.cache_token()
    nl.default_left[0, 0] ^= True
    assert nl.cache_token() != token


def test_nan_routed_save_load_token_and_cli(tmp_path, capsys):
    """A NaN-routed node list as an import hands it over (raw thresholds),
    ranked by its own mapper, saved, loaded and scored through api.predict
    and `cli predict` over float rows with NaN in them."""
    from ddt_tpu.cli import main

    rng = np.random.default_rng(8)
    ens = random_node_list(rng, 6, 60, 8, missing=True, learning_rate=0.3,
                           base_score=0.1, loss="logloss",
                           has_raw_thresholds=True, has_bin_thresholds=False)
    live = ens.live_nodes
    ens.threshold_raw[live] = rng.standard_normal(int(live.sum())).round(2)
    plain = dataclasses.replace(ens, default_left=None, missing_bin=False)
    assert plain.cache_token() != ens.cache_token()
    mapper = lightgbm_io.threshold_bin_mapper(ens)
    assert mapper.missing_bin and ens.missing_bin_value == NAN
    assert int(ens.threshold_bin[live].max()) < NAN - 1
    path = str(tmp_path / "routed.npz")
    api.save_model(path, ens, mapper=mapper)
    bundle = api.load_model(path)
    back = bundle.ensemble
    assert isinstance(back, NodeListEnsemble) and back.missing_routes
    np.testing.assert_array_equal(back.default_left, ens.default_left)
    assert back.cache_token() == ens.cache_token()
    X = rng.standard_normal((500, 8)).astype(np.float32)
    X[rng.random(X.shape) < 0.5] = np.nan
    assert (mapper.transform(X) == NAN).mean() > 0.4
    cfg = TrainConfig(backend="tpu", predict_impl="pallas")
    got = api.predict(bundle, X, raw=True, cfg=cfg)
    want = ens.predict_raw(X)                 # the raw walk: NaN itself
    assert np.abs(got - want).max() <= 1e-6 * max(1.0, np.abs(want).max())
    assert np.abs(got - plain.predict_raw(X)).max() > 0.01
    assert "nan->" in back.dump_text(0) and "nan->" not in plain.dump_text(0)
    # an unrouted artifact saved before this field existed still loads
    d = plain.to_dict()
    d.pop("missing_bin")
    assert not NodeListEnsemble.from_dict(d).missing_routes
    data = str(tmp_path / "rows.npz")
    np.savez(data, X=X, y=np.zeros(len(X), np.float32))
    assert main(["predict", "--backend=tpu", f"--model={path}",
                 f"--data={data}"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["rows"] == 500 and rec["phases_ms"]["missing_routes"] == 1
    assert rec["phases_ms"]["select_k_blocks"] == 1


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
    assert counts["path_mxu_tiles_per_tree"] == 5
    assert counts["select_nodes_per_lane"] == 2
    assert counts["trees_per_step"] * counts["table_blocks"] >= 20
    assert counts["table_bytes"] >= 20 * 256 * 256 * 2


# ------------------------------------------------------------------ #
# category sets (PR 55): LightGBM's categorical splits in a node list
# ------------------------------------------------------------------ #

# 6 columns of 3 to 300 categories (a column of 300 ids names 255 at most)
CAT_COLUMNS = ((0, 3), (1, 17), (2, 60), (3, 130), (4, 255), (5, 300))
# the Allstate claims model's 32 columns (benchmark config
# allstate-lgbm-500t-255l-cat): 16 categorical, with the ids a set may name
ALLSTATE_COLUMNS = ((3, 75), (4, 254), (5, 254), (6, 10), (7, 3), (8, 6),
                    (9, 3), (10, 3), (11, 6), (12, 4), (13, 4), (14, 2),
                    (15, 3), (16, 11), (17, 11), (27, 15))


def category_ensemble(seed, n_trees=12, n_leaves=63, columns=CAT_COLUMNS,
                      n_features=6, missing=False, max_set=32):
    """Seeded random leaf-wise trees whose nodes on `columns` ask category
    sets of 1 to 32 of the column's bins (the others ordinal splits)."""
    return random_node_list(
        np.random.default_rng(seed), n_trees, n_leaves, n_features,
        dyadic=True, missing=missing, max_set=max_set,
        categories=tuple((c, min(k, 255)) for c, k in columns),
        learning_rate=0.5, base_score=0.25, loss="logloss")


def category_rows(seed, n, columns=CAT_COLUMNS, n_features=6):
    """uint8 rows: a category column's bins 0 .. k + 1 (two no set names),
    an ordinal column's any bin."""
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, 255, (n, n_features)).astype(np.uint8)
    for c, k in columns:
        Xb[:, c] = rng.integers(0, min(k, 254) + 2, n)
    return Xb


@pytest.mark.parametrize("columns,missing,n_leaves,n_features", [
    (CAT_COLUMNS, False, 63, 6),            # every node a set
    (CAT_COLUMNS[1:4], False, 63, 6),       # sets beside ordinal nodes
    (CAT_COLUMNS[1:4], True, 63, 6),        # ... whose NaN has directions
    (CAT_COLUMNS, False, (2, 40), 6),       # ragged trees, 128 lanes
    (((2, 200),), True, 255, 6),            # 256 lanes, one wide column
    (ALLSTATE_COLUMNS, False, 255, 32),     # the cell's shape: six one-hot
    (ALLSTATE_COLUMNS, True, 255, 32),      # K-blocks beside the ordinal one
], ids=["all-sets", "mixed", "mixed-nan", "ragged", "255-leaves",
        "allstate", "allstate-nan"])
def test_category_sets_score_as_the_reference_walks(columns, missing,
                                                    n_leaves, n_features):
    """The plain reference's walk of the sets, the host backend's, the
    jax.numpy twin and the Pallas kernel (interpreted) agree bit for bit on
    seeded random models (dyadic leaf values), through api.predict."""
    ens = category_ensemble(54, n_leaves=n_leaves, columns=columns,
                            missing=missing, n_features=n_features)
    assert ens.has_cat_splits
    Xb = category_rows(55, 700, columns, n_features)
    want = numpy_predict.predict_raw_node_list(ens, Xb)
    np.testing.assert_array_equal(ens.predict_raw(Xb, binned=True), want)
    np.testing.assert_array_equal(
        api.predict(ens, Xb, binned=True, raw=True,
                    cfg=TrainConfig(backend="cpu")), want)
    for impl in ("onehot", "pallas"):
        got = api.predict(ens, Xb, binned=True, raw=True, cfg=TrainConfig(
            backend="tpu", n_bins=255, predict_impl=impl))
        np.testing.assert_array_equal(got, want, err_msg=impl)
    # a model WITHOUT a set builds the tables it did: no fifth table
    plain = leafwise_ensemble(54, 3, 63, 6).compile()
    assert plain.cat_blocks == 0 and len(plain.arrays()) == 3
    ce = ens.compile()
    assert len(ce.arrays()) == 5
    assert ce.sel.shape[1] == ce.ordinal_rows + 128 * ce.cat_blocks
    assert ce.ordinal_rows == (0 if columns == CAT_COLUMNS
                               else -(-n_features // 16) * 16)
    if columns == ALLSTATE_COLUMNS:
        # sets AND thresholds in every tree, and the cell's K-blocks
        sets = ens.cat_nodes.sum(axis=1)
        assert (sets > 0).all() and (sets < ens.n_leaves - 1).all()
        assert ce.cat_blocks == 6 and ce.sel.shape[1] == 32 + 768


def test_category_sets_cache_token_save_load_and_dump(tmp_path):
    ens = category_ensemble(56, n_trees=3, n_leaves=15)
    other = category_ensemble(56, n_trees=3, n_leaves=15)
    assert ens.cache_token() == other.cache_token()
    s = int(other.cat_index[other.cat_nodes][0])
    other.cat_bin_sets[s, 0] ^= np.uint32(1)
    assert ens.cache_token() != other.cache_token()
    path = str(tmp_path / "cats.npz")
    ens.save(path)
    back = api.load_model(path).ensemble
    assert isinstance(back, NodeListEnsemble) and back.has_cat_splits
    for k, _ in NodeListEnsemble._CAT_ARRAYS:
        np.testing.assert_array_equal(getattr(back, k), getattr(ens, k))
    Xb = category_rows(57, 200)
    np.testing.assert_array_equal(back.predict_raw(Xb, binned=True),
                                  ens.predict_raw(Xb, binned=True))
    assert " in bins {" in ens.dump_text(0)
    # sets past what one path matrix holds are refused BY NAME
    with pytest.raises(ValueError, match="SUB-TREE form"):
        category_ensemble(58, n_trees=2, n_leaves=600).compile()


# ------------------------------------------------------------------ #
# the set test's select by SPANS (PR 56): an uncut tree's node lanes
# ordered by K-block, each lane tile asking its own K-blocks alone
# ------------------------------------------------------------------ #

def _compiled_twice(ens, monkeypatch):
    """(the model's tables as the build makes them, its tables under DENSE
    spans: what the build made before it read the spans from the model)."""
    from ddt_tpu.models import tree

    ce = tree.CompiledNodeList.build(ens)
    with monkeypatch.context() as m:
        m.setattr(tree, "choose_set_spans", lambda *a: None)
        dense = tree.CompiledNodeList.build(ens)
    assert dense.select_spans == () and dense.cat_ordinal_at == 0
    return ce, dense


def _cat_sets(ce, n_features):
    from ddt_tpu.ops import predict_paths

    cat = predict_paths.CatSets(ce.cat_blocks, ce.sel.shape[1],
                                ce.select_spans, ce.cat_ordinal_at)
    k_rows = predict_paths._cat_block_rows(n_features, cat)
    return cat, np.concatenate([[0], np.cumsum(k_rows)])


def _scores(ce, Xb, use_pallas):
    """The scoring program over compiled tables: the Pallas kernel
    (interpreted) or its jax.numpy twin."""
    from ddt_tpu.ops import predict as predict_ops

    sel, planes, paths, expand, bins = ce.arrays()
    return np.asarray(predict_ops.predict_raw_effective_paths(
        sel, planes, paths, Xb, ce.learning_rate, ce.base_score,
        use_pallas=use_pallas, missing_routes=ce.missing_bin_value >= 0,
        select_spans=ce.select_spans, cat_expand=expand, cat_bins=bins,
        cat_ordinal_at=ce.cat_ordinal_at))


def _held_to_the_dense_build(ens, Xb, monkeypatch):
    """What every model with sets must keep under its spans: every weight
    tile the kernel skips is all zeros in `sel`, the kernel's scores are
    the dense build's BITS (leaf values of any float32), and kernel and
    twin agree with the reference's walk."""
    ce, dense = _compiled_twice(ens, monkeypatch)
    cat, first = _cat_sets(ce, ens.n_features)
    for j, (start, stop) in enumerate(ce.select_spans):
        tile = ce.sel[:, :, 128 * j:128 * (j + 1)].astype(np.float32)
        assert not tile[:, :first[start]].any(), (j, "below its span")
        assert not tile[:, first[stop]:].any(), (j, "past its span")
    # the leaf lanes and every table's shape are the dense build's
    for a, b in zip(ce.arrays(), dense.arrays()):
        assert a.shape == b.shape and a.dtype == b.dtype
    np.testing.assert_array_equal(ce.planes[:, 1:3], dense.planes[:, 1:3])
    got = _scores(ce, Xb, True)
    np.testing.assert_array_equal(got, _scores(dense, Xb, True))
    want = numpy_predict.predict_raw_node_list(ens, Xb)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    np.testing.assert_allclose(_scores(ce, Xb, False), want, rtol=0,
                               atol=2e-5)
    return ce, cat


def _float_leaves(seed, n_trees, n_leaves, columns, n_features,
                  missing=False):
    """`category_ensemble` with N(0, 1) leaf values: sums that ROUND, so
    that equal scores say the float32 adds kept their order."""
    return random_node_list(
        np.random.default_rng(seed), n_trees, n_leaves, n_features,
        missing=missing, max_set=32,
        categories=tuple((c, min(k, 255)) for c, k in columns),
        learning_rate=0.1, base_score=0.25, loss="logloss")


@pytest.mark.parametrize("missing", [False, True], ids=["plain", "nan"])
def test_set_spans_at_the_cells_shape(missing, monkeypatch):
    """The Allstate cell's shape (six one-hot K-blocks beside the ordinal
    one, 255 leaves): the ordinal block read by BOTH lane tiles and each
    one-hot block by ONE, 8 select tiles where 14, and the plan says 12 /
    7 / 8 / 7."""
    from ddt_tpu.ops import predict_paths

    ens = _float_leaves(54, 6, 255, ALLSTATE_COLUMNS, 32, missing)
    Xb = category_rows(55, 600, ALLSTATE_COLUMNS, 32)
    ce, cat = _held_to_the_dense_build(ens, Xb, monkeypatch)
    assert len(ce.select_spans) == 2
    assert sum(stop - start for start, stop in ce.select_spans) == 8
    # the ordinal K-block (behind `cat_ordinal_at` one-hot ones) in both
    assert all(start <= ce.cat_ordinal_at < stop
               for start, stop in ce.select_spans)
    assert ce.sel.shape[1] == 32 + 768 and ce.cat_blocks == 6
    plan = predict_paths.path_plan(500, ce.lanes, 32, cat=cat)
    assert (plan.path_mxu_tiles_per_tree, plan.catset_mxu_tiles_per_tree,
            plan.select_mxu_tiles, plan.select_k_blocks) == (12, 7, 8, 7)
    assert plan.trees_per_step == predict_paths.path_plan(
        500, ce.lanes, 32, cat=cat._replace(spans=())).trees_per_step


@pytest.mark.parametrize("columns,n_features,n_leaves,tiles", [
    # one column's sets beside ONE ordinal column: either component passes
    # 128 nodes in some tree, so both tiles read both: dense
    (((0, 100),), 2, 255, None),
    # every node a set of one of two columns, a block each: no tile can
    # hold a column's nodes alone in every tree: dense
    (((0, 100), (1, 120)), 2, 255, None),
    # ... of four columns, a block each (63.5 nodes a tree each): any two
    # pass 128 together in some tree, so a tile holds ONE alone and the
    # other two blocks are read by both: 6 where 8
    (((0, 100), (1, 120), (2, 90), (3, 128)), 4, 255, 6),
    # all sets and no ordinal K row (six one-hot blocks, the two of each
    # wide column tied): 8 where 12
    (CAT_COLUMNS, 6, 255, 8),
    # three ordinal columns beside three columns' sets
    (CAT_COLUMNS[1:4], 6, 255, None),
], ids=["ordinal-and-one", "two-blocks", "four-blocks", "all-sets",
        "mixed"])
def test_set_spans_fit_every_tree_or_stay_dense(columns, n_features,
                                                n_leaves, tiles,
                                                monkeypatch):
    """A component whose nodes pass 128 in some tree is read by both
    tiles; where no assignment under the dense count fits every tree the
    spans are the dense ones; the scores agree either way."""
    ens = _float_leaves(61, 8, n_leaves, columns, n_features)
    Xb = category_rows(62, 400, columns, n_features)
    ce, cat = _held_to_the_dense_build(ens, Xb, monkeypatch)
    if tiles is None:
        assert ce.select_spans == () and ce.cat_ordinal_at == 0
    else:
        assert sum(stop - start for start, stop in ce.select_spans) == tiles
    # every tree fits: a lane tile's own nodes are at most its 128 lanes
    # (the build would have raised on a lane past W)
    assert ce.lanes == 256


def test_a_set_over_two_blocks_lies_in_one_tiles_span(monkeypatch):
    """A column of more than 128 named ids takes two one-hot blocks, and a
    set that names ids of both ties them: the node's lane tile reads
    both."""
    columns = ((2, 200), (4, 40))
    ens = _float_leaves(63, 8, 255, columns, 6)
    ce, cat = _held_to_the_dense_build(
        ens, category_rows(64, 400, columns, 6), monkeypatch)
    _, first = _cat_sets(ce, 6)
    sel = ce.sel.astype(np.float32)
    # the K-blocks every node lane reads
    reads = np.stack([sel[:, first[k]:first[k + 1]].any(axis=1)
                      for k in range(len(first) - 1)], axis=-1)  # [T, W, K]
    two = reads.sum(axis=-1) == 2
    assert two.any() and ce.select_spans
    for j, (start, stop) in enumerate(ce.select_spans):
        blocks = np.nonzero(reads[:, 128 * j:128 * (j + 1)][
            two[:, 128 * j:128 * (j + 1)]])[1]
        assert ((blocks >= start) & (blocks < stop)).all()
    # ... and the tile the pair is NOT in skips both: fewer than dense
    assert sum(stop - start for start, stop in ce.select_spans) < 2 * (
        len(first) - 1)


@pytest.mark.parametrize("n_leaves,lanes", [(63, 128), (300, 384),
                                            (400, 512)])
def test_set_spans_of_another_width_are_dense(n_leaves, lanes, monkeypatch):
    """One lane tile has nothing to skip, three and four are not searched:
    the tables are the dense build's bit for bit, and so is the program
    (its static facts are `select_spans` () and `cat_ordinal_at` 0)."""
    ens = category_ensemble(65, n_trees=4, n_leaves=n_leaves,
                            columns=ALLSTATE_COLUMNS, n_features=32)
    ce, dense = _compiled_twice(ens, monkeypatch)
    assert ce.lanes == lanes
    assert ce.select_spans == () and ce.cat_ordinal_at == 0
    for a, b in zip(ce.arrays(), dense.arrays()):
        np.testing.assert_array_equal(a.astype(np.float32),
                                      b.astype(np.float32))


def test_set_spans_are_read_from_the_model_alone():
    """Two builds of one model: one `select_spans`, the same tables."""
    ens = category_ensemble(66, n_trees=5, n_leaves=255,
                            columns=ALLSTATE_COLUMNS, n_features=32)
    a, b = ens.compile(), dataclasses.replace(ens).compile()
    assert a.select_spans == b.select_spans != ()
    assert a.cat_ordinal_at == b.cat_ordinal_at
    for x, y in zip(a.arrays(), b.arrays()):
        np.testing.assert_array_equal(x.astype(np.float32),
                                      y.astype(np.float32))


@pytest.mark.parametrize("counts,blocks,lanes,want", [
    # the cell's picture: the ordinal component past 128 in some tree, so
    # in both tiles; the four others one tile each, the first listed of
    # the most balanced
    ([[130, 20, 30, 40, 34], [100, 60, 10, 70, 14]], [1, 2, 2, 1, 1], 256,
     [2, 0, 1, 1, 0]),
    # everything fits one tile: a tile still reads SOME block, and of the
    # equal counts the least full
    ([[40, 50, 30]], [1, 1, 1], 256, [0, 1, 0]),
    # two components that both pass 128 somewhere: dense
    ([[130, 100], [100, 130]], [1, 1], 256, None),
    # one component: nothing to split
    ([[200]], [3], 256, None),
    # nine components: past what is searched whole
    ([[10] * 9], [1] * 9, 256, None),
    # another width
    ([[30, 30]], [1, 1], 128, None),
    ([[130, 30]], [1, 1], 512, None),
    # the FEWEST tiles first: the 3-block component in ONE tile (5 tiles,
    # the fuller tile 120 lanes) although both reading it would leave the
    # tiles less full (8 tiles, 100 lanes)
    ([[20, 100, 100]], [3, 1, 1], 256, [0, 0, 1]),
], ids=["cell", "one-tile", "both-wide", "one-component", "nine", "w128",
        "w512", "fewest-tiles"])
def test_choose_set_spans_on_a_table(counts, blocks, lanes, want):
    from ddt_tpu.models import tree

    got = tree.choose_set_spans(np.array(counts), np.array(blocks), lanes)
    if want is None:
        assert got is None
    else:
        assert got.tolist() == want
