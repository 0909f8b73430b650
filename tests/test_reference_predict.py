"""`api.predict` on the normal path against the plain reference
(`ddt_tpu/reference/numpy_predict.py`), on seeded random trees.

The traversal kernel streams its node tables by blocks of G tree groups
(`ops/predict_pallas.table_plan`). G follows from the VMEM budget; the tests
shrink the BUDGET (never the program) so that a few hundred trees already
take several blocks, and compare LOGITS, not the argmax: with random trees
the largest score changes on rounding.

Tolerance 1e-5 on scores of magnitude about 1: both sides sum float32 leaf
values, the reference tree by tree and the kernel 128 trees a dot (500 terms
a class in Covertype's own model, some 40-70 here), which differ by a few
float32 roundings (6e-8 a term). Leaf values rounded to bfloat16 miss by 1e-3
and more here (1e-2 at 500 terms), so a path that computed in the precision
below would fail: `test_bfloat16_leaves_fail_the_tolerance` holds that.
"""

import jax
import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import TreeEnsemble
from ddt_tpu.ops import predict_pallas as jpp
from ddt_tpu.reference import numpy_predict
from ddt_tpu.telemetry import annotations as an

TOL = dict(rtol=0, atol=1e-5)
BINS = 255


def random_trees(n_trees, depth, n_features, n_classes, seed,
                 missing=False, cat=(), leafy=0.0):
    """Seeded random trees, full unless `leafy` (the share of internal
    nodes turned into leaves), round-major classes."""
    rng = np.random.default_rng(seed)
    n_nodes = 2 ** (depth + 1) - 1
    is_leaf = rng.random((n_trees, n_nodes)) < leafy
    is_leaf[:, n_nodes // 2:] = True
    return TreeEnsemble(
        feature=rng.integers(0, n_features, (n_trees, n_nodes),
                             dtype=np.int32),
        threshold_bin=rng.integers(0, BINS - 1, (n_trees, n_nodes),
                                   dtype=np.int32),
        threshold_raw=np.zeros((n_trees, n_nodes), np.float32),
        is_leaf=is_leaf,
        leaf_value=rng.standard_normal((n_trees, n_nodes)).astype(
            np.float32),
        split_gain=np.zeros((n_trees, n_nodes), np.float32),
        max_depth=depth, n_features=n_features, learning_rate=0.1,
        base_score=0.25, loss="softmax" if n_classes > 1 else "logloss",
        n_classes=max(n_classes, 2),
        default_left=(rng.random((n_trees, n_nodes)) < 0.5) if missing
        else None,
        missing_bin=missing, n_bins=BINS,
        cat_features=np.asarray(cat, np.int32) if cat else None)


def rows_of(n_rows, n_features, seed):
    # every bin, the reserved top one included
    return np.random.default_rng(seed).integers(
        0, BINS, size=(n_rows, n_features), dtype=np.uint8)


def score(ens, Xb, impl="pallas"):
    """The normal path: `api.predict`. Off the chip the auto dispatch is
    the one-hot path, so the kernel (interpreted here) is asked for by
    name, as the benchmark's rehearsal does."""
    cfg = TrainConfig(backend="tpu", n_bins=BINS, predict_impl=impl)
    got = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    root = an.root_spans("predict")[-1]
    spans = {s["name"]: s["counts"] for s in root["spans"]}
    return got, root["counts"], spans.get("ddt:predict:ensemble")


# (trees, depth, features, classes, missing, cat, G the budget admits,
#  the plan that follows: groups, G, blocks, trees a group, class dots a
#  grid step: 1 where a block's groups share one, which takes groups of
#  whole rounds, 126 trees at 7 classes and at 3)
CASES = [
    # Covertype's shape, three groups of which one fits: three blocks
    pytest.param(300, 8, 54, 7, False, (), 1, (3, 1, 3, 128, 1),
                 id="covtype-G1"),
    # two fit: blocks of 2, the last one ragged (3 groups padded to 4)
    pytest.param(300, 8, 54, 7, False, (), 2, (3, 2, 2, 126, 1),
                 id="covtype-G2-ragged"),
    # five groups of which two fit: evened out to 3 blocks of 2
    pytest.param(520, 5, 54, 7, False, (), 2, (5, 2, 3, 126, 1),
                 id="covtype-d5-evened"),
    # the whole ensemble fits: one block, tables resident; 256 padded
    # trees are two groups of 128 and three of 126, so each keeps its dot
    pytest.param(256, 8, 54, 7, False, (), 2, (2, 2, 1, 128, 2),
                 id="covtype-resident"),
    pytest.param(300, 6, 28, 1, False, (), 1, (3, 1, 3, 128, 1),
                 id="one-class"),
    pytest.param(300, 6, 28, 1, False, (), 3, (3, 3, 1, 128, 1),
                 id="one-class-shared-dot"),
    pytest.param(300, 4, 12, 3, True, (1, 4), 2, (3, 2, 2, 126, 1),
                 id="both-operands"),
    pytest.param(200, 4, 12, 1, True, (), 1, (2, 1, 2, 128, 1),
                 id="missing-only"),
    pytest.param(200, 4, 12, 3, False, (0, 5), 1, (2, 1, 2, 128, 1),
                 id="cat-only"),
]


@pytest.mark.parametrize("T,depth,F,C,missing,cat,fit,plan", CASES)
def test_kernel_over_table_blocks_matches_reference(budget, T, depth, F, C,
                                                    missing, cat, fit, plan):
    optional = int(missing) + int(bool(cat))
    budget(fit, depth, F, C, optional)
    ens = random_trees(T, depth, F, C, seed=7 * T + depth + 1000 * fit,
                       missing=missing,
                       cat=cat, leafy=0.1)
    Xb = rows_of(600, F, seed=depth)                 # 3 row tiles, one ragged
    got, root, ensemble = score(ens, Xb)
    want = numpy_predict.predict_raw(ens, Xb)
    assert got.shape == want.shape == ((600, C) if C > 1 else (600,))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, **TOL)

    groups, g, blocks, per_group, dots = plan
    assert (ensemble["tree_group"], ensemble["table_groups"],
            ensemble["groups_per_step"]) == (128, groups, g)
    assert (ensemble["trees_per_group"],
            ensemble["class_dots_per_step"]) == (per_group, dots)
    n_int = 2 ** depth - 1
    # every block's node tables, and the class one-hot: one the whole
    # ensemble shares, or one a group
    table_bytes = 128 * 4 * (blocks * g * ((2 + optional) * n_int
                                           + n_int + 1)
                             + (blocks * g if dots == g else 1) * C)
    assert ensemble["table_bytes"] == table_bytes
    assert root["classes"] == C
    assert root["tables_streamed_bytes"] == (
        3 * table_bytes if blocks > 1 else 0)


@pytest.mark.parametrize("C", [1, 7])
def test_blocks_do_not_change_the_scores(budget, C):
    """One block or several: the same leaf for every (row, tree). Dyadic
    leaf values, whose float32 sums are exact in any order, so that
    equal bits mean equal selection (the order inside a dot belongs to
    the compiler)."""
    ens = random_trees(300, 5, 20, C, seed=5)
    ens.leaf_value = np.round(ens.leaf_value * 64) / np.float32(64)
    Xb = rows_of(300, 20, seed=2)
    scores = []
    for fit in (3, 1):
        budget(fit, 5, 20, C)
        jax.clear_caches()               # the next call traces anew
        assert jpp.table_plan(384, 5, 20, C, None, 0).blocks == 3 // fit
        scores.append(score(ens, Xb)[0])
    np.testing.assert_array_equal(*scores)


@pytest.mark.parametrize("impl", ["auto", "onehot"])
@pytest.mark.parametrize("C,missing,cat", [(7, False, ()), (1, True, (2,))])
def test_onehot_path_matches_reference(impl, C, missing, cat):
    """What `auto` resolves to off the chip: the XLA one-hot path."""
    ens = random_trees(70, 5, 20, C, seed=11, missing=missing, cat=cat,
                       leafy=0.2)
    Xb = rows_of(500, 20, seed=3)
    got, root, ensemble = score(ens, Xb, impl)
    np.testing.assert_allclose(got, numpy_predict.predict_raw(ens, Xb),
                               **TOL)
    assert ensemble["tree_group"] == ensemble["table_groups"] == 0
    assert root["tables_streamed_bytes"] == 0 and root["classes"] == C


def test_bfloat16_leaves_fail_the_tolerance():
    """The control: the same trees with leaf values rounded to bfloat16
    miss the float32 reference by far more than TOL, at one class and at
    seven; and the float64 switch moves the reference by less than TOL."""
    import jax.numpy as jnp

    for C in (1, 7):
        ens = random_trees(300, 6, 20, C, seed=13)
        Xb = rows_of(400, 20, seed=4)
        want = numpy_predict.predict_raw(ens, Xb)
        exact = numpy_predict.predict_raw(ens, Xb, dtype=np.float64)
        assert exact.dtype == np.float64
        assert np.abs(want - exact).max() < TOL["atol"]
        ens.leaf_value = np.asarray(
            jnp.asarray(ens.leaf_value).astype(jnp.bfloat16).astype(
                jnp.float32))
        low = numpy_predict.predict_raw(ens, Xb)
        assert np.abs(low - want).max() > 50 * TOL["atol"]


def test_reference_walks_node_by_node():
    """The reference against a hand-walked tree: depth 2, one early leaf,
    a one-vs-rest node and a reserved missing bin."""
    ens = random_trees(2, 2, 3, 1, seed=0, missing=True, cat=(2,))
    ens.n_bins = 8
    ens.feature[:] = [[0, 1, 2, -1, -1, -1, -1]] * 2
    ens.threshold_bin[:] = [[3, 5, 4, 0, 0, 0, 0]] * 2
    ens.is_leaf[:] = [[False, False, False, True, True, True, True],
                      [False, True, False, True, True, True, True]]
    ens.default_left[:] = [[True, False, True, False, False, False, False]] * 2
    ens.leaf_value[:] = [[0, 0, 0, 1, 2, 3, 4], [0, 10, 0, 0, 0, 30, 40]]
    Xb = np.array([[3, 5, 0],    # left, left: 1; tree 1 stops at node 1: 10
                   [3, 7, 0],    # left, missing at node 1 -> right: 2; 10
                   [4, 0, 4],    # right, category matched -> left: 3; 30
                   [4, 0, 5],    # right, another category -> right: 4; 40
                   [7, 0, 7]],   # missing at the root -> left, left: 1; 10
                  np.uint8)
    np.testing.assert_array_equal(
        [numpy_predict.leaf_of_rows(ens, 0, Xb),
         numpy_predict.leaf_of_rows(ens, 1, Xb)],
        [[3, 4, 5, 6, 3], [1, 1, 5, 6, 1]])
    np.testing.assert_allclose(
        numpy_predict.predict_raw(ens, Xb),
        0.25 + 0.1 * np.array([11, 12, 33, 44, 11]), rtol=1e-6)
