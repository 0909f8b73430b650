"""The benchmark's plain reference of a ROUTED ensemble
(`benchmark/reference_routed.py`: a learned direction for the NaN bin,
one-vs-rest category nodes, the ordinal compare) against the system, on the
CPU at a small size: the shape of the CTR configuration
(`criteo-ctr-100t-d6`: depth 6, 39 columns of which the last 26 are
categorical, 255 bins) with one partial tree group (100 trees of 128 lanes)
and with two (130).

The reference imports nothing of the program, and the program's own NumPy
oracle (`TreeEnsemble`) nothing of the reference: the two are held to each
other leaf for leaf, and both scoring paths (the Pallas traversal kernel,
interpreted, and the XLA one-hot path) to the reference's scores.
"""

import os
import sys

import numpy as np
import pytest

BENCHMARK = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCHMARK)

import datagen_routed  # noqa: E402
import reference_routed  # noqa: E402

from ddt_tpu import api  # noqa: E402
from ddt_tpu.config import TrainConfig  # noqa: E402
from ddt_tpu.models.tree import empty_ensemble  # noqa: E402
from ddt_tpu.telemetry import annotations  # noqa: E402

DEPTH, FEATURES, NUMERIC, BINS, ROWS = 6, 39, 13, 255, 2000
CAT = tuple(range(NUMERIC, FEATURES))
LR, BASE = 0.1, 0.0
ROUTES = {"missing": (True, ()), "category": (False, CAT),
          "both": (True, CAT)}


def routed_model(routes: str, n_trees: int, seed: int):
    """(ensemble, its tables, rows, the reference's keywords) for one of
    ROUTES: the benchmark's own trees and click-log rows, the ensemble
    carrying the missing table, the categorical table, or both."""
    missing, cat = ROUTES[routes]
    tables = datagen_routed.random_routed_trees(n_trees, DEPTH, FEATURES,
                                                BINS, cat, seed)
    ens = empty_ensemble(n_trees, DEPTH, FEATURES, LR, BASE, "logloss",
                         missing_bin=missing, n_bins=BINS, cat_features=cat)
    for k, v in tables.items():
        getattr(ens, k)[:] = v
    Xb = datagen_routed.click_log_bins(ROWS, NUMERIC, FEATURES, BINS, seed)
    how = dict(missing_bin=BINS - 1 if missing else None, cat_features=cat)
    return ens, tables, Xb, how


@pytest.mark.parametrize("impl", ["pallas", "onehot"])
@pytest.mark.parametrize("n_trees", [100, 130])
@pytest.mark.parametrize("routes", list(ROUTES))
def test_api_predict_agrees_with_the_routed_reference(routes, n_trees, impl):
    ens, tables, Xb, how = routed_model(routes, n_trees, seed=31 + n_trees)
    cfg = TrainConfig(backend="tpu", n_bins=BINS, predict_impl=impl)
    got = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    visits = np.zeros(len(reference_routed.ROUTES), np.int64)
    want = reference_routed.raw_scores(tables, DEPTH, LR, BASE, Xb,
                                       routes=visits, **how)
    assert got.dtype == np.float32 and got.shape == (ROWS,)
    # float32 sums of 100 (130) leaf values N(0,1) x 0.1, |score| up to 5:
    # a few float32 roundings of 3e-7 each, in whatever order the path adds
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    # every route the model carries decided visits, the others none
    missing, cat = ROUTES[routes]
    assert visits.sum() == ROWS * n_trees * DEPTH and visits[0] > 0
    assert (visits[1] > 0) == (visits[2] > 0) == bool(cat)
    assert (visits[3] > 0) == (visits[4] > 0) == missing
    # and the program says which form of the kernel served
    root = annotations.root_spans("predict")[-1]
    tables_routed = (missing + bool(cat)) if impl == "pallas" else 0
    assert root["counts"]["routing_tables"] == tables_routed
    built = [s["counts"] for s in root["spans"]
             if s["name"] == "ddt:predict:ensemble"]
    if built:                       # a miss of the model cache
        assert built[0]["trees"] == n_trees
        assert built[0]["routing_tables"] == tables_routed
        assert built[0]["table_groups"] == (
            -(-n_trees // 128) if impl == "pallas" else 0)


@pytest.mark.parametrize("n_trees", [100, 130])
@pytest.mark.parametrize("routes", list(ROUTES))
def test_routed_reference_reaches_the_oracles_leaves(routes, n_trees):
    ens, tables, Xb, how = routed_model(routes, n_trees, seed=57 + n_trees)
    oracle = ens._traverse_np(Xb, binned=True)              # [T, R]
    for t in range(n_trees):
        leaf = reference_routed.leaf_of_rows(
            tables["feature"][t], tables["threshold_bin"][t],
            tables["is_leaf"][t], DEPTH, Xb,
            tables["default_left"][t] if how["missing_bin"] else None,
            how["missing_bin"], how["cat_features"])
        np.testing.assert_array_equal(leaf, oracle[t])
    np.testing.assert_allclose(
        ens.predict_raw(Xb, binned=True),
        reference_routed.raw_scores(tables, DEPTH, LR, BASE, Xb, **how),
        rtol=0, atol=1e-5)


def test_three_tests_in_their_order_on_a_hand_made_stump():
    """Missing overrides category overrides ordinal, on one node."""
    tables = {"feature": np.array([[1, -1, -1]], np.int32),
              "threshold_bin": np.array([[7, 0, 0]], np.int32),
              "is_leaf": np.array([[False, True, True]]),
              "leaf_value": np.array([[0, 1, 2]], np.float32),
              "default_left": np.array([[False, False, False]])}
    Xb = np.array([[0, 7], [0, 6], [0, 8], [0, 254]], np.uint8)

    def scores(**how):
        return reference_routed.raw_scores(tables, 1, 1.0, 0.0, Xb,
                                           **how).tolist()

    assert scores() == [1, 1, 2, 2]                         # b <= 7
    assert scores(cat_features=(1,)) == [1, 2, 2, 2]        # b == 7
    assert scores(missing_bin=254) == [1, 1, 2, 2]          # NaN -> right
    tables["default_left"][:] = True
    assert scores(missing_bin=254) == [1, 1, 2, 1]          # NaN -> left
    assert scores(missing_bin=254, cat_features=(1,)) == [1, 2, 2, 1]
    visits = np.zeros(5, np.int64)
    reference_routed.raw_scores(tables, 1, 1.0, 0.0, Xb, 254, (1,), visits)
    assert visits.tolist() == [0, 1, 2, 1, 0]
