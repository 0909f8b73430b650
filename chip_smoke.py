#!/usr/bin/env python
"""chip_smoke.py — does the system still train and score on the chip?

One process drives the main path once, through the entry points `cli train`
and `cli predict` call (ddt_tpu.api.train -> Driver fused path ->
TPUDevice.grow_rounds; ddt_tpu.api.predict -> TPUDevice.predict_raw), at the
full width of BASELINE.json config 1: 1,000,000 rows x 28 features from
`synthetic_binary`, 255 bins, depth 6, backend="tpu", every other field at
its default. Depth of the ENSEMBLE is cut: ten boosting rounds, not a
hundred. Then all 1M binned rows are scored with the ten trees. And one
ensemble of Covertype's own shape (500 rounds x 7 classes, depth 8, 54
features: random trees, the scorer does not care) scores 100,000 rows through
the same `api.predict`, its node tables streamed by blocks of tree groups.
And one of the CTR model's shape (100 trees, depth 6, 39 columns: 13 numeric
with a NaN bin and learned directions, 26 categorical split one-vs-rest)
scores 1,000,000 rows, served by the ROUTED form of the traversal kernel.
And one of LightGBM's Higgs model's shape (500 leaf-wise trees of 255 leaves,
28 features: a NODE LIST no heap holds) scores 200,000 rows, served by the
PATH-MATRIX form of the traversal kernel, its feature select answering two
nodes a result lane; and small node lists at 28, 64, 65, 129 and 968 columns
(the last width that packs so, and the first that does not), with and
without learned NaN directions, hold that kernel to its jax.numpy twin and
to the node walk in every bit, at 1 to 4,999 rows. And an AVERAGED FOREST
(12 trees of 700-1,400 leaves with 10-class leaf vectors over 784 columns:
every tree cut into sub-trees of 256 lanes, chained) scores 100,000 rows
through the SUB-TREE form of that kernel, `subtrees_per_tree` > 1 on its
spans, against the walk of the uncut trees. And one of CatBoost's
Epsilon model's shape (8000 OBLIVIOUS trees of depth 6, 2000 dense columns)
scores 300,000 rows, served by the oblivious form of the traversal kernel (6
select columns a tree, never the 63-node expansion); small oblivious
ensembles at depth 1, 6 and 8 and 28, 129 and 2000 columns hold that kernel
to its twin and to the bit walk in every bit.

It asserts WHAT ran (the Pallas kernels, compiled: `tpu_custom_call` in both
lowered programs; histogram resolved to `pallas`, sibling subtraction on; no
OOM degrade, no fault retry) and that the results are RIGHT by means that
need no device and no compiled artefact: chip scores against the NumPy
traversal, a small chip training against reference/numpy_trainer.

    python chip_smoke.py              # on a machine with a TPU; exit 0 = pass
    python chip_smoke.py --rehearse   # CPU, 1/100 of the rows, kernels
                                      # interpreted: debug the control flow
                                      # before chip time is spent on it

With four devices visible the ten rounds repeat on a rows=4 mesh and on a
2x2 (rows x features) mesh (rehearse that with
XLA_FLAGS=--xla_force_host_platform_device_count=4).

No phase is wrapped in a try: any exception ends the run non-zero. Without
--rehearse there is no CPU path: a platform other than "tpu" is an error.
Every time printed is a SMOKE TIMING — one sample, compile and transfers
mixed in as labelled — not a measurement.

Last line of stdout on success, and only then:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

ROWS, FEATURES, BINS, DEPTH, ROUNDS = 1_000_000, 28, 255, 6, 10
SEED = 42
SCORE_CHECK_ROWS = 50_000
MC_ROUNDS, MC_ROWS = 500, 100_000      # the 7-class scoring phase
ROUTED_ROWS = 1_000_000                # the routed scoring phase
LEAFWISE_ROWS = 200_000                # the node-list scoring phase
FOREST_ROWS = 100_000                  # the averaged-forest scoring phase
OBLIVIOUS_ROWS = 300_000               # the oblivious phase: three chunks
SCORE_TOL = dict(rtol=3e-4, atol=3e-4)      # as __graft_entry__'s oracle check
# Chip-vs-oracle training parity: the bounds the earlier chip runs measured
# inside (0.9871 agreement, 0.0024 AUC). Never bitwise
# across platforms (ops/split.py "Determinism boundary").
PARITY_MIN_AGREEMENT = 0.95
PARITY_MAX_AUC_DELTA = 0.01


def say(msg: str) -> None:
    print(msg, flush=True)


def timing(what: str, **secs: float) -> None:
    body = " ".join(f"{k}={v:.2f}s" for k, v in secs.items())
    say(f"smoke timing (one sample, not a measurement): {what}: {body}")


class Compiles:
    """Backend-compile seconds since construction (telemetry's own
    jit_compile_seconds counter) — compile printed apart from run."""

    def __init__(self):
        from ddt_tpu.telemetry import counters

        self._c = counters
        self._start = counters.snapshot()

    def split(self, wall: float) -> dict:
        d = self._c.delta(self._start)
        c = float(d["jit_compile_seconds"])
        return {"compile": c, "rest": wall - c}


def rounds_program_args(be, rows: int, features: int) -> list:
    """ShapeDtypeStructs of the fused-rounds program's operands, laid out
    as the backend lays the real ones out (for .lower())."""
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rp = -(-rows // be.row_shards) * be.row_shards
    fp = -(-features // be.feature_partitions) * be.feature_partitions
    vec = be._row_sharding()
    return [sds((rp, fp), jnp.uint8, be._named(be.layout.binned_data())),
            sds((rp,), jnp.float32, vec), sds((rp,), jnp.float32, vec),
            sds((rp,), jnp.float32, vec)]


def check_ensemble(ens, n_trees: int) -> None:
    n_nodes = 2 ** (DEPTH + 1) - 1
    assert ens.n_trees == n_trees, ens.n_trees
    assert ens.feature.shape == (n_trees, n_nodes), ens.feature.shape
    assert np.isfinite(ens.leaf_value).all(), "non-finite leaf values"
    assert np.isfinite(ens.split_gain).all(), "non-finite split gains"
    n_splits = int((~ens.is_leaf).sum())
    # A depth-6 tree on this data splits far more than once; an ensemble
    # of stumps would mean the histograms or the gains came back empty.
    assert n_splits >= 10 * n_trees, f"only {n_splits} splits grown"
    assert ((ens.feature >= -1) & (ens.feature < FEATURES)).all()


def train_and_score(cfg, Xb, y, label: str):
    """The main path: api.train then api.predict, with the assertions on
    what ran. Returns (ensemble, raw scores, backend)."""
    import jax

    from ddt_tpu import api
    from ddt_tpu.backends import get_backend
    from ddt_tpu.ops import grow as grow_ops
    from ddt_tpu.ops import histogram as hist_ops
    from ddt_tpu.utils import device

    rows = Xb.shape[0]
    comp = Compiles()
    t0 = time.perf_counter()
    res = api.train(Xb, y, cfg, binned=True)
    wall = time.perf_counter() - t0
    timing(f"{label} train, {ROUNDS} rounds x {rows} rows, first call",
           wall=wall, **comp.split(wall))
    ens = res.ensemble
    check_ensemble(ens, ROUNDS)
    loss = res.history[-1]["train_loss"]
    assert np.isfinite(loss) and loss < np.log(2.0), \
        f"train loss {loss} not below the prior's {np.log(2.0):.4f}"
    say(f"{label} train: loss after round {ROUNDS} = {loss:.5f}")

    be = get_backend(cfg)            # the instance api.train just used
    rounds_fn = be._rounds_fns.get(ROUNDS)
    assert rounds_fn is not None, \
        "the fused path did not run (no grow_rounds program was built)"
    lowered = rounds_fn.lower(*rounds_program_args(be, rows, FEATURES))
    if device.platform() == "tpu":
        assert "tpu_custom_call" in lowered.as_text(), \
            "rounds program carries no compiled Pallas kernel"
    # Levels 0..5 build 1..32 nodes (half that from level 1 on under
    # sibling subtraction): every one must resolve to the kernel.
    for n_nodes in (1, 2, 4, 8, 16, 32):
        impl = hist_ops.resolve_hist_impl(
            cfg.hist_impl, n_nodes=n_nodes, n_features=FEATURES,
            n_bins=BINS)
        assert impl == "pallas", f"hist impl at {n_nodes} nodes: {impl}"
    assert grow_ops.resolve_hist_subtraction(cfg.hist_subtraction), \
        "sibling subtraction resolved off"
    say(f"{label} train: fused rounds program, hist=pallas, "
        f"subtraction=on, split_comms={be.split_comms}")

    t0 = time.perf_counter()
    api.train(Xb, y, cfg, binned=True)
    timing(f"{label} train, same call again (programs compiled)",
           wall=time.perf_counter() - t0)

    comp = Compiles()
    t0 = time.perf_counter()
    scores = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    wall = time.perf_counter() - t0
    timing(f"{label} predict, {rows} rows x {ens.n_trees} trees, first "
           "call", wall=wall, **comp.split(wall))
    assert scores.shape == (rows,) and scores.dtype == np.float32, \
        (scores.shape, scores.dtype)
    assert np.isfinite(scores).all(), "non-finite scores"
    fn, ens_dev = be._predict_fn(ens)
    x_spec = jax.ShapeDtypeStruct(
        (-(-rows // be.row_shards) * be.row_shards, FEATURES), np.uint8,
        sharding=be._row_sharding(extra_dims=1))
    if device.platform() == "tpu":
        assert "tpu_custom_call" in jax.jit(fn).lower(
            *ens_dev, x_spec).as_text(), \
            "scoring program carries no compiled Pallas kernel"
    t0 = time.perf_counter()
    again = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    timing(f"{label} predict, same call again", wall=time.perf_counter() - t0)
    np.testing.assert_array_equal(scores, again)
    return ens, scores, be


def check_scores_against_numpy(ens, Xb, scores) -> None:
    n = min(SCORE_CHECK_ROWS, Xb.shape[0])
    want = ens.predict_raw(Xb[:n], binned=True)      # NumPy traversal
    np.testing.assert_allclose(scores[:n], want, **SCORE_TOL)
    say(f"scores: {n} rows match TreeEnsemble.predict_raw (NumPy) within "
        f"{SCORE_TOL['rtol']:g}; max |diff| = "
        f"{float(np.abs(scores[:n] - want).max()):.2e}")


def assert_compiled_kernel(cfg, ens, rows: int, what: str) -> None:
    """On the chip: the program that scores `ens` carries a compiled Pallas
    kernel (a CPU lowers none)."""
    import jax

    from ddt_tpu.backends import get_backend
    from ddt_tpu.utils import device

    if device.platform() != "tpu":
        return
    be = get_backend(cfg)
    fn, ens_dev = be._predict_fn(ens)
    x_spec = jax.ShapeDtypeStruct((rows, ens.n_features), np.uint8,
                                  sharding=be._row_sharding(extra_dims=1))
    assert "tpu_custom_call" in jax.jit(fn).lower(
        *ens_dev, x_spec).as_text(), \
        f"{what} scoring program carries no compiled Pallas kernel"


def score_multiclass(overrides: dict, rows: int) -> None:
    """Covertype's own ensemble shape through `api.predict`: 3,500 random
    full trees (500 rounds x 7 classes) of depth 8 over 54 features. Asserts
    that the traversal kernel served it by the auto dispatch, its tables
    streamed in more than one block, its groups held whole rounds of the
    classes and shared one class dot a block, and holds every class column
    to the plain reference (reference/numpy_predict, in float64) on the first rows."""
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.models.tree import empty_ensemble
    from ddt_tpu.reference import numpy_predict
    from ddt_tpu.telemetry.annotations import root_spans

    T, depth, F, C = MC_ROUNDS * 7, 8, 54, 7
    rng = np.random.default_rng(SEED)
    ens = empty_ensemble(T, depth, F, 0.1, 0.0, "softmax", C)
    n_int = 2 ** depth - 1
    ens.feature[:, :n_int] = rng.integers(0, F, (T, n_int))
    ens.threshold_bin[:, :n_int] = rng.integers(0, BINS - 1, (T, n_int))
    ens.is_leaf[:, n_int:] = True
    ens.leaf_value[:, n_int:] = rng.standard_normal((T, n_int + 1))
    Xb = rng.integers(0, BINS, size=(rows, F), dtype=np.uint8)
    cfg = TrainConfig(n_bins=BINS, backend="tpu", **overrides)
    comp = Compiles()
    t0 = time.perf_counter()
    scores = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    wall = time.perf_counter() - t0
    timing(f"7-class predict, {rows} rows x {T} trees x depth {depth}, "
           "first call", wall=wall, **comp.split(wall))
    assert scores.shape == (rows, C) and scores.dtype == np.float32, \
        (scores.shape, scores.dtype)
    assert np.isfinite(scores).all(), "non-finite scores"
    root = root_spans("predict")[-1]
    built = {s["name"]: s["counts"] for s in root["spans"]}[
        "ddt:predict:ensemble"]
    say(f"7-class predict: ddt:predict:ensemble {built}; root "
        f"classes={root['counts']['classes']} tables_streamed_bytes="
        f"{root['counts']['tables_streamed_bytes']}")
    assert built["tree_group"] == 128, "the traversal kernel did not serve"
    assert built["table_groups"] == -(-T // 128), built
    # 18 whole rounds of the 7 classes a group, so that lane l is class
    # l % 7 in every group and a block's groups share ONE class dot.
    assert (built["trees_per_group"], built["class_dots_per_step"]) == (
        126, 1), built
    assert (built["nodes_per_tile"], built["mxu_tiles_per_group"]) == (
        2, 2 ** (depth - 1)), "two nodes do not share a weight tile"
    assert built["groups_per_step"] < built["table_groups"], \
        "the node tables did not stream"
    # a step of 7 x 128 weight tiles keeps 256 rows (PR 42)
    assert built["rows_per_step"] == 256, built
    assert root["counts"]["tables_streamed_bytes"] > 0
    assert_compiled_kernel(cfg, ens, rows, "7-class")
    n = min(2_000, rows)
    # float64 in the reference: summed in float32 tree by tree, 500 terms
    # a class, its own rounding is most of the gap (6.7e-6 of the 1e-5 on
    # the v5e, against 1.6e-6 at most this way; PERF.md, PR 27).
    want = numpy_predict.predict_raw(ens, Xb[:n], dtype=np.float64)
    gap = float(np.abs(scores[:n] - want).max())
    say(f"7-class scores: {n} rows x {C} classes against "
        f"reference/numpy_predict (float64), max |diff| = {gap:.2e} "
        "(<= 1e-5)")
    assert gap <= 1e-5, gap


def score_routed(overrides: dict, rows: int) -> None:
    """The CTR model's shape through `api.predict`: 100 random full trees
    of depth 6 over 39 columns, 13 numeric with a NaN bin and a learned
    direction a node, 26 categorical split one-vs-rest. Asserts that the
    ROUTED form of the traversal kernel served it by the auto dispatch
    (both routing tables, one node a weight tile, both tables' routes
    inside that tile: `routes_in_tile` 2) and holds a sample of
    rows to the NumPy oracle, `TreeEnsemble.predict_raw`."""
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.models.tree import empty_ensemble
    from ddt_tpu.telemetry.annotations import root_spans

    T, depth, F, numeric = 100, 6, 39, 13
    rng = np.random.default_rng(SEED)
    ens = empty_ensemble(T, depth, F, 0.1, 0.0, "logloss", missing_bin=True,
                         n_bins=BINS, cat_features=tuple(range(numeric, F)))
    n_int = 2 ** depth - 1
    ens.feature[:, :n_int] = rng.integers(0, F, (T, n_int))
    ens.threshold_bin[:, :n_int] = rng.integers(0, BINS - 2, (T, n_int))
    ens.default_left[:, :n_int] = rng.random((T, n_int)) < 0.5
    ens.is_leaf[:, n_int:] = True
    ens.leaf_value[:, n_int:] = rng.standard_normal((T, n_int + 1))
    # Bins 0..253 everywhere, then a fifth of the numeric cells missing.
    Xb = rng.integers(0, BINS - 1, size=(rows, F), dtype=np.uint8)
    Xb[:, :numeric][rng.random((rows, numeric)) < 0.2] = BINS - 1
    cfg = TrainConfig(n_bins=BINS, backend="tpu", **overrides)
    comp = Compiles()
    t0 = time.perf_counter()
    scores = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    wall = time.perf_counter() - t0
    timing(f"routed predict, {rows} rows x {T} trees x depth {depth} x {F} "
           "features, first call", wall=wall, **comp.split(wall))
    assert scores.shape == (rows,) and scores.dtype == np.float32, \
        (scores.shape, scores.dtype)
    assert np.isfinite(scores).all(), "non-finite scores"
    root = root_spans("predict")[-1]
    built = {s["name"]: s["counts"] for s in root["spans"]}[
        "ddt:predict:ensemble"]
    say(f"routed predict: ddt:predict:ensemble {built}; root "
        f"routing_tables={root['counts']['routing_tables']}; "
        f"routes_in_tile={built['routes_in_tile']}")
    assert built["tree_group"] == 128, "the traversal kernel did not serve"
    assert (built["trees"], built["table_groups"]) == (T, 1), built
    assert built["routing_tables"] == root["counts"]["routing_tables"] == 2, \
        "the kernel does not route by both tables"
    assert (built["nodes_per_tile"], built["mxu_tiles_per_group"]) == (
        1, n_int), built
    assert built["routes_in_tile"] == 2, \
        "the two tables' routes do not ride the MXU weight tile"
    # ONE group of 63 weight tiles a step: the step takes 1,024 rows (PR 42)
    assert built["rows_per_step"] == 1024, built
    assert_compiled_kernel(cfg, ens, rows, "routed")
    n = min(SCORE_CHECK_ROWS, rows)
    want = ens.predict_raw(Xb[:n], binned=True)      # NumPy traversal
    # Both sides sum 100 leaf values x 0.1 in float32, in their own order.
    gap = float(np.abs(scores[:n] - want).max())
    say(f"routed scores: {n} rows against TreeEnsemble.predict_raw (NumPy), "
        f"max |diff| = {gap:.2e} (<= 1e-5)")
    assert gap <= 1e-5, gap


def score_node_list(overrides: dict, rows: int) -> None:
    """LightGBM's Higgs model's shape through `api.predict`: 500 random
    leaf-wise trees of 255 leaves over 28 features, a node list (a random
    leaf is split 254 times, so a tree is some 20 levels deep). Asserts
    that the PATH-MATRIX form of the traversal kernel served it by the auto
    dispatch (`node_list` 1 on its spans, its tables in blocks of trees)
    and holds a sample of rows to the plain node walk
    (reference/numpy_predict, in float64)."""
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.models.tree import random_node_list
    from ddt_tpu.reference import numpy_predict
    from ddt_tpu.telemetry.annotations import root_spans

    T, L, F = 500, 255, FEATURES
    rng = np.random.default_rng(SEED)
    ens = random_node_list(rng, T, L, F, BINS, learning_rate=0.1,
                           base_score=0.0, loss="logloss")
    Xb = rng.integers(0, BINS, size=(rows, F), dtype=np.uint8)
    cfg = TrainConfig(n_bins=BINS, backend="tpu", **overrides)
    comp = Compiles()
    t0 = time.perf_counter()
    scores = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    wall = time.perf_counter() - t0
    timing(f"node-list predict, {rows} rows x {T} trees x {L} leaves "
           f"(deepest {ens.deepest_leaf}), first call", wall=wall,
           **comp.split(wall))
    assert scores.shape == (rows,) and scores.dtype == np.float32, \
        (scores.shape, scores.dtype)
    assert np.isfinite(scores).all(), "non-finite scores"
    root = root_spans("predict")[-1]
    built = {s["name"]: s["counts"] for s in root["spans"]}[
        "ddt:predict:ensemble"]
    say(f"node-list predict: ddt:predict:ensemble {built}; root "
        f"node_list={root['counts']['node_list']} tables_streamed_bytes="
        f"{root['counts']['tables_streamed_bytes']}")
    assert built["node_list"] == root["counts"]["node_list"] == 1, built
    assert built["deepest_leaf"] > 12, "a tree a heap could have held"
    assert built["trees_per_step"] > 0, "the path-matrix kernel did not serve"
    assert built["trees_per_step"] * built["table_blocks"] >= T, built
    assert (built["select_nodes_per_lane"],
            built["path_mxu_tiles_per_tree"]) == (2, 5), \
        "the select does not answer two nodes a lane"
    assert_compiled_kernel(cfg, ens, rows, "node-list")
    n = min(2_000, rows)
    want = numpy_predict.predict_raw_node_list(ens, Xb[:n], dtype=np.float64)
    gap = float(np.abs(scores[:n] - want).max())
    say(f"node-list scores: {n} rows against reference/numpy_predict "
        f"(float64), max |diff| = {gap:.2e} (<= 1e-5)")
    assert gap <= 1e-5, gap


def score_node_list_grid(overrides: dict) -> None:
    """The path-matrix kernel, compiled, against its jax.numpy twin and the
    plain node walk on the shapes' edges: 1, 255, 256, 257 and 4,999 rows
    (one ragged row tile, and two), 28, 64, 65, 129 and 968 columns (two
    nodes a lane of the select up to 64, the mantissa on the VPU at 64;
    1, 2 and 8 K-blocks, the last of 1 and of 72 columns), with and
    without learned NaN directions; 12 trees of 255 leaves, dyadic leaf
    values, so the three agree in every bit."""
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.models.tree import random_node_list
    from ddt_tpu.reference import numpy_predict
    from ddt_tpu.telemetry.annotations import root_spans

    rng = np.random.default_rng(SEED)
    kernel = TrainConfig(n_bins=BINS, backend="tpu", **overrides)
    twin = TrainConfig(n_bins=BINS, backend="tpu", predict_impl="onehot")
    t0 = time.perf_counter()
    for F in (28, 64, 65, 129, 968):
        for missing in (False, True):
            ens = random_node_list(rng, 12, 255, F, BINS, dyadic=True,
                                   missing=missing, learning_rate=0.5,
                                   base_score=0.25, loss="logloss")
            for rows in (1, 255, 256, 257, 4_999):
                Xb = rng.integers(0, BINS - 1, size=(rows, F), dtype=np.uint8)
                Xb[rng.random((rows, F)) < 0.6] = BINS - 1
                want = numpy_predict.predict_raw_node_list(
                    ens, Xb, np.float64).astype(np.float32)
                # the twin first: the program whose stages are read after
                # this phase is the last one built, the kernel's
                assert np.array_equal(
                    api.predict(ens, Xb, binned=True, raw=True, cfg=twin),
                    want), (F, missing, rows, "jax.numpy form")
                got = api.predict(ens, Xb, binned=True, raw=True, cfg=kernel)
                root = root_spans("predict")[-1]
                built = root["counts"]
                assert built["node_list"] == 1 and built[
                    "routing_tables"] == int(missing) and built[
                        "select_k_blocks"] == -(-F // 128), built
                for span in root["spans"]:      # the model's first call
                    if span["name"] == "ddt:predict:ensemble":
                        assert span["counts"]["select_nodes_per_lane"] == (
                            2 if F <= 64 else 1), span["counts"]
                assert np.array_equal(got, want), (
                    F, missing, rows, float(np.abs(got - want).max()))
            assert_compiled_kernel(kernel, ens, 4_999,
                                   f"node-list F={F} missing={missing}")
    timing("node-list grid: 5 row counts x 5 widths x with/without NaN "
           "routes, kernel == jax.numpy form == node walk in every bit",
           wall=time.perf_counter() - t0)


def score_forest(overrides: dict, rows: int) -> None:
    """An averaged forest through `api.predict`: 12 random trees of 700 to
    1,400 leaves over 784 columns of 256 bins with a 10-class vector a leaf
    (a node list with vector leaves, loss "mean"). Asserts that the
    SUB-TREE form of the path kernel served it by the auto dispatch
    (`subtrees_per_tree` > 1 and `leaf_columns` 10 on its spans: every tree
    cut into sub-trees of 256 lanes and chained, the class dot's three
    bfloat16 pieces; `select_mxu_tiles` under 14: a sub-tree's lanes
    ordered by their column's K-block, each lane tile of the select asking
    for its own blocks alone; `exit_mxu_tiles` 2: the exits' table ONE lane
    tile, some ten lanes of the chain's links behind 30 of class pieces),
    holds the scores [rows, 10] to the walk of
    the uncut trees (reference/numpy_predict.predict_proba_node_list,
    float64), and holds the compiled kernel and its jax.numpy twin bit-equal
    on dyadic leaf vectors over 8 trees (every sum and the mean exact), at
    784 columns so that the spans engage there too, and at 54 and 100
    columns, where the sub-trees are HALVED (`resolve_mxu_tiles` 2: PR 51)."""
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.models.tree import random_node_list
    from ddt_tpu.reference import numpy_predict
    from ddt_tpu.telemetry.annotations import root_spans

    T, F, C, bins = 12, 784, 10, 256
    rng = np.random.default_rng(SEED + 47)
    ens = random_node_list(rng, T, (700, 1400), F, bins, leaf_columns=C)
    Xb = rng.integers(0, bins, size=(rows, F), dtype=np.uint8)
    cfg = TrainConfig(n_bins=bins, backend="tpu", **overrides)
    comp = Compiles()
    t0 = time.perf_counter()
    scores = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    wall = time.perf_counter() - t0
    timing(f"forest predict, {rows} rows x {T} trees x "
           f"{ens.n_leaves.min()}-{ens.n_leaves.max()} leaves x {C} classes "
           f"(deepest {ens.deepest_leaf}), first call", wall=wall,
           **comp.split(wall))
    assert scores.shape == (rows, C) and scores.dtype == np.float32, \
        (scores.shape, scores.dtype)
    assert np.isfinite(scores).all(), "non-finite scores"
    root = root_spans("predict")[-1]
    built = {s["name"]: s["counts"] for s in root["spans"]}[
        "ddt:predict:ensemble"]
    say(f"forest predict: ddt:predict:ensemble {built}; root "
        f"subtrees_per_tree={root['counts']['subtrees_per_tree']} "
        f"select_mxu_tiles={root['counts']['select_mxu_tiles']} "
        f"exit_mxu_tiles={root['counts']['exit_mxu_tiles']} "
        f"tables_streamed_bytes={root['counts']['tables_streamed_bytes']}")
    assert built["node_list"] == root["counts"]["node_list"] == 1, built
    assert built["subtrees_per_tree"] > 1, "no tree was cut"
    assert built["subtrees_per_tree"] == root["counts"]["subtrees_per_tree"]
    # the entries are PACKED (PR 53): several pieces of a tree in one,
    # glued by copies of their common ancestors
    assert built["pieces_per_subtree"] > 1 \
        and built["glue_copies_per_subtree"] > 0, built
    assert (built["subtree_lanes"], built["leaf_columns"],
            built["class_dot_passes"], built["select_k_blocks"]) == (
                256, C, 3, 7), built
    assert built["trees_per_step"] > 0, "the path kernel did not serve"
    # 7 K-blocks x 2 lane tiles dense; uniform columns split at the middle
    assert 7 <= built["select_mxu_tiles"] <= 9, built
    # 3 x 10 lanes of pieces and a chain of a dozen sub-trees: one tile
    assert (built["exit_mxu_tiles"], built["chain_mxu_tiles_per_tree"]) == (
        2, 0), built
    assert_compiled_kernel(cfg, ens, rows, "forest")
    n = min(2_000, rows)
    want = numpy_predict.predict_proba_node_list(ens, Xb[:n])
    gap = float(np.abs(scores[:n] - want).max())
    say(f"forest scores: {n} rows against reference/numpy_predict "
        f"(float64), max |diff| = {gap:.2e} (<= 1e-5)")
    assert gap <= 1e-5, gap
    exact = random_node_list(rng, 8, (300, 900), F, bins, dyadic=True,
                             leaf_columns=3)
    spans = exact.compile().select_spans
    assert spans == ((0, 3), (3, 7)), spans
    Xe = rng.integers(0, bins, size=(4_999, F), dtype=np.uint8)
    kernel = api.predict(exact, Xe, binned=True, raw=True, cfg=cfg)
    twin = api.predict(exact, Xe, binned=True, raw=True, cfg=TrainConfig(
        n_bins=bins, backend="tpu", predict_impl="onehot"))
    walk = numpy_predict.predict_proba_node_list(exact, Xe)
    assert np.array_equal(kernel, twin) and np.array_equal(
        kernel, walk.astype(np.float32)), "dyadic forest not bit-equal"
    say("forest grid: kernel, twin and walk bit-equal on 8 dyadic trees x "
        f"4,999 rows x {F} columns x 3 classes, select spans {spans}")
    # One K-block: HALVED sub-trees (two halves of 128 lanes that share
    # their spine, the path table's diagonal blocks alone: 2 tiles of
    # resolve where 4), under the packed select and under the unpacked one.
    for few in (54, 100):
        halved = random_node_list(rng, 8, (300, 900), few, bins, dyadic=True,
                                  leaf_columns=3)
        assert halved.compile().halved, few
        Xh = rng.integers(0, bins, size=(4_999, few), dtype=np.uint8)
        kernel = api.predict(halved, Xh, binned=True, raw=True, cfg=cfg)
        said = root_spans("predict")[-1]["counts"]
        assert (said["resolve_mxu_tiles"], said["select_nodes_per_lane"]) == (
            2, 2 if few <= 64 else 1), said
        assert said["spine_copies_per_subtree"] > 0, said
        assert said["pieces_per_subtree"] > 1, said
        twin = api.predict(halved, Xh, binned=True, raw=True, cfg=TrainConfig(
            n_bins=bins, backend="tpu", predict_impl="onehot"))
        walk = numpy_predict.predict_proba_node_list(halved, Xh)
        assert np.array_equal(kernel, twin) and np.array_equal(
            kernel, walk.astype(np.float32)), f"halved {few}f not bit-equal"
        say(f"forest grid: kernel, twin and walk bit-equal on 8 dyadic trees "
            f"x 4,999 rows x {few} columns in HALVED sub-trees, "
            f"path_mxu_tiles_per_tree={said['path_mxu_tiles_per_tree']} "
            f"spine_copies_per_subtree={said['spine_copies_per_subtree']}")


def score_oblivious(overrides: dict, rows: int) -> None:
    """CatBoost's Epsilon model's shape through `api.predict`: 8000 random
    oblivious trees of depth 6 over 2000 dense columns. Asserts that the
    OBLIVIOUS form of the traversal kernel served it by the auto dispatch
    (`oblivious` 1 and `select_columns_per_tree` 6 on its spans: the layout
    as it is, never the 63-node expansion; 63 groups of 128 trees streamed)
    and holds a sample of rows to the plain bit walk
    (reference/numpy_predict, in float64)."""
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.models.tree import random_oblivious
    from ddt_tpu.reference import numpy_predict
    from ddt_tpu.telemetry.annotations import root_spans

    T, D, F = 8000, 6, 2000
    rng = np.random.default_rng(SEED)
    ens = random_oblivious(rng, T, D, F, BINS, scale=0.1)
    Xb = rng.integers(0, BINS, size=(rows, F), dtype=np.uint8)
    cfg = TrainConfig(n_bins=BINS, backend="tpu", **overrides)
    comp = Compiles()
    t0 = time.perf_counter()
    scores = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    wall = time.perf_counter() - t0
    timing(f"oblivious predict, {rows} rows x {T} trees x depth {D} x {F} "
           "columns, first call", wall=wall, **comp.split(wall))
    assert scores.shape == (rows,) and scores.dtype == np.float32, \
        (scores.shape, scores.dtype)
    assert np.isfinite(scores).all(), "non-finite scores"
    root = root_spans("predict")[-1]
    built = {s["name"]: s["counts"] for s in root["spans"]}[
        "ddt:predict:ensemble"]
    said = {k: root["counts"][k] for k in (
        "oblivious", "select_columns_per_tree", "select_k_blocks",
        "routing_tables", "tables_streamed_bytes", "chunks")}
    say(f"oblivious predict: ddt:predict:ensemble {built}; root {said}")
    assert built["oblivious"] == root["counts"]["oblivious"] == 1, built
    assert built["select_columns_per_tree"] == D, "an expansion served"
    assert built["trees_per_step"] == 128, "the oblivious kernel did not serve"
    assert (built["table_blocks"], built["select_k_blocks"]) == (63, 16), built
    assert built["oblivious_mxu_tiles_per_tree"] == 0.75, built
    # all but the last of a row tile's 2 x 63 resolves run beside a later
    # sub-tile's select (PR 40)
    assert built["resolves_under_select"] == 0.9921, built
    assert_compiled_kernel(cfg, ens, rows, "oblivious")
    n = min(2_000, rows)
    want = numpy_predict.predict_raw_oblivious(ens, Xb[:n], dtype=np.float64)
    gap = float(np.abs(scores[:n] - want).max())
    say(f"oblivious scores: {n} rows against reference/numpy_predict "
        f"(float64), max |diff| = {gap:.2e} (<= 5e-5)")
    assert gap <= 5e-5, gap


def score_oblivious_grid(overrides: dict) -> None:
    """The oblivious kernel, compiled, against its jax.numpy twin and the
    plain bit walk on the shapes' edges: depth 1, 6 and 8; 28, 129 and 2000
    columns (1, 2 and 16 K-blocks of the select, the second of one column);
    1, 1025 and 4,999 rows (one ragged row tile, two and three); 130
    trees (two groups, the last with 126 filler lanes), dyadic leaf values,
    so the three agree in every bit."""
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.models.tree import random_oblivious
    from ddt_tpu.ops.predict_oblivious import predict_oblivious_fits
    from ddt_tpu.reference import numpy_predict
    from ddt_tpu.telemetry.annotations import root_spans

    rng = np.random.default_rng(SEED)
    kernel = TrainConfig(n_bins=BINS, backend="tpu", **overrides)
    twin = TrainConfig(n_bins=BINS, backend="tpu", predict_impl="onehot")
    t0 = time.perf_counter()
    for D in (1, 6, 8):
        for F in (28, 129, 2000):
            ens = random_oblivious(rng, 130, D, F, BINS, dyadic=True,
                                   scale=0.5, bias=0.25)
            fits = bool(overrides) or predict_oblivious_fits(D, F)
            for rows in (1, 1_025, 4_999):
                Xb = rng.integers(0, BINS, size=(rows, F), dtype=np.uint8)
                want = numpy_predict.predict_raw_oblivious(
                    ens, Xb, np.float64).astype(np.float32)
                # the twin first: the program whose stages are read after
                # this phase is the last one built, the kernel's
                assert np.array_equal(
                    api.predict(ens, Xb, binned=True, raw=True, cfg=twin),
                    want), (D, F, rows, "jax.numpy form")
                got = api.predict(ens, Xb, binned=True, raw=True, cfg=kernel)
                built = root_spans("predict")[-1]["counts"]
                assert built["oblivious"] == 1 and built[
                    "select_columns_per_tree"] == D and built[
                        "select_k_blocks"] == -(-F // 128), built
                # depth 8 at 2000 columns is past the kernel's VMEM rule:
                # the auto dispatch hands it to the jax.numpy form
                assert (built["tables_streamed_bytes"] > 0) == fits, built
                assert np.array_equal(got, want), (
                    D, F, rows, float(np.abs(got - want).max()))
            if fits:
                assert_compiled_kernel(kernel, ens, 4_999,
                                       f"oblivious depth={D} F={F}")
    timing("oblivious grid: 3 depths x 3 widths x 3 row counts, kernel == "
           "jax.numpy form == bit walk in every bit",
           wall=time.perf_counter() - t0)


def check_device_stages() -> None:
    """Every instruction the scoring programs traced from this package is
    under a named stage (telemetry/annotations.device_stages: the newest
    heap model's program, the routed one, the node list's and the oblivious
    ensemble's, each at the chunk loop's own shape). An `unscoped`
    instruction with a source line in `ddt_tpu/` is device work that no
    per-layer metric of the benchmark would read: it fails here, before a
    benchmark run."""
    from ddt_tpu.telemetry.annotations import UNSCOPED, device_stages

    t0 = time.perf_counter()
    stages = device_stages()
    for program in ("jit_predict_raw_effective",
                    "jit_predict_raw_effective_paths",
                    "jit_predict_raw_effective_oblivious"):
        held = stages[program]
        lost = {name: e for name, e in held.items()
                if e["stage"] == UNSCOPED
                and e["source"].startswith("ddt_tpu/")}
        assert not lost, f"{program}: under no device stage: {lost}"
        by_stage = collections.Counter(e["stage"] for e in held.values())
        say(f"device stages of {program}: " + ", ".join(
            f"{k} {v}" for k, v in sorted(by_stage.items())))
    timing("device_stages(), three programs", wall=time.perf_counter() - t0)


def parity_against_reference(overrides: dict) -> None:
    """5 trees on 20k rows: the chip against reference/numpy_trainer (pure
    NumPy — no native library decides this verdict)."""
    from ddt_tpu import api
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.data.datasets import synthetic_binary
    from ddt_tpu.data.quantizer import quantize
    from ddt_tpu.reference import numpy_trainer
    from ddt_tpu.utils.metrics import auc

    X, y = synthetic_binary(24_000, n_features=12, seed=31)
    Xt, yt, Xv, yv = X[:20_000], y[:20_000], X[20_000:], y[20_000:]
    Xb, mapper = quantize(Xt, n_bins=BINS, seed=31)
    Xvb = mapper.transform(Xv)
    cfg = TrainConfig(n_trees=5, max_depth=4, n_bins=BINS, backend="tpu",
                      **overrides)
    chip = api.train(Xb, yt, cfg, binned=True, log_every=10**9).ensemble
    ref = numpy_trainer.fit(Xb, yt, cfg.replace(backend="cpu"))
    agree = float((chip.feature == ref.feature).mean())
    d_auc = abs(auc(yv, chip.predict_raw(Xvb, binned=True))
                - auc(yv, ref.predict_raw(Xvb, binned=True)))
    say(f"parity vs reference/numpy_trainer (5 trees, 20k rows): "
        f"split agreement = {agree:.4f} (>= {PARITY_MIN_AGREEMENT}), "
        f"held-out AUC difference = {d_auc:.5f} "
        f"(<= {PARITY_MAX_AUC_DELTA})")
    assert agree >= PARITY_MIN_AGREEMENT, agree
    assert d_auc <= PARITY_MAX_AUC_DELTA, d_auc


def barrier_experiment(be, Xb) -> None:
    """Is jax.block_until_ready a barrier on this machine? One histogram
    build timed three ways: not waited for, under block_until_ready, under
    a scalar read-back (which cannot return before the program ran)."""
    import jax
    import jax.numpy as jnp

    rows = Xb.shape[0]
    rng = np.random.default_rng(SEED)
    data = be.upload(Xb)
    g = be._put_rows(rng.standard_normal(rows).astype(np.float32))
    h = be._put_rows(np.ones(rows, np.float32))
    ni = be._put_rows(np.zeros(rows, np.int32))

    def build():
        return be.build_histograms(data, g, h, ni, 1)

    float(jnp.sum(build()))                  # compile both programs
    jax.block_until_ready(build())
    enq, blk, rdb = [], [], []
    for _ in range(5):
        t0 = time.perf_counter()
        out = build()
        enq.append(time.perf_counter() - t0)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        jax.block_until_ready(build())
        blk.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(jnp.sum(build()))
        rdb.append(time.perf_counter() - t0)
    med = {k: float(np.median(v)) * 1e3
           for k, v in (("enqueue_only", enq), ("block_until_ready", blk),
                        ("scalar_readback", rdb))}
    say("smoke timing (one sample of 5, not a measurement): histogram "
        f"build, {rows} rows x {FEATURES} features, 1 node: "
        + " ".join(f"{k}={v:.2f}ms" for k, v in med.items()))
    say("block_until_ready waits for the device: "
        + ("yes" if med["block_until_ready"] > 0.5 * med["scalar_readback"]
           else "NO — it returned long before the read-back did"))


def four_device_phases(cfg, Xb, y, ens1, scores1) -> None:
    """The same ten rounds on a rows=4 mesh and on a 2x2 mesh."""
    rows = Xb.shape[0]
    for label, kw, shard_shape in (
            ("rows=4", dict(n_partitions=4), (rows // 4, FEATURES)),
            ("2x2", dict(mesh_shape=(2, 2)), (rows // 2, FEATURES // 2))):
        cfg4 = cfg.replace(**kw)
        ens4, scores4, be4 = train_and_score(cfg4, Xb, y, label)
        data = be4.upload(Xb)
        assert len(data.sharding.device_set) == 4, data.sharding
        shapes = [tuple(s.data.shape) for s in data.addressable_shards]
        assert shapes == [shard_shape] * 4, shapes
        del data
        text = be4._rounds_fns[ROUNDS].lower(
            *rounds_program_args(be4, rows, FEATURES)).compile().as_text()
        assert "reduce-scatter" in text, \
            f"{label}: compiled rounds program has no reduce-scatter"
        same = all(np.array_equal(getattr(ens4, f), getattr(ens1, f))
                   for f in ("feature", "threshold_bin", "is_leaf"))
        if same:
            say(f"{label}: 4 devices hold a quarter each {shard_shape}, "
                "reduce-scatter compiled in, all "
                f"{ens1.n_trees} trees match the one-device trees in "
                "structure")
        else:
            # f32 sums taken in another order can flip a decision whose
            # candidates tie at a bf16 boundary; anything else is a bug.
            # The at-scale cross-partition contract (tests/tree_compare):
            # bitwise up to the first divergence, which must be a PROVEN
            # tie; later trees train on what that choice changed.
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tests"))
            from tree_compare import assert_prefix_identity_mod_ties

            prefix, first = assert_prefix_identity_mod_ties(
                ens1, ens4, cfg.min_split_gain)
            say(f"{label}: {prefix} of {ens1.n_trees} trees match the "
                f"one-device trees bitwise; tree {first} diverges at a "
                "proven bf16 tie (f32 summation order)")
        np.testing.assert_allclose(scores4[:SCORE_CHECK_ROWS],
                                   ens4.predict_raw(
                                       Xb[:SCORE_CHECK_ROWS], binned=True),
                                   **SCORE_TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, 1/100 of the rows, kernels interpreted; "
                         "prints REHEARSAL, never the pass line")
    args = ap.parse_args(argv)

    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif not (os.environ.get("JAX_PLATFORMS")
              or os.environ.get("JAX_PLATFORM_NAME")):
        # A chip that cannot be opened must be an error, not a CPU run.
        jax.config.update("jax_platforms", "tpu")
    dev = jax.devices()[0]
    count = len(jax.devices())
    say(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={count}")
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX's platform is {dev.platform!r}, not 'tpu' "
              "(JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}); this check runs on "
              "the chip only. `--rehearse` debugs the control flow on a "
              "CPU and proves nothing.", file=sys.stderr)
        return 1

    from ddt_tpu.backends.tpu import (DEFAULT_COMPILE_CACHE_DIR,
                                      enable_persistent_compile_cache)
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.data.datasets import synthetic_binary
    from ddt_tpu.data.quantizer import quantize
    from ddt_tpu.telemetry import counters

    enable_persistent_compile_cache()
    cache_dir = jax.config.jax_compilation_cache_dir
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} ({n_cached} entries at start; "
        + ("from $JAX_COMPILATION_CACHE_DIR"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "the checkout's default") + ")")
    assert os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or cache_dir == DEFAULT_COMPILE_CACHE_DIR, cache_dir
    counters.install_jax_listener()
    all_compiles = Compiles()
    t_start = time.perf_counter()

    rows = ROWS // 100 if args.rehearse else ROWS
    # Rehearsal forces what a TPU resolves by default, so that the same
    # kernels run (interpreted) and the same assertions hold.
    overrides = (dict(hist_impl="pallas", hist_subtraction="on",
                      predict_impl="pallas") if args.rehearse else {})
    t0 = time.perf_counter()
    X, y = synthetic_binary(rows, n_features=FEATURES, seed=SEED)
    Xb, _ = quantize(X, n_bins=BINS, seed=SEED)
    del X
    timing(f"host: generate + quantize {rows} x {FEATURES}",
           wall=time.perf_counter() - t0)
    cfg = TrainConfig(n_trees=ROUNDS, max_depth=DEPTH, n_bins=BINS,
                      backend="tpu", **overrides)

    ens, scores, be = train_and_score(cfg, Xb, y, "one device")
    check_scores_against_numpy(ens, Xb, scores)
    score_multiclass(overrides, MC_ROWS // 100 if args.rehearse else MC_ROWS)
    score_routed(overrides, ROUTED_ROWS // 100 if args.rehearse
                 else ROUTED_ROWS)
    score_node_list(overrides, LEAFWISE_ROWS // 100 if args.rehearse
                    else LEAFWISE_ROWS)
    score_node_list_grid(overrides)
    score_forest(overrides, FOREST_ROWS // 100 if args.rehearse
                 else FOREST_ROWS)
    score_oblivious(overrides, OBLIVIOUS_ROWS // 100 if args.rehearse
                    else OBLIVIOUS_ROWS)
    score_oblivious_grid(overrides)
    check_device_stages()
    parity_against_reference(overrides)
    barrier_experiment(be, Xb)
    if count >= 4:
        four_device_phases(cfg, Xb, y, ens, scores)
    else:
        say(f"{count} device(s) visible: the four-device phases did not "
            "run")

    snap = counters.snapshot()
    assert snap["hist_oom_degrades"] == 0, snap["hist_oom_degrades"]
    assert snap["fault_retries"] == 0, snap["fault_retries"]
    wall = time.perf_counter() - t_start
    timing("whole run", wall=wall, **all_compiles.split(wall))
    n_now = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {n_now} entries at end ({n_now - n_cached} new); "
        "hist_oom_degrades=0 fault_retries=0")
    if args.rehearse:
        say("REHEARSAL complete: CPU, interpreted kernels, "
            f"{rows} rows — this proves nothing about the chip")
        return 0
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
