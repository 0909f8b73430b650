"""The path-matrix kernel's feature select at two nodes a result lane
(ops/predict_paths.select_nodes_per_lane, pack_select): the interpreted
kernel held to its jax.numpy twin and to the plain node walk in every bit,
on trees built to sit on the packing's edges, and the plan's arithmetic."""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import random_node_list
from ddt_tpu.ops import predict as predict_ops
from ddt_tpu.ops import predict_paths
from ddt_tpu.reference import numpy_predict

N_BINS = 256                    # bins 0..255; with NaN routes 255 is NaN's
LEAVES = {128: 100, 256: 255, 512: 500}     # node lanes -> leaves a tree
N_TREES = 5
EDGE_BINS = (0, 1, 253, 254, 255)


@functools.lru_cache(maxsize=None)
def edge_model(n_features: int, lanes: int, missing: bool):
    """Five random leaf-wise trees moved onto the packing's edges. Node n
    and node n + Wp answer on one result lane. Tree 0: both nodes of
    every lane read the SAME feature. Tree 1: thresholds 0, 253 and 254
    (the last value bin below a NaN bin of 255) by turns, so a lane pairs
    unlike ones. Tree 2 (NaN routes): a NaN-left node beside a NaN-right
    one on every lane, tree 3 the other way round. Tree 4 as drawn."""
    rng = np.random.default_rng([n_features, lanes, missing])
    ens = random_node_list(rng, N_TREES, LEAVES[lanes], n_features, N_BINS,
                           dyadic=True, missing=missing, learning_rate=0.5,
                           base_score=0.25, loss="logloss")
    n_nodes = ens.feature.shape[1]
    wp = predict_paths._lane_pad(lanes // 2)
    second = np.arange(wp, n_nodes)             # the lanes' second nodes
    ens.feature[0, second] = ens.feature[0, second - wp]
    ens.threshold_bin[1] = np.array([0, 253, 254])[
        (np.arange(n_nodes) + np.arange(n_nodes) // 128) % 3]
    if missing:
        ens.default_left[2], ens.default_left[3] = True, False
        ens.default_left[2, second] = False
        ens.default_left[3, second] = True
    return ens, ens.compile()


@functools.lru_cache(maxsize=None)
def rows_and_answers(n_features: int, lanes: int, missing: bool, n_rows: int):
    """uint8 rows heavy on the edge bins, the node walk's scores and the
    jax.numpy twin's over the tables as the model compiles them."""
    ens, ce = edge_model(n_features, lanes, missing)
    rng = np.random.default_rng([n_features, lanes, missing, n_rows])
    Xb = rng.integers(0, N_BINS, (n_rows, n_features)).astype(np.uint8)
    edge = rng.random(Xb.shape) < 0.5
    Xb[edge] = rng.choice(EDGE_BINS, int(edge.sum()))
    walk = numpy_predict.predict_raw_node_list(
        ens, Xb, np.float64).astype(np.float32)
    twin = np.asarray(predict_ops._predict_paths(
        *map(jnp.asarray, ce.arrays()), jnp.asarray(Xb), learning_rate=0.5,
        base=0.25, missing_routes=missing))
    return Xb, walk, twin


def handed_over(ce, n_features: int):
    """The tables as a backend hands them to the kernel: packed on the
    host where the select answers two nodes a lane."""
    if predict_paths.select_nodes_per_lane(n_features, ce.lanes) == 1:
        return ce.arrays()
    return (*predict_paths.pack_select(ce.sel, ce.planes, n_features,
                                       xp=np), ce.paths)


@pytest.fixture
def blocks_of_three(monkeypatch):
    """Five trees in two table blocks of three: one FILLER tree."""
    monkeypatch.setattr(predict_paths, "_MAX_TREES_PER_STEP", 4)
    plan = predict_paths.path_plan(N_TREES, 256, 28)
    assert (plan.trees_per_step, plan.table_blocks) == (3, 2)


@pytest.mark.parametrize("row_dtype", [np.uint8, np.int32],
                         ids=["uint8", "int32"])
@pytest.mark.parametrize("n_rows", [1, 255, 513, 4_999])
@pytest.mark.parametrize("missing", [False, True], ids=["plain", "nan"])
@pytest.mark.parametrize("lanes", [128, 256, 512])
@pytest.mark.parametrize("n_features", [1, 28, 56, 57, 64, 65, 129])
def test_kernel_is_the_twin_and_the_walk_in_every_bit(
        n_features, lanes, missing, n_rows, row_dtype, blocks_of_three):
    _, ce = edge_model(n_features, lanes, missing)
    Xb, walk, twin = rows_and_answers(n_features, lanes, missing, n_rows)
    np.testing.assert_array_equal(twin, walk)
    got = np.asarray(predict_paths.predict_paths_pallas(
        *map(jnp.asarray, handed_over(ce, n_features)),
        jnp.asarray(Xb.astype(row_dtype)), learning_rate=0.5, base=0.25,
        missing_routes=missing))
    np.testing.assert_array_equal(got, walk)


@pytest.mark.parametrize("lanes,n_features,per_lane,tiles", [
    (256, 28, 2, 5), (512, 28, 2, 18), (128, 28, 1, 2), (256, 968, 1, 20),
    (256, 64, 2, 5), (256, 65, 1, 6), (384, 28, 2, 11), (512, 129, 1, 24)])
def test_the_rule_and_the_tiles_it_buys(lanes, n_features, per_lane, tiles):
    assert predict_paths.select_nodes_per_lane(n_features, lanes) == per_lane
    assert predict_paths.path_mxu_tiles_per_tree(lanes, n_features) == tiles
    plan = predict_paths.path_plan(500, lanes, n_features)
    assert plan.select_nodes_per_lane == per_lane
    assert plan.path_mxu_tiles_per_tree == tiles
    assert plan.span_counts()["select_nodes_per_lane"] == per_lane
    # the jax.numpy form takes the select as the model compiles it
    unserved = predict_paths.path_plan(500, lanes, n_features, served=False)
    assert unserved.select_nodes_per_lane == 1
    assert unserved.path_mxu_tiles_per_tree == (
        predict_paths.path_mxu_tiles_per_tree(lanes, n_features, 1))


@pytest.mark.parametrize("n_features,missing,per_step,blocks,tree_bytes", [
    # [80, 128] bf16 where [32, 256] was: two copies of 32 K rows and the
    # mantissa's 8, to whole bf16 sublane tiles
    (28, False, 50, 10, 80 * 128 * 2 + 8 * 256 * 4 + 256 * 256 * 2),
    (28, True, 50, 10, 80 * 128 * 2 + 8 * 256 * 4 + 256 * 256 * 2),
    # no K row left for the mantissa at 57..64 columns: the whole tile
    (64, False, 50, 10, 128 * 128 * 2 + 8 * 256 * 4 + 256 * 256 * 2),
    (968, True, 10, 50, 976 * 256 * 2 + 8 * 256 * 4 + 256 * 256 * 2)])
def test_the_blocks_stay_and_the_bytes_follow_the_table(
        n_features, missing, per_step, blocks, tree_bytes):
    plan = predict_paths.path_plan(500, 256, n_features,
                                   missing_routes=missing)
    assert (plan.trees_per_step, plan.table_blocks) == (per_step, blocks)
    assert plan.table_bytes == 500 * tree_bytes
    assert predict_paths.predict_paths_fits(256, n_features)


def test_a_packed_sub_tile_is_twice_as_long():
    """The walk's sub-tile: 512 rows, 1024 where the select answers two
    nodes a lane (timings on the chip, the module's constants); the plan
    charges VMEM for it and a short batch is one tile of whole sub-tiles."""
    assert (predict_paths._sub_rows(1), predict_paths._sub_rows(2)) \
        == (predict_paths.SUB_ROWS, 1024) == (512, 1024)
    assert predict_paths.TILE_ROWS % 1024 == 0
    # the same tables at both lengths fit 64 trees: the cap binds, not VMEM
    assert predict_paths.path_plan(64, 256, 28).trees_per_step == 64
    assert predict_paths.path_plan(64, 256, 65).trees_per_step == 64


@pytest.mark.parametrize("n_features,lanes,k_rows", [
    (1, 256, 32), (28, 256, 80), (56, 512, 128), (57, 256, 128),
    (64, 384, 128)])
def test_pack_select_is_one_table_on_the_host_and_in_a_program(
        n_features, lanes, k_rows):
    """numpy's and jax.numpy's packing are one table; its shape is the
    plan's; lane n holds node n's one-hot over the first copy's K rows and
    node Wp + n's over the second's, the mantissa where it has a row."""
    rng = np.random.default_rng(n_features)
    leaves = lanes - 3
    ce = random_node_list(rng, 3, leaves, n_features, N_BINS, missing=True,
                          learning_rate=0.5, base_score=0.0,
                          loss="logloss").compile()
    assert ce.lanes == lanes
    sel, planes = predict_paths.pack_select(ce.sel, ce.planes, n_features,
                                            xp=np)
    sel_j, planes_j = predict_paths.pack_select(
        jnp.asarray(ce.sel), jnp.asarray(ce.planes), n_features)
    np.testing.assert_array_equal(np.asarray(sel_j, np.float32),
                                  sel.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(planes_j), planes)
    wp = predict_paths._lane_pad(lanes // 2)
    assert sel.shape == (3, k_rows, wp) == (
        3, *predict_paths._select_shape(lanes, n_features, 2))
    assert sel.dtype == ce.sel.dtype and planes.shape == ce.planes.shape
    stride = -(-n_features // 8) * 8
    first = sel[:, :n_features].astype(np.float32)
    second = sel[:, stride:stride + n_features].astype(np.float32)
    unpacked = ce.sel[:, :n_features].astype(np.float32)
    np.testing.assert_array_equal(first, unpacked[:, :, :wp])
    np.testing.assert_array_equal(second[:, :, :lanes - wp],
                                  unpacked[:, :, wp:])
    mantissa = sel[:, 2 * stride:].astype(np.float32)
    if 2 * stride + 8 <= 128:
        assert (mantissa[:, 0] == 1.5 * 2.0 ** 23).all()
        assert not mantissa[:, 1:].any()
    else:
        assert mantissa.size == 0
    # thresholds: the first copy's as they were, the second's in the high
    # byte above the mantissa, +BIG where there is no node
    np.testing.assert_array_equal(planes[:, [1, 2, 4, 5, 6, 7]],
                                  ce.planes[:, [1, 2, 4, 5, 6, 7]])
    np.testing.assert_array_equal(planes[:, [0, 3], :wp],
                                  ce.planes[:, [0, 3], :wp])
    thr, up = ce.planes[:, 0, wp:], ce.planes[:, 3, wp:]
    M = 1.5 * 2.0 ** 23
    np.testing.assert_array_equal(
        planes[:, 0, wp:], np.where(thr < 2.0 ** 29,
                                    M + 256 * (thr + 1) - 1, thr))
    np.testing.assert_array_equal(
        planes[:, 3, wp:], np.where(up < 2.0 ** 29, M + 256 * up, up))


@pytest.mark.parametrize("missing", [False, True], ids=["plain", "nan"])
@pytest.mark.parametrize("n_features,lanes,per_lane", [
    (28, 256, 2), (64, 512, 2), (65, 256, 1), (28, 128, 1)])
def test_a_backend_packs_once_a_model_and_says_so(n_features, lanes,
                                                  per_lane, missing):
    """Through api.predict: the tables go up packed (the `ensemble` span's
    `bytes` are the packed plan's), `select_nodes_per_lane` stands on the
    span, and the scores are the walk's and the jax.numpy form's."""
    from ddt_tpu.telemetry.annotations import recent_spans

    ens, _ = edge_model(n_features, lanes, missing)
    Xb, walk, _ = rows_and_answers(n_features, lanes, missing, 513)
    cfg = TrainConfig(backend="tpu", n_bins=N_BINS, predict_impl="pallas")
    got = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg)
    np.testing.assert_array_equal(got, walk)
    counts = [sp for sp in recent_spans()
              if sp["name"] == "ddt:predict:ensemble"][-1]["counts"]
    assert counts["select_nodes_per_lane"] == per_lane
    assert counts["path_mxu_tiles_per_tree"] == (
        predict_paths.path_mxu_tiles_per_tree(lanes, n_features))
    assert counts["bytes"] == counts["table_bytes"] == N_TREES * (
        predict_paths._tree_bytes(lanes, n_features, per_lane))
    assert counts["missing_routes"] == int(missing)
    twin = api.predict(ens, Xb, binned=True, raw=True, cfg=TrainConfig(
        backend="tpu", n_bins=N_BINS, predict_impl="onehot"))
    np.testing.assert_array_equal(twin, walk)


def test_the_packed_program_makes_no_table_and_widens_nothing(monkeypatch):
    """The chunk program as a backend builds it for a model whose select
    packs: its tables go up packed ([K2, W/2] where [Fp, W] was), and no
    instruction of the program is under `predict:tables` or
    `predict:widen`; handed the tables as the model compiles them, every
    call's program packs them, under `predict:tables`."""
    import jax

    from ddt_tpu.telemetry import annotations as an

    jax.clear_caches()
    ens, ce = edge_model(28, 256, False)
    Xb, walk, _ = rows_and_answers(28, 256, False, 513)
    be = get_backend(TrainConfig(backend="tpu", n_bins=N_BINS,
                                 predict_impl="pallas"))
    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", 256)
    np.testing.assert_array_equal(be.predict_raw(ens, Xb), walk)
    fn, ens_dev = be._predict_fn(ens)
    assert [a.shape for a in ens_dev] == [
        (N_TREES, 80, 128), (N_TREES, 8, 256), (N_TREES, 256, 256)]
    held = an.device_stages()["jit_predict_raw_effective_paths"]
    seen = {e["stage"] for e in held.values()}
    assert "predict:traverse_paths" in seen
    assert not seen & {"predict:tables", "predict:widen"}

    text = jax.jit(fn).lower(*map(jnp.asarray, ce.arrays()),
                             jnp.asarray(Xb)).compile().as_text()
    assert "predict:tables" in text and "predict:widen" not in text
