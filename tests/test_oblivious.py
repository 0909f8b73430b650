"""The OBLIVIOUS ensemble layout (CatBoost's symmetric trees) and its scoring
forms, held to the plain bit walk of `ddt_tpu/reference/numpy_predict.py` on
seeded random models (CPU, small sizes, the Pallas kernel interpreted), and
to the EXISTING scorers over the same model expanded to a heap and to a node
list: three independent opinions."""

from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.models import catboost_io
from ddt_tpu.models.tree import (LAYOUTS, NodeListEnsemble,
                                 ObliviousEnsemble, TreeEnsemble,
                                 ensemble_from_dict, random_node_list,
                                 random_oblivious)
from ddt_tpu.ops import predict as predict_ops
from ddt_tpu.ops import predict_oblivious
from ddt_tpu.reference import numpy_predict
from ddt_tpu.telemetry import annotations as an
from ddt_tpu.utils import device


def oblivious(seed, n_trees, depth, n_features, dyadic=True, **meta):
    meta = {"scale": 0.5, "bias": 0.25, **meta}
    return random_oblivious(np.random.default_rng(seed), n_trees, depth,
                            n_features, dyadic=dyadic, **meta)


def rows(seed, n, n_features, n_bins=255):
    return np.random.default_rng(seed).integers(
        0, n_bins, (n, n_features)).astype(np.uint8)


def cfg(impl):
    return TrainConfig(backend="tpu", predict_impl=impl)


def with_borders(ens, seed):
    """`ens` with border lists of its own (n_bins - 1 a feature) and the
    raw borders its split ranks name, as an import carries them."""
    rng = np.random.default_rng(seed)
    ens.borders = np.sort(rng.standard_normal(
        (ens.n_features, ens.n_bins - 1)).astype(np.float32), axis=1)
    ens.split_raw = ens.borders[ens.split_feature, ens.split_bin]
    return ens


# ------------------------------------------------------------------ #
# the reference walk and the bit order
# ------------------------------------------------------------------ #

def two_split_tree():
    """bit 0: f0 > 3, bit 1: f1 > 5; the four leaves told apart."""
    return ObliviousEnsemble(
        split_feature=np.array([[0, 1]], np.int32),
        split_bin=np.array([[3, 5]], np.int32),
        leaf_value=np.array([[10.0, 20.0, 30.0, 40.0]], np.float32),
        n_features=2, scale=0.1, bias=1.0, loss="mse", n_bins=255)


def test_the_first_split_is_the_low_bit():
    ens = two_split_tree()
    Xb = np.array([[3, 5], [4, 5], [3, 6], [4, 6], [0, 0], [254, 254]],
                  np.uint8)
    want_leaf = [0, 1, 2, 3, 0, 3]
    assert list(numpy_predict.leaf_of_rows_oblivious(ens, 0, Xb)) == want_leaf
    assert list(ens._leaf_np(Xb, binned=True)[0]) == want_leaf
    want = 1.0 + 0.1 * np.array([10.0, 20.0, 30.0, 40.0, 10.0, 40.0])
    for dtype in (np.float32, np.float64):
        got = numpy_predict.predict_raw_oblivious(ens, Xb, dtype)
        assert got.dtype == dtype
        np.testing.assert_allclose(got, want, rtol=1e-6)
    for impl in ("pallas", "onehot"):
        got = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg(impl))
        np.testing.assert_allclose(got, want, rtol=1e-6)
    # an index built high-bit-first would swap leaves 1 and 2
    assert want[1] != want[2]


# ------------------------------------------------------------------ #
# kernel (interpreted), twin and host walk against the reference
# ------------------------------------------------------------------ #

GRID = [(d, f) for d in (1, 6, 8) for f in (28, 200, 2000)]


@pytest.mark.parametrize("depth,n_features", GRID,
                         ids=[f"d{d}-f{f}" for d, f in GRID])
def test_kernel_twin_and_walk_agree_with_the_reference(depth, n_features):
    """One, two and 16 K-blocks of the select; 130 trees: two groups, the
    last with 126 filler lanes. Dyadic leaf values and scale: the sums
    round nowhere, so the forms agree to the bit."""
    ens = oblivious(40 + depth, 130, depth, n_features)
    Xb = rows(41, 300, n_features)
    want = numpy_predict.predict_raw_oblivious(ens, Xb, np.float64)
    assert np.array_equal(ens.predict_raw(Xb, binned=True), want)
    ce = ens.compile()
    assert ce.sel.shape == (2, depth, -(-n_features // 16) * 16, 128)
    tables = [jnp.asarray(a) for a in ce.arrays()]
    kernel = predict_oblivious.predict_oblivious_pallas(
        *tables, jnp.asarray(Xb), scale=ce.scale, bias=ce.bias)
    twin = predict_ops._predict_oblivious(
        *tables, jnp.asarray(Xb), scale=ce.scale, bias=ce.bias)
    assert np.array_equal(np.asarray(kernel), want)
    assert np.array_equal(np.asarray(twin), want)
    plan = predict_oblivious.oblivious_plan(130, depth, n_features)
    assert plan.select_k_blocks == -(-n_features // 128)
    assert plan.table_blocks == 2 and plan.trees_per_step == 128


@pytest.mark.parametrize("impl", ["pallas", "onehot"])
def test_api_predict_on_the_normal_path(impl):
    """Ragged rows past one chunk: the chunk loop, the upload pieces, the
    fetch and the place are the other layouts'."""
    ens = oblivious(50, 200, 6, 40, dyadic=False)
    Xb = rows(51, 1111, 40)
    want = numpy_predict.predict_raw_oblivious(ens, Xb, np.float64)
    be = get_backend(cfg(impl))
    type(be).PREDICT_ROW_CHUNK, old = 256, type(be).PREDICT_ROW_CHUNK
    try:
        got = api.predict(ens, Xb, binned=True, raw=True, backend=be)
    finally:
        type(be).PREDICT_ROW_CHUNK = old
    assert got.dtype == np.float32 and got.shape == (1111,)
    assert np.abs(got - want).max() <= 1e-5
    root = an.root_spans("predict")[-1]["counts"]
    assert root["branch"] == "chunks" and root["chunks"] == 5
    assert root["oblivious"] == 1 and root["select_columns_per_tree"] == 6
    assert root["routing_tables"] == 0 and root["select_k_blocks"] == 1
    assert (root["tables_streamed_bytes"] > 0) == (impl == "pallas")
    proba = api.predict(ens, Xb, binned=True, backend=be)
    np.testing.assert_allclose(proba, 1 / (1 + np.exp(-want)), rtol=1e-5)


def test_three_opinions_heap_and_node_list_scorers_agree():
    """`to_heap()` and `to_node_list()` scored by the EXISTING scorers (the
    heap kernel and one-hot form, the path kernel and its twin, the host
    walks) give the oblivious forms' scores."""
    ens = oblivious(60, 9, 4, 12)
    Xb = rows(61, 257, 12)
    want = numpy_predict.predict_raw_oblivious(ens, Xb, np.float64)
    heap, nodes = ens.to_heap(), ens.to_node_list()
    assert isinstance(heap, TreeEnsemble) and heap.max_depth == 4
    assert isinstance(nodes, NodeListEnsemble)
    assert (nodes.n_leaves == 16).all()       # 15 nodes where 4 splits were
    assert np.array_equal(numpy_predict.predict_raw(heap, Xb, np.float64),
                          want)
    assert np.array_equal(
        numpy_predict.predict_raw_node_list(nodes, Xb, np.float64), want)
    for model in (ens, heap, nodes):
        for impl in ("pallas", "onehot"):
            got = api.predict(model, Xb, binned=True, raw=True,
                              cfg=cfg(impl))
            assert np.array_equal(got, want), (type(model).__name__, impl)


def test_a_shallow_tree_is_padded_in_its_high_bits():
    """Depth 2 beside depth 3: the filler split never sets its bit, so the
    leaves it would reach stay unvisited."""
    ens = ObliviousEnsemble(
        split_feature=np.array([[0, 1, 2], [1, 0, 0]], np.int32),
        split_bin=np.array([[3, 5, 7], [2, 4, 255]], np.int32),
        leaf_value=np.array([np.arange(8), [1, 2, 3, 4, 0, 0, 0, 0]],
                            np.float32),
        n_features=3, n_bins=255)
    assert ens.n_splits == 5
    Xb = rows(62, 400, 3)
    assert (ens._leaf_np(Xb, binned=True)[1] < 4).all()
    want = numpy_predict.predict_raw_oblivious(ens, Xb, np.float64)
    for impl in ("pallas", "onehot"):
        got = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg(impl))
        assert np.array_equal(got, want)


# ------------------------------------------------------------------ #
# the pipeline's edges (PR 40): a sub-tile resolved beside the next one's
# select, a step's last by the next group's step, a row tile's last flushed
# ------------------------------------------------------------------ #

# (rows, trees, depth, features): one sub-tile a step (no pipeline), exactly
# two, a ragged last tile; one group and several (a deferred resolve crosses
# a group and is flushed at the last); depth 1 and the deepest pipelined; a
# depth the rule leaves rolled (9, 10); 1, 2 and 16 K-blocks.
PIPELINE = [
    (1, 5, 1, 28), (1024, 130, 6, 28), (1025, 130, 6, 129),
    (2048, 5, 6, 28), (2049, 300, 1, 28), (4999, 130, 3, 129),
    (1500, 130, 8, 28), (1025, 257, 2, 2000), (1025, 130, 9, 28),
    (300, 5, 10, 28),
]


@pytest.mark.parametrize("n_rows,n_trees,depth,n_features", PIPELINE,
                         ids=["r%d-t%d-d%d-f%d" % c for c in PIPELINE])
def test_the_pipelined_kernel_at_its_edges(n_rows, n_trees, depth,
                                           n_features):
    """Kernel (interpreted), jax.numpy twin and bit walk, bit-equal on
    dyadic leaves; a second call gives the first one's bits (the scratch a
    call leaves behind is the next call's first deferred resolve: it must
    add zeros)."""
    ens = oblivious(300 + depth, n_trees, depth, n_features)
    Xb = rows(301 + n_rows, n_rows, n_features)
    want = numpy_predict.predict_raw_oblivious(ens, Xb, np.float64)
    ce = ens.compile()
    tables = [jnp.asarray(a) for a in ce.arrays()]
    score = lambda: np.asarray(predict_oblivious.predict_oblivious_pallas(
        *tables, jnp.asarray(Xb), scale=ce.scale, bias=ce.bias))
    first = score()
    assert np.array_equal(first, want)
    assert np.array_equal(score(), first)
    twin = predict_ops._predict_oblivious(
        *tables, jnp.asarray(Xb), scale=ce.scale, bias=ce.bias)
    assert np.array_equal(np.asarray(twin), want)
    # a step of one sub-tile has no select to resolve under; of two, up to
    # depth 8
    assert not predict_oblivious._pipelined(depth, 1)
    assert predict_oblivious._pipelined(depth, 2) == (depth <= 8)


def test_the_pipeline_is_a_rule_on_the_shape_and_the_span_says_its_share():
    plan = predict_oblivious.oblivious_plan
    # all but the last of a row tile's 2 x groups resolves
    assert plan(8000, 6, 2000).resolves_under_select == round(125 / 126, 4)
    assert plan(100, 8, 28).resolves_under_select == 0.5
    assert plan(300, 1, 28).resolves_under_select == round(5 / 6, 4)
    # past depth 8 the two unrolled resolves are more than the compiler's
    # scheduler orders: the rolled form, nothing under a select
    assert plan(300, 9, 28).resolves_under_select == 0.0
    assert plan(300, 10, 28).resolves_under_select == 0.0
    assert plan(8000, 6, 2000, served=False).resolves_under_select == 0.0
    assert predict_oblivious.SPAN_COUNTS[-1] == "resolves_under_select"
    assert "resolves_under_select" in predict_oblivious.PHASES_COUNTS
    # the scratch is the pipelined form's alone
    assert predict_oblivious._scratch_shapes(9, 2) == []
    assert predict_oblivious._scratch_shapes(6, 1) == []
    idx, carry = predict_oblivious._scratch_shapes(6, 2)
    assert idx.shape == (2, 1024, 128) and carry.shape == (64, 128)


# The widest shape a depth that `predict_oblivious_fits` admitted at 073a17b,
# for uint8 and for int32 rows: the pipeline's scratch lost none (the
# estimate is never more than it was).
ADMITTED = {1: (3584, 1664), 2: (3328, 1536), 3: (2976, 1408),
            4: (2736, 1328), 5: (2560, 1280), 6: (2304, 1152),
            7: (2048, 1024), 8: (1920, 1024), 9: (1664, 896),
            10: (1536, 768)}


@pytest.mark.parametrize("depth", sorted(ADMITTED))
def test_fits_admits_every_shape_it_admitted_before_the_pipeline(depth):
    fits = predict_oblivious.predict_oblivious_fits
    wide_u8, wide_i32 = ADMITTED[depth]
    assert fits(depth, wide_u8) and fits(depth, wide_i32, jnp.int32)
    assert fits(depth, 28) and fits(depth, 968)
    # and at the depths the rule leaves rolled, nothing else
    if depth > 8:
        assert not fits(depth, wide_u8 + 1)
        assert not fits(depth, wide_i32 + 1, jnp.int32)
    assert fits(7, 2000) and not fits(8, 2000)


# ------------------------------------------------------------------ #
# the dispatch rule and the plan
# ------------------------------------------------------------------ #

def test_auto_takes_the_kernel_where_the_plan_fits():
    fits = predict_oblivious.predict_oblivious_fits
    assert fits(6, 2000) and fits(8, 200) and fits(1, 28) and fits(10, 28)
    assert not fits(11, 28)              # the multiplexer's trace
    assert not fits(10, 2000)            # VMEM
    serves = predict_oblivious.kernel_serves    # the layout's own rule
    with device.assume_platform("tpu"):
        assert serves(None, 6, 2000)
        assert not serves(None, 11, 28)
    assert not serves(None, 6, 2000)     # a CPU
    assert serves(True, 11, 28)


def test_the_plan_at_the_epsilon_models_shape():
    plan = predict_oblivious.oblivious_plan(8000, 6, 2000)
    assert plan.span_counts() == {
        "oblivious": 1, "depth": 6, "select_columns_per_tree": 6,
        "trees_per_lane_tile": 21.33, "select_k_blocks": 16,
        "oblivious_mxu_tiles_per_tree": 0.75, "trees_per_step": 128,
        "table_blocks": 63, "table_bytes": 63 * (6 * 2000 * 128 * 2
                                                 + 8 * 128 * 4
                                                 + 64 * 128 * 4),
        "row_operand_bytes": 1, "leaf_columns": 1, "link": "none",
        "resolve_selects_per_tree": 63, "resolve_gathers_per_tree": 0,
        "resolves_under_select": 0.9921}
    assert plan.root_counts() == {
        "routing_tables": 0, "oblivious": 1, "select_columns_per_tree": 6,
        "select_k_blocks": 16, "leaf_columns": 1, "link": "none",
        "resolve_selects_per_tree": 63, "resolve_gathers_per_tree": 0}
    assert plan.blocks == 63 and plan.tile_rows == 2048
    twin = predict_oblivious.oblivious_plan(8000, 6, 2000, served=False)
    assert twin.oblivious == 1 and twin.blocks == 0 and twin.table_bytes == 0
    assert set(predict_oblivious.PHASES_COUNTS) == set(
        predict_oblivious.SPAN_COUNTS) - {"table_bytes"}


def test_the_ensemble_span_says_what_serves():
    ens = oblivious(70, 200, 6, 300)
    for impl, step in (("pallas", 128), ("onehot", 0)):
        get_backend(cfg(impl))._predict_fn(ens)
        built = [sp for sp in an.recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"][-1]["counts"]
        assert built["oblivious"] == 1 and built["depth"] == 6
        assert built["select_columns_per_tree"] == 6
        assert built["select_k_blocks"] == 3 and built["trees"] == 200
        assert built["trees_per_step"] == step
        assert built["table_blocks"] == (2 if step else 0)
        assert list(built)[-len(predict_oblivious.SPAN_COUNTS):] == list(
            predict_oblivious.SPAN_COUNTS)


def test_the_kernel_refuses_float_rows_and_empty_batches_score_nothing():
    ens = oblivious(71, 5, 3, 4)
    ce = ens.compile()
    tables = [jnp.asarray(a) for a in ce.arrays()]
    with pytest.raises(ValueError, match="binned"):
        predict_ops.predict_raw_effective_oblivious(
            *tables, jnp.zeros((3, 4), jnp.float32), scale=1.0, bias=0.0)
    out = predict_ops.predict_raw_effective_oblivious(
        *tables, jnp.zeros((0, 4), jnp.uint8), scale=1.0, bias=0.5)
    assert out.shape == (0,)


# ------------------------------------------------------------------ #
# the layout: constructor, save, load, cache_token
# ------------------------------------------------------------------ #

def test_constructor_refusals():
    ok = dict(split_feature=np.zeros((2, 3), np.int32),
              split_bin=np.zeros((2, 3), np.int32),
              leaf_value=np.zeros((2, 8), np.float32), n_features=4)
    ObliviousEnsemble(**ok)
    with pytest.raises(ValueError, match="vector leaves"):
        ObliviousEnsemble(**ok, loss="softmax")
    with pytest.raises(ValueError, match=r"leaf_value \[2, 8\]"):
        ObliviousEnsemble(**{**ok, "leaf_value": np.zeros((2, 4),
                                                          np.float32)})
    with pytest.raises(ValueError, match="outside 0 .. 3"):
        ObliviousEnsemble(**{**ok, "split_feature": np.full((2, 3), 4,
                                                            np.int32)})


def test_save_load_cache_token_and_cli(tmp_path, capsys):
    from ddt_tpu.cli import main

    ens = oblivious(80, 33, 5, 9, dyadic=False)
    path = str(tmp_path / "m.npz")
    ens.save(path)
    back = TreeEnsemble.load(path)
    assert isinstance(back, ObliviousEnsemble)
    assert back.cache_token() == ens.cache_token()
    bundle = api.load_model(path)
    assert isinstance(bundle.ensemble, ObliviousEnsemble)
    assert bundle.manifest is not None
    other = oblivious(80, 33, 5, 9, dyadic=False)
    assert other.cache_token() == ens.cache_token()
    other.leaf_value[0, 0] += 1.0
    assert other.cache_token() != ens.cache_token()
    other = oblivious(80, 33, 5, 9, dyadic=False, scale=0.25)
    assert other.cache_token() != ens.cache_token()

    # the CLI scores raw rows through the artifact's mapper: the model's
    # own borders
    ens = with_borders(oblivious(82, 33, 5, 9, dyadic=False, n_bins=17), 83)
    api.save_model(path, ens, mapper=ens.bin_mapper())
    X = np.random.default_rng(81).standard_normal((300, 9)).astype(
        np.float32)
    data = str(tmp_path / "rows.npz")
    np.savez(data, X=X, y=np.zeros(len(X), np.float32))
    out = str(tmp_path / "scores.npy")
    assert main(["predict", "--backend=tpu", f"--model={path}",
                 f"--data={data}", f"--out={out}"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["rows"] == 300
    phases = rec["phases_ms"]
    assert phases["oblivious"] == 1 and phases["depth"] == 5
    assert phases["select_columns_per_tree"] == 5
    assert set(predict_oblivious.PHASES_COUNTS) <= set(phases)
    assert "table_bytes" not in phases and "tables_streamed_bytes" in phases
    want = 1 / (1 + np.exp(-ens.predict_raw(X).astype(np.float64)))
    np.testing.assert_allclose(np.load(out), want, rtol=1e-5)
    assert main(["inspect", f"--model={path}", "--tree=0"]) == 0
    said = capsys.readouterr().out
    assert '"max_depth": 5' in said and "bit 0: f" in said


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_layout_names_itself_in_its_saved_dictionary(layout):
    rng = np.random.default_rng(90)
    ens = {"heap": lambda: oblivious(90, 3, 2, 4).to_heap(),
           "node_list": lambda: random_node_list(
               rng, 3, 5, 4, learning_rate=0.1, base_score=0.0,
               loss="logloss"),
           "oblivious": lambda: oblivious(90, 3, 2, 4)}[layout]()
    d = ens.to_dict()
    assert bytes(d["layout"]).decode() == layout
    back = ensemble_from_dict(d)
    assert type(back) is LAYOUTS[layout] is type(ens)
    assert back.cache_token() == ens.cache_token()


def test_the_old_saved_forms_are_still_read_and_an_unknown_one_is_named():
    heap = oblivious(91, 3, 2, 4).to_heap()
    d = heap.to_dict()
    d.pop("layout")                       # a heap saved before the key
    back = ensemble_from_dict(d)
    assert isinstance(back, TreeEnsemble)
    assert back.cache_token() == heap.cache_token()
    d["layout"] = np.bytes_(b"forest")
    with pytest.raises(ValueError, match="'forest'"):
        ensemble_from_dict(d)


# ------------------------------------------------------------------ #
# CatBoost's JSON
# ------------------------------------------------------------------ #

FIXTURE = {
    "oblivious_trees": [
        {"splits": [
            {"split_type": "FloatFeature", "float_feature_index": 0,
             "border": 0.5, "split_index": 1},
            {"split_type": "FloatFeature", "float_feature_index": 2,
             "border": -1.25, "split_index": 4}],
         "leaf_values": [0.1, 0.2, 0.3, 0.4], "leaf_weights": [1, 1, 1, 1]},
        {"splits": [
            {"split_type": "FloatFeature", "float_feature_index": 1,
             "border": 7.0, "split_index": 3}],
         "leaf_values": [-1.0, 1.0]}],
    "features_info": {"float_features": [
        {"feature_index": 0, "flat_feature_index": 0,
         "borders": [0.25, 0.5, 0.75], "has_nans": False,
         "nan_value_treatment": "AsIs"},
        {"feature_index": 1, "flat_feature_index": 1, "borders": [7.0],
         "has_nans": False, "nan_value_treatment": "AsIs"},
        {"feature_index": 2, "flat_feature_index": 2,
         "borders": [-1.25, 3.5], "has_nans": True,
         "nan_value_treatment": "AsIs"}]},
    "scale_and_bias": [2.0, [0.5]],
}


def test_a_hand_written_json_with_unequal_border_lists():
    ens = catboost_io.from_catboost_json(json.dumps(FIXTURE))
    assert ens.depth == 2 and ens.n_trees == 2 and ens.n_features == 3
    assert ens.n_bins == 4 and ens.scale == 2.0 and ens.bias == 0.5
    assert ens.split_bin.tolist() == [[1, 0], [0, 255]]
    assert ens.split_feature.tolist() == [[0, 2], [1, 0]]
    assert ens.n_splits == 3
    assert np.isinf(ens.borders[1, 1:]).all() and np.isinf(ens.borders[2, 2])
    X = np.array([[0.5, 7.0, -1.25], [0.51, 7.5, -1.0], [0.0, 0.0, 9.0],
                  [0.75, 8.0, -2.0]], np.float32)
    # x > border, the first split the low bit
    want = 0.5 + 2.0 * (np.array([0.1, 0.4, 0.3, 0.2])
                        + np.array([-1.0, 1.0, -1.0, 1.0]))
    np.testing.assert_allclose(ens.predict_raw(X), want, rtol=1e-6)
    mapper = ens.bin_mapper()
    Xb = mapper.transform(X)
    assert Xb.dtype == np.uint8 and Xb.max() <= 3
    np.testing.assert_allclose(ens.predict_raw(Xb, binned=True), want,
                               rtol=1e-6)
    for impl in ("pallas", "onehot"):
        got = api.predict(ens, X, mapper=mapper, raw=True, cfg=cfg(impl))
        np.testing.assert_allclose(got, want, rtol=1e-6)
    # the older spelling of the bias, and a given bin count
    older = dict(FIXTURE, scale_and_bias=[2.0, 0.5])
    assert catboost_io.from_catboost_json(older, n_bins=255).n_bins == 255


def test_border_ranks_are_exact_at_the_borders():
    """bin(x) > k  <=>  x > border_k for x at, just below and just above
    every border: binned scoring is raw scoring, to the bit."""
    ens = with_borders(oblivious(95, 20, 4, 5, n_bins=9), 95)
    edges = ens.borders
    X = np.concatenate([edges.T, np.nextafter(edges.T, np.float32(-9)),
                        np.nextafter(edges.T, np.float32(9))])
    Xb = ens.bin_mapper().transform(X)
    assert np.array_equal(ens.predict_raw(X), ens.predict_raw(Xb,
                                                              binned=True))


def test_catboost_json_round_trip():
    ens = catboost_io.from_catboost_json(FIXTURE)
    back = catboost_io.from_catboost_json(catboost_io.to_catboost_json(ens))
    assert back.cache_token() == ens.cache_token()
    np.testing.assert_array_equal(back.borders, ens.borders)
    np.testing.assert_array_equal(back.split_raw, ens.split_raw)
    X = np.random.default_rng(96).standard_normal((200, 3)).astype(
        np.float32) * 4
    assert np.array_equal(back.predict_raw(X), ens.predict_raw(X))
    with pytest.raises(ValueError, match="border lists"):
        catboost_io.to_catboost_json(oblivious(96, 2, 2, 3))


def _with(path, value):
    """FIXTURE with `value` at `path` (a tuple of keys and indices)."""
    m = json.loads(json.dumps(FIXTURE))
    at = m
    for k in path[:-1]:
        at = at[k]
    at[path[-1]] = value
    return m


REFUSALS = {
    "ctr-features": (_with(("features_info", "ctrs"), [{"identifier": "x"}]),
                     "hash table at scoring time"),
    "categorical-features": (
        _with(("features_info", "categorical_features"),
              [{"feature_index": 0, "flat_feature_index": 3}]),
        "categorical features"),
    "ctr-split": (_with(("oblivious_trees", 0, "splits", 0, "split_type"),
                        "OnlineCtr"), "split of type 'OnlineCtr'"),
    "one-hot-split": (_with(("oblivious_trees", 0, "splits", 0,
                             "split_type"), "OneHotFeature"),
                      "split of type 'OneHotFeature'"),
    "text-features": (_with(("features_info", "text_features"),
                            [{"feature_index": 0}]), "text features"),
    "embedding-features": (_with(("features_info", "embedding_features"),
                                 [{"feature_index": 0}]),
                           "embedding features"),
    "vector-leaves": (_with(("oblivious_trees", 1, "leaf_values"),
                            [0.0] * 6), "vector leaves"),
    "vector-bias": (_with(("scale_and_bias",), [1.0, [0.0, 0.0, 0.0]]),
                    "vector leaves"),
    "nan-side": (_with(("features_info", "float_features", 2,
                        "nan_value_treatment"), "AsFalse"),
                 "NaN side for float feature 2"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_the_import_refuses_by_name(case):
    model, said = REFUSALS[case]
    with pytest.raises(ValueError, match=said) as e:
        catboost_io.from_catboost_json(model)
    assert "do not support yet" in str(e.value)


def test_the_import_refuses_non_symmetric_trees_and_bad_borders():
    lossguide = {k: v for k, v in FIXTURE.items() if k != "oblivious_trees"}
    lossguide["trees"] = [{"left": {}, "right": {}}]
    with pytest.raises(ValueError, match="non-symmetric trees"):
        catboost_io.from_catboost_json(lossguide)
    with pytest.raises(ValueError, match="none of its borders"):
        catboost_io.from_catboost_json(_with(
            ("oblivious_trees", 0, "splits", 0, "border"), 0.6))
    wide = _with(("features_info", "float_features", 1, "borders"),
                 [float(i) for i in range(300)])
    with pytest.raises(ValueError, match="border_count > 255"):
        catboost_io.from_catboost_json(wide)
    with pytest.raises(ValueError, match="not ascending"):
        catboost_io.from_catboost_json(_with(
            ("features_info", "float_features", 0, "borders"),
            [0.5, 0.25, 0.75]))
