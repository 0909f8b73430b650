"""VECTOR LEAVES in the oblivious layout (CatBoost's `MultiClass`: C values a
leaf, one index a (row, tree), the softmax on the device), held to the plain
bit walk of `ddt_tpu/reference/numpy_predict.py` on seeded random models
(CPU, small sizes, the Pallas kernel interpreted), to the model's `to_heap`
expansion scored by the heap reference, and to the library's JSON as
`models/catboost_io.py` takes it."""

from __future__ import annotations

import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddt_tpu import api
from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.models import catboost_io
from ddt_tpu.models.tree import (ObliviousEnsemble, TreeEnsemble,
                                 ensemble_from_dict, random_oblivious)
from ddt_tpu.ops import predict as predict_ops
from ddt_tpu.ops import predict_oblivious
from ddt_tpu.reference import numpy_predict
from ddt_tpu.telemetry import annotations as an
from ddt_tpu.utils import device


def vector_model(seed, n_trees, depth, n_features, n_classes, dyadic=False,
                 **meta):
    rng = np.random.default_rng(seed)
    meta = {"scale": 0.5, "bias": rng.integers(-8, 9, n_classes) / 8.0,
            **meta}
    return random_oblivious(rng, n_trees, depth, n_features, dyadic=dyadic,
                            n_classes=n_classes, **meta)


def rows(seed, n, n_features, n_bins=255):
    return np.random.default_rng(seed).integers(
        0, n_bins, (n, n_features)).astype(np.uint8)


def cfg(impl):
    return TrainConfig(backend="tpu", predict_impl=impl)


def lookup_count(depth, n_classes):
    """[VALU operations, of them gathers] the leaf lookup costs a (row,
    tree) by the module's rule: vector leaves of depth 3 or more take
    2^(D-3) sublane gathers and the 2^(D-3) - 1 selects among their results
    a class (PR 58), every other shape the multiplexer's 2^D - 1 selects."""
    if n_classes > 1 and depth >= 3:
        vregs = 1 << (depth - 3)
        return [n_classes * (2 * vregs - 1), n_classes * vregs]
    return [n_classes * ((1 << depth) - 1), 0]


def bfloat16(values):
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000).view(
        np.float32)


# ------------------------------------------------------------------ #
# api.predict against the plain reference
# ------------------------------------------------------------------ #

# (depth, classes, features, trees, rows): one, two and three groups; one
# and two K-blocks; a ragged last row tile (2049, 1100) and a batch of fewer
# rows than a sub-tile; a resolve unrolled beside a select (C (2^D - 1) <=
# 255) and the rolled step; depth 9 at 3 classes is past the dispatch rule
# (1,533 selects: `pallas` demands the kernel all the same, interpreted).
GRID = [(1, 3, 5, 1, 300), (3, 7, 54, 130, 2049), (6, 7, 54, 130, 1100),
        (6, 3, 200, 300, 1100), (3, 3, 200, 1, 1025), (9, 3, 5, 130, 300),
        (6, 7, 5, 300, 300), (1, 7, 54, 300, 2049)]


@pytest.mark.parametrize("impl", ["pallas", "onehot"])
@pytest.mark.parametrize("depth,n_classes,n_features,n_trees,n_rows", GRID,
                         ids=["d%d-c%d-f%d-t%d-r%d" % c for c in GRID])
def test_api_predict_margins_and_probabilities(depth, n_classes, n_features,
                                               n_trees, n_rows, impl):
    """Margins within float32 rounding of the float64 walk, tightly enough
    that bfloat16 leaf values fail; probabilities by the device's own link
    (`predict:link`), rows that sum to 1."""
    ens = vector_model(100 + depth, n_trees, depth, n_features, n_classes)
    Xb = rows(101 + n_rows, n_rows, n_features)
    want = numpy_predict.predict_raw_oblivious(ens, Xb, np.float64)
    assert want.shape == (n_rows, n_classes)
    got = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg(impl))
    assert got.dtype == np.float32 and got.shape == (n_rows, n_classes)
    tol = 2e-6 * max(1.0, np.sqrt(n_trees))
    assert np.abs(got - want).max() <= tol
    rounded = ObliviousEnsemble(
        split_feature=ens.split_feature, split_bin=ens.split_bin,
        leaf_value=bfloat16(ens.leaf_value), n_features=n_features,
        scale=ens.scale, bias=ens.bias, loss="softmax")
    control = numpy_predict.predict_raw_oblivious(rounded, Xb, np.float64)
    assert np.abs(control - want).max() > 20 * tol
    proba = api.predict(ens, Xb, binned=True, cfg=cfg(impl))
    assert proba.dtype == np.float32 and proba.shape == (n_rows, n_classes)
    assert np.abs(proba - numpy_predict.softmax(want)).max() <= tol
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-6)
    root = an.root_spans("predict")[-1]["counts"]
    assert root["classes"] == n_classes and root["oblivious"] == 1
    assert root["leaf_columns"] == n_classes and root["link"] == "softmax"
    assert [root["resolve_selects_per_tree"],
            root["resolve_gathers_per_tree"]] == lookup_count(depth,
                                                              n_classes)


def test_the_host_walk_and_the_cpu_backend_answer_the_same():
    ens = vector_model(110, 40, 4, 9, 5)
    Xb = rows(111, 500, 9)
    want = numpy_predict.predict_raw_oblivious(ens, Xb, np.float64)
    np.testing.assert_allclose(ens.predict_raw(Xb, binned=True), want,
                               atol=1e-5)
    np.testing.assert_allclose(ens.predict(Xb, binned=True),
                               numpy_predict.softmax(want), atol=1e-6)
    cpu = TrainConfig(backend="cpu")
    np.testing.assert_allclose(
        api.predict(ens, Xb, binned=True, raw=True, cfg=cpu), want,
        atol=1e-5)
    np.testing.assert_allclose(api.predict(ens, Xb, binned=True, cfg=cpu),
                               numpy_predict.softmax(want), atol=1e-6)
    assert not get_backend(cpu).links_on_device(ens)
    tpu = get_backend(cfg("onehot"))
    assert tpu.links_on_device(ens)
    assert not tpu.links_on_device(random_oblivious(
        np.random.default_rng(0), 3, 2, 4))


def test_the_chunk_loop_places_rows_by_class_columns():
    """Ragged rows past one chunk: each chunk's [rows, C] lands in its rows
    of the one result, the link taken a chunk at a time."""
    ens = vector_model(120, 200, 6, 54, 7)
    Xb = rows(121, 1111, 54)
    want = numpy_predict.softmax(
        numpy_predict.predict_raw_oblivious(ens, Xb, np.float64))
    be = get_backend(cfg("pallas"))
    type(be).PREDICT_ROW_CHUNK, old = 256, type(be).PREDICT_ROW_CHUNK
    try:
        got = api.predict(ens, Xb, binned=True, backend=be)
    finally:
        type(be).PREDICT_ROW_CHUNK = old
    assert got.shape == (1111, 7) and np.abs(got - want).max() <= 1e-5
    root = an.root_spans("predict")[-1]["counts"]
    assert root["branch"] == "chunks" and root["chunks"] == 5
    assert root["classes"] == 7 and root["tables_streamed_bytes"] > 0


# ------------------------------------------------------------------ #
# the kernel (interpreted) against the jax.numpy twin, to the bit
# ------------------------------------------------------------------ #

# (rows, trees, depth, classes, features): a step of one sub-tile, of two
# with a ragged tile after; one group and three; pipelined (depth 5 x 7: 217
# selects; depth 1) and rolled (depth 6 x 7: 441); two K-blocks. The lookup
# by the rule's both sides (PR 58): the multiplexer (depth 1 and 2) and the
# sublane gather from its shortest (depth 3: one vreg a class, one gather
# and no select; pipelined) through depth 4 (two vregs, one select) to the
# deepest tree the kernel takes at 7 classes (depth 7: 16 vregs a class).
KERNEL = [(300, 5, 3, 3, 5), (2049, 300, 5, 7, 54), (1100, 130, 6, 7, 54),
          (1025, 130, 1, 3, 200), (2048, 130, 6, 3, 28),
          (1100, 130, 4, 3, 54), (1030, 130, 7, 7, 54), (300, 5, 2, 7, 5)]


@pytest.mark.parametrize("n_rows,n_trees,depth,n_classes,n_features", KERNEL,
                         ids=["r%d-t%d-d%d-c%d-f%d" % c for c in KERNEL])
def test_kernel_and_twin_agree_to_the_bit_on_dyadic_leaves(
        n_rows, n_trees, depth, n_classes, n_features):
    """Dyadic leaf values, scale and bias: the sums round nowhere, so the
    kernel, its twin and the walk are bit-equal, class column by class
    column; a second call gives the first one's bits (the pipeline's
    scratch)."""
    ens = vector_model(200 + depth, n_trees, depth, n_features, n_classes,
                       dyadic=True)
    Xb = rows(201 + n_rows, n_rows, n_features)
    want = numpy_predict.predict_raw_oblivious(ens, Xb, np.float64)
    ce = ens.compile()
    L = 1 << depth
    assert ce.n_classes_out == n_classes and ce.bias == tuple(ens.bias)
    assert ce.leaf.shape == (-(-n_trees // 128), n_classes * L, 128)
    # class c's leaf rows: column c of the leaves, a tree a lane
    for c in (0, n_classes - 1):
        lanes = min(128, n_trees)
        assert np.array_equal(ce.leaf[0, c * L:(c + 1) * L, :lanes],
                              ens.leaf_value[:128, :, c].T)
    tables = [jnp.asarray(a) for a in ce.arrays()]
    score = lambda: np.asarray(predict_oblivious.predict_oblivious_pallas(
        *tables, jnp.asarray(Xb), scale=ce.scale, bias=ce.bias))
    first = score()
    assert first.shape == (n_rows, n_classes)
    assert np.array_equal(first, want)
    assert np.array_equal(score(), first)
    twin = predict_ops._predict_oblivious(
        *tables, jnp.asarray(Xb), scale=ce.scale, bias=ce.bias)
    assert np.array_equal(np.asarray(twin), want)
    assert predict_oblivious._pipelined(depth, 2, n_classes) == (
        n_classes * (L - 1) <= 255)
    assert predict_oblivious._gathered(depth, n_classes) == (depth >= 3)


@pytest.mark.parametrize("n_classes", [2, 3, 7])
@pytest.mark.parametrize("depth", [3, 4, 6, 7])
def test_the_gathered_lookup_is_the_multiplexers(depth, n_classes):
    """The lookup by sublane gathers (`_gathered_leaves`) against the
    multiplexer of all D bits (`_mux`), in the Pallas interpreter, on an
    index plane that holds every leaf 0 .. 2^D - 1 in every sublane
    position of a strip, the lanes out of step with one another: the same
    float32 value for every (row, tree) and class, to the bit."""
    from jax.experimental import pallas as pl

    po = predict_oblivious
    L = 1 << depth
    n_rows = 8 * L
    r, lane = np.arange(n_rows)[:, None], np.arange(128)[None, :]
    idx = ((r // 8 + 5 * (r % 8) + 3 * lane) % L).astype(np.int32)
    for s in range(8):      # sublane position s of the strips: every leaf
        assert len(set(idx[s::8, 77])) == L
    leaf = np.random.default_rng(depth * 10 + n_classes).standard_normal(
        (n_classes * L, 128)).astype(np.float32)

    def kernel(idx_ref, leaf_ref, gathered_ref, muxed_ref):
        got = idx_ref[:]
        for c, plane in enumerate(po._gathered_leaves(got, leaf_ref, depth)):
            gathered_ref[c] = plane
        bits = [(got & (1 << d)) != 0 for d in range(depth)]
        leaves = po._leaves(leaf_ref, n_rows)
        for c in range(n_classes):
            muxed_ref[c] = po._mux(bits, leaves, depth - 1, c * L)

    planes = jax.ShapeDtypeStruct((n_classes, n_rows, 128), jnp.float32)
    gathered, muxed = pl.pallas_call(
        kernel, out_shape=(planes, planes), interpret=True)(
            jnp.asarray(idx), jnp.asarray(leaf))
    want = leaf.reshape(n_classes, L, 128)[:, idx, lane]
    assert np.array_equal(np.asarray(muxed), want)
    assert np.array_equal(np.asarray(gathered), want)
    assert po.resolve_gathers(depth, n_classes) == n_classes * L // 8
    assert [po.resolve_selects(depth, n_classes),
            po.resolve_gathers(depth, n_classes)] == lookup_count(
                depth, n_classes)


def test_the_entry_takes_the_link_and_refuses_what_it_cannot():
    ens = vector_model(210, 5, 3, 4, 3)
    ce = ens.compile()
    tables = [jnp.asarray(a) for a in ce.arrays()]
    entry = predict_ops.predict_raw_effective_oblivious
    Xb = jnp.asarray(rows(211, 50, 4))
    margins = entry(*tables, Xb, scale=ce.scale, bias=ce.bias)
    proba = entry(*tables, Xb, scale=ce.scale, bias=ce.bias, link="softmax")
    np.testing.assert_allclose(
        np.asarray(proba), numpy_predict.softmax(np.asarray(margins)),
        atol=1e-6)
    empty = entry(*tables, jnp.zeros((0, 4), jnp.uint8), scale=ce.scale,
                  bias=ce.bias, link="softmax")
    assert empty.shape == (0, 3)
    with pytest.raises(ValueError, match="link 'sigmoid'"):
        entry(*tables, Xb, scale=ce.scale, bias=ce.bias, link="sigmoid")
    with pytest.raises(ValueError, match="3 leaf column"):
        entry(*tables, Xb, scale=ce.scale, bias=(0.0, 0.0))
    one = random_oblivious(np.random.default_rng(3), 5, 3, 4).compile()
    with pytest.raises(ValueError, match="link 'softmax'"):
        entry(*[jnp.asarray(a) for a in one.arrays()], Xb, scale=1.0,
              bias=0.0, link="softmax")
    scalar = random_oblivious(np.random.default_rng(3), 5, 3, 4)
    with pytest.raises(ValueError, match="links_on_device"):
        get_backend(cfg("onehot")).predict_raw(scalar, np.asarray(Xb),
                                               link=True)


# ------------------------------------------------------------------ #
# the dispatch rule, the plan and the spans
# ------------------------------------------------------------------ #

def test_the_rule_is_told_the_leaf_columns():
    fits = predict_oblivious.predict_oblivious_fits
    assert fits(6, 54, n_cls=7) and fits(7, 54, n_cls=7)
    assert not fits(8, 54, n_cls=7)             # 1,785 selects: the trace
    assert fits(10, 28) and not fits(10, 28, n_cls=2)
    assert fits(6, 2000) and fits(6, 2000, n_cls=7)
    # the leaf table's windows and the output's grow with C: VMEM
    assert fits(1, 3584) and not fits(1, 3584, n_cls=512)
    vmem = predict_oblivious._vmem_bytes
    # depth 9, both steps rolled: two more classes' leaf windows, and a
    # step of vector leaves resolves in blocks of 128 rows: three planes
    # of a sub-tile fewer, one plane of indices in scratch
    plane = 1024 * 128 * 4
    assert vmem(9, 54, 1, 3) - vmem(9, 54, 1, 1) == (
        2 * 2 * 512 * 128 * 4 - 3 * plane + plane)
    with device.assume_platform("tpu"):     # the layout's own rule
        assert predict_oblivious.kernel_serves(None, 6, 54, n_cls=7)
        assert not predict_oblivious.kernel_serves(None, 8, 54, n_cls=7)
    # where the rule says no, the twin serves, and the span says so
    deep = vector_model(300, 3, 8, 6, 7)
    with device.assume_platform("tpu"):
        get_backend(TrainConfig(backend="tpu"))._predict_fn(deep)
    built = [sp for sp in an.recent_spans()
             if sp["name"] == "ddt:predict:ensemble"][-1]["counts"]
    assert built["trees_per_step"] == 0 and built["table_blocks"] == 0
    assert built["leaf_columns"] == 7
    # (the lookup's count by its rule, had the kernel served: 32 gathers
    # and 31 selects a class)
    assert built["resolve_selects_per_tree"] == 7 * 63
    assert built["resolve_gathers_per_tree"] == 7 * 32


@pytest.mark.parametrize("n_classes", [1, 2, 3, 7])
@pytest.mark.parametrize("depth", range(1, 11))
def test_the_rules_answer_as_they_did_for_the_multiplexer(depth, n_classes):
    """`_pipelined` and `predict_oblivious_fits` are rules about the
    multiplexer's trace, C (2^D - 1) selects, and the gathered lookup (PR
    58) moved neither: what the parent answered at every shape (read
    there: the formula at 54 columns, where VMEM binds nowhere; at 2000
    columns depth 7 fits and depth 8 does not, one column or seven)."""
    po = predict_oblivious
    muxed = n_classes * ((1 << depth) - 1)
    assert po._pipelined(depth, 2, n_classes) == (muxed <= 255)
    assert not po._pipelined(depth, 1, n_classes)
    assert po.predict_oblivious_fits(depth, 54, n_cls=n_classes) == (
        muxed <= 1023)
    assert po.predict_oblivious_fits(depth, 2000, n_cls=n_classes) == (
        muxed <= 1023 and depth <= 7)
    # the count the span gives is the lookup's own, by the other rule
    plan = po.oblivious_plan(300, depth, 54, n_cls=n_classes)
    assert [plan.resolve_selects_per_tree,
            plan.resolve_gathers_per_tree] == lookup_count(depth, n_classes)
    assert (plan.resolves_under_select > 0) == (muxed <= 255)


def test_the_plan_at_the_covertype_models_shape():
    plan = predict_oblivious.oblivious_plan(1000, 6, 54, n_cls=7,
                                            link="softmax")
    assert plan.span_counts() == {
        "oblivious": 1, "depth": 6, "select_columns_per_tree": 6,
        "trees_per_lane_tile": 21.33, "select_k_blocks": 1,
        "oblivious_mxu_tiles_per_tree": 0.0469, "trees_per_step": 128,
        "table_blocks": 8,
        "table_bytes": 8 * (6 * 64 * 128 * 2 + 8 * 128 * 4
                            + 7 * 64 * 128 * 4),
        "row_operand_bytes": 1, "leaf_columns": 7, "link": "softmax",
        # 8 gathers and 7 selects a class where the multiplexer took 63
        "resolve_selects_per_tree": 105, "resolve_gathers_per_tree": 56,
        "resolves_under_select": 0.0}
    root = plan.root_counts()
    assert [root["resolve_selects_per_tree"],
            root["resolve_gathers_per_tree"]] == [105, 56]
    # one column at that shape, and vector leaves under a vreg's 8 leaves:
    # the multiplexer
    for depth, n_cls, said in ((6, 1, [63, 0]), (2, 7, [21, 0])):
        counts = predict_oblivious.oblivious_plan(
            1000, depth, 54, n_cls=n_cls).root_counts()
        assert [counts["resolve_selects_per_tree"],
                counts["resolve_gathers_per_tree"]] == said
    # seven classes at depth 5 are unrolled beside a select: 217 selects
    assert predict_oblivious.oblivious_plan(
        1000, 5, 54, n_cls=7).resolves_under_select == round(15 / 16, 4)
    idx, carry = predict_oblivious._scratch_shapes(5, 2, 7)
    assert idx.shape == (2, 1024, 128) and carry.shape == (7 * 32, 128)
    # a multiplexer of 441 selects: the rolled step, ONE plane of indices
    # for its blocks
    idx, = predict_oblivious._scratch_shapes(6, 2, 7)
    assert idx.shape == (1, 1024, 128)
    assert predict_oblivious._scratch_shapes(9, 2, 1) == []


def test_the_span_and_the_stage_map_say_the_link():
    ens = vector_model(310, 200, 6, 54, 7)
    be = get_backend(cfg("pallas"))
    for link, said in ((False, "none"), (True, "softmax")):
        be._predict_entry(ens, link=link)
        built = [sp for sp in an.recent_spans()
                 if sp["name"] == "ddt:predict:ensemble"][-1]["counts"]
        assert built["oblivious"] == 1 and built["leaf_columns"] == 7
        assert built["link"] == said
        assert built["resolve_selects_per_tree"] == 105
        assert built["resolve_gathers_per_tree"] == 56
        assert built["select_columns_per_tree"] == 6
        assert built["oblivious_mxu_tiles_per_tree"] == 0.0469
        assert list(built)[-len(predict_oblivious.SPAN_COUNTS):] == list(
            predict_oblivious.SPAN_COUNTS)
    # the stage map of the program that ends in the link (the model's
    # last registered program)
    api.predict(ens, rows(311, 300, 54), binned=True, backend=be)
    held = an.device_stages()["jit_predict_raw_effective_oblivious"]
    named = {v["stage"] for v in held.values()}
    assert {"predict:link", "predict:accumulate"} <= named


# ------------------------------------------------------------------ #
# the heap expansion: a second, independent scorer
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("depth,n_classes", [(1, 3), (4, 7), (6, 3)])
def test_the_heap_expansion_scored_by_the_heap_reference(depth, n_classes):
    """A tree of vector leaves is C round-major heap trees (the same
    splits, column c of the leaves) and the bias vector one more round of
    root leaves; the heap walk and the node list's give the margins."""
    ens = vector_model(400 + depth, 9, depth, 12, n_classes, dyadic=True,
                       scale=1.0)
    Xb = rows(401, 257, 12)
    want = numpy_predict.predict_raw_oblivious(ens, Xb, np.float64)
    heap = ens.to_heap()
    assert isinstance(heap, TreeEnsemble) and heap.loss == "softmax"
    assert heap.n_trees == (9 + 1) * n_classes
    assert heap.n_classes == n_classes and heap.max_depth == depth
    assert np.array_equal(numpy_predict.predict_raw(heap, Xb, np.float64),
                          want)
    # heap tree t C + c holds class c's column of tree t
    t, c = 4, n_classes - 1
    bottom = heap.leaf_value[t * n_classes + c, (1 << depth) - 1:]
    assert np.array_equal(bottom, ens.leaf_value[t, :, c])
    nodes = ens.to_node_list()
    got = api.predict(nodes, Xb, binned=True, raw=True, cfg=cfg("onehot"))
    np.testing.assert_allclose(got, want, atol=1e-5)
    got = api.predict(heap, Xb, binned=True, raw=True, cfg=cfg("onehot"))
    np.testing.assert_allclose(got, want, atol=1e-5)


# ------------------------------------------------------------------ #
# the layout: constructor, save, load, cache_token, cli
# ------------------------------------------------------------------ #

def test_constructor_takes_vector_leaves_and_names_what_it_wants():
    ok = dict(split_feature=np.zeros((2, 3), np.int32),
              split_bin=np.zeros((2, 3), np.int32),
              leaf_value=np.zeros((2, 8, 4), np.float32), n_features=4,
              loss="softmax")
    ens = ObliviousEnsemble(**ok)
    assert ens.n_classes == 4 and ens.leaf_columns == 4
    assert ens.bias.shape == (4,) and ens.base_score == [0.0] * 4
    assert ObliviousEnsemble(**ok, bias=0.5).bias.tolist() == [0.5] * 4
    assert ObliviousEnsemble(**ok, n_classes=4).n_classes == 4
    scalar = ObliviousEnsemble(**{**ok, "loss": "logloss",
                                  "leaf_value": np.zeros((2, 8))})
    assert scalar.n_classes == 2 and scalar.leaf_columns == 1
    assert scalar.bias == 0.0
    with pytest.raises(ValueError, match=r"vector leaves \[2, 8, C\]"):
        ObliviousEnsemble(**{**ok, "leaf_value": np.zeros((2, 8))})
    with pytest.raises(ValueError, match=r"vector leaves \[2, 8, C\]"):
        ObliviousEnsemble(**{**ok, "loss": "logloss"})
    with pytest.raises(ValueError, match="C >= 2"):
        ObliviousEnsemble(**{**ok, "leaf_value": np.zeros((2, 8, 1))})
    with pytest.raises(ValueError, match="n_classes 5"):
        ObliviousEnsemble(**ok, n_classes=5)
    with pytest.raises(ValueError, match=r"of 4 classes, \[4\]"):
        ObliviousEnsemble(**ok, bias=np.zeros(3))
    with pytest.raises(ValueError, match="bias is a scalar, got"):
        ObliviousEnsemble(**{**ok, "loss": "mse",
                             "leaf_value": np.zeros((2, 8))},
                          bias=np.zeros(2))


def test_save_load_cache_token_and_cli(tmp_path, capsys):
    from ddt_tpu.cli import main

    ens = vector_model(500, 33, 5, 9, 4)
    path = str(tmp_path / "m.npz")
    ens.save(path)
    back = TreeEnsemble.load(path)
    assert isinstance(back, ObliviousEnsemble) and back.loss == "softmax"
    assert back.n_classes == 4 and back.leaf_value.shape == (33, 32, 4)
    assert np.array_equal(back.bias, ens.bias)
    assert back.cache_token() == ens.cache_token()
    assert ensemble_from_dict(ens.to_dict()).cache_token() == \
        ens.cache_token()
    other = vector_model(500, 33, 5, 9, 4)
    other.bias[2] += 1.0
    assert other.cache_token() != ens.cache_token()

    # the CLI scores raw rows through the artifact's mapper
    rng = np.random.default_rng(501)
    ens = vector_model(502, 33, 5, 9, 4, n_bins=17)
    ens.borders = np.sort(rng.standard_normal((9, 16)).astype(np.float32),
                          axis=1)
    ens.split_raw = ens.borders[ens.split_feature, ens.split_bin]
    api.save_model(path, ens, mapper=ens.bin_mapper())
    X = rng.standard_normal((300, 9)).astype(np.float32)
    data = str(tmp_path / "rows.npz")
    np.savez(data, X=X, y=np.zeros(len(X), np.float32))
    out = str(tmp_path / "scores.npy")
    assert main(["predict", "--backend=tpu", f"--model={path}",
                 f"--data={data}", f"--out={out}"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    phases = rec["phases_ms"]
    assert phases["oblivious"] == 1 and phases["leaf_columns"] == 4
    assert phases["link"] == "softmax"
    # depth 5: 4 gathers and 3 selects a class
    assert phases["resolve_selects_per_tree"] == 4 * 7
    assert phases["resolve_gathers_per_tree"] == 4 * 4
    want = numpy_predict.softmax(ens.predict_raw(X).astype(np.float64))
    got = np.load(out)
    assert got.shape == (300, 4)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert main(["inspect", f"--model={path}", "--tree=0"]) == 0
    said = capsys.readouterr().out
    assert '"n_classes": 4' in said and '"loss": "softmax"' in said
    assert "leaf 31: " in said and "bit 0: f" in said


# ------------------------------------------------------------------ #
# CatBoost's JSON: MultiClass
# ------------------------------------------------------------------ #

# Two trees over three float features, THREE classes: `leaf_values` is
# leaf-major, the class innermost (leaf i's value for class c at i * 3 + c);
# `leaf_weights` stays 2^splits long. The format as `catboost_io` takes it.
FIXTURE = {
    "oblivious_trees": [
        {"splits": [
            {"split_type": "FloatFeature", "float_feature_index": 0,
             "border": 0.5, "split_index": 1},
            {"split_type": "FloatFeature", "float_feature_index": 2,
             "border": -1.25, "split_index": 4}],
         "leaf_values": [0.1, 0.2, 0.3,      # leaf 0: classes 0, 1, 2
                         1.1, 1.2, 1.3,      # leaf 1 (bit 0 set)
                         2.1, 2.2, 2.3,      # leaf 2 (bit 1 set)
                         3.1, 3.2, 3.3],     # leaf 3
         "leaf_weights": [1, 1, 1, 1]},
        {"splits": [
            {"split_type": "FloatFeature", "float_feature_index": 1,
             "border": 7.0, "split_index": 3}],
         "leaf_values": [-1.0, 0.0, 1.0, 10.0, 20.0, 30.0],
         "leaf_weights": [2, 2]}],
    "features_info": {"float_features": [
        {"feature_index": 0, "flat_feature_index": 0,
         "borders": [0.25, 0.5, 0.75], "has_nans": False,
         "nan_value_treatment": "AsIs"},
        {"feature_index": 1, "flat_feature_index": 1, "borders": [7.0],
         "has_nans": False, "nan_value_treatment": "AsIs"},
        {"feature_index": 2, "flat_feature_index": 2,
         "borders": [-1.25, 3.5], "has_nans": False,
         "nan_value_treatment": "AsIs"}]},
    "scale_and_bias": [2.0, [0.5, -0.5, 0.25]],
    "model_info": {"params": {"loss_function": {"type": "MultiClass"}}},
}


def test_a_hand_written_multiclass_json():
    ens = catboost_io.from_catboost_json(json.dumps(FIXTURE))
    assert ens.loss == "softmax" and ens.n_classes == 3
    assert ens.depth == 2 and ens.n_trees == 2 and ens.n_features == 3
    assert ens.leaf_value.shape == (2, 4, 3)
    assert ens.leaf_value[0, 2].tolist() == pytest.approx([2.1, 2.2, 2.3])
    assert ens.leaf_value[1, 1].tolist() == [10.0, 20.0, 30.0]
    assert (ens.leaf_value[1, 2:] == 0).all()       # the shallow tree's fill
    assert ens.bias.tolist() == [0.5, -0.5, 0.25] and ens.scale == 2.0
    X = np.array([[0.5, 7.0, -1.25], [0.51, 7.5, -1.0], [0.0, 0.0, 9.0],
                  [0.75, 8.0, -2.0]], np.float32)
    # leaves reached: tree 0: 0, 3, 2, 1; tree 1: 0, 1, 0, 1
    want = np.array([0.5, -0.5, 0.25]) + 2.0 * (
        np.array([[0.1, 0.2, 0.3], [3.1, 3.2, 3.3], [2.1, 2.2, 2.3],
                  [1.1, 1.2, 1.3]])
        + np.array([[-1.0, 0.0, 1.0], [10.0, 20.0, 30.0]])[[0, 1, 0, 1]])
    np.testing.assert_allclose(ens.predict_raw(X), want, rtol=1e-6)
    mapper = ens.bin_mapper()
    for impl in ("pallas", "onehot"):
        got = api.predict(ens, X, mapper=mapper, raw=True, cfg=cfg(impl))
        np.testing.assert_allclose(got, want, rtol=1e-6)
        proba = api.predict(ens, X, mapper=mapper, cfg=cfg(impl))
        np.testing.assert_allclose(proba, numpy_predict.softmax(want),
                                   atol=1e-6)
    # read class-major, the first leaf's classes would be leaves 0, 1, 2's
    assert ens.leaf_value[0, 0].tolist() != pytest.approx([0.1, 1.1, 2.1])
    # the objective: `model_info` where it is there, the argument first,
    # the width of the leaves where neither says
    bare = {k: v for k, v in FIXTURE.items() if k != "model_info"}
    assert catboost_io.from_catboost_json(bare).loss == "softmax"
    assert catboost_io.from_catboost_json(
        dict(bare, model_info={"params": json.dumps(
            {"loss_function": {"type": "MultiClass"}})})).loss == "softmax"
    with pytest.raises(ValueError, match="vector leaves"):
        catboost_io.from_catboost_json(FIXTURE, loss="logloss")


def test_multiclass_json_round_trip():
    ens = catboost_io.from_catboost_json(FIXTURE)
    text = catboost_io.to_catboost_json(ens)
    written = json.loads(text)
    assert written["model_info"]["params"]["loss_function"]["type"] == \
        "MultiClass"
    assert len(written["oblivious_trees"][0]["leaf_values"]) == 12
    assert len(written["oblivious_trees"][1]["leaf_values"]) == 6
    assert written["scale_and_bias"] == [2.0, [0.5, -0.5, 0.25]]
    back = catboost_io.from_catboost_json(text)
    assert back.cache_token() == ens.cache_token()
    assert back.loss == "softmax"
    np.testing.assert_array_equal(back.leaf_value, ens.leaf_value)
    X = np.random.default_rng(96).standard_normal((200, 3)).astype(
        np.float32) * 4
    assert np.array_equal(back.predict_raw(X), ens.predict_raw(X))
    # a one-column model's round trip keeps its objective too
    one = catboost_io.from_catboost_json(
        {**{k: v for k, v in FIXTURE.items() if k != "model_info"},
         "oblivious_trees": [{"splits": FIXTURE["oblivious_trees"][1][
             "splits"], "leaf_values": [-1.0, 1.0]}],
         "scale_and_bias": [1.0, [0.0]]}, loss="mse")
    assert catboost_io.from_catboost_json(
        catboost_io.to_catboost_json(one)).loss == "mse"


def _with(path, value):
    """FIXTURE with `value` at `path` (a tuple of keys and indices)."""
    m = json.loads(json.dumps(FIXTURE))
    at = m
    for k in path[:-1]:
        at = at[k]
    at[path[-1]] = value
    return m


REFUSALS = {
    "one-vs-all": (_with(("model_info", "params", "loss_function", "type"),
                         "MultiClassOneVsAll"),
                   "the objective MultiClassOneVsAll"),
    "no-multiple-of-the-leaves": (
        _with(("oblivious_trees", 0, "leaf_values"), [0.0] * 10),
        r"no multiple of 2\^splits \(tree 0: 10 values for 2 splits\)"),
    "another-width-than-the-bias": (
        _with(("oblivious_trees", 1, "leaf_values"), [0.0] * 4),
        r"a width that is not the bias's \(tree 1: 4 leaf values for 1 "
        r"splits, 2 a leaf; a bias of 3 values\)"),
    "a-scalar-bias-under-vector-leaves": (
        _with(("scale_and_bias",), [1.0, 0.0]),
        r"a width that is not the bias's \(tree 0: 12 leaf values"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_the_import_refuses_by_name(case):
    model, said = REFUSALS[case]
    with pytest.raises(ValueError, match=said) as e:
        catboost_io.from_catboost_json(model)
    assert "do not support yet" in str(e.value)


# ------------------------------------------------------------------ #
# a model of ONE column is what it was before there were vector leaves
# ------------------------------------------------------------------ #

# SHA-1 (16 hex digits) of the compiled tables' bytes and of the scoring
# program's jaxpr (the kernel's body in it, source lines cut), read on the
# PARENT of PR 57 (commit 6b0774a) under jax 0.9.0 for the model below.
PARENT_TABLES, PARENT_JAXPR = "ba602e00e894e0a9", "440c6e18fb38f39d"


def test_a_one_column_models_tables_and_program_are_the_parents():
    ens = random_oblivious(np.random.default_rng(57), 130, 6, 28, scale=0.5,
                           bias=0.25)
    ce = ens.compile()
    assert ce.n_classes_out == 1 and ce.bias == 0.25
    assert ce.leaf.shape == (2, 64, 128)
    h = hashlib.sha1()
    for a in ce.arrays():
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest()[:16] == PARENT_TABLES
    assert ens.cache_token()[:16] == "70664b7334f42844"
    if jax.__version__ != "0.9.0":
        pytest.skip(f"the jaxpr was pinned under jax 0.9.0, not "
                    f"{jax.__version__}")
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ce.arrays()]
    args.append(jax.ShapeDtypeStruct((3000, 28), jnp.uint8))
    with device.assume_platform("tpu"):
        text = str(jax.make_jaxpr(
            lambda *a: predict_ops.predict_raw_effective_oblivious(
                *a, scale=ce.scale, bias=ce.bias, use_pallas=True))(*args))
    text = re.sub(r"\S+\.py:\d+", "", text)
    assert hashlib.sha1(text.encode()).hexdigest()[:16] == PARENT_JAXPR
