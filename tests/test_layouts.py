"""The scoring-program seam (ops/predict.LAYOUTS): a layout is an ENTRY.

Three things are held here, for every layout at once:

(a) what a layout's entry returns is one record of one protocol
    (ops/predict.ScoringProgram), whatever the layout and its shape;
(b) the kernel-or-twin choice is made ONCE, on the host, by the entry:
    the program it binds asks no `*_fits` when it is traced;
(c) a layout the backend has never heard of scores through
    `TPUDevice.predict_raw`, and its counts reach the root span and the
    CLI's `phases_ms`, with no edit outside its own module: here a FAKE
    fourth layout that lives in this file.
"""

import sys
import time
import types
import typing

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import (empty_ensemble, random_node_list,
                                 random_oblivious)
from ddt_tpu.ops import predict as predict_ops
from ddt_tpu.ops import predict_oblivious, predict_pallas, predict_paths
from ddt_tpu.telemetry import annotations as an
from ddt_tpu.utils import device

F, BINS = 6, 31
# each kernel module's budget predicate: what its layout's rule asks
FITS = ((predict_pallas, "predict_pallas_fits"),
        (predict_paths, "predict_paths_fits"),
        (predict_oblivious, "predict_oblivious_fits"))


def _heap(routed=False, classes=1):
    rng = np.random.default_rng(5900)
    ens = empty_ensemble(
        6 * classes, 3, F, 0.1, 0.0, "softmax" if classes > 1 else "logloss",
        max(classes, 2), missing_bin=routed, n_bins=BINS,
        cat_features=(0, 1) if routed else ())
    n_int = 7
    ens.feature[:, :n_int] = rng.integers(0, F, (ens.n_trees, n_int))
    ens.threshold_bin[:, :n_int] = rng.integers(0, BINS - 2,
                                                (ens.n_trees, n_int))
    ens.is_leaf[:, n_int:] = True
    ens.leaf_value[:, n_int:] = rng.standard_normal(
        (ens.n_trees, n_int + 1)).astype(np.float32)
    if routed:
        ens.default_left[:, :n_int] = rng.random((ens.n_trees, n_int)) < 0.5
    return ens


def _node_list(seed, leaves=20, **kw):
    meta = {} if "leaf_columns" in kw else dict(
        learning_rate=0.5, base_score=0.25, loss="logloss")
    return random_node_list(np.random.default_rng(seed), 3, leaves, F,
                            n_bins=BINS, **{**meta, **kw})


# name -> (the model, the module that owns its layout, link)
MODELS = {
    "heap": (lambda: _heap(), predict_pallas, False),
    "heap-routed-7classes": (lambda: _heap(True, 7), predict_pallas, False),
    "node-list": (lambda: _node_list(5901), predict_paths, False),
    "node-list-nan": (lambda: _node_list(5902, missing=True), predict_paths,
                      False),
    "node-list-chained": (lambda: _node_list(5903, (300, 500),
                                             leaf_columns=3),
                          predict_paths, False),
    "node-list-softmax-link": (lambda: random_node_list(
        np.random.default_rng(5904), 6, (300, 500), F, n_bins=BINS,
        learning_rate=0.5, base_score=0.25, loss="softmax", n_classes=3),
        predict_paths, True),
    "node-list-category-sets": (lambda: _node_list(
        5905, 40, categories=((1, 8), (2, 20))), predict_paths, False),
    "oblivious": (lambda: random_oblivious(
        np.random.default_rng(5906), 5, 3, F, n_bins=BINS, scale=0.5,
        bias=0.25), predict_oblivious, False),
    "oblivious-vector-link": (lambda: random_oblivious(
        np.random.default_rng(5907), 5, 3, F, n_bins=BINS, scale=0.5,
        bias=0.25, n_classes=3), predict_oblivious, True),
}


def _program(name, impl="auto"):
    build, owner, link = MODELS[name]
    ens = build()
    ce = ens.compile()
    entry = predict_ops.layout_entry(ce.layout)
    assert entry is owner.scoring_program
    return ens, ce, owner, entry(ce, F, np.dtype(np.uint8), impl, link)


def _avals(prog, rows):
    tables = [jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in prog.fill(prog.tables)]
    return tables + [jax.ShapeDtypeStruct((rows, F), jnp.uint8)]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_layouts_entry_returns_the_one_record(name):
    ens, ce, owner, prog = _program(name)
    assert ens.layout == ce.layout in predict_ops.LAYOUTS
    assert isinstance(prog, predict_ops.ScoringProgram)
    assert prog.tier == "f32"
    # the plan's protocol: what the chunk loop, the cache and the spans ask
    plan = prog.plan
    assert plan.blocks == 0          # a CPU: the jax.numpy form scores
    assert plan.step_rows(4096) == plan.step_rows(4096)
    assert plan.table_bytes == 0
    assert list(plan.span_counts()) == list(owner.SPAN_COUNTS)
    assert set(owner.PHASES_COUNTS) == set(owner.SPAN_COUNTS) - {
        "table_bytes"}
    assert "routing_tables" in plan.root_counts()
    assert set(owner.PHASES_COUNTS) <= set(predict_ops.phases_counts())
    # the entry is the module's jitted one, by the name the stage map and
    # the benchmark's readers know it
    assert prog.entry.__name__.startswith("predict_raw_effective")
    assert prog.entry is getattr(predict_ops, prog.entry.__name__)
    # the tables, as they go up, are what the bound program takes, and
    # its answer has the columns the record says
    assert all(isinstance(a, np.ndarray) for a in prog.tables)
    rows = 300
    out = jax.eval_shape(prog.fn, *_avals(prog, rows))
    assert out.dtype == jnp.float32
    assert out.shape == ((rows,) if prog.columns == 1
                         else (rows, prog.classes))
    assert prog.classes == ce.n_classes_out
    link = MODELS[name][2]
    assert (ens.loss in predict_ops.LAYOUTS[ens.layout].links) == link
    assert plan.root_counts().get("link", "none") == (
        "softmax" if link else "none")


@pytest.mark.parametrize("name", ["node-list", "node-list-chained"])
def test_a_node_lists_last_block_is_filled_inside_the_put(name):
    """Where the kernel serves, the per-tree tables go up whole blocks
    long; the fill is made a table at a time, as each is asked for."""
    ens, ce, owner, prog = _program(name, impl="pallas")
    plan = prog.plan
    assert plan.blocks > 0 and plan.trees_per_step > 0
    whole = plan.trees_per_step * plan.table_blocks
    gen = prog.fill(prog.tables)
    assert iter(gen) is gen and not isinstance(gen, (list, tuple))
    up = list(gen)
    per_tree = 4 if ce.chained else 3
    assert [len(a) for a in up[:per_tree]] == [whole] * per_tree
    entries = len(ce.sel)
    assert whole >= entries
    assert (up[1][entries:, 1] == -1.0).all()    # no leaf of any length
    for a, b in zip(prog.tables, up):
        assert (b[:len(a)] == a).all()


def test_the_quantized_ladder_is_the_heap_entrys():
    """`lut` / `lut4` are tiers of the heap layout alone: its entry walks
    the ladder and says which rung serves; the other layouts serve the f32
    program."""
    assert _program("heap", "lut")[3].tier == "lut"
    assert _program("heap", "lut4")[3].tier == "lut4"
    assert _program("heap", "lut")[3].entry is None
    for name in ("node-list", "oblivious"):
        assert _program(name, "lut4")[3].tier == "f32"


def test_the_common_rule_takes_fits_as_it_is_handed():
    asked = []

    def fits():
        asked.append(1)
        return True

    rule = predict_ops.resolve_use_pallas
    assert rule(False, True, fits) is False
    assert rule(True, True, fits) is True
    assert rule(None, True, fits) is False       # a CPU: never asked
    assert not asked
    with device.assume_platform("tpu"):
        assert rule(None, True, fits) is True
        assert rule(None, False, fits) is False  # raw rows: never asked
        assert rule(None, True, lambda: False) is False
    assert len(asked) == 1
    with pytest.raises(ValueError, match="requires binned"):
        rule(True, False, fits)
    import inspect

    assert list(inspect.signature(rule).parameters) == [
        "use_pallas", "binned", "fits"]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_choice_is_made_once_on_the_host(name, monkeypatch):
    """Under a TPU the entry asks its layout's `*_fits` while it builds,
    and binds the answer; tracing and lowering the bound program asks no
    `*_fits` again."""
    asked = []
    for owner, fits in FITS:
        real = getattr(owner, fits)
        monkeypatch.setattr(owner, fits, lambda *a, _real=real, _n=fits,
                            **kw: asked.append(_n) or _real(*a, **kw))
    with device.assume_platform("tpu"):
        ens, ce, owner, prog = _program(name)
    assert len(asked) == 1 and asked[0].startswith(
        owner.__name__.rsplit(".", 1)[1])
    assert prog.plan.blocks > 0      # the kernel serves every one of them

    def refuse(*a, **kw):
        raise AssertionError("the program asked its layout's rule again")

    for owner_, fits in FITS:
        monkeypatch.setattr(owner_, fits, refuse)
    with device.assume_platform("tpu"):
        exported = jax.export.export(jax.jit(prog.fn), platforms=("tpu",))(
            *_avals(prog, 300))
    assert "tpu_custom_call" in exported.mlir_module()
    # ... and off the TPU the same bound program is still the kernel's
    # (interpreted): the choice is the build's, not the trace's
    assert "pallas" in str(jax.make_jaxpr(prog.fn)(*_avals(prog, 300)))


def _direct_calls():
    """Each layout's jitted entry as a direct caller reaches it (the
    tests, __graft_entry__.py): the compiled tables by hand."""
    heap = _heap().compile()
    paths = _node_list(5901).compile()
    obl = MODELS["oblivious"][0]().compile()
    return {
        "predict_pallas_fits": lambda Xc, use_pallas: (
            predict_ops.predict_raw_effective(
                *heap.arrays(), Xc, max_depth=heap.max_depth,
                learning_rate=heap.learning_rate, base=heap.base_score,
                n_classes=1, tree_chunk=heap.tree_chunk,
                use_pallas=use_pallas)),
        "predict_paths_fits": lambda Xc, use_pallas: (
            predict_ops.predict_raw_effective_paths(
                *paths.arrays(), Xc, learning_rate=paths.learning_rate,
                base=paths.base_score, use_pallas=use_pallas)),
        "predict_oblivious_fits": lambda Xc, use_pallas: (
            predict_ops.predict_raw_effective_oblivious(
                *obl.arrays(), Xc, scale=obl.scale, bias=obl.bias,
                use_pallas=use_pallas)),
    }


@pytest.mark.parametrize("owner,fits", FITS, ids=[f for _, f in FITS])
def test_a_direct_callers_none_asks_the_layouts_own_rule_once(
        owner, fits, monkeypatch):
    asked = []
    real = getattr(owner, fits)
    monkeypatch.setattr(owner, fits, lambda *a, **kw: asked.append(
        (a, kw)) or real(*a, **kw))
    call = _direct_calls()[fits]
    Xc = jax.ShapeDtypeStruct((300, F), jnp.uint8)
    with device.assume_platform("tpu"):
        for use_pallas, asks in ((None, 1), (True, 0), (False, 0)):
            del asked[:]
            jax.eval_shape(lambda x, u=use_pallas: call(x, u), Xc)
            assert len(asked) == asks, (use_pallas, asked)


# ------------------------------------------------------------------ #
# (c) a fourth layout, known to nothing but this file
# ------------------------------------------------------------------ #

class FakePlan(typing.NamedTuple):
    fake_weights: int
    fake_served: int
    table_bytes: int = 0
    blocks: int = 0

    def step_rows(self, rows: int) -> int:
        return 256

    def span_counts(self) -> dict:
        return {k: getattr(self, k) for k in FAKE_SPAN_COUNTS}

    def root_counts(self) -> dict:
        return {"routing_tables": 0, "fake_weights": self.fake_weights}


FAKE_SPAN_COUNTS = ("fake_weights", "fake_served", "table_bytes")


@jax.jit
def predict_raw_effective_fake(weights, Xc):
    """A "model" of one weight a column: the weighted sum of a row's bins."""
    return Xc.astype(jnp.float32) @ weights


def _fake_scoring_program(ce, n_features, row_dtype, predict_impl, link):
    assert n_features == len(ce.weights) and not link
    served = predict_ops.resolve_use_pallas(
        predict_ops.USE_PALLAS[predict_impl], True, lambda: True)

    def fn0(weights, Xc, entry=predict_raw_effective_fake):
        return entry(weights, Xc)

    return predict_ops.ScoringProgram(
        FakePlan(len(ce.weights), int(served)), (ce.weights,), fn0,
        predict_raw_effective_fake, 1, 1)


class FakeCompiled(typing.NamedTuple):
    token: str
    weights: np.ndarray
    layout: str = "fake"


class FakeEnsemble:
    layout = "fake"
    loss = "mse"
    n_trees = 1
    n_nodes = 0
    n_features = F

    def __init__(self, weights):
        self.weights = np.asarray(weights, np.float32)

    def cache_token(self):
        return "fake:" + self.weights.tobytes().hex()

    def compile(self, tree_chunk=64):
        return FakeCompiled(self.cache_token(), self.weights)


@pytest.fixture
def fake_layout(monkeypatch):
    owner = types.ModuleType("tests_fake_layout")
    owner.scoring_program = _fake_scoring_program
    owner.SPAN_COUNTS = FAKE_SPAN_COUNTS
    owner.PHASES_COUNTS = ("fake_weights", "fake_served")
    monkeypatch.setitem(sys.modules, owner.__name__, owner)
    monkeypatch.setitem(predict_ops.LAYOUTS, "fake",
                        predict_ops.Layout(owner.__name__))
    return owner


def test_a_fourth_layout_scores_with_no_edit_of_the_backend(fake_layout):
    from ddt_tpu import cli

    ens = FakeEnsemble(np.arange(F) / 8.0)
    be = get_backend(TrainConfig(backend="tpu", n_bins=BINS))
    Xb = np.random.default_rng(5908).integers(0, BINS, size=(700, F),
                                              dtype=np.uint8)
    t0 = time.perf_counter_ns()
    out = be.predict_raw(ens, Xb)
    np.testing.assert_array_equal(out, Xb.astype(np.float32) @ ens.weights)
    root = an.root_spans("predict")[-1]
    phases = cli._predict_phases_ms(t0)
    assert not be.links_on_device(ens)
    with pytest.raises(ValueError, match="links_on_device"):
        be.predict_raw(ens, Xb, link=True)
    # its counts: on the `ensemble` span in its own order, on the root ...
    built, = [s for s in root["spans"]
              if s["name"] == "ddt:predict:ensemble"]
    assert list(built["counts"]) == ["bytes", "trees", *FAKE_SPAN_COUNTS]
    assert built["counts"]["bytes"] == ens.weights.nbytes
    assert [s["name"].removeprefix("ddt:predict:ensemble:")
            for s in root["spans"] if s["cause"] == built["id"]] == [
                "compile", "pack", "upload"]
    assert root["counts"]["fake_weights"] == F
    assert root["counts"]["classes"] == 1
    assert root["counts"]["tables_streamed_bytes"] == 0
    # ... and in the CLI's `phases_ms`, beside the steps' times
    assert phases["fake_weights"] == F and phases["fake_served"] == 0
    assert phases["ensemble"] > 0 and "tree_group" not in phases
    # the stage map knows its program by the entry's name
    assert "jit_predict_raw_effective_fake" in an.device_stages()
    # the second call is a hit of the same cache as every layout's
    assert be.resolved_predict_impl(ens.cache_token()) == "f32"
    be.predict_raw(ens, Xb[:10])
    again = an.root_spans("predict")[-1]
    assert again["counts"]["compiled_ensemble_cache_hits"] == 1
    assert again["counts"]["fake_weights"] == F


def test_the_backend_and_the_cli_name_no_layout():
    """The acceptance count, kept as a test: outside the one lookup the
    backend names no layout's class and no kernel module, and the CLI no
    kernel module."""
    import inspect

    from ddt_tpu import cli
    from ddt_tpu.backends import tpu

    backend, shell = inspect.getsource(tpu), inspect.getsource(cli)
    for name in ("CompiledNodeList", "CompiledOblivious", "NodeListEnsemble",
                 "ObliviousEnsemble", "predict_paths", "predict_oblivious",
                 "predict_pallas", "predict_lut", "_build_paths_fn",
                 "_build_oblivious_fn"):
        assert name not in backend, name
    assert backend.count("def _build_predict_fn") == 1
    assert backend.count("layout_entry(") == 1
    for name in ("predict_paths", "predict_oblivious", "predict_pallas"):
        assert name not in shell, name
