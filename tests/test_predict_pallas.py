"""Pallas traversal kernel exactness sweep + compiled-ensemble cache tests.

The kernel contract (ops/predict_pallas.py): the SAME leaf for every (row,
tree) as the one-hot predict path — missing-value
routing, categorical one-vs-rest, softmax round-major classes, uneven
tree/row remainders, R=0 — and oracle-grade agreement with the NumPy
scorer. Selection is integer-exact; the one float step is the class dot,
whose summation order belongs to the compiler (two XLA programs need not
add in the same order: on the installed jax the single-output dot differs
by an ulp between the interpreted kernel and the scan). So the sweep
scores DYADIC leaf values, whose sums are exact in any order — equality
there is equality of the selection — and random leaf values are held to
F32_ACC_TOL. Runs through Pallas interpret mode on CPU (the kernel logic
the chip compiles; tests/test_tpu_lowering.py lowers the compiled form).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ddt_tpu import api
from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.data.datasets import synthetic_binary
from ddt_tpu.data.quantizer import quantize
from ddt_tpu.models.tree import CompiledEnsemble, TreeEnsemble
from ddt_tpu.ops import predict as jpred
from ddt_tpu.ops import predict_pallas as jpp
from ddt_tpu.reference import numpy_trainer as oracle

# |sum of <= 11 leaf values of magnitude ~1| in f32, any order: a few ulp
# of 1.0 (6e-8). 1e-6 admits that and is 4000x below one bf16 rounding of
# a single leaf value (2^-8), so a reduced-precision path cannot pass.
F32_ACC_TOL = dict(rtol=1e-6, atol=1e-6)


def _rand_ensemble(T=9, depth=3, F=6, bins=31, n_classes=1, seed=0,
                   missing=False, cat=()):
    """Random full-ish trees wrapped in a TreeEnsemble (the NumPy oracle
    needs the object; the device paths take its arrays)."""
    rng = np.random.default_rng(seed)
    N = 2 ** (depth + 1) - 1
    ens = TreeEnsemble(
        feature=rng.integers(0, F, size=(T, N)).astype(np.int32),
        threshold_bin=rng.integers(0, bins - 1, (T, N)).astype(np.int32),
        threshold_raw=np.zeros((T, N), np.float32),
        is_leaf=rng.random((T, N)) < 0.25,
        leaf_value=rng.standard_normal((T, N)).astype(np.float32),
        split_gain=np.zeros((T, N), np.float32),
        max_depth=depth, n_features=F, learning_rate=0.1, base_score=0.3,
        loss="softmax" if n_classes > 1 else "logloss",
        n_classes=max(n_classes, 2),
        default_left=(rng.random((T, N)) < 0.5) if missing else None,
        missing_bin=missing, n_bins=bins,
        cat_features=np.asarray(cat, np.int32) if cat else None,
    )
    return ens


def _dev_args(ens):
    use_missing = ens.missing_bin and ens.default_left is not None
    kw = dict(
        max_depth=ens.max_depth, learning_rate=ens.learning_rate,
        base=ens.base_score,
        n_classes=ens.n_classes if ens.loss == "softmax" else 1,
        missing_bin_value=ens.n_bins - 1 if use_missing else -1,
    )
    opt = {}
    if use_missing:
        opt["default_left"] = jnp.asarray(ens.default_left)
    if ens.has_cat_splits:
        opt["cat_node"] = jnp.asarray(
            np.isin(ens.feature, ens.cat_features))
    args = (jnp.asarray(ens.feature), jnp.asarray(ens.threshold_bin),
            jnp.asarray(ens.is_leaf), jnp.asarray(ens.leaf_value))
    return args, kw, opt


@pytest.mark.parametrize("n_classes,tree_chunk,rows", [
    (1, 64, 500),      # T=9 < tree_chunk: one ragged tree chunk
    (1, 4, 511),       # uneven tree remainder (9 % 4) + odd row count
    (3, 2, 257),       # softmax round-major, rows not a tile multiple
    (1, 3, 0),         # R = 0
])
@pytest.mark.parametrize("missing,cat", [
    (False, ()), (True, ()), (False, (1, 4)), (True, (2,)),
])
def test_pallas_exact_vs_onehot_sweep(n_classes, tree_chunk, rows,
                                      missing, cat):
    """The kernel's headline contract: the one-hot path's leaf selection,
    over the full routing matrix x chunk-remainder x class sweep."""
    ens = _rand_ensemble(n_classes=n_classes, missing=missing, cat=cat,
                         seed=n_classes * 7 + tree_chunk)
    # Multiples of 1/64: every partial sum is exact in f32, so the two
    # paths agree bitwise iff they select the same leaves.
    ens.leaf_value = np.round(ens.leaf_value * 64) / 64
    args, kw, opt = _dev_args(ens)
    Xb = np.random.default_rng(rows + 1).integers(
        0, ens.n_bins, size=(rows, ens.n_features)).astype(np.int32)
    want = np.asarray(jpred.predict_raw(
        *args, jnp.asarray(Xb), tree_chunk=tree_chunk, use_pallas=False,
        **kw, **opt))
    got = np.asarray(jpp.predict_raw_pallas(
        *args, jnp.asarray(Xb), tree_chunk=tree_chunk, **kw, **opt))
    np.testing.assert_array_equal(want, got)
    # and the dispatch flag reaches the same kernel
    via_flag = np.asarray(jpred.predict_raw(
        *args, jnp.asarray(Xb), tree_chunk=tree_chunk, use_pallas=True,
        **kw, **opt))
    np.testing.assert_array_equal(want, via_flag)


@pytest.mark.parametrize("missing,cat", [
    (False, ()), (True, ()), (False, (0, 3)),
])
def test_pallas_matches_numpy_oracle(missing, cat):
    """Three-way agreement: pallas == one-hot to f32 accumulation and
    both match the NumPy reference scorer to float tolerance
    (accumulation order is the only seam — selection is integer-exact
    everywhere, which the dyadic sweep above holds bitwise)."""
    ens = _rand_ensemble(T=11, depth=4, missing=missing, cat=cat, seed=5)
    args, kw, opt = _dev_args(ens)
    rng = np.random.default_rng(9)
    Xb = rng.integers(0, ens.n_bins, size=(800, ens.n_features))
    want_np = ens.predict_raw(Xb.astype(np.uint8), binned=True)
    onehot = np.asarray(jpred.predict_raw(
        *args, jnp.asarray(Xb.astype(np.int32)), tree_chunk=4,
        use_pallas=False, **kw, **opt))
    pallas = np.asarray(jpp.predict_raw_pallas(
        *args, jnp.asarray(Xb.astype(np.int32)), tree_chunk=4, **kw,
        **opt))
    np.testing.assert_allclose(pallas, onehot, **F32_ACC_TOL)
    np.testing.assert_allclose(pallas, want_np, rtol=2e-4, atol=2e-5)


def test_pallas_trained_model_softmax_and_binary():
    """Oracle-trained ensembles (not random trees) through the kernel:
    the reference trainer's exact leaf layout, both losses."""
    X, y = synthetic_binary(600, n_features=5, seed=7)
    Xb, mapper = quantize(X, n_bins=32)
    for loss_kw, C in [({}, 1),
                       ({"loss": "softmax", "n_classes": 3}, 3)]:
        yy = (y + (X[:, 0] > 0)).astype(np.int32) if C == 3 else y
        cfg = TrainConfig(n_trees=5, max_depth=3, n_bins=32,
                          backend="cpu", **loss_kw)
        ens = oracle.fit(Xb, yy, cfg, mapper=mapper)
        args, kw, opt = _dev_args(ens)
        want = ens.predict_raw(Xb, binned=True)
        onehot = np.asarray(jpred.predict_raw(
            *args, jnp.asarray(Xb.astype(np.int32)), tree_chunk=4,
            use_pallas=False, **kw, **opt))
        pallas = np.asarray(jpp.predict_raw_pallas(
            *args, jnp.asarray(Xb.astype(np.int32)), tree_chunk=4, **kw,
            **opt))
        np.testing.assert_allclose(pallas, onehot, **F32_ACC_TOL)
        np.testing.assert_allclose(pallas, want, rtol=1e-4, atol=1e-5)


def test_pallas_rejects_float_data():
    ens = _rand_ensemble()
    args, kw, _ = _dev_args(ens)
    X = np.random.default_rng(0).standard_normal(
        (10, ens.n_features)).astype(np.float32)
    with pytest.raises(ValueError, match="binned"):
        jpred.predict_raw(*args, jnp.asarray(X), use_pallas=True, **kw)


def _mux_cases():
    """Depth 1-6 x tree counts 9, 64, 130, 1000 (one group with 119 filler
    trees, half a group, two groups, eight) x 1 and 7 classes x with and
    without the missing and categorical operands, at 6 features (two
    nodes a weight tile). The 1000-tree ensembles cost 10-20 s each to
    trace interpreted, so they take every combination at depth 6 only,
    and one each at depths 1-5. Then the feature counts that decide how
    many nodes share a weight tile and where the copies of the row tile
    lie: 28 and 54 (the benchmark's), 56 (8 rows of the tile to spare for
    the mantissa row, no gap between the copies), 57 and 64 (none to
    spare: the mantissa is added on the VPU; 64 fills the tile), 65 and
    128 (one node a tile), 42 and 43, over odd and even depths: 1-8 for
    the first four, which cost 5-16 s each at depths 7 and 8."""
    combos = [(1, False, ()), (7, False, ()), (1, True, (1, 4)),
              (7, True, (2,))]
    for depth in range(1, 7):
        for T in (9, 64, 130):
            for combo in combos:
                yield (depth, T, 6, *combo)
        for combo in (combos if depth == 6 else [combos[depth % 4]]):
            yield (depth, 1000, 6, *combo)
    for i, F in enumerate((28, 54, 64, 65, 42, 43, 56, 57, 128)):
        for depth in (range(1, 9) if i < 4 else (1, 2, 5, 6)):
            # every combination at each depth, over the feature counts
            yield (depth, 9 if depth > 6 else 130, F,
                   *combos[(i + depth) % 4])
    for F in (54, 65):                        # all four at the deep end
        for combo in combos:
            yield (7, 9, F, *combo)


@pytest.fixture
def drop_compiled():
    """Every case below compiles four programs of its own shape, the
    interpreted kernels large ones, and a process that keeps them all
    aborts inside the CPU compiler some 120 cases in (under
    `--dist loadfile` one worker runs this whole file)."""
    yield
    jax.clear_caches()


@pytest.mark.parametrize("depth,T,F,n_classes,missing,cat",
                         list(dict.fromkeys(_mux_cases())))
def test_mux_tree_matches_onehot(drop_compiled, depth, T, F, n_classes,
                                 missing, cat):
    """The value mux tree on 128-lane tree groups selects the one-hot
    path's leaf for every (row, tree), with one node or two a weight
    tile: bit-equal scores on dyadic leaf values, F32_ACC_TOL on random
    ones (a group's 128 trees are summed in one dot, the one-hot path's
    64 at a time)."""
    assert jpp.nodes_per_tile(F) == (2 if F <= 64 else 1)
    ens = _rand_ensemble(T=T, depth=depth, F=F, n_classes=n_classes,
                         bins=31 if F == 6 else 255,
                         missing=missing, cat=cat, seed=100 * depth + T)
    # Keep the sums at the magnitude F32_ACC_TOL was sized for (11 leaf
    # values of about 1), whatever the tree count.
    ens.leaf_value *= min(1.0, 11 / T)
    Xb = jnp.asarray(np.random.default_rng(depth).integers(
        0, ens.n_bins, size=(300, ens.n_features)).astype(np.int32))
    random_values = ens.leaf_value
    for values, check in [
        (np.round(random_values * 64 * max(1, T // 11)) / 64,
         np.testing.assert_array_equal),
        (random_values,
         lambda a, b: np.testing.assert_allclose(a, b, **F32_ACC_TOL)),
    ]:
        ens.leaf_value = values.astype(np.float32)
        args, kw, opt = _dev_args(ens)
        want = np.asarray(jpred.predict_raw(
            *args, Xb, tree_chunk=64, use_pallas=False, **kw, **opt))
        got = np.asarray(jpp.predict_raw_pallas(
            *args, Xb, tree_chunk=64, **kw, **opt))
        check(want, got)


def _assert_every_leaf(ens, Xb, got):
    """`got` [rows] scored an ensemble whose tree t holds n * B^t at heap
    node n (B = the node count + 1, learning rate 1, base 0): its base-B
    digits are the leaves the rows reached, exact in float32, held to the
    NumPy walk (reference/numpy_predict.py) tree by tree."""
    from ddt_tpu.reference import numpy_predict

    T, n_nodes = ens.feature.shape
    leaves = np.stack([numpy_predict.leaf_of_rows(ens, t, Xb)
                       for t in range(T)], axis=1)       # [rows, T]
    digits = got.astype(np.int64)[:, None] // (n_nodes + 1) ** np.arange(
        T) % (n_nodes + 1)
    np.testing.assert_array_equal(digits, leaves)
    np.testing.assert_array_equal(got, got.astype(np.int64))


def _shared_dot_cases():
    """(classes, trees, depth, features, missing, cat, G the budget admits
    or None for the real budget, the plan that follows: groups, G, blocks,
    trees a group, class dots a grid step)."""
    plain = (6, False, ())
    # Tree counts that straddle a group of whole rounds (126 at 7
    # classes; the compiled layout pads to 64s): up to 128 padded trees
    # are ONE group, whose program is what it was; 256 are two groups of
    # 128 or three of 126, and at depth 2 the dots saved outweigh the
    # group.
    for T in (125, 126, 127):
        yield (7, T, 2, *plain, None, (1, 1, 1, 128, 1))
    for T in (252, 253):
        yield (7, T, 2, *plain, None, (3, 3, 1, 126, 1))
    yield (1, 128, 2, *plain, None, (1, 1, 1, 128, 1))
    yield (1, 129, 2, *plain, None, (2, 2, 1, 128, 1))
    # 1, 2, 3 and 7 classes over depths 3-5 in one block, in several
    # (the last one ragged), and in blocks of one group, each its own dot.
    for C, per_group in ((1, 128), (2, 128), (3, 126), (7, 126)):
        for depth in (3, 4, 5):
            yield (C, 300, depth, *plain, None, (3, 3, 1, per_group, 1))
        yield (C, 300, 3, *plain, 2, (3, 2, 2, per_group, 1))
        yield (C, 300, 3, *plain, 1, (3, 1, 3, 128, 1))
    # Six groups of whole rounds where 128s would be five: 3 blocks of 2
    # either way.
    yield (7, 640, 3, *plain, 2, (6, 2, 3, 126, 1))
    # 10 classes: 120 trees a group where that costs no group ...
    yield (10, 300, 2, *plain, None, (3, 3, 1, 120, 1))
    # The deep end, two groups each: depth 6 at one class, 7 at seven
    # (192 padded trees: two groups either way), 8 at three; at depth 7
    # a third group of 126 costs more than the dot saved, so 256 padded
    # trees keep two groups of 128 and a dot each.
    yield (1, 130, 6, *plain, None, (2, 2, 1, 128, 1))
    yield (7, 190, 7, *plain, None, (2, 2, 1, 126, 1))
    yield (7, 253, 7, *plain, None, (2, 2, 1, 128, 2))
    yield (3, 130, 8, *plain, None, (2, 2, 1, 126, 1))
    # One node a weight tile, and the routed forms of several groups,
    # folded and with the integer routing (no cell runs them).
    yield (1, 200, 6, 65, False, (), None, (2, 2, 1, 128, 1))
    yield (3, 300, 4, 6, True, (1, 4), None, (3, 3, 1, 126, 1))
    yield (3, 300, 4, 60, True, (1, 4), None, (3, 3, 1, 126, 1))
    yield (1, 300, 4, 6, True, (), 2, (3, 2, 2, 128, 1))


@pytest.mark.parametrize("C,T,depth,F,missing,cat,fit,plan",
                         list(_shared_dot_cases()))
def test_shared_class_dot_matches_reference(budget, C, T, depth, F, missing,
                                            cat, fit, plan):
    """One class dot a block of tree groups, the groups' value planes
    summed lane by lane first (groups of whole rounds where C does not
    divide 128): the plain reference's scores (reference/numpy_predict)
    and the one-hot path's, bit for bit on dyadic leaf values, learning
    rate and base score (every sum exact in float32 in any order, so
    equal bits are equal leaves and equal classes) and to F32_ACC_TOL on
    random ones (eight lane values are added before the dot, where each
    group's dot was added after)."""
    from ddt_tpu.reference import numpy_predict

    optional = int(missing) + int(bool(cat))
    if fit is not None:
        budget(fit, depth, F, C, optional)
    got_plan = jpp.table_plan(-(-T // 64) * 64, depth, F, C, None, optional)
    assert (*got_plan[:3], got_plan.trees_per_group,
            got_plan.class_dots_per_step) == plan
    ens = _rand_ensemble(T=T, depth=depth, F=F, n_classes=C,
                         bins=31 if F == 6 else 255, missing=missing,
                         cat=cat, seed=1000 * C + 10 * T + depth)
    ens.learning_rate, ens.base_score = 0.5, 0.25
    # Sums of about 11 leaf values of magnitude 1, as F32_ACC_TOL was
    # sized for, whatever the tree count.
    random_values = ens.leaf_value * np.float32(min(1.0, 11 * C / T))
    Xb = np.random.default_rng(depth).integers(
        0, ens.n_bins, size=(300, F)).astype(np.int32)
    for values, check in [
        (np.round(random_values * 64 * max(1, T // (11 * C))) / 64,
         np.testing.assert_array_equal),
        (random_values,
         lambda a, b: np.testing.assert_allclose(a, b, **F32_ACC_TOL)),
    ]:
        ens.leaf_value = values.astype(np.float32)
        args, kw, opt = _dev_args(ens)
        got = np.asarray(jpp.predict_raw_pallas(
            *args, jnp.asarray(Xb), tree_chunk=64, **kw, **opt))
        onehot = np.asarray(jpred.predict_raw(
            *args, jnp.asarray(Xb), tree_chunk=64, use_pallas=False, **kw,
            **opt))
        check(onehot, got)
        check(numpy_predict.predict_raw(ens, Xb), got)


@pytest.mark.parametrize("depth", [2, 3, 4])
@pytest.mark.parametrize("F", [28, 54, 64, 65])
@pytest.mark.parametrize("missing,cat", [
    (False, False), (True, True), (True, False), (False, True)])
def test_packed_fields_are_exact(F, depth, missing, cat):
    """Two nodes a weight tile return their bins as two bytes of one
    integer; the compare reads each byte alone. Rows that put 255 (with
    256 bins also the reserved missing bin) in both bytes at once,
    siblings that split on the same feature, and the thresholds 0, 254
    and 255 are where a carry or a mask that leaks would show. Every
    tree's leaf is checked, not a sum that could cancel: tree t's value
    at heap node n is n * B^t, so the score is the integer whose base-B
    digits are the leaves, exact in float32, against the NumPy walk
    (reference/numpy_predict.py). F = 65 is the same check on one node a
    tile."""
    n_nodes = 2 ** (depth + 1) - 1
    T = 24 // (depth + 1)                  # B^T <= 2^24
    ens = _rand_ensemble(T=T, depth=depth, F=F, bins=256, missing=missing,
                         cat=(3,) if cat else (), seed=F + depth)
    rng = np.random.default_rng(depth)
    ens.threshold_bin = rng.choice(
        [0, 1, 127, 128, 254, 255], size=(T, n_nodes)).astype(np.int32)
    ens.threshold_bin[0] = np.array([0, 127, 255])[np.arange(n_nodes) % 3]
    ens.feature[:2] = 3                    # siblings on one feature
    ens.feature[2] = F - 1                 # the last K row of each copy
    ens.is_leaf[:3, :2 ** depth - 1] = False     # full trees
    ens.learning_rate, ens.base_score = 1.0, 0.0
    ens.leaf_value = (np.arange(n_nodes)[None, :] * float(n_nodes + 1)
                      ** np.arange(T)[:, None]).astype(np.float32)
    Xb = rng.integers(0, 256, size=(300, F))
    for i, b in enumerate((255, 254, 0, 1, 127, 128)):
        Xb[i] = b
        Xb[10 + i, 3] = b                  # one feature, the rest random
        Xb[20 + i] = np.where(np.arange(F) % 2, b, 255)
    Xb = Xb.astype(np.uint8)
    args, kw, opt = _dev_args(ens)
    got = np.asarray(jpp.predict_raw_pallas(
        *args, jnp.asarray(Xb.astype(np.int32)), tree_chunk=64, **kw,
        **opt))
    _assert_every_leaf(ens, Xb, got)


def _folded_cases():
    """(F, depth, missing, cat, bins): every feature count (56 the last
    that folds, 57 the fallback's first) with every table set, the depths
    1-4 and both NaN bins (30, 254) dealt over them; then the corners of
    the product that dealing leaves out."""
    cases = []
    tables = [(True, False), (False, True), (True, True)]
    for i, F in enumerate((5, 39, 56, 57)):
        for j, (missing, cat) in enumerate(tables):
            cases.append((F, 1 + (i + j) % 4, missing, cat,
                          (31, 255)[(i + j) % 2]))
    cases += [(39, 4, True, True, 255), (39, 1, True, True, 31),
              (56, 3, True, True, 31), (56, 4, True, False, 31),
              (5, 4, False, True, 255), (57, 4, True, True, 255),
              (5, 3, True, True, 255), (56, 2, False, True, 31)]
    return list(dict.fromkeys(cases))


@pytest.mark.parametrize("F,depth,missing,cat,bins", _folded_cases())
def test_folded_routes_are_exact(drop_compiled, F, depth, missing, cat,
                                 bins):
    """The folded routed form (the missing and category routes inside the
    MXU weight tile, `routes_in_tile`) reaches `_descend_comp`'s leaf AND
    the NumPy walk's (reference/numpy_predict.py) for every (row, tree):
    thresholds 0 / 1 / 253 / 254 / 255 and the NaN bin itself (a category
    equal to it), `default_left` both ways at ordinal and category nodes,
    rows that put 0 / 253 / 254 / 255 and the NaN bin in every column.
    Checked leaf by leaf as test_packed_fields_are_exact does (tree t's
    value at heap node n is n * B^t), and bit for bit against the one-hot
    path on the same dyadic values. F = 57 is the same check on the
    integer routing the fold leaves to wider models."""
    nan_bin = bins - 1
    tables = missing + cat
    assert jpp.routes_in_tile(F, tables) == (tables if F <= 56 else 0)
    n_nodes = 2 ** (depth + 1) - 1
    T = 24 // (depth + 1)                  # B^T <= 2^24
    ens = _rand_ensemble(T=T, depth=depth, F=F, bins=bins, missing=missing,
                         cat=(0, 3) if cat else (), seed=7 * F + depth)
    rng = np.random.default_rng(F + depth)
    ens.threshold_bin = rng.choice(
        [0, 1, nan_bin - 1, nan_bin, 253, 254, 255],
        size=(T, n_nodes)).astype(np.int32)
    ens.feature[0] = 3                     # a category tree, where there are
    ens.threshold_bin[0] = np.array(       # any, with the NaN bin in it
        [nan_bin, 0, 255])[np.arange(n_nodes) % 3]
    ens.feature[1] = F - 1                 # the last K row of each part
    ens.is_leaf[:2, :2 ** depth - 1] = False     # full trees
    if missing:
        ens.default_left[0] = np.arange(n_nodes) % 2 == 0
        ens.default_left[1] = np.arange(n_nodes) % 2 == 1
    ens.learning_rate, ens.base_score = 1.0, 0.0
    ens.leaf_value = (np.arange(n_nodes)[None, :] * float(n_nodes + 1)
                      ** np.arange(T)[:, None]).astype(np.float32)
    Xb = rng.integers(0, bins, size=(300, F))
    for i, b in enumerate((0, 1, nan_bin - 1, nan_bin, 253, 254, 255)):
        Xb[i] = b
        Xb[10 + i, 3 % F] = b              # one feature, the rest random
        Xb[20 + i] = np.where(np.arange(F) % 2, b, nan_bin)
    Xb = Xb.astype(np.uint8)
    args, kw, opt = _dev_args(ens)
    Xi = jnp.asarray(Xb.astype(np.int32))
    got = np.asarray(jpp.predict_raw_pallas(*args, Xi, tree_chunk=64, **kw,
                                            **opt))
    _assert_every_leaf(ens, Xb, got)
    want = np.asarray(jpred.predict_raw(*args, Xi, tree_chunk=64,
                                        use_pallas=False, **kw, **opt))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("nan_bin", [-1, 2, 30, 254, 255])
@pytest.mark.parametrize("missing,cat", [
    (True, False), (False, True), (True, True)])
def test_folded_constants_are_exact_in_bfloat16(nan_bin, missing, cat):
    """What the prologue puts into the weight tile (`_folded_routes`:
    delta and c) is an integer bfloat16 holds, and with h the fold's one
    compare IS `_descend_comp`'s routing: every threshold -3..258 and a
    pushed-down leaf's +BIG, ordinal and category, both directions, a
    live feature and a leaf's -1, at every bin 0..255, stated here in
    integers."""
    big = 2 ** 30
    thr, is_cat, dl, live = (a.ravel() for a in np.meshgrid(
        np.r_[-3:259, big], [False, True], [False, True], [False, True],
        indexing="ij"))
    keep = live | (thr == big)             # +BIG is a leaf's, and only its
    thr, is_cat, dl, live = (a[keep] for a in (thr, is_cat, dl, live))
    feat = np.where(live, 3, -1)[None, :]
    h, delta, c = jpp._folded_routes(
        jnp.asarray(feat), jnp.asarray(thr[None, :], jnp.int32),
        jnp.asarray(dl[None, :]) if missing else None,
        jnp.asarray(is_cat[None, :]) if cat else None, nan_bin)
    for const in (delta, c):
        if const is not None:
            np.testing.assert_array_equal(
                np.asarray(const),
                np.asarray(const.astype(jnp.bfloat16).astype(jnp.float32)))
            assert np.abs(np.asarray(const)).max() <= 512
    h, delta, c = (np.zeros(thr.shape) if a is None else np.asarray(a)[0]
                   for a in (h, delta, c))
    # A leaf's one-hot is empty: colval 0, no indicator; its c still adds.
    b = np.arange(256)[:, None] * live[None, :]
    m = (b == nan_bin) & live[None, :]
    want = b > thr[None, :]
    if cat:
        want = np.where((is_cat & live)[None, :], b != thr[None, :], want)
    if missing:
        want = np.where(b == nan_bin, ~dl[None, :], want)
    w = (2 if cat else 1) * b + delta[None, :] * m + c[None, :]
    got = (np.abs(w) if cat else w) > h[None, :]
    np.testing.assert_array_equal(got, want)


def _kernel_jaxpr(F, missing=False, cat=(), T=9, depth=3):
    ens = _rand_ensemble(T=T, depth=depth, F=F, bins=255, seed=F,
                         missing=missing, cat=cat)
    args, kw, opt = _dev_args(ens)
    Xb = jnp.zeros((300, F), jnp.int32)
    return str(jax.make_jaxpr(lambda *a: jpp.predict_raw_pallas(
        *a, tree_chunk=64, **kw, **opt))(*args, Xb))


@pytest.mark.parametrize("missing,cat,k_rows", [
    (True, (1,), 56 + 56 + 8), (True, (), 56 + 56), (False, (1,), 56 + 8)])
def test_routed_models_fold_or_keep_the_integer_routing(missing, cat,
                                                        k_rows):
    """At 56 features the routed program is the folded one: one matmul a
    node over [2x | m | ones], an `abs` with the categorical table, no
    int32 routing. At 57 it is the integer routing on [256, 57] x
    [57, 128] (the whole jaxpr was compared with the parent commit's at
    57, 64 and 65 features, one table and both: the same text, PR 32)."""
    folded = _kernel_jaxpr(56, missing, cat)
    assert f"bf16[256,{k_rows}]" in folded and f"bf16[{k_rows},128]" in folded
    assert folded.count("dot_general") == 7 + 1          # nodes + class dot
    assert (" abs " in folded) == bool(cat)
    assert "i32[256,128]" not in folded
    wide = _kernel_jaxpr(57, missing, cat)
    assert "bf16[256,57]" in wide and "bf16[57,128]" in wide
    assert " abs " not in wide and "i32[256,128]" in wide
    assert wide.count("dot_general") == 7 + 1


def test_wide_models_keep_the_one_node_program():
    """Past 64 features two copies of a row do not fit the weight tile's
    128 K rows, and the scoring program is the one it was before nodes
    were packed: one [256, F] x [F, 128] matmul a node, an f32 compare,
    nothing of the packed form in it (at F = 65, 72 and 128 the whole
    jaxpr was compared with the parent commit's by hand: the same text,
    PR 28). At F = 64 the packed operands are there."""
    packed_ops = ("= bitcast[", "concatenate", "shift_left")
    wide = _kernel_jaxpr(65)
    assert not [op for op in packed_ops if op in wide]
    assert wide.count("dot_general") == 7 + 1            # nodes + class dot
    assert "bf16[256,65]" in wide and "bf16[65,128]" in wide
    packed = _kernel_jaxpr(64)
    assert not [op for op in packed_ops if op not in packed]
    assert packed.count("dot_general") == 4 + 1
    assert "bf16[256,128]" in packed and "bf16[128,128]" in packed


def test_one_group_keeps_the_parents_program():
    """The CTR model's shape, 100 trees x depth 6 x 39 features with both
    routing tables: ONE group a grid step, so there is no dot to share,
    and the scoring program is the one it was (the whole jaxpr was
    compared with the parent commit's at this shape, at 64 trees x 28
    features, at 128 trees x 7 classes and at 60 features with both
    tables: the same text, PR 34): one class dot, on the group's own
    window of a class one-hot laid out by blocks, no add of value
    planes. Eight groups (1000 trees) make one dot too, on a [8, 128]
    one-hot (since PR 36 turned over, the class on the first of 8
    sublanes) that no block index reaches, and seven adds of [256, 128]
    planes before it."""
    plan = jpp.table_plan(128, 6, 39, 1, None, 2)
    assert (plan.groups_per_step, plan.blocks, plan.class_dots_per_step,
            plan.trees_per_group, plan.tree_group) == (1, 1, 1, 128, 128)
    class_dot = "precision=(Precision.HIGHEST, Precision.HIGHEST)"
    plane_add = "f32[256,128] = add "
    one = _kernel_jaxpr(39, True, (13, 20), T=100, depth=6)
    assert one.count("dot_general") == 63 + 1
    assert one.count(class_dot) == 1 and plane_add not in one
    assert "f32[1,8,128]" in one
    eight = _kernel_jaxpr(28, T=1000, depth=6)
    assert eight.count("dot_general") == 8 * 32 + 1
    assert eight.count(class_dot) == 1 and eight.count(plane_add) == 7
    assert "f32[8,128]" in eight and "f32[1,8,1024]" not in eight


# form -> (classes, trees, depth, features, missing, cat, G the budget
# admits or None for the real budget, the plan that follows: groups, G,
# blocks, trees a group, class dots a grid step)
_INTERFACE_FORMS = {
    "packed": (1, 130, 3, 6, False, (), None, (2, 2, 1, 128, 1)),
    "one-node-F80": (1, 9, 3, 80, False, (), None, (1, 1, 1, 128, 1)),
    "folded-routed": (1, 130, 3, 6, True, (1, 4), None, (2, 2, 1, 128, 1)),
    "integer-routed-F60": (1, 9, 3, 60, True, (1, 4), None,
                           (1, 1, 1, 128, 1)),
    "one-table": (1, 9, 3, 6, True, (), None, (1, 1, 1, 128, 1)),
    "7class-blocks": (7, 300, 3, 6, False, (), 2, (3, 2, 2, 126, 1)),
    "7class-dot-a-group": (7, 253, 4, 6, False, (), None,
                           (2, 2, 1, 128, 2)),
}


@pytest.mark.parametrize("rows", [255, 256, 257, 1000])
@pytest.mark.parametrize("dtype", [np.uint8, np.int32])
@pytest.mark.parametrize("form", list(_INTERFACE_FORMS))
def test_kernel_takes_the_rows_as_they_come(budget, form, dtype, rows):
    """The kernel's HBM interface: uint8 or int32 rows go in as they are
    (the tile widened in VMEM), over a grid of the UNPADDED rows (one
    short of a tile, a tile, a tile and one, 3 tiles + 232: the last
    block ragged), and the scores come out class-major. Every form of
    the kernel against the jax.numpy form to F32_ACC_TOL, bins up to 254
    (a uint8 read as signed would miss), and the uint8 call bit-equal to
    the int32 call on the same bins: the row operand's width decides
    nothing."""
    C, T, depth, F, missing, cat, fit, plan = _INTERFACE_FORMS[form]
    optional = int(missing) + int(bool(cat))
    if fit is not None:
        budget(fit, depth, F, C, optional)
    got_plan = jpp.table_plan(-(-T // 64) * 64, depth, F, C, None, optional,
                              dtype)
    assert (*got_plan[:3], got_plan.trees_per_group,
            got_plan.class_dots_per_step) == plan
    assert (got_plan.row_operand_bytes, got_plan.scores_class_major) \
        == (np.dtype(dtype).itemsize, 1)
    assert got_plan.routes_in_tile == (optional if F <= 56 else 0)
    ens = _rand_ensemble(T=T, depth=depth, F=F, n_classes=C, bins=255,
                         missing=missing, cat=cat, seed=len(form) + T)
    ens.leaf_value *= np.float32(min(1.0, 11 * C / T))
    args, kw, opt = _dev_args(ens)
    Xb = np.random.default_rng(rows).integers(
        0, 255, size=(rows, F), dtype=np.uint8)
    Xb[::7, 0] = 254                                  # the NaN bin, if any

    def kernel(X):
        return np.asarray(jpp.predict_raw_pallas(
            *args, jnp.asarray(X), tree_chunk=64, **kw, **opt))

    got = kernel(Xb.astype(dtype))
    assert got.shape == ((rows,) if C == 1 else (rows, C))
    want = np.asarray(jpred.predict_raw(
        *args, jnp.asarray(Xb.astype(np.int32)), tree_chunk=64,
        use_pallas=False, **kw, **opt))
    np.testing.assert_allclose(got, want, **F32_ACC_TOL)
    if dtype == np.uint8:
        np.testing.assert_array_equal(got, kernel(Xb.astype(np.int32)))


# what the rows are: the program's R, the rows a step is FORCED to take
# (None: the plan's own, `TablePlan.step_rows`), the step that follows
_STEP_ROWS = {
    # a long program takes the plan's step: 16 whole steps and 300 rows
    "planned-ragged": (16 * 1024 + 300, None, 1024),
    # ... half of it where 1,024 would pass a sixteenth of the rows
    "planned-half": (16 * 512 + 1, None, 512),
    # a short one keeps 256 (200 rows: one step, mostly past row R)
    "planned-short": (200, None, 256),
    # R not a multiple of the tile; R smaller than it (a tile the caller
    # names is charged as G's 256 rows are, 12 KiB a row: 1,024 of them
    # leave no group room, and the interpreter then runs every group in
    # one block with a class dot each: another order of the float adds)
    "forced-512-ragged": (1000, 512, 512),
    "forced-512-short": (300, 512, 512),
}


@pytest.mark.parametrize("rows", list(_STEP_ROWS))
@pytest.mark.parametrize("form", ["packed", "folded-routed",
                                  "integer-routed-F60",
                                  "7class-dot-a-group"])
def test_a_longer_step_scores_the_same_bits(budget, form, rows):
    """The rows a grid step takes decide no score: a row's matmuls, its
    mux tree and its class dot (the same 128 lanes contracted) are its
    own, so the kernel at the planned step (1,024 rows for these small
    models in a long program), at a forced one, and at 256 rows give the
    same bits, random leaf values and all; with a ragged last step, and
    with a step longer than the program."""
    C, T, depth, F, missing, cat, fit, _ = _INTERFACE_FORMS[form]
    optional = int(missing) + int(bool(cat))
    if fit is not None:
        budget(fit, depth, F, C, optional)
    R, forced, step = _STEP_ROWS[rows]
    plan = jpp.table_plan(-(-T // 64) * 64, depth, F, C, None, optional,
                          np.uint8)
    assert plan.tile_rows == 1024
    if forced is None:
        assert plan.step_rows(R) == step
    ens = _rand_ensemble(T=T, depth=depth, F=F, n_classes=C, bins=255,
                         missing=missing, cat=cat, seed=len(form) + R)
    args, kw, opt = _dev_args(ens)
    Xb = np.random.default_rng(R).integers(0, 255, size=(R, F),
                                           dtype=np.uint8)
    Xb[::7, 0] = 254                                  # the NaN bin, if any

    def kernel(tile_r):
        fn = lambda X: jpp.predict_raw_pallas(          # noqa: E731
            *args, X, tree_chunk=64, tile_r=tile_r, **kw, **opt)
        text = str(jax.make_jaxpr(fn)(jnp.asarray(Xb)))
        return np.asarray(fn(jnp.asarray(Xb))), text

    got, text = kernel(forced)
    assert f"u8[{step},{F}]" in text                  # the row block
    want, text = kernel(256)
    assert f"u8[256,{F}]" in text
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype,operand", [
    (np.uint8, "u8"), (np.int32, "i32"), (np.int8, "i32"),
    (np.int16, "i32"), (np.uint16, "i32")])
def test_row_operand_is_the_datas_own_dtype(dtype, operand):
    """What crosses into the kernel: uint8 and int32 rows as they come,
    300 of them (no pad to 512), any other integer cast to int32 in XLA
    (the `predict:widen` stage, empty otherwise); the result [C, 300]."""
    ens = _rand_ensemble(T=9, depth=3, F=6, bins=31, seed=61)
    args, kw, opt = _dev_args(ens)
    Xb = np.random.default_rng(6).integers(0, 31, size=(300, 6))
    fn = lambda X: jpp.predict_raw_pallas(              # noqa: E731
        *args, X, tree_chunk=64, **kw, **opt)
    text = str(jax.make_jaxpr(fn)(jnp.asarray(Xb.astype(dtype))))
    assert re.search(r"f32\[1,300\] = pallas_call\[", text)
    assert "[512," not in text and ",512]" not in text
    assert "f32[300,1]" not in text
    cast = "i32[300,6] = convert_element_type" in text
    assert cast == (dtype not in (np.uint8, np.int32))
    assert ("i32[300,6]" in text) == (operand == "i32")
    np.testing.assert_array_equal(
        np.asarray(fn(jnp.asarray(Xb.astype(dtype)))),
        np.asarray(fn(jnp.asarray(Xb.astype(np.int32)))))


@pytest.mark.parametrize("F,depth,nodes,tiles", [
    (1, 6, 2, 32), (28, 6, 2, 32), (54, 8, 2, 128), (56, 8, 2, 128),
    (57, 3, 2, 4), (64, 1, 2, 1), (65, 6, 1, 63), (128, 8, 1, 255),
    (1024, 2, 1, 3),
])
def test_nodes_per_tile_is_read_from_the_shape(F, depth, nodes, tiles):
    """P follows from the feature count alone, and with the depth the
    MXU results a tree group costs; both ride on the table plan. So does
    `routes_in_tile`: with a routing table one node a tile at any F, and
    the tables' routes inside that tile where [2x | m | ones] fits its
    128 K rows (F <= 56), all of them or none."""
    assert jpp.nodes_per_tile(F) == nodes
    assert jpp.mxu_tiles_per_group(depth, F) == tiles
    plan = jpp.table_plan(1024, depth, F, 1, None, 0)
    assert (plan.nodes_per_tile, plan.mxu_tiles_per_group) == (nodes, tiles)
    assert (plan.routing_tables, plan.routes_in_tile) == (0, 0)
    for tables in (1, 2):
        assert jpp.routes_in_tile(F, tables) == (tables if F <= 56 else 0)
        plan = jpp.table_plan(128, min(depth, 6), F, 1, None, tables)
        assert (plan.nodes_per_tile, plan.mxu_tiles_per_group) == (
            1, 2 ** min(depth, 6) - 1)
        assert (plan.routing_tables, plan.routes_in_tile) == (
            tables, tables if F <= 56 else 0)


@pytest.mark.parametrize("depth,F,C,optional,tile_r,fits", [
    (6, 28, 1, 2, None, True),        # the bench shape
    (10, 512, 1, 2, None, False),     # monster shape blows the VMEM budget
    # No [tile, Nint*lanes] array exists, so depth costs tables only, and
    # an ensemble without the missing and categorical tables is not
    # charged for them.
    (7, 28, 1, 0, None, True),
    (8, 54, 7, 0, None, True),        # Covertype's own shape
    (6, 54, 7, 1, None, True),
    (6, 54, 7, 2, None, True),
    (7, 54, 7, 2, None, True),
    # With BOTH routing tables and more than 56 features (the integer
    # routing) the compiler keeps 192 B a row and node: at depth 7 there
    # is room left for three groups' tables (the guard refused 1024 such
    # trees while every table had to be resident), at depth 8 the working
    # set alone is past the budget.
    (7, 60, 1, 2, None, True),
    (8, 60, 1, 2, None, False),
    # a larger tile is charged by the row
    (6, 28, 1, 0, 512, True),
    (6, 60, 1, 2, 512, False),
    # The folded routed form (F <= 56) keeps nothing per node: depth
    # costs tables only there too, and past depth 10 they do not fit.
    (7, 28, 1, 2, None, True),
    (8, 28, 1, 2, None, True),
    (6, 28, 1, 2, 512, True),
    (10, 28, 1, 2, None, True),
    (11, 28, 1, 2, None, False),
])
def test_pallas_fits_guard(depth, F, C, optional, tile_r, fits):
    """Depth, features, classes, the optional operands and the tile decide
    whether the kernel serves a model; the tree count is no term of the
    rule (the node tables stream by blocks of tree groups)."""
    from ddt_tpu.ops.predict_pallas import predict_pallas_fits, table_plan

    assert predict_pallas_fits(depth, F, C, tile_r, optional) is fits
    for tpad in (64, 1024, 1 << 20):
        assert (table_plan(tpad, depth, F, C, tile_r,
                           optional).groups_per_step > 0) is fits


@pytest.mark.parametrize("tpad,depth,F,C,optional,groups,g,blocks,step", [
    # The kernel regroups the trees in 128s itself: a padded count that is
    # no multiple of 128 is planned like its next multiple.
    (64, 6, 28, 1, 2, 1, 1, 1, 1024),
    (192, 6, 28, 1, 2, 2, 2, 1, 512),
    (1024, 6, 28, 1, 2, 8, 8, 1, 256),   # the bench shape: one resident block
    (1088, 6, 28, 1, 2, 9, 9, 1, 256),
    # The edges at which the resident-tables guard turned a model away
    # (AOT-compiled under the real limit, PERF.md section 6, PR 26) are
    # now where one block becomes two, evened out.
    (1152, 8, 54, 7, 0, 9, 9, 1, 256),
    (1280, 8, 54, 7, 0, 10, 5, 2, 256),
    (2816, 6, 54, 7, 1, 22, 22, 1, 256),
    # 7 classes: groups of whole rounds, 126 trees, where the class dots
    # that saves outweigh the groups it adds (PR 34): 2,944 padded trees
    # are 23 groups of 128 and 24 of 126, two blocks of 12 either way
    (2944, 6, 54, 7, 1, 24, 12, 2, 256),
    (1536, 6, 60, 7, 2, 12, 12, 1, 256),
    (1664, 6, 60, 7, 2, 14, 7, 2, 256),  # 13 of 128: two blocks of 7 as well
    (384, 7, 60, 7, 2, 3, 3, 1, 256),
    (1024, 7, 28, 1, 0, 8, 8, 1, 256),
    (1024, 7, 60, 1, 2, 8, 3, 3, 256),
    # ... which both tables cost only by the integer routing (more than
    # 56 features); folded, the same models are planned as with one table
    # (a 13th group of 126 at 63 weight tiles against twelve dots of 6;
    # a 14th against thirteen)
    (1536, 6, 54, 7, 2, 13, 13, 1, 256),
    (1664, 6, 54, 7, 2, 14, 14, 1, 256),
    (2944, 6, 54, 7, 2, 24, 12, 2, 256),
    (1024, 7, 28, 1, 2, 8, 8, 1, 256),
    (3520, 8, 54, 7, 2, 28, 6, 5, 256),
    (128, 8, 28, 1, 2, 1, 1, 1, 256),
    # Covertype's own model, 500 rounds x 7 classes: 9 groups fit, so 4
    # blocks, of 7 (not 3 of 9 and one of 1 filled to 9); 28 groups of
    # 128 and 28 of 126
    (3520, 8, 54, 7, 0, 28, 7, 4, 256),
    # the trace is bounded whatever the tree count
    (1 << 20, 6, 28, 1, 0, 8192, 27, 304, 256),
    (128, 8, 60, 1, 2, 1, 0, 0, 256),      # nothing fits
    # The rows a grid step takes (PR 42): 256, doubled while the step's MXU
    # weight tiles, G x tiles a group, times its rows stay within 256 x 256,
    # 1,024 at most. The three heap cells' shapes: 8 x 32 and 7 x 128
    # tiles keep 256 rows, the CTR model's one group of 63 takes 1,024 ...
    (1024, 6, 28, 1, 0, 8, 8, 1, 256),
    (128, 6, 39, 1, 2, 1, 1, 1, 1024),
    # ... and between them: one and two groups of 32 tiles 1,024, four
    # 512; depth 8 in one group (128 tiles) 512, depth 7 1,024; one node
    # a tile, 127 a group at depth 7: 512
    (128, 6, 28, 1, 0, 1, 1, 1, 1024),
    (256, 6, 28, 1, 0, 2, 2, 1, 1024),
    (512, 6, 28, 1, 0, 4, 4, 1, 512),
    (128, 8, 28, 1, 0, 1, 1, 1, 512),
    (128, 7, 28, 1, 0, 1, 1, 1, 1024),
    (128, 7, 65, 1, 0, 1, 1, 1, 512),
    # where the kernel's VMEM at the tile is past the budget it is
    # halved: 257 columns, 1,793; the integer routing of both tables,
    # charged by the node
    (128, 6, 256, 1, 0, 1, 1, 1, 1024),
    (128, 6, 257, 1, 0, 1, 1, 1, 512),
    (128, 6, 1793, 1, 0, 1, 1, 1, 256),
    (128, 6, 57, 1, 2, 1, 1, 1, 512),
    (128, 7, 57, 1, 2, 1, 1, 1, 256),
])
def test_table_plan(tpad, depth, F, C, optional, groups, g, blocks, step):
    """How many tree groups a table block holds (G) and how many blocks a
    row tile walks: the budget's arithmetic, pinned. G is what it was
    before the blocks shared their class dot: `_vmem_bytes` charges a
    class window a group as it did; and what it was before the step's
    rows followed from its tiles: G is fitted at 256 rows whatever
    `tile_rows` turns out to be. A program takes the step where it has
    sixteen of them, half of it where eight, and a program of 200 rows
    keeps 256."""
    plan = jpp.table_plan(tpad, depth, F, C, None, optional)
    assert plan[:3] == (groups, g, blocks)
    assert g * blocks >= groups * bool(g)
    assert groups == -(-tpad // (plan.trees_per_group or 128))
    nodes = 4 * 128 * ((2 + optional) * (2 ** depth - 1) + 2 ** depth)
    # the class one-hot: one window the whole ensemble shares, fetched
    # once, or one a group
    shared = plan.class_dots_per_step < g
    assert plan.table_bytes == (blocks * g * nodes + 4 * 128 * C * (
        bool(g) if shared else blocks * g))
    assert plan.tile_rows == plan.rows_per_step == step
    assert [plan.step_rows(r) for r in (200, 16 * step - 1, 16 * step,
                                        2_000_000)] == [
        256, max(step // 2, 256), step, step]
    if g:
        assert jpp._vmem_bytes(g, depth, F, C, 256, optional) \
            <= jpp._VMEM_BUDGET_BYTES
        assert jpp._vmem_bytes(g, depth, F, C, step, optional,
                               jpp._STEP_ROW_BYTES if step > 256
                               else jpp._ROW_BYTES) <= jpp._VMEM_BUDGET_BYTES


@pytest.mark.parametrize("tpad,depth,F,C,optional,per_group,dots,groups", [
    # One class, and any C that divides 128: lane l is class l % C in
    # every group of 128, so every block of more than one group shares
    # its dot and nothing else moves.
    (1024, 6, 28, 1, 0, 128, 1, 8),       # the 1000-tree cell
    (1024, 6, 28, 2, 0, 128, 1, 8),
    (1024, 8, 54, 64, 0, 128, 1, 8),
    (128, 6, 39, 1, 2, 128, 1, 1),        # the CTR cell: one group
    (64, 6, 28, 1, 0, 128, 1, 1),
    # Covertype's own model: 28 groups of 126 where 28 of 128 were
    (3520, 8, 54, 7, 0, 126, 1, 28),
    (3520, 8, 54, 7, 2, 126, 1, 28),
    # 3 classes: 126 too; 1,216 padded trees are 10 groups either way
    (1216, 6, 28, 3, 0, 126, 1, 10),
    # 10 classes at depth 8: 9 groups of 120 for 8 of 128 are 128 weight
    # tiles more, the seven dots saved 42: each group keeps its dot ...
    (1024, 8, 54, 10, 0, 128, 8, 8),
    # ... at depth 3 (4 tiles a group) the dots are what costs
    (1024, 3, 54, 10, 0, 120, 1, 9),
    # 7 classes where the 126s do not fit the groups the 128s fill:
    # 1,152 padded trees in 9 groups, resident, against 10 in two blocks
    (1152, 8, 54, 7, 0, 128, 9, 9),
    # more classes than lanes: no whole round fits a group
    (1024, 4, 28, 200, 0, 128, 8, 8),
    # blocks of ONE group (depth 10, both tables folded): a dot each, and
    # the program of before
    (1024, 10, 28, 1, 2, 128, 1, 8),
    (128, 8, 60, 1, 2, 128, 0, 1),        # nothing fits
])
def test_class_dot_layout_is_read_from_the_shape(tpad, depth, F, C, optional,
                                                 per_group, dots, groups):
    """Whether a block's groups share one class dot, and the trees a
    group then holds (whole rounds of C), follow from C, the padded tree
    count and G: the layout that asks the MXU for fewer results a row
    tile, the 128-tree groups with a dot each on a tie."""
    plan = jpp.table_plan(tpad, depth, F, C, None, optional)
    assert (plan.trees_per_group, plan.class_dots_per_step,
            plan.table_groups) == (per_group, dots, groups)
    assert dots in (1, plan.groups_per_step)
    assert plan.tree_group == 128
    if per_group < 128:
        assert per_group % C == 0 and per_group + C > 128 and dots == 1


def test_padded_tree_count_must_be_a_multiple_of_the_chunk():
    """What the guard used to answer for (`not fits(1000, 64, ...)`): the
    kernel entry itself refuses a tree count the compiled layout cannot
    have."""
    z = jnp.zeros((1000, 127), jnp.int32)
    with pytest.raises(ValueError, match="not a multiple"):
        jpp.predict_effective_pallas(
            z, z, jnp.zeros((1000, 64)), jnp.ones((1000, 1)),
            jnp.zeros((8, 28), jnp.int32), max_depth=6, learning_rate=0.1,
            base=0.0, tree_chunk=64)


@pytest.mark.parametrize("impl,T,want,routed,F", [
    ("pallas", 9, 128, 0, 5), ("pallas", 130, 128, 0, 5),
    ("onehot", 9, 0, 0, 5),
    ("auto", 9, 0, 0, 5),  # off the chip the auto dispatch is the one-hot path
    ("lut", 9, 0, 0, 5),   # its own kernel, its own chunking
    # the routed forms: the missing table, and the categorical one with it
    ("pallas", 9, 128, 1, 5), ("pallas", 130, 128, 2, 5),
    ("onehot", 9, 0, 2, 5),
    # ... past 56 features, where the routes do not fit the weight tile
    ("pallas", 9, 128, 1, 57), ("pallas", 9, 128, 2, 57),
])
def test_ensemble_span_says_which_form_served(impl, T, want, routed, F):
    """`tree_group` on the `ddt:predict:ensemble` span: the lane width of
    the traversal kernel's tree planes, 128 whatever the tree count, and 0
    when that kernel does not serve the model; `routing_tables`: how many
    of the missing and categorical tables that kernel routes by;
    `routes_in_tile`: how many of those it routes inside the MXU weight
    tile (all of them at F <= 56), not by integers on the VPU;
    `trees_per_group` and `class_dots_per_step`: the lanes of a group
    that hold trees and the class dots a grid step makes, 1 where the
    block's groups share it; `row_operand_bytes` and
    `scores_class_major`: the kernel's HBM interface (PR 36);
    `rows_per_step`: the plan's `tile_rows` (PR 42)."""
    from ddt_tpu.telemetry import annotations as an

    ens = _rand_ensemble(T=T, depth=3, F=F, bins=31, seed=40 + T,
                         missing=routed >= 1, cat=(1, 3) if routed == 2
                         else ())
    Xb = np.random.default_rng(3).integers(0, 31, size=(50, F),
                                           dtype=np.uint8)
    be = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                 predict_impl=impl))
    be.predict_raw(ens, Xb)
    root = an.root_spans("predict")[-1]
    counts = {s["name"]: s for s in root["spans"]}[
        "ddt:predict:ensemble"]["counts"]
    # The span carries the plan by the kernel module's one list of names.
    assert list(counts) == ["bytes", "trees", *jpp.SPAN_COUNTS]
    assert counts["tree_group"] == want and counts["trees"] == T
    # ... on the call's root too: a later call finds the model resident
    # and opens no `ensemble` span, and still says which form served it.
    tables = routed if want else 0
    assert counts["routing_tables"] == tables
    assert root["counts"]["routing_tables"] == tables
    assert counts["routes_in_tile"] == (tables if F <= 56 else 0)
    # The table plan rides on the same span: these small models are one
    # resident block of all their groups, and nothing streams.
    groups = -(-T // 128) if want else 0
    assert (counts["table_groups"], counts["groups_per_step"]) \
        == (groups, groups)
    # (the class one-hot once: two groups share one dot and its window)
    assert counts["table_bytes"] == 4 * 128 * (
        groups * ((2 + routed) * 7 + 8) + bool(groups))
    assert (counts["trees_per_group"], counts["class_dots_per_step"]) \
        == ((128, 1) if want else (0, 0))
    # ... and what crosses between XLA and the kernel: the backend's
    # uint8 chunk as it is, the scores class-major.
    assert (counts["row_operand_bytes"], counts["scores_class_major"]) \
        == ((1, 1) if want else (0, 0))
    # ... and the rows a grid step of a long program takes: these steps
    # hold 4 to 14 weight tiles (this call's 50 rows run one of 256)
    assert counts["rows_per_step"] == (1024 if want else 0)
    # ... and how the kernel uses the MXU: 5 features, so two nodes a
    # weight tile, the root's and one a pair of siblings (7 nodes: 4);
    # with a routing table one node a tile.
    assert (counts["nodes_per_tile"], counts["mxu_tiles_per_group"]) \
        == (((1, 7) if routed else (2, 4)) if want else (0, 0))
    assert root["counts"]["classes"] == 1
    assert root["counts"]["tables_streamed_bytes"] == 0


# --------------------------------------------------------------------- #
# CompiledEnsemble: host layout + device-resident cache
# --------------------------------------------------------------------- #

def test_compiled_ensemble_effective_arrays_match_traced():
    """The host pushdown twin is bitwise-identical to the traced one —
    the compiled path may never drift from predict_raw's prologue."""
    ens = _rand_ensemble(T=6, depth=4, seed=3)
    ce = CompiledEnsemble.build(ens, tree_chunk=4)
    tpad = ce.n_trees_padded - ens.n_trees

    def pad(a, fill=0):
        return jnp.pad(jnp.asarray(a), ((0, tpad), (0, 0)),
                       constant_values=fill)

    ef, et, ev, _ = jpred._effective_arrays(
        pad(ens.feature, -1), pad(ens.threshold_bin),
        pad(ens.is_leaf, True), pad(ens.leaf_value), ens.max_depth)
    np.testing.assert_array_equal(ce.eff_feat, np.asarray(ef))
    np.testing.assert_array_equal(ce.eff_thr, np.asarray(et))
    lo = (1 << ens.max_depth) - 1
    np.testing.assert_array_equal(ce.bot_val, np.asarray(ev)[:, lo:])


def test_backend_compiled_ensemble_cache_hits_and_invalidation():
    """Repeat scoring hits the device-resident cache (counter moves);
    mutating the model in place changes the token and serves fresh
    trees — a cached compiled ensemble may never go stale."""
    from ddt_tpu.telemetry import counters as tele_counters

    Xb = np.random.default_rng(0).integers(
        0, 31, size=(400, 6), dtype=np.uint8)
    ens = _rand_ensemble(T=5, depth=3, F=6, bins=31, seed=11)
    be = get_backend(TrainConfig(backend="tpu", n_bins=31))
    c0 = tele_counters.snapshot()
    a = be.predict_raw(ens, Xb)
    b = be.predict_raw(ens, Xb)
    np.testing.assert_array_equal(a, b)
    assert tele_counters.delta(c0)["compiled_ensemble_cache_hits"] == 1
    tok0 = ens.cache_token()
    ens.leaf_value[:] += 1.0                      # in-place mutation
    assert ens.cache_token() != tok0
    c = be.predict_raw(ens, Xb)
    assert not np.allclose(a, c)                  # fresh trees served
    np.testing.assert_allclose(
        c, ens.predict_raw(Xb, binned=True), rtol=2e-4, atol=2e-5)


def test_backend_predict_impl_pallas_matches_onehot():
    """cfg.predict_impl='pallas' forces the kernel through the whole
    backend path (compiled cache + chunking) — same leaves, same scores."""
    Xb = np.random.default_rng(2).integers(
        0, 31, size=(300, 5), dtype=np.uint8)
    ens = _rand_ensemble(T=7, depth=3, F=5, bins=31, seed=2)
    be_1h = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                    predict_impl="onehot"))
    be_pl = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                    predict_impl="pallas"))
    # The class dot's summation order is the compiler's: random leaf
    # values to F32_ACC_TOL, dyadic ones (every sum exact) bit for bit.
    np.testing.assert_allclose(be_1h.predict_raw(ens, Xb),
                               be_pl.predict_raw(ens, Xb), **F32_ACC_TOL)
    ens.leaf_value = np.round(ens.leaf_value * 64) / 64
    np.testing.assert_array_equal(be_1h.predict_raw(ens, Xb),
                                  be_pl.predict_raw(ens, Xb))


def test_predict_impl_flag_validation():
    with pytest.raises(ValueError, match="predict_impl"):
        TrainConfig(predict_impl="cuda")


# --------------------------------------------------------------------- #
# overlapped streaming + the multi-chip flag
# --------------------------------------------------------------------- #

def test_predict_streaming_matches_in_memory():
    from ddt_tpu.streaming import predict_streaming

    X, y = synthetic_binary(2000, n_features=6, seed=4)
    Xb, _ = quantize(X, n_bins=31)
    cfg = TrainConfig(n_trees=6, max_depth=3, n_bins=31, backend="tpu")
    ens = api.train(Xb, y, cfg, binned=True, log_every=10**9).ensemble
    be = get_backend(cfg)
    want = be.predict_raw(ens, Xb)

    def cf(c):                    # ragged last chunk: 600*3 + 200
        return Xb[c * 600:(c + 1) * 600], None

    got = predict_streaming(cf, 4, ens, backend=be)
    np.testing.assert_array_equal(want, got)
    # sink form streams per-chunk scores and returns the row count
    parts = {}
    rows = predict_streaming(cf, 4, ens, backend=be,
                             sink=lambda c, s: parts.__setitem__(c, s))
    assert rows == 2000
    np.testing.assert_array_equal(
        np.concatenate([parts[i] for i in range(4)]), want)
    # host fallback (backend=None) agrees to scorer tolerance
    host = predict_streaming(cf, 4, ens, backend=None)
    np.testing.assert_allclose(host, want, rtol=2e-4, atol=2e-5)
    # oversized chunks (past the backend's per-dispatch row bound) must
    # route through the backend's own chunked path, not one big dispatch
    # (the 10M x 1000 single-dispatch OOM class), and stay in order
    from ddt_tpu.backends.tpu import TPUDevice

    old = TPUDevice.PREDICT_ROW_CHUNK
    TPUDevice.PREDICT_ROW_CHUNK = 256
    try:
        big = predict_streaming(cf, 4, ens, backend=be)
    finally:
        TPUDevice.PREDICT_ROW_CHUNK = old
    np.testing.assert_array_equal(big, want)


def test_api_predict_n_partitions_flag():
    """Multi-chip scoring is a flag: api.predict(n_partitions=4) row-
    shards over a parallel.mesh row mesh and matches the single-chip
    path exactly (8 virtual CPU devices, conftest)."""
    X, y = synthetic_binary(1500, n_features=6, seed=6)
    Xb, _ = quantize(X, n_bins=31)
    cfg = TrainConfig(n_trees=4, max_depth=3, n_bins=31, backend="tpu")
    ens = api.train(Xb, y, cfg, binned=True, log_every=10**9).ensemble
    want = api.predict(ens, Xb, binned=True, backend=get_backend(cfg),
                       raw=True)
    got = api.predict(ens, Xb, binned=True, n_partitions=4, raw=True)
    np.testing.assert_array_equal(want, got)
    # The sharded scoring program is compiled ONCE per model: a bare
    # shard_map ran eagerly and re-compiled its body on every call
    # (PR 21 found it on the chip: 1.4 s a call against 0.03 s).
    from ddt_tpu.telemetry import counters as tele_counters

    tele_counters.install_jax_listener()
    c0 = tele_counters.snapshot()
    again = api.predict(ens, Xb, binned=True, n_partitions=4, raw=True)
    assert tele_counters.delta(c0)["jit_compiles"] == 0
    np.testing.assert_array_equal(got, again)
