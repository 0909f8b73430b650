"""XGBoost models from the library's JSON (models/xgboost_io.py) and the
softmax NODE LIST they forced (models/tree.NodeListEnsemble with `loss`
"softmax": round-major trees of one column each, in the sub-tree form of
the path kernel with the link on the device): hand-written JSON models (the
schema, as a fixture builder: `xgboost` is not installed here; where it is,
one more test fits a real classifier) held to the plain float64 walk of the
library's own arrays (reference/numpy_predict.predict_xgboost_json).
Seeded; the lane count of a sub-tree is lowered to one tile (128) so that
trees of a few hundred leaves make chains three sub-trees deep beside trees
of one sub-tree and of one leaf."""

import importlib.util
import json

import numpy as np
import pytest

from ddt_tpu import api, cli
from ddt_tpu.config import TrainConfig
from ddt_tpu.models import tree
from ddt_tpu.models.lightgbm_io import (from_lightgbm_text,
                                        threshold_bin_mapper)
from ddt_tpu.models.tree import (NodeListEnsemble, TreeEnsemble,
                                 cut_subtrees, ensemble_from_dict)
from ddt_tpu.models.xgboost_io import from_xgboost_json, load_xgboost
from ddt_tpu.reference.numpy_predict import predict_xgboost_json

F = 9
# a column's cuts: float32 values a row can sit ON, zero of either sign and
# a denormal among them
GRID = np.asarray([-3.5, -1.0, -0.0, 1e-42, 0.25, 1.0, 2.5, 7.0, 1e6],
                  np.float32)


@pytest.fixture(autouse=True)
def one_tile_subtrees(monkeypatch):
    monkeypatch.setattr(tree, "SUBTREE_LANES", 128)


def xgb_tree(rng, n_leaves, max_depth, features=F, grid=GRID):
    """One tree in the library's arrays: a random leaf within `max_depth`
    split until `n_leaves` are there (nodes in the order they are made, as
    the library numbers them); leaf values eighths in -2..2 (dyadic)."""
    left, right, column, cond, nan_left, depth = [-1], [-1], [0], [0.0], [0], [0]
    open_ = [0]
    for _ in range(n_leaves - 1):
        room = [n for n in open_ if depth[n] < max_depth]
        if not room:
            break
        # (the newest leaf one time in three: depth beside breadth)
        n = room[-1] if rng.random() < 0.33 else room[
            int(rng.integers(len(room)))]
        open_.remove(n)
        column[n] = int(rng.integers(features))
        cond[n] = float(grid[int(rng.integers(len(grid)))])
        nan_left[n] = int(rng.integers(2))
        for side in (left, right):
            side[n] = len(left)
            open_.append(len(left))
            for a, v in ((left, -1), (right, -1), (column, 0), (cond, 0.0),
                         (nan_left, 0), (depth, depth[n] + 1)):
                a.append(v)
    for n in open_:
        cond[n] = float(rng.integers(-16, 17)) / 8.0
    k = len(left)
    return {"left_children": left, "right_children": right,
            "split_indices": column, "split_conditions": cond,
            "default_left": nan_left, "split_type": [0] * k,
            "loss_changes": [float(x >= 0) for x in left],
            "tree_param": {"num_nodes": str(k), "size_leaf_vector": "1"}}


def xgb_model(trees, classes, objective, features=F, base="5E-1"):
    """The library's JSON around `trees` (round-major: tree i to class
    i % classes), numbers as the strings the library writes."""
    c = max(classes, 1)
    return {"learner": {
        "learner_model_param": {"base_score": base, "num_feature":
                                str(features), "num_class":
                                str(classes if classes > 1 else 0)},
        "objective": {"name": objective},
        "gradient_booster": {"name": "gbtree", "model": {
            "gbtree_model_param": {"num_trees": str(len(trees))},
            "trees": trees,
            "tree_info": [i % c for i in range(len(trees))]}}},
        "version": [2, 0, 3]}


def drawn_model(seed, classes, objective, max_depth, sizes, rounds=2):
    """`rounds` x classes trees whose leaf counts cycle through `sizes`."""
    rng = np.random.default_rng(seed)
    n = max(rounds * max(classes, 1), len(sizes))
    return xgb_model([xgb_tree(rng, sizes[i % len(sizes)], max_depth)
                      for i in range(n)], classes, objective)


def rows_on_and_off(seed, n, missing):
    """Float32 rows that sit ON the cuts, a float32 either side of them,
    between them, and (with `missing`) on NaN."""
    rng = np.random.default_rng(seed)
    on = GRID[rng.integers(0, len(GRID), (n, F))]
    pick = rng.integers(0, 4, (n, F))
    X = np.select(
        [pick == 0, pick == 1, pick == 2],
        [on, np.nextafter(on, np.float32(-np.inf)),
         np.nextafter(on, np.float32(np.inf))],
        rng.normal(0, 3, (n, F)).astype(np.float32)).astype(np.float32)
    if missing:
        X[rng.random((n, F)) < 0.15] = np.nan
    return X


def cfg_of(impl):
    return TrainConfig(backend="tpu", predict_impl=impl, n_bins=256)


OBJECTIVES = {"logistic": (1, "binary:logistic"),
              "squarederror": (1, "reg:squarederror"),
              "softprob3": (3, "multi:softprob"),
              "softprob7": (7, "multi:softprob")}


@pytest.mark.parametrize("missing", [True, False],
                         ids=["missing", "no-missing"])
@pytest.mark.parametrize("depth,sizes", [(3, (1, 5, 8)),
                                         (16, (1, 30, 420, 90))],
                         ids=["heap-d3", "node-list-d16"])
@pytest.mark.parametrize("name", list(OBJECTIVES))
def test_kernel_twin_walk_and_reference_agree(name, depth, sizes, missing):
    """Trees of one leaf, of one sub-tree and of many in one model: the
    interpreted kernel, its jax.numpy twin, the host walk (raw rows and
    binned) and the float64 walk of the library's own arrays give the same
    margins BIT FOR BIT (dyadic leaf values), and the link's answers agree
    to float32 rounding."""
    classes, objective = OBJECTIVES[name]
    model = drawn_model(100 + depth + classes, classes, objective, depth,
                        sizes)
    ens = from_xgboost_json(json.dumps(model), missing=missing)
    assert isinstance(ens, TreeEnsemble if depth == 3 else NodeListEnsemble)
    assert ens.loss == {1: {"binary:logistic": "logloss"}.get(
        objective, "mse")}.get(classes, "softmax")
    mapper = threshold_bin_mapper(ens, n_bins=256)
    assert mapper.missing_bin == missing
    X = rows_on_and_off(7 + depth, 300, missing)
    Xb = mapper.transform(X)
    want = predict_xgboost_json(model, X, raw=True)
    assert want.shape == ((300, classes) if classes > 1 else (300,))
    np.testing.assert_array_equal(ens.predict_raw(X), want.astype(np.float32))
    np.testing.assert_array_equal(ens.predict_raw(Xb, binned=True),
                                  want.astype(np.float32))
    proba = predict_xgboost_json(model, X)
    np.testing.assert_allclose(ens.predict(X), proba, atol=1e-6)
    for impl in ("pallas", "onehot"):
        got = api.predict(ens, Xb, binned=True, raw=True, cfg=cfg_of(impl))
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, want.astype(np.float32))
        np.testing.assert_allclose(
            api.predict(ens, X, mapper=mapper, cfg=cfg_of(impl)), proba,
            atol=1e-6)
    if isinstance(ens, NodeListEnsemble) and classes > 1:
        ce = ens.compile()
        cut = cut_subtrees(ens, tree.SUBTREE_LANES)
        assert cut.n_subtrees.min() == 1 and cut.n_subtrees.max() >= 3
        assert ce.chained and ce.leaf_columns == classes and not ce.mean
        assert ce.subtrees_max == cut.n_subtrees.max()
        assert ce.single_subtree_trees == (cut.n_subtrees == 1).sum() > 0
        # a tree's leaves lie in its class's lanes alone: exact zeros else
        pieces = ce.leaves[:, :, :3 * classes].astype(np.float32).reshape(
            ce.n_subtrees, -1, 3, classes)
        first = np.concatenate([[0], np.cumsum(cut.n_subtrees)])
        for t in range(ens.n_trees):
            other = np.arange(classes) != t % classes
            assert not pieces[first[t]:first[t + 1]][..., other].any()


@pytest.mark.parametrize("missing", [True, False],
                         ids=["missing", "no-missing"])
@pytest.mark.parametrize("classes", [7, 3], ids=["softprob7", "softprob3"])
def test_ragged_softmax_trees_in_halved_subtrees(monkeypatch, classes,
                                                 missing):
    """Sub-trees of TWO lane tiles, as the module has them: a model of one
    K-block is cut into HALVED sub-trees (models/tree.cut_subtrees: two
    halves of 128 lanes that share their spine, the path table's diagonal
    blocks alone) under the PACKED select (9 columns: two nodes a result
    lane). Trees of one leaf, of tens of nodes and of thousands, 16 deep, in
    one model: every part admits its k (the build raises where one does
    not), parts with a second half are there beside parts of one, and the
    interpreted kernel, its twin, the host walk and the float64 walk of the
    library's own arrays give the same margins BIT FOR BIT; the link's
    answers hold the tolerance they held."""
    monkeypatch.setattr(tree, "SUBTREE_LANES", 256)
    model = drawn_model(300 + classes, classes, "multi:softprob", 16,
                        (1, 30, 2400, 90, 700, 257, 3100), rounds=2)
    ens = from_xgboost_json(json.dumps(model), missing=missing)
    assert isinstance(ens, NodeListEnsemble) and ens.deepest_leaf == 16
    mapper = threshold_bin_mapper(ens, n_bins=256)
    ce = ens.compile()
    cut = cut_subtrees(ens, 256, halved=True)
    assert ce.halved and ce.paths.shape == (ce.n_subtrees, 128, 256)
    assert ce.n_subtrees == cut.n_subtrees.sum() > ens.n_trees
    assert ce.single_subtree_trees == (cut.n_subtrees == 1).sum() > 0
    assert ce.subtrees_max == cut.n_subtrees.max() >= 10
    assert 0 < ce.spine_copies == np.count_nonzero(cut.copy)
    # the entries are PACKED (PR 53): several pieces of a tree glued by
    # copies of their common ancestors, a slot each
    assert ce.pieces == np.count_nonzero(cut.root) + (
        ens.n_leaves == 1).sum() > 2 * ce.n_subtrees
    assert ce.glue_copies == len(cut.tree) - ens.n_splits \
        == ce.pieces - ce.n_subtrees
    # entries of one half (under 128 slots, no copy) beside entries of two
    first = np.concatenate([[0], np.cumsum(cut.n_subtrees)])
    entry = first[cut.tree] + cut.subtree
    two = np.zeros(ce.n_subtrees, bool)
    two[entry[cut.lane >= 128]] = True
    assert two.any() and not two.all()
    assert (np.bincount(entry, minlength=ce.n_subtrees)[~two] < 128).all()
    # the exits of a second half lie in its own 128 lanes, the path
    # lengths say which lanes hold an exit: one more than an entry's slots
    held = ce.planes[:, 1, :] >= 0
    assert (held.sum(axis=1)[two] == np.bincount(entry)[two] + 1).all()
    assert held[two][:, 128].all() and not held[~two][:, 128:].any()
    X = rows_on_and_off(17 + classes, 300, missing)
    Xb = mapper.transform(X)
    want = predict_xgboost_json(model, X, raw=True).astype(np.float32)
    np.testing.assert_array_equal(ens.predict_raw(Xb, binned=True), want)
    proba = predict_xgboost_json(model, X)
    for impl in ("pallas", "onehot"):
        np.testing.assert_array_equal(
            api.predict(ens, Xb, binned=True, raw=True, cfg=cfg_of(impl)),
            want)
        np.testing.assert_allclose(
            api.predict(ens, X, mapper=mapper, cfg=cfg_of(impl)), proba,
            atol=1e-6)


def stump(cond, column=0, nan_left=0, low=-1.0, high=1.0):
    """x < cond: `low`, else `high`."""
    return {"left_children": [1, -1, -1], "right_children": [2, -1, -1],
            "split_indices": [column, 0, 0],
            "split_conditions": [cond, low, high],
            "default_left": [nan_left, 0, 0], "split_type": [0, 0, 0]}


EDGES = [np.float32(v) for v in (
    -7.25, -0.0, 0.0, 1e-45, 0.1, 3.0, 3.4e38, np.inf)]


@pytest.mark.parametrize("layout", ["heap", "node-list"])
@pytest.mark.parametrize("missing", [True, False],
                         ids=["missing", "no-missing"])
def test_the_strict_test_holds_on_the_thresholds(layout, missing):
    """THE STRICT TEST: a stump a threshold (`x < t`), its leaves powers of
    two apart so that the margin names the leaf of every stump, and rows ON
    every threshold, on the float32 either side of it, on +-0.0 and +-inf:
    raw and binned, host and device, they go where the library's rule sends
    them. The thresholds: zero of either sign, the smallest denormal, one
    that float32 rounds (0.1), the largest finite float32 and +inf."""
    trees = [stump(float(t), low=0.0, high=2.0 ** i)
             for i, t in enumerate(EDGES)]
    if layout == "node-list":       # a chain 13 nodes down: past the heap
        k = 13                      # node i: left a leaf (k + i), right i + 1
        trees.append({
            "left_children": list(range(k, 2 * k)) + [-1] * (k + 1),
            "right_children": list(range(1, k)) + [2 * k] + [-1] * (k + 1),
            "split_indices": [0] * (2 * k + 1),
            "split_conditions": [100.0] * k + [0.0] * (k + 1),
            "default_left": [0] * (2 * k + 1)})
    model = xgb_model(trees, 0, "reg:squarederror", features=1, base="0")
    ens = from_xgboost_json(model, missing=missing)
    assert isinstance(ens, NodeListEnsemble) == (layout == "node-list")
    at = np.asarray(EDGES, np.float32)
    values = np.unique(np.concatenate([
        at, np.nextafter(at, np.float32(-np.inf)),
        np.nextafter(at, np.float32(np.inf)),
        np.asarray([-np.inf, np.inf, 0.0, 1.0, -1e-45], np.float32)]))
    values = values[~np.isnan(values)]
    X = np.concatenate([values, -values[values == 0]])[:, None].astype(
        np.float32)
    want = predict_xgboost_json(model, X, raw=True)
    # the rule itself, spelled out: stump i answers 2^i where not x < t
    spelled = sum((2.0 ** i) * ~(X[:, 0].astype(np.float64) < float(t))
                  for i, t in enumerate(EDGES))
    np.testing.assert_array_equal(want, spelled)
    np.testing.assert_array_equal(ens.predict_raw(X), want)
    mapper = threshold_bin_mapper(ens, n_bins=256)
    Xb = mapper.transform(X)
    np.testing.assert_array_equal(ens.predict_raw(Xb, binned=True), want)
    for impl in ("pallas", "onehot"):
        np.testing.assert_array_equal(
            api.predict(ens, Xb, binned=True, raw=True, cfg=cfg_of(impl)),
            want)
    # `<=` for `<` is another model: the rows ON a threshold tell
    loose = from_xgboost_json(model, missing=missing)
    with np.errstate(over="ignore"):        # the largest float32 to +inf
        loose.threshold_raw[:] = np.nextafter(loose.threshold_raw,
                                              np.float32(np.inf))
    assert (loose.predict_raw(X) != want).sum() >= len(EDGES) - 1


@pytest.mark.parametrize("thresholds,missing,fits", [
    (254, True, True), (255, True, False), (255, False, True),
    (256, False, False)])
def test_a_column_carries_254_thresholds_with_missing_values_and_255_without(
        thresholds, missing, fits):
    """The mapper's refusal at the byte's edge: 256 bins hold 255 cuts, and
    NaN's reserved bin takes one."""
    model = xgb_model([stump(float(t)) for t in range(thresholds)], 0,
                      "reg:squarederror", features=1)
    ens = from_xgboost_json(model, missing=missing)
    if not fits:
        with pytest.raises(ValueError, match="distinct thresholds"):
            threshold_bin_mapper(ens, n_bins=256)
        return
    mapper = threshold_bin_mapper(ens, n_bins=256)
    X = np.arange(-1, thresholds + 1, dtype=np.float32)[:, None]
    if missing:
        X[0] = np.nan
    Xb = mapper.transform(X)
    assert Xb.max() == 255      # the top cut's far side, or NaN's own bin
    np.testing.assert_array_equal(
        ens.predict_raw(Xb, binned=True).astype(np.float64),
        predict_xgboost_json(model, X, raw=True))


def broken(change):
    model = drawn_model(3, 3, "multi:softprob", 4, (6,))
    change(model["learner"])
    return model


def _set(path, value):
    def change(learner):
        at = learner
        for k in path[:-1]:
            at = at[k]
        at[path[-1]] = value
    return change


BOOSTER = ("gradient_booster",)
TREE0 = BOOSTER + ("model", "trees", 0)
REFUSED = {
    "dart": (_set(BOOSTER + ("name",), "dart"), "dart"),
    "gblinear": (_set(BOOSTER + ("name",), "gblinear"), "gblinear"),
    "category-sets": (_set(TREE0 + ("split_type",), [1] * 11),
                      "split_type 1"),
    "vector-leaves": (_set(TREE0 + ("tree_param",),
                           {"size_leaf_vector": "3"}), "multi_output_tree"),
    "objective": (_set(("objective", "name"), "rank:pairwise"),
                  "no link function"),
    "not-round-major": (_set(BOOSTER + ("model", "tree_info"),
                             [0, 0, 1, 1, 2, 2]), "round-major"),
    "class-count": (_set(("learner_model_param", "num_class"), "0"),
                    "num_class"),
    "base-score-vector": (_set(("learner_model_param", "base_score"),
                               "[1E-1,2E-1,7E-1]"), "entries differ"),
    "threshold-minus-inf": (_set(TREE0 + ("split_conditions",),
                                 [-np.inf] * 11), "STRICT TEST"),
    "stray-child": (_set(TREE0 + ("left_children",), [99] + [-1] * 10),
                    "outside the tree"),
}


@pytest.mark.parametrize("name", list(REFUSED))
def test_what_the_import_refuses_is_refused_by_name(name):
    change, said = REFUSED[name]
    with pytest.raises(ValueError, match=said):
        from_xgboost_json(broken(change))


def test_the_binary_file_is_refused_by_name(tmp_path):
    path = tmp_path / "m.ubj"
    path.write_bytes(b"{L\x00\x00\x00\x00\x00\x00\x00\x07learner{")
    for load in (load_xgboost, api.load_model):
        with pytest.raises(ValueError, match="UBJSON"):
            load(str(path))
    with pytest.raises(ValueError, match="UBJSON"):
        from_xgboost_json(path.read_bytes())
    # base_score as 3.x writes it, a vector of one; a model of no tree
    model = drawn_model(4, 0, "binary:logistic", 3, (4,))
    model["learner"]["learner_model_param"]["base_score"] = "[2.5E-1]"
    assert from_xgboost_json(model).base_score == pytest.approx(np.log(1 / 3))
    model["learner"]["gradient_booster"]["model"].update(trees=[],
                                                         tree_info=[])
    with pytest.raises(ValueError, match="no tree"):
        from_xgboost_json(model)


def softmax_node_list(seed=21, classes=3):
    ens = from_xgboost_json(drawn_model(seed, classes, "multi:softprob", 16,
                                        (1, 40, 300)))
    return ens, threshold_bin_mapper(ens, n_bins=256)


def test_round_trips_keep_the_classes(tmp_path):
    ens, mapper = softmax_node_list()
    Xb = mapper.transform(rows_on_and_off(8, 200, True))
    want = ens.predict_raw(Xb, binned=True)
    again = ensemble_from_dict(ens.to_dict())
    assert isinstance(again, NodeListEnsemble) and again.loss == "softmax"
    assert again.n_classes == 3 and again.cache_token() == ens.cache_token()
    np.testing.assert_array_equal(again.predict_raw(Xb, binned=True), want)
    path = str(tmp_path / "m.npz")
    api.save_model(path, ens, mapper)
    bundle = api.load_model(path)
    np.testing.assert_array_equal(
        bundle.ensemble.predict_raw(Xb, binned=True), want)
    assert bundle.mapper.missing_bin
    assert "leaf=" in ens.dump_text(1)
    # the text LightGBM reads holds them too (tree t to class t % 3)
    text = ens.to_lightgbm_text()
    assert "num_class=3" in text
    back = from_lightgbm_text(text)
    assert isinstance(back, NodeListEnsemble) and back.loss == "softmax"
    X = rows_on_and_off(9, 100, False)
    np.testing.assert_allclose(back.predict_raw(X), ens.predict_raw(X),
                               atol=1e-5)


def test_the_cache_token_moves_with_the_class_of_a_tree():
    """The same six trees as 2 rounds x 3 classes and as 3 rounds x 2
    (`tree_info` 0 1 2 0 1 2 against 0 1 0 1 0 1) are two models."""
    rng = np.random.default_rng(31)
    trees = [xgb_tree(rng, 40, 16) for _ in range(6)]
    three = from_xgboost_json(xgb_model(trees, 3, "multi:softprob"))
    two = from_xgboost_json(xgb_model(trees, 2, "multi:softprob"))
    assert three.cache_token() != two.cache_token()
    for ens in (two, three):
        threshold_bin_mapper(ens, n_bins=256)
    assert three.compile().token != two.compile().token
    assert three.leaf_columns == 3 and two.leaf_columns == 2


def test_a_lightgbm_multiclass_model_past_the_heap_is_a_node_list():
    """31 leaves a tree 14 levels down, three classes: what was expanded
    into a dense heap of 2^15 slots a tree (or refused) is a node list now,
    and scores on the device as the walk does."""
    rng = np.random.default_rng(41)
    src = from_xgboost_json(xgb_model(
        [xgb_tree(rng, 31, 14) for _ in range(6)], 3, "multi:softprob"),
        missing=False)
    assert src.deepest_leaf > 11
    ens = from_lightgbm_text(src.to_lightgbm_text())
    assert isinstance(ens, NodeListEnsemble) and ens.loss == "softmax"
    assert ens.n_classes == 3 and ens.n_leaves.max() == 31
    mapper = threshold_bin_mapper(ens, n_bins=255)
    X = rows_on_and_off(42, 300, False)
    Xb = mapper.transform(X)
    want = ens.predict_raw(X)
    assert want.shape == (300, 3)
    np.testing.assert_allclose(want, src.predict_raw(X), atol=1e-5)
    for impl in ("pallas", "onehot"):
        np.testing.assert_allclose(
            api.predict(ens, Xb, binned=True, raw=True, cfg=cfg_of(impl)),
            want, atol=1e-5)
    # softmax's round-major trees take the sub-tree form, which refuses
    # category sets by name (PR 55 serves them in the uncut form)
    with pytest.raises(ValueError, match="category-set"):
        tree._refuse_routes("CompiledNodeList.build", chained_sets=True)


def test_from_heap_of_a_three_class_heap():
    model = drawn_model(51, 3, "multi:softprob", 3, (1, 5, 8))
    heap = from_xgboost_json(model)
    mapper = threshold_bin_mapper(heap, n_bins=256)
    assert isinstance(heap, TreeEnsemble)
    ens = NodeListEnsemble.from_heap(heap)
    assert ens.loss == "softmax" and ens.n_classes == 3
    assert ens.missing_routes
    X = rows_on_and_off(52, 300, True)
    Xb = mapper.transform(X)
    want = predict_xgboost_json(model, X, raw=True).astype(np.float32)
    np.testing.assert_array_equal(ens.predict_raw(Xb, binned=True), want)
    for impl in ("pallas", "onehot"):
        np.testing.assert_array_equal(
            api.predict(ens, Xb, binned=True, raw=True, cfg=cfg_of(impl)),
            want)


def test_the_spans_say_softmax_and_the_link(monkeypatch):
    """`link`, `leaf_columns` and the cut's shape on the `ensemble` span and
    the call's root; the link taken by the program: another cache entry of
    the same model."""
    from ddt_tpu.backends import get_backend
    from ddt_tpu.telemetry import annotations as an

    ens, mapper = softmax_node_list(61, classes=7)
    cut = cut_subtrees(ens, tree.SUBTREE_LANES)
    Xb = mapper.transform(rows_on_and_off(62, 100, True))
    be = get_backend(cfg_of("pallas"))
    assert be.links_on_device(ens)
    raw = be.predict_raw(ens, Xb)
    proba = be.predict_raw(ens, Xb, link=True)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-6)
    np.testing.assert_array_equal(proba.argmax(axis=1), raw.argmax(axis=1))
    for root, link in zip(an.root_spans("predict")[-2:],
                          ("none", "softmax")):
        built = [s for s in root["spans"]
                 if s["name"] == "ddt:predict:ensemble"][0]["counts"]
        for counts in (root["counts"], built):
            assert counts["link"] == link and counts["leaf_columns"] == 7
            assert counts["subtrees_per_tree"] == round(
                cut.n_subtrees.mean(), 2)
            assert counts["subtrees_per_tree_max"] == cut.n_subtrees.max()
            assert counts["single_subtree_trees"] == (
                cut.n_subtrees == 1).sum()
            # what the packed entries hold (PR 53)
            assert counts["pieces_per_subtree"] == round(
                (cut.root.sum() + (ens.n_leaves == 1).sum())
                / cut.n_subtrees.sum(), 2) > 1
            assert counts["glue_copies_per_subtree"] == round(
                (len(cut.tree) - ens.n_splits) / cut.n_subtrees.sum(), 2) > 0
            assert counts["select_nodes_per_lane"] == 1     # 128 lanes
        assert root["counts"]["classes"] == 7
    heap = from_xgboost_json(drawn_model(63, 3, "multi:softprob", 3, (4,)))
    assert not be.links_on_device(heap)
    with pytest.raises(ValueError, match="links_on_device"):
        be.predict_raw(heap, Xb, link=True)


def test_cli_inspect_and_predict_read_the_librarys_json(tmp_path, capsys):
    model = drawn_model(71, 7, "multi:softprob", 16, (1, 40, 300),
                        rounds=1)
    path, out = str(tmp_path / "m.json"), str(tmp_path / "p.npy")
    with open(path, "w") as f:
        json.dump(model, f)
    assert cli.main(["inspect", "--model", path, "--tree", "2"]) == 0
    said = capsys.readouterr().out
    head = json.loads(said.splitlines()[0])
    assert head["loss"] == "softmax" and head["n_classes"] == 7
    assert head["n_trees"] == 7 and head["max_depth"] > 11
    assert "leaf=" in said and "nan->" in said
    X = rows_on_and_off(72, 200, True)
    data = str(tmp_path / "x.npz")
    np.savez(data, X=X, y=np.zeros(len(X), np.int64))
    assert cli.main(["predict", "--model", path, "--data", data, "--out",
                     out, "--backend", "tpu"]) == 0
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert said["phases_ms"]["node_list"] == 1
    assert said["phases_ms"]["leaf_columns"] == 7
    assert said["phases_ms"]["link"] == "softmax"
    got = np.load(out)
    want = predict_xgboost_json(model, X)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(got.argmax(axis=1), want.argmax(axis=1))


HAS_XGBOOST = importlib.util.find_spec("xgboost") is not None


@pytest.mark.skipif(not HAS_XGBOOST, reason="xgboost is not installed: "
                    "the import is held to hand-written JSON models alone")
def test_a_real_classifier_agrees_with_its_own_predict_proba(tmp_path):
    from sklearn.datasets import load_digits
    from xgboost import XGBClassifier

    X, y = load_digits(return_X_y=True)
    X = X.astype(np.float32)
    clf = XGBClassifier(n_estimators=5, max_depth=14, max_bin=256,
                        tree_method="hist").fit(X[:1200], y[:1200])
    path = str(tmp_path / "m.json")
    clf.save_model(path)
    bundle = api.load_model(path)
    got = api.predict(bundle, X[1200:], cfg=cfg_of("pallas"))
    np.testing.assert_allclose(got, clf.predict_proba(X[1200:]), atol=1e-6)


def test_the_covtype_model_packs_into_13800_entries():
    """The XGBoost cell's own model at full size (benchmark/datagen_xgb.py,
    its model seed, through the import): 2,855,946 nodes in at most 13,800
    HALVED entries (16,051 until PR 53; 12,502 would do by the nodes alone,
    a tree's nodes over 255), the 691 trees that fit an entry still ONE,
    the largest tree under 60, and the cut no dearer than it was: under 3.5
    x the time of `_parents`, the proof of the node lists that every cut
    starts with (the connected cut took 1.8 x that on the same machine,
    this one 1 x)."""
    import importlib.util
    import pathlib
    import time

    bench = pathlib.Path(__file__).parent.parent / "benchmark"
    spec = importlib.util.spec_from_file_location(
        "_datagen_xgb_full", bench / "datagen_xgb.py")
    datagen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(datagen)
    cell = json.loads(
        (bench / "configs/covtype-xgb-softprob-d16.json").read_text())
    sh = cell["shapes"]
    ens = from_xgboost_json(datagen.drawn_model(
        sh["rounds"], sh["features"], sh["model_seed"],
        base_score=cell["model"]["base_score"],
        **cell["assumed"]["drawing"]), missing=False)
    assert (ens.n_trees, ens.n_splits) == (2450, 2_855_946)
    t0 = time.perf_counter()
    ens._parents()
    t1 = time.perf_counter()
    spans, cut = tree.choose_select_spans(ens, 256)
    t2 = time.perf_counter()
    assert spans == ((0, 1), (0, 1)) and cut.copy is not None
    fewest = -(-(ens.n_leaves.astype(np.int64) - 1) // 255)
    assert fewest.sum() == 12_502 <= cut.n_subtrees.sum() <= 13_800
    # (740 by their nodes alone; 49 of them not with their longest path)
    assert (cut.n_subtrees == 1).sum() == 691 < (fewest == 1).sum() == 740
    assert 20 < cut.n_subtrees.max() < 60
    assert tree.exit_table_lanes(7, cut.n_subtrees) == (128, 21)
    assert t2 - t1 < 3.5 * (t1 - t0) + 0.5
