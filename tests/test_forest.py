"""The averaged forest: a node list with VECTOR LEAVES whose trees are cut
into chained sub-trees (models/tree.cut_subtrees, CompiledNodeList's
sub-tree form; ops/predict_paths.py's chain and class dot, interpreted;
ops/predict._predict_chain, its jax.numpy twin) held to the walk of the
UNCUT tree (reference/numpy_predict.predict_proba_node_list), and
scikit-learn's own forests through models/sklearn_io.py. Seeded; the lane
count of a sub-tree is lowered to one tile (128) so that trees of a few
hundred leaves make chains three sub-trees deep."""

import json

import numpy as np
import pytest

from ddt_tpu import api, cli
from ddt_tpu.config import TrainConfig
from ddt_tpu.models import tree
from ddt_tpu.models.tree import (NodeListEnsemble, cut_subtrees,
                                 ensemble_from_dict, random_node_list,
                                 split_bfloat16)
from ddt_tpu.ops import predict_paths
from ddt_tpu.reference.numpy_predict import (leaf_of_rows_node_list,
                                             predict_proba_node_list)

BINS, F = 64, 12


@pytest.fixture(autouse=True)
def one_tile_subtrees(monkeypatch):
    monkeypatch.setattr(tree, "SUBTREE_LANES", 128)


def forest(seed, n_trees, leaves, columns, missing=False, dyadic=False,
           features=F):
    return random_node_list(
        np.random.default_rng(seed), n_trees, leaves, features, n_bins=BINS,
        dyadic=dyadic, missing=missing, leaf_columns=columns)


def rows_of(seed, n, features=F):
    return np.random.default_rng(seed).integers(
        0, BINS, (n, features)).astype(np.uint8)


def scored(ens, Xb, impl):
    return api.predict(ens, Xb, binned=True, raw=True, cfg=TrainConfig(
        backend="tpu", predict_impl=impl, n_bins=BINS))


# the slot of every tree's node 0 (`SubtreeCut`: node n of tree t is slot
# first_node(ens)[t] + n), and behind the last the nodes' count
first_node = tree._first_nodes


def mnist_forest(n_trees):
    """The first `n_trees` trees of the benchmark's forest (its drawing, its
    forest seed) and the cell's shapes."""
    import importlib.util
    import pathlib

    bench = pathlib.Path(__file__).parent.parent / "benchmark"
    spec = importlib.util.spec_from_file_location(
        "_datagen_forest", bench / "datagen_forest.py")
    datagen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(datagen)
    cell = json.loads((bench / "configs/mnist-rf-100t-full.json").read_text())
    sh = cell["shapes"]
    t = datagen.grown_forest(n_trees, sh["features"], sh["n_bins"],
                             sh["n_classes"], sh["forest_seed"],
                             **cell["assumed"]["drawing"])
    zeros = np.zeros(t["feature"].shape, np.float32)
    return NodeListEnsemble(
        feature=t["feature"], threshold_bin=t["threshold_bin"],
        threshold_raw=zeros, left_child=t["left_child"],
        right_child=t["right_child"], leaf_value=t["leaf_value"],
        n_leaves=t["n_leaves"], split_gain=zeros, n_features=sh["features"],
        learning_rate=1.0, base_score=0.0, loss="mean", n_classes=10,
        n_bins=sh["n_bins"]), sh


def slots_of(ens, cut, t):
    """Tree t's slots: its nodes' (in their order), its glue copies'."""
    base = first_node(ens)
    return np.arange(base[t], base[t + 1]), ens.n_splits + np.nonzero(
        cut.tree[ens.n_splits:] == t)[0]


def chain_depth(ens, cut, t):
    """Entries on the longest chain of tree t (1: the tree is uncut)."""
    parent = ens._parents()[0][t]
    own, _ = slots_of(ens, cut, t)
    sub, depth = cut.subtree[own], {}
    for n in np.argsort(sub, kind="stable"):
        if cut.root[own][n]:
            up = parent[n]
            depth[sub[n]] = max(depth.get(sub[n], 1), 1 + (
                depth[sub[up]] if up >= 0 else 0))
    return max(depth.values(), default=1)


def glued(cut, mine):
    """An entry's slots `mine` in the pre-order of its glued tree (left
    before right), and the slots above each in the entry."""
    kids = {}
    for s in mine:
        kids.setdefault(int(cut.up[s]), []).append(int(s))
    top, = kids[-1]
    pre, depth, stack = [], {}, [(top, 0)]
    while stack:
        s, d = stack.pop()
        pre.append(s)
        depth[s] = d
        stack += [(c, d + 1) for c in sorted(
            kids.get(s, []), key=lambda c: -cut.side[c])]
    assert sorted(pre) == sorted(int(s) for s in mine)    # one tree, whole
    return pre, depth


def entries_of(ens, cut, lanes):
    """What an ENTRY of the cut is (models/tree.cut_subtrees), held over
    every tree; yields (tree, entry, its slots in the glued tree's
    pre-order, the slots above each, its exits a slot) for the caller's
    own bounds. Every internal node in ONE entry; an entry's pieces each
    connected, none above another, each piece's parent in an EARLIER entry
    of its tree; p pieces glued by p - 1 copies, each the lowest common
    ancestor it stands for, a piece or copy on either side of it; nodes and
    copies at most `lanes` - 1, exits at most `lanes`."""
    M, base = ens.n_splits, first_node(ens)
    parent, hangs = ens._parents()[:2]
    assert len(cut.tree) >= M and not cut.root[M:].any()
    assert np.array_equal(cut.origin[:M], np.nonzero(ens.live_nodes)[1])
    assert np.array_equal(cut.tree[:M], np.nonzero(ens.live_nodes)[0])

    def above(t, n):                    # the node's ancestors, itself first
        out = [n]
        while parent[t, out[-1]] >= 0:
            out.append(int(parent[t, out[-1]]))
        return out

    for t in range(ens.n_trees):
        own, glue = slots_of(ens, cut, t)
        if not len(own):
            assert cut.n_subtrees[t] == 1 and not len(glue)
            continue
        sub, root = cut.subtree[own], cut.root[own]
        assert root[0] and sub[0] == 0
        assert sorted(set(sub)) == list(range(cut.n_subtrees[t]))
        # a piece is connected, by the tree's own links: a node that roots
        # none hangs on a node of its entry; a piece's root on an EARLIER
        # entry's node (a link's target, its parent numbered before it)
        inner, hung = np.nonzero(~root)[0], np.nonzero(root)[0][1:]
        assert (sub[parent[t, inner]] == sub[inner]).all()
        assert (cut.up[own[inner]] == base[t] + parent[t, inner]).all()
        assert (cut.side[own[inner]] == hangs[t, inner]).all()
        assert (sub[parent[t, hung]] < sub[hung]).all()
        kids = np.stack([ens.left_child[t, :len(own)],
                         ens.right_child[t, :len(own)]], 1)
        n_exits = ((kids < 0) | (sub[np.maximum(kids, 0)] != sub[:, None])
                   ).sum(axis=1)
        for k in range(cut.n_subtrees[t]):
            tops = np.nonzero(root & (sub == k))[0]
            copies = glue[cut.subtree[glue] == k]
            assert len(tops) >= 1 and len(copies) == len(tops) - 1
            chains = {int(r): above(t, int(r)) for r in tops}
            lcas = set()
            for i, a in enumerate(tops):
                for b in tops[i + 1:]:
                    assert a not in chains[int(b)] and b not in chains[int(a)]
                    lcas.add(next(x for x in chains[int(a)]
                                  if x in chains[int(b)]))
            # (p leaves of a binary tree branch at p - 1 nodes)
            assert sorted(cut.origin[copies]) == sorted(lcas)
            mine = np.concatenate([own[sub == k], copies])
            pre, depth = glued(cut, mine)
            for s in copies:            # a copy: one slot on either side,
                below = mine[cut.up[mine] == s]     # under that child of
                assert sorted(cut.side[below]) == [-1, 1]   # its node
                for c in below:
                    chain = above(t, int(cut.origin[c]))
                    at = chain.index(int(cut.origin[s]))
                    assert at >= 1 and hangs[t, chain[at - 1]] == cut.side[c]
            for r in tops:              # a piece hangs on a copy, or is all
                assert (cut.up[own[r]] in copies) == (len(tops) > 1)
            exits = {int(s): int(n_exits[s - base[t]]) if s < M else 0
                     for s in mine}
            assert len(mine) <= lanes - 1
            assert sum(exits.values()) == len(mine) + 1 <= lanes
            yield t, k, pre, depth, exits


@pytest.mark.parametrize("lanes", [8, 32, 128])
def test_the_cut_is_a_partition_into_connected_subtrees(lanes):
    """(The name is older than the meaning: a partition of the NODES into
    entries, an entry one or several connected pieces glued by copies of
    their common ancestors, `entries_of`.) The lanes of an entry: the
    pre-order of its glued tree, 0.. without a gap; and the cut FILLS: it
    makes no fewer entries than nodes / bound, and few more."""
    ens = forest(1, 6, (1, 700), 3)
    cut = cut_subtrees(ens, lanes)
    assert cut.copy is None
    pieces = packed = 0
    for t, k, pre, depth, exits in entries_of(ens, cut, lanes):
        assert cut.lane[pre].tolist() == list(range(len(pre)))
        pieces += int(cut.root[pre].sum())
        packed += int(cut.root[pre].sum() > 1)
    assert packed > 3 and pieces == cut.root.sum()
    assert len(cut.tree) - ens.n_splits == pieces - (
        cut.n_subtrees[ens.n_leaves > 1]).sum()
    # the fewest entries a bound allows is no fewer than nodes / bound; the
    # connected cut (Kundu and Misra's, until PR 53) made 1.11-1.6 x that
    fewest = np.maximum(np.ceil((ens.n_leaves - 1) / (lanes - 1)), 1)
    assert (cut.n_subtrees >= fewest).all()
    assert cut.n_subtrees.sum() <= {8: 1.2, 32: 1.1, 128: 1.1}[lanes] \
        * fewest.sum()
    if lanes == 128:
        assert max(chain_depth(ens, cut, t) for t in range(6)) >= 3


def test_a_cycle_and_a_stray_child_are_refused():
    ens = forest(2, 2, 40, 1)
    ens.left_child[0, 5] = 99
    with pytest.raises(ValueError, match="outside its tree"):
        cut_subtrees(ens, 8)
    # nodes 3 and 4 made each other's child: one parent each, out of reach
    loop = forest(2, 1, 40, 1)
    up, side = loop._parents()[:2]
    for n in (3, 4):
        hangs = (loop.left_child if side[0, n] < 0 else loop.right_child)
        hangs[0, up[0, n]] = ~0 if n == 3 else ~1
    loop.left_child[0, 3], loop.left_child[0, 4] = 4, 3
    with pytest.raises(ValueError):
        cut_subtrees(loop, 8)


# the select's spans at 784 columns: 7 K-blocks, the last of 16 columns
SPANS = {"dense": ((0, 7), (0, 7)), "shared": ((0, 4), (3, 7)),
         "apart": ((0, 3), (3, 7)), "lopsided": ((0, 1), (1, 7)),
         "wide": ((0, 5), (2, 7))}


@pytest.mark.parametrize("name", list(SPANS))
def test_the_cut_under_the_spans_bound(name):
    """Still entries of glued pieces, parents first (`entries_of`); an
    entry holds all three bounds, its glue copies counted, and every lane's
    K-block lies in its tile's span; the bound costs few entries."""
    spans = SPANS[name]
    ens = forest(61, 5, (1, 2500), 3, features=784)
    cut, free = cut_subtrees(ens, 256, spans), cut_subtrees(ens, 256)
    block = ens.feature[cut.tree, cut.origin] // 128    # a slot's K-block
    first, stop = np.array(spans)[cut.lane // 128].T
    assert ((block >= first) & (block < stop)).all() and cut.lane.max() < 256
    packed = 0
    for t, k, pre, depth, exits in entries_of(ens, cut, 256):
        mine, lane = block[pre], cut.lane[pre]
        assert (mine < spans[1][0]).sum() <= 128      # only tile 0 reads
        assert (mine >= spans[0][1]).sum() <= 128     # only tile 1
        # a lane a slot; inside a tile the pre-order, with no gap
        for tile in (0, 1):
            here = lane[lane // 128 == tile]
            assert here.tolist() == list(range(128 * tile,
                                               128 * tile + len(here)))
        packed += int(cut.root[pre].sum() > 1)
    assert packed > 10
    if name == "dense":
        for a, b in zip(cut, free):
            np.testing.assert_array_equal(a, b)
    # (lopsided: six of seven nodes are the second tile's alone, 128 an
    # entry)
    assert free.n_subtrees.sum() <= cut.n_subtrees.sum() \
        <= (1.7 if name == "lopsided" else 1.1) * free.n_subtrees.sum()


def test_the_spans_cost_the_mnist_forests_cut_few_parts(monkeypatch):
    """12 trees of the benchmark's forest (its drawing, its forest seed):
    the rule splits 784 columns at the middle and its cut asks no more than
    1.1 x the entries of the unbounded one (204 and 191, where the trees'
    nodes over 255 a tree are 187; the connected cut of before PR 53 made
    253 and 241)."""
    ens, _ = mnist_forest(12)
    free = cut_subtrees(ens, 256)
    spans, cut = tree.choose_select_spans(ens, 256)
    assert spans == ((0, 3), (3, 7))
    assert free.n_subtrees.sum() < cut.n_subtrees.sum() \
        <= 1.1 * free.n_subtrees.sum()
    assert free.n_subtrees.sum() <= 1.03 * np.ceil(
        (ens.n_leaves - 1) / 255).sum()
    for a, b in zip(cut, cut_subtrees(ens, 256, spans)):
        np.testing.assert_array_equal(a, b)
    # the exits' table is ONE tile either way (30 lanes of pieces, a chain
    # of some 25 sub-trees): 13 tiles a sub-tree where the dense spans ask
    # 20: fewer a tree, for all the parts more
    for parts in (cut.n_subtrees, free.n_subtrees):
        assert tree.exit_table_lanes(10, parts) == (128, 30)
    assert tree.subtree_mxu_tiles(spans, 256, 128) == 13
    assert cut.n_subtrees.sum() * 13 < 0.75 * free.n_subtrees.sum() \
        * tree.subtree_mxu_tiles(SPANS["dense"], 256, 128)
    # ... and fewer than the halves' 18 (the select asked whole in both
    # tiles), so the rule leaves this forest its spans. The tables' SHA-1
    # is PR 53's (the packed cut's: a later change to the cut or the
    # numbering shows)
    import hashlib

    monkeypatch.setattr(tree, "SUBTREE_LANES", 256)
    ce = ens.compile()
    assert not ce.halved and ce.select_spans == spans
    assert cut.copy is None and ce.spine_copies == 0
    h = hashlib.sha1()
    for a in ce.arrays():
        h.update(np.ascontiguousarray(a).view(np.uint8).tobytes())
    assert h.hexdigest()[:16] == "f6facea64c1681bb"


@pytest.mark.parametrize("features,spans,tiles,resolve", [
    (784, ((0, 3), (3, 7)), 7, 4),  # uniform columns: split at the middle
    # four K-blocks: the halves would ask 8; the middle block shared costs
    # a tile of select more than the blocks told apart and, the cut filling
    # its entries, four entries fewer (19 x 11 tiles against 23 x 10: every
    # entry of the split apart must balance 128 nodes a tile to be full)
    (400, ((0, 2), (1, 4)), 5, 4),
    (300, ((0, 1), (1, 3)), 3, 4),  # three: 3 + 4 against the halves' 6 + 2
    # two K-blocks: the spans' 2 + 4 and the halves' 4 + 2 ask alike a
    # sub-tree, and the halves' one bound cuts fewer parts than a bound a
    # lane tile (24 against 25)
    (200, ((0, 2), (0, 2)), 4, 2),
    (129, ((0, 2), (0, 2)), 4, 2),  # one column of 129 past the first block
    (100, ((0, 1), (0, 1)), 2, 2),  # one K-block: the halves, 2 + 2 for 2 + 4
])
def test_the_spans_are_read_from_the_model(monkeypatch, features, spans,
                                           tiles, resolve):
    monkeypatch.setattr(tree, "SUBTREE_LANES", 256)
    ens = forest(62, 4, (600, 1500), 3, features=features)
    ce = ens.compile()
    assert ce.select_spans == spans and ce.halved == (resolve == 2)
    chain = predict_paths.chain_of(4, 3, ce.leaves.shape[2], ce.select_spans,
                                   ce.paths.shape)
    plan = predict_paths.path_plan(ce.n_subtrees, 256, features, chain=chain)
    assert plan.resolve_mxu_tiles == resolve
    assert plan.select_mxu_tiles == tiles == tree.subtree_mxu_tiles(
        spans, 256, 0, ce.halved) - resolve
    # 9 lanes of pieces and a few links: one tile of exits, 2 weight tiles
    assert ce.leaves.shape[2] == 128 and plan.exit_mxu_tiles == 2
    assert plan.path_mxu_tiles_per_tree == round(
        ce.n_subtrees / 4 * tree.subtree_mxu_tiles(spans, 256, 128,
                                                   ce.halved))
    # every non-zero of the select lies in a K-block its lane tile reads
    _, rows, lanes = np.nonzero(ce.sel.astype(np.float32))
    first, stop = np.array(spans)[lanes // 128].T
    assert ((rows // 128 >= first) & (rows // 128 < stop)).all()
    # a lane a node, one a glue copy of its entry's pieces' common
    # ancestors, and (the halves) one more a copy of a spine's slot
    assert len(rows) == ens.n_splits + ce.glue_copies + ce.spine_copies
    assert (ce.spine_copies > 0) == ce.halved and ce.glue_copies > 0


def test_columns_that_crowd_one_block_and_models_with_nothing_to_split(
        monkeypatch):
    """Every node on columns 0..127 of 784: the first lane tile reads that
    block alone and the cut is the unbounded one (no node that only one
    tile may hold). A model of one-leaf trees keeps the dense spans."""
    monkeypatch.setattr(tree, "SUBTREE_LANES", 256)
    ens = forest(63, 3, (600, 900), 2, features=784)
    ens.feature[ens.live_nodes] %= 128
    spans, cut = tree.choose_select_spans(ens, 256)
    assert spans == ens.compile().select_spans == ((0, 1), (0, 7))
    for a, b in zip(cut, cut_subtrees(ens, 256)):
        np.testing.assert_array_equal(a, b)
    lone = forest(64, 3, 1, 2, features=784)
    assert lone.compile().select_spans == ((0, 7), (0, 7))
    # one tile a sub-tree: nothing to tell apart
    monkeypatch.setattr(tree, "SUBTREE_LANES", 128)
    assert forest(63, 3, (600, 900), 2, features=784).compile(
        ).select_spans == ((0, 7),)
    with pytest.raises(ValueError, match="two lane tiles"):
        cut_subtrees(ens, 128, ((0, 3), (3, 7)))
    for gap in (((0, 3), (4, 7)), ((1, 3), (3, 7)), ((0, 3), (3, 6))):
        with pytest.raises(ValueError, match="to no lane tile"):
            cut_subtrees(ens, 256, gap)


def test_three_bfloat16_pieces_hold_a_float32_exactly():
    rng = np.random.default_rng(3)
    v = np.concatenate([rng.standard_normal(5000) * 10.0 ** rng.integers(
        -6, 6, 5000), [0.0, 1.0, 1 / 3, 0.1, 255.0, 1e-30]]).astype(
        np.float32)
    pieces = [p.astype(np.float32) for p in split_bfloat16(v)]
    np.testing.assert_array_equal((pieces[2] + pieces[1]) + pieces[0], v)


def dense_paths(ce):
    """The [S, W, W] path matrices of compiled tables: as they are, or of
    HALVED sub-trees the two diagonal blocks the table holds side by side,
    put back on the diagonal (the off-diagonal blocks are the zeros nobody
    stored)."""
    if not ce.halved:
        return ce.paths
    h = ce.lanes // 2
    dense = np.zeros((ce.n_subtrees, ce.lanes, ce.lanes), ce.paths.dtype)
    dense[:, :h, :h], dense[:, h:, h:] = ce.paths[:, :, :h], ce.paths[:, :, h:]
    return dense


def tables_scores(ce, Xb):
    """The compiled tables' own equations in NumPy (ops/predict.py, "The
    chain"): every sub-tree's contribution, summed."""
    sel, paths, leaves = (a.astype(np.float32) for a in (
        ce.sel, dense_paths(ce), ce.leaves))
    C = ce.leaf_columns
    cl = -(-3 * C // 128) * 128
    # one tile of exits: the links behind the 3 C lanes of pieces, and the
    # sums and the activity 128 lanes each that take the same products
    shared = leaves.shape[2] == 128
    hand = 3 * C if shared else 0
    X = np.pad(Xb.astype(np.float32), ((0, 0), (0, sel.shape[1] - F)))
    acc = np.zeros((len(Xb), cl), np.float32)
    act = np.zeros((len(Xb), 128 if shared else leaves.shape[2] - cl),
                   np.float32)
    for k in range(ce.n_subtrees):
        v = X @ sel[k]
        right = v > ce.planes[k, 0]
        if ce.missing_bin_value >= 0:
            right &= v < ce.planes[k, 3]
        e = (np.where(right, 1.0, -1.0).astype(np.float32) @ paths[k]
             == ce.planes[k, 1]).astype(np.float32)
        assert (e.sum(axis=1) == 1).all()       # one exit a row, always
        if ce.planes[k, 4, 0] > 0:
            act[:], act[:, hand] = 0.0, 1.0
        y, a = e @ leaves[k], act[:, hand:hand + 1].copy()
        acc += a * (y if shared else y[:, :cl])
        act = np.roll(act, -1, axis=1) + a * (y if shared else y[:, cl:])
    return (acc[:, 2 * C:3 * C] + acc[:, C:2 * C] + acc[:, :C]) / ce.n_trees


@pytest.mark.parametrize("columns,missing,exit_lanes", [
    (1, False, 128), (3, True, 128), (10, False, 128),
    (42, True, 128),           # 126 lanes of pieces, chains of 3 at most
    (43, False, 384),          # 129: two class tiles and the activity's
    (85, True, 384)])
def test_the_subtrees_contributions_sum_to_the_uncut_walk(columns, missing,
                                                          exit_lanes):
    ens = forest(4 + columns, 5, (1, 600) if columns != 42 else (1, 300),
                 columns, missing=missing)
    ce = ens.compile()
    assert ce.chained and ce.mean and ce.n_subtrees > ens.n_trees
    assert ce.leaves.shape[1] == ce.lanes == 128
    assert ce.leaves.shape[2] == exit_lanes
    assert ce.deepest_leaf == ens.deepest_leaf
    Xb = rows_of(5, 300)
    np.testing.assert_allclose(
        tables_scores(ce, Xb), predict_proba_node_list(ens, Xb),
        atol=2e-6 * np.abs(ens.leaf_value).max())


# ---------------------------------------------------------------------- #
# kernel (interpreted), twin, reference
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("columns,leaves,missing", [
    (1, (1, 100), False),          # every tree one sub-tree
    (3, (100, 250), True),         # one or two
    (10, (1, 700), False),         # many, chains three deep
    (10, (300, 700), True),
])
def test_kernel_twin_and_reference_agree(columns, leaves, missing):
    ens = forest(10 + columns, 7, leaves, columns, missing=missing)
    Xb = rows_of(11, 333)          # a ragged last sub-tile
    want = predict_proba_node_list(ens, Xb)
    kernel, twin = scored(ens, Xb, "pallas"), scored(ens, Xb, "onehot")
    for got in (kernel, twin):
        assert got.shape == (333, columns) and got.dtype == np.float32
        np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(kernel, twin, atol=3e-7)
    np.testing.assert_allclose(ens.predict_raw(Xb, binned=True), want,
                               atol=1e-6)
    # every (row, tree) reaches the walk's leaf: a forest of one-hot
    # leaves counts them (the sum of 7 trees' one-hots is exact)
    marks = NodeListEnsemble(**{**vars(ens), "leaf_value": np.eye(
        ens.leaf_value.shape[1], dtype=np.float32)[None].repeat(7, 0)})
    hits = scored(marks, Xb, "pallas") * 7
    for t in range(7):
        leaf = leaf_of_rows_node_list(ens, t, Xb)
        assert (hits[np.arange(333), leaf] >= 1).all()
    np.testing.assert_array_equal(hits.sum(axis=1), np.full(333, 7.0))


@pytest.mark.parametrize("columns,exit_lanes", [(3, 128), (10, 128),
                                                (43, 384)])
def test_blocks_that_cut_through_a_tree_and_ragged_row_tiles(
        monkeypatch, columns, exit_lanes):
    """Three sub-trees a block and row tiles of 512: a tree's chain runs
    over several grid steps (the activity is kept in scratch over the block
    axis, under either layout of the exits), the last block holds filler
    entries, the last row tile is ragged."""
    monkeypatch.setattr(predict_paths, "_MAX_TREES_PER_STEP", 3)
    monkeypatch.setattr(predict_paths, "TILE_ROWS", 512)
    # (a seed whose entries' count is no prime: the plan takes no filler)
    ens = forest(21 + 2 * (columns == 3), 8, (1, 500), columns, dyadic=True)
    ce = ens.compile()
    assert ce.leaves.shape[2] == exit_lanes
    plan = predict_paths.path_plan(
        ce.n_subtrees, 128, F, chain=predict_paths.chain_of(
            8, columns, exit_lanes))
    assert plan.trees_per_step in (2, 3) and plan.table_blocks > 4
    Xb = rows_of(22, 1100)
    want = predict_proba_node_list(ens, Xb).astype(np.float32)
    # dyadic leaf values, 8 trees: every sum and the mean are exact
    np.testing.assert_array_equal(scored(ens, Xb, "pallas"), want)
    np.testing.assert_array_equal(scored(ens, Xb, "onehot"), want)


def joined(*models):
    """One ensemble of the models' trees, in their order (a tree of one
    leaf has no node and so no `default_left`: none goes left)."""
    per_tree = {}
    for k, v in vars(models[0]).items():
        if isinstance(v, np.ndarray):
            parts = [getattr(m, k) if getattr(m, k) is not None
                     else np.zeros(m.feature.shape, v.dtype) for m in models]
            tail = np.max([a.shape[1:] for a in parts], axis=0)
            per_tree[k] = np.concatenate([np.pad(
                a, [(0, 0)] + [(0, int(t) - n)
                               for n, t in zip(a.shape[1:], tail)],
                constant_values=-1 if k == "feature" else 0) for a in parts])
    return NodeListEnsemble(**{**vars(models[0]), **per_tree})


def tree_of_parts(rng, parts, **meta):
    """One random tree that `cut_subtrees` cuts into exactly `parts`
    sub-trees of 128 lanes (some hundred leaves a part: drawn again, larger
    or smaller, until the count is met)."""
    leaves = 100 * parts
    for _ in range(200):
        ens = random_node_list(rng, 1, leaves, F, n_bins=BINS, dyadic=True,
                               missing=True, **meta)
        got = int(cut_subtrees(ens, 128).n_subtrees[0])
        if got == parts:
            return ens
        leaves += 50 * (parts - got)
    raise AssertionError(f"no tree of {parts} sub-trees in 200 draws")


@pytest.mark.parametrize("columns,most", [(42, 3), (40, 9), (10, 99),
                                          (1, 126)])
def test_the_one_tile_of_exits_at_the_rules_edge_and_one_past_it(
        monkeypatch, columns, most):
    """THE RULE (models/tree.exit_table_lanes): 3 C + n - 1 <= 128 for the
    most sub-trees n of a tree. AT the edge a link from the tree's first
    sub-tree to its last would lie in lane 127, and what the shift wraps round from the class lanes would be read one
    sub-tree past the tree's last: the longest chain the tile holds, among
    three short trees (whose roots clear what it left), scores the uncut
    walk's leaves bit for bit (dyadic leaf values, learned NaN directions).
    One sub-tree more and the model gets [V | L], the table of before, and
    its scores. One column: a scalar tree cut past PATH_UNCUT_LANES. (Blocks
    of 8 entries: the chain crosses a dozen grid steps, and the interpreted
    kernel's trace is 8 sub-trees long, not 64.)"""
    monkeypatch.setattr(predict_paths, "_MAX_TREES_PER_STEP", 8)
    meta = dict(leaf_columns=columns) if columns > 1 else dict(
        learning_rate=1.0, base_score=0.0, loss="logloss")
    rng = np.random.default_rng(100 + columns)
    small = [random_node_list(rng, 1, n, F, n_bins=BINS, dyadic=True,
                              missing=True, **meta) for n in (150, 1, 40)]
    cl = -(-3 * columns // 128) * 128
    Xb = rows_of(93, 200)
    Xb[::5, ::2] = BINS - 1
    for n, width in ((most, 128), (most + 1, cl + 128)):
        ens = joined(small[0], tree_of_parts(rng, n, **meta), *small[1:])
        ce = ens.compile()
        assert ce.n_subtrees == 2 + n + 2 and ce.leaves.shape[2] == width
        assert tree.exit_table_lanes(columns, np.array([2, n, 1, 1])) == (
            width, 3 * columns if width == 128 else cl)
        want = predict_proba_node_list(ens, Xb) if columns > 1 \
            else ens.predict_raw(Xb, binned=True)
        for impl in ("pallas", "onehot"):   # 4 trees: the mean is exact too
            np.testing.assert_array_equal(scored(ens, Xb, impl),
                                          want.astype(np.float32))


def _one_block(ens):
    """Tree 0's nodes all on columns of K-block 5: sub-trees whose nodes
    only the second lane tile may hold."""
    live = ens.live_nodes[0]
    ens.feature[0, live] = 640 + ens.feature[0, live] % 128
    return ens


@pytest.mark.parametrize("features,missing,scalar,shape,spans", [
    (784, False, False, None, ((0, 3), (3, 7))),       # the spans engage
    (784, True, False, None, ((0, 3), (3, 7))),        # ... under NaN routes
    (784, False, False, _one_block, ((0, 3), (3, 7))),
    (784, True, True, None, ((0, 3), (3, 7))),  # past PATH_UNCUT_LANES
    (400, True, False, None, ((0, 2), (1, 4))),        # a block shared
    (100, False, False, None, ((0, 1), (0, 1))),       # dense
], ids=["784f", "784f-nan", "784f-one-block", "784f-scalar-nan", "400f-nan",
        "100f"])
def test_kernel_twin_and_walk_are_bit_equal_under_the_spans(
        monkeypatch, features, missing, scalar, shape, spans):
    """Sub-trees of two lane tiles, their lanes ordered by K-block: the
    skipped tiles are zeros and every partial product an integer, so the
    reached leaf is the uncut walk's; dyadic leaf values over 8 trees make
    every sum exact."""
    monkeypatch.setattr(tree, "SUBTREE_LANES", 256)
    rng = np.random.default_rng(91)
    meta = dict(learning_rate=0.5, base_score=0.25, loss="logloss") \
        if scalar else dict(leaf_columns=3)
    ens = random_node_list(rng, 8, (520, 900), features, n_bins=BINS,
                           dyadic=True, missing=missing, **meta)
    ens = shape(ens) if shape else ens
    ce = ens.compile()
    assert ce.chained and ce.select_spans == spans and ce.lanes == 256
    # one K-block: the halves (PR 51), whose select is dense
    assert ce.halved == (features == 100) and ce.halved == (
        ce.spine_copies > 0)
    Xb = rows_of(92, 600, features)
    Xb[::7, ::3] = BINS - 1        # rows that sit in the NaN bin
    want = ens.predict_raw(Xb, binned=True) if scalar else \
        predict_proba_node_list(ens, Xb)
    for impl in ("pallas", "onehot"):
        np.testing.assert_array_equal(scored(ens, Xb, impl),
                                      want.astype(np.float32))


@pytest.mark.parametrize("lanes,features,columns,missing,digest,halves", [
    (128, 100, 3, False, "7fe203fa6815716f", None),
    (128, 12, 10, True, "8a0c9f93d4d23817", None),
    (128, 784, 3, False, "b13cdd8e3f863c7f", None),   # one tile: no span
    (256, 100, 3, False, "ea266811894a765a", "422f9df3445fea59"),
    (256, 12, 10, True, "3a80e9b8c48eb1c5", "89574fa661e21424"),
    # scalar leaves: softmax's round-major trees, 3 classes
    (256, 28, 0, False, "8b9493e4ac8fec69", "021c07c233c444e3"),
    # 129 and 255 lanes of pieces, which no chain shares a tile with
    (128, 12, 43, False, "4b6bd2919d86bedb", None),
    (256, 12, 85, True, "7856a4876b0d7220", "5bb678aaa009461d"),
])
def test_dense_spans_build_the_parents_tables_bit_for_bit(
        monkeypatch, lanes, features, columns, missing, digest, halves):
    """A tree that fits one entry is ONE piece and builds the tables it
    built before the cut packed its entries (PR 53): SHA-1s of the four
    tables as the parent commit (bfa48fe) built them, for seeded models
    whose every tree fits an entry, numbered in pre-order under dense spans
    (`digest`) and, two lane tiles over one K-block, as the HALVES the rule
    gives them (`halves`). (Until PR 53 this test held models of cut trees
    to the commit before the spans, da8e8d4: a cut tree's entries are no
    longer what they were, `entries_of` says what they are.)"""
    import hashlib

    def sha(ce):
        h = hashlib.sha1()
        for a in ce.arrays():
            h.update(np.ascontiguousarray(a).view(np.uint8).tobytes())
        return h.hexdigest()[:16]

    monkeypatch.setattr(tree, "SUBTREE_LANES", lanes)
    rng = np.random.default_rng(81)
    ens = random_node_list(rng, 5, (1, 3 * lanes // 4), features,
                           n_bins=BINS, missing=missing,
                           leaf_columns=columns) \
        if columns else random_node_list(
            rng, 6, (1, 3 * lanes // 4), features, n_bins=BINS,
            learning_rate=0.1, base_score=0.5, loss="softmax", n_classes=3)
    ce = ens.compile()
    assert ce.halved == (lanes == 256)
    if ce.halved:
        # (two lane tiles over one K-block: the rule takes the halves since
        # PR 51. The dense layout is still the builder's to build, for the
        # models the rule leaves it: handed to it here.)
        assert sha(ce) == halves
        monkeypatch.setattr(
            tree, "choose_select_spans", lambda ens, lanes:
            (tree.dense_spans(features, lanes), cut_subtrees(ens, lanes)))
        ce = ens.compile()
    assert not ce.halved
    assert ce.select_spans == tree.dense_spans(features, lanes)
    assert ce.n_subtrees == ce.pieces == ens.n_trees and not ce.glue_copies
    assert (ce.leaves.shape[2] == 128) == (columns not in (43, 85))
    assert sha(ce) == digest


def test_a_tall_scalar_tree_is_cut_and_keeps_its_margin(monkeypatch):
    """One output column and no mean: a tree of more lanes than one path
    matrix should hold takes the sub-tree form and answers the margin [R]
    (base + learning_rate x sum); a tree within the bound keeps today's
    uncut form."""
    rng = np.random.default_rng(31)
    meta = dict(learning_rate=0.1, base_score=0.5, loss="logloss")
    tall = random_node_list(rng, 3, 700, F, n_bins=BINS, **meta)
    short = random_node_list(rng, 3, 500, F, n_bins=BINS, **meta)
    assert tall.compile().chained and not tall.compile().mean
    assert not short.compile().chained
    assert short.compile().lanes == tree.PATH_UNCUT_LANES == 512
    Xb = rows_of(32, 200)
    for impl in ("pallas", "onehot"):
        got = scored(tall, Xb, impl)
        assert got.shape == (200,)
        np.testing.assert_allclose(got, tall.predict_raw(Xb, binned=True),
                                   atol=2e-6)


def test_the_plan_says_what_serves_and_the_rule_refuses_what_cannot_build():
    chain = predict_paths.chain_of(100, 10, 256)
    assert chain == predict_paths.Chain(100, 10, 128, 128)
    plan = predict_paths.path_plan(2015, 256, 784, 26, chain=chain,
                                   widest_tree=4864)
    # the MNIST forest's shape under dense spans: 7 K-blocks x 2 + 4 + 2 + 2
    # tiles a sub-tree
    assert predict_paths.path_mxu_tiles_per_tree(256, 784, 1, 256) == 22
    assert plan.select_mxu_tiles == 14
    assert (plan.path_mxu_tiles_per_tree, plan.subtrees_per_tree,
            plan.subtree_lanes, plan.leaf_columns, plan.class_dot_passes,
            plan.chain_mxu_tiles_per_tree, plan.select_k_blocks,
            plan.nodes_per_tree) == (443, 20.15, 256, 10, 3, 40, 7, 4864)
    assert plan.trees_per_step >= 4 and plan.table_blocks > 1
    assert plan.table_bytes == plan.trees_per_step * plan.table_blocks * (
        784 * 256 * 2 + 8 * 256 * 4 + 256 * 256 * 2 + 256 * 256 * 2)
    assert plan.exit_mxu_tiles == 4
    assert set(predict_paths.CHAIN_COUNTS) <= set(plan.root_counts())
    # ... and with the exits' table the forest gets, ONE tile: 13 a sub-tree
    # under its spans, no tile of the chain's own, an entry 64 KB lighter
    one = predict_paths.chain_of(100, 10, 128, ((0, 3), (3, 7)))
    assert one == predict_paths.Chain(100, 10, 128, 128, ((0, 3), (3, 7)),
                                      True)
    assert (chain.exit_lanes, chain.at_hand, one.exit_lanes, one.at_hand) \
        == (256, 0, 128, 30)
    shared = predict_paths.path_plan(2112, 256, 784, 26, chain=one,
                                     widest_tree=4864)
    assert (shared.path_mxu_tiles_per_tree, shared.exit_mxu_tiles,
            shared.chain_mxu_tiles_per_tree, shared.select_mxu_tiles,
            shared.class_dot_passes) == (275, 2, 0, 7, 3)
    assert shared.table_bytes == shared.trees_per_step \
        * shared.table_blocks * (784 * 256 * 2 + 8 * 256 * 4
                                 + 256 * 256 * 2 + 256 * 128 * 2)
    assert shared.trees_per_step >= plan.trees_per_step
    with pytest.raises(ValueError, match="holds no chain"):
        predict_paths.chain_of(1, 85, 256)
    # one column and the widest leaf the rule takes; 128 columns are three
    # class tiles whose output windows do not fit beside a row tile: the
    # guard says so and `auto` takes the jax.numpy form
    assert predict_paths.predict_paths_fits(
        256, 28, chain=predict_paths.chain_of(1, 85, 256 + 128))
    assert not predict_paths.predict_paths_fits(
        256, 28, chain=predict_paths.chain_of(1, 128, 384 + 128))
    # an uncut model's plan says a tree is a sub-tree of its own
    flat = predict_paths.path_plan(500, 256, 28)
    assert (flat.subtrees_per_tree, flat.subtree_lanes, flat.leaf_columns,
            flat.chain_mxu_tiles_per_tree, flat.class_dot_passes,
            flat.exit_mxu_tiles) == (1.0, 256, 1, 0, 0, 0)


@pytest.mark.parametrize("exit_lanes", [128, 256])
@pytest.mark.parametrize("spans,entries,select,tiles,per_tree", [
    (((0, 3), (3, 7)), 2112, 7, 15, 317),      # what the forest's build finds
    (((0, 4), (3, 7)), 2043, 8, 16, 327),
    (((0, 4), (2, 7)), 2016, 9, 17, 343),
    ((), 2015, 14, 22, 443),                   # dense, as before
])
def test_the_plan_counts_the_select_by_its_spans(spans, entries, select,
                                                 tiles, per_tree, exit_lanes):
    """(`tiles`, `per_tree`: with [V | L], 256 lanes of exits; ONE tile of
    them asks two weight tiles a sub-tree fewer.)"""
    chain = predict_paths.chain_of(100, 10, exit_lanes, spans)
    if exit_lanes == 128:
        tiles, per_tree = tiles - 2, round(entries / 100 * (tiles - 2))
        assert per_tree == 275 or entries != 2112
    assert predict_paths.path_mxu_tiles_per_tree(
        256, 784, 1, exit_lanes, chain.select_spans) == tiles
    for served in (True, False):
        plan = predict_paths.path_plan(entries, 256, 784, 26, chain=chain,
                                       served=served, widest_tree=4864)
        assert (plan.select_mxu_tiles, plan.path_mxu_tiles_per_tree,
                plan.select_k_blocks) == (select, per_tree, 7)
    assert 13 <= tiles <= 17 and 7 <= select <= 9 or not spans
    # the tables stay whole in HBM: the bytes of an entry do not move
    assert plan.table_bytes == 0 and predict_paths.path_plan(
        entries, 256, 784, chain=chain).table_bytes % (
            784 * 256 * 2 + 8 * 256 * 4 + 256 * 256 * 2
            + 256 * exit_lanes * 2) == 0
    # what the uncut models said, they say: Higgs's packed select (one
    # K-block, two nodes a lane), Bosch's 8 K-blocks x 2 lane tiles
    for features, said in ((28, (1, 5)), (968, (16, 20))):
        flat = predict_paths.path_plan(500, 256, features)
        assert (flat.select_mxu_tiles, flat.path_mxu_tiles_per_tree) == said
        assert predict_paths.path_mxu_tiles_per_tree(256, features) == said[1]
    # a chained model of one K-block whose select packs two nodes a lane
    # counts the packed select, whatever its (dense) spans say
    packed = predict_paths.path_plan(40, 256, 28, chain=predict_paths.chain_of(
        8, 3, 256, ((0, 1), (0, 1))))
    assert (packed.select_nodes_per_lane, packed.select_mxu_tiles) == (2, 1)


@pytest.mark.parametrize("columns,exit_tiles", [(10, 1), (85, 3)])
def test_the_spans_say_the_subtree_form(columns, exit_tiles):
    from ddt_tpu.backends import get_backend
    from ddt_tpu.telemetry import annotations as an

    ens = forest(41, 4, (300, 600), columns)
    be = get_backend(TrainConfig(backend="tpu", n_bins=BINS,
                                 predict_impl="pallas"))
    out = be.predict_raw(ens, rows_of(42, 50))
    assert out.shape == (50, columns)
    root = an.root_spans("predict")[-1]
    built = [s for s in root["spans"]
             if s["name"] == "ddt:predict:ensemble"][0]["counts"]
    assert list(built)[-len(predict_paths.CHAIN_COUNTS):] == list(
        predict_paths.CHAIN_COUNTS)
    assert built["select_mxu_tiles"] == 1        # one tile, one K-block
    assert built["subtrees_per_tree"] > 1 and built["subtree_lanes"] == 128
    assert built["leaf_columns"] == root["counts"]["classes"] == columns
    assert built["class_dot_passes"] == 3
    # the exits' table a sub-tree of ONE lane tile: one weight tile where
    # the links share the pieces' (and then no tile is the chain's own),
    # 85 columns' two class tiles and the activity's
    assert built["exit_mxu_tiles"] == exit_tiles
    assert built["chain_mxu_tiles_per_tree"] == (
        0 if exit_tiles == 1 else round(built["subtrees_per_tree"]))
    for k in predict_paths.CHAIN_COUNTS + ("node_list",
                                           "path_mxu_tiles_per_tree"):
        assert root["counts"][k] == built[k]


# ---------------------------------------------------------------------- #
# scikit-learn's own forests
# ---------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def digits():
    from sklearn.datasets import load_digits

    X, y = load_digits(return_X_y=True)
    return X.astype(np.float32), y


def test_a_sklearn_classifier_agrees_with_its_own_predict_proba(digits):
    from sklearn.ensemble import RandomForestClassifier

    from ddt_tpu.models.lightgbm_io import threshold_bin_mapper
    from ddt_tpu.models.sklearn_io import from_sklearn

    X, y = digits
    rf = RandomForestClassifier(n_estimators=12, random_state=0).fit(
        X[:1200], y[:1200])
    ens = from_sklearn(rf)
    assert ens.loss == "mean" and ens.leaf_value.shape[2] == 10
    assert ens.n_trees == 12 and ens.n_features == 64
    assert not ens.has_bin_thresholds
    assert int(ens.n_leaves.max()) > 128         # cut, at one tile a sub-tree
    want = rf.predict_proba(X)
    np.testing.assert_allclose(ens.predict_raw(X), want, atol=1e-6)
    with pytest.raises(ValueError, match="raw thresholds only"):
        ens.compile()
    mapper = threshold_bin_mapper(ens, n_bins=256)
    for impl in ("pallas", "onehot"):
        proba = api.predict(ens, X, mapper=mapper, cfg=TrainConfig(
            backend="tpu", predict_impl=impl, n_bins=256))
        assert proba.shape == (len(X), 10) and proba.dtype == np.float32
        np.testing.assert_allclose(proba, want, atol=1e-6)
        np.testing.assert_array_equal(rf.classes_[proba.argmax(axis=1)],
                                      rf.predict(X))
    np.testing.assert_allclose(
        predict_proba_node_list(ens, mapper.transform(X)), want, atol=1e-6)


def test_a_sklearn_regressor_is_one_column_and_a_single_tree_a_forest(
        digits):
    from sklearn.ensemble import RandomForestRegressor
    from sklearn.tree import DecisionTreeClassifier

    from ddt_tpu.models.lightgbm_io import threshold_bin_mapper
    from ddt_tpu.models.sklearn_io import from_sklearn

    X, y = digits
    rr = RandomForestRegressor(n_estimators=5, random_state=1).fit(
        X[:800], y[:800])
    ens = from_sklearn(rr)
    assert ens.leaf_value.shape[2] == 1 and ens.n_classes == 1
    mapper = threshold_bin_mapper(ens, n_bins=256)
    out = api.predict(ens, X, mapper=mapper, cfg=TrainConfig(
        backend="tpu", predict_impl="pallas", n_bins=256))
    assert out.shape == (len(X), 1)
    np.testing.assert_allclose(out[:, 0], rr.predict(X), rtol=1e-6)
    one = from_sklearn(DecisionTreeClassifier(max_depth=4).fit(X, y))
    assert one.n_trees == 1 and one.leaf_value.shape[2] == 10

    class TwoOutputs:
        estimators_, n_outputs_, n_features_in_ = rr.estimators_, 2, 64

    with pytest.raises(ValueError, match="multi-output forest"):
        from_sklearn(TwoOutputs())
    with pytest.raises(ValueError, match="not a fitted scikit-learn"):
        from_sklearn(RandomForestRegressor())


def test_thresholds_round_down_to_float32():
    from ddt_tpu.models.sklearn_io import _float32_below

    t = np.array([0.5, 1 / 3, 0.1, 1e-8 + 1.0, -2 / 3, 16777217.0])
    below = _float32_below(t)
    assert below.dtype == np.float32 and (below.astype(np.float64) <= t).all()
    assert (np.nextafter(below, np.float32(np.inf)).astype(np.float64)
            > t).all()


# ---------------------------------------------------------------------- #
# round trips and refusals
# ---------------------------------------------------------------------- #

def test_round_trips_keep_the_vectors(tmp_path):
    ens = forest(51, 3, (1, 60), 4)
    back = ensemble_from_dict(ens.to_dict())
    assert isinstance(back, NodeListEnsemble) and back.loss == "mean"
    np.testing.assert_array_equal(back.leaf_value, ens.leaf_value)
    assert back.cache_token() == ens.cache_token()
    path = str(tmp_path / "forest.npz")
    ens.save(path)
    loaded = tree.TreeEnsemble.load(path)
    assert loaded.cache_token() == ens.cache_token()
    Xb = rows_of(52, 40)
    np.testing.assert_array_equal(loaded.predict(Xb, binned=True),
                                  ens.predict_raw(Xb, binned=True))
    # the token follows one entry of one leaf's vector, and the columns
    token = ens.cache_token()
    ens.leaf_value[1, 0, 2] += 1.0
    assert ens.cache_token() != token
    # the same bytes as [T, L, 4] and as [T, 2 L, 2] are two models
    flat = forest(53, 2, 9, 4)
    folded = NodeListEnsemble(**{**vars(flat), "n_classes": 2,
                                 "leaf_value": flat.leaf_value.reshape(
                                     2, 18, 2)})
    assert folded.cache_token() != flat.cache_token()
    assert "leaf=[" in ens.dump_text(0)


def test_cli_inspect_and_predict_read_a_saved_forest(tmp_path, capsys,
                                                     digits):
    from sklearn.ensemble import RandomForestClassifier

    from ddt_tpu.models.lightgbm_io import threshold_bin_mapper
    from ddt_tpu.models.sklearn_io import from_sklearn

    X, y = digits
    rf = RandomForestClassifier(n_estimators=4, random_state=2).fit(
        X[:600], y[:600])
    ens = from_sklearn(rf)
    mapper = threshold_bin_mapper(ens, n_bins=256)
    model, out = str(tmp_path / "f.npz"), str(tmp_path / "p.npy")
    api.save_model(model, ens, mapper)
    assert cli.main(["inspect", "--model", model, "--tree", "0"]) == 0
    said = capsys.readouterr().out
    head = json.loads(said.splitlines()[0])
    assert head["loss"] == "mean" and head["n_trees"] == 4
    assert head["max_depth"] == ens.deepest_leaf and head["n_classes"] == 10
    assert "leaf=[" in said
    data = str(tmp_path / "x.npz")
    np.savez(data, X=X[:200], y=y[:200])
    assert cli.main(["predict", "--model", model, "--data", data, "--out",
                     out, "--backend", "tpu"]) == 0
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert said["phases_ms"]["node_list"] == 1
    assert said["phases_ms"]["leaf_columns"] == 10
    got = np.load(out)
    np.testing.assert_allclose(got, rf.predict_proba(X[:200]), atol=1e-6)
    np.testing.assert_array_equal(rf.classes_[got.argmax(axis=1)],
                                  rf.predict(X[:200]))


def test_what_stays_refused_is_refused_by_name():
    ens = forest(71, 2, 9, 3)
    parts = vars(ens)
    with pytest.raises(ValueError, match="averaged forest"):
        NodeListEnsemble(**{**parts, "loss": "mse"})
    with pytest.raises(ValueError, match="averaged forest"):
        NodeListEnsemble(**{**parts, "learning_rate": 0.1})
    with pytest.raises(ValueError, match="averaged forest"):
        NodeListEnsemble(**{**parts, "leaf_value": ens.leaf_value[:, :, 0]})
    with pytest.raises(ValueError, match="softmax"):
        NodeListEnsemble(**{**parts, "loss": "softmax"})
    with pytest.raises(ValueError, match="vector leaves"):
        ens.to_lightgbm_text()
    # what the sub-tree form still refuses, by name: category sets (PR 55
    # serves them in trees one path matrix holds)
    tree._refuse_routes("from_heap")
    with pytest.raises(ValueError, match="SUB-TREE form"):
        tree._refuse_routes("build", chained_sets=True)
    cats = tree.random_node_list(
        np.random.default_rng(3), 2, (600, 700), 6, categories=((1, 9),),
        learning_rate=0.1, base_score=0.0, loss="logloss")
    with pytest.raises(ValueError, match="category-set nodes in the SUB-TREE"):
        cats.compile()
    with pytest.raises(ValueError, match="binned"):
        api.predict(ens, rows_of(72, 8).astype(np.float32), cfg=TrainConfig(
            backend="tpu", n_bins=BINS))


# ---------------------------------------------------------------------- #
# the halves: a sub-tree numbered as two halves that share their spine
# ---------------------------------------------------------------------- #

# leaves a tree: tens of nodes, one leaf, thousands, and the halves' edges
RAGGED = (30, 1, 2600, 90, 700, 128, 129, 255, 256, 257, 400, 1, 1500, 60)


def ragged(seed, features=54, missing=False):
    """Softmax's round-major trees, two rounds of 7 classes, ragged."""
    rng = np.random.default_rng(seed)
    return joined(*[random_node_list(
        rng, 1, n, features, n_bins=BINS, dyadic=True, missing=missing,
        learning_rate=0.5, base_score=0.25, loss="softmax", n_classes=7)
        for n in RAGGED])


@pytest.mark.parametrize("seed,missing", [(71, False), (72, True),
                                          (73, False)])
def test_halved_subtrees_are_two_halves_that_share_their_spine(seed, missing):
    """Still entries of glued pieces, parents first (`entries_of`); every
    entry admits its k: the first half the first k slots of the glued
    tree's pre-order in lanes 0.., the second half copies of slot k's
    ancestors in the entry (top down, from lane 128; a glue copy is copied
    like a node) and then the later slots in pre-order; both halves within
    128 lanes of slots and of exits; a second half's slot has no ancestor in
    the first half but a copied one; an entry of under 128 slots is one
    half."""
    ens = ragged(seed, missing=missing)
    cut, free = cut_subtrees(ens, 256, halved=True), cut_subtrees(ens, 256)
    assert cut.copy is not None and free.copy is None
    second_halves = copies = packed = 0
    for t, k, pre, depth, exits in entries_of(ens, cut, 256):
        n = len(pre)
        # the cut's bound: the slots and the longest path's together
        assert n + max(depth.values()) + 1 <= 255
        split = int((cut.lane[pre] < 128).sum())
        first, second = pre[:split], pre[split:]
        assert 1 <= split <= 128
        assert cut.lane[first].tolist() == list(range(split))
        spine = []
        if second:
            a = second[0]
            while cut.up[a] >= 0:
                a = int(cut.up[a])
                spine.insert(0, a)
            second_halves += 1
        assert cut.copy[spine].tolist() == list(range(128, 128 + len(spine)))
        assert {s for s in pre if cut.copy[s]} == set(spine) <= set(first)
        assert cut.lane[second].tolist() == list(range(
            128 + len(spine), 128 + len(spine) + len(second)))
        assert 128 + len(spine) + len(second) <= 256
        for x in second:
            a = x
            while cut.up[a] >= 0:
                a = int(cut.up[a])
                assert cut.lane[a] >= 128 or cut.copy[a] > 0
        assert sum(exits[s] for s in first) <= 128 \
            >= sum(exits[s] for s in second)
        if n < 128:
            assert not second
        copies += len(spine)
        packed += int(cut.root[pre].sum() > 1)
    assert second_halves > 10 and copies == np.count_nonzero(cut.copy)
    assert packed > 10
    # what the halves' bound costs the cut
    assert free.n_subtrees.sum() <= cut.n_subtrees.sum() \
        <= 1.2 * free.n_subtrees.sum()
    with pytest.raises(ValueError, match="two lane tiles under dense"):
        cut_subtrees(ens, 128, halved=True)


def hung_slots(cut, n_nodes):
    """{(tree, entry): the entry's top slot} and {(a glue copy's slot,
    side): the slot that hangs there}."""
    tops = {(int(cut.tree[s]), int(cut.subtree[s])): int(s)
            for s in np.nonzero(cut.up < 0)[0]}
    below = {(int(cut.up[s]), int(cut.side[s])): int(s)
             for s in np.nonzero(cut.up >= n_nodes)[0]}
    return tops, below


def walked_exit(ens, cut, t, k, x, hung):
    """Where row x leaves entry k of tree t by the node walk from the
    entry's top (a glue copy asks its node's question and hands the row to
    the slot on that side): (the leaf, or None; the entry it goes on in, or
    None)."""
    tops, below = hung
    base = first_node(ens)[t]
    s = tops[t, k]
    while True:
        n = int(cut.origin[s])
        b = int(x[ens.feature[t, n]])
        left = b <= ens.threshold_bin[t, n]
        if ens.missing_routes and b == ens.n_bins - 1:
            left = bool(ens.default_left[t, n])
        if s >= ens.n_splits:
            s = below[s, -1 if left else 1]
            continue
        c = int(ens.left_child[t, n] if left else ens.right_child[t, n])
        if c < 0:
            return ~c, None
        if cut.subtree[base + c] != k:
            return None, int(cut.subtree[base + c])
        s = base + c


def entries_exits(ens, ce, cut, Xb):
    """Every (row, entry) of the compiled tables leaves the entry by the
    exit the node walk from its top takes: a real leaf's value in its
    columns' lanes (a softmax tree's in its class's alone), or the link to
    the entry that holds the child. The tables' own equations in NumPy,
    an entry at a time, whether or not the row is active there."""
    C, features = ce.leaf_columns, ens.n_features
    sel, paths, leaves = (a.astype(np.float32) for a in (
        ce.sel, dense_paths(ce), ce.leaves))
    link0 = tree.exit_table_lanes(C, cut.n_subtrees)[1]
    first = np.concatenate([[0], np.cumsum(cut.n_subtrees)])
    hung = hung_slots(cut, ens.n_splits)
    X = np.pad(Xb.astype(np.float32), ((0, 0), (0, sel.shape[1] - features)))
    for t in range(ens.n_trees):
        for k in range(cut.n_subtrees[t]):
            g = first[t] + k
            v = X @ sel[g]
            right = v > ce.planes[g, 0]
            if ens.missing_routes:
                right &= v < ce.planes[g, 3]
            e = np.where(right, 1.0, -1.0).astype(np.float32) @ paths[g] \
                == ce.planes[g, 1]
            assert (e.sum(axis=1) == 1).all()
            y = e.astype(np.float32) @ leaves[g]
            value = y[:, 2 * C:3 * C] + y[:, C:2 * C] + y[:, :C]
            for r in range(len(Xb)):
                leaf, to = (0, None) if ens.n_leaves[t] == 1 else \
                    walked_exit(ens, cut, t, k, Xb[r], hung)
                want = np.zeros(C, np.float32)
                link = np.zeros(leaves.shape[2] - link0, np.float32)
                if to is not None:
                    link[to - k - 1] = 1.0
                elif ens.vector_leaves:
                    want[:] = ens.leaf_value[t, leaf]
                else:
                    want[t % C] = ens.leaf_value[t, leaf]
                np.testing.assert_array_equal(value[r], want)
                np.testing.assert_array_equal(y[r, link0:], link)


@pytest.mark.parametrize("features,missing,packed,digest", [
    (54, False, 2, "78dca5a2a5336ccb"),  # the packed select over the halves
    (9, True, 2, "f6b521df954703e3"),    # ... with NaN routes
    (100, True, 1, "3e9d4fbdc8392c25"),  # the unpacked select
])
def test_halved_tables_resolve_every_exit_in_its_own_half(
        monkeypatch, features, missing, packed, digest):
    """The tables of a model whose sub-trees are halved: `paths` the two
    diagonal blocks alone; a copy's column, threshold and NaN bound its
    node's; every (row, sub-tree) leaves the sub-tree by the exit the node
    walk from its root takes, a real leaf's value in its class's lanes or
    the link to the part that hangs there; kernel (interpreted), twin and
    walk bit-equal, the link's answers to float32 rounding; the spans say
    `resolve_mxu_tiles` 2 and the copies. The tables' SHA-1 is PR 53's
    (the packed cut's; PR 51's before it): a later change to the cut or
    the numbering shows."""
    import hashlib

    from ddt_tpu.backends import get_backend
    from ddt_tpu.telemetry import annotations as an

    monkeypatch.setattr(tree, "SUBTREE_LANES", 256)
    ens = ragged(75 + features, features, missing)
    ce = ens.compile()
    spans, cut = tree.choose_select_spans(ens, 256)
    assert spans == ce.select_spans == tree.dense_spans(features, 256)
    for a, b in zip(cut, cut_subtrees(ens, 256, halved=True)):
        np.testing.assert_array_equal(a, b)
    S, C = ce.n_subtrees, 7
    assert ce.halved and ce.paths.shape == (S, 128, 256)
    assert ce.leaves.shape == (S, 256, 128) and ce.planes.shape == (S, 8, 256)
    h = hashlib.sha1()
    for a in ce.arrays():
        h.update(np.ascontiguousarray(a).view(np.uint8).tobytes())
    assert h.hexdigest()[:16] == digest
    first = np.concatenate([[0], np.cumsum(cut.n_subtrees)])
    # a spine copy asks its slot's question and hangs no exit
    s = np.nonzero(cut.copy)[0]
    t, n = cut.tree[s], cut.origin[s]
    entry, own, cp = first[t] + cut.subtree[s], cut.lane[s], cut.copy[s]
    assert len(s) == ce.spine_copies > 0
    sel = ce.sel.astype(np.float32)
    np.testing.assert_array_equal(sel[entry, :, cp], sel[entry, :, own])
    np.testing.assert_array_equal(sel[entry, ens.feature[t, n], cp], 1.0)
    for row in (0, 3):
        np.testing.assert_array_equal(ce.planes[entry, row, cp],
                                      ce.planes[entry, row, own])
    np.testing.assert_array_equal(ce.planes[entry, 0, cp],
                                  ens.threshold_bin[t, n])
    # ... and so does a glue copy, its node's own lane in an earlier entry
    glue = np.arange(ens.n_splits, len(cut.tree))
    assert len(glue) == ce.glue_copies > 0
    np.testing.assert_array_equal(
        ce.planes[first[cut.tree[glue]] + cut.subtree[glue], 0,
                  cut.lane[glue]],
        ens.threshold_bin[cut.tree[glue], cut.origin[glue]])
    # every node lane is one slot's or one copy's, the others ask nothing
    assert (sel.sum(axis=1) <= 1).all()
    assert sel.sum() == len(cut.tree) + len(s)
    # the exit every (row, entry) takes is the node walk's
    Xb = rows_of(76, 40, features)
    Xb[::5, ::2] = BINS - 1
    paths = dense_paths(ce).astype(np.float32)
    assert not paths[:, :128, 128:].any() and not paths[:, 128:, :128].any()
    entries_exits(ens, ce, cut, Xb)
    # kernel, twin, walk
    Xb = rows_of(77, 700, features)
    Xb[::7, ::3] = BINS - 1
    want = ens.predict_raw(Xb, binned=True)
    for impl in ("pallas", "onehot"):
        np.testing.assert_array_equal(scored(ens, Xb, impl), want)
    be = get_backend(TrainConfig(backend="tpu", predict_impl="pallas",
                                 n_bins=BINS))
    proba = be.predict_raw(ens, Xb, link=True)
    z = want.astype(np.float64)
    z = np.exp(z - z.max(axis=1, keepdims=True))
    np.testing.assert_allclose(proba, z / z.sum(axis=1, keepdims=True),
                               atol=1e-6)
    root = an.root_spans("predict")[-1]
    built = [s for s in root["spans"]
             if s["name"] == "ddt:predict:ensemble"][0]["counts"]
    for counts in (root["counts"], built):
        assert counts["resolve_mxu_tiles"] == 2
        assert counts["spine_copies_per_subtree"] == round(
            ce.spine_copies / S, 2) > 0
        assert counts["select_nodes_per_lane"] == packed
        assert counts["path_mxu_tiles_per_tree"] == round(
            S / ens.n_trees * (3 - packed + 2 + 2))


def test_the_rule_takes_the_halves_for_one_k_block_and_keeps_the_forests_spans(
        monkeypatch):
    """`choose_select_spans` from the model alone: halved sub-trees at 54
    and at 100 columns (2 tiles of resolve where 4, for a few more parts),
    the K-block spans for a forest over 784 columns, whose select the halves
    would ask whole (14 + 2 + 2 against 7 + 4 + 2); spans that bound a tile
    take no halves beside them; an uncut model says 4 and no copy."""
    monkeypatch.setattr(tree, "SUBTREE_LANES", 256)
    for features in (54, 100):
        ens = ragged(80, features)
        spans, cut = tree.choose_select_spans(ens, 256)
        assert spans == tree.dense_spans(features, 256)
        assert cut.copy is not None
        free = cut_subtrees(ens, 256)
        assert cut.n_subtrees.sum() * tree.subtree_mxu_tiles(
            spans, 256, 128, True) < free.n_subtrees.sum() \
            * tree.subtree_mxu_tiles(spans, 256, 128)
        assert (tree.subtree_mxu_tiles(spans, 256, 128, True),
                tree.subtree_mxu_tiles(spans, 256, 128)) == (6, 8)
    wide = forest(62, 4, (600, 1500), 3, features=784)
    spans, cut = tree.choose_select_spans(wide, 256)
    assert spans == ((0, 3), (3, 7)) and cut.copy is None
    assert not wide.compile().halved
    assert tree.subtree_mxu_tiles(tree.dense_spans(784, 256), 256, 128,
                                  True) == 18
    with pytest.raises(ValueError, match="two lane tiles under dense"):
        cut_subtrees(wide, 256, ((0, 3), (3, 7)), halved=True)
    flat = predict_paths.path_plan(500, 256, 28)
    assert (flat.resolve_mxu_tiles, flat.spine_copies_per_subtree) == (4, 0.0)


# ---------------------------------------------------------------------- #
# fuller entries (PR 53): several pieces of a tree in one entry, glued by
# copies of their common ancestors
# ---------------------------------------------------------------------- #

def one_piece_an_entry(monkeypatch, spans, halved):
    """The CONNECTED cut, the case "one piece an entry" of the same code:
    the packer replaced by one that gives an entry ONE top piece of a
    frontier sub-tree (half the lanes at most, which holds a bound a lane
    tile and the halves' bound too) and never a second one, and the rule by
    the spans the packed cut's model was given."""
    def filled(flat, lanes, only, halved):
        cap, pieces = (lanes - 1) // 2, []
        for t in np.nonzero(np.diff(flat.first))[0]:
            frontier, entry = [int(flat.first[t])], 0
            while frontier:
                q = frontier.pop(0)
                end = x = q + min(cap, int(flat.size[q]))
                pieces.append((q, end - q, entry))
                entry += 1
                while x > q:            # up from the piece's last node
                    x = end - 1 if x == end else int(flat.up[x])
                    frontier += [int(c) for c in flat.kids[x] if c >= end]
        return tuple(np.array(a, np.int64) for a in zip(*pieces)) \
            if pieces else (np.zeros(0, np.int64),) * 3

    monkeypatch.setattr(tree, "_filled", filled)
    monkeypatch.setattr(tree, "choose_select_spans", lambda ens, lanes: (
        spans, cut_subtrees(ens, lanes, spans, halved)))


def fresh_scores(ens, Xb, impl):
    """Scored by a backend of its own: the model's tables are cached by the
    model's digest, and the same model is compiled under two cuts here."""
    from ddt_tpu.backends import get_backend

    return get_backend(TrainConfig(backend="tpu", predict_impl=impl,
                                   n_bins=BINS), use_cache=False
                       ).predict_raw(ens, Xb)


PACKED = {
    # softmax's round-major trees, halved, NaN routes
    "halved-softmax-nan": dict(lanes=256, features=54, missing=True,
                               meta=dict(learning_rate=0.5, base_score=0.25,
                                         loss="softmax", n_classes=7)),
    # vector leaves under the K-block spans, NaN routes
    "spans-vector-nan": dict(lanes=256, features=784, missing=True,
                             meta=dict(leaf_columns=3)),
    # one lane tile an entry, blocks of three entries cut through the trees
    "blocks-vector": dict(lanes=128, features=F, missing=False, step=3,
                          meta=dict(leaf_columns=10)),
    # [V | L]: the links in lane tiles of their own
    "wide-vector-nan": dict(lanes=128, features=F, missing=True,
                            meta=dict(leaf_columns=43)),
    # one column: a scalar tree past PATH_UNCUT_LANES
    "scalar": dict(lanes=256, features=100, missing=False,
                   meta=dict(learning_rate=0.5, base_score=0.25,
                             loss="logloss")),
}


@pytest.mark.parametrize("dyadic", [True, False], ids=["dyadic", "normal"])
@pytest.mark.parametrize("name", list(PACKED))
def test_packed_tables_take_the_walks_exits_and_score_as_the_connected_cuts(
        monkeypatch, name, dyadic):
    """Ragged random trees, their entries packed: every (row, entry) of
    the tables leaves by the exit the node walk takes (`entries_exits`: a
    glue copy sends the row to the piece it reaches), kernel and twin give
    the walk's scores, and the scores over the CONNECTED cut's tables of
    the same model (more entries, summed in other blocks) are the same BIT
    FOR BIT on dyadic leaf values and to float32 rounding otherwise."""
    case = PACKED[name]
    monkeypatch.setattr(tree, "SUBTREE_LANES", case["lanes"])
    if "step" in case:
        monkeypatch.setattr(predict_paths, "_MAX_TREES_PER_STEP",
                            case["step"])
        monkeypatch.setattr(predict_paths, "TILE_ROWS", 512)
    rng = np.random.default_rng(530)
    ens = joined(*[random_node_list(
        rng, 1, n, case["features"], n_bins=BINS, dyadic=dyadic,
        missing=case["missing"], **case["meta"])
        # (8 trees: a forest's mean of dyadic values is exact)
        for n in (30, 1, 1300, 90, 700, 257, 400, 900)
        if n > 1 or case["meta"].get("loss") != "logloss"])
    ce = ens.compile()
    spans, cut = tree.choose_select_spans(ens, case["lanes"])
    assert ce.chained and ce.n_subtrees == cut.n_subtrees.sum()
    assert ce.pieces > 2 * ce.n_subtrees and ce.glue_copies > ce.n_subtrees
    assert ce.halved == (name in ("halved-softmax-nan", "scalar"))
    assert (name == "spans-vector-nan") == (spans != tree.dense_spans(
        case["features"], case["lanes"]))
    Xb = rows_of(531, 24, case["features"])
    Xb[::5, ::2] = BINS - 1             # rows that sit in the NaN bin
    entries_exits(ens, ce, cut, Xb)
    Xb = rows_of(532, 700, case["features"])
    Xb[::7, ::3] = BINS - 1
    want = ens.predict_raw(Xb, binned=True)
    packed = {impl: fresh_scores(ens, Xb, impl)
              for impl in ("pallas", "onehot")}
    one_piece_an_entry(monkeypatch, spans, ce.halved)
    connected = ens.compile()
    assert connected.pieces == connected.n_subtrees > 1.3 * ce.n_subtrees
    assert not connected.glue_copies
    for impl, got in packed.items():
        other = fresh_scores(ens, Xb, impl)
        if dyadic:
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(got, other)
        else:
            np.testing.assert_allclose(got, want, atol=2e-6)
            np.testing.assert_allclose(got, other, atol=1e-6)


def test_a_node_that_is_glue_in_two_entries():
    """A tree's upper nodes are the common ancestors of pieces in SEVERAL
    entries: a copy of the same node in each, every one asking its
    question under its own entry's lanes, and the scores the walk's."""
    ens = forest(533, 4, (900, 1400), 3, missing=True, dyadic=True)
    cut = cut_subtrees(ens, 128)
    glue = np.arange(ens.n_splits, len(cut.tree))
    node = cut.tree[glue] * ens.feature.shape[1] + cut.origin[glue]
    often = np.bincount(node).max()
    assert often >= 3                   # one node, glue in three entries
    s = glue[node == np.bincount(node).argmax()]
    assert len(set(cut.subtree[s])) == often    # ... an entry each
    ce = ens.compile()
    first = np.concatenate([[0], np.cumsum(cut.n_subtrees)])
    entry = first[cut.tree[s]] + cut.subtree[s]
    t, n = cut.tree[s[0]], cut.origin[s[0]]
    # ... and beside its own lane in the entry that holds the node itself
    own = first_node(ens)[t] + n
    entry = np.append(entry, first[t] + cut.subtree[own])
    lane = np.append(cut.lane[s], cut.lane[own])
    sel = ce.sel.astype(np.float32)
    np.testing.assert_array_equal(sel[entry, ens.feature[t, n], lane], 1.0)
    np.testing.assert_array_equal(ce.planes[entry, 0, lane],
                                  ens.threshold_bin[t, n])
    Xb = rows_of(534, 16)
    Xb[::3, ::2] = BINS - 1
    entries_exits(ens, ce, cut, Xb)
    Xb = rows_of(535, 300)
    for impl in ("pallas", "onehot"):
        np.testing.assert_array_equal(
            scored(ens, Xb, impl), predict_proba_node_list(ens, Xb))


def spine_tree(rng, hung, columns):
    """One tree: a spine of 127 nodes going right whose node i has on its
    left a random sub-tree of `hung[i]` leaves (else a leaf), the last
    one's right child a leaf; dyadic leaf vectors, NaN directions."""
    leaves = []

    def leaf():
        leaves.append(rng.integers(-16, 17, (columns,)) / 8.0)
        return ~(len(leaves) - 1)

    nodes = [[int(rng.integers(F)), int(rng.integers(BINS - 2)), 0.0, 0.0,
              None, i + 1, bool(rng.integers(2))] for i in range(127)]
    nodes[-1][5] = leaf()
    for i in range(127):
        if i not in hung:
            nodes[i][4] = leaf()
            continue
        sub = random_node_list(rng, 1, hung[i], F, n_bins=BINS, dyadic=True,
                               missing=True, leaf_columns=columns)
        n0, l0 = len(nodes), len(leaves)
        nodes[i][4] = n0
        for j in range(hung[i] - 1):
            kids = [int(c) + n0 if c >= 0 else ~(~int(c) + l0) for c in (
                sub.left_child[0, j], sub.right_child[0, j])]
            nodes.append([int(sub.feature[0, j]),
                          int(sub.threshold_bin[0, j]), 0.0, 0.0, *kids,
                          bool(sub.default_left[0, j])])
        leaves += list(sub.leaf_value[0, :hung[i]])
    return tree.node_list_from_trees(
        [(nodes, leaves)], n_features=F, n_bins=BINS, missing_bin=True,
        learning_rate=1.0, base_score=0.0, loss="mean", n_classes=columns)


@pytest.mark.parametrize("columns,most", [(42, 3), (40, 9)])
def test_a_pieces_link_reaches_the_last_lane_the_rule_allows(
        monkeypatch, columns, most):
    """THE RULE's edge with packed entries: a piece of the tree's LAST
    entry hangs on a node of its FIRST, so the link lies in lane 3 C + n - 2
    = 127, the last the one tile of exits has, and the chain carries it
    n - 1 entries along. (The connected cut linked a part to parts near
    it; the packer's frontier holds a sub-tree until an entry has room for
    it.) The tree: a spine of 127 nodes, entry 0; n - 2 sub-trees of 127
    nodes on its first nodes, an entry each, the largest first; and one of
    100 nodes at its far end, which fits beside none of them and is left
    to the last entry. Kernel and twin score the walk's leaves."""
    monkeypatch.setattr(predict_paths, "_MAX_TREES_PER_STEP", 8)
    rng = np.random.default_rng(536 + columns)
    small = random_node_list(rng, 1, 60, F, n_bins=BINS, dyadic=True,
                             missing=True, leaf_columns=columns)
    tall = spine_tree(rng, {**{i: 128 for i in range(most - 2)}, 126: 101},
                      columns)
    ens = joined(small, tall, small, small)
    spans, cut = tree.choose_select_spans(ens, 128)
    assert cut.n_subtrees.tolist() == [1, most, 1, 1]
    own, _ = slots_of(ens, cut, 1)
    assert (cut.subtree[own[:127]] == 0).all()         # the spine
    hung = ens.left_child[1, 126]
    assert cut.subtree[own[hung]] == most - 1 and cut.root[own[hung]]
    ce = ens.compile()
    assert ce.leaves.shape[2] == 128 and ce.n_subtrees == most + 3
    assert tree.exit_table_lanes(columns, cut.n_subtrees) == (
        128, 3 * columns) and 3 * columns + most - 2 == 127
    assert ce.leaves[1, :, 127].astype(np.float32).sum() == 1
    Xb = rows_of(537, 300)
    Xb[::5, ::2] = BINS - 1
    Xb[::2, ens.feature[1, :127]] = BINS - 2    # half the rows: down the spine
    want = predict_proba_node_list(ens, Xb).astype(np.float32)
    for impl in ("pallas", "onehot"):   # 4 trees: the mean is exact too
        np.testing.assert_array_equal(scored(ens, Xb, impl), want)
    entries_exits(ens, ce, cut, Xb[:12])


def test_the_mnist_forest_packs_into_1800_entries():
    """The forest cell's own model at full size (benchmark/datagen_forest.py,
    its forest seed): 396,889 nodes in at most 1,800 entries (2,112 until
    PR 53; 1,557 would do by the nodes alone) under the spans the rule
    gives it, 20-21 at most a tree (30 lanes of pieces and the chain: ONE
    tile of exits), and the cut no dearer than it was: under 3.5 x the time
    of `_parents`, the proof of the node lists that every cut starts with
    (the connected cut took 1.8 x that on the same machine, this one 1 x)."""
    import time

    ens, sh = mnist_forest(100)
    assert sh["n_trees"] == ens.n_trees
    assert ens.n_splits == 396_889
    t0 = time.perf_counter()
    ens._parents()
    t1 = time.perf_counter()
    spans, cut = tree.choose_select_spans(ens, 256)
    t2 = time.perf_counter()
    assert spans == ((0, 3), (3, 7)) and cut.copy is None
    assert 1_557 <= cut.n_subtrees.sum() <= 1_800
    assert tree.exit_table_lanes(10, cut.n_subtrees) == (128, 30)
    assert cut.n_subtrees.max() <= 25
    assert t2 - t1 < 3.5 * (t1 - t0) + 0.5
