"""Host spans (telemetry/annotations.phase_span): the in-memory records,
the spans and counters of one TPUDevice.predict_raw call on each of its
three branches, and the compile listener's new counters; and the device
half: the stage of every instruction of a scoring program
(annotations.device_stages), made when asked and never by a call."""

import collections
import json
import re
import threading
import time

import jax
import numpy as np
import pytest

from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import TreeEnsemble
from ddt_tpu.telemetry import annotations as an
from ddt_tpu.telemetry import counters as tele_counters


def _rand_ensemble(seed, T=5, depth=3, F=6, bins=31):
    rng = np.random.default_rng(seed)
    N = 2 ** (depth + 1) - 1
    return TreeEnsemble(
        feature=rng.integers(0, F, size=(T, N)).astype(np.int32),
        threshold_bin=rng.integers(0, bins - 1, (T, N)).astype(np.int32),
        threshold_raw=np.zeros((T, N), np.float32),
        is_leaf=rng.random((T, N)) < 0.25,
        leaf_value=rng.standard_normal((T, N)).astype(np.float32),
        split_gain=np.zeros((T, N), np.float32),
        max_depth=depth, n_features=F, learning_rate=0.1, base_score=0.3,
        loss="logloss", n_classes=2, n_bins=bins)


def _small_node_list(seed):
    """3 trees of 3 leaves: n0 -> (L0, n1), n1 -> (L1, L2)."""
    from ddt_tpu.models.tree import node_list_from_trees

    rng = np.random.default_rng(seed)
    trees = [([(int(rng.integers(6)), int(rng.integers(30)), 0.0, 0.0,
                ~0, 1),
               (int(rng.integers(6)), int(rng.integers(30)), 0.0, 0.0,
                ~1, ~2)], [1.0, 2.0, 4.0]) for _ in range(3)]
    return node_list_from_trees(trees, n_features=6, learning_rate=0.5,
                                base_score=0.0, loss="logloss", n_bins=31)


def _by_name(root):
    out = collections.defaultdict(list)
    for s in root["spans"]:
        out[s["name"]].append(s)
    return out


# ------------------------------------------------------------------ #
# the records
# ------------------------------------------------------------------ #

def test_nesting_gives_cause_and_root_on_one_thread():
    with an.phase_span("t:outer", rows=3) as outer:
        with an.phase_span("t:mid") as mid:
            with an.phase_span("t:leaf") as leaf:
                pass
        with an.phase_span("t:second") as second:
            second.counts["bytes"] = 7
    assert (outer.cause, outer.root) == (None, outer.id)
    assert (mid.cause, mid.root) == (outer.id, outer.id)
    assert (leaf.cause, leaf.root) == (mid.id, outer.id)
    assert (second.cause, second.root) == (outer.id, outer.id)
    assert outer.id < mid.id < leaf.id < second.id
    assert outer.start <= mid.start <= leaf.start <= leaf.end <= mid.end \
        <= second.start <= second.end <= outer.end
    root, = [r for r in an.root_spans("t:outer") if r["id"] == outer.id]
    assert [s["name"] for s in root["spans"]] == [
        "ddt:t:outer", "ddt:t:mid", "ddt:t:leaf", "ddt:t:second"]
    assert root["counts"] == {"rows": 3}
    assert root["spans"][-1]["counts"] == {"bytes": 7}
    # the next span of this thread is a root again: the stack unwound
    with an.phase_span("t:after") as after:
        pass
    assert after.cause is None and after.root == after.id


def test_a_span_opened_on_another_thread_is_a_root():
    seen = {}

    def work():
        with an.phase_span("t:worker") as w:
            with an.phase_span("t:worker:child") as c:
                pass
        seen["w"], seen["c"] = w, c

    with an.phase_span("t:main") as main:
        th = threading.Thread(target=work)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
        with an.phase_span("t:main:child") as child:
            pass
    w, c = seen["w"], seen["c"]
    assert w.cause is None and w.root == w.id != main.id
    assert (c.cause, c.root) == (w.id, w.id)
    assert (child.cause, child.root) == (main.id, main.id)
    assert main.start <= w.start <= w.end <= main.end   # same clock


def test_the_ring_is_bounded(monkeypatch):
    assert an._ring.maxlen == an.SPAN_RING >= 4096
    monkeypatch.setattr(an, "_ring", collections.deque(maxlen=16))
    ids = []
    for _ in range(17):
        with an.phase_span("t:ring") as s:
            pass
        ids.append(s.id)
    kept = an.recent_spans()
    assert len(kept) == 16
    assert [s["id"] for s in kept] == ids[1:]       # the oldest went


def test_a_span_that_raises_is_recorded_and_unwinds():
    with pytest.raises(KeyError):
        with an.phase_span("t:raises") as s:
            raise KeyError("x")
    assert s.end >= s.start
    assert an.recent_spans()[-1]["id"] == s.id
    with an.phase_span("t:next") as nxt:
        pass
    assert nxt.cause is None


def test_the_anchor_turns_a_span_into_wall_clock_time():
    perf, wall = an.SPAN_ANCHOR
    with an.phase_span("t:anchor") as s:
        now = time.time_ns()
    as_wall = s.start - perf + wall
    assert abs(as_wall - now) < 5e9         # same instant, two clocks


def test_an_empty_span_costs_microseconds():
    """A generous guard, not a measurement (that is PERF.md's, on the
    chip's host): the mean of 100,000 empty spans stays under 20 us."""
    t0 = time.perf_counter_ns()
    for _ in range(100_000):
        with an.phase_span("t:cost"):
            pass
    assert (time.perf_counter_ns() - t0) / 100_000 < 20_000


# ------------------------------------------------------------------ #
# one predict_raw call
# ------------------------------------------------------------------ #

BRANCHES = {
    # branch: (partitions, PREDICT_ROW_CHUNK, rows,
    #          {span: how many of it}, chunks[, PREDICT_FIRST_PIECE_BYTES])
    "one": (1, 4096, 1000,
            dict(upload=1, dispatch=1, fetch=1, place=0, concat=0), 1),
    # pieces of PREDICT_UPLOAD_CHUNKS = 2 chunks, a later one under the
    # compute of the one before it: 4 chunks in 2 pieces, 7 in 4 (the
    # last piece one remainder chunk)
    "chunks": (1, 256, 1000,
               dict(upload=2, dispatch=4, fetch=4, place=4, concat=0), 4),
    "chunks-4pieces": (1, 256, 1700,
                       dict(upload=4, dispatch=7, fetch=7, place=7,
                            concat=0), 7),
    # wide rows: a chunk (256 rows x 6 B) is all a leading piece may hold,
    # so the first TWO pieces are one chunk, the rest two: 1 1 2 2 1
    "chunks-lead": (1, 256, 1700,
                    dict(upload=5, dispatch=7, fetch=7, place=7, concat=0),
                    7, 256 * 6),
    "mesh": (4, 64, 1000,
             dict(upload=4, dispatch=4, fetch=1, place=0, concat=0), 4),
}


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_predict_raw_records_one_root_per_call(branch, monkeypatch):
    parts, row_chunk, R, want, chunks, *first_bytes = BRANCHES[branch]
    be = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                 n_partitions=parts))
    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", row_chunk)
    for b in first_bytes:
        monkeypatch.setattr(type(be), "PREDICT_FIRST_PIECE_BYTES", b)
    ens = _rand_ensemble(seed=1000 + sorted(BRANCHES).index(branch))
    Xb = np.random.default_rng(5).integers(0, 31, size=(R, 6),
                                           dtype=np.uint8)

    c0 = tele_counters.snapshot()
    scores = be.predict_raw(ens, Xb)
    moved = tele_counters.delta(c0)
    root = an.root_spans("predict")[-1]
    kids = _by_name(root)

    assert root["counts"]["rows"] == R
    assert root["counts"]["chunks"] == chunks
    assert root["counts"]["branch"] == branch.split("-")[0]
    for k in type(be)._PREDICT_ROOT_COUNTERS:
        assert root["counts"][k] == moved[k]
    assert root["counts"]["compiled_ensemble_cache_hits"] == 0
    assert len(kids["ddt:predict"]) == 1
    assert len(kids["ddt:predict:token"]) == 1
    assert len(kids["ddt:predict:ensemble"]) == 1
    for name, n in want.items():
        assert len(kids["ddt:predict:" + name]) == n, name
    by_id = {s["id"]: s for s in root["spans"]}
    for s in root["spans"]:
        if s["id"] != root["id"]:
            # a step is the root's own child; what a step waits for or is
            # built of hangs under it, and is named after it
            above = by_id[s["cause"]]
            assert s["root"] == root["id"]
            assert above["id"] == root["id"] \
                or s["name"].startswith(above["name"] + ":")
            assert above["start"] <= s["start"] <= s["end"] <= above["end"]
            assert root["start"] <= s["start"] <= s["end"] <= root["end"]
    for name in ("dispatch", "fetch", "place"):
        assert [s["counts"]["chunk"] for s in kids["ddt:predict:" + name]] \
            == list(range(want[name]))
    if root["counts"]["branch"] == "chunks":
        # a piece's transfer starts when the last chunk of the piece before
        # it has been dispatched, and before its own first chunk is
        per = type(be).PREDICT_UPLOAD_CHUNKS
        up, disp = kids["ddt:predict:upload"], kids["ddt:predict:dispatch"]
        assert [s["counts"]["piece"] for s in up] == list(range(len(up)))
        assert up[0]["end"] <= disp[0]["start"]
        # the chunk a piece starts at: `per` chunks a piece, but one a
        # leading piece where PREDICT_FIRST_PIECE_BYTES holds no more
        starts_at = ([0, 1] + list(range(2, chunks, per)) if first_bytes
                     else list(range(0, chunks, per)))
        assert len(up) == len(starts_at)
        for p in range(1, len(up)):
            assert disp[starts_at[p] - 1]["end"] <= up[p]["start"]
            assert up[p]["end"] <= disp[starts_at[p]]["start"]
        assert [s["counts"]["bytes"] for s in up] == [
            6 * (min(R, row_chunk * b) - row_chunk * a)
            for a, b in zip(starts_at, starts_at[1:] + [chunks])]
        # Inside a LATER piece's upload, the wait for the piece before it
        # is a span of its own (the one place the host learns that a
        # piece's bytes have arrived), counted by the piece WAITED for;
        # the upload keeps its name, counts and extent. The first holds
        # none: piece 0's put is the first upload itself.
        waits = kids["ddt:predict:upload:wait"]
        assert [w["cause"] for w in waits] == [u["id"] for u in up[1:]]
        for p, (w, u) in enumerate(zip(waits, up[1:])):
            assert w["counts"] == {"piece": p,
                                   "bytes": up[p]["counts"]["bytes"]}
            assert set(u["counts"]) == {"piece", "bytes"}
            assert u["start"] <= w["start"] <= w["end"] <= u["end"]
    else:
        assert "ddt:predict:upload:wait" not in kids
    # bytes where the work happens, and the same bytes on the counters
    ens_bytes = kids["ddt:predict:ensemble"][0]["counts"]["bytes"]
    assert ens_bytes > 0
    assert sum(s["counts"]["bytes"] for s in kids["ddt:predict:upload"]) \
        == Xb.nbytes
    assert sum(s["counts"]["bytes"] for s in kids["ddt:predict:fetch"]) \
        == scores.nbytes
    if want["place"]:       # every chunk lands once, and nothing else does
        assert sum(s["counts"]["bytes"] for s in kids["ddt:predict:place"]) \
            == scores.nbytes
    assert moved["h2d_bytes"] == Xb.nbytes + ens_bytes
    assert moved["d2h_bytes"] == scores.nbytes
    np.testing.assert_allclose(scores, ens.predict_raw(Xb, binned=True),
                               rtol=2e-4, atol=2e-5)

    # the second call finds the model resident: no ensemble span, and
    # the link carries the batch and the scores alone
    c1 = tele_counters.snapshot()
    again = be.predict_raw(ens, Xb)
    moved = tele_counters.delta(c1)
    second = an.root_spans("predict")[-1]
    assert second["id"] > root["id"]
    assert "ddt:predict:ensemble" not in _by_name(second)
    assert second["counts"]["compiled_ensemble_cache_hits"] == 1
    assert (moved["h2d_bytes"], moved["d2h_bytes"]) \
        == (Xb.nbytes, scores.nbytes)
    np.testing.assert_array_equal(scores, again)


@pytest.mark.parametrize("cell,T,depth,F,C,routed,step,tiles", [
    ("score1000t-100m-1chip", 1000, 6, 28, 1, False, 256, 8 * 32),
    ("covtype3500t-d8-score-1chip", 3500, 8, 54, 7, False, 256, 7 * 128),
    ("criteo100t-d6-score-1chip", 100, 6, 39, 1, True, 1024, 1 * 63),
    # between the cells: four groups of 32 tiles, one of 128
    ("500 trees", 500, 6, 28, 1, False, 512, 4 * 32),
    ("depth 8, one group", 100, 8, 28, 1, False, 512, 128),
])
def test_the_ensemble_span_says_the_rows_a_step(cell, T, depth, F, C, routed,
                                                step, tiles):
    """`rows_per_step` on the `ddt:predict:ensemble` span (PR 42): the
    rows a grid step of the traversal kernel takes in a long program, the
    plan's `tile_rows`, by the MXU weight tiles the step holds: 256 in
    the 1000-tree and the Covertype cell, 1,024 in the CTR cell, whose
    step is one group of 63. (The span is the cache miss's: the model's
    tables are built and staged, no row is scored.)"""
    from ddt_tpu.models.tree import empty_ensemble

    ens = empty_ensemble(
        T, depth, F, 0.1, 0.0, "softmax" if C > 1 else "logloss", max(C, 2),
        missing_bin=routed, n_bins=255, cat_features=(0, 1) if routed else ())
    n_int = (1 << depth) - 1
    ens.feature[:, :n_int] = np.arange(n_int) % F
    ens.is_leaf[:, n_int:] = True
    be = get_backend(TrainConfig(backend="tpu", n_bins=255, max_depth=depth,
                                 predict_impl="pallas"))
    plan = be._predict_entry(ens)[3]
    counts = [s for s in an.recent_spans()
              if s["name"] == "ddt:predict:ensemble"][-1]["counts"]
    assert counts["trees"] == T
    assert counts["groups_per_step"] * counts["mxu_tiles_per_group"] == tiles
    assert counts["rows_per_step"] == plan.tile_rows == step
    assert counts["routing_tables"] == 2 * routed


@pytest.mark.parametrize("impl", ["pallas", "auto"])
def test_a_node_list_says_which_form_serves(impl, monkeypatch):
    """A node-list model through the same chunk loop: the `ensemble` span
    carries the path form's plan (ops/predict_paths.SPAN_COUNTS), the
    root `node_list`, `path_mxu_tiles_per_tree` and what the kernel
    streams of its tables; a heap model's spans carry none of them."""
    from ddt_tpu.ops import predict_paths

    rng = np.random.default_rng(77)
    ens = _small_node_list(78)
    be = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                 predict_impl=impl))
    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", 256)
    Xb = rng.integers(0, 31, size=(1000, 6), dtype=np.uint8)
    scores = be.predict_raw(ens, Xb)
    np.testing.assert_array_equal(scores, ens.predict_raw(Xb, binned=True))

    root = an.root_spans("predict")[-1]
    kids = _by_name(root)
    assert root["counts"]["branch"] == "chunks"
    assert root["counts"]["chunks"] == 4
    assert root["counts"]["node_list"] == 1
    assert root["counts"]["path_mxu_tiles_per_tree"] == 2     # 128 lanes
    assert root["counts"]["routing_tables"] == 0
    # 3 trees are one block: fetched once and resident, nothing streamed
    assert root["counts"]["tables_streamed_bytes"] == 0
    counts = kids["ddt:predict:ensemble"][0]["counts"]
    assert list(counts) == ["bytes", "trees", *predict_paths.SPAN_COUNTS]
    served = impl == "pallas"      # a CPU's auto takes the jax.numpy form
    assert counts == dict(
        bytes=counts["bytes"], trees=3, node_list=1, nodes_per_tree=128,
        leaves_per_tree=128, deepest_leaf=2, path_mxu_tiles_per_tree=2,
        trees_per_step=3 * served, table_blocks=1 * served,
        table_bytes=3 * (16 * 128 * 2 + 8 * 128 * 4 + 128 * 128 * 2) * served,
        select_k_blocks=1, missing_routes=0, row_operand_bytes=1,
        select_nodes_per_lane=1,           # 128 lanes: one tile already
        # no node asks a category set (PR 55)
        category_sets=0, category_nodes=0, category_set_bits_max=0,
        catset_mxu_tiles_per_tree=0,
        # one uncut path matrix a tree: a sub-tree of its own, no chain,
        # and no link function taken by the program
        subtrees_per_tree=1.0, subtrees_per_tree_max=1,
        single_subtree_trees=3, subtree_lanes=128, leaf_columns=1,
        link="none", chain_mxu_tiles_per_tree=0, class_dot_passes=0,
        select_mxu_tiles=1,                # one K-block x one lane tile
        exit_mxu_tiles=0,                  # no exits' table: no chain
        # one lane tile of nodes against one of leaves; no sub-tree, so
        # none in two halves
        resolve_mxu_tiles=1, spine_copies_per_subtree=0.0,
        # ... and every tree ONE piece, glued to nothing (PR 53)
        pieces_per_subtree=1.0, glue_copies_per_subtree=0.0)
    assert predict_paths.CHAIN_COUNTS[-6:] == (
        "select_mxu_tiles", "exit_mxu_tiles", "resolve_mxu_tiles",
        "spine_copies_per_subtree", "pieces_per_subtree",
        "glue_copies_per_subtree")
    assert counts["bytes"] == counts["table_bytes"] or not served
    assert root["counts"]["select_k_blocks"] == 1
    assert root["counts"]["select_nodes_per_lane"] == 1
    assert root["counts"]["link"] == "none"

    # blocks of trees stream once a row tile
    plan = predict_paths.path_plan(500, 256, 28, 17)
    assert plan.node_list == 1 and plan.deepest_leaf == 17
    # two nodes a lane of the select (F <= 64, 256 lanes): 1 + 4 tiles,
    # the select table [80, 128] where [32, 256] was
    assert (plan.select_nodes_per_lane, plan.path_mxu_tiles_per_tree) == (2, 5)
    assert plan.trees_per_step * plan.table_blocks >= 500
    assert plan.blocks == plan.table_blocks > 1
    assert plan.table_bytes == plan.trees_per_step * plan.table_blocks * (
        80 * 128 * 2 + 8 * 256 * 4 + 256 * 256 * 2)

    # Bosch's width: 8 K-blocks of the select, 20 weight tiles a tree, the
    # row tile charged at the rows' own width, no filler tree in 50 blocks
    wide = predict_paths.path_plan(500, 256, 968, missing_routes=True)
    assert (wide.select_k_blocks, wide.path_mxu_tiles_per_tree,
            wide.missing_routes, wide.row_operand_bytes,
            wide.select_nodes_per_lane) == (8, 20, 1, 1, 1)
    assert wide.trees_per_step * wide.table_blocks == 500
    assert wide.table_bytes == 500 * (976 * 256 * 2 + 8 * 256 * 4
                                      + 256 * 256 * 2)
    assert predict_paths.path_plan(
        500, 256, 968, row_dtype=np.int32).row_operand_bytes == 4

    # a heap model says nothing of the path form
    heap = _rand_ensemble(seed=2024)
    be.predict_raw(heap, Xb)
    root = an.root_spans("predict")[-1]
    assert "node_list" not in root["counts"]
    assert "select_k_blocks" not in root["counts"]
    assert "node_list" not in _by_name(root)[
        "ddt:predict:ensemble"][0]["counts"]


@pytest.mark.parametrize("impl", ["pallas", "auto"])
def test_an_oblivious_model_says_which_form_serves(impl, monkeypatch):
    """An oblivious model through the same chunk loop: the `ensemble` span
    carries the oblivious form's plan (ops/predict_oblivious.SPAN_COUNTS,
    spelled out here), `resolves_under_select` last: the share of a row
    tile's resolves the kernel issues beside a later select's matmuls."""
    from ddt_tpu.ops import predict_oblivious

    ens = _small_oblivious(3205)               # 5 trees x depth 3 x 6
    be = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                 predict_impl=impl))
    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", 256)
    Xb = np.random.default_rng(79).integers(0, 31, size=(1000, 6),
                                            dtype=np.uint8)
    scores = be.predict_raw(ens, Xb)
    np.testing.assert_allclose(scores, ens.predict_raw(Xb, binned=True),
                               rtol=1e-5, atol=1e-6)
    root = an.root_spans("predict")[-1]
    assert root["counts"]["oblivious"] == 1
    assert root["counts"]["select_columns_per_tree"] == 3
    counts = _by_name(root)["ddt:predict:ensemble"][0]["counts"]
    assert list(counts) == ["bytes", "trees",
                            *predict_oblivious.SPAN_COUNTS]
    served = impl == "pallas"      # a CPU's auto takes the jax.numpy form
    assert counts == dict(
        bytes=counts["bytes"], trees=5, oblivious=1, depth=3,
        select_columns_per_tree=3, trees_per_lane_tile=42.67,
        select_k_blocks=1, oblivious_mxu_tiles_per_tree=0.0234,
        trees_per_step=128 * served, table_blocks=1 * served,
        table_bytes=(3 * 16 * 128 * 2 + 8 * 128 * 4 + 8 * 128 * 4) * served,
        row_operand_bytes=1,
        # one column a leaf: a multiplexer of 2^3 - 1 selects, no link
        leaf_columns=1, link="none", resolve_selects_per_tree=7,
        resolve_gathers_per_tree=0,
        # one group: of a row tile's two resolves the first runs beside
        # the second sub-tile's select
        resolves_under_select=0.5 * served)


@pytest.mark.parametrize("impl", ["pallas", "onehot"])
@pytest.mark.parametrize("categories,missing,said", [
    # every node a set: no ordinal K row; 3 + 40 rows share ONE one-hot
    # block: 1 select tile + 1 resolve, as an ordinal model of the shape
    (((0, 3), (1, 40), (2, 5), (3, 7), (4, 9), (5, 11)), False,
     dict(select_k_blocks=1, path_mxu_tiles_per_tree=2,
          catset_mxu_tiles_per_tree=0, missing_routes=0)),
    # sets of two wide columns (200 and 180 bins: a whole block each and
    # their rests sharing a third) beside ordinal nodes WITH NaN
    # directions: the ordinal K-block and the sets' three
    (((2, 200), (4, 180)), True,
     dict(select_k_blocks=4, path_mxu_tiles_per_tree=5,
          catset_mxu_tiles_per_tree=3, missing_routes=1)),
])
def test_a_node_list_with_category_sets_says_so(impl, categories, missing,
                                                said):
    """Category sets on the spans (PR 55): `category_sets`, `category_nodes`
    and `category_set_bits_max` on the `ensemble` span, with the K-blocks
    and weight tiles the kernel REALLY asks (`select_k_blocks`,
    `path_mxu_tiles_per_tree`) and what the set test adds beside an ordinal
    model of the shape (`catset_mxu_tiles_per_tree`); the root of every
    call repeats them."""
    from ddt_tpu.models.tree import random_node_list

    rng = np.random.default_rng(81)
    ens = random_node_list(rng, 3, 9, 6, n_bins=255, missing=missing,
                           dyadic=True, categories=categories, max_set=32,
                           learning_rate=0.5, base_score=0.0, loss="logloss")
    be = get_backend(TrainConfig(backend="tpu", n_bins=255,
                                 predict_impl=impl))
    Xb = rng.integers(0, 255, size=(300, 6), dtype=np.uint8)
    for c, k in categories:
        Xb[:, c] = rng.integers(0, k + 2, 300)
    for call in range(2):       # the second from the model cache
        scores = be.predict_raw(ens, Xb)
        root = an.root_spans("predict")[-1]["counts"]
        assert root["category_sets"] == 1 and root["node_list"] == 1
        assert {k: root[k] for k in said if k in root} == {
            k: v for k, v in said.items() if k in root}
        assert root["catset_mxu_tiles_per_tree"] == said[
            "catset_mxu_tiles_per_tree"]
    np.testing.assert_array_equal(scores, ens.predict_raw(Xb, binned=True))
    built = [sp for sp in an.recent_spans()
             if sp["name"] == "ddt:predict:ensemble"][-1]["counts"]
    assert {k: built[k] for k in said} == said
    sets = ens.cat_nodes
    assert built["category_sets"] == 1
    assert built["category_nodes"] == int(sets.sum())
    assert built["category_set_bits_max"] == int(
        ens.cat_set_bits()[ens.cat_index[sets]].sum(axis=1).max())
    assert built["select_nodes_per_lane"] == 1


@pytest.mark.parametrize("impl", ["pallas", "onehot"])
def test_the_set_tests_spans_are_on_the_spans(impl):
    """PR 56: an uncut tree of two lane tiles with category sets whose
    K-blocks split (the Allstate cell's columns) says the tiles its SPANS
    ask: `select_mxu_tiles` 8 where 14, `path_mxu_tiles_per_tree` 12,
    `catset_mxu_tiles_per_tree` 7, `select_k_blocks` 7 as before; the root
    of every call repeats them."""
    from ddt_tpu.models.tree import random_node_list

    categories = ((3, 75), (4, 254), (5, 254), (6, 10), (7, 3), (8, 6),
                  (9, 3), (10, 3), (11, 6), (12, 4), (13, 4), (14, 2),
                  (15, 3), (16, 11), (17, 11), (27, 15))
    rng = np.random.default_rng(83)
    ens = random_node_list(rng, 3, 255, 32, n_bins=255, dyadic=True,
                           categories=categories, max_set=32,
                           learning_rate=0.5, base_score=0.0, loss="logloss")
    assert ens.compile().select_spans == ((0, 4), (3, 7))
    be = get_backend(TrainConfig(backend="tpu", n_bins=255,
                                 predict_impl=impl))
    Xb = rng.integers(0, 255, size=(300, 32), dtype=np.uint8)
    for c, k in categories:
        Xb[:, c] = rng.integers(0, k + 2, 300)
    said = dict(select_k_blocks=7, path_mxu_tiles_per_tree=12,
                catset_mxu_tiles_per_tree=7, select_mxu_tiles=8,
                resolve_mxu_tiles=4, select_nodes_per_lane=1,
                category_sets=1)
    for call in range(2):       # the second from the model cache
        scores = be.predict_raw(ens, Xb)
        root = an.root_spans("predict")[-1]["counts"]
        assert {k: root[k] for k in said} == said
    np.testing.assert_array_equal(scores, ens.predict_raw(Xb, binned=True))
    built = [sp for sp in an.recent_spans()
             if sp["name"] == "ddt:predict:ensemble"][-1]["counts"]
    assert {k: built[k] for k in said} == said


@pytest.mark.parametrize("n_features,k_blocks", [(6, 1), (129, 2), (300, 3)])
def test_a_nan_routed_node_list_says_so(n_features, k_blocks):
    """Learned NaN directions and the width of the select on the spans:
    `missing_routes` and `select_k_blocks` on the `ensemble` span,
    `routing_tables` 1 and `select_k_blocks` on the root, of every call."""
    from ddt_tpu.models.tree import random_node_list

    rng = np.random.default_rng(79)
    ens = random_node_list(rng, 3, 9, n_features, n_bins=31, missing=True,
                           dyadic=True, learning_rate=0.5, base_score=0.0, loss="logloss")
    be = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                 predict_impl="pallas"))
    Xb = rng.integers(0, 31, size=(300, n_features), dtype=np.uint8)
    for call in range(2):       # the second from the model cache
        scores = be.predict_raw(ens, Xb)
        root = an.root_spans("predict")[-1]
        assert root["counts"]["routing_tables"] == 1
        assert root["counts"]["select_k_blocks"] == k_blocks
        assert root["counts"]["node_list"] == 1
    np.testing.assert_array_equal(scores, ens.predict_raw(Xb, binned=True))
    built = [sp for sp in an.recent_spans()
             if sp["name"] == "ddt:predict:ensemble"][-1]["counts"]
    assert (built["missing_routes"], built["select_k_blocks"],
            built["row_operand_bytes"]) == (1, k_blocks, 1)


# The chunk loop's result against its chunks scored one call each (the
# `one` branch): rows a whole number of chunks and rows with a remainder
# chunk, 7 chunks whose last piece is the remainder chunk alone, one class
# and seven, host rows and rows already on the device.
CHUNK_CASES = [(rows, classes, resident)
               for rows in (768, 1000, 1700)
               for classes in (1, 7)
               for resident in (False, True)]


@pytest.mark.parametrize(
    "rows,classes,resident", CHUNK_CASES,
    ids=[f"{r}rows-{c}class-{'device' if d else 'host'}"
         for r, c, d in CHUNK_CASES])
def test_the_chunk_loop_places_every_chunk_in_one_array(
        rows, classes, resident, monkeypatch):
    be = get_backend(TrainConfig(backend="tpu", n_bins=31))
    ens = _rand_ensemble(seed=1300 + classes, T=3 * classes)
    if classes > 1:
        ens.loss, ens.n_classes = "softmax", classes
    Xb = np.random.default_rng(rows + classes).integers(
        0, 31, size=(rows, 6), dtype=np.uint8)
    # every chunk by itself, while a chunk is still the whole batch
    per_chunk = [be.predict_raw(ens, Xb[i:i + 256])
                 for i in range(0, rows, 256)]
    assert an.root_spans("predict")[-1]["counts"]["branch"] == "one"

    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", 256)
    scores = be.predict_raw(ens, jax.device_put(Xb) if resident else Xb)
    root = an.root_spans("predict")[-1]
    assert root["counts"]["branch"] == "chunks"
    assert root["counts"]["chunks"] == len(per_chunk)
    assert root["counts"]["classes"] == classes

    assert type(scores) is np.ndarray and scores.dtype == np.float32
    assert scores.shape == ((rows,) if classes == 1 else (rows, classes))
    assert scores.flags.c_contiguous and scores.flags.owndata
    assert scores.flags.writeable
    np.testing.assert_array_equal(scores, np.concatenate(per_chunk))

    # The benchmark's clock anchor: fetch[k] ends when the host holds
    # chunk k, and the copy into the result comes after, a span of its own.
    kids = _by_name(root)
    fetch, place = kids["ddt:predict:fetch"], kids["ddt:predict:place"]
    assert len(fetch) == len(place) == len(per_chunk)
    assert "ddt:predict:concat" not in kids
    for k, (f, p, part) in enumerate(zip(fetch, place, per_chunk)):
        assert f["counts"]["chunk"] == p["counts"]["chunk"] == k
        assert f["counts"]["bytes"] == p["counts"]["bytes"] == part.nbytes
        assert f["end"] <= p["start"]
        if k:               # chunk k is fetched after chunk k - 1 landed
            assert place[k - 1]["end"] <= f["start"]


def test_a_device_resident_batch_uploads_nothing(monkeypatch):
    be = get_backend(TrainConfig(backend="tpu", n_bins=31))
    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", 256)
    ens = _rand_ensemble(seed=1100)
    Xb = np.random.default_rng(6).integers(0, 31, size=(1000, 6),
                                           dtype=np.uint8)
    want = be.predict_raw(ens, Xb)
    c0 = tele_counters.snapshot()
    got = be.predict_raw(ens, jax.device_put(Xb))
    root = an.root_spans("predict")[-1]
    assert root["counts"]["branch"] == "chunks"
    assert _by_name(root)["ddt:predict:upload"][0]["counts"]["bytes"] == 0
    assert tele_counters.delta(c0)["h2d_bytes"] == 0
    np.testing.assert_array_equal(want, got)


def test_a_compiled_ensemble_skips_the_token_span():
    be = get_backend(TrainConfig(backend="tpu", n_bins=31))
    ens = _rand_ensemble(seed=1200)
    Xb = np.random.default_rng(7).integers(0, 31, size=(64, 6),
                                           dtype=np.uint8)
    be.predict_raw(ens, Xb, compiled=ens.compile(tree_chunk=64))
    kids = _by_name(an.root_spans("predict")[-1])
    assert "ddt:predict:token" not in kids
    assert len(kids["ddt:predict:ensemble"]) == 1


# ------------------------------------------------------------------ #
# the host's side: a root's account, its pauses, the slow call kept
# ------------------------------------------------------------------ #

def _span(name, id_, cause, root, start, end, **counts):
    return {"name": "ddt:" + name, "id": id_, "cause": cause, "root": root,
            "start": start, "end": end, "counts": counts}


def test_account_sums_to_the_root_on_nested_and_overlapping_spans():
    """Every instant of the root belongs to one span: the innermost open
    at it, of two overlapping siblings the one that started last."""
    spans = [
        _span("predict", 1, None, 1, 1_000, 11_000, rows=5),
        _span("predict:upload", 2, 1, 1, 1_500, 4_000),
        _span("predict:upload:wait", 3, 2, 1, 2_000, 3_000),
        # two siblings that overlap by 500 (never on one thread; a
        # reader's hand-made spans may)
        _span("predict:dispatch", 4, 1, 1, 5_000, 7_000),
        _span("predict:fetch", 5, 1, 1, 6_500, 9_000),
        # a span that leaves the root's extent is cut to it
        _span("predict:place", 6, 1, 1, 10_500, 12_000),
        # and one of no length owns nothing
        _span("predict:token", 7, 1, 1, 1_200, 1_200)]
    root = dict(spans[0], spans=spans)
    found = an.account(root)
    assert found["name"] == "ddt:predict"
    assert found["duration_ns"] == 10_000
    assert found["counts"] == {"rows": 5}
    assert found["self_ns"] == {
        "predict:upload": 1_500, "predict:upload:wait": 1_000,
        "predict:dispatch": 1_500, "predict:fetch": 2_500,
        "predict:place": 500, "unnamed": 3_000}
    assert list(found["self_ns"])[-1] == "unnamed"
    assert sum(found["self_ns"].values()) == found["duration_ns"]
    # pure: the root it was given is as it was
    assert root["counts"] == {"rows": 5} and len(root["spans"]) == 7


@pytest.mark.parametrize("branch", ["one", "chunks-4pieces", "mesh"])
def test_account_sums_to_a_real_call_to_the_nanosecond(branch, monkeypatch):
    parts, row_chunk, R, want, chunks = BRANCHES[branch]
    be = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                 n_partitions=parts))
    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", row_chunk)
    ens = _rand_ensemble(seed=4100 + sorted(BRANCHES).index(branch))
    Xb = np.random.default_rng(11).integers(0, 31, size=(R, 6),
                                            dtype=np.uint8)
    for call in range(2):           # the model's build, then resident
        be.predict_raw(ens, Xb)
        root = an.root_spans("predict")[-1]
        found = an.account(root)
        assert sum(found["self_ns"].values()) == found["duration_ns"] \
            == root["end"] - root["start"]
        assert all(v >= 0 for v in found["self_ns"].values())
        names = {s["name"].removeprefix("ddt:") for s in root["spans"]
                 if s["id"] != root["id"] and s["end"] > s["start"]}
        assert set(found["self_ns"]) == names | {"unnamed"}
        assert ("predict:ensemble:compile" in names) == (call == 0)
        assert ("predict:upload:wait" in names) == (branch[:6] == "chunks")


def test_the_root_carries_the_hosts_pauses(monkeypatch):
    import gc

    be = get_backend(TrainConfig(backend="tpu", n_bins=31))
    ens = _rand_ensemble(seed=4200)
    Xb = np.random.default_rng(12).integers(0, 31, size=(500, 6),
                                            dtype=np.uint8)
    be.predict_raw(ens, Xb)
    counts = an.root_spans("predict")[-1]["counts"]
    held = [k for k in tele_counters.HOST_COUNTERS if k in counts]
    for k in held:
        assert isinstance(counts[k], int) and counts[k] >= 0, k
    assert held == ["cpu_ns", "gc_pause_ns", "gc_collections",
                    "gc_gen2_collections"]
    assert counts["cpu_ns"] > 0

    # a collection inside the call is counted on its root, generation 2
    # apart, with the time every thread waited for it
    real = type(be)._predict_raw

    def collecting(self, *args, **kw):
        gc.collect()                # generation 2
        gc.collect(0)
        return real(self, *args, **kw)

    monkeypatch.setattr(type(be), "_predict_raw", collecting)
    be.predict_raw(ens, Xb)
    counts = an.root_spans("predict")[-1]["counts"]
    assert counts["gc_collections"] >= 2
    assert 1 <= counts["gc_gen2_collections"] < counts["gc_collections"]
    assert counts["gc_pause_ns"] > 0


def test_the_hosts_pauses_are_a_calls_and_not_the_registrys():
    """statusd scrapes the registry, and a scrape reads the same twice: a
    clock and a count the collector moves are read by host_pauses() at a
    call's two ends and never written there."""
    import gc

    kept = tele_counters.snapshot()
    a = tele_counters.host_pauses()
    assert tuple(a) == tele_counters.HOST_COUNTERS
    sum(i * i for i in range(200_000))          # CPU time passes
    gc.collect()
    moved = tele_counters.host_pauses(a)
    assert tuple(moved) == tele_counters.HOST_COUNTERS
    assert moved["cpu_ns"] > 0 and moved["gc_pause_ns"] > 0
    assert moved["gc_collections"] >= 1 <= moved["gc_gen2_collections"]
    assert tele_counters.snapshot() == kept
    assert not set(kept) & set(tele_counters.HOST_COUNTERS)


def _ended_root(took_ns, **counts):
    """A finished root `ddt:predict` of `took_ns`, as __exit__ leaves it."""
    with an.phase_span("predict", **counts) as sp:
        pass
    sp.end = sp.start + took_ns
    return sp


SHAPE = ("rows", "chunks", "branch", "classes")


def _noted(took_ns, caplog, **counts):
    """note_root of a root of `took_ns`; the warnings it logged."""
    import logging

    caplog.clear()
    with caplog.at_level(logging.WARNING, logger=an.__name__):
        an.note_root(_ended_root(took_ns, **counts), SHAPE)
    return [r for r in caplog.records if r.name == an.__name__]


@pytest.fixture
def no_history(monkeypatch):
    monkeypatch.setattr(an, "_history", {})
    monkeypatch.setattr(an, "_slow", collections.deque(maxlen=an.SLOW_RING))


SLOW_CASES = {
    # name: (steady ms, the call's ms, kept?)
    "7% and 1.0 s over 14.45 s (the forest cell's)": (14_450, 15_453, True),
    "25% and 365 ms over 1.44 s (Epsilon's)": (1_440, 1_805, True),
    "9% and 110 ms over 1.26 s (the routed cell's)": (1_260, 1_370, True),
    "30% of a 3 ms micro-batch is 0.9 ms": (3, 3.9, False),
    "19 ms of 100 ms": (100, 119, False),
    "4% of 10 s is 400 ms": (10_000, 10_400, False),
    "a faster call": (1_000, 500, False),
}


@pytest.mark.parametrize("case", sorted(SLOW_CASES))
def test_a_slow_root_is_one_5_percent_and_20_ms_over_the_median(
        case, no_history, caplog):
    steady, took, kept = SLOW_CASES[case]
    shape = dict(rows=1000, chunks=4, branch="chunks", classes=1)
    # the first call of a shape (it compiles) is neither held to anything
    # nor counted; eight steady calls log nothing
    assert _noted(int(steady * 40e6), caplog, **shape) == []
    for k in range(8):
        assert _noted(int(steady * 1e6) + 1000 * k, caplog, **shape) == []
    assert an.slow_calls() == []
    logged = _noted(int(took * 1e6), caplog, **shape)
    assert len(logged) == len(an.slow_calls()) == int(kept)
    if kept:
        rec, = an.slow_calls()
        assert rec["shape"] == shape
        assert rec["ms"] == pytest.approx(took)
        assert rec["median_ms"] == pytest.approx(steady, abs=0.01)
        assert rec["excess_ms"] == pytest.approx(took - steady, abs=0.01)
        line = logged[0].getMessage()
        assert "\n" not in line and line.startswith("slow call ")
        assert json.loads(line.removeprefix("slow call ")) == rec
    # another shape has a history of its own: its first call is not held
    # to this one's
    assert _noted(int(steady * 40e6), caplog, **dict(shape, rows=7)) == []
    # the rings are bounded
    assert an._slow.maxlen == an.SLOW_RING == 64
    assert all(h.maxlen == an.SLOW_HISTORY == 8
               for h in an._history.values())


def test_the_shapes_kept_are_bounded(no_history, monkeypatch):
    monkeypatch.setattr(an, "SLOW_SHAPES", 4)
    for rows in range(10):
        an.note_root(_ended_root(1000, rows=rows), SHAPE)
    assert len(an._history) == 4
    assert [k[1] for k in an._history] == [6, 7, 8, 9]


def test_a_call_slowed_by_its_fetch_is_kept_with_its_account(
        monkeypatch, caplog):
    """A 60 ms stall inside one chunk's fetch: the call lands in
    slow_calls() with `predict:fetch` holding the excess, and its end logs
    ONE warning, one line."""
    import logging

    from ddt_tpu.backends import tpu as tpu_backend

    monkeypatch.setattr(an, "_history", {})
    monkeypatch.setattr(an, "_slow", collections.deque(maxlen=an.SLOW_RING))
    be = get_backend(TrainConfig(backend="tpu", n_bins=31))
    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", 256)
    ens = _rand_ensemble(seed=4400)
    Xb = np.random.default_rng(14).integers(0, 31, size=(1000, 6),
                                            dtype=np.uint8)
    with caplog.at_level(logging.WARNING, logger=an.__name__):
        for _ in range(6):          # the first compiles; five steady
            be.predict_raw(ens, Xb)
        steady = [r["end"] - r["start"]
                  for r in an.root_spans("predict")[-5:]]

        class Stalling:
            """numpy, but the next asarray of a device array waits."""
            stalls = 1

            def __getattr__(self, name):
                return getattr(np, name)

            def asarray(self, a, *args, **kw):
                if self.stalls and isinstance(a, jax.Array):
                    self.stalls -= 1
                    time.sleep(0.060)
                return np.asarray(a, *args, **kw)

        monkeypatch.setattr(tpu_backend, "np", Stalling())
        be.predict_raw(ens, Xb)
        monkeypatch.setattr(tpu_backend, "np", np)
    slow_id = an.root_spans("predict")[-1]["id"]
    rec, = [r for r in an.slow_calls() if r["id"] == slow_id]
    assert rec["excess_ms"] >= 20 and rec["excess_ms"] >= 0.05 * rec[
        "median_ms"]
    assert rec["median_ms"] * 1e6 == pytest.approx(
        sorted(steady)[2], rel=1e-9)
    assert rec["account_ms"]["predict:fetch"] >= 59
    assert sum(rec["account_ms"].values()) == pytest.approx(rec["ms"])
    assert rec["longest"][0]["name"] == "predict:fetch"
    assert rec["longest"][0]["chunk"] == 0 and rec["longest"][0]["ms"] >= 59
    assert len(rec["longest"]) == 3
    assert rec["shape"] == dict(rows=1000, chunks=4, branch="chunks",
                                classes=1)
    assert tuple(rec["pauses"]) == tele_counters.HOST_COUNTERS
    mine = [r for r in caplog.records if r.name == an.__name__
            and f'"id": {slow_id},' in r.getMessage()]
    assert len(mine) == 1 and "\n" not in mine[0].getMessage()


def test_what_the_root_pays_for_its_pauses_and_its_history():
    """A generous guard, not a measurement (PERF.md section 6, PR 52 has
    that): the process's CPU time read at the call's two ends, the
    movement and the root's place in its shape's history, everything a
    scoring call's root gained in PR 52, stay under 100 us a call."""
    sp = _ended_root(1_000_000, rows=1, chunks=1, branch="one", classes=1)
    n = 3000
    t0 = time.perf_counter_ns()
    for _ in range(n):
        paused = tele_counters.host_pauses()
        sp.counts.update(tele_counters.host_pauses(paused))
        an.note_root(sp, SHAPE)
    each = (time.perf_counter_ns() - t0) / n
    assert each < 100_000, each


MODEL_BUILDS = {
    "heap": lambda: _rand_ensemble(seed=4500),
    "heap-routed": lambda: _routed(_rand_ensemble(seed=4501), 4502),
    "node-list": lambda: _small_node_list(4503),
    "forest": lambda: _small_forest(4504),
    "oblivious": lambda: _small_oblivious(4505),
}


@pytest.mark.parametrize("model,impl", [
    *((m, "auto") for m in sorted(MODEL_BUILDS)), ("heap", "lut")])
def test_the_stages_of_a_models_build_tile_its_ensemble_span(model, impl):
    """`compile`, `pack`, `upload`, in that order and apart, inside
    `ddt:predict:ensemble`, which keeps its own counts."""
    ens = MODEL_BUILDS[model]()
    be = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                 predict_impl=impl))
    Xb = np.random.default_rng(15).integers(0, 31, size=(300, 6),
                                            dtype=np.uint8)
    be.predict_raw(ens, Xb)
    root = an.root_spans("predict")[-1]
    kids = _by_name(root)
    built, = kids["ddt:predict:ensemble"]
    stages = [s for s in root["spans"] if s["cause"] == built["id"]]
    lut = impl == "lut" and model.startswith("heap")
    assert [s["name"].removeprefix("ddt:predict:ensemble:")
            for s in stages] == ["compile", "pack", "upload"]
    compile_, pack, upload = stages
    assert built["start"] <= compile_["start"] and upload["end"] \
        <= built["end"]
    assert compile_["end"] <= pack["start"] and pack["end"] \
        <= upload["start"]
    assert compile_["counts"]["trees"] == ens.n_trees
    assert compile_["counts"]["nodes"] == ens.n_nodes > 0
    assert ("subtrees" in compile_["counts"]) == (
        model in ("node-list", "forest"))
    if model == "forest":
        assert compile_["counts"]["subtrees"] >= ens.n_trees
    # the bytes issued are the bytes packed and the fill of the kernel's
    # last block of trees, which goes on inside the put
    assert 0 < pack["counts"]["bytes"] <= upload["counts"]["bytes"] \
        == built["counts"]["bytes"]
    assert be.resolved_predict_impl(ens.cache_token()) \
        == ("lut" if lut else "f32")
    # the stages are the span's time but for its own (the plan, imports)
    found = an.account(root)["self_ns"]
    assert sum(s["end"] - s["start"] for s in stages) + found[
        "predict:ensemble"] == built["end"] - built["start"]


# ------------------------------------------------------------------ #
# device stages
# ------------------------------------------------------------------ #

STAGES = {"predict:widen", "predict:tables", "predict:traverse",
          "predict:traverse_paths", "predict:traverse_oblivious",
          "predict:accumulate", "predict:link"}


def _small_oblivious(seed):
    from ddt_tpu.models.tree import random_oblivious

    return random_oblivious(np.random.default_rng(seed), 5, 3, 6, n_bins=31,
                            scale=0.5, bias=0.25)


def _small_forest(seed):
    """3 trees of 300 to 500 leaves with a 3-column vector a leaf: two or
    three sub-trees a tree."""
    from ddt_tpu.models.tree import random_node_list

    return random_node_list(np.random.default_rng(seed), 3, (300, 500), 6,
                            n_bins=31, leaf_columns=3)


def _small_softmax_node_list(seed):
    """6 round-major trees of 3 classes, 300 to 500 leaves each with a
    scalar a leaf: the sub-tree form, its link taken by the program."""
    from ddt_tpu.models.tree import random_node_list

    return random_node_list(np.random.default_rng(seed), 6, (300, 500), 6,
                            n_bins=31, learning_rate=0.5, base_score=0.25,
                            loss="softmax", n_classes=3)


def _routed(ens, seed):
    """`ens` with a NaN bin, learned directions and category features:
    both routing tables."""
    rng = np.random.default_rng(seed)
    ens.default_left = rng.random(ens.feature.shape) < 0.5
    ens.missing_bin = True
    ens.cat_features = np.asarray([1, 4], np.int32)
    return ens


# model -> (builder, the program it scores by)
STAGE_MODELS = {
    "heap": (lambda: _rand_ensemble(seed=3100),
             "jit_predict_raw_effective"),
    "heap-7class": (lambda: _rand_ensemble(seed=3101, T=14),
                    "jit_predict_raw_effective"),
    "heap-routed": (lambda: _routed(_rand_ensemble(seed=3102), 3103),
                    "jit_predict_raw_effective"),
    "node-list": (lambda: _small_node_list(3104),
                  "jit_predict_raw_effective_paths"),
    "oblivious": (lambda: _small_oblivious(3105),
                  "jit_predict_raw_effective_oblivious"),
    # an averaged forest: the sub-tree form (the chain and the class dot)
    "forest": (lambda: _small_forest(3106),
               "jit_predict_raw_effective_paths"),
    # softmax's round-major trees as a node list: the link on the device
    "softmax-node-list": (lambda: _small_softmax_node_list(3107),
                          "jit_predict_raw_effective_paths"),
}
STAGE_CASES = [(m, impl) for m in STAGE_MODELS
               for impl in ("pallas", "onehot")]


@pytest.mark.parametrize("model,impl", STAGE_CASES,
                         ids=[f"{m}-{i}" for m, i in STAGE_CASES])
def test_every_instruction_of_a_scoring_program_has_a_stage(
        model, impl, monkeypatch):
    """Interpreted Pallas and the jax.numpy forms, heap, node list and
    oblivious ensemble, with and without routing tables: what the program
    traced is under one of the named stages; `unscoped` holds what no
    source line made (parameters, constants, the compiler's copies and
    converts)."""
    # `source` is the line that FIRST traced an instruction in this
    # process: jnp's own jitted helpers (`%`, `jnp.pad`) keep the jaxpr,
    # source lines and all, of whichever module called them first with the
    # same shapes, so a trainer's test run earlier in this worker would
    # lend `ops/split.py` to a constant of `predict:tables`.
    jax.clear_caches()
    build, program = STAGE_MODELS[model]
    ens = build()
    if model == "heap-7class":
        ens.loss, ens.n_classes = "softmax", 7
    be = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                 predict_impl=impl))
    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", 256)
    # (the node list's jax.numpy forms scan the chunk's rows in two pieces:
    # what comes out of the scan is put together under a stage too)
    from ddt_tpu.ops import predict as predict_ops

    monkeypatch.setattr(predict_ops, "_PATHS_ROW_CHUNK", 128)
    Xb = np.random.default_rng(8).integers(0, 31, size=(600, 6),
                                           dtype=np.uint8)
    link = be.links_on_device(ens)
    scores = be.predict_raw(ens, Xb, link=link)
    np.testing.assert_allclose(
        scores, (ens.predict if link else ens.predict_raw)(Xb, binned=True),
        rtol=2e-4, atol=2e-5)

    stages = an.device_stages()
    held = stages[program]
    assert len(held) > 10
    seen = {e["stage"] for e in held.values()}
    assert seen <= STAGES | {an.UNSCOPED}
    traverse = "predict:traverse"
    if impl == "pallas":
        traverse = {"node-list": "predict:traverse_paths",
                    "forest": "predict:traverse_paths",
                    "softmax-node-list": "predict:traverse_paths",
                    "oblivious": "predict:traverse_oblivious"}.get(
                        model, traverse)
    assert {traverse, "predict:accumulate"} <= seen
    assert ("predict:link" in seen) == link     # the softmax, on the device
    # The kernels take the uint8 chunk as it is and widen a tile in VMEM:
    # no instruction of their programs is the widening (the heap kernel
    # since PR 36, the path kernel since PR 37, the oblivious kernel). The
    # jax.numpy forms still widen in XLA.
    assert ("predict:widen" in seen) != (impl == "pallas")
    for name, e in held.items():
        assert re.fullmatch(r"%[\w.-]+", name)
        assert e["op"].endswith(")")
        if e["stage"] == an.UNSCOPED:
            assert e["source"] == "", (name, e)     # nobody's source line
        elif e["source"]:
            assert re.fullmatch(r"ddt_tpu/ops/predict\w*\.py:\d+",
                                e["source"]), (name, e)
    assert any(e["op"].startswith("parameter(") for e in held.values())
    # the chunk loop's two small programs, each one stage as a whole
    assert stages["jit_dynamic_slice"] == {an.WHOLE_PROGRAM: {
        "stage": "predict:slice", "op": "program",
        "source": stages["jit_reshape"][an.WHOLE_PROGRAM]["source"]}}
    assert stages["jit_reshape"][an.WHOLE_PROGRAM]["stage"] \
        == "predict:unflatten"
    assert re.fullmatch(r"ddt_tpu/backends/tpu\.py:\d+",
                        stages["jit_reshape"][an.WHOLE_PROGRAM]["source"])


def test_the_stage_map_is_made_when_asked_and_once(monkeypatch):
    """predict_raw registers how to read the program and lowers nothing;
    device_stages() makes the map on its first call and keeps it; a new
    model of the same program name drops the kept map."""
    be = get_backend(TrainConfig(backend="tpu", n_bins=31,
                                 predict_impl="pallas"))
    monkeypatch.setattr(type(be), "PREDICT_ROW_CHUNK", 256)
    program = "jit_predict_raw_effective"
    Xb = np.random.default_rng(9).integers(0, 31, size=(600, 6),
                                           dtype=np.uint8)
    lowered = []

    def counted(name, hlo=None, **kw):
        if hlo is not None:
            made = hlo

            def hlo():
                lowered.append(name)
                return made()
        return register(name, hlo, **kw)

    register = an.stage_program
    from ddt_tpu.backends import tpu as tpu_backend
    monkeypatch.setattr(tpu_backend, "stage_program", counted)

    tele_counters.install_jax_listener()
    c0 = tele_counters.snapshot()
    # depth 4: a scoring program no other test of this file compiles
    be.predict_raw(_rand_ensemble(seed=3200, depth=4), Xb)
    be.predict_raw(_rand_ensemble(seed=3200, depth=4), Xb)  # resident now
    assert lowered == []
    assert program not in an._stage_maps
    assert callable(an._stage_programs[program])
    compiled_by_the_calls = tele_counters.delta(c0)["jit_compiles"]

    c1 = tele_counters.snapshot()
    first = an.device_stages()
    again = an.device_stages()
    assert lowered == [program]
    assert first[program] is again[program]
    # the executable is the one the calls compiled: jit's own cache
    assert compiled_by_the_calls >= 1
    assert tele_counters.delta(c1)["jit_compiles"] == 0

    # another model, same program name: the newest's map, made anew
    be.predict_raw(_rand_ensemble(seed=3201, T=70), Xb)
    assert program not in an._stage_maps
    assert lowered == [program]
    newest = an.device_stages()[program]
    assert lowered == [program, program]
    assert newest is not first[program]


NAME_STACK_ENTRIES = {
    "predict_raw_effective": dict(use_pallas=False),
    "predict_raw_effective-pallas": dict(use_pallas=True),
    "predict_raw": dict(use_pallas=False),
    "predict_raw-pallas": dict(use_pallas=True),
    "predict_raw_pallas": dict(),
}


@pytest.mark.parametrize("entry", sorted(NAME_STACK_ENTRIES))
def test_the_name_stack_holds_ddt_predict_once(entry):
    """One path of scopes from every entry point: `ddt:predict`, then the
    stage; never `ddt:predict/ddt:predict`."""
    from ddt_tpu.ops import predict as predict_ops
    from ddt_tpu.ops import predict_pallas

    ens = _rand_ensemble(seed=3300)
    Xb = np.random.default_rng(10).integers(0, 31, size=(64, 6),
                                            dtype=np.uint8)
    kw = dict(NAME_STACK_ENTRIES[entry], max_depth=ens.max_depth,
              learning_rate=0.1, base=0.3)
    if entry.startswith("predict_raw_effective"):
        ce = ens.compile(tree_chunk=64)
        lowered = predict_ops.predict_raw_effective.lower(
            *ce.arrays()[:4], Xb, **kw)
    else:
        fn = (predict_pallas.predict_raw_pallas
              if entry == "predict_raw_pallas" else predict_ops.predict_raw)
        lowered = fn.lower(ens.feature, ens.threshold_bin, ens.is_leaf,
                           ens.leaf_value, Xb, **kw)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    # whole paths only: a reduction's scalar computation keeps the tail
    scoped = [n for n in names if n.startswith("jit(") and "ddt:" in n]
    assert len(scoped) > 10
    for n in scoped:
        parts = n.split("/")
        assert parts.count("ddt:predict") == 1, n
        stages = [p for p in parts if p.startswith("ddt:predict:")]
        assert parts.index("ddt:predict") < parts.index(stages[0]) \
            if stages else True, n


def test_stages_of_hlo_reads_both_ways_a_text_names_its_source():
    """The tables at the head (`stack_frame_id`) and the inline form; the
    innermost stage wins; a phase's root scope alone is no stage."""
    text = """HloModule jit_f, is_scheduled=true

FileNames
1 "%(repo)sddt_tpu/ops/predict.py"
2 "/elsewhere/site.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=296 end_line=296 column=1 end_column=9}
2 {file_name_id=2 function_name_id=1 line=7 end_line=8 column=1 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}

%%fused_computation (p: u8[8,4]) -> s32[8,4] {
  %%p = u8[8,4]{1,0:T(8,128)(4,1)} parameter(0)
  ROOT %%convert.2 = s32[8,4]{1,0} convert(%%p), metadata={op_name="jit(f)/ddt:predict/ddt:predict:widen/convert_element_type" stack_frame_id=1}
}

ENTRY %%main (Xc.1: u8[8,4]) -> f32[8] {
  %%Xc.1 = u8[8,4]{0,1} parameter(0), metadata={op_name="Xc"}
  %%copy.5 = u8[8,4]{1,0:T(8,128)(4,1)} copy(%%Xc.1), metadata={op_name="Xc"}
  %%pad_convert_fusion = s32[8,4]{1,0} fusion(%%copy.5), kind=kLoop, calls=%%fused_computation, metadata={op_name="jit(f)/ddt:predict/ddt:predict:widen/jit(_pad)/pad" stack_frame_id=1}, backend_config={"a":{"b":"metadata={op_name=\\"x\\"}"}}
  %%copy-start = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%%pad_convert_fusion)
  %%while.1 = (s32[], f32[8]{0}) while(%%copy-start), condition=%%c, body=%%b, metadata={op_name="jit(f)/ddt:predict/ddt:predict:traverse/while/body/ddt:predict:accumulate/add" source_file="/elsewhere/site.py" source_line=12}
  %%rooted = f32[8]{0} add(%%while.1, %%while.1), metadata={op_name="jit(f)/ddt:predict/add" stack_frame_id=2}
  ROOT %%fusion.1 = f32[8]{0:T(1024)} fusion(%%rooted), kind=kLoop, calls=%%x, metadata={op_name="jit(f)/ddt:predict/ddt:predict:accumulate/mul" stack_frame_id=9}
}
""" % {"repo": an._REPO}
    assert an.stages_of_hlo(text) == {
        "%p": {"stage": "unscoped", "source": "", "op": "parameter(0)"},
        "%convert.2": {"stage": "predict:widen", "op": "convert(%p)",
                       "source": "ddt_tpu/ops/predict.py:296"},
        "%Xc.1": {"stage": "unscoped", "source": "", "op": "parameter(0)"},
        "%copy.5": {"stage": "unscoped", "source": "",
                    "op": "copy(%Xc.1)"},
        "%pad_convert_fusion": {"stage": "predict:widen",
                                "op": "fusion(%copy.5)",
                                "source": "ddt_tpu/ops/predict.py:296"},
        "%copy-start": {"stage": "unscoped", "source": "",
                        "op": "copy-start(%pad_convert_fusion)"},
        "%while.1": {"stage": "predict:accumulate",
                     "op": "while(%copy-start)",
                     "source": "/elsewhere/site.py:12"},
        "%rooted": {"stage": "unscoped", "op": "add(%while.1, %while.1)",
                    "source": "/elsewhere/site.py:7"},
        "%fusion.1": {"stage": "predict:accumulate", "source": "",
                      "op": "fusion(%rooted)"},
    }


# ------------------------------------------------------------------ #
# the compile listener
# ------------------------------------------------------------------ #

def test_listener_times_tracing_and_lowering_of_a_fresh_jit():
    tele_counters.install_jax_listener()
    c0 = tele_counters.snapshot()
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    moved = tele_counters.delta(c0)
    assert moved["jit_compiles"] >= 1
    assert moved["jit_trace_seconds"] > 0
    assert moved["jit_lower_seconds"] > 0
    assert moved["jit_compile_seconds"] > 0
    assert moved["compile_cache_hits"] == 0     # the suite's cache is off


def test_listener_counts_loads_from_the_persistent_cache():
    """jax reports a persistent-cache hit as a plain event; the suite
    keeps the cache off, so the event is fired by hand."""
    from jax import monitoring

    tele_counters.install_jax_listener()
    c0 = tele_counters.snapshot()
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event(
        "/jax/compilation_cache/compile_requests_use_cache")
    assert tele_counters.delta(c0)["compile_cache_hits"] == 1


# ------------------------------------------------------------------ #
# the operator's surface
# ------------------------------------------------------------------ #

def test_cli_predict_prints_phases_ms(tmp_path, capsys):
    from ddt_tpu.cli import main

    model = str(tmp_path / "ens.npz")
    assert main(["train", "--backend=tpu", "--dataset=higgs", "--rows=1500",
                 "--trees=3", "--depth=3", "--bins=31",
                 f"--out={model}"]) == 0
    capsys.readouterr()
    assert main(["predict", "--backend=tpu", f"--model={model}",
                 "--dataset=higgs", "--rows=400", "--bins=31"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # Not times: the traversal kernel's table plan, by the names its own
    # module lists for this line. On a CPU the auto dispatch takes the
    # one-hot path, so the kernel's group is 0, no group of it holds
    # trees, no table block, nothing streamed.
    from ddt_tpu.ops.predict_pallas import PHASES_COUNTS

    assert "rows_per_step" in PHASES_COUNTS
    for count in (*PHASES_COUNTS, "tables_streamed_bytes"):
        assert rec["phases_ms"].pop(count) == 0
    assert sorted(rec["phases_ms"]) == sorted(
        ["token", "ensemble", "upload", "dispatch", "fetch", "place"])
    assert all(v >= 0 for v in rec["phases_ms"].values())
    assert rec["phases_ms"]["upload"] > 0 and rec["phases_ms"]["fetch"] > 0
    assert sum(rec["phases_ms"].values()) <= rec["wallclock_s"] * 1e3

    # the NumPy backend opens no device call: nothing to break down
    assert main(["predict", "--backend=cpu", f"--model={model}",
                 "--dataset=higgs", "--rows=400", "--bins=31"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["phases_ms"] is None
