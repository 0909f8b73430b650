"""Declarative 2D (rows x features) mesh + host-sharded ingest (ISSUE
11, ROADMAP item 2).

Three contracts on the 8-virtual-device CPU mesh:

- **Structure identity at any (Pr, Pf).** Reduce-scatter split finding
  now COMPOSES with a sharded feature axis — the scatter runs over the
  row axes within each feature slab and ONE winner combine gathers over
  both axes by global flattened candidate index — so trees must be
  structure-identical to single-device at every mesh shape, including
  ragged F, softmax, missing-bin, categorical, and engineered exact
  ties.
- **Ownership.** The host-sharded chunk source
  (data.chunks.HostShardedChunks) must never let a process read a
  feature sub-shard it does not own, and the streamed trainer over it
  must reproduce the plain streamed path bitwise at the same logical
  chunk bounds.
- **Payload.** The second-axis-aware hist_allreduce_bytes model must
  show per-level collective payload <= 1/(Pr*Pf) of the
  replicated-feature allreduce baseline plus the O(Pr*Pf*nodes) winner
  term — the ISSUE 11 acceptance criterion, witnessed in-process.
"""

import numpy as np
import pytest

from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig, load_config_file
from ddt_tpu.data import chunks as chunks_lib
from ddt_tpu.data import datasets
from ddt_tpu.data.quantizer import quantize
from ddt_tpu.driver import Driver
from ddt_tpu.parallel import mesh as mesh_lib


def _fit(Xb, y, **kw):
    cfg = TrainConfig(n_trees=3, max_depth=4, n_bins=31, backend="tpu",
                      **kw)
    be = get_backend(cfg)
    return Driver(be, cfg, log_every=10 ** 9).fit(Xb, y), be


def _assert_structure_equal(e1, eN):
    np.testing.assert_array_equal(e1.feature, eN.feature)
    np.testing.assert_array_equal(e1.threshold_bin, eN.threshold_bin)
    np.testing.assert_array_equal(e1.is_leaf, eN.is_leaf)
    np.testing.assert_allclose(e1.leaf_value, eN.leaf_value,
                               rtol=2e-4, atol=2e-5)


MESH_SHAPES = [(1, 1), (2, 2), (4, 2), (1, 4)]


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES,
                         ids=[f"{pr}x{pf}" for pr, pf in MESH_SHAPES])
def test_mesh2d_structure_identity(mesh_shape):
    X, y = datasets.synthetic_binary(4096, n_features=10, seed=11)
    Xb, _ = quantize(X, n_bins=31, seed=11)
    e1, _ = _fit(Xb, y)
    eN, be = _fit(Xb, y, mesh_shape=mesh_shape)
    # The resolver composes: any mesh with a row wire scatters.
    pr, pf = mesh_shape
    want = "reduce_scatter" if pr > 1 else "allreduce"
    assert be.split_comms == want
    _assert_structure_equal(e1, eN)


@pytest.mark.parametrize("mesh_shape", [(2, 2), (2, 4), (1, 4)],
                         ids=["2x2", "2x4", "1x4"])
def test_mesh2d_ragged_features(mesh_shape):
    """F=9 does not divide Pf: upload pads all-zero columns, which must
    never win a split; reduce-scatter pads again over the row axes."""
    X, y = datasets.synthetic_binary(2048, n_features=9, seed=23)
    Xb, _ = quantize(X, n_bins=31, seed=23)
    e1, _ = _fit(Xb, y)
    eN, _ = _fit(Xb, y, mesh_shape=mesh_shape,
                 split_comms="reduce_scatter" if mesh_shape[0] > 1
                 else "auto")
    assert e1.feature.max() < 9
    _assert_structure_equal(e1, eN)


def test_mesh2d_softmax():
    X, y = datasets.synthetic_multiclass(1500, n_features=12, seed=3)
    Xb, _ = quantize(X, n_bins=31, seed=3)
    e1, _ = _fit(Xb, y, loss="softmax", n_classes=4)
    eN, be = _fit(Xb, y, loss="softmax", n_classes=4, mesh_shape=(2, 2))
    assert be.split_comms == "reduce_scatter"
    _assert_structure_equal(e1, eN)


def test_mesh2d_missing_bin():
    """missing_policy='learn': the direction-block tie-break (RIGHT
    before LEFT) must survive the two-axis winner combine."""
    X, y = datasets.synthetic_binary(3000, n_features=10, seed=7)
    X = X.copy()
    X[::7, 3] = np.nan
    X[::11, 6] = np.nan
    Xb, _ = quantize(X, n_bins=31, seed=7, missing_policy="learn")
    e1, _ = _fit(Xb, y, missing_policy="learn")
    eN, _ = _fit(Xb, y, missing_policy="learn", mesh_shape=(2, 2),
                 split_comms="reduce_scatter")
    _assert_structure_equal(e1, eN)
    np.testing.assert_array_equal(e1.default_left, eN.default_left)


def test_mesh2d_categorical_and_sampling():
    X, y = datasets.synthetic_binary(4096, n_features=10, seed=5)
    Xb, _ = quantize(X, n_bins=31, seed=5)
    kw = dict(cat_features=(1, 4), subsample=0.7, colsample_bytree=0.6)
    e1, _ = _fit(Xb, y, **kw)
    eN, _ = _fit(Xb, y, mesh_shape=(4, 2), **kw)
    _assert_structure_equal(e1, eN)


def test_mesh2d_duplicate_column_tie_break():
    """Engineered EXACT gain tie across feature shards: column 7 is a
    byte-for-byte copy of column 0, so their best candidates tie
    exactly. On the (2, 2) mesh the copies live on DIFFERENT feature
    shards and their slabs on different row shards — the combined
    winner must still be the single-device argmax's pick (the smallest
    global flattened candidate index: feature 0)."""
    X, y = datasets.synthetic_binary(2048, n_features=8, seed=13)
    Xb, _ = quantize(X, n_bins=31, seed=13)
    Xb = Xb.copy()
    Xb[:, 7] = Xb[:, 0]
    e1, _ = _fit(Xb, y)
    eN, _ = _fit(Xb, y, mesh_shape=(2, 2),
                 split_comms="reduce_scatter")
    _assert_structure_equal(e1, eN)
    # The tie itself must have been broken toward the lower global id
    # wherever the duplicated pair was the winner.
    split_feats = e1.feature[(~e1.is_leaf) & (e1.feature >= 0)]
    assert 7 not in split_feats


def test_mesh2d_fused_rounds_match_granular():
    """The fused multi-round scan on the 2D rs mesh grows bit-identical
    trees to the granular per-tree path (they share one grow_tree
    program; profile=True forces the granular loop)."""
    X, y = datasets.synthetic_binary(3000, n_features=10, seed=2)
    Xb, _ = quantize(X, n_bins=31, seed=2)
    cfg = TrainConfig(n_trees=4, max_depth=4, n_bins=31, backend="tpu",
                      mesh_shape=(2, 2))
    be = get_backend(cfg)
    fused = Driver(be, cfg, log_every=10 ** 9).fit(Xb, y)
    granular = Driver(be, cfg, log_every=10 ** 9, profile=True).fit(Xb, y)
    # Structure bitwise; leaves to tolerance — the scan context can
    # contract the leaf one-hot matmul differently than the standalone
    # program (the documented FMA-contraction seam, driver.py).
    _assert_structure_equal(granular, fused)


# ------------------------------------------------------------------ #
# config + layout plumbing
# ------------------------------------------------------------------ #

def test_mesh_shape_config_normalizes_and_conflicts():
    cfg = TrainConfig(mesh_shape=(4, 2))
    assert cfg.n_partitions == 4 and cfg.feature_partitions == 2
    # canonicalized to None: both spellings are byte-identical configs
    # (equal run ids / cache keys), and .replace() on partition fields
    # never false-conflicts.
    assert cfg.mesh_shape is None
    assert cfg == TrainConfig(n_partitions=4, feature_partitions=2)
    assert cfg.replace(n_partitions=4) == cfg
    # agreeing explicit values are fine
    TrainConfig(mesh_shape=(4, 2), n_partitions=4, feature_partitions=2)
    with pytest.raises(ValueError, match="conflicts"):
        TrainConfig(mesh_shape=(4, 2), n_partitions=2)
    with pytest.raises(ValueError, match="conflicts"):
        TrainConfig(mesh_shape=(4, 2), feature_partitions=4)
    with pytest.raises(ValueError, match="mesh_shape"):
        TrainConfig(mesh_shape=(4,))
    with pytest.raises(ValueError, match="mesh_shape"):
        TrainConfig(mesh_shape=(0, 2))


def test_mesh_shape_config_file(tmp_path):
    p = tmp_path / "cfg.json"
    p.write_text('{"mesh_shape": [2, 2], "n_trees": 3}')
    d = load_config_file(str(p))
    assert d["mesh_shape"] == (2, 2)
    cfg = TrainConfig(**d)
    assert cfg.n_partitions == 2 and cfg.feature_partitions == 2


def test_cli_mesh_shape_parse():
    from ddt_tpu.cli import _parse_mesh_shape

    assert _parse_mesh_shape(None) is None
    assert _parse_mesh_shape("4,2") == (4, 2)
    assert _parse_mesh_shape(" 4 , 2 ") == (4, 2)
    with pytest.raises(SystemExit):
        _parse_mesh_shape("4")
    with pytest.raises(SystemExit):
        _parse_mesh_shape("a,b")


def test_spec_layout_rules():
    P = mesh_lib.P
    lay = mesh_lib.SpecLayout(row_axes="rows", feature_axis="features")
    assert lay.binned_data() == P("rows", "features")
    assert lay.row_vector() == P("rows")
    assert lay.level_hist_scattered() == P(None, "rows")
    assert lay.specs("data", "grad", "mask") == (
        P("rows", "features"), P("rows"), P())
    # pod form: tuple row axes
    pod = mesh_lib.SpecLayout(row_axes=("hosts", "rows"),
                              feature_axis=None)
    assert pod.binned_data() == P(("hosts", "rows"), None)
    assert pod.spec("pred") == P(("hosts", "rows"), None)
    assert pod.spec("pred1d") == P(("hosts", "rows"))
    # single-device layout degenerates to replicated
    solo = mesh_lib.SpecLayout(row_axes=None)
    assert solo.binned_data() == P()
    # unmatched names fail loudly
    with pytest.raises(ValueError, match="no partition rule"):
        lay.spec("mystery_operand")


def test_make_mesh_2d_shapes():
    m = mesh_lib.make_mesh_2d(4, 2)
    assert m.axis_names == ("rows", "features")
    assert m.shape == {"rows": 4, "features": 2}
    m3 = mesh_lib.make_mesh_2d(2, 2, n_hosts=2)
    assert m3.axis_names == ("hosts", "rows", "features")
    with pytest.raises(ValueError, match="devices"):
        mesh_lib.make_mesh_2d(16, 2)


# ------------------------------------------------------------------ #
# payload model (the acceptance criterion's witness)
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("D, F, B, meshes", [
    (6, 1024, 255, [(2, 2), (4, 2), (2, 4), (8, 4)]),
    (3, 64, 15, [(4, 2)]),     # narrow and shallow: the winner term weighs
])
def test_hist_allreduce_bytes_2d_payload_bound(D, F, B, meshes):
    """Per-level collective payload on the 2D rs mesh must be
    <= 1/(Pr*Pf) of the replicated-feature allreduce baseline plus the
    winner term — and the resolved backend config must feed exactly
    this model (collective_bytes_per_tree)."""
    from ddt_tpu.telemetry.counters import hist_allreduce_bytes

    base = hist_allreduce_bytes(D, F, B, partitions=8, mode="allreduce")
    leaf_term = (1 << D) * 4 * 2
    for pr, pf in meshes:
        got = hist_allreduce_bytes(D, F, B, partitions=pr,
                                   feature_partitions=pf,
                                   mode="reduce_scatter")
        winner = sum(pr * pf * (1 << d) * 4 * 4 for d in range(D))
        assert got - winner - leaf_term <= \
            (base - leaf_term) / (pr * pf) + pr * B * 8 * D, \
            (pr, pf, got, base)
        # the whole payload, winner term included, stays near Pr*Pf
        assert base / got > 0.75 * pr * pf, (pr, pf, got, base)
    # back-compat: the pre-2D keyword surface is unchanged.
    assert hist_allreduce_bytes(D, F, B, partitions=8) == base
    assert hist_allreduce_bytes(
        D, F, B, partitions=8, mode="reduce_scatter") == \
        hist_allreduce_bytes(D, F, B, partitions=8,
                             mode="reduce_scatter", feature_partitions=1)


def test_backend_collective_bytes_uses_second_axis():
    cfg = TrainConfig(n_bins=31, max_depth=4, backend="tpu",
                      mesh_shape=(2, 2))
    be = get_backend(cfg)
    cfg1d = TrainConfig(n_bins=31, max_depth=4, backend="tpu",
                        n_partitions=4, split_comms="allreduce")
    be1d = get_backend(cfg1d)
    F = 1024
    got_2d = be.collective_bytes_per_tree(F)
    replicated = be1d.collective_bytes_per_tree(F)
    # <= 1/(Pr*Pf) of the replicated-feature baseline + winner/leaf
    # terms (the ISSUE 11 acceptance criterion).
    winner = sum(4 * (1 << d) * 4 * 4 for d in range(4))
    leaf = (1 << 4) * 4 * 2
    assert got_2d - winner - leaf <= (replicated - leaf) / 4


# ------------------------------------------------------------------ #
# host-sharded ingest: ownership + bitwise streaming + repartition
# ------------------------------------------------------------------ #

def _shard_dir(tmp_path, Xb, y, n_files):
    d = str(tmp_path / f"shards{n_files}")
    chunks_lib.shard_arrays(Xb, y, d, n_chunks=n_files)
    return d


def test_host_sharded_ownership_contract(tmp_path):
    X, y = datasets.synthetic_binary(1024, n_features=6, seed=1)
    Xb, _ = quantize(X, n_bins=15, seed=1)
    d = _shard_dir(tmp_path, Xb, y, 8)
    v0 = chunks_lib.HostShardedChunks(d, 4, process_index=0,
                                      process_count=2)
    v1 = chunks_lib.HostShardedChunks(d, 4, process_index=1,
                                      process_count=2)
    assert v0.n_chunks == 2
    assert v0.owned_slots(0) == [0, 1] and v1.owned_slots(0) == [2, 3]
    # no host reads a sub-shard it doesn't own
    with pytest.raises(PermissionError, match="ownership"):
        v0.read_part(0, 2)
    with pytest.raises(PermissionError, match="ownership"):
        v1.read_part(1, 0)
    # full-chunk reads are forbidden on multi-process views
    with pytest.raises(PermissionError, match="full-chunk"):
        v1(0)
    # labels stay a global side channel (y members only)
    np.testing.assert_array_equal(
        np.concatenate([v0.labels(c) for c in range(2)]), y)
    # assignment rotation moves ownership wholesale, coverage preserved
    v0.rotate_assignment()
    assert v0.assignment == (1, 1, 0, 0)
    assert v0.owned_slots(0) == [2, 3]
    # validation: bad groupings fail loudly
    with pytest.raises(ValueError, match="group"):
        chunks_lib.HostShardedChunks(d, 3, process_index=0,
                                     process_count=1)
    with pytest.raises(ValueError, match="multiple"):
        chunks_lib.HostShardedChunks(d, 4, process_index=0,
                                     process_count=3)


def test_host_sharded_streamed_bitwise_vs_plain(tmp_path):
    """Host-sharded streamed training == plain directory streaming at
    the same logical chunk bounds, BITWISE — and == the in-memory
    Driver in structure."""
    from ddt_tpu.streaming import fit_streaming

    X, y = datasets.synthetic_binary(4096, n_features=10, seed=11)
    Xb, _ = quantize(X, n_bins=31, seed=11)
    cfg = TrainConfig(n_trees=3, max_depth=4, n_bins=31, backend="tpu",
                      n_partitions=2)
    be = get_backend(cfg)

    d8 = _shard_dir(tmp_path, Xb, y, 8)      # 2 logical x 4 sub-shards
    src = chunks_lib.host_sharded_chunks(d8, shards_per_chunk=4)
    e_hs = fit_streaming(src, src.n_chunks, cfg, backend=be)

    d2 = _shard_dir(tmp_path, Xb, y, 2)      # same logical bounds
    e_dir = fit_streaming(chunks_lib.directory_chunks(d2), 2, cfg,
                          backend=be)
    for k in ("feature", "threshold_bin", "is_leaf", "leaf_value",
              "split_gain"):
        np.testing.assert_array_equal(getattr(e_dir, k),
                                      getattr(e_hs, k), err_msg=k)

    e_mem, _ = _fit(Xb, y, n_partitions=2)
    _assert_structure_equal(e_mem, e_hs)


def test_watchdog_streamed_repartition_bit_exact(tmp_path):
    """Injected straggler on the streamed device loop: the watchdog's
    ACTION fires at checkpoint-cadence boundaries (mesh rotation +
    resident-state reshard + chunk-cache drop) and the ensemble is
    bit-identical to an undisturbed run."""
    from ddt_tpu.robustness import faultplan
    from ddt_tpu.streaming import fit_streaming
    from ddt_tpu.telemetry.events import RunLog

    X, y = datasets.synthetic_binary(2048, n_features=8, seed=4)
    Xb, _ = quantize(X, n_bins=29, seed=4)
    d = _shard_dir(tmp_path, Xb, y, 4)
    cfg = TrainConfig(n_trees=6, max_depth=3, n_bins=29, backend="tpu",
                      n_partitions=2, seed=4,
                      straggler_repartition=True)
    be = get_backend(cfg)

    def src():
        return chunks_lib.host_sharded_chunks(d, shards_per_chunk=2)

    ref = fit_streaming(src(), 2, cfg, backend=be)
    rl = RunLog()
    prev = faultplan.activate(faultplan.load_plan({"faults": [
        {"site": "straggler", "device": 1, "delay_ms": 600000.0,
         "rounds": [1, 6], "times": 6}]}))
    try:
        chaotic = fit_streaming(
            src(), 2, cfg, backend=be, run_log=rl,
            checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2)
    finally:
        faultplan.deactivate(prev)
    for k in ("feature", "threshold_bin", "is_leaf", "leaf_value"):
        np.testing.assert_array_equal(getattr(ref, k),
                                      getattr(chaotic, k), err_msg=k)
    kinds = [e["kind"] for e in rl.events("fault")]
    assert "straggler_detected" in kinds
    assert "repartition" in kinds


def test_watchdog_repartition_2d_mesh_bit_exact(tmp_path):
    """The in-memory watchdog ACTION now covers the 2D mesh too:
    rotate_row_partitions rolls the ROW axis of the device grid
    (feature columns preserved), so an injected straggler on a
    (2, 2) mesh repartitions without perturbing the model."""
    from ddt_tpu import api
    from ddt_tpu.robustness import faultplan
    from ddt_tpu.telemetry.events import RunLog

    X, y = datasets.synthetic_binary(1600, n_features=8, seed=4)
    Xb, _ = quantize(X, n_bins=29, seed=4)
    cfg = TrainConfig(n_trees=6, max_depth=3, n_bins=29, backend="tpu",
                      mesh_shape=(2, 2), seed=4,
                      straggler_repartition=True)
    ref = api.train(Xb, y, cfg, binned=True)
    rl = RunLog()
    prev = faultplan.activate(faultplan.load_plan({"faults": [
        {"site": "straggler", "device": 1, "delay_ms": 600000.0,
         "rounds": [1, 6], "times": 6}]}))
    try:
        chaotic = api.train(Xb, y, cfg, binned=True, run_log=rl,
                            checkpoint_dir=str(tmp_path / "ck"),
                            checkpoint_every=2)
    finally:
        faultplan.deactivate(prev)
    for k in ("feature", "threshold_bin", "is_leaf", "leaf_value"):
        np.testing.assert_array_equal(getattr(ref.ensemble, k),
                                      getattr(chaotic.ensemble, k),
                                      err_msg=k)
    kinds = [e["kind"] for e in rl.events("fault")]
    assert "straggler_detected" in kinds
    assert "repartition" in kinds


def test_upload_row_shards_matches_upload():
    """Single-process assembly: upload_row_shards(parts) is the same
    device layout and values as upload(concat(parts))."""
    cfg = TrainConfig(n_bins=15, backend="tpu", n_partitions=2)
    be = get_backend(cfg)
    rng = np.random.default_rng(0)
    parts = [rng.integers(0, 15, size=(500, 6), dtype=np.uint8)
             for _ in range(2)]
    a = be.upload_row_shards(parts, 1000)
    b = be.upload(np.concatenate(parts))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.sharding == b.sharding
