"""Comms-lean split finding (ISSUE 10): reduce-scatter gain sharding,
compressed collectives, slab-pipelined overlap — parallel/comms.py and
its wiring through the fused rounds, the granular surface, and the
streaming trainers.

Contracts pinned here:
- default-path bit-identity: N-partition trees == 1-partition trees
  under split_comms=reduce_scatter (structure exact, leaf values to
  float tolerance — the same contract test_distributed.py holds for the
  allreduce path);
- reduce_scatter parity vs allreduce across classes x missing x ragged
  F/P remainders x streaming;
- bf16 / int32_fixed wire dtypes hold their COMPUTED error bound and
  the split-agreement contract; int32_fixed merges are bit-stable under
  reduction order (integer sums commute);
- slab-pipelined overlap phasing is BIT-identical (collectives are
  elementwise — phasing cannot change a single value);
- the corrected hist_allreduce_bytes counter witnesses the >= 2x
  per-level payload reduction IN-PROCESS on a multi-device run (the
  acceptance criterion, not a docs claim).
"""

import numpy as np
import pytest

from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.data import datasets
from ddt_tpu.data.quantizer import quantize
from ddt_tpu.driver import Driver
from ddt_tpu.parallel import comms
from ddt_tpu.telemetry import counters as tele_counters


def _fit(Xb, y, **kw):
    kw.setdefault("n_trees", 3)
    kw.setdefault("max_depth", 4)
    kw.setdefault("n_bins", 31)
    kw.setdefault("backend", "tpu")
    cfg = TrainConfig(**kw)
    be = get_backend(cfg)
    return Driver(be, cfg, log_every=10**9).fit(Xb, y), be


def _assert_same_structure(a, b):
    np.testing.assert_array_equal(a.feature, b.feature)
    np.testing.assert_array_equal(a.threshold_bin, b.threshold_bin)
    np.testing.assert_array_equal(a.is_leaf, b.is_leaf)
    np.testing.assert_array_equal(a.default_left, b.default_left)


def _assert_same_trees(a, b):
    _assert_same_structure(a, b)
    np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                               rtol=2e-4, atol=2e-5)


@pytest.fixture(scope="module")
def binary_data():
    X, y = datasets.synthetic_binary(4096, n_features=10, seed=11)
    Xb, _ = quantize(X, n_bins=31, seed=11)
    return Xb, y


# --------------------------------------------------------------------- #
# the collectives themselves
# --------------------------------------------------------------------- #

def test_reduce_scatter_matches_psum_slice():
    """reduce_scatter over the tuple (hosts, rows) pod axes: each shard
    holds its contiguous block of the full sum, in flattened axis
    order."""
    import jax

    P = jax.sharding.PartitionSpec
    mesh = jax.make_mesh((2, 4), ("hosts", "rows"))
    x = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)

    def f(a):
        return comms.reduce_scatter(a, ("hosts", "rows"), dim=1)

    g = jax.shard_map(f, mesh=mesh, in_specs=P(("hosts", "rows")),
                      out_specs=P(None, ("hosts", "rows")))
    out = np.asarray(g(x)).reshape(-1)
    np.testing.assert_allclose(out, x.sum(axis=0), rtol=1e-6)


def test_reduce_scatter_requires_alignment():
    import jax

    P = jax.sharding.PartitionSpec
    mesh = jax.make_mesh((8,), ("rows",))

    def f(a):
        return comms.reduce_scatter(a, "rows", dim=1)

    g = jax.shard_map(f, mesh=mesh, in_specs=P("rows"),
                      out_specs=P(None, "rows"))
    with pytest.raises(ValueError, match="multiple"):
        g(np.zeros((8, 12), np.float32))          # 12 % 8 != 0


def test_int32_fixed_merge_is_order_independent():
    """The int32_fixed selling point: quantized partials sum in INTEGER
    arithmetic, so any reduction order produces bitwise-identical merged
    histograms (f32 psum order was the old nondeterminism seam). Host
    twin of comms.hist_reduce's quantize -> int-sum -> dequantize."""
    rng = np.random.default_rng(0)
    P = 8
    parts = rng.standard_normal((P, 4, 5, 16, 2)).astype(np.float32)
    m = np.abs(parts).max()
    cap = ((1 << 30) - 1) // P
    q = np.round(parts / (m / cap)).astype(np.int64)
    orders = [np.arange(P), np.arange(P)[::-1],
              rng.permutation(P), rng.permutation(P)]
    sums = [q[o].cumsum(axis=0)[-1] for o in orders]
    for s in sums[1:]:
        np.testing.assert_array_equal(sums[0], s)   # bitwise


@pytest.mark.parametrize("dtype", ["bf16", "int32_fixed"])
def test_hist_reduce_holds_computed_error_bound(dtype):
    """Merged histograms under a compressed wire dtype sit within
    comms.comms_error_bound of the exact f32 merge."""
    import jax

    P = jax.sharding.PartitionSpec
    n_dev = 8
    mesh = jax.make_mesh((n_dev,), ("rows",))
    rng = np.random.default_rng(3)
    parts = rng.standard_normal((n_dev, 2, 6, 16, 2)).astype(np.float32)

    def f(a):
        return comms.hist_reduce(a[0], "rows", comms_dtype=dtype)

    g = jax.shard_map(f, mesh=mesh, in_specs=P("rows"),
                      out_specs=P())
    got = np.asarray(g(parts))
    exact = parts.astype(np.float64).sum(axis=0)
    bound = comms.comms_error_bound(dtype, n_dev, float(np.abs(parts).max()))
    assert bound > 0
    assert float(np.abs(got - exact).max()) <= bound


def test_comms_error_bound_f32_is_zero():
    assert comms.comms_error_bound("f32", 8, 123.0) == 0.0
    with pytest.raises(ValueError):
        comms.comms_error_bound("fp8", 8, 1.0)


def test_combine_shard_winners_global_tiebreak():
    """Cross-shard combine reproduces the single-device argmax exactly:
    max gain, ties broken by the smallest GLOBAL flattened candidate
    index — including the missing-bin rule that the RIGHT-direction
    block precedes the LEFT block regardless of shard."""
    import jax
    import jax.numpy as jnp

    P = jax.sharding.PartitionSpec
    mesh = jax.make_mesh((2,), ("rows",))
    # Shard 0 proposes feature 0 with dl=True; shard 1 proposes feature
    # 5 with dl=False — equal gains. Global flattened order puts the
    # RIGHT (dl=False) block first, so shard 1 must win under
    # missing_bin even though shard 0 comes first.
    gains = np.array([[2.0], [2.0]], np.float32)
    feats = np.array([[0], [5]], np.int32)
    bins_ = np.array([[3], [1]], np.int32)
    dls = np.array([[True], [False]])

    def f(g, ft, b, d):
        return comms.combine_shard_winners(
            g[0], ft[0], b[0], d[0], "rows",
            n_features=8, n_bins=16, missing_bin=True)

    # check_vma=False as in the backend's grow programs: the outputs are
    # replicated by construction, but the static checker cannot see
    # through the gathered argmax.
    g = jax.shard_map(
        f, mesh=mesh, in_specs=(P("rows"),) * 4,
        out_specs=(P(), P(), P(), P()), check_vma=False)
    ga, fa, ba, da = (np.asarray(x) for x in g(
        jnp.asarray(gains), jnp.asarray(feats), jnp.asarray(bins_),
        jnp.asarray(dls)))
    assert fa[0] == 5 and ba[0] == 1 and not da[0]
    # Same-direction tie: smallest feature wins regardless of shard.
    dls2 = np.array([[False], [False]])
    ga, fa, ba, da = (np.asarray(x) for x in g(
        jnp.asarray(gains), jnp.asarray(feats), jnp.asarray(bins_),
        jnp.asarray(dls2)))
    assert fa[0] == 0 and ba[0] == 3


def test_resolve_split_comms():
    assert comms.resolve_split_comms(
        "auto", distributed=True) == "reduce_scatter"
    assert comms.resolve_split_comms(
        "auto", distributed=False) == "allreduce"
    # ISSUE 11: reduce-scatter COMPOSES with a sharded feature axis on
    # the 2D mesh — the old refusal is gone; the resolver keys on
    # whether a ROW wire exists.
    assert comms.resolve_split_comms(
        "auto", distributed=True, feature_partitions=2,
        row_shards=4) == "reduce_scatter"
    assert comms.resolve_split_comms(
        "reduce_scatter", distributed=True, feature_partitions=2,
        row_shards=4) == "reduce_scatter"
    # A pure feature mesh (Pr=1) has no row wire: nothing to scatter.
    assert comms.resolve_split_comms(
        "auto", distributed=True, feature_partitions=4,
        row_shards=1) == "allreduce"
    assert comms.resolve_split_comms(
        "reduce_scatter", distributed=True,
        row_shards=1) == "allreduce"
    assert comms.resolve_split_comms(
        "reduce_scatter", distributed=False) == "allreduce"
    with pytest.raises(ValueError, match="split_comms"):
        comms.resolve_split_comms("ring", distributed=True)


def test_config_validates_comms_fields():
    with pytest.raises(ValueError, match="split_comms"):
        TrainConfig(split_comms="ring")
    with pytest.raises(ValueError, match="hist_comms_dtype"):
        TrainConfig(hist_comms_dtype="fp8")
    with pytest.raises(ValueError, match="hist_comms_slabs"):
        TrainConfig(hist_comms_slabs=-1)


# --------------------------------------------------------------------- #
# bit-identity + parity (the acceptance contracts)
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("n_partitions", [2, 8])
def test_reduce_scatter_partitioned_equals_single(n_partitions,
                                                  binary_data):
    Xb, y = binary_data
    e1, _ = _fit(Xb, y)
    eN, be = _fit(Xb, y, n_partitions=n_partitions,
                  split_comms="reduce_scatter")
    assert be.split_comms == "reduce_scatter"
    _assert_same_trees(e1, eN)


def test_auto_resolves_reduce_scatter_on_mesh(binary_data):
    Xb, y = binary_data
    e1, _ = _fit(Xb, y)
    eA, be = _fit(Xb, y, n_partitions=8)            # default split_comms
    assert be.split_comms == "reduce_scatter"
    _assert_same_trees(e1, eA)


def test_reduce_scatter_pod_mesh_ragged_features():
    """(hosts, rows) tuple axes + F=9 over 8 row shards: the scatter
    pads F to 16, the pad columns are masked out of gain, and the
    combine maps slab winners back to global ids."""
    X, y = datasets.synthetic_binary(4001, n_features=9, seed=5)
    Xb, _ = quantize(X, n_bins=31, seed=5)
    e1, _ = _fit(Xb, y)
    eP, be = _fit(Xb, y, host_partitions=2, n_partitions=4,
                  split_comms="reduce_scatter")
    assert be.split_comms == "reduce_scatter"
    _assert_same_trees(e1, eP)
    assert e1.feature.max() < 9


@pytest.mark.parametrize("case", ["softmax", "missing"])
def test_reduce_scatter_parity_vs_allreduce(case):
    kw = {}
    if case == "softmax":
        X, y = datasets.synthetic_multiclass(2000, n_features=12, seed=3)
        kw = dict(loss="softmax", n_classes=3)
    else:
        X, y = datasets.synthetic_binary(3000, n_features=7, seed=9)
        X = X.copy()
        X[::11, 2] = np.nan
        kw = dict(missing_policy="learn")
    Xb, _ = quantize(X, n_bins=31, seed=3,
                     missing_policy=("learn" if case == "missing"
                                     else "zero"))
    ar, _ = _fit(Xb, y, n_partitions=8, split_comms="allreduce", **kw)
    rs, _ = _fit(Xb, y, n_partitions=8, split_comms="reduce_scatter", **kw)
    _assert_same_structure(ar, rs)
    np.testing.assert_allclose(ar.leaf_value, rs.leaf_value,
                               rtol=2e-4, atol=2e-5)


def test_reduce_scatter_streaming_matches_in_memory(binary_data):
    """The streamed device loop under an rs mesh grows the in-memory
    trainer's exact trees (the streamed==in-memory contract, extended
    to the scattered collective)."""
    from ddt_tpu.streaming import fit_streaming

    Xb, y = binary_data

    def chunk_fn(c):
        s = slice(c * 1024, (c + 1) * 1024)
        return Xb[s], y[s]

    e_mem, _ = _fit(Xb, y)
    cfg = TrainConfig(n_trees=3, max_depth=4, n_bins=31, backend="tpu",
                      n_partitions=4, split_comms="reduce_scatter")
    e_str = fit_streaming(chunk_fn, 4, cfg)
    _assert_same_structure(e_mem, e_str)


@pytest.mark.parametrize("dtype", ["bf16", "int32_fixed"])
def test_compressed_wire_split_agreement(dtype, binary_data):
    """Split-agreement contract: on well-separated data (gains far above
    the computed wire-error bound) the compressed merge picks identical
    splits in BOTH collective modes."""
    Xb, y = binary_data
    e1, _ = _fit(Xb, y)
    for mode in ("allreduce", "reduce_scatter"):
        eC, _ = _fit(Xb, y, n_partitions=8, split_comms=mode,
                     hist_comms_dtype=dtype)
        _assert_same_structure(e1, eC)


def test_slab_pipelined_overlap_is_bitwise(binary_data):
    """Overlap phasing must be invisible: f32/bf16 collectives are
    elementwise, so slabs=3 and slabs=1 produce BIT-identical models
    (leaf values included — stronger than the cross-partition
    contract)."""
    Xb, y = binary_data
    for mode, dtype in (("allreduce", "f32"), ("reduce_scatter", "f32"),
                        ("reduce_scatter", "bf16")):
        eA, _ = _fit(Xb, y, n_partitions=8, split_comms=mode,
                     hist_comms_dtype=dtype, hist_comms_slabs=1)
        eB, _ = _fit(Xb, y, n_partitions=8, split_comms=mode,
                     hist_comms_dtype=dtype, hist_comms_slabs=3)
        _assert_same_structure(eA, eB)
        np.testing.assert_array_equal(eA.leaf_value, eB.leaf_value)


def test_slab_pipelined_int32_fixed_split_agreement(binary_data):
    """int32_fixed derives its fixed-point scale PER collective, so
    slab phasing changes the quantization grid (documented carve-out —
    parallel/comms.hist_reduce): not bitwise vs slabs=1, but the grids
    stay inside the error bound and split agreement holds on
    well-separated data."""
    Xb, y = binary_data
    eA, _ = _fit(Xb, y, n_partitions=8, hist_comms_dtype="int32_fixed",
                 hist_comms_slabs=1)
    eB, _ = _fit(Xb, y, n_partitions=8, hist_comms_dtype="int32_fixed",
                 hist_comms_slabs=3)
    _assert_same_structure(eA, eB)
    np.testing.assert_allclose(eA.leaf_value, eB.leaf_value,
                               rtol=2e-4, atol=2e-5)


def test_resolve_comms_slabs():
    assert comms.resolve_comms_slabs(0, distributed=False) == 1
    assert comms.resolve_comms_slabs(
        0, distributed=True, platform="cpu") == 1
    assert comms.resolve_comms_slabs(
        0, distributed=True, platform="tpu") == comms._AUTO_SLABS
    assert comms.resolve_comms_slabs(5, distributed=False) == 5
    with pytest.raises(ValueError):
        comms.resolve_comms_slabs(-2, distributed=True)


# --------------------------------------------------------------------- #
# streamed sibling subtraction (the PR 6 leftover)
# --------------------------------------------------------------------- #

def test_streamed_subtraction_matches_in_memory(binary_data):
    """Both streaming loops with hist_subtraction=on grow the in-memory
    subtraction trainer's trees — half the streamed histogram payload
    per level >= 1 (left children only; right assembled on host)."""
    from ddt_tpu.streaming import fit_streaming

    Xb, y = binary_data

    def chunk_fn(c):
        s = slice(c * 1024, (c + 1) * 1024)
        return Xb[s], y[s]

    e_mem, _ = _fit(Xb, y, hist_subtraction="on")
    e_plain, _ = _fit(Xb, y)
    _assert_same_structure(e_mem, e_plain)   # the trick changes nothing
    cfg = TrainConfig(n_trees=3, max_depth=4, n_bins=31,
                      hist_subtraction="on")
    for backend in ("tpu", "cpu"):           # device + host loops
        e_str = fit_streaming(chunk_fn, 4, cfg.replace(backend=backend))
        _assert_same_structure(e_mem, e_str)


def test_streamed_subtraction_on_mesh(binary_data):
    from ddt_tpu.streaming import fit_streaming

    Xb, y = binary_data

    def chunk_fn(c):
        s = slice(c * 1024, (c + 1) * 1024)
        return Xb[s], y[s]

    e_mem, _ = _fit(Xb, y, hist_subtraction="on")
    cfg = TrainConfig(n_trees=3, max_depth=4, n_bins=31, backend="tpu",
                      n_partitions=4, hist_subtraction="on")
    e_str = fit_streaming(chunk_fn, 4, cfg)
    _assert_same_structure(e_mem, e_str)


# --------------------------------------------------------------------- #
# the corrected payload counter (acceptance witness)
# --------------------------------------------------------------------- #

def test_hist_allreduce_bytes_back_compat():
    """Positional-only calls return the historical estimate exactly."""
    assert tele_counters.hist_allreduce_bytes(2, 3, 4) \
        == (1 + 2) * 3 * 4 * 8 + 4 * 8


def test_hist_allreduce_bytes_effective_model():
    base = tele_counters.hist_allreduce_bytes(4, 8, 16)
    # Subtraction halves levels >= 1 (histogram part only).
    sub = tele_counters.hist_allreduce_bytes(4, 8, 16, subtraction=True)
    leaf = (1 << 4) * 8
    hist_base = base - leaf
    expected_sub = sum(
        ((1 << d) if d == 0 else (1 << d) // 2) * 8 * 16 * 8
        for d in range(4))
    assert sub == expected_sub + leaf
    assert sub < hist_base  # strictly less traffic
    # bf16 halves the histogram bytes.
    bf = tele_counters.hist_allreduce_bytes(4, 8, 16, comms_dtype="bf16")
    assert bf == (hist_base // 2) + leaf
    # reduce_scatter over 8 shards: per-device slab + winner tuples.
    rs = tele_counters.hist_allreduce_bytes(4, 8, 16, partitions=8,
                                            mode="reduce_scatter")
    assert rs < base
    assert base / rs >= 2.0


def test_collective_counter_witnesses_2x_reduction(binary_data):
    """The acceptance criterion, witnessed in-process: a multi-device
    training run under reduce_scatter records <= half the allreduce
    mode's collective bytes through the CORRECTED counter."""
    Xb, y = binary_data
    deltas = {}
    for mode in ("allreduce", "reduce_scatter"):
        s0 = tele_counters.snapshot()
        _, be = _fit(Xb, y, n_partitions=8, split_comms=mode)
        deltas[mode] = tele_counters.delta(s0)["collective_bytes_est"]
        assert deltas[mode] == 3 * be.collective_bytes_per_tree(10)
    assert deltas["allreduce"] / deltas["reduce_scatter"] >= 2.0


def test_partition_phases_carry_effective_bytes(binary_data, tmp_path):
    """Mesh runs' partition_phases events carry the EFFECTIVE (mode-
    aware) payload estimate, and the manifest carries the resolved comms
    extras the report's comms line renders."""
    import json

    from ddt_tpu.telemetry.report import read_events, render, summarize

    Xb, y = binary_data
    log = tmp_path / "run.jsonl"
    cfg = TrainConfig(n_trees=2, max_depth=3, n_bins=31, backend="tpu",
                      n_partitions=8)
    be = get_backend(cfg)
    Driver(be, cfg, log_every=10**9, run_log=str(log)).fit(Xb, y)
    events = read_events(str(log))
    man = next(e for e in events if e["event"] == "run_manifest")
    assert man["split_comms"] == "reduce_scatter"
    assert man["hist_comms_dtype"] == "f32"
    parts = [e for e in events if e["event"] == "partition_phases"]
    assert parts
    per_tree = be.collective_bytes_per_tree(10)
    for p in parts:
        for lane in p["partitions"]:
            assert lane["hist_allreduce_bytes"] \
                == per_tree * p.get("rounds", 1)
    s = summarize(events)
    assert s["comms"]["split_comms"] == "reduce_scatter"
    text = render(s)
    assert "split_comms=reduce_scatter" in text
    json.dumps(s)                                  # JSON-clean


def test_roofline_comms_row():
    """roofline_table renders a comms row from the effective collective
    bytes: verdict 'comms' when the wire utilization rivals the carrying
    phase's HBM leg, 'overlapped' when hidden."""
    from ddt_tpu.telemetry.costmodel import roofline_table

    phases = [{"phase": "hist", "ms_total": 1000.0, "ms_per_call": 10.0,
               "calls": 100, "share": 1.0}]
    cost = [{"op": "hist", "phase": "hist", "flops": 1e9,
             "bytes_accessed": 1e6, "calls": 100, "platform": "cpu",
             "device_kind": "cpu"}]
    hot = roofline_table(phases, cost,
                         counters={"collective_bytes_est": int(20e9)},
                         wallclock_s=1.0)
    row = next(r for r in hot if r["phase"] == "comms")
    assert row["verdict"] == "comms"
    assert row["coll_util"] > 0
    cold = roofline_table(phases, cost,
                          counters={"collective_bytes_est": 10_000},
                          wallclock_s=1.0)
    row = next(r for r in cold if r["phase"] == "comms")
    assert row["verdict"] == "overlapped"
    none = roofline_table(phases, cost, counters={}, wallclock_s=1.0)
    assert all(r["phase"] != "comms" for r in none)
