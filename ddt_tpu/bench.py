"""Benchmark harness (SURVEY.md §2 "Benchmark harness", §3 "Benchmark entry").

Measures the BASELINE.json metrics:
- `histogram`: HistogramBuilder throughput, M-rows/sec/chip — warm-up jit,
  then time K iterations of build_histograms alone (isolates metric #1 from
  the driver loop, matching the reference's "CPU-reference histogram
  throughput" comparison).
- `train`: end-to-end Higgs-style 100-tree build wallclock.
- `predict`: batch ensemble scoring rows/sec (the 10M-row × 1000-tree config).

All entry points return plain dicts; the CLI and the repo-root bench.py emit
them as JSON lines.
"""

from __future__ import annotations

import time

import numpy as np

from ddt_tpu.config import TrainConfig
from ddt_tpu.telemetry import counters as tele_counters


def _hist_inputs(rows, features, bins, n_nodes, seed):
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    g = rng.standard_normal(rows).astype(np.float32)
    h = rng.random(rows).astype(np.float32) + 0.5
    node_index = rng.integers(0, n_nodes, size=rows).astype(np.int32)
    return Xb, g, h, node_index


def bench_histogram(
    backend: str = "tpu",
    rows: int = 1_000_000,
    features: int = 28,
    bins: int = 255,
    n_nodes: int = 32,
    iters: int = 10,
    partitions: int = 1,
    hist_impl: str = "auto",
    seed: int = 0,
    reps: int = 3,
) -> dict:
    """Time the HistogramBuilder kernel. n_nodes=32 ≈ the deepest (widest)
    level of the depth-6 Higgs config — the shape that dominates runtime.

    min-of-`reps` timing on BOTH backends: a single rep under- or
    over-states either side (run-to-run spread on the chip: not measured;
    the CPU shares a noisy VM). The minimum is
    the closest observable to true kernel time, applied symmetrically."""
    from ddt_tpu.backends import get_backend

    cfg = TrainConfig(
        n_bins=bins, backend=backend, n_partitions=partitions,
        hist_impl=hist_impl,
    )
    be = get_backend(cfg)
    tele_counters.install_jax_listener()
    c0 = tele_counters.snapshot()
    Xb, g, h, node_index = _hist_inputs(rows, features, bins, n_nodes, seed)

    data = be.upload(Xb)
    dt = float("inf")
    if backend == "tpu":
        from ddt_tpu.utils.device import device_sync as sync

        g_d = be._put_rows(g)
        h_d = be._put_rows(h)
        ni_d = be._put_rows(node_index)
        out = be.build_histograms(data, g_d, h_d, ni_d, n_nodes)
        sync(out)                           # warm-up: compile + first run
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = be.build_histograms(data, g_d, h_d, ni_d, n_nodes)
            sync(out)
            dt = min(dt, (time.perf_counter() - t0) / iters)
    else:
        be.build_histograms(data, g, h, node_index, n_nodes)  # warm caches
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                be.build_histograms(data, g, h, node_index, n_nodes)
            dt = min(dt, (time.perf_counter() - t0) / iters)

    if backend == "tpu":
        from ddt_tpu.ops.histogram import resolve_hist_impl

        impl = resolve_hist_impl(
            hist_impl, n_nodes=n_nodes, n_features=features, n_bins=bins
        )
    else:
        impl = "native-c++" if getattr(be, "_native", None) else "numpy"

    n_chips = max(1, partitions)
    mrows = rows / dt / 1e6 / n_chips
    out = {
        "kernel": "histogram",
        "backend": backend,
        "impl": impl,
        "rows": rows, "features": features, "bins": bins, "n_nodes": n_nodes,
        "iters": iters, "partitions": partitions,
        "sec_per_build": dt,
        "mrows_per_sec_per_chip": mrows,
        # Telemetry counter: compiles triggered by this bench — a value
        # above the expected warm-up compile means the timed loop is
        # recompiling (shape churn), which invalidates the throughput.
        "jit_compiles": tele_counters.delta(c0)["jit_compiles"],
    }
    if backend == "tpu" and partitions == 1:
        # Roofline stamp (cost-observatory satellite): XLA's own cost
        # model for the measured program joined against the measured
        # per-build wallclock — achieved/peak fractions the benchwatch
        # sentinel can band (a silent dispatch regression shows up as a
        # utilization collapse even when absolute Mrows/s drift hides it).
        out.update(_roofline_util(
            "hist",
            lambda d, gg, hh, ni: be.build_histograms(d, gg, hh, ni,
                                                      n_nodes),
            (data, g_d, h_d, ni_d), dt))
    return out


def _roofline_util(prefix: str, fn, args: tuple,
                   sec_per_call: float) -> dict:
    """{<prefix>_roofline_flops_util, <prefix>_roofline_hbm_util} from
    costmodel.analyze of the measured program at the measured per-call
    wallclock (arrays ride as real arguments, never closure constants —
    XLA would fold constants out of the cost model). Returns {} when the
    analysis fails (capture must never fail a bench)."""
    from ddt_tpu.telemetry import costmodel

    rec = costmodel.analyze(fn, *args)
    if rec.get("error") or sec_per_call <= 0:
        return {}
    peaks = costmodel.peaks_for(rec.get("device_kind"))
    return {
        f"{prefix}_roofline_flops_util":
            round(rec["flops"] / sec_per_call / 1e9 / peaks["gflops"], 5),
        f"{prefix}_roofline_hbm_util":
            round(rec["bytes_accessed"] / sec_per_call / 1e9
                  / peaks["gbs"], 5),
    }


def _paired_ab_reps(bout, key_a, key_b, reps: int):
    """Order-alternating PAIRED reps — the ONE home of the two-arm A/B
    timing protocol that survives run-to-run bands (both arms of a
    pair share the band, so the per-rep ratio is robust where cross-run
    comparisons are not; alternating the order cancels residual
    within-pair drift). `bout(key)` runs and
    times one bout of that arm. Returns ({key: [dt, ...]},
    [dt_a / dt_b per rep]) — callers reduce per-arm dts (min or median)
    and take the median of the ratios as the A/B evidence."""
    dts = {key_a: [], key_b: []}
    ratios = []
    for rep in range(reps):
        order = (key_a, key_b) if rep % 2 == 0 else (key_b, key_a)
        pair = {}
        for k in order:
            pair[k] = bout(k)
            dts[k].append(pair[k])
        ratios.append(pair[key_a] / pair[key_b])
    return dts, ratios


def bench_histogram_ab(
    bins_a: int = 255,
    bins_b: int = 64,
    rows: int = 1_000_000,
    features: int = 28,
    n_nodes: int = 32,
    iters: int = 10,
    reps: int = 8,
    seed: int = 0,
) -> dict:
    """PAIRED two-arm histogram timing on the device backend.

    Where wallclock drifts in bands, even interleaved min-of-reps can
    compare arms across bands and reverse a conclusion run to run
    (experiments/hist_ab_paired.py). The robust statistic is the
    PER-REP PAIRED RATIO with the arm order alternating every rep: both
    arms of a pair share the band, so the median of ratios survives
    run-to-run drift. Per-arm throughputs are min-of-reps as before
    (the headline number); the ratio field is the A/B evidence."""
    from ddt_tpu.backends import get_backend
    from ddt_tpu.utils.device import device_sync as sync

    arms = {}
    for bins in (bins_a, bins_b):
        be = get_backend(TrainConfig(n_bins=bins, backend="tpu"))
        Xb, g, h, ni = _hist_inputs(rows, features, bins, n_nodes, seed)
        args = (be.upload(Xb), be._put_rows(g), be._put_rows(h),
                be._put_rows(ni))
        sync(be.build_histograms(*args, n_nodes))   # compile + first run
        arms[bins] = {"be": be, "args": args}

    def bout(bins):
        be, args = arms[bins]["be"], arms[bins]["args"]
        t0 = time.perf_counter()
        for _ in range(iters):
            out = be.build_histograms(*args, n_nodes)
        sync(out)
        return (time.perf_counter() - t0) / iters

    dts, ratios = _paired_ab_reps(bout, bins_a, bins_b, reps)
    dt_a, dt_b = min(dts[bins_a]), min(dts[bins_b])
    m_a, m_b = rows / dt_a / 1e6, rows / dt_b / 1e6
    out = {
        "kernel": "histogram_ab",
        "rows": rows, "features": features, "n_nodes": n_nodes,
        "bins_a": bins_a, "bins_b": bins_b,
        "mrows_a": m_a, "mrows_b": m_b,
        "ratio_b_over_a": float(np.median(ratios)),   # median paired ratio
    }
    # Roofline stamp for the headline (255-bin) arm: XLA's cost model at
    # the arm's measured per-build wallclock (cost-observatory satellite;
    # benchwatch bands the utilization fractions).
    be_a, args_a = arms[bins_a]["be"], arms[bins_a]["args"]
    out.update(_roofline_util(
        "hist",
        lambda d, gg, hh, ni: be_a.build_histograms(d, gg, hh, ni,
                                                    n_nodes),
        args_a, dt_a))
    return out


def bench_hist_fused_ab(
    rows: int = 1_000_000,
    features: int = 28,
    bins: int = 255,
    depth: int = 6,
    iters: int = 4,
    reps: int = 8,
    seed: int = 0,
) -> dict:
    """PAIRED fused-round A/B: the whole per-tree level loop
    (ops/grow.grow_tree — hist -> [subtract] -> gain -> route, one
    dispatch) with the sibling-subtraction trick ON vs OFF, at the
    Higgs-1M depth-6 shape. Same statistic as bench_histogram_ab (the
    one that survives run-to-run bands): per-rep PAIRED
    ratio with the arm order alternating every rep, median-of-ratios as
    the A/B evidence, min-of-reps per-arm timing as the headline.
    ratio_on_over_off > 1 means subtraction is winning; ~1.0 means the
    trick silently fell out of the dispatch (the floor's target).
    Throughputs are NOMINAL hist-row-equivalents (rows x depth levels /
    sec) so the two arms share a unit."""
    import functools

    import jax
    import jax.numpy as jnp

    from ddt_tpu.ops import grow as grow_ops
    from ddt_tpu.utils.device import device_sync as sync

    rng = np.random.default_rng(seed)
    Xb = jnp.asarray(rng.integers(0, bins, size=(rows, features),
                                  dtype=np.uint8))
    g = jnp.asarray(rng.standard_normal(rows).astype(np.float32))
    h = jnp.asarray((rng.random(rows) + 0.5).astype(np.float32))

    def build(sub):
        return jax.jit(functools.partial(
            grow_ops.grow_tree, max_depth=depth, n_bins=bins,
            reg_lambda=1.0, min_child_weight=1e-3, min_split_gain=0.0,
            hist_subtraction=sub))

    fns = {}
    for sub in (True, False):
        fns[sub] = build(sub)
        sync(fns[sub](Xb, g, h).leaf_value)   # compile + first run

    def bout(sub):
        t0 = time.perf_counter()
        for _ in range(iters):
            tree = fns[sub](Xb, g, h)
        sync(tree.leaf_value)
        return (time.perf_counter() - t0) / iters

    # ratio = dt_off / dt_on: > 1 means subtraction wins.
    dts, ratios = _paired_ab_reps(bout, False, True, reps)
    dt_on, dt_off = min(dts[True]), min(dts[False])
    out = {
        "kernel": "hist_fused_ab",
        "rows": rows, "features": features, "bins": bins, "depth": depth,
        "iters": iters, "reps": reps,
        "mrows_on": rows * depth / dt_on / 1e6,
        "mrows_off": rows * depth / dt_off / 1e6,
        "ratio_on_over_off": float(np.median(ratios)),
    }
    # Roofline stamp for the fused (subtraction-ON) round — XLA's own
    # cost model at the measured per-tree wallclock; benchwatch bands the
    # utilization fractions (a silent fallback to full-level builds shows
    # up here even when wallclock drift hides it).
    out.update(_roofline_util("hist_fused", fns[True], (Xb, g, h), dt_on))
    return out


def bench_hist_comms_ab(
    rows: int = 1_000_000,
    features: int = 28,
    bins: int = 255,
    depth: int = 6,
    iters: int = 4,
    reps: int = 8,
    seed: int = 0,
    host_partitions: int | None = None,
    n_partitions: int | None = None,
) -> dict:
    """PAIRED split-comms A/B on the pod mesh: the whole per-tree fused
    level loop with split_comms="allreduce" vs "reduce_scatter", same
    data, same mesh (docs/PERF.md "Histogram comms"). Default mesh is
    the pod shape — hosts x rows over every visible device (2 x N/2 when
    >= 4 devices, so the collective crosses the mesh's slow outer axis)
    — which is the CPU multi-device harness in tier-1 and the real
    ICI+DCN fabric on a chip image.

    Same statistic as bench_hist_fused_ab: per-rep PAIRED ratio with the
    arm order alternating every rep, median-of-ratios as the A/B
    evidence (ratio_allreduce_over_rs > 1 means reduce-scatter wins),
    min-of-reps per-arm timing as the headline. The deterministic
    per-level payload ratio (telemetry.counters.hist_allreduce_bytes,
    both modes) is stamped alongside — wallclock on a one-host virtual
    mesh moves little (localhost "wire"), the payload model is the
    invariant, and the chip floor (HIST_COMMS_AB_FLOOR) guards the
    wallclock side where a real fabric exists."""
    import jax

    from ddt_tpu.backends import get_backend
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.telemetry import counters as tele_counters
    from ddt_tpu.utils.device import device_sync as sync

    n_dev = len(jax.devices())
    if host_partitions is None or n_partitions is None:
        if n_dev >= 4:
            host_partitions, n_partitions = 2, n_dev // 2
        else:
            host_partitions, n_partitions = 1, max(1, n_dev)
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    g = rng.standard_normal(rows).astype(np.float32)
    h = (rng.random(rows) + 0.5).astype(np.float32)

    arms = {}
    for mode in ("allreduce", "reduce_scatter"):
        cfg = TrainConfig(
            backend="tpu", n_bins=bins, max_depth=depth,
            host_partitions=host_partitions, n_partitions=n_partitions,
            split_comms=mode, seed=seed,
        )
        be = get_backend(cfg)
        data = be.upload(Xb)
        gd = be._put_rows(g)
        hd = be._put_rows(h)
        fn = be._grow_fn
        sync(fn(data, gd, hd)[0])       # compile + first run
        arms[mode] = (fn, data, gd, hd, be)

    def bout(mode):
        fn, data, gd, hd, _ = arms[mode]
        t0 = time.perf_counter()
        for _ in range(iters):
            packed, _delta = fn(data, gd, hd)
        sync(packed)
        return (time.perf_counter() - t0) / iters

    # ratio = dt_allreduce / dt_rs: > 1 means reduce-scatter wins
    # (_paired_ab_reps returns dt_key_a / dt_key_b per rep).
    dts, ratios = _paired_ab_reps(bout, "allreduce", "reduce_scatter",
                                  reps)
    dt_rs = min(dts["reduce_scatter"])
    dt_ar = min(dts["allreduce"])
    P = arms["allreduce"][4].row_shards
    bytes_ar = tele_counters.hist_allreduce_bytes(depth, features, bins,
                                                  partitions=P)
    bytes_rs = tele_counters.hist_allreduce_bytes(
        depth, features, bins, partitions=P, mode="reduce_scatter")
    return {
        "kernel": "hist_comms_ab",
        "rows": rows, "features": features, "bins": bins, "depth": depth,
        "iters": iters, "reps": reps,
        "host_partitions": host_partitions, "n_partitions": n_partitions,
        "mrows_rs": rows * depth / dt_rs / 1e6,
        "mrows_allreduce": rows * depth / dt_ar / 1e6,
        "ratio_allreduce_over_rs": float(np.median(ratios)),
        "payload_bytes_allreduce": bytes_ar,
        "payload_bytes_rs": bytes_rs,
        "payload_ratio": round(bytes_ar / bytes_rs, 3),
    }


def bench_hist_2d(
    rows: int = 200_000,
    features: int = 1024,
    bins: int = 64,
    depth: int = 6,
    iters: int = 4,
    reps: int = 8,
    seed: int = 0,
    n_partitions: int | None = None,
    feature_partitions: int | None = None,
) -> dict:
    """PAIRED 1D-row-mesh vs 2D (rows x features)-mesh whole-tree A/B at
    a WIDE shape (F >= 1k — the regime ROADMAP item 2 exists for: a
    replicated feature axis makes every device hold, build, and ship
    all F columns' histograms). Same device count both arms: the 1D arm
    puts every device on rows, the 2D arm splits them (Pr, Pf); both
    run the resolved split_comms (reduce_scatter on any row wire), so
    the A/B isolates the LAYOUT — per-device histogram slab F/(Pr·Pf)
    vs F/P, with the winner combine over both axes.

    Same statistic as bench_hist_comms_ab (the one that survives
    run-to-run bands): per-rep PAIRED ratio, order alternating
    every rep, median-of-ratios as the A/B evidence
    (ratio_1d_over_2d > 1 means the 2D mesh wins), min-of-reps per-arm
    timing as the headline. The deterministic per-tree payload ratio
    (telemetry.counters.hist_allreduce_bytes with the second axis) is
    stamped alongside — on a one-host virtual mesh wallclock moves
    little (localhost "wire"); the payload model is the invariant and
    the chip floor (HIST_2D_AB_FLOOR) guards the wallclock side where
    a real fabric exists."""
    import jax

    from ddt_tpu.backends import get_backend
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.telemetry import counters as tele_counters
    from ddt_tpu.utils.device import device_sync as sync

    n_dev = len(jax.devices())
    if n_partitions is None or feature_partitions is None:
        if n_dev >= 4:
            n_partitions, feature_partitions = n_dev // 2, 2
        elif n_dev >= 2:
            n_partitions, feature_partitions = 1, 2
        else:
            raise ValueError("bench_hist_2d needs >= 2 devices")
    n_used = n_partitions * feature_partitions
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    g = rng.standard_normal(rows).astype(np.float32)
    h = (rng.random(rows) + 0.5).astype(np.float32)

    meshes = {"1d": (n_used, 1), "2d": (n_partitions, feature_partitions)}
    arms = {}
    for key, (pr, pf) in meshes.items():
        cfg = TrainConfig(
            backend="tpu", n_bins=bins, max_depth=depth,
            mesh_shape=(pr, pf), seed=seed,
        )
        be = get_backend(cfg)
        data = be.upload(Xb)
        gd = be._put_rows(g)
        hd = be._put_rows(h)
        fn = be._grow_fn
        sync(fn(data, gd, hd)[0])       # compile + first run
        arms[key] = (fn, data, gd, hd, be)

    def bout(key):
        fn, data, gd, hd, _ = arms[key]
        t0 = time.perf_counter()
        for _ in range(iters):
            packed, _delta = fn(data, gd, hd)
        sync(packed)
        return (time.perf_counter() - t0) / iters

    # ratio = dt_1d / dt_2d: > 1 means the 2D mesh wins.
    dts, ratios = _paired_ab_reps(bout, "1d", "2d", reps)
    dt_2d, dt_1d = min(dts["2d"]), min(dts["1d"])
    be_1d, be_2d = arms["1d"][4], arms["2d"][4]
    bytes_1d = tele_counters.hist_allreduce_bytes(
        depth, features, bins, partitions=be_1d.row_shards,
        mode=be_1d.split_comms)
    bytes_2d = tele_counters.hist_allreduce_bytes(
        depth, features, bins, partitions=be_2d.row_shards,
        feature_partitions=be_2d.feature_partitions,
        mode=be_2d.split_comms)
    # The acceptance comparator (ISSUE 11): the REPLICATED-FEATURE
    # allreduce baseline — every device receiving every column's bins —
    # on the same device count. payload_ratio = baseline / 2D effective
    # bytes, the deterministic 1/(Pr·Pf) factor the counter model
    # witnesses in-process (tests/test_mesh2d.py). NOTE the 1D-rs arm's
    # RECEIVED slab ties the 2D arm's at equal device count (both
    # F/n_dev per device); the 2D win over 1D-rs is the Pf-fold smaller
    # pre-collective histogram working set and ring traffic, which the
    # wallclock ratio — not the received-bytes model — measures.
    bytes_replicated = tele_counters.hist_allreduce_bytes(
        depth, features, bins, partitions=be_1d.row_shards,
        mode="allreduce")
    return {
        "kernel": "hist_2d_ab",
        "rows": rows, "features": features, "bins": bins, "depth": depth,
        "iters": iters, "reps": reps,
        "mesh_1d": list(meshes["1d"]), "mesh_2d": list(meshes["2d"]),
        "mrows_2d": rows * depth / dt_2d / 1e6,
        "mrows_1d": rows * depth / dt_1d / 1e6,
        "ratio_1d_over_2d": float(np.median(ratios)),
        "payload_bytes_replicated": bytes_replicated,
        "payload_bytes_1d": bytes_1d,
        "payload_bytes_2d": bytes_2d,
        "payload_ratio": round(bytes_replicated / bytes_2d, 3),
    }


def bench_hist_quant_ab(
    rows: int = 1_000_000,
    features: int = 28,
    bins: int = 255,
    depth: int = 6,
    iters: int = 4,
    reps: int = 8,
    seed: int = 0,
    grad_dtype: str = "int8",
) -> dict:
    """PAIRED quantized-gradient A/B: the whole per-tree fused level
    loop (ops/grow.grow_tree) with grad_dtype="f32" vs "int8" (or
    "int16"), same data, same shape — the ISSUE 14 tentpole's wallclock
    witness (docs/PERF.md "Quantized gradients"). Same statistic as
    bench_hist_fused_ab: per-rep PAIRED ratio with the arm order
    alternating every rep, median-of-ratios as the A/B evidence
    (ratio_f32_over_quant > 1 means the integer path wins), min-of-reps
    per-arm timing as the headline; throughputs are NOMINAL
    hist-row-equivalents (rows x depth / sec) so the arms share a unit.

    Both arms resolve their OWN sibling-subtraction default ('auto':
    integer hists subtract exactly everywhere, f32 only on a real chip)
    — the A/B measures the shipped configs, not a lab pairing. The
    deterministic payload_ratio stamps the g/h HBM-stream byte model
    (telemetry.counters.grad_stream_bytes — 4x int8, 2x int16): on CPU
    the wallclock moves little (the interpreted kernel dominates), the
    byte model is the invariant, and the chip floor
    (HIST_QUANT_AB_FLOOR) guards the wallclock side where HBM bandwidth
    is real."""
    import functools

    import jax
    import jax.numpy as jnp

    from ddt_tpu.ops import grow as grow_ops
    from ddt_tpu.utils.device import device_sync as sync

    rng = np.random.default_rng(seed)
    Xb = jnp.asarray(rng.integers(0, bins, size=(rows, features),
                                  dtype=np.uint8))
    g = jnp.asarray(rng.standard_normal(rows).astype(np.float32))
    h = jnp.asarray((rng.random(rows) * 0.25).astype(np.float32))

    def build(dt):
        from ddt_tpu.ops.grow import resolve_hist_subtraction

        return jax.jit(functools.partial(
            grow_ops.grow_tree, max_depth=depth, n_bins=bins,
            reg_lambda=1.0, min_child_weight=1e-3, min_split_gain=0.0,
            hist_subtraction=resolve_hist_subtraction(
                "auto", integer_hists=dt != "f32"),
            grad_dtype=dt, quant_seed=seed))

    fns = {}
    for dt in ("f32", grad_dtype):
        fns[dt] = build(dt)
        sync(fns[dt](Xb, g, h).leaf_value)   # compile + first run

    def bout(dt):
        t0 = time.perf_counter()
        for _ in range(iters):
            tree = fns[dt](Xb, g, h)
        sync(tree.leaf_value)
        return (time.perf_counter() - t0) / iters

    # ratio = dt_f32 / dt_quant: > 1 means the integer path wins.
    dts, ratios = _paired_ab_reps(bout, "f32", grad_dtype, reps)
    dt_q = min(dts[grad_dtype])
    dt_f = min(dts["f32"])
    bytes_f = tele_counters.grad_stream_bytes(rows, depth, "f32")
    bytes_q = tele_counters.grad_stream_bytes(rows, depth, grad_dtype)
    out = {
        "kernel": "hist_quant_ab",
        "rows": rows, "features": features, "bins": bins, "depth": depth,
        "iters": iters, "reps": reps, "grad_dtype": grad_dtype,
        "mrows_quant": rows * depth / dt_q / 1e6,
        "mrows_f32": rows * depth / dt_f / 1e6,
        "ratio_f32_over_quant": float(np.median(ratios)),
        "grad_stream_bytes_f32": bytes_f,
        "grad_stream_bytes_quant": bytes_q,
        "payload_ratio": round(bytes_f / bytes_q, 3),
    }
    # Roofline stamp for the quantized arm: XLA's cost model at the
    # measured per-tree wallclock (benchwatch bands the fractions; an
    # integer path silently falling back to f32 streams shows up as an
    # HBM-utilization jump even when wallclock drift hides it).
    out.update(_roofline_util("hist_quant", fns[grad_dtype], (Xb, g, h),
                              dt_q))
    return out


def bench_histogram_one_dispatch(
    rows: int = 1_000_000,
    features: int = 28,
    bins: int = 255,
    n_nodes: int = 32,
    iters: int = 10,
    reps: int = 8,
    seed: int = 0,
) -> dict:
    """One-dispatch headline twin: `iters` kernel invocations inside ONE
    jitted lax.fori_loop — two host round-trips per rep instead of one
    per dispatch. experiments/hist_dispatch_ab.py measured the
    dispatch-loop protocol at 33% within-window spread (incl. spuriously
    FAST samples that min-of-reps then reports) vs 7.6% for this
    formulation in the same window; device-rate bands remain real across
    windows (docs/PERF.md round-5 addendum), but this statistic is far
    better conditioned within one. A tiny data dependence (g advanced by
    a scalar read of the previous histogram) keeps XLA from hoisting the
    loop body; the +iters elementwise adds on g are noise against the
    histogram passes.

    Reports BOTH median-of-reps and min-of-reps (round-5 advisor
    finding): min-of-reps is the very statistic the dispatch-loop
    docstring criticizes for promoting transient fast-tail excursions to
    the run's value, and with the external 45-65 drift min-of-8 still
    biases the floored metric toward lucky windows. The median (the
    stat experiments/hist_dispatch_ab.py already uses) is the headline
    `mrows_per_sec_per_chip`; the min is kept as `_min` fields for
    comparability with earlier artifacts."""
    import jax
    import jax.numpy as jnp

    from ddt_tpu.ops import histogram as hist_ops

    Xb_h, g_h, h_h, ni_h = _hist_inputs(rows, features, bins, n_nodes, seed)
    Xb = jnp.asarray(Xb_h)
    g0 = jnp.asarray(g_h)
    h = jnp.asarray(h_h)
    ni = jnp.asarray(ni_h)

    @jax.jit
    def k_in_one(g):
        def body(_, carry):
            g2, acc = carry
            out = hist_ops.build_histograms(Xb, g2, h, ni, n_nodes, bins)
            s = out[0, 0, 0, 0] * jnp.float32(1e-30)
            return g2 + s, acc + s
        return jax.lax.fori_loop(0, iters, body, (g, jnp.float32(0.0)))[1]

    float(k_in_one(g0))                      # compile + first run
    dts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(k_in_one(g0))                  # scalar fetch = the barrier
        dts.append((time.perf_counter() - t0) / iters)
    dt_med = float(np.median(dts))
    dt_min = float(np.min(dts))
    return {
        "kernel": "histogram_one_dispatch",
        "rows": rows, "features": features, "bins": bins,
        "n_nodes": n_nodes, "iters": iters,
        "sec_per_build": dt_med,
        "sec_per_build_min": dt_min,
        "mrows_per_sec_per_chip": rows / dt_med / 1e6,
        "mrows_per_sec_per_chip_min": rows / dt_min / 1e6,
    }


def bench_train(
    backend: str = "tpu",
    rows: int = 1_000_000,
    features: int = 28,
    bins: int = 255,
    trees: int = 100,
    depth: int = 6,
    partitions: int = 1,
    hist_impl: str = "auto",
    seed: int = 0,
    run_log=None,
) -> dict:
    """End-to-end boosted-build wallclock (the Higgs-1M/depth-6/100-tree
    config when called with defaults). `run_log` (path or telemetry
    RunLog) attaches the structured run log to the TIMED run — the bench
    artifact then carries per-round records and counters alongside the
    headline wallclock."""
    from ddt_tpu import api
    from ddt_tpu.data import datasets
    from ddt_tpu.data.quantizer import quantize

    X, y = datasets.synthetic_binary(rows, n_features=features, seed=seed)
    Xb, _ = quantize(X, n_bins=bins, seed=seed)
    cfg = TrainConfig(
        n_trees=trees, max_depth=depth, n_bins=bins, backend=backend,
        n_partitions=partitions, hist_impl=hist_impl, seed=seed,
    )
    tele_counters.install_jax_listener()
    # Warm-up: compile the per-tree program on a 2-tree run, then time.
    api.train(Xb, y, cfg.replace(n_trees=2), binned=True, log_every=10**9)
    c0 = tele_counters.snapshot()
    t0 = time.perf_counter()
    res = api.train(Xb, y, cfg, binned=True, log_every=10**9,
                    run_log=run_log)
    dt = time.perf_counter() - t0
    return {
        "kernel": "train",
        "backend": backend, "rows": rows, "trees": trees, "depth": depth,
        "partitions": partitions,
        "wallclock_s": dt,
        "trees_per_sec": trees / dt,
        "final_train_loss": res.history[-1]["train_loss"]
        if res.history else None,
        # Compiles INSIDE the timed run (telemetry.counters). Nonzero is
        # expected once per distinct block/round shape (the warm-up's
        # 2-round block differs from the timed blocks); a value growing
        # WITH `trees` means per-round shape churn — the silent killer
        # the counter exists to surface (arXiv:1810.09868).
        "jit_compiles_timed": tele_counters.delta(c0)["jit_compiles"],
    }


def _predict_setup(rows, features, bins, trees, depth, seed, backend="tpu",
                   partitions=1):
    """(backend, Xb, ensemble) for the scoring benches — random full
    trees (all internal nodes split; plausible worst case), shared by
    bench_predict and bench_predict_both so the two can't drift."""
    from ddt_tpu.backends import get_backend
    from ddt_tpu.models.tree import empty_ensemble

    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    n_nodes = 2 ** (depth + 1) - 1
    ens = empty_ensemble(trees, depth, features, 0.1, 0.0, "logloss")
    ens.feature[:] = rng.integers(0, features, size=(trees, n_nodes))
    ens.threshold_bin[:] = rng.integers(0, bins - 1, size=(trees, n_nodes))
    ens.is_leaf[:, (n_nodes // 2):] = True
    ens.leaf_value[:] = rng.standard_normal(
        (trees, n_nodes)).astype(np.float32)
    cfg = TrainConfig(backend=backend, n_partitions=partitions, n_bins=bins)
    return get_backend(cfg), Xb, ens


def bench_predict(
    backend: str = "tpu",
    rows: int = 1_000_000,
    features: int = 28,
    bins: int = 255,
    trees: int = 1000,
    depth: int = 6,
    partitions: int = 1,
    seed: int = 0,
) -> dict:
    """Batch inference throughput (the 1000-tree × large-batch config)."""
    be, Xb, ens = _predict_setup(rows, features, bins, trees, depth, seed,
                                 backend, partitions)
    # Warm-up with one FULL untimed pass: jit caches are shape-keyed and
    # device backends chunk rows internally, so only an identical call is
    # guaranteed to compile every shape (incl. a remainder chunk) the timed
    # run will hit.
    be.predict_raw(ens, Xb)
    t0 = time.perf_counter()
    out = be.predict_raw(ens, Xb)
    dt = time.perf_counter() - t0
    assert out.shape[0] == rows
    return {
        "kernel": "predict",
        "backend": backend, "rows": rows, "trees": trees, "depth": depth,
        "wallclock_s": dt,
        "mrows_per_sec": rows / dt / 1e6,
    }


def bench_predict_both(
    rows: int = 10_000_000,
    features: int = 28,
    bins: int = 255,
    trees: int = 1000,
    depth: int = 6,
    seed: int = 0,
    reps: int = 2,
) -> tuple[dict, dict, dict]:
    """(resident, total, compute) predict measurements sharing ONE
    dataset, ensemble, and warm-up pass — the 280 MB batch and 1000-tree
    model are built once, the warm full pass compiles every chunk shape
    the timed paths hit, and only the timing loops differ. The resident
    arm (batch device-uploaded ONCE, outside timing) measures scoring
    compute + the overlapped result fetch rather than the host→device
    link — the 280 MB upload (on the chip: not measured) must not
    swamp the kernel regression the floor exists to catch. The COMPUTE arm goes one step further (round-5 phase
    breakdown: the D2H fetch was ~65% of even the resident wallclock
    on the earlier host): it syncs the chunk outputs on device
    without copying them back, isolating the descent/leaf-select kernels
    the 0.8-era floor was actually trying to guard — a band-stable
    number a tight floor can sit under. The repo-root bench floors
    resident AND compute and records total as context."""
    import jax

    from ddt_tpu.utils.device import device_sync

    be, Xb, ens = _predict_setup(rows, features, bins, trees, depth, seed)
    be.predict_raw(ens, Xb)                       # warm-up, all shapes
    data = jax.device_put(Xb)
    device_sync(data)
    # Which traversal the auto dispatch resolved to (pallas on a real TPU
    # at VMEM-fitting shapes since the inference overhaul; one-hot
    # otherwise) — recorded so floor trips can be attributed.
    from ddt_tpu.ops.predict import resolve_use_pallas

    impl = ("pallas" if resolve_use_pallas(None, True, depth, features, 1,
                                           0) else "onehot")
    base = {"kernel": "predict", "backend": "tpu", "rows": rows,
            "trees": trees, "depth": depth, "impl": impl}
    out = []
    for resident, arg, n in ((True, data, reps), (False, Xb, 1)):
        dt = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            got = be.predict_raw(ens, arg)
            dt = min(dt, time.perf_counter() - t0)
        assert got.shape[0] == rows
        out.append({**base, "resident": resident, "wallclock_s": dt,
                    "mrows_per_sec": rows / dt / 1e6})

    # Compute-only arm: same chunked programs, outputs synced on device,
    # nothing row-sized crosses to host.
    fn, ens_dev = be._predict_fn(ens)
    chunk = be.PREDICT_ROW_CHUNK
    dt = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        outs = [fn(*ens_dev, data[i:i + chunk])
                for i in range(0, rows, chunk)]
        for o in outs:
            device_sync(o)
        dt = min(dt, time.perf_counter() - t0)
    rec = {**base, "resident": "compute_only", "wallclock_s": dt,
           "mrows_per_sec": rows / dt / 1e6}
    # Roofline stamp for the scoring kernel (cost-observatory satellite):
    # one full-size chunk's program at its share of the measured compute
    # wallclock (the chunks are homogeneous up to the remainder).
    n_chunks = -(-rows // chunk)
    rec.update(_roofline_util("predict", fn,
                              (*ens_dev, data[:min(chunk, rows)]),
                              dt / n_chunks))
    out.append(rec)
    return out[0], out[1], out[2]


def bench_predict_pallas_ab(
    rows: int = 4_000_000,
    features: int = 28,
    bins: int = 255,
    trees: int = 1000,
    depth: int = 6,
    seed: int = 0,
    reps: int = 8,
) -> dict:
    """PAIRED pallas-vs-one-hot traversal timing, compute-only + resident.

    Same protocol as bench_histogram_ab (the statistic that survives
    run-to-run bands): per-rep PAIRED ratio with the arm order
    alternating every rep, median-of-ratios as the A/B evidence and
    median-of-reps per-arm throughput as the headline (the histogram
    protocol's statistic — min-of-reps promotes fast-tail excursions).
    Both arms run predict_raw_effective on the SAME device-resident
    CompiledEnsemble arrays and batch, so only the traversal formulation
    differs; outputs are asserted equal first (the kernel's exactness
    contract, witnessed per bench run like split_agreement).

    Meaningful on a real chip only — off-TPU the pallas arm runs the
    interpreter (minutes per dispatch); the repo-root bench gates on
    on_tpu."""
    import jax
    import jax.numpy as jnp

    from ddt_tpu.ops import predict as predict_ops
    from ddt_tpu.utils.device import device_sync

    _, Xb, ens = _predict_setup(rows, features, bins, trees, depth, seed)
    ce = ens.compile(tree_chunk=64)
    dev = [jnp.asarray(a) for a in ce.arrays()]
    Xd = jax.device_put(Xb)
    device_sync(Xd)

    def run(use_pallas):
        out = predict_ops.predict_raw_effective(
            *dev, Xd, max_depth=ce.max_depth,
            learning_rate=ce.learning_rate, base=ce.base_score,
            n_classes=ce.n_classes_out, tree_chunk=ce.tree_chunk,
            use_pallas=use_pallas)
        device_sync(out)
        return out
    # Warm-up compiles both arms AND witnesses the exactness contract.
    a0, b0 = run(True), run(False)
    assert bool(jnp.all(a0 == b0)), \
        "pallas traversal diverged from the one-hot path"

    def bout(use_pallas):
        t0 = time.perf_counter()
        run(use_pallas)
        return time.perf_counter() - t0

    # ratio = dt_onehot / dt_pallas: > 1 means pallas faster.
    dts, ratios = _paired_ab_reps(bout, False, True, reps)
    med = {arm: float(np.median(v)) for arm, v in dts.items()}
    return {
        "kernel": "predict_pallas_ab",
        "rows": rows, "features": features, "bins": bins,
        "trees": trees, "depth": depth, "reps": reps,
        "pallas_mrows_per_sec": rows / med[True] / 1e6,
        "onehot_mrows_per_sec": rows / med[False] / 1e6,
        "ratio_pallas_over_onehot": float(np.median(ratios)),
        "exact_match": True,            # asserted above
    }


def bench_serve_latency(
    backend: str = "tpu",
    rows: int = 20_000,
    features: int = 16,
    bins: int = 63,
    trees: int = 50,
    depth: int = 4,
    qps_points: tuple = (50, 200, 8000),
    n_requests: int = 200,
    max_wait_ms: float = 1.0,
    max_batch: int = 64,
    quantize: bool = False,
    seed: int = 0,
) -> dict:
    """Latency-under-load for the serving tier (ISSUE 8 acceptance arm;
    CPU-runnable — the admission/queueing behavior under test is host
    code, the model is small enough that per-dispatch device time is
    milliseconds on any platform).

    Protocol:
    - COLD comparator: one `api.predict` single-row call against a
      FRESH backend instance with nothing cached (first-call compile +
      CompiledEnsemble build + upload) — what an RPC handler that calls
      the batch API per request would pay on a cold model, the exact
      path `cli serve` exists to replace.
    - then, per open-loop arrival rate in `qps_points`: `n_requests`
      single-row requests submitted on schedule (arrival i at t0 +
      i/qps, independent of completions — open loop, so queueing shows
      up as latency rather than rate throttling), p50/p99 latency and
      coalesce width recorded from the engine's own stats. The TOP
      point must SATURATE the admission window on any box — at 8000/s
      the default 1 ms window alone gathers ~8 arrivals irrespective of
      per-dispatch speed, which is what keeps the repo-root bench's
      SERVE_COALESCE_MIN floor a property of the batcher, not of the
      host's dispatch latency.

    Stamped into BENCH artifacts as serve_* metrics and banded by
    tools/benchwatch (latency lower-is-better — the direction table
    grew the latency sign for exactly these)."""
    import threading

    from ddt_tpu import api
    from ddt_tpu.serve.engine import ServeEngine

    rng = np.random.default_rng(seed)
    be0, Xb, ens = _predict_setup(rows, features, bins, trees, depth, seed,
                                  backend=backend)
    del be0     # the serving engine builds its own backend below
    bundle = api.ModelBundle(ensemble=ens, mapper=None)

    # Cold comparator: a backend built OUTSIDE the module cache
    # (use_cache=False — the cache key normalizes cfg.seed away at
    # subsample=1.0, so a merely-distinct config would alias the warm
    # instance) so its device-resident predict cache is empty, AND the
    # process-global jit trace/executable caches cleared so the call
    # pays compile + build + upload every run — without this, an
    # in-process repeat (a second bench arm, a quantize=True A/B leg)
    # gets the first run's executable back in ~1 ms and the 10x
    # cold-over-p99 floor false-fails as a PERF REGRESSION. The engine
    # below re-traces its bucket shapes at warm-up, off the request
    # path — bench time, not serving latency.
    import jax as _jax

    from ddt_tpu.backends import get_backend as _get_backend

    _jax.clear_caches()
    cold_cfg = TrainConfig(backend=backend, n_bins=bins)
    t0 = time.perf_counter()
    api.predict(ens, Xb[:1], binned=True,
                backend=_get_backend(cold_cfg, use_cache=False))
    cold_ms = (time.perf_counter() - t0) * 1e3

    cfg = TrainConfig(backend=backend, n_bins=bins,
                      predict_impl="lut" if quantize else "auto")
    engine = ServeEngine(bundle, cfg, max_wait_ms=max_wait_ms,
                         max_batch=max_batch, quantize=quantize)
    arms = []
    for qps in qps_points:
        engine.stats.window_summary(reset=True)      # fresh window
        pendings = []
        t_start = time.perf_counter()
        for i in range(n_requests):
            target = t_start + i / qps
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)             # open-loop arrival
            r = int(rng.integers(0, rows))
            pendings.append(engine.predict_async(Xb[r:r + 1]))
        for p in pendings:
            p.result(timeout=60.0)
        w = engine.stats.window_summary(reset=True)
        arms.append({"qps": qps, **{k: w[k] for k in
                                    ("requests", "batches", "p50_ms",
                                     "p99_ms", "p999_ms", "coalesce_mean",
                                     "coalesce_max", "queue_depth_max")}})
    engine.close()
    # Headline = the MIDDLE qps point (index len//2): busy enough to
    # coalesce, not so hot that the arm measures pure saturation.
    head = arms[len(arms) // 2]
    return {
        "kernel": "serve_latency",
        "backend": backend, "rows_model": rows, "trees": trees,
        "depth": depth, "features": features,
        "max_wait_ms": max_wait_ms, "max_batch": max_batch,
        "quantized": bool(quantize),
        "cold_predict_ms": round(cold_ms, 3),
        "arms": arms,
        "serve_qps": head["qps"],
        "serve_p50_ms": head["p50_ms"],
        "serve_p99_ms": head["p99_ms"],
        "serve_p999_ms": head["p999_ms"],
        "serve_coalesce_mean": head["coalesce_mean"],
        "serve_coalesce_max": max(a["coalesce_max"] for a in arms),
        "serve_cold_over_p99": (round(cold_ms / head["p99_ms"], 2)
                                if head["p99_ms"] > 0 else None),
    }


def bench_predict_lut_ab(
    rows: int = 4_000_000,
    features: int = 28,
    bins: int = 255,
    trees: int = 1000,
    depth: int = 6,
    seed: int = 0,
    reps: int = 8,
) -> dict:
    """PAIRED quantized-LUT vs f32 traversal timing — the serving tier's
    A/B arm (ISSUE 8). Same statistic as bench_predict_pallas_ab (the
    one that survives run-to-run bands): per-rep PAIRED
    ratio, order alternating every rep, median-of-ratios as the
    evidence. The f32 arm is whatever the auto dispatch resolves
    (Pallas on a real chip); the LUT arm streams raw uint8 rows against
    int8/fp16 tables. The error contract is witnessed per run: max
    |lut - f32| must sit under the tables' computed bound.

    Meaningful on a real chip only — off-TPU both Pallas arms run the
    interpreter; the repo-root bench gates on on_tpu."""
    import jax
    import jax.numpy as jnp

    from ddt_tpu.ops import predict as predict_ops
    from ddt_tpu.ops import predict_lut
    from ddt_tpu.utils.device import device_sync

    _, Xb, ens = _predict_setup(rows, features, bins, trees, depth, seed)
    ce = ens.compile(tree_chunk=64)
    tables = ce.quantize()
    dev_f32 = [jnp.asarray(a) for a in ce.arrays()]
    lut_ops = tuple(jnp.asarray(a)
                    for a in predict_lut.lut_device_operands(tables))
    Xd = jax.device_put(Xb)
    device_sync(Xd)
    lut_static = dict(
        max_depth=tables.max_depth, learning_rate=tables.learning_rate,
        base=tables.base_score, n_classes=tables.n_classes_out,
        tree_chunk=tables.tree_chunk,
        n_trees_padded=tables.n_trees_padded,
        missing_bin_value=tables.missing_bin_value,
        use_missing=tables.eff_dl is not None,
        use_cat=tables.eff_cat is not None,
        use_scale=tables.leaf_scale is not None)
    lut_jit = jax.jit(lambda *a: predict_lut.predict_effective_lut_ops(
        a[:-1], a[-1], **lut_static))

    def run(arm):
        if arm == "lut":
            out = lut_jit(*lut_ops, Xd)
        else:
            out = predict_ops.predict_raw_effective(
                *dev_f32, Xd, max_depth=ce.max_depth,
                learning_rate=ce.learning_rate, base=ce.base_score,
                n_classes=ce.n_classes_out, tree_chunk=ce.tree_chunk)
        device_sync(out)
        return out

    # Warm-up compiles both arms AND witnesses the error contract.
    a0, b0 = np.asarray(run("lut")), np.asarray(run("f32"))
    err = float(np.abs(a0 - b0).max())
    assert err <= tables.max_abs_err * (1 + 1e-5) + 1e-6, \
        (err, tables.max_abs_err)

    def bout(arm):
        t0 = time.perf_counter()
        run(arm)
        return time.perf_counter() - t0

    # ratio = dt_f32 / dt_lut: > 1 means the quantized path wins.
    dts, ratios = _paired_ab_reps(bout, "f32", "lut", reps)
    med = {arm: float(np.median(v)) for arm, v in dts.items()}
    return {
        "kernel": "predict_lut_ab",
        "rows": rows, "features": features, "bins": bins,
        "trees": trees, "depth": depth, "reps": reps,
        "lut_mrows_per_sec": rows / med["lut"] / 1e6,
        "f32_mrows_per_sec": rows / med["f32"] / 1e6,
        "ratio_lut_over_f32": float(np.median(ratios)),
        "lut_max_abs_err": err,
        "lut_err_bound": tables.max_abs_err,
    }


def bench_predict_lut4_ab(
    rows: int = 4_000_000,
    features: int = 28,
    bins: int = 15,
    trees: int = 1000,
    depth: int = 6,
    seed: int = 0,
    reps: int = 8,
    ab: "bool | None" = None,
    express_trees: int = 50,
    express_depth: int = 4,
    express_features: int = 16,
    express_bins: int = 15,
    n_single: int = 120,
    n_storm: int = 300,
    max_wait_ms: float = 20.0,
) -> dict:
    """int4 tier + express lane, the two ISSUE 12 measurements in one
    artifact.

    PART 1 — paired int8-vs-int4 A/B (the bench_predict_lut_ab
    protocol: alternating order, median-of-ratios): both quantized
    kernels at the bench shape, `bins=15` so the int4 thresholds ride
    the nibble pack (the TreeLUT regime the tier exists for). The int4
    error contract is witnessed per run against the f32 one-hot path.
    Meaningful on a real chip only (off-TPU both arms run the Pallas
    interpreter) — `ab=None` auto-skips there; the repo-root bench
    gates on on_tpu and the chip floor is PREDICT_LUT4_AB_FLOOR.

    PART 2 — express-lane two-regime arm (host behavior, runs on every
    platform): a small int4-served engine measured in BOTH regimes.
    EMPTY QUEUE: sequential single-row requests — with the lane on,
    latency is dispatch only; with it off, every lone request eats the
    admission window, so `max_wait_ms` (deliberately large, 20 ms, to
    dominate host noise) is the coalesced path's latency FLOOR and
    express p99 must sit measurably below it. SATURATED: a burst of
    async submissions keeps the queue non-empty, the lane closes, and
    both engines coalesce — express-on p99 must not regress the
    express-off p99 (the lane's never-worse contract)."""
    import jax
    import jax.numpy as jnp

    from ddt_tpu import api
    from ddt_tpu.ops import predict as predict_ops
    from ddt_tpu.ops import predict_lut
    from ddt_tpu.serve.engine import ServeEngine
    from ddt_tpu.utils.device import device_sync

    out = {
        "kernel": "predict_lut4_ab",
        "rows": rows, "features": features, "bins": bins,
        "trees": trees, "depth": depth, "reps": reps,
        "express_max_wait_ms": max_wait_ms,
    }
    if ab is None:
        ab = jax.default_backend() == "tpu"

    if ab:
        _, Xb, ens = _predict_setup(rows, features, bins, trees, depth,
                                    seed)
        ce = ens.compile(tree_chunk=64)
        t8 = ce.quantize()
        t4 = ce.quantize(leaf_dtype="int4")
        pk = t4.pack_int4()
        ops8 = tuple(jnp.asarray(a)
                     for a in predict_lut.lut_device_operands(t8))
        ops4 = tuple(jnp.asarray(a) for a in pk.ops)
        Xd = jax.device_put(Xb)
        device_sync(Xd)
        st8 = dict(
            max_depth=t8.max_depth, learning_rate=t8.learning_rate,
            base=t8.base_score, n_classes=t8.n_classes_out,
            tree_chunk=t8.tree_chunk, n_trees_padded=t8.n_trees_padded,
            missing_bin_value=t8.missing_bin_value,
            use_missing=t8.eff_dl is not None,
            use_cat=t8.eff_cat is not None,
            use_scale=t8.leaf_scale is not None)
        jit8 = jax.jit(lambda *a: predict_lut.predict_effective_lut_ops(
            a[:-1], a[-1], **st8))
        st4 = pk.static_kwargs()
        jit4 = jax.jit(lambda *a: predict_lut.predict_effective_lut4_ops(
            a[:-1], a[-1], **st4))

        def run(arm):
            o = (jit4(*ops4, Xd) if arm == "int4" else jit8(*ops8, Xd))
            device_sync(o)
            return o

        # Warm-up compiles both arms AND witnesses the int4 error
        # contract against the true f32 one-hot answer.
        a4 = np.asarray(run("int4"))
        np.asarray(run("int8"))
        f32 = np.asarray(predict_ops.predict_raw_effective(
            *[jnp.asarray(a) for a in ce.arrays()], Xd,
            max_depth=ce.max_depth, learning_rate=ce.learning_rate,
            base=ce.base_score, n_classes=ce.n_classes_out,
            tree_chunk=ce.tree_chunk, use_pallas=False))
        err = float(np.abs(a4 - f32).max())
        assert err <= t4.max_abs_err * (1 + 1e-5) + 1e-6, \
            (err, t4.max_abs_err)

        def bout(arm):
            t0 = time.perf_counter()
            run(arm)
            return time.perf_counter() - t0

        # ratio = dt_int8 / dt_int4: > 1 means the bit-packed tier wins.
        dts, ratios = _paired_ab_reps(bout, "int8", "int4", reps)
        med = {arm: float(np.median(v)) for arm, v in dts.items()}
        out.update({
            "lut4_mrows_per_sec": rows / med["int4"] / 1e6,
            "lut8_mrows_per_sec": rows / med["int8"] / 1e6,
            "ratio_int4_over_int8": float(np.median(ratios)),
            "lut4_max_abs_err": err,
            "lut4_err_bound": t4.max_abs_err,
            "lut4_thr_packed": pk.thr_packed,
        })

    # ---- express-lane two-regime arm (host code, every platform) ----
    _, Xe, ens_e = _predict_setup(4096, express_features, express_bins,
                                  express_trees, express_depth, seed)
    bundle = api.ModelBundle(ensemble=ens_e, mapper=None)
    cfg = TrainConfig(backend="tpu", n_bins=express_bins,
                      predict_impl="lut4")
    rng = np.random.default_rng(seed)

    def one_engine(express: bool) -> dict:
        eng = ServeEngine(bundle, cfg, max_wait_ms=max_wait_ms,
                          max_batch=64, quantize="int4",
                          express_lane=express)
        try:
            # EMPTY-QUEUE regime: strictly sequential singles — the
            # queue is empty at every submit by construction.
            eng.stats.window_summary(reset=True)
            for _ in range(n_single):
                r = int(rng.integers(0, len(Xe)))
                eng.predict(Xe[r:r + 1], timeout=60.0)
            empty = eng.stats.window_summary(reset=True)
            # SATURATED regime: concurrent submitters keep the queue
            # non-empty (a single-threaded async burst would SERIALIZE
            # through the express lane — each synchronous express
            # dispatch completes before the next submit, so the queue
            # never forms); under real concurrency the lane closes and
            # coalescing takes over.
            import threading

            n_threads = 16
            per = max(1, n_storm // n_threads)
            barrier = threading.Barrier(n_threads)
            errs: list = []

            def worker(tid):
                rngl = np.random.default_rng(seed + 1 + tid)
                barrier.wait()
                for _ in range(per):
                    r = int(rngl.integers(0, len(Xe)))
                    try:
                        eng.predict(Xe[r:r + 1], timeout=120.0)
                    # Collected and asserted empty after the join — a
                    # failed storm request is the bench's own verdict.
                    except Exception as e:  # ddtlint: disable=broad-except
                        errs.append(repr(e))

            threads = [threading.Thread(target=worker, args=(t,))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(180)
            if errs:
                raise AssertionError(
                    f"saturated-arm requests failed: {errs[:3]}")
            sat = eng.stats.window_summary(reset=True)
            return {"empty": empty, "sat": sat}
        finally:
            eng.close()

    on = one_engine(express=True)
    off = one_engine(express=False)
    out.update({
        "express_empty_p50_ms": on["empty"]["p50_ms"],
        "express_empty_p99_ms": on["empty"]["p99_ms"],
        "coalesced_empty_p50_ms": off["empty"]["p50_ms"],
        "coalesced_empty_p99_ms": off["empty"]["p99_ms"],
        "express_hits_empty": on["empty"]["express"],
        "express_saturated_p99_ms": on["sat"]["p99_ms"],
        "coalesced_saturated_p99_ms": off["sat"]["p99_ms"],
        "express_hits_saturated": on["sat"]["express"],
        "express_gain": (round(off["empty"]["p99_ms"]
                               / on["empty"]["p99_ms"], 2)
                         if on["empty"]["p99_ms"] > 0 else None),
    })
    return out


def bench_registry_cold_load(
    backend: str = "tpu",
    features: int = 16,
    bins: int = 63,
    trees: int = 100,
    depth: int = 5,
    max_batch: int = 64,
    quantize: bool = False,
    seed: int = 0,
) -> dict:
    """Cold-start-to-serving latency: restore-from-registry (AOT
    deserialize + per-bucket XLA compile + warm) vs the full in-process
    ServableModel build (validate + compile layout + TRACE every bucket
    + compile + warm) — the prologue the registry's export boundary
    exists to amortize (ISSUE 9). Both arms start from cleared jax
    caches so each pays its honest cold path; the AOT arm additionally
    witnesses bit-identical scores against the in-process build."""
    import shutil
    import tempfile

    import jax as _jax

    from ddt_tpu import api
    from ddt_tpu.backends import get_backend as _get_backend
    from ddt_tpu.registry.loader import load_servable, push_servable
    from ddt_tpu.serve.engine import ServableModel, default_buckets

    _be, Xb, ens = _predict_setup(4 * max_batch, features, bins, trees,
                                  depth, seed, backend=backend)
    del _be
    bundle = api.ModelBundle(ensemble=ens, mapper=None)
    root = tempfile.mkdtemp(prefix="ddt_reg_bench_")
    try:
        push_servable(root, bundle, name="bench", max_batch=max_batch,
                      quantize=quantize)
        cold_cfg = TrainConfig(backend=backend, n_bins=bins,
                               predict_impl="lut" if quantize else "auto")

        _jax.clear_caches()
        t0 = time.perf_counter()
        rebuild = ServableModel(
            bundle, _get_backend(cold_cfg, use_cache=False),
            quantize=quantize, buckets=default_buckets(max_batch))
        rebuild.warmup()
        rebuild_ms = (time.perf_counter() - t0) * 1e3
        want = rebuild.score_binned(Xb[:max_batch])

        _jax.clear_caches()
        t0 = time.perf_counter()
        report = load_servable(root, "bench", quantize=quantize)
        report.model.warmup()
        aot_ms = (time.perf_counter() - t0) * 1e3
        got = report.model.score_binned(Xb[:max_batch])
        if report.mode.startswith("aot") and not np.array_equal(want, got):
            raise AssertionError(
                "registry-restored scores diverge from the in-process "
                "build — the bit-exactness contract broke")
        return {
            "kernel": "registry_cold_load", "backend": backend,
            "trees": trees, "depth": depth, "features": features,
            "max_batch": max_batch, "quantized": bool(quantize),
            "mode": report.mode,
            "registry_rebuild_cold_ms": round(rebuild_ms, 3),
            "registry_aot_cold_ms": round(aot_ms, 3),
            "registry_aot_speedup": round(rebuild_ms / aot_ms, 3)
            if aot_ms > 0 else None,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_bench(kernel: str = "histogram", **kw) -> dict:
    # None-valued kwargs defer to each bench fn's own default — the CLI
    # passes --features=None unless the user set it, so the wide-shape
    # kernels (hist_2d: F=1024) keep their documented defaults instead
    # of inheriting a narrow-arm constant.
    kw = {k: v for k, v in kw.items() if v is not None}
    if kernel == "histogram":
        keys = ("backend", "rows", "features", "bins", "iters",
                "partitions", "hist_impl", "seed", "reps")
        return bench_histogram(**{k: kw[k] for k in keys if k in kw})
    if kernel == "train":
        keys = ("backend", "rows", "features", "bins", "trees", "depth",
                "partitions", "hist_impl", "seed")
        return bench_train(**{k: kw[k] for k in keys if k in kw})
    if kernel == "predict":
        keys = ("backend", "rows", "features", "bins", "trees", "depth",
                "partitions", "seed")
        return bench_predict(**{k: kw[k] for k in keys if k in kw})
    if kernel == "serve":
        keys = ("backend", "rows", "features", "bins", "trees", "depth",
                "seed")
        return bench_serve_latency(**{k: kw[k] for k in keys if k in kw})
    if kernel == "registry":
        keys = ("backend", "features", "bins", "trees", "depth", "seed")
        return bench_registry_cold_load(
            **{k: kw[k] for k in keys if k in kw})
    if kernel == "hist_comms":
        keys = ("rows", "features", "bins", "depth", "iters", "seed")
        return bench_hist_comms_ab(**{k: kw[k] for k in keys if k in kw})
    if kernel == "hist_2d":
        keys = ("rows", "features", "bins", "depth", "iters", "seed")
        return bench_hist_2d(**{k: kw[k] for k in keys if k in kw})
    if kernel == "hist_quant":
        keys = ("rows", "features", "bins", "depth", "iters", "seed",
                "grad_dtype")
        return bench_hist_quant_ab(**{k: kw[k] for k in keys if k in kw})
    if kernel == "lut4":
        keys = ("rows", "features", "bins", "trees", "depth", "seed")
        return bench_predict_lut4_ab(
            **{k: kw[k] for k in keys if k in kw})
    raise ValueError(f"unknown bench kernel {kernel!r}")
