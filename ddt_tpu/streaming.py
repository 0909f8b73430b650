"""Streaming trainer for datasets that don't fit in device (or host) memory.

The 10B-row / 1024-feature stress config (BASELINE.json) cannot hold a binned
matrix anywhere — 10 TB of uint8. SURVEY.md §5's "long axis" story: shard and
STREAM the row axis with per-chunk histogram accumulation. Histograms are
small (≤ MBs) and additive, so streaming needs no ring algorithms: per level,

    hist = Σ_chunks build_histograms(chunk, g_chunk, h_chunk, node_of_row)

with node_of_row recomputed per chunk by STATELESS traversal of the partial
tree — a row's node at level d is fully determined by the tree grown so far,
so no per-row state survives between chunks. Gradients are likewise stateless:
pred of a row is the partial ensemble's score (optionally cached per chunk on
host when it fits — cache_preds trades O(T²) rescoring for O(R) host RAM).

The chunk source is a callable (chunk_idx) -> (Xb_chunk, y_chunk): pure, so
any chunk can be regenerated on any host at any time (the deterministic
synthetic generator data/datasets.stress_binned_chunk is one; a file-backed
loader fits the same signature). Chunks may differ in size (each distinct
size jit-compiles its own per-level program — keep the number of distinct
sizes small); empty chunks are not allowed. This trainer matches the
in-memory Driver bitwise on the same data (tests/test_streaming.py),
except at exact bf16-boundary candidate ties where the chunked f32
summation order can legitimately pick the other side (~1 node per 160k,
measured — ops/split.py "Determinism boundary").

Distribution composes: each chunk is row-sharded over the TPUDevice mesh like
any other upload, so a v5e-64 pod streams 8 host-chunks in parallel while each
chunk's histogram psum rides ICI (SURVEY.md §7 M6).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable

import numpy as np

from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import TreeEnsemble, empty_ensemble
from ddt_tpu.reference.numpy_trainer import grad_hess
from ddt_tpu.telemetry import costmodel
from ddt_tpu.telemetry import counters as tele_counters
from ddt_tpu.telemetry.annotations import phase_ctx
from ddt_tpu.ops.grow import resolve_hist_subtraction
from ddt_tpu.telemetry.events import (
    PartitionRecorder, RoundRecorder, RunLog, comms_manifest_fields,
    derive_run_id, device_manifest_fields, emit_early_stop,
    emit_train_heartbeat, finish_run_log)
from ddt_tpu.utils import checkpoint
from ddt_tpu.utils.profiling import PhaseTimer

log = logging.getLogger("ddt_tpu.streaming")

ChunkFn = Callable[[int], tuple[np.ndarray, np.ndarray]]


def _emit_round(run_log: "RunLog | None", rnd: int, ms: float,
                ev: "_StreamEval | None", status=None) -> None:
    """Streaming round event: ms + the round's eval score when tracked
    (train loss is deliberately absent — computing it would cost an extra
    full pass over the chunks). Also the streamed loops' round-boundary
    progress hook: bumps the train_rounds counter and, when a live
    TrainStatus is attached (cli --status-port), pushes the round into
    its rolling window/ring."""
    tele_counters.record_train_round()
    if run_log is None and status is None:
        return
    val_score = None
    if ev is not None and ev.history:
        last = ev.history[-1]
        if last.get("round") == rnd + 1:
            val_score = last.get(f"valid_{ev.metric}")
    rec = RoundRecorder.make_record(rnd, ms, None,
                                    ev.metric if ev is not None else None,
                                    val_score)
    if run_log is not None:
        run_log.emit("round", **rec)
    if status is not None:
        status.round_end(rnd, ms, rec)


def validate_mapper_config(mapper, cfg: TrainConfig) -> None:
    """The mapper↔config consistency guards api.train enforces, for the
    streaming paths (a mismatched mapper trains a silently wrong model,
    not a crashing one)."""
    if mapper.n_bins != cfg.n_bins:
        raise ValueError(
            f"mapper was fitted with n_bins={mapper.n_bins} but "
            f"cfg.n_bins={cfg.n_bins}"
        )
    if (cfg.missing_policy == "learn") != mapper.missing_bin:
        raise ValueError(
            f"mapper.missing_bin={mapper.missing_bin} but "
            f"cfg.missing_policy={cfg.missing_policy!r}; refit the mapper "
            "with the same policy"
        )
    if cfg.cat_features:
        bad = mapper.non_identity_columns(cfg.cat_features)
        if bad:
            raise ValueError(
                f"cat_features {bad} were not identity-binned by this "
                "mapper; refit it with "
                f"cat_features={tuple(sorted(cfg.cat_features))}"
            )


def binned_chunks(chunk_fn: ChunkFn, mapper, cfg: TrainConfig) -> ChunkFn:
    """Adapt a RAW-float chunk source into the binned source
    fit_streaming consumes, via a fitted BinMapper (see
    data/quantizer.fit_bin_mapper_streaming for fitting one without
    materialising the dataset). Purity is preserved: any chunk still
    regenerates anywhere, bins included — which also means every re-read
    re-bins; callers whose binned chunks fit somewhere can cache them.

    `cfg` is required so the mapper↔config consistency guards that
    api.train enforces hold on this path too."""
    validate_mapper_config(mapper, cfg)

    def f(c: int):
        X, y = chunk_fn(c)
        return mapper.transform(np.asarray(X, np.float32)), y

    # Side-channel accessors so fit_streaming's label-only pass 0 and
    # shape probe skip the (expensive) binning of chunks they would
    # otherwise transform and throw away.
    f.labels = lambda c: chunk_fn(c)[1]
    f.n_features = mapper.n_features
    return f


def _go_right(
    fv: np.ndarray,           # winning-column bin values for the live rows
    nodes: np.ndarray,        # their heap slots
    feature: np.ndarray,
    threshold_bin: np.ndarray,
    default_left: np.ndarray | None,
    missing_bin_value: int,
    cat_features: tuple,
) -> np.ndarray:
    """Routing decision with the full split semantics (ordinal,
    categorical one-vs-rest, reserved-NaN-bin default direction) — the
    single host home of the streamed routing rule."""
    thr = threshold_bin[nodes]
    go_right = fv > thr
    if cat_features:
        cat = np.isin(feature[nodes], cat_features)
        go_right = np.where(cat, fv != thr, go_right)
    if missing_bin_value >= 0:
        go_right = np.where(fv == missing_bin_value,
                            ~default_left[nodes], go_right)
    return go_right


def _traverse_partial(
    Xb: np.ndarray,
    feature: np.ndarray,
    threshold_bin: np.ndarray,
    is_leaf: np.ndarray,
    depth: int,
    default_left: np.ndarray | None = None,
    missing_bin_value: int = -1,
    cat_features: tuple = (),
) -> np.ndarray:
    """Stateless node assignment at `depth`: heap slot per row, or -1 when the
    row froze at a leaf above this level. Mirrors the in-memory grow loop's
    (node_id, frozen) evolution exactly."""
    R = Xb.shape[0]
    node = np.zeros(R, np.int64)
    frozen = np.zeros(R, bool)
    for d in range(depth):
        live = ~frozen & ~is_leaf[node]
        frozen |= is_leaf[node]
        f = feature[node[live]]
        fv = Xb[live, f].astype(np.int64)
        go_right = _go_right(fv, node[live], feature, threshold_bin,
                             default_left, missing_bin_value, cat_features)
        node[live] = 2 * node[live] + 1 + go_right
    offset = (1 << depth) - 1
    out = (node - offset).astype(np.int32)
    out[frozen] = -1
    return out


def _apply_level_splits(
    hist: np.ndarray,
    cfg: TrainConfig,
    depth: int,
    feature: np.ndarray,
    threshold_bin: np.ndarray,
    is_leaf: np.ndarray,
    leaf_value: np.ndarray,
    split_gain: np.ndarray,
    default_left: np.ndarray | None = None,
    feature_mask: np.ndarray | None = None,
) -> None:
    """Level-`depth` split decisions from the accumulated histogram,
    written into the node arrays in place. The SINGLE home of the
    streamed split rule — both the host and device loops call this, so
    host/device bit-identity cannot drift. `feature_mask` is the round's
    colsample mask (ops/sampling.colsample_mask — the identical rule the
    Driver applies inside grow: masked features never win the argmax)."""
    from ddt_tpu.reference.numpy_trainer import best_splits, node_totals

    n_level = 1 << depth
    offset = n_level - 1
    G, H = node_totals(hist)
    cat_mask = None
    if cfg.cat_features:
        cat_mask = np.zeros(hist.shape[1], bool)
        cat_mask[list(cfg.cat_features)] = True
    gains, feats, bins, dls = best_splits(
        hist, cfg.reg_lambda, cfg.min_child_weight,
        feature_mask=feature_mask,
        missing_bin=cfg.missing_policy == "learn", cat_mask=cat_mask)
    with np.errstate(divide="ignore", invalid="ignore"):   # empty nodes
        value = np.where(H > 0, -G / (H + cfg.reg_lambda), 0.0).astype(
            np.float32)
    do_split = (gains > cfg.min_split_gain) & np.isfinite(gains) & (H > 0)
    for i in range(n_level):
        slot = offset + i
        if do_split[i]:
            feature[slot] = feats[i]
            threshold_bin[slot] = bins[i]
            split_gain[slot] = gains[i]
            if default_left is not None:
                default_left[slot] = dls[i]
        else:
            is_leaf[slot] = True
            leaf_value[slot] = value[i]


def _assemble_subtracted_level(
    parent_hist: np.ndarray,     # [2^(d-1), F, B, 2]: previous level's
    #                              fully-ACCUMULATED histograms
    left: np.ndarray,            # [2^(d-1), F, B, 2]: this level's
    #                              accumulated LEFT-child histograms
    is_leaf: np.ndarray,
    depth: int,
) -> np.ndarray:
    """Sibling-subtraction assembly for the streamed host accumulator —
    the host twin of ops/grow.level_histograms' subtract branch: right
    child = parent - left, gated to exactly zero for children of parents
    that did NOT split (a frozen parent's phantom right child would
    otherwise inherit the full parent mass), interleaved back to level
    order (left = 2p, right = 2p + 1). Dtype-generic: quantized-gradient
    levels carry int32 accumulations, where the subtraction is EXACT
    (the f32-ULP right-child seam does not exist on that path)."""
    half = 1 << (depth - 1)
    offset = half - 1
    gate = ~is_leaf[offset:offset + half]
    right = np.where(gate[:, None, None, None],
                     parent_hist - left, left.dtype.type(0))
    out = np.empty((2 * half,) + left.shape[1:], left.dtype)
    out[0::2] = left
    out[1::2] = right
    return out


def _apply_final_leaves(
    Gl: np.ndarray,
    Hl: np.ndarray,
    cfg: TrainConfig,
    is_leaf: np.ndarray,
    leaf_value: np.ndarray,
) -> None:
    """Final-level leaf values from streamed (G, H) aggregates (shared by
    the host and device loops)."""
    n_last = 1 << cfg.max_depth
    offset = n_last - 1
    with np.errstate(divide="ignore", invalid="ignore"):   # empty nodes
        vals = np.where(Hl > 0, -Gl / (Hl + cfg.reg_lambda), 0.0)
    is_leaf[offset:offset + n_last] = True
    leaf_value[offset:offset + n_last] = vals.astype(np.float32)


class _StreamEval:
    """Held-out-chunk validation for the streaming trainers (round-2
    verdict item 3): per-round metric over streamed validation chunks,
    best-round tracking, early stopping. Metrics evaluate on HOST in f64
    over the concatenated per-chunk raw scores — the Driver's host eval
    path, so auc works and stopping decisions are backend-invariant (the
    f32 device-metric boundary documented in driver.py does not apply
    here). Validation labels are O(val rows) host state — the val set is
    the small fraction; the 10B-row axis being streamed is the train set.
    """

    def __init__(self, valid_chunk_fn: ChunkFn, n_valid_chunks: int,
                 metric_name: str | None, loss: str,
                 early_stopping_rounds: int | None,
                 history: list | None):
        from ddt_tpu.utils.metrics import GREATER_IS_BETTER, default_metric

        if n_valid_chunks < 1:
            raise ValueError("validation needs n_valid_chunks >= 1")
        self.fn = valid_chunk_fn
        self.n = n_valid_chunks
        self.metric = metric_name or default_metric(loss)
        if self.metric not in GREATER_IS_BETTER:
            raise ValueError(
                f"unknown metric {self.metric!r}; "
                f"have {sorted(GREATER_IS_BETTER)}"
            )
        if self.metric == "auc" and loss == "softmax":
            # Same guard as Driver.fit: the rank formulation is binary,
            # and multiclass raw scores crash deep inside the host auc.
            raise ValueError(
                "auc is a binary metric; softmax validation supports "
                "logloss or accuracy"
            )
        self.sign = 1.0 if GREATER_IS_BETTER[self.metric] else -1.0
        self.patience = early_stopping_rounds
        self.history = history if history is not None else []
        labels_of = getattr(valid_chunk_fn, "labels", None) or (
            lambda c: valid_chunk_fn(c)[1])
        ys = [np.asarray(labels_of(c)) for c in range(self.n)]
        if any(len(y) == 0 for y in ys):
            raise ValueError("empty validation chunks are not allowed")
        self._ys = ys
        self.y = np.concatenate(ys)
        self.lens = [len(y) for y in ys]
        self.best = -np.inf
        self.best_round: int | None = None
        self.best_score: float | None = None

    def labels(self, c: int) -> np.ndarray:
        """Chunk c's labels WITHOUT re-reading (or re-binning) the chunk."""
        return self._ys[c]

    def record(self, rnd: int, raw_scores: np.ndarray) -> bool:
        """Score round `rnd` from the concatenated raw validation scores;
        returns True when early stopping says stop AFTER this round."""
        from ddt_tpu.utils.metrics import evaluate

        s = evaluate(self.metric, self.y, raw_scores)
        self.history.append({"round": rnd + 1, f"valid_{self.metric}": s})
        log.info("streaming: round %d valid_%s=%.6f", rnd + 1, self.metric,
                 s)
        if self.sign * s > self.best:
            self.best = self.sign * s
            self.best_round = rnd
            self.best_score = s
        if self.patience is None:
            return False
        if self.best_round is None:
            # Same guard as Driver.fit: NaN never improves on -inf.
            raise ValueError(
                f"validation {self.metric} has been NaN since round 1 "
                "(degenerate validation chunks); cannot early-stop on it"
            )
        return rnd - self.best_round >= self.patience


# Default HBM budget for the device-resident chunk cache: big enough to
# hold mid-size out-of-core datasets entirely (a v5e core has 16 GB),
# small enough to leave the working set (histograms, preds, pipeline
# buffers) ample headroom.
DEVICE_CHUNK_CACHE_BYTES = 6 << 30


class _DeviceChunkCache:
    """Memoises `backend.upload(chunk)` per chunk index up to a shared
    byte budget. Streamed training re-reads every chunk (max_depth + 1)
    times per tree; when the binned chunks fit in device memory, paying
    the host→device transfer once and serving every later pass from HBM
    removes the per-pass transfer entirely (what that buys on the chip:
    not measured). Chunks past the budget simply upload
    per use, preserving O(working-set) device memory for datasets that
    do not fit. Safe because no stream op donates its data operand
    (backends/tpu.py _stream_fn: only pred is donated)."""

    def __init__(self, backend, chunk_fn, budget: list):
        self._backend = backend
        self._chunk_fn = chunk_fn
        self._budget = budget          # [remaining_bytes], shared train/val
        self._cached: dict = {}        # c -> (handle, nbytes)

    def _upload(self, c: int):
        """One chunk's device handle — via the host-sharded per-process
        assembly when the source is per-host-addressable
        (data.chunks.HostShardedChunks + TPUDevice.upload_row_shards:
        this process reads ONLY its own sub-shards), else the classic
        full-chunk read + row-sharded upload."""
        src = self._chunk_fn
        if getattr(src, "host_sharded", False) and \
                getattr(self._backend, "upload_row_shards", None) \
                is not None:
            parts = [src.read_part(c, s) for s in src.owned_slots(c)]
            return self._backend.upload_row_shards(parts,
                                                   src.chunk_rows(c))
        Xc = np.asarray(src(c)[0])
        return self._backend.upload(Xc)

    def get(self, c: int):
        hit = self._cached.get(c)
        if hit is not None:
            return hit[0]
        h = self._upload(c)
        # Budget accounting uses the handle's ACTUAL per-process device
        # footprint (upload pads rows to the shard count and uneven chunk
        # sizes pad differently, so host-side Xc.nbytes undercounts).
        # Summing addressable shards is per-process by construction —
        # exactly what a per-process HBM budget should track.
        try:
            nbytes = sum(s.data.nbytes for s in h.addressable_shards)
        except (AttributeError, TypeError):
            nbytes = int(np.asarray(h).nbytes)   # host arrays: no shards
        if nbytes <= self._budget[0]:
            self._budget[0] -= nbytes
            self._cached[c] = (h, nbytes)
        return h

    def clear(self) -> None:
        """Drop every cached handle and refund the budget — the streamed
        re-partition rebuilt the mesh, so cached placements are stale
        (the next get() re-uploads onto the rotated device order)."""
        for _, nbytes in self._cached.values():
            self._budget[0] += nbytes
        self._cached.clear()


def fit_streaming(
    chunk_fn: ChunkFn,
    n_chunks: int,
    cfg: TrainConfig,
    backend=None,
    cache_preds: bool = True,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 25,
    valid_chunk_fn: ChunkFn | None = None,
    n_valid_chunks: int = 0,
    eval_metric: str | None = None,
    early_stopping_rounds: int | None = None,
    history: list | None = None,
    device_chunk_cache: "bool | int" = True,
    run_log: "RunLog | str | None" = None,
    profile: bool = False,
    profiler_window=None,
    status=None,
) -> TreeEnsemble:
    """Train a GBDT over streamed chunks — see _fit_streaming_impl
    directly below for the full contract (validation, checkpointing,
    device streaming, sampling, telemetry). This wrapper owns exactly
    one concern: run-scoped state built HERE — a run log coerced from a
    path string, the cost-capture collector, a still-open xprof window,
    the robustness fault sink, a cfg.fault_plan chaos plan — is torn
    down on every exit, success or mid-run exception (the Driver has
    the same shim on fit), so repeated failing fits cannot leak file
    handles or bill capture work to later runs.

    The chunk sources are additionally wrapped in the stream-read retry
    seam (utils/retry.retrying_chunk_fn): every read — training, value
    and label-only alike, on both the host and device loops — retries
    transient I/O faults with jittered backoff, each failed attempt
    emitting a schema'd `fault` event. Chunk sources are pure by
    contract, so a retried re-read changes nothing."""
    from ddt_tpu.robustness import faultplan, set_fault_sink
    from ddt_tpu.utils import retry as retry_lib

    # Load the plan BEFORE touching any process-global state: a bad plan
    # file must fail clean, not leak the sink or the cost collector.
    plan = None
    if cfg.fault_plan and faultplan.active_plan() is None:
        plan = faultplan.load_plan(cfg.fault_plan)
    own_run_log = isinstance(run_log, str)
    run_log = RunLog.coerce(run_log)
    # Device-truth cost capture (telemetry/costmodel.py): telemetry runs
    # only; torn down below even when the fit dies mid-round.
    cost = costmodel.activate() if run_log is not None else None
    prev_sink = set_fault_sink(run_log)
    plan_prev = None
    plan_armed = False
    if plan is not None:
        plan_prev = faultplan.activate(plan)
        plan_armed = True
    chunk_fn = retry_lib.retrying_chunk_fn(chunk_fn)
    if valid_chunk_fn is not None:
        valid_chunk_fn = retry_lib.retrying_chunk_fn(valid_chunk_fn)
    try:
        return _fit_streaming_impl(
            chunk_fn, n_chunks, cfg, backend=backend,
            cache_preds=cache_preds, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            valid_chunk_fn=valid_chunk_fn, n_valid_chunks=n_valid_chunks,
            eval_metric=eval_metric,
            early_stopping_rounds=early_stopping_rounds, history=history,
            device_chunk_cache=device_chunk_cache, run_log=run_log,
            profile=profile, cost_collector=cost,
            profiler_window=profiler_window, status=status)
    finally:
        costmodel.deactivate(cost)
        if profiler_window is not None:
            profiler_window.close()
        if plan_armed:
            faultplan.deactivate(plan_prev)
        set_fault_sink(prev_sink)
        if own_run_log and run_log is not None:
            run_log.close()


def _fit_streaming_impl(
    chunk_fn: ChunkFn,
    n_chunks: int,
    cfg: TrainConfig,
    backend=None,
    cache_preds: bool = True,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 25,
    valid_chunk_fn: ChunkFn | None = None,
    n_valid_chunks: int = 0,
    eval_metric: str | None = None,
    early_stopping_rounds: int | None = None,
    history: list | None = None,
    device_chunk_cache: "bool | int" = True,
    run_log: "RunLog | None" = None,
    profile: bool = False,
    cost_collector=None,
    profiler_window=None,
    status=None,
) -> TreeEnsemble:
    """Train a GBDT over `n_chunks` streamed chunks.

    Observability: `run_log` (a JSONL path or telemetry.RunLog) emits the
    same schema-versioned event stream as Driver.fit — run manifest,
    per-round records (with the round's eval metric when validation is
    on), per-phase timings, resume events, device counters — rendered by
    `python -m ddt_tpu.cli report`. `profile=True` additionally logs the
    PhaseTimer breakdown at INFO; either flag turns phase timing on
    (host wallclock per hist/gain/leaf/predict/eval phase; the streamed
    loops' natural pass boundaries already sync, so no extra barriers
    are added).

    Validation/early stopping (round-2 verdict item 3): pass held-out
    chunks via `valid_chunk_fn`/`n_valid_chunks` — each round's freshly
    grown trees are applied to per-chunk validation predictions (device-
    resident on device backends, exactly like the training state) and the
    metric is recorded in `history` ({"round", "valid_<metric>"}, the
    Driver's record shape). With `early_stopping_rounds=k`, training
    stops after k rounds without improvement and the returned ensemble is
    truncated to the best round — identical truncation semantics to
    Driver.fit. On checkpoint resume, best-round tracking restarts at the
    resume round (earlier rounds' scores are not re-evaluated).

    Device backends exposing the stream_* surface (TPUDevice) run the
    whole per-(chunk, level) step on device — traversal, grads, histogram,
    psum — with the NEXT chunk's upload overlapping the current chunk's
    compute, and per-chunk boosting state (pred, labels) resident on
    device for the whole run (ops/stream.py; supports softmax and
    n_partitions/host_partitions > 1). Host backends stream the host
    formulation (binary/mse/softmax — one tree per class per round from
    round-start preds, like the Driver). Both match the in-memory Driver
    on the same data bitwise — including missing_policy='learn'
    (reserved NaN bin + learned default directions) and categorical
    one-vs-rest splits (tests/test_streaming.py) — except when a node's
    two best candidate gains are exact bf16-boundary ties, where the
    chunked host accumulation's f32 summation order can legitimately
    pick the other candidate (~1 node per 160k, measured; ops/split.py
    "Determinism boundary", chunked-accumulation paragraph).

    Sampling configs stream too (round-4 verdict item 2): bagging keeps
    a row by the stateless counter-based hash of (seed, round, GLOBAL
    row id) — ops/sampling — computed per chunk from the chunk's row
    offset (O(chunk), on device on the device path), and colsample draws
    the same per-(round, class) host masks as the Driver, applied at the
    shared split-selection home (_apply_level_splits). Both therefore
    grow the in-memory Driver's exact trees, same contract (and same
    bf16-boundary-tie seam) as deterministic streaming.

    `device_chunk_cache` (device backends only): True caches uploaded
    binned chunks in device memory up to DEVICE_CHUNK_CACHE_BYTES —
    but only when the device has memory of its own (on a CPU-platform
    run the "device" IS host RAM, so True degrades to no caching there:
    pinning min(dataset, 6 GiB) of host memory would break the O(chunk)
    host contract this trainer exists for). An int budget is always
    honored verbatim (that is how the CPU-platform tests force the
    cache on); False re-uploads every pass (the pre-round-4 behavior).
    Caching changes no results — the same buffers feed the same ops —
    only how often the H2D link is paid: once per chunk instead of
    (max_depth + 1) times per tree. Host memory stays O(chunk); device
    memory grows to min(dataset, budget).
    """
    if backend is None:
        from ddt_tpu.backends import get_backend

        backend = get_backend(cfg)

    device = hasattr(backend, "stream_level_hist")
    if cfg.grad_dtype != "f32" and not device:
        # The quantized path's per-round scale pass and integer builds
        # are device ops (backends/tpu.py stream_grad_stats /
        # stream_level_hist); the host loop's numpy builders have no
        # integer twin. Refuse loudly — a silently-f32 "quantized" run
        # is worse than an error (backend='tpu' runs on CPU XLA too).
        raise NotImplementedError(
            f"grad_dtype={cfg.grad_dtype!r} streaming requires a device "
            "backend exposing the stream_* surface (backend='tpu'); the "
            "host streaming loop has no integer histogram path")

    # Telemetry prologue — BEFORE pass 0 so the transfer counters see the
    # label uploads; host-side bookkeeping only (no device syncs), and
    # everything below is skipped when run_log is None and profile False.
    t_fit0 = time.perf_counter()
    counters_start = None
    timer = PhaseTimer() if (profile or run_log is not None) else None
    ph = phase_ctx(timer)
    if run_log is not None:
        tele_counters.install_jax_listener()
        counters_start = tele_counters.snapshot()

    # Pass 0: base score from running label sums + shape discovery — no
    # O(R) host state anywhere in this trainer except the optional preds
    # cache (see below); at the 10B-row target everything else is O(chunk).
    # Device backends also ship labels NOW (one read of each chunk, not a
    # second pass): labels stay device-resident for the whole run.
    y_sum, y_cnt = 0.0, 0
    chunk_lens = []
    y_dev = []
    # binned_chunks-style adapters expose a label-only accessor so this
    # pass doesn't pay for binning feature matrices it never reads.
    labels_of = getattr(chunk_fn, "labels", None) or (
        lambda c: chunk_fn(c)[1])
    for c in range(n_chunks):
        yc = labels_of(c)
        if len(yc) == 0:
            # Fail HERE, at the cause — a zero-row chunk otherwise dies
            # far away (device shard padding / NaN base score).
            raise ValueError(
                f"chunk {c} is empty; empty chunks are not allowed "
                "(re-cut the chunk boundaries)"
            )
        y_sum += float(np.sum(yc))
        y_cnt += len(yc)
        chunk_lens.append(len(yc))
        if device:
            y_dev.append(backend.upload_labels(np.asarray(yc)))
    # Global row offset per chunk — the bagging hash is a function of a
    # row's GLOBAL id, so chunk boundaries cannot change the masks.
    chunk_starts = np.concatenate(
        [[0], np.cumsum(chunk_lens)]).astype(np.int64)
    mean = y_sum / max(1, y_cnt)
    if cfg.loss == "logloss":
        p_ = float(np.clip(mean, 1e-6, 1 - 1e-6))
        bs = float(np.log(p_ / (1 - p_)))
    elif cfg.loss == "softmax":
        bs = 0.0
    else:
        bs = float(mean)
    F = getattr(chunk_fn, "n_features", None)
    if F is None:
        F = chunk_fn(0)[0].shape[1]

    C = cfg.n_classes if cfg.loss == "softmax" else 1
    ens = empty_ensemble(
        cfg.n_trees * C, cfg.max_depth, F, cfg.learning_rate, bs,
        cfg.loss, cfg.n_classes,
        missing_bin=cfg.missing_policy == "learn", n_bins=cfg.n_bins,
        cat_features=cfg.cat_features,
    )

    trainer_name = "streaming_device" if device else "streaming_host"
    # Deterministic config digest: the v2 merge key AND the xprof
    # window's trace-dir name — computed whenever either consumer wants
    # it (the FULL config feeds it so sweep points differing in any
    # field refuse to merge).
    run_id = None
    if (run_log is not None or profiler_window is not None
            or status is not None):
        run_id = derive_run_id(
            trainer=trainer_name, rows=int(y_cnt), features=int(F),
            n_chunks=n_chunks, **dataclasses.asdict(cfg))
    if profiler_window is not None:
        profiler_window.bind(run_id)
    if status is not None:
        # Live status daemon (telemetry/statusd.py) — seed the run
        # identity/denominators before round 0 so the first scrape
        # already answers "which run, how far along".
        status.begin_run(run_id=run_id, total_rounds=cfg.n_trees,
                         rows=int(y_cnt))
    if run_log is not None:
        run_log.run_id = run_id
        run_log.emit(
            "run_manifest",
            trainer=trainer_name,
            backend=getattr(backend, "name", "unknown"), loss=cfg.loss,
            n_trees=cfg.n_trees, max_depth=cfg.max_depth,
            n_bins=cfg.n_bins, rows=int(y_cnt), features=int(F),
            n_classes=C, seed=cfg.seed, n_chunks=n_chunks,
            distributed=bool(getattr(backend, "distributed", False)),
            run_id=run_id,
            host=int(getattr(backend, "host_index", 0)),
            **comms_manifest_fields(backend),
            **device_manifest_fields(backend),
            # v3 extras: the xprof cross-reference (telemetry/profiler).
            **(profiler_window.manifest_fields()
               if profiler_window is not None else {}))

    # Per-partition attribution for mesh runs (inert otherwise — the
    # recorder only probes when distributed AND a run log is attached;
    # the streamed estimate is per chunk-pass, n_chunks allreduces/round).
    part_rec = PartitionRecorder(
        run_log, backend,
        bytes_per_round=(
            C * n_chunks * backend.collective_bytes_per_tree(
                int(F), streamed=True)
            if getattr(backend, "distributed", False) else 0))
    # Straggler watchdog (robustness/watchdog.py) — detection always
    # (fault events per trip); behind cfg.straggler_repartition the
    # DEVICE streaming loop also ACTS at checkpoint-cadence boundaries:
    # mesh rotation + resident-state reshard + chunk-cache drop + a
    # host-sharded source's chunk-shard->host assignment rotation
    # (bit-identical by construction — the rotate_row_partitions
    # contract extended to the streamed path, ROADMAP item 2). Exists
    # exactly when the recorder is active.
    watchdog = None
    if part_rec.active:
        from ddt_tpu.robustness.watchdog import StragglerWatchdog

        watchdog = StragglerWatchdog(
            threshold=cfg.straggler_skew_threshold)

    def _finish(e: TreeEnsemble) -> TreeEnsemble:
        """Telemetry epilogue — every fit_streaming return funnels
        through here (the early-stop returns included) so a run log is
        always terminated by the shared phase_timings/counters/run_end
        sequence (telemetry.events.finish_run_log; the owning wrapper
        closes path-built logs)."""
        if profile and timer is not None:
            timer.log_report(log)
        if status is not None:
            status.set_phase("done")
        finish_run_log(run_log, timer, counters_start, e.n_trees // C,
                       round(time.perf_counter() - t_fit0, 4),
                       partitions=part_rec, costs=cost_collector)
        return e

    # Checkpoint/resume (SURVEY.md §5) — the streamed runs are the LONGEST
    # ones, so restartability matters most here. Boosting state is
    # reconstituted by rescoring the restored partial ensemble per chunk
    # with the Driver's per-round accumulation order (bit-exact resume).
    start_round = 0
    if checkpoint_dir is not None:
        if checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}")
        from ddt_tpu.utils.checkpoint import try_resume

        start_round = try_resume(checkpoint_dir, ens, cfg,
                                 run_log=run_log)
        if start_round > 0:
            log.info("streaming: resumed from checkpoint at round %d",
                     start_round)
            if run_log is not None:
                run_log.emit("fault", kind="checkpoint_resume",
                             round=start_round)
        if start_round >= cfg.n_trees:
            # Already finished (e.g. a preemptible-restart loop re-runs
            # the command): return the restored ensemble without the full
            # boosting-state reconstitution pass over the dataset.
            return _finish(ens)

    if early_stopping_rounds is not None and valid_chunk_fn is None:
        raise ValueError("early_stopping_rounds requires valid_chunk_fn")
    ev = None
    if valid_chunk_fn is not None:
        ev = _StreamEval(valid_chunk_fn, n_valid_chunks, eval_metric,
                         cfg.loss, early_stopping_rounds, history)

    if device:
        return _finish(_fit_streaming_device(
            chunk_fn, n_chunks, cfg, backend, ens, bs, C, y_dev,
            chunk_starts,
            start_round=start_round, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, ev=ev,
            device_chunk_cache=device_chunk_cache,
            ph=ph, run_log=run_log, part_rec=part_rec,
            window=profiler_window, watchdog=watchdog, status=status))

    # The ONE optional O(R·C) structure: per-chunk cached raw scores (4C
    # bytes/row). cache_preds=False recomputes scores from the partial
    # ensemble instead (O(T) traversals per row per round) — choose by host
    # RAM.
    def _fresh_pred(c):
        if C > 1:
            return np.zeros((chunk_lens[c], C), np.float32)   # softmax bs=0
        return np.full(chunk_lens[c], bs, np.float32)

    preds = (
        [_fresh_pred(c) for c in range(n_chunks)] if cache_preds else None
    )
    if preds is not None and start_round > 0:
        part = ens.truncate(start_round * C)
        for c in range(n_chunks):
            preds[c] = part.predict_raw_roundwise(
                chunk_fn(c)[0], binned=True).astype(np.float32)

    # Validation predictions: host-resident per val chunk (always cached —
    # the val set is the small fraction), updated per round like the
    # Driver's incremental val_raw.
    val_preds = None
    if ev is not None:
        def _fresh_val(c):
            if C > 1:
                return np.zeros((ev.lens[c], C), np.float32)
            return np.full(ev.lens[c], bs, np.float32)

        val_preds = [_fresh_val(c) for c in range(ev.n)]
        if start_round > 0:
            part = ens.truncate(start_round * C)
            for c in range(ev.n):
                val_preds[c] = part.predict_raw_roundwise(
                    ev.fn(c)[0], binned=True).astype(np.float32)

    missing_val = cfg.missing_bin_value
    # Streamed sibling subtraction (the fused rounds' halving, extended
    # to the host accumulation loop): levels >= 1 build only LEFT-child
    # chunk histograms — half the device work AND half the streamed
    # collective payload per pass — and the right children are assembled
    # by subtraction from the previous level's ACCUMULATED histogram
    # (_assemble_subtracted_level). Platform-gated exactly like the
    # fused path (resolve_hist_subtraction): right children differ from
    # direct builds by f32 chunk-summation ULPs.
    subtract = resolve_hist_subtraction(cfg.hist_subtraction)
    coll_bytes_round = 0
    if getattr(backend, "distributed", False):
        coll_bytes_round = C * n_chunks * backend.collective_bytes_per_tree(
            F, streamed=True)
    t_out = start_round * C
    for rnd in range(start_round, cfg.n_trees):
        if profiler_window is not None:       # xprof window: start edge
            profiler_window.round_start(rnd)
        t_round = time.perf_counter()
        # Gradients for every class tree of a round come from the
        # ROUND-START preds (the Driver computes grad_hess once per round,
        # then grows C trees from its columns), so pred updates are
        # deferred until after all classes — mirroring the device loop.
        def chunk_grads(c: int, Xc, yc, cls: int):
            pred_c = preds[c] if preds is not None else _rescore(
                ens, rnd * C, Xc, bs
            )
            g, h = grad_hess(pred_c, np.asarray(yc), cfg.loss)
            if g.ndim == 2:
                g, h = g[:, cls], h[:, cls]
            if cfg.subsample < 1.0:
                from ddt_tpu.ops.sampling import row_keep_np

                keep = row_keep_np(cfg.seed, rnd, int(chunk_starts[c]),
                                   len(yc), cfg.subsample)
                g, h = g * keep, h * keep
            return g, h

        def colsample_mask_for(cls: int):
            if cfg.colsample_bytree >= 1.0:
                return None
            from ddt_tpu.ops.sampling import colsample_mask

            return colsample_mask(cfg.seed, rnd, cls, F,
                                  cfg.colsample_bytree)

        round_trees = []
        for cls in range(C):
            fmask = colsample_mask_for(cls)
            # Grow one tree level-by-level; histograms accumulate across
            # chunks.
            feature = np.full(cfg.n_nodes_total, -1, np.int32)
            threshold_bin = np.zeros(cfg.n_nodes_total, np.int32)
            is_leaf = np.zeros(cfg.n_nodes_total, bool)
            leaf_value = np.zeros(cfg.n_nodes_total, np.float32)
            split_gain = np.zeros(cfg.n_nodes_total, np.float32)
            default_left = np.zeros(cfg.n_nodes_total, bool)

            route_kw = dict(default_left=default_left,
                            missing_bin_value=missing_val,
                            cat_features=cfg.cat_features)
            prev_hist = None
            for depth in range(cfg.max_depth):
                n_level = 1 << depth
                sub = subtract and depth >= 1 and prev_hist is not None
                hist = None
                with ph("hist"):
                    for c in range(n_chunks):
                        Xc, yc = chunk_fn(c)
                        ni = _traverse_partial(
                            Xc, feature, threshold_bin, is_leaf, depth,
                            **route_kw
                        )
                        if sub:
                            # LEFT children keyed by parent slot: half
                            # the per-chunk build and half the streamed
                            # collective payload (right children come
                            # from subtraction below).
                            is_l = (ni >= 0) & (ni % 2 == 0)
                            ni = np.where(is_l, ni // 2, -1).astype(
                                np.int32)
                        g, h = chunk_grads(c, Xc, yc, cls)
                        data = backend.upload(Xc)
                        part = np.asarray(
                            backend.build_histograms(
                                data, g, h, ni,
                                n_level // 2 if sub else n_level)
                        )
                        hist = part if hist is None else hist + part
                if sub:
                    hist = _assemble_subtracted_level(prev_hist, hist,
                                                      is_leaf, depth)
                with ph("gain"):
                    _apply_level_splits(hist, cfg, depth, feature,
                                        threshold_bin, is_leaf, leaf_value,
                                        split_gain, default_left,
                                        feature_mask=fmask)
                prev_hist = hist if subtract else None

            # Final level: per-terminal (G, H) aggregates streamed the
            # same way.
            n_last = 1 << cfg.max_depth
            Gl = np.zeros(n_last, np.float32)
            Hl = np.zeros(n_last, np.float32)
            with ph("leaf"):
                for c in range(n_chunks):
                    Xc, yc = chunk_fn(c)
                    ni = _traverse_partial(
                        Xc, feature, threshold_bin, is_leaf, cfg.max_depth,
                        **route_kw
                    )
                    g, h = chunk_grads(c, Xc, yc, cls)
                    act = ni >= 0
                    np.add.at(Gl, ni[act], g[act])
                    np.add.at(Hl, ni[act], h[act])
                _apply_final_leaves(Gl, Hl, cfg, is_leaf, leaf_value)

            ens.feature[t_out] = feature
            ens.threshold_bin[t_out] = threshold_bin
            ens.is_leaf[t_out] = is_leaf
            ens.leaf_value[t_out] = leaf_value
            ens.split_gain[t_out] = split_gain
            if ens.default_left is not None:
                ens.default_left[t_out] = default_left
            t_out += 1
            round_trees.append((feature, threshold_bin, is_leaf,
                                leaf_value, default_left))

        if preds is not None:
            # leaf slot per row = heap slot where traversal stopped: either
            # offset+ni (made it to the last level) or the frozen leaf —
            # rescore via the tree to keep it simple and exact.
            with ph("predict"):
                for c in range(n_chunks):
                    Xc, _ = chunk_fn(c)
                    for cls, (feature, threshold_bin, is_leaf, leaf_value,
                              default_left) in enumerate(round_trees):
                        slot = _leaf_slot(
                            Xc, feature, threshold_bin, is_leaf,
                            cfg.max_depth,
                            default_left=default_left,
                            missing_bin_value=missing_val,
                            cat_features=cfg.cat_features,
                        )
                        dv = cfg.learning_rate * leaf_value[slot]
                        if C > 1:
                            preds[c][:, cls] += dv
                        else:
                            preds[c] += dv

        if coll_bytes_round:
            tele_counters.record_collective(coll_bytes_round)
        tele_counters.record_grad_stream(
            C * tele_counters.grad_stream_bytes(
                int(y_cnt), cfg.max_depth, cfg.grad_dtype))
        stop = False
        if ev is not None:
            with ph("eval"):
                for c in range(ev.n):
                    Xv, _ = ev.fn(c)
                    for cls, (feature, threshold_bin, is_leaf, leaf_value,
                              default_left) in enumerate(round_trees):
                        slot = _leaf_slot(
                            Xv, feature, threshold_bin, is_leaf,
                            cfg.max_depth,
                            default_left=default_left,
                            missing_bin_value=missing_val,
                            cat_features=cfg.cat_features,
                        )
                        dv = cfg.learning_rate * leaf_value[slot]
                        if C > 1:
                            val_preds[c][:, cls] += dv
                        else:
                            val_preds[c] += dv
                stop = ev.record(rnd, np.concatenate(val_preds))
        dt_ms = (time.perf_counter() - t_round) * 1e3
        _emit_round(run_log, rnd, dt_ms, ev, status=status)
        if profiler_window is not None:       # xprof window: stop edge
            profiler_window.round_end(rnd)
        if stop:
            log.info(
                "streaming: early stop at round %d (best %s=%.6f at "
                "round %d)", rnd + 1, ev.metric, ev.best_score,
                ev.best_round + 1)
            emit_early_stop(run_log, rnd + 1, ev.metric,
                            ev.best_round + 1, ev.best_score)
            ens = ens.truncate((ev.best_round + 1) * C)
            checkpoint.maybe_save(checkpoint_dir, ens, cfg,
                                  ev.best_round + 1)
            return _finish(ens)

        log.info("streaming: round %d/%d done", rnd + 1, cfg.n_trees)
        checkpoint.maybe_save(checkpoint_dir, ens, cfg, rnd + 1,
                              checkpoint_every)
        if checkpoint_every >= 1 and (rnd + 1) % checkpoint_every == 0:
            if status is not None and checkpoint_dir is not None:
                status.checkpoint_saved(rnd + 1)
            emit_train_heartbeat(
                run_log, rnd=rnd, total_rounds=cfg.n_trees,
                checkpoint_round=(rnd + 1 if checkpoint_dir is not None
                                  else None),
                ms_per_round=dt_ms)

    checkpoint.maybe_save(checkpoint_dir, ens, cfg, cfg.n_trees)
    return _finish(ens)


def _merge_quant_stats(acc, st):
    """Host reduction of per-chunk quantization stats [C, 4] (max|g|,
    sum|g|, max|h|, sum|h|): maxes max exactly, sums accumulate in f64
    (the f32 cast happens once inside quant_scale_np; chunk-order ULPs
    are absorbed by the power-of-two scale snap — ops/grad)."""
    st = np.asarray(st, np.float64)
    if acc is None:
        return st
    out = acc.copy()
    out[:, 0] = np.maximum(acc[:, 0], st[:, 0])
    out[:, 2] = np.maximum(acc[:, 2], st[:, 2])
    out[:, 1] = acc[:, 1] + st[:, 1]
    out[:, 3] = acc[:, 3] + st[:, 3]
    return out


def _fit_streaming_device(
    chunk_fn: ChunkFn,
    n_chunks: int,
    cfg: TrainConfig,
    backend,
    ens: TreeEnsemble,
    bs: float,
    C: int,
    y_dev: list,
    chunk_starts: np.ndarray,
    start_round: int = 0,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 25,
    ev: "_StreamEval | None" = None,
    device_chunk_cache: "bool | int" = True,
    ph=None,
    run_log: "RunLog | None" = None,
    part_rec: "PartitionRecorder | None" = None,
    window=None,
    watchdog=None,
    status=None,
) -> TreeEnsemble:
    """Device streaming loop: see fit_streaming. Per tree it makes
    max_depth histogram passes + 1 leaf pass (+ 1 pred-update pass between
    rounds) over the chunks; each pass re-reads only Xb (uint8 —
    pred/labels stay device-resident) — from the device chunk cache when
    it fits the budget, else re-uploaded with the next chunk's host read
    + H2D upload enqueued BEFORE the current chunk's small output is
    fetched, so the transfer rides under the device compute (double
    buffering via JAX's async dispatch)."""
    if ph is None:
        ph = phase_ctx(None)
    if part_rec is None:
        part_rec = PartitionRecorder(None, backend)      # inert
    if device_chunk_cache is True:
        # Platform guard (see fit_streaming's docstring): on the CPU
        # platform the device buffers ARE host RAM — a default-on cache
        # would pin the dataset in host memory. Real accelerators cache.
        from ddt_tpu.utils import device

        on_host = device.platform() == "cpu"
        cache_budget = [0 if on_host else DEVICE_CHUNK_CACHE_BYTES]
    elif device_chunk_cache is False:
        cache_budget = [0]
    else:
        cache_budget = [int(device_chunk_cache)]
    chunks = _DeviceChunkCache(backend, chunk_fn, cache_budget)
    val_chunks = (_DeviceChunkCache(backend, ev.fn, cache_budget)
                  if ev is not None else None)
    # Device-resident per-chunk boosting state (labels were shipped during
    # pass 0): pred for the whole run — 4C bytes/row, row-sharded over the
    # mesh like the data, per-chip tiny next to the streamed Xb.
    pred_dev = [backend.init_pred(h, bs) for h in y_dev]
    # Validation predictions: device-resident per val chunk, updated per
    # round by the same stream_update_pred op as the training state; the
    # raw scores are fetched each round for host-side (f64) metric
    # evaluation.
    val_pred = None
    if ev is not None:
        # ev.labels avoids re-reading (and, through a binned_chunks
        # adapter, re-binning) each val chunk just for its labels; the
        # handles exist for init_pred's padded row shape + validity mask.
        val_y_dev = [backend.upload_labels(ev.labels(c))
                     for c in range(ev.n)]
        val_pred = [backend.init_pred(h, bs) for h in val_y_dev]
    if start_round > 0:
        # Resume: REPLAY the identical device update ops over the restored
        # trees (rounds ascending, classes ascending — the training
        # order). Host rescoring would differ by FMA-contraction ULPs
        # (XLA fuses pred + lr*dv into one rounding); replaying the same
        # compiled op is bit-exact vs an uninterrupted run by
        # construction. One upload pass over the chunks, start_round*C
        # cheap update dispatches each.
        def _replay(preds_list, src_of, n_of):
            for c in range(n_of):
                data = src_of.get(c)
                for r in range(start_round):
                    for cls in range(C):
                        slot = r * C + cls
                        tree_full = (
                            ens.feature[slot], ens.threshold_bin[slot],
                            ens.is_leaf[slot], ens.leaf_value[slot],
                            ens.default_left[slot],
                        )
                        preds_list[c] = backend.stream_update_pred(
                            data, preds_list[c], tree_full, cfg.max_depth,
                            cls)

        _replay(pred_dev, chunks, n_chunks)
        if ev is not None:
            _replay(val_pred, val_chunks, ev.n)

    n_feat = ens.n_features

    def passes(tree, depth, kind, class_idx, rnd, build_left=False,
               scales=None):
        """One full pass over the chunks; yields per-chunk device outputs
        with the next read/upload already in flight. Histogram outputs
        are sliced back to the real feature count (reduce-scatter mode
        pads F to the shard count with zero columns). `scales` is the
        round's (gscale, hscale) under quantized gradients — outputs
        are then RAW int32 partials the caller accumulates exactly and
        dequantizes once per level."""
        data = chunks.get(0)
        for c in range(n_chunks):
            tc0 = time.perf_counter()
            if kind == "hist":
                out = backend.stream_level_hist(
                    data, pred_dev[c], y_dev[c], tree, depth, class_idx,
                    rnd=rnd, row_start=int(chunk_starts[c]),
                    build_left=build_left, quant_scales=scales)
            else:
                out = backend.stream_leaf_gh(
                    data, pred_dev[c], y_dev[c], tree, depth, class_idx,
                    rnd=rnd, row_start=int(chunk_starts[c]),
                    quant_scales=scales)
            if c + 1 < n_chunks:        # prefetch: overlap H2D with compute
                data = chunks.get(c + 1)
            # Flight recorder: per-device completion of this chunk's pass
            # — AFTER the prefetch enqueue so the probe barrier rides
            # under the next chunk's H2D; the asarray below was already
            # a sync, so active-recorder cost is the probe bookkeeping.
            part_rec.observe(kind, out, tc0)
            part = np.asarray(out)      # fetch (device likely done by now)
            if kind == "hist" and part.shape[1] != n_feat:
                part = part[:, :n_feat]     # drop scatter pad columns
            yield part

    t_out = start_round * C
    # The previous round's finished trees, NOT yet applied to the resident
    # preds: the application is folded into the NEXT round's first data
    # pass (stream_round_start) — one pass where round 2 used to spend two
    # (round-2 verdict item 6). The final round's trees are never applied
    # (pred is dead after the last gradients — same as the old loop, which
    # skipped its trailing update pass).
    prev_trees = None
    quant = cfg.grad_dtype != "f32"
    subtract = resolve_hist_subtraction(cfg.hist_subtraction,
                                        integer_hists=quant)
    coll_bytes_round = 0
    if getattr(backend, "distributed", False):
        coll_bytes_round = C * n_chunks * backend.collective_bytes_per_tree(
            ens.n_features, streamed=True)
    for rnd in range(start_round, cfg.n_trees):
        if window is not None:                # xprof window: start edge
            window.round_start(rnd)
        t_round = time.perf_counter()
        # Quantized gradients (cfg.grad_dtype): the round's per-class
        # scales must exist BEFORE any histogram build, so the round
        # opens with a stats pass — FUSED into the previous round's
        # tree application (stream_round_start returns [C, 4] stats
        # instead of a depth-0 histogram; the depth-0 build then runs
        # as a normal quantized pass below) or, when there are no trees
        # to apply yet, a chunk-read-free gradstats pass over resident
        # pred/labels. One shared grid per (round, class) is what makes
        # every cross-chunk/cross-shard integer merge of the round
        # bit-exact.
        round_scales = None
        if quant:
            from ddt_tpu.ops.grad import GRAD_ROW_LIMIT, quant_scale_np

            if int(chunk_starts[-1]) >= GRAD_ROW_LIMIT:
                # The int32 overflow proof's row ceiling (ops/grad.py:
                # sum|q| <= 2^30 + n_rows must stay under INT32_MAX).
                raise ValueError(
                    f"quantized streaming over {int(chunk_starts[-1])} "
                    f"rows exceeds the overflow proof's row ceiling "
                    f"({GRAD_ROW_LIMIT}); use grad_dtype='f32'")
            acc = None
            if prev_trees is not None:
                data = chunks.get(0)
                for c in range(n_chunks):
                    tc0 = time.perf_counter()
                    pred_dev[c], st = backend.stream_round_start(
                        data, pred_dev[c], y_dev[c], prev_trees,
                        rnd=rnd, row_start=int(chunk_starts[c]))
                    if c + 1 < n_chunks:
                        data = chunks.get(c + 1)
                    part_rec.observe("roundstart", st, tc0)
                    acc = _merge_quant_stats(acc, np.asarray(st))
            else:
                for c in range(n_chunks):
                    acc = _merge_quant_stats(acc, np.asarray(
                        backend.stream_grad_stats(
                            pred_dev[c], y_dev[c], rnd=rnd,
                            row_start=int(chunk_starts[c]))))
            round_scales = [
                (quant_scale_np(acc[c_, 0], acc[c_, 1], cfg.grad_dtype),
                 quant_scale_np(acc[c_, 2], acc[c_, 3], cfg.grad_dtype))
                for c_ in range(C)]
            log.debug("streaming: round %d grad-quant scales %s", rnd,
                      round_scales)
            tele_counters.record_grad_quant_round()
        # Gradients for EVERY class tree of a round come from the
        # round-start preds (the Driver computes grad_hess once per round,
        # then grows C trees from its columns) — so pred updates are
        # deferred to the fused round-start pass.
        round_trees = []
        for cls in range(C):
            fmask = None
            if cfg.colsample_bytree < 1.0:
                from ddt_tpu.ops.sampling import colsample_mask

                fmask = colsample_mask(cfg.seed, rnd, cls,
                                       ens.n_features,
                                       cfg.colsample_bytree)
            feature = np.full(cfg.n_nodes_total, -1, np.int32)
            threshold_bin = np.zeros(cfg.n_nodes_total, np.int32)
            is_leaf = np.zeros(cfg.n_nodes_total, bool)
            leaf_value = np.zeros(cfg.n_nodes_total, np.float32)
            split_gain = np.zeros(cfg.n_nodes_total, np.float32)
            default_left = np.zeros(cfg.n_nodes_total, bool)
            tree = (feature, threshold_bin, is_leaf, default_left)

            sc = round_scales[cls] if quant else None
            prev_hist = None
            for depth in range(cfg.max_depth):
                sub = subtract and depth >= 1 and prev_hist is not None
                hist = None
                with ph("hist"):
                    if (depth == 0 and cls == 0 and prev_trees is not None
                            and not quant):
                        # Fused round-start: apply the previous round's
                        # trees to the resident preds AND build this
                        # tree's depth-0 histogram (the NEW round's
                        # bagging mask) in one dispatch per chunk.
                        # (Quantized rounds consumed this pass for
                        # scale stats above — depth 0 streams normally.)
                        data = chunks.get(0)
                        for c in range(n_chunks):
                            tc0 = time.perf_counter()
                            pred_dev[c], out = backend.stream_round_start(
                                data, pred_dev[c], y_dev[c], prev_trees,
                                rnd=rnd, row_start=int(chunk_starts[c]))
                            if c + 1 < n_chunks:
                                data = chunks.get(c + 1)
                            part_rec.observe("roundstart", out, tc0)
                            part = np.asarray(out)
                            if part.shape[1] != ens.n_features:
                                part = part[:, :ens.n_features]
                            hist = part if hist is None else hist + part
                    else:
                        # Sibling subtraction (levels >= 1): stream only
                        # LEFT-child histograms — half the per-chunk
                        # device work and half the collective payload.
                        for part in passes(tree, depth, "hist", cls, rnd,
                                           build_left=sub, scales=sc):
                            hist = part if hist is None else hist + part
                if sub:
                    hist = _assemble_subtracted_level(prev_hist, hist,
                                                      is_leaf, depth)
                # Quantized levels accumulate int32 — cross-chunk adds
                # and the subtraction above are EXACT; dequantize once
                # per level, feeding the shared split-decision home.
                histf = hist
                if quant:
                    histf = hist.astype(np.float32) * np.array(
                        [sc[0], sc[1]], np.float32)
                with ph("gain"):
                    _apply_level_splits(histf, cfg, depth, feature,
                                        threshold_bin, is_leaf, leaf_value,
                                        split_gain, default_left,
                                        feature_mask=fmask)
                prev_hist = hist if subtract else None

            # Final level: streamed (G, H) aggregates (int32 under
            # quantized gradients — dequantized after the last chunk).
            GH = None
            with ph("leaf"):
                for part in passes(tree, cfg.max_depth, "leaf", cls, rnd,
                                   scales=sc):
                    GH = part if GH is None else GH + part
                if quant:
                    _apply_final_leaves(
                        GH[:, 0].astype(np.float32) * np.float32(sc[0]),
                        GH[:, 1].astype(np.float32) * np.float32(sc[1]),
                        cfg, is_leaf, leaf_value)
                else:
                    _apply_final_leaves(GH[:, 0], GH[:, 1], cfg, is_leaf,
                                        leaf_value)

            round_trees.append(
                (feature, threshold_bin, is_leaf, leaf_value,
                 default_left))
            ens.feature[t_out] = feature
            ens.threshold_bin[t_out] = threshold_bin
            ens.is_leaf[t_out] = is_leaf
            ens.leaf_value[t_out] = leaf_value
            ens.split_gain[t_out] = split_gain
            if ens.default_left is not None:
                ens.default_left[t_out] = default_left
            t_out += 1

        prev_trees = round_trees
        if coll_bytes_round:
            tele_counters.record_collective(coll_bytes_round)
        tele_counters.record_grad_stream(
            C * tele_counters.grad_stream_bytes(
                int(chunk_starts[-1]), cfg.max_depth, cfg.grad_dtype))

        stop = False
        if ev is not None:
            # Two phases, matching the host loop's naming: "predict"
            # applies the round's trees to the resident val preds and
            # drains the raw scores (device work — the stream_update op
            # carries its XLA cost analysis under this name), "eval" is
            # the host-side (f64) metric reduction.
            with ph("predict"):
                scores = []
                data = val_chunks.get(0)
                for c in range(ev.n):
                    for cls, tree_full in enumerate(round_trees):
                        val_pred[c] = backend.stream_update_pred(
                            data, val_pred[c], tree_full, cfg.max_depth,
                            cls)
                    if c + 1 < ev.n:
                        data = val_chunks.get(c + 1)
                    scores.append(np.asarray(val_pred[c])[: ev.lens[c]])
            with ph("eval"):
                stop = ev.record(rnd, np.concatenate(scores))
        dt_ms = (time.perf_counter() - t_round) * 1e3
        _emit_round(run_log, rnd, dt_ms, ev, status=status)
        if window is not None:                # xprof window: stop edge
            window.round_end(rnd)
        if watchdog is not None:
            from ddt_tpu.robustness.watchdog import feed_watchdog

            feed_watchdog(watchdog, run_log, rnd,
                          part_rec.flush_round(rnd), log,
                          prefix="streaming: ")
        else:
            part_rec.flush_round(rnd)
        if stop:
            log.info(
                "streaming: early stop at round %d (best %s=%.6f at "
                "round %d)", rnd + 1, ev.metric, ev.best_score,
                ev.best_round + 1)
            emit_early_stop(run_log, rnd + 1, ev.metric,
                            ev.best_round + 1, ev.best_score)
            ens = ens.truncate((ev.best_round + 1) * C)
            checkpoint.maybe_save(checkpoint_dir, ens, cfg,
                                  ev.best_round + 1)
            return ens

        log.info("streaming: round %d/%d done", rnd + 1, cfg.n_trees)
        checkpoint.maybe_save(checkpoint_dir, ens, cfg, rnd + 1,
                              checkpoint_every)
        if checkpoint_every >= 1 and (rnd + 1) % checkpoint_every == 0:
            if status is not None and checkpoint_dir is not None:
                status.checkpoint_saved(rnd + 1)
            emit_train_heartbeat(
                run_log, rnd=rnd, total_rounds=cfg.n_trees,
                checkpoint_round=(rnd + 1 if checkpoint_dir is not None
                                  else None),
                ms_per_round=dt_ms)
        if (watchdog is not None and cfg.straggler_repartition
                and watchdog.pending_repartition
                and checkpoint_every >= 1
                and (rnd + 1) % checkpoint_every == 0
                and getattr(backend, "rotate_row_partitions", None)
                is not None):
            # The watchdog's streamed ACTION (the in-memory path's
            # rotate_row_partitions contract extended to the streamed
            # loop, ROADMAP item 2): rotate the row-shard -> device
            # assignment at the checkpoint boundary, move every
            # RESIDENT handle (labels, predictions) onto the rotated
            # mesh, drop the device chunk caches (their placements are
            # stale; the next pass re-uploads onto the new order), and
            # rotate a host-sharded source's chunk-shard -> host
            # assignment so reads keep following the devices. Shard
            # CONTENTS and the global row order are untouched — the
            # model is bit-identical by construction (tested). Scope
            # honesty: rotate_row_partitions is single-controller only
            # (multi-process meshes return False -> detection only,
            # like the in-memory path), and on one process the
            # assignment rotation is an identity (every slot is
            # local) — the rot() call keeps the mesh/ingest pairing
            # explicit for ROADMAP item 5's multi-process rework,
            # where host-level rotation makes both halves real.
            if backend.rotate_row_partitions():
                extra = 1 if C > 1 else 0
                y_dev = [type(h)(backend.reshard_rows(h.y),
                                 backend.reshard_rows(h.valid))
                         for h in y_dev]
                pred_dev = [backend.reshard_rows(p, extra_dims=extra)
                            for p in pred_dev]
                if ev is not None:
                    val_y_dev = [type(h)(backend.reshard_rows(h.y),
                                         backend.reshard_rows(h.valid))
                                 for h in val_y_dev]
                    val_pred = [backend.reshard_rows(p, extra_dims=extra)
                                for p in val_pred]
                chunks.clear()
                if val_chunks is not None:
                    val_chunks.clear()
                rot = getattr(chunk_fn, "rotate_assignment", None)
                if rot is not None:
                    rot()
                log.warning(
                    "streaming: repartitioned at round %d: rotated row "
                    "shards off the straggling device", rnd + 1)
                if run_log is not None:
                    run_log.emit("fault", kind="repartition",
                                 round=rnd + 1, rotation=1)
            watchdog.repartition_done()

    checkpoint.maybe_save(checkpoint_dir, ens, cfg, cfg.n_trees)
    return ens


def predict_streaming(
    chunk_fn: ChunkFn,
    n_chunks: int,
    ens: TreeEnsemble,
    backend=None,
    raw: bool = True,
    sink=None,
    max_in_flight: int = 3,
) -> "np.ndarray | int":
    """Out-of-core batch scoring: stream binned chunks through a
    DOUBLE-BUFFERED host→device pipeline; returns the concatenated scores
    ([R] or [R, C] raw margins; `raw=False` applies the loss's
    probability transform) — or, with a `sink`, streams them out too.

    The pipeline shape (device backends): chunk c's scoring program is
    dispatched asynchronously, chunk c+1's host read + H2D upload is
    enqueued WHILE c computes, and c's device→host score fetch is started
    (`copy_to_host_async`) as soon as its dispatch returns — so the H2D
    link, the traversal kernels, and the D2H drain all run concurrently
    (the round-5 overlapped-fetch result, extended to out-of-core input).
    The ensemble's pushed-down tables upload ONCE via the backend's
    compiled-ensemble cache and stay resident across chunks AND calls.
    Chunks may differ in size (each distinct size compiles one program —
    keep the number of distinct sizes small). Host backends (or
    backend=None) fall back to per-chunk scoring, same contract.

    `sink(chunk_idx, scores)` — when given, per-chunk scores stream out
    through it (at most `max_in_flight` chunks of scores are ever
    host-resident) and the TOTAL ROW COUNT is returned instead of an
    array: a 10B-row score vector has no business being concatenated in
    host memory (the CLI's --stream-dir predict writes per-shard .npy
    files through this).

    `chunk_fn` is the fit_streaming chunk source convention:
    (chunk_idx) -> (Xb_chunk uint8 [r, F], labels) — labels are ignored
    here, so score-time sources may return anything (e.g. None) there.
    Composes with distribution: each chunk row-shards over the backend's
    mesh like any other upload (multi-chip scoring from the same flag).
    """
    if n_chunks < 1:
        raise ValueError("predict_streaming needs n_chunks >= 1")

    def transform(out_np):
        if raw:
            return out_np
        from ddt_tpu.ops.predict import predict_proba
        import jax.numpy as jnp

        return np.asarray(predict_proba(jnp.asarray(out_np), ens.loss))

    rows = 0
    collected: list = []

    def emit(c, scores):
        nonlocal rows
        scores = transform(scores)
        rows += len(scores)
        if sink is None:
            collected.append(scores)
        else:
            sink(c, scores)

    if getattr(backend, "_predict_fn", None) is None:
        # Host path: no pipeline to overlap — score chunk by chunk
        # (through the backend's scorer when one was given: CPUDevice
        # prefers the native C++ traversal, bitwise-equal to NumPy).
        for c in range(n_chunks):
            Xc = np.asarray(chunk_fn(c)[0])
            emit(c, backend.predict_raw(ens, Xc) if backend is not None
                 else ens.predict_raw(Xc, binned=True))
    else:
        fn, ens_dev = backend._predict_fn(ens)   # compiled-ensemble cache
        # Device working-set bound: a chunk past the backend's per-call
        # row limit may NOT go down as one dispatch (the 10M x 1000
        # config OOM-kills the chip that way — backends/tpu.py
        # predict_chunk_rows, the rows a dispatch takes at the chunk's
        # width). Oversized chunks route through
        # backend.predict_raw, whose internal chunking + overlapped
        # fetch already handle the big-batch case; the double-buffered
        # pipeline below covers the (normal) bounded-chunk regime.
        chunk_rows = getattr(backend, "predict_chunk_rows", None)
        shards = max(1, getattr(backend, "row_shards", 1))

        def fits(x):
            return chunk_rows is None \
                or x.shape[0] <= chunk_rows(x.shape[1]) * shards

        Xc = np.asarray(chunk_fn(0)[0])
        data = backend._put_rows(Xc, extra_dims=1) if fits(Xc) else None
        pending: list = []                       # (idx, device scores, n)

        def drain(keep: int) -> None:
            # Copies are already in flight; asarray only materialises.
            while len(pending) > keep:
                ci, o, n = pending.pop(0)
                emit(ci, np.asarray(o)[:n])  # ddtlint: disable=host-sync

        for c in range(n_chunks):
            cur, n_rows = Xc, Xc.shape[0]
            out_c = None if data is None else fn(*ens_dev, data)
            if c + 1 < n_chunks:                 # overlap next H2D
                Xc = np.asarray(chunk_fn(c + 1)[0])
                data = (backend._put_rows(Xc, extra_dims=1)
                        if fits(Xc) else None)
            if out_c is None:
                # Oversized chunk: drain the pipeline in order, then let
                # the backend's own chunked/overlapped path score it.
                drain(0)
                emit(c, backend.predict_raw(ens, cur))
                continue
            try:
                out_c.copy_to_host_async()       # start D2H drain now
            except AttributeError:               # non-jax backend arrays
                pass
            pending.append((c, out_c, n_rows))
            drain(max_in_flight)                 # bounded host residency
        drain(0)
    if sink is not None:
        return rows
    return np.concatenate(collected)


def _leaf_slot(Xb, feature, threshold_bin, is_leaf, max_depth,
               default_left=None, missing_bin_value=-1,
               cat_features=()) -> np.ndarray:
    """Heap slot where each row's traversal of one tree terminates."""
    R = Xb.shape[0]
    node = np.zeros(R, np.int64)
    for _ in range(max_depth):
        live = ~is_leaf[node]
        f = feature[node[live]]
        fv = Xb[live, f].astype(np.int64)
        go_right = _go_right(fv, node[live], feature, threshold_bin,
                             default_left, missing_bin_value, cat_features)
        node[live] = 2 * node[live] + 1 + go_right
    return node


def _rescore(ens: TreeEnsemble, n_trees_done: int, Xb, bs) -> np.ndarray:
    """Stateless pred of the first n_trees_done trees (cache_preds=False).
    [R] for binary/mse, [R, C] for softmax."""
    C = ens.n_classes if ens.loss == "softmax" else 1
    if n_trees_done == 0:
        if C > 1:
            return np.zeros((Xb.shape[0], C), np.float32)
        return np.full(Xb.shape[0], bs, np.float32)
    return ens.truncate(n_trees_done).predict_raw(
        Xb, binned=True).astype(np.float32)
