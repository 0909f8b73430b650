"""ServeEngine: device-resident models + micro-batch scoring + SLO stats.

The serving tier's scoring half (docs/SERVING.md). One engine owns:

- a `ServableModel` per live model version — the per-model prologue
  (mapper validation, CompiledEnsemble build, optional int8 LUT
  quantization, device upload, bucket-shape warm-up traces) paid ONCE
  at publish time, so the request path is: bin rows -> pad to bucket ->
  one pre-traced dispatch -> scatter (the api.predict per-call prologue
  hoist, ISSUE 8 satellite);
- a `MicroBatcher` whose dispatcher scores each admitted batch against
  the model reference read ONCE per batch — hot-swap is an atomic
  reference publish, so every request observes exactly the old or the
  new model, never a mix (tests/test_serve.py pins this mid-flight);
- `ServeStats`, the first-class latency telemetry: per-request p50/p99/
  p999, coalesce width, queue depth — emitted as the run log's
  `serve_latency` event (schema v4) and surfaced by `cli report`'s
  serving section, the same observatory that attributes training phases.

HOT-LOOP MODULE (the ddtlint serve-blocking-io rule): no `time.sleep`,
no synchronous file reads — model files are loaded by the CALLER
(cli/http layer) and handed in as ready ModelBundles.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import logging
import threading
import time

import numpy as np

from ddt_tpu.backends import get_backend
from ddt_tpu.config import TrainConfig
from ddt_tpu.serve.batcher import (MicroBatcher, PendingRequest,
                                   trace_breakdown)
from ddt_tpu.telemetry import counters as tele_counters
# Host-side probability transform (ONE home shared with api.predict —
# no device round-trip for an [R]-sized vector on the request path).
from ddt_tpu.utils.metrics import predict_proba_np as proba_np

log = logging.getLogger("ddt_tpu.serve")


def normalize_quantize(q) -> "str | None":
    """Normalize every spelling of the serving quantization tier to
    None | "int8" | "int4" (the ladder docs/SERVING.md tabulates).
    Accepts the legacy bool opt-in (True = the int8 TreeLUT tier), the
    cfg.predict_impl spellings ("lut"/"lut4"), and the leaf-dtype
    spellings the registry manifests carry."""
    if q is None or q is False:
        return None
    if q is True:
        return "int8"
    s = str(q).lower()
    if s in ("", "none", "false", "f32"):
        return None
    if s in ("int8", "lut", "true", "float16"):
        return "int8"
    if s in ("int4", "lut4"):
        return "int4"
    raise ValueError(
        f"unknown quantization tier {q!r} (expected int8 or int4)")


#: serving tier -> the cfg.predict_impl that dispatches it.
TIER_IMPL = {"int8": "lut", "int4": "lut4"}
#: serving tier -> the QuantizedTables leaf dtype it quantizes to.
TIER_LEAF_DTYPE = {"int8": "float16", "int4": "int4"}


def default_buckets(max_batch: int) -> tuple[int, ...]:
    """Power-of-two pad-to-bucket ladder up to max_batch — the FIXED set
    of batch shapes every dispatch rides (each bucket traces once at
    warm-up; zero retracing under load)."""
    out = [1]
    while out[-1] < max_batch:
        out.append(min(out[-1] * 2, max_batch))
    return tuple(out)


def bucket_for(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class ServableModel:
    """One model version, fully prepared to score micro-batches.

    Build cost (validation + CompiledEnsemble + optional quantized
    tables + device upload + one traced dispatch per bucket) is paid
    here, off the request path; `score()` is transform + pad + dispatch.
    Instances are immutable once built — the engine swaps whole
    references.

    Subclass seam: `_invoke(Xb)` is the one device-dispatch point the
    pad/chunk/probability logic funnels through — the registry's
    AOT-restored model (ddt_tpu/registry/loader.py) overrides ONLY it,
    scoring through deserialized StableHLO instead of the backend's
    traced path, and inherits every shape contract here verbatim."""

    #: short registry digest when this model came from an artifact
    #: (stamped into serve_latency / hot_swap events); None for models
    #: published straight from a file or bundle.
    artifact_digest: "str | None" = None
    #: True when scoring rides deserialized AOT blobs (zero retrace).
    aot: bool = False
    #: RestoredModel pins the tier it restored; backend-scoring models
    #: leave this None and ask the backend what actually resolved.
    _impl_override: "str | None" = None

    def __init__(self, bundle, backend, *, quantize=False,
                 buckets: tuple[int, ...] = (1,), raw: bool = False,
                 tables=None):
        from ddt_tpu.api import validate_mapper_model

        self.ens = bundle.ensemble
        self.mapper = bundle.mapper
        self.backend = backend
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.raw = bool(raw)
        self.quantize_tier = normalize_quantize(quantize)
        self.quantized = self.quantize_tier is not None
        if self.mapper is not None:
            # The full mapper-vs-model contract (missing-bin policy,
            # identity-binned categorical columns), checked ONCE per
            # model version — api.predict pays this per call.
            validate_mapper_model(self.mapper, self.ens)
        self.compiled = self.ens.compile(tree_chunk=64)
        self.token = self.compiled.token
        if self.quantize_tier:
            # Error contract rides on the tables (ops/predict_lut.py);
            # recorded here so /healthz and the smoke test can surface
            # the served bound. Pre-built `tables` (the registry's
            # carried lut_tables.npz, token-pinned by the loader) take
            # precedence over re-quantizing: the exported quantized
            # representation is what serves, even across version skew.
            if tables is not None:
                # Carried tables define the representation; an int4
                # request must get int4 tables (an int8 artifact cannot
                # silently serve as the int4 tier, or the reported
                # error bound would describe the wrong grid).
                if ((tables.leaf_dtype == "int4")
                        != (self.quantize_tier == "int4")):
                    raise ValueError(
                        f"carried tables are leaf_dtype="
                        f"{tables.leaf_dtype!r} but the serving tier is "
                        f"{self.quantize_tier!r}; re-export with "
                        f"--quantize={self.quantize_tier}")
                # Seed the compiled model's memo so the backend's LUT
                # dispatch consumes THESE tables, not a re-derivation —
                # keyed by THEIR leaf_dtype, not the default's.
                self.compiled.seed_quantized(tables)
                self.tables = self.compiled.quantize(
                    leaf_dtype=tables.leaf_dtype)
            else:
                self.tables = self.compiled.quantize(
                    leaf_dtype=TIER_LEAF_DTYPE[self.quantize_tier])
            self.max_abs_err = self.tables.max_abs_err
        else:
            self.tables = None
            self.max_abs_err = 0.0

    @property
    def predict_impl(self) -> str:
        """The tier ACTUALLY serving this model ("lut4" | "lut" |
        "f32") — asks the backend what its fallback ladder resolved, so
        a silent VMEM-guard trip is visible in /healthz and
        serve_latency instead of only in debug logs (resolution happens
        at warmup, before the model is ever published)."""
        if self._impl_override is not None:
            return self._impl_override
        be = self.backend
        if be is not None and hasattr(be, "resolved_predict_impl"):
            return be.resolved_predict_impl(self.token)
        return "f32"

    @property
    def n_features(self) -> int:
        return int(self.ens.n_features)

    def transform(self, rows: np.ndarray) -> np.ndarray:
        """Raw float rows -> uint8 bins with the TRAINING-TIME mapper
        (never refit — the round-1 verdict contract)."""
        if self.mapper is None:
            raise ValueError(
                "model artifact carries no bin mapper; submit pre-binned "
                "uint8 rows")
        return self.mapper.transform(rows)

    def score_binned(self, Xb: np.ndarray) -> np.ndarray:
        """Scores for a BINNED block, padded to the nearest bucket so
        the dispatch rides a pre-traced shape."""
        n = Xb.shape[0]
        cap = self.buckets[-1]
        if n > cap:
            # An over-sized solo request must ALSO ride pre-traced
            # shapes: score it in largest-bucket pieces rather than
            # handing the backend a never-warmed shape (each distinct
            # over-size n would pay a fresh compile on the shared
            # dispatcher thread, stalling every queued request).
            # Probabilities are per-row, so piecewise == whole-batch.
            return np.concatenate([self.score_binned(Xb[i:i + cap])
                                   for i in range(0, n, cap)])
        b = bucket_for(n, self.buckets)
        if n < b:
            Xb = np.concatenate(
                [Xb, np.zeros((b - n, Xb.shape[1]), np.uint8)])
        out = self._invoke(Xb)[:n]
        return out if self.raw else proba_np(out, self.ens.loss)

    def _invoke(self, Xb: np.ndarray) -> np.ndarray:
        """One raw-score dispatch at an exact bucket shape (see the
        class doc's subclass seam)."""
        return self.backend.predict_raw(self.ens, Xb,
                                        compiled=self.compiled)

    def warmup(self) -> None:
        """Trace every bucket shape BEFORE the model is published — a
        swap never makes a live request pay a compile."""
        dummy = np.zeros((1, self.n_features), np.uint8)
        for b in self.buckets:
            self.score_binned(np.repeat(dummy, b, axis=0))


@dataclasses.dataclass
class _Window:
    """One latency-accounting window (reset on each serve_latency emit).

    BOUNDED: a persistent server nobody polls (`cli serve` with no
    /stats?emit=1 caller and no run log) must not accumulate per-request
    floats forever — the sample deques keep the most recent CAP
    requests/batches, so quantiles degrade to trailing-window estimates
    under unpolled steady load instead of the process OOMing. `requests`
    and `batches` stay exact counts regardless."""

    CAP = 65_536

    latencies_ms: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=_Window.CAP))
    widths: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=_Window.CAP))
    requests: int = 0
    queue_depth_max: int = 0
    batches: int = 0
    express: int = 0            # requests the express lane dispatched
    t_start: float = dataclasses.field(default_factory=time.perf_counter)


#: FIXED log-spaced latency histogram bucket upper bounds in ms (the
#: /metrics exposition's `le=` ladder, ISSUE 17): 0.1 ms doubling to
#: ~3.3 s, plus an implicit +Inf overflow bucket. Fixed — never derived
#: from observed data — so two scrapes (or two processes) are always
#: bucket-compatible, the property Prometheus histogram aggregation
#: assumes.
HIST_BUCKETS_MS = tuple(round(0.1 * 2.0 ** i, 4) for i in range(16))


def _quantile(sorted_vals: list, q: float) -> float:
    """Nearest-rank quantile on a pre-sorted list (p999 on a 100-request
    smoke run must be the honest max, not an interpolation artifact)."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(np.ceil(q * len(sorted_vals))) - 1)
    return float(sorted_vals[max(0, i)])


class ServeStats:
    """Thread-safe latency/coalesce accounting: a bounded all-time ring
    plus the current emit window, a NON-RESETTING log-spaced latency
    histogram (the /metrics exposition — scrapes never reset it, unlike
    the emit window), and a bounded ring of the last TRACE_RING
    completed request traces (GET /debug/requests)."""

    RING = 65_536
    TRACE_RING = 256

    def __init__(self):
        self._lock = threading.Lock()
        self._all = collections.deque(maxlen=self.RING)
        self._win = _Window()
        self.requests = 0
        self.coalesce_max = 0
        self.express = 0
        # Cumulative per-bucket counts on the FIXED HIST_BUCKETS_MS
        # ladder (+1 overflow slot) + the running latency sum — the
        # strictly monotonic state /metrics renders; `requests` above is
        # the matching _count series.
        self._hist = [0] * (len(HIST_BUCKETS_MS) + 1)
        self._hist_sum_ms = 0.0
        self._traces: collections.deque = collections.deque(
            maxlen=self.TRACE_RING)

    def record_batch(self, n_requests: int, queue_depth: int,
                     latencies_ms: list, express: bool = False,
                     traces: "list | None" = None) -> None:
        with self._lock:
            self.requests += n_requests
            self.coalesce_max = max(self.coalesce_max, n_requests)
            self._all.extend(latencies_ms)
            for v in latencies_ms:
                self._hist[bisect.bisect_left(HIST_BUCKETS_MS, v)] += 1
                self._hist_sum_ms += v
            if traces:
                self._traces.extend(traces)
            w = self._win
            w.batches += 1
            w.requests += n_requests
            w.widths.append(n_requests)
            w.queue_depth_max = max(w.queue_depth_max, queue_depth)
            w.latencies_ms.extend(latencies_ms)
            if express:
                self.express += n_requests
                w.express += n_requests

    def _summary_locked(self, w: _Window) -> dict:
        lat = sorted(w.latencies_ms)
        return {
            "requests": w.requests,
            "batches": w.batches,
            "window_s": round(time.perf_counter() - w.t_start, 6),
            "p50_ms": round(_quantile(lat, 0.50), 4),
            "p99_ms": round(_quantile(lat, 0.99), 4),
            "p999_ms": round(_quantile(lat, 0.999), 4),
            "max_ms": round(lat[-1], 4) if lat else 0.0,
            "coalesce_mean": (round(float(np.mean(w.widths)), 3)
                              if w.widths else 0.0),
            "coalesce_max": max(w.widths) if w.widths else 0,
            "queue_depth_max": w.queue_depth_max,
            "express": w.express,
        }

    def window_summary(self, reset: bool = False) -> dict:
        """Current window's latency summary (the serve_latency payload);
        `reset=True` starts a fresh window (emit semantics)."""
        with self._lock:
            out = self._summary_locked(self._win)
            if reset:
                self._win = _Window()
        return out

    def snapshot(self) -> dict:
        """All-time view for /healthz & tests."""
        with self._lock:
            lat = sorted(self._all)
            return {
                "requests": self.requests,
                "coalesce_max": self.coalesce_max,
                "express": self.express,
                "p50_ms": round(_quantile(lat, 0.50), 4),
                "p99_ms": round(_quantile(lat, 0.99), 4),
                "p999_ms": round(_quantile(lat, 0.999), 4),
            }

    def metrics_state(self) -> dict:
        """The non-resetting histogram state the /metrics exposition
        renders: fixed bucket bounds, cumulative-compatible per-bucket
        counts (last slot = +Inf overflow), running sum, and the
        lifetime request count. STRICTLY read-only — a scrape must
        never perturb the emit window (the /metrics vs /stats?emit=1
        contract tests/test_serve.py pins)."""
        with self._lock:
            return {"buckets_ms": list(HIST_BUCKETS_MS),
                    "counts": list(self._hist),
                    "sum_ms": round(self._hist_sum_ms, 4),
                    "count": self.requests,
                    "express": self.express}

    def traces_snapshot(self) -> list:
        """Completed-trace ring, oldest first (GET /debug/requests and
        the serve_trace flush read this; read-only like metrics_state)."""
        with self._lock:
            return list(self._traces)


def coerce_rows(rows) -> np.ndarray:
    """Submit-side row normalization shared by ServeEngine and the
    fleet engine: [F] promotes to [1, F], anything but 2-D is refused,
    and non-uint8 input becomes contiguous f32 (the transform path's
    dtype; uint8 rows are pre-binned and pass through untouched)."""
    rows = np.asarray(rows)
    if rows.ndim == 1:
        rows = rows[None, :]
    if rows.ndim != 2:
        raise ValueError(f"rows must be [n, F], got {rows.shape}")
    if rows.dtype != np.uint8:
        rows = np.ascontiguousarray(rows, np.float32)
    return rows


def dispatch_batch(model, batch, queue_depth: int, stats,
                   observer=None) -> list:
    """Score ONE admitted micro-batch against `model` and deliver every
    result/error — the per-batch body shared by ServeEngine._dispatch
    and the fleet engine's per-model dispatch (ddt_tpu/serve/fleet.py).
    The caller read the model reference ONCE (hot-swap/eviction
    atomicity: every request in the batch is scored by exactly this
    version); this function never touches engine state beyond `stats`.
    Returns the per-request latencies (ms) of the delivered requests —
    the fleet's SLO burn-rate tracker consumes them.

    Raw float requests bin HERE, under the same model that scores them —
    binning at submit time could pair model A's bins with model B's
    trees across a swap. Transform failures are PER-REQUEST: a malformed
    submission fails its own waiter only, never the valid requests that
    happened to share its admission window.

    `observer(Xb, scores, lats)` — the drift/shadow seam (ISSUE 19,
    ddt_tpu/serve/drift.py) — runs AFTER every waiter has its result:
    structurally off the response path, so champion responses are
    bit-identical with or without it, and a failing observer is
    contained (the dispatcher thread must survive anything a tracker
    raises). It sees the batch exactly as scored: the concatenated
    binned uint8 matrix and this model's scores.

    Trace marks (ISSUE 17) ride the requests' own `marks` dicts on the
    batcher's injected clock (marks carry the clock — the whole
    breakdown stays on one timebase): `gate` at entry (the dispatch
    gate is held here), `device`/`done` around the device call, `wake`
    just before result publication. Completed breakdowns land in the
    stats trace ring BEFORE any waiter wakes — a client that queries
    /debug/requests the moment result() returns finds its own trace."""
    clk = None
    for r in batch:
        if r.marks is not None:
            clk = r.marks["_clock"]
            break
    if clk is not None:
        t = clk()
        for r in batch:
            if r.marks is not None:
                r.marks["gate"] = t
    good, blocks = [], []
    for r in batch:
        # Feature-count check against the model ACTUALLY scoring this
        # batch (submit-time validation saw the pre-swap model; a swap
        # to a different-width model must fail only the stale-width
        # requests, never the valid ones sharing their window).
        if r.rows.shape[1] != model.n_features:
            r.set_error(ValueError(
                f"rows have {r.rows.shape[1]} features; the "
                f"serving model expects {model.n_features}"))
            continue
        if r.rows.dtype == np.uint8:
            good.append(r)
            blocks.append(r.rows)
            continue
        try:
            blocks.append(model.transform(r.rows))
            good.append(r)
        # Delivered to this request's own waiter; co-batched requests
        # proceed.
        except Exception as e:  # ddtlint: disable=broad-except
            r.set_error(e)
    if not good:
        return []
    Xb = blocks[0] if len(blocks) == 1 else np.concatenate(blocks)
    if clk is not None:
        t = clk()
        for r in good:
            if r.marks is not None:
                r.marks["device"] = t
    scores = model.score_binned(Xb)
    done = time.perf_counter()
    if clk is not None:
        t = clk()
        for r in good:
            if r.marks is not None:
                r.marks["done"] = t
    lats = [(done - r.t_submit) * 1e3 for r in good]
    express = bool(good and good[0].express)
    traces = None
    if clk is not None:
        t_wake = clk()
        traces = []
        for r in good:
            if r.marks is None:
                continue
            r.marks["wake"] = t_wake
            rec = {"trace_id": r.trace_id, "rows": r.n,
                   "express": bool(r.express)}
            rec.update(trace_breakdown(r))
            traces.append(rec)
    # Stats land BEFORE any waiter wakes: a caller that resets the
    # stats window the moment result() returns must find this batch in
    # the window it completed in, and never see it leak into the next
    # one (a load generator that resets the window per rate does
    # exactly that).
    tele_counters.record_serve_requests(len(good))
    tele_counters.record_serve_batch()
    if express:
        tele_counters.record_serve_express()
    stats.record_batch(len(good), queue_depth, lats, express=express,
                       traces=traces)
    off = 0
    for req in good:
        # Attribution BEFORE the result event fires: a waiter that
        # wakes on set_result must already see which version scored it
        # (hot-swap attribution — PendingRequest.model_token).
        req.model_token = model.token
        req.set_result(scores[off:off + req.n])
        off += req.n
    if observer is not None:
        try:
            observer(Xb, scores, lats)
        except Exception:  # ddtlint: disable=broad-except
            # Observers (drift accumulation, shadow enqueue) are strictly
            # best-effort: they must never take the dispatch loop down or
            # touch the already-delivered results.
            pass
    return lats


class ServeEngine:
    """The persistent scoring process's core (transport-agnostic: the
    HTTP front end, the CLI and the tests all drive this same object).

    Request path: submit -> admission batch (MicroBatcher) -> one
    dispatch against the model reference read at batch start -> scatter
    -> per-request latency recorded. Model path: `swap(bundle)` builds
    + warms the new ServableModel entirely off the request path, then
    publishes the reference atomically (in-flight batches keep scoring
    the version they started with)."""

    def __init__(self, bundle, cfg: TrainConfig | None = None, *,
                 backend=None, max_wait_ms: float = 1.0,
                 max_batch: int = 256, quantize=False,
                 raw: bool = False, run_log=None,
                 express_lane: bool = True,
                 model_name: "str | None" = None,
                 request_traces: bool = True):
        from ddt_tpu.telemetry.events import RunLog

        self.cfg = cfg if cfg is not None else TrainConfig()
        # Optional fleet-style identity (ISSUE 15): when set, every
        # serve_latency window, hot_swap event, and /healthz payload
        # carries the model_name dimension — schema-additive, absent on
        # anonymous single-model servers so old logs/consumers are
        # untouched.
        self.model_name = model_name
        self.quantize_tier = normalize_quantize(quantize)
        want_impl = TIER_IMPL.get(self.quantize_tier)
        if want_impl is not None and self.cfg.predict_impl != want_impl:
            # quantize= IS the LUT-tier opt-in — the backend dispatch
            # and the engine's health/error-bound reporting must agree.
            self.cfg = self.cfg.replace(predict_impl=want_impl)
        self.backend = backend if backend is not None \
            else get_backend(self.cfg)
        self.buckets = default_buckets(max_batch)
        self.quantize = self.quantize_tier is not None
        self.raw = bool(raw)
        self.express_lane = bool(express_lane)
        self.stats = ServeStats()
        self.run_log = RunLog.coerce(run_log)
        # Registry root for reference-based hot swaps (`cli serve
        # --registry` sets it; the HTTP layer resolves refs — this
        # module never does file I/O, the serve-blocking-io contract).
        self.registry_root: "str | None" = None
        self.request_traces = bool(request_traces)
        self._swap_lock = threading.Lock()
        self._model = self._build(bundle)
        self._batcher = MicroBatcher(self._dispatch,
                                     max_wait_ms=max_wait_ms,
                                     max_batch=max_batch,
                                     request_traces=self.request_traces)

    # ------------------------------------------------------------------ #
    # model lifecycle
    # ------------------------------------------------------------------ #

    def _build(self, bundle) -> ServableModel:
        if isinstance(bundle, ServableModel):
            # A prebuilt model (the registry loader's AOT restore, or a
            # caller-constructed ServableModel): publish as-is — its
            # prologue was paid where it was built. Warm-up is repeated
            # here because it is the PUBLISH-side guarantee that no
            # live request ever pays a compile; on an already-warm
            # model it is a handful of cached dispatches.
            bundle.warmup()
            return bundle
        m = ServableModel(bundle, self.backend,
                          quantize=self.quantize_tier,
                          buckets=self.buckets, raw=self.raw)
        m.warmup()
        return m

    @property
    def model_token(self) -> str:
        return self._model.token

    @property
    def n_features(self) -> int:
        """Feature width of the CURRENTLY served model (the raw wire
        path derives row count from it; a request racing a hot swap is
        re-validated at dispatch like every other)."""
        return self._model.n_features

    def swap(self, bundle) -> dict:
        """Zero-downtime hot swap: build + warm the new version OFF the
        request path, then publish atomically. Returns {old, new} tokens
        (idempotent swaps — same content digest — still republish, which
        is harmless and keeps the semantics trivial)."""
        with self._swap_lock:               # serialize concurrent swaps
            new = self._build(bundle)
            old = self._model.token
            old_digest = self._model.artifact_digest
            # Single-assignment publish: readers (_dispatch, health,
            # the express lane) take ONE unlocked reference read and see
            # exactly the old or the new model, never a mix — the
            # declared exemption the threadmodel pass verifies stays a
            # lone reference store.
            self._model = new  # ddtlint: atomic-publish
        tele_counters.record_serve_hot_swap()
        if self.run_log is not None:
            # Registry provenance rides on the event: which ARTIFACT
            # (not just which content token) is serving before/after —
            # the digest is how an operator joins a swap to `registry
            # list` and to the training run's own log (docs/REGISTRY.md).
            extra = ({"model_name": self.model_name}
                     if self.model_name is not None else {})
            self.run_log.emit("fault", kind="hot_swap", old=old,
                              new=new.token,
                              old_artifact=old_digest,
                              new_artifact=new.artifact_digest,
                              **extra)
        log.info("hot-swapped model %s -> %s", old[:12], new.token[:12])
        return {"old": old, "new": new.token}

    # ------------------------------------------------------------------ #
    # request path
    # ------------------------------------------------------------------ #

    def predict_async(self, rows: np.ndarray,
                      trace_id: "str | None" = None) -> PendingRequest:
        rows = coerce_rows(rows)
        if rows.shape[1] != self._model.n_features:
            raise ValueError(
                f"rows have {rows.shape[1]} features; the served model "
                f"expects {self._model.n_features}")
        if self.express_lane and rows.shape[0] == 1:
            # Express lane (ISSUE 12): with an empty queue and no batch
            # mid-dispatch, a single-row request scores RIGHT HERE on
            # the caller's thread against the pre-traced [1, F] bucket
            # — no admission window, no handoff. Under load express()
            # returns None and the request coalesces like any other
            # (tail latency never regresses; batcher.py documents the
            # fairness argument).
            req = self._batcher.express(rows, 1, trace_id=trace_id)
            if req is not None:
                return req
        return self._batcher.submit(rows, rows.shape[0],
                                    trace_id=trace_id)

    def predict(self, rows: np.ndarray, timeout: float | None = 30.0):
        return self.predict_async(rows).result(timeout)

    def _dispatch(self, batch, queue_depth: int) -> None:
        # ONE model reference per micro-batch: every request in it is
        # scored by exactly this version (hot-swap atomicity); the
        # per-batch body lives in dispatch_batch (shared with the fleet
        # engine's per-model dispatch).
        model = self._model
        dispatch_batch(model, batch, queue_depth, self.stats)

    # ------------------------------------------------------------------ #
    # telemetry
    # ------------------------------------------------------------------ #

    def emit_latency(self, reset: bool = True) -> dict | None:
        """Emit the current window as a `serve_latency` run-log event
        (schema v4); returns the payload (None when the window is empty
        — an idle server emits nothing)."""
        summary = self.stats.window_summary(reset=reset)
        if summary["requests"] == 0:
            return None
        m = self._model
        summary["model_token"] = m.token
        if self.model_name is not None:
            summary["model_name"] = self.model_name
        # The tier ACTUALLY serving (satellite fix, ISSUE 12): a vmem
        # guard that silently degraded lut4 -> lut -> f32 shows up in
        # every telemetry window, not only in debug logs.
        summary["predict_impl"] = m.predict_impl
        if m.artifact_digest is not None:
            summary["artifact_digest"] = m.artifact_digest
        if self.run_log is not None:
            self.run_log.emit("serve_latency", **summary)
        return summary

    def debug_traces(self) -> dict:
        """model name -> completed-trace ring (GET /debug/requests).
        Anonymous single-model servers key on "default"."""
        return {self.model_name or "default":
                self.stats.traces_snapshot()}

    def flush_traces(self, reason: str = "on_demand") -> int:
        """Flush the completed-trace ring into the run log as ONE
        schema-additive `serve_trace` event (on demand via
        GET /debug/requests?emit=1; the fleet also flushes on SLO
        breach). Returns the number of traces flushed (0 on an empty
        ring or a log-less engine — nothing is emitted then)."""
        traces = self.stats.traces_snapshot()
        if not traces or self.run_log is None:
            return 0
        extra = ({"model_name": self.model_name}
                 if self.model_name is not None else {})
        self.run_log.emit("serve_trace", traces=traces,
                          count=len(traces),
                          model_token=self._model.token,
                          reason=reason, **extra)
        return len(traces)

    def metrics_snapshot(self) -> dict:
        """Live, non-resetting state for the /metrics exposition
        (serve/metrics.py renders it): per-model latency histograms on
        the fixed ladder, live backlog, residency. Read-only — the
        /metrics vs /stats?emit=1 contract."""
        name = self.model_name or "default"
        return {
            "models": {name: {
                "hist": self.stats.metrics_state(),
                "backlog_rows": self._batcher.backlog_rows(),
                "slo": None,
            }},
            "resident_models": 1,
            "max_resident": None,
        }

    def health(self) -> dict:
        m = self._model
        return {
            "ok": True,
            "model_name": self.model_name,
            "model_token": m.token,
            "quantized": m.quantized,
            "quantize_tier": getattr(m, "quantize_tier", None),
            "predict_impl": m.predict_impl,
            "lut_max_abs_err": m.max_abs_err,
            "buckets": list(self.buckets),
            "express_lane": self.express_lane,
            "artifact_digest": m.artifact_digest,
            "aot": m.aot,
            **self.stats.snapshot(),
        }

    def close(self) -> None:
        self._batcher.close()
        self.emit_latency(reset=True)
        if self.run_log is not None:
            self.run_log.close()
