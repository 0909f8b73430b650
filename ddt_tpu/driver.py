"""Driver: the host-side tree-construction / boosting loop (layer L5).

The reference's `Driver` grows trees level-by-level against a `DeviceBackend`
and is explicitly "unchanged above the operator layer" when backends swap
[BASELINE]. This Driver is that loop, shaped for TPU dispatch economics
(SURVEY.md §3 call stack):

    for round in 1..n_trees:                      (sequential, host)
      g, h = backend.grad_hess(pred, y)           (device, fused elementwise)
      for c in classes:                           (1 for binary/mse)
        handle, delta = backend.grow_tree(data, g_c, h_c) (ONE device dispatch:
              histograms → [psum over mesh] → gains → splits → row routing,
              all levels)
        pred = backend.apply_delta(pred, delta, c)
      ensemble[t-1] = backend.fetch_tree(prev_handle)     (≈KBs to host, ONE
              transfer, pipelined one round behind so the device→host
              round-trip hides under the next tree's compute)

Boosting state (`pred`) is an opaque backend handle — on TPUDevice it lives
sharded on device for the whole run; the Driver never sees a float of it.

Observability (SURVEY.md §5): structured per-round records (train loss,
ms/tree) via `logging`, collected in `Driver.history`, and — when a
`run_log` is attached — emitted as schema-versioned JSONL telemetry events
(ddt_tpu/telemetry: run manifest, per-round records, per-phase timings,
early-stop decisions, resume/fault events, device counters; render with
`ddt_tpu.cli report`). With no run_log the hot loop pays nothing: no device
syncs, no file I/O. Checkpoint/resume
(SURVEY.md §5): pass `checkpoint_dir` — after every `checkpoint_every` rounds
the partial ensemble + cursor is written; `fit` resumes from the cursor if a
checkpoint exists (utils/checkpoint.py).

Validation tracking: `fit(..., eval_set=(Xb_val, y_val))` scores the held-out
set every round by incremental host-side traversal of each freshly grown tree
(O(rows·depth) NumPy — the val set never occupies device memory), records
`valid_<metric>` in history, and with `early_stopping_rounds=k` stops when
the metric hasn't improved in k rounds and truncates the ensemble to the best
round (utils/metrics.py).

Two documented exceptions to the cross-backend determinism story (the split
DECISIONS are bit-identical per ops/split.py; these are about reported
SCORES):

- f32 score boundary: device backends evaluate metrics with their f32 device
  twins (utils/metrics.device_metric) while host backends use the f64 host
  implementations, so per-round validation scores — and early-stopping
  choices on rounds tied within f32 resolution — can differ between TPU and
  CPU backends for the same data. Binary auc rides the binned-rank device
  twin (round-5: auc eval/early-stop now stays on the fused dispatch path),
  whose within-bin tie mass widens this seam to ~1/DEVICE_AUC_BINS (~2e-5)
  on the score values. (Softmax-auc is rejected at fit — the rank
  formulation is binary.)
- Resume score seam: on checkpoint resume with a device backend and an
  eval_set, val predictions are reconstituted by host roundwise rescoring,
  which differs from the uninterrupted device accumulation by FMA-contraction
  ULPs; near-tied best_round selection may shift across a resume. (The
  streaming trainer replays the device ops instead and is bit-exact — its
  runs are the week-long ones where this matters.)
"""

from __future__ import annotations

import dataclasses
import logging
import time

import numpy as np

from ddt_tpu.backends.base import DeviceBackend
from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import TreeEnsemble, empty_ensemble
from ddt_tpu.reference.numpy_trainer import base_score
from ddt_tpu.robustness import faultplan, set_fault_sink
from ddt_tpu.telemetry import costmodel
from ddt_tpu.telemetry import counters as tele_counters
from ddt_tpu.telemetry.annotations import phase_ctx
from ddt_tpu.telemetry.events import (
    PartitionRecorder, RoundRecorder, RunLog, comms_manifest_fields,
    derive_run_id, device_manifest_fields, emit_early_stop,
    emit_train_heartbeat, finish_run_log)
from ddt_tpu.utils import checkpoint
from ddt_tpu.utils.profiling import PhaseTimer

log = logging.getLogger("ddt_tpu.driver")

# The cap on rounds per fused dispatch is cfg.fused_block_rounds — a
# config field (not a constant) because it encodes a runtime-watchdog
# interaction that varies by deployment; rationale in
# TrainConfig's field docstring.


def _traverse_one(
    feature: np.ndarray,
    threshold_bin: np.ndarray,
    is_leaf: np.ndarray,
    Xb: np.ndarray,
    max_depth: int,
    default_left: np.ndarray | None = None,
    missing_bin_value: int = -1,
    cat_features: tuple = (),
) -> np.ndarray:
    """Leaf heap-slot per row for ONE tree (node arrays [n_nodes])."""
    R = Xb.shape[0]
    rows = np.arange(R)
    node = np.zeros(R, np.int64)
    for _ in range(max_depth):
        leaf = is_leaf[node]
        feat = feature[node]
        fv = Xb[rows, np.maximum(feat, 0)]
        go_right = fv > threshold_bin[node]
        if cat_features is not None and len(cat_features):
            go_right = np.where(np.isin(feat, cat_features),
                                fv != threshold_bin[node], go_right)
        if missing_bin_value >= 0:
            go_right = np.where(fv == missing_bin_value,
                                ~default_left[node], go_right)
        nxt = 2 * node + 1 + go_right
        node = np.where(leaf, node, nxt)
    return node


class Driver:
    """Backend-agnostic boosting driver (the L5→L4 contract consumer)."""

    def __init__(
        self,
        backend: DeviceBackend,
        cfg: TrainConfig,
        log_every: int = 10,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 25,
        profile: bool = False,
        run_log: "RunLog | str | None" = None,
        profiler_window=None,
        status=None,
    ):
        self.backend = backend
        self.cfg = cfg
        self.log_every = log_every
        self.checkpoint_dir = checkpoint_dir
        if checkpoint_dir is not None and checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}")
        self.checkpoint_every = checkpoint_every
        self.history: list[dict] = []
        self.best_round: int | None = None
        self.best_score: float | None = None
        # profile=True records a per-phase wallclock breakdown (SURVEY.md §5
        # tracing): each phase ends with a device barrier, so rounds get
        # SLOWER (the fast path pipelines phases without syncs) but the
        # report shows where device time actually goes. A run_log alone
        # also times phases — WITHOUT the barriers (numbers then measure
        # host dispatch + whatever the async queue back-pressures, which
        # is honest for a pipeline) and WITHOUT forcing the granular path.
        self.profile = profile
        # A path string means the Driver OWNS the log (opens and closes
        # it); a RunLog instance stays the caller's to close.
        self._own_run_log = isinstance(run_log, str)
        self.run_log = RunLog.coerce(run_log)
        self.timer = (
            PhaseTimer() if (profile or self.run_log is not None) else None
        )
        self._recorder: RoundRecorder | None = None
        self._part_rec: PartitionRecorder | None = None
        # Device-truth cost capture (telemetry/costmodel.py): a collector
        # is installed only for telemetry runs (_fit prologue) and torn
        # down in fit's finally — runs without a log never lower/compile
        # anything extra (guard-tested).
        self._cost = None
        # Programmatic xprof capture window (telemetry/profiler.py), or
        # None — every hook below is behind an `is not None` check.
        self._window = profiler_window
        # Live-ops status aggregate (telemetry/statusd.TrainStatus), or
        # None — same gating contract as the window above: without
        # `--status-port` the trainer holds no statusd state and every
        # round-boundary hook is one `is not None` test (ISSUE 20).
        self._status = status

    def _draw_colsample_mask(self, rnd: int, c: int, F: int) -> np.ndarray:
        """The per-(seed, round, class) colsample feature mask; the draw
        itself lives in ops/sampling.colsample_mask (shared with the
        streaming trainers) because the fused == granular == streamed
        ensemble-parity guarantee depends on every path drawing
        bit-identical masks."""
        from ddt_tpu.ops.sampling import colsample_mask

        return colsample_mask(self.cfg.seed, rnd, c, F,
                              self.cfg.colsample_bytree)

    def _psync(self, x) -> None:
        """Backend barrier on x's producer chain — only when PROFILING
        (the fast path must stay sync-free to pipeline rounds; a run_log
        alone adds zero syncs); no-op on host-resident backends."""
        if self.profile:
            self.backend.sync(x)

    def _finish_run(self, t0: float, completed_rounds: int,
                    counters_start: dict | None) -> None:
        """Telemetry epilogue shared by the granular and fused paths:
        phase report at INFO (profiled runs), then the shared
        phase_timings / counters / run_end epilogue
        (telemetry.events.finish_run_log)."""
        if self.profile and self.timer is not None:
            self.timer.log_report(log)
        if self._status is not None:
            self._status.set_phase("done")
        finish_run_log(self.run_log, self.timer, counters_start,
                       completed_rounds,
                       round(time.perf_counter() - t0, 4),
                       partitions=self._part_rec, costs=self._cost)

    def fit(
        self,
        Xb: np.ndarray,
        y: np.ndarray,
        eval_set: tuple[np.ndarray, np.ndarray] | None = None,
        eval_metric: str | None = None,
        early_stopping_rounds: int | None = None,
        sample_weight: np.ndarray | None = None,
    ) -> TreeEnsemble:
        """Train on binned uint8 data. Returns the grown ensemble.

        `sample_weight` (float [R], >= 0, not all zero): per-row instance
        weights scaling each row's gradient/hessian contribution and the
        weighted-mean training loss; the base score becomes the weighted
        mean. Integer weights are exactly equivalent to duplicating rows
        (tested). Validation metrics stay unweighted; the streaming
        trainer does not take weights.

        (Ownership shim around _fit: a Driver-OWNED run log — one built
        from a path string — is closed on every exit, success or mid-run
        exception such as the NaN-eval ValueError, so repeated failing
        fits cannot leak file handles. fit_streaming carries the same
        shim. The same shim scopes the robustness state: the fault-event
        sink points at this run's log for the duration, and a
        cfg.fault_plan chaos plan is activated here — unless one is
        already active process-wide, e.g. the CLI armed it before
        multihost bootstrap — and deactivated on every exit.)"""
        # Load the plan BEFORE touching any process-global state: a bad
        # plan file must fail clean, not leak the sink or collectors.
        plan = None
        if self.cfg.fault_plan and faultplan.active_plan() is None:
            plan = faultplan.load_plan(self.cfg.fault_plan)
        prev_sink = set_fault_sink(self.run_log)
        plan_prev = None
        plan_armed = False
        if plan is not None:
            plan_prev = faultplan.activate(plan)
            plan_armed = True
        try:
            return self._fit(
                Xb, y, eval_set=eval_set, eval_metric=eval_metric,
                early_stopping_rounds=early_stopping_rounds,
                sample_weight=sample_weight)
        finally:
            # Cost capture must not outlive its run (a later telemetry-
            # less fit in the same process must pay zero capture work),
            # and a still-open xprof window (death inside the round
            # range) must be stopped so the trace flushes.
            costmodel.deactivate(self._cost)
            if self._window is not None:
                self._window.close()
            if plan_armed:
                faultplan.deactivate(plan_prev)
            set_fault_sink(prev_sink)
            if self._own_run_log and self.run_log is not None:
                self.run_log.close()

    def _fit(
        self,
        Xb: np.ndarray,
        y: np.ndarray,
        eval_set: tuple[np.ndarray, np.ndarray] | None = None,
        eval_metric: str | None = None,
        early_stopping_rounds: int | None = None,
        sample_weight: np.ndarray | None = None,
    ) -> TreeEnsemble:
        """fit's body (see fit for the full contract)."""
        cfg = self.cfg
        R, F = Xb.shape
        if Xb.dtype != np.uint8:
            raise TypeError(f"Xb must be uint8 binned data, got {Xb.dtype}")
        C = cfg.n_classes if cfg.loss == "softmax" else 1
        if cfg.cat_features and cfg.cat_features[-1] >= F:
            # Validate here, where F is known: the TPU path's scatter
            # would silently DROP out-of-bounds indices (JAX semantics)
            # while the NumPy twin raises — a backend-parity trap.
            raise ValueError(
                f"cat_features index {cfg.cat_features[-1]} out of range "
                f"for {F} features"
            )
        if sample_weight is not None:
            sample_weight = np.asarray(sample_weight, np.float32)
            if sample_weight.shape != (R,):
                raise ValueError(
                    f"sample_weight must be [R]={R}, got "
                    f"{sample_weight.shape}")
            if not np.all(np.isfinite(sample_weight)) \
                    or (sample_weight < 0).any():
                raise ValueError("sample_weight must be finite and >= 0")
            if not (sample_weight > 0).any():
                raise ValueError("sample_weight is all zero")
        bs = base_score(np.asarray(y), cfg.loss, cfg.n_classes,
                        sample_weight=sample_weight)

        # Telemetry prologue — BEFORE the first upload so the transfer
        # counters see the data plane; all of it is host-side bookkeeping
        # (zero device syncs) and absent entirely when run_log is None.
        t_fit0 = time.perf_counter()
        counters_start = None
        # The deterministic config digest serves two consumers: the v2
        # manifest merge key AND the xprof capture window's trace-dir
        # name (telemetry/profiler.py) — computed whenever either wants
        # it. The FULL config feeds the digest: two sweep points
        # differing only in, say, learning_rate must refuse to merge, so
        # no field may be left out.
        run_id = None
        if self.run_log is not None or self._window is not None \
                or self._status is not None:
            run_id = derive_run_id(
                trainer="driver", rows=int(R), features=int(F),
                **dataclasses.asdict(cfg))
        # Exposed for artifact provenance: api.train stamps it into the
        # TrainResult so saved models' embedded manifests (and registry
        # artifacts) cross-reference this run's log (docs/REGISTRY.md).
        self.run_id = run_id
        if self._window is not None:
            self._window.bind(run_id)
        if self._status is not None:
            self._status.begin_run(run_id=run_id,
                                   total_rounds=cfg.n_trees, rows=int(R))
        if self.run_log is not None:
            tele_counters.install_jax_listener()
            counters_start = tele_counters.snapshot()
            # Device-truth cost capture (telemetry/costmodel.py): active
            # for this run only; deactivated in fit's finally.
            self._cost = costmodel.activate()
            self.run_log.emit(
                "run_manifest", trainer="driver",
                backend=self.backend.name, loss=cfg.loss,
                n_trees=cfg.n_trees, max_depth=cfg.max_depth,
                n_bins=cfg.n_bins, rows=int(R), features=int(F),
                n_classes=C, seed=cfg.seed,
                distributed=bool(getattr(self.backend, "distributed",
                                         False)),
                # v2 extras: the cross-host merge key + lane label
                # (telemetry.merge) — identical on every pod host by SPMD
                # construction.
                run_id=run_id,
                host=int(getattr(self.backend, "host_index", 0)),
                # ISSUE-10 extras (schema extras, no version bump): the
                # RESOLVED split-finding comms config — report renders
                # the per-mode comms line from these.
                **comms_manifest_fields(self.backend),
                **device_manifest_fields(self.backend),
                # v3 extras: the xprof cross-reference — a flight-recorder
                # lane and a profiler session join on run_id through
                # these (telemetry/profiler.py).
                **(self._window.manifest_fields()
                   if self._window is not None else {}))

        data = self.backend.upload(Xb)
        y_dev = self.backend.upload_labels(np.asarray(y),
                                           sample_weight=sample_weight)
        pred = self.backend.init_pred(y_dev, bs)

        ens = empty_ensemble(
            cfg.n_trees * C, cfg.max_depth, F, cfg.learning_rate, bs,
            cfg.loss, cfg.n_classes,
            missing_bin=cfg.missing_policy == "learn", n_bins=cfg.n_bins,
            cat_features=cfg.cat_features,
        )

        start_round = 0
        if self.checkpoint_dir is not None:
            from ddt_tpu.utils.checkpoint import try_resume

            start_round = try_resume(self.checkpoint_dir, ens, cfg,
                                     run_log=self.run_log)
            if start_round > 0:
                # Reconstitute boosting state by rescoring the partial
                # ensemble with fit's own per-round accumulation order, so
                # resumed training is BIT-identical to an uninterrupted run
                # (pairwise-summed predict_raw differs in ULPs, which could
                # flip a bf16-boundary gain downstream).
                part = ens.truncate(start_round * C)
                pred = self.backend.load_pred(
                    np.asarray(part.predict_raw_roundwise(Xb, binned=True))
                )
                log.info("resumed from checkpoint at round %d", start_round)
                if self.run_log is not None:
                    self.run_log.emit("fault", kind="checkpoint_resume",
                                      round=start_round)

        # --- validation-set state ---
        # Two realisations of per-round eval scoring:
        #   device (TPUDevice): validation predictions live ON DEVICE; each
        #   round's packed tree handles are applied there (eval_round), so
        #   the host never traverses the val set and the tree-fetch
        #   pipeline stays on. Only the metric crosses to host — a scalar
        #   when its f32 device twin exists (every shipped valid metric),
        #   else a raw-score vector (the twin-less-metric fallback).
        #   host (CPUDevice): incremental NumPy traversal per tree.
        metric_name = None
        val_raw = None
        use_dev_eval = False
        dev_metric = None
        val_data_dev = val_y_dev = val_pred_dev = None
        if eval_set is not None:
            from ddt_tpu.utils.metrics import (
                GREATER_IS_BETTER, default_metric, evaluate)

            Xb_val, y_val = eval_set
            Xb_val = np.asarray(Xb_val)
            y_val = np.asarray(y_val)
            if Xb_val.dtype != np.uint8:
                raise TypeError("eval_set features must be uint8 binned data")
            metric_name = eval_metric or default_metric(cfg.loss)
            if metric_name not in GREATER_IS_BETTER:
                raise ValueError(
                    f"unknown metric {metric_name!r}; "
                    f"have {sorted(GREATER_IS_BETTER)}"
                )
            if metric_name == "auc" and C > 1:
                # The rank formulation is binary; multiclass raw scores
                # would crash deep inside the host auc (shape mismatch on
                # ravel) — fail at the cause instead.
                raise ValueError(
                    "auc is a binary metric; softmax eval_set supports "
                    "logloss or accuracy"
                )
            sign = 1.0 if GREATER_IS_BETTER[metric_name] else -1.0
            if C > 1:
                val_raw = np.full((Xb_val.shape[0], C), bs, np.float32)
            else:
                val_raw = np.full(Xb_val.shape[0], bs, np.float32)
            if start_round > 0:
                k = start_round * C
                val_raw = ens.truncate(k).predict_raw_roundwise(
                    Xb_val, binned=True).astype(np.float32)
            best = -np.inf
            if getattr(self.backend, "eval_round", None) is not None:
                from ddt_tpu.utils.metrics import device_metric

                use_dev_eval = True
                dev_metric = (
                    metric_name
                    if device_metric(metric_name, n_classes=C) is not None
                    else None
                )
                val_data_dev = self.backend.upload(Xb_val)
                val_y_dev = self.backend.upload_labels(y_val)
                val_pred_dev = self.backend.load_pred(val_raw)
        elif early_stopping_rounds is not None:
            raise ValueError("early_stopping_rounds requires an eval_set")

        t_out = start_round * C
        completed_rounds = cfg.n_trees
        # One-deep fetch pipeline: a device backend's grow_tree returns an
        # unresolved handle; resolving it costs a device→host round-trip,
        # so we fetch tree k while tree k+1 computes. HOST-side eval needs each tree immediately for
        # incremental scoring (pipeline bypassed); device-side eval applies
        # the handle on device, so the pipeline stays on.
        pending: tuple | None = None   # (handle, ensemble slot)

        # Phase context (telemetry.annotations.phase_ctx): host PhaseTimer
        # + a `ddt:<phase>` profiler span, so Perfetto host tracks carry
        # the same names as the run log's phase_timings; bare nullcontext
        # when neither profiling nor telemetry is on.
        ph = phase_ctx(self.timer)

        self._recorder = RoundRecorder(
            self.history, self.run_log, self.log_every, cfg.n_trees,
            metric_name, log)
        # Estimated allreduce payload per round (telemetry.counters): the
        # psum lives inside the fused device program, so the host records
        # the statically-known histogram shapes instead of observing the
        # wire. Zero on single-device runs.
        coll_bytes_round = 0
        if getattr(self.backend, "distributed", False):
            # EFFECTIVE payload for the resolved comms config (mode,
            # wire dtype, subtraction) — backends/tpu.py
            # collective_bytes_per_tree is the one home.
            coll_bytes_round = C * self.backend.collective_bytes_per_tree(F)
        # Effective per-round g/h HBM stream (telemetry.counters
        # grad_stream_bytes — the quantized-gradient byte win's witness:
        # f32 and int8/int16 runs record their own dtype's model, so two
        # run logs' counters carry the ratio).
        self._grad_bytes_round = C * tele_counters.grad_stream_bytes(
            R, cfg.max_depth, cfg.grad_dtype)
        # Per-partition attribution (the distributed flight recorder):
        # active only on mesh runs WITH a run log — it probes per-device
        # shard completion, which is a barrier on the observed handle.
        # Single-device runs and disabled telemetry get the inert
        # recorder (no probes, no syncs — the PR-2 invariant).
        self._part_rec = part_rec = PartitionRecorder(
            self.run_log, self.backend, bytes_per_round=coll_bytes_round)
        # Straggler watchdog (robustness/watchdog.py): consumes the
        # recorder's per-round lanes, so it exists exactly when the
        # recorder does — detection events always, the repartition
        # ACTION only behind cfg.straggler_repartition (which also
        # forces the granular path below: the rotation needs a round
        # boundary a fused block does not yield).
        self._watchdog = None
        if part_rec.active:
            from ddt_tpu.robustness.watchdog import StragglerWatchdog

            self._watchdog = StragglerWatchdog(
                threshold=cfg.straggler_skew_threshold)

        def _store(handle, slot):
            with ph("fetch_tree"):
                tree = self.backend.fetch_tree(handle)
            ens.feature[slot] = tree["feature"]
            ens.threshold_bin[slot] = tree["threshold_bin"]
            ens.is_leaf[slot] = tree["is_leaf"]
            ens.leaf_value[slot] = tree["leaf_value"]
            ens.split_gain[slot] = tree["split_gain"]
            ens.default_left[slot] = tree["default_left"]
            return tree

        # Stochastic training (cfg.subsample / cfg.colsample_bytree):
        # bagging row masks are STATELESS counter-based draws — a pure
        # hash of (seed, round, global row id), ops/sampling — so every
        # path (host-drawn here, device in-scan on the fused path,
        # per-chunk in the streaming trainers) computes the identical bit
        # on every backend/partition layout AND across checkpoint resume.
        # Colsample [F] feature masks stay host-drawn (KBs; same shared
        # home, ops/sampling.colsample_mask).
        bagging = cfg.subsample < 1.0
        colsample = cfg.colsample_bytree < 1.0

        # Fused block path: backends exposing grow_rounds run whole blocks
        # of rounds in one device dispatch + one tree fetch. Validation
        # rides INSIDE the scan (grow_rounds_eval) when its metric has a
        # device twin; EARLY STOPPING rides too — the stopping rule is
        # replayed post-hoc over the block's per-round scores vector
        # (training past the stop point cannot change earlier trees, so
        # truncation gives the EXACT granular-path model; blocks are
        # capped at the patience so overrun work is bounded). Every
        # stochastic-training combination composes with the fused path
        # since round 5: colsample [K, C, F] masks (KBs) and bagging's
        # round ids both ride the scan as xs (the row masks themselves
        # are recomputed in-scan from the counter hash), with or without
        # in-scan eval. Only profiling always runs granular (per-phase
        # barriers), plus the host-eval fallbacks below.
        fused_eval = (
            eval_set is not None
            and use_dev_eval
            and dev_metric is not None
            and getattr(self.backend, "grow_rounds_eval", None) is not None
        )
        fused_masked = (
            colsample
            and getattr(self.backend, "grow_rounds_masked", None)
            is not None
        )
        if (
            getattr(self.backend, "grow_rounds", None) is not None
            and (eval_set is None or fused_eval)
            and not self.profile
            and not cfg.straggler_repartition
            and (not colsample or fused_masked)
        ):
            eval_state = None
            if fused_eval:
                eval_state = (val_data_dev, val_pred_dev, val_y_dev,
                              dev_metric, sign)
            ens = self._fit_fused(
                data, y_dev, pred, ens, start_round, C,
                eval_state=eval_state,
                early_stopping_rounds=early_stopping_rounds,
                colsample_features=F if colsample else None,
                coll_bytes_round=coll_bytes_round)
            self._finish_run(t_fit0, ens.n_trees // C, counters_start)
            return ens

        for rnd in range(start_round, cfg.n_trees):
            if self._window is not None:      # xprof window: start edge
                self._window.round_start(rnd)
            t0 = time.perf_counter()
            round_handles: list = []
            with ph("grad"):
                g, h = self.backend.grad_hess(pred, y_dev)
                self._psync(h)
            if bagging:
                from ddt_tpu.ops.sampling import row_keep_np

                rmask = row_keep_np(cfg.seed, rnd, 0, R, cfg.subsample)
                g, h = self.backend.apply_row_mask(g, h, rmask)
            for c in range(C):
                gc = g[:, c] if C > 1 else g
                hc = h[:, c] if C > 1 else h
                fmask = (
                    self._draw_colsample_mask(rnd, c, F) if colsample
                    else None
                )
                tg0 = time.perf_counter()
                with ph("grow"):
                    handle, delta = self.backend.grow_tree(
                        data, gc, hc, feature_mask=fmask,
                        tree_id=rnd * C + c)
                    self._psync(delta)
                # Flight recorder: per-device completion of this tree's
                # growth (hist + allreduce + gain + route). No-op unless
                # distributed AND a run log is attached.
                part_rec.observe("grow", handle, tg0)
                with ph("apply_delta"):
                    pred = self.backend.apply_delta(pred, delta, c)
                    self._psync(pred)
                if use_dev_eval:
                    round_handles.append(handle)
                    if pending is not None:
                        _store(*pending)
                    pending = (handle, t_out)
                elif val_raw is not None:
                    tree = _store(handle, t_out)
                    leaf = _traverse_one(
                        tree["feature"], tree["threshold_bin"],
                        tree["is_leaf"], Xb_val, cfg.max_depth,
                        default_left=tree["default_left"],
                        missing_bin_value=cfg.missing_bin_value,
                        cat_features=cfg.cat_features,
                    )
                    dv = cfg.learning_rate * tree["leaf_value"][leaf]
                    if C > 1:
                        val_raw[:, c] += dv
                    else:
                        val_raw += dv
                else:
                    if pending is not None:
                        _store(*pending)
                    pending = (handle, t_out)
                t_out += 1

            val_score = None
            if use_dev_eval:
                with ph("eval"):
                    val_pred_dev, sc = self.backend.eval_round(
                        val_data_dev, val_pred_dev, round_handles,
                        val_y_dev, dev_metric)
                if dev_metric is not None:
                    val_score = float(sc)
                else:           # metric has no f32 device twin (auc):
                    # sc is a replicated copy of the predictions (safe to
                    # resolve even on a multi-host mesh); pad rows dropped.
                    val_score = evaluate(
                        metric_name, y_val,
                        np.asarray(sc)[: Xb_val.shape[0]],
                    )
            elif val_raw is not None:
                val_score = evaluate(metric_name, y_val, val_raw)
            dt = time.perf_counter() - t0
            if coll_bytes_round:
                tele_counters.record_collective(coll_bytes_round)
            tele_counters.record_grad_stream(self._grad_bytes_round)
            if cfg.grad_dtype != "f32":
                tele_counters.record_grad_quant_round()

            if val_score is not None:
                if sign * val_score > best:
                    best = sign * val_score
                    self.best_round = rnd
                    self.best_score = val_score

            self._recorder.record(
                rnd, dt * 1e3, val_score,
                lambda: self.backend.loss_value(pred, y_dev))
            self._observe_straggler(rnd, part_rec.flush_round(rnd))
            if self._window is not None:      # xprof window: stop edge
                self._window.round_end(rnd)
            tele_counters.record_train_round()
            if self._status is not None:      # live-ops plane (ISSUE 20)
                # history only holds on-cadence records; off-cadence
                # rounds get a fresh bare record for the /debug ring.
                self._status.round_end(
                    rnd, dt * 1e3,
                    self.history[-1]
                    if (self.history
                        and self.history[-1].get("round") == rnd + 1)
                    else RoundRecorder.make_record(rnd, dt * 1e3, None))

            if early_stopping_rounds is not None and self.best_round is None:
                # NaN never compares greater, so a NaN-from-round-1 metric
                # leaves best_round unset; fail with the cause, not a
                # TypeError from the subtraction below.
                raise ValueError(
                    f"validation {metric_name} has been NaN since round 1 "
                    "(degenerate eval_set — e.g. constant scores or a "
                    "single-class slice); cannot early-stop on it"
                )
            if (
                early_stopping_rounds is not None
                and rnd - self.best_round >= early_stopping_rounds
            ):
                log.info(
                    "early stop at round %d (best %s=%.6f at round %d)",
                    rnd + 1, metric_name, self.best_score,
                    self.best_round + 1,
                )
                emit_early_stop(self.run_log, rnd + 1, metric_name,
                                self.best_round + 1, self.best_score)
                if pending is not None:   # flush BEFORE truncating: the
                    _store(*pending)      # pending slot indexes the full-
                    pending = None        # size arrays
                ens = ens.truncate((self.best_round + 1) * C)
                completed_rounds = self.best_round + 1
                break

            if (
                self.checkpoint_dir is not None
                and (rnd + 1) % self.checkpoint_every == 0
            ):
                if pending is not None:        # flush the fetch pipeline
                    _store(*pending)
                    pending = None
                checkpoint.maybe_save(self.checkpoint_dir, ens, cfg,
                                      rnd + 1)
                if self._status is not None:
                    self._status.checkpoint_saved(rnd + 1)
            # Liveness heartbeat at the checkpoint CADENCE, checkpoint
            # directory or not (ISSUE 20): a SIGKILLed run's log ends at
            # most one cadence past its last heartbeat, which is what
            # `report progress` rolls up. No-op without a run log.
            if self.run_log is not None and self.checkpoint_every >= 1 \
                    and (rnd + 1) % self.checkpoint_every == 0:
                emit_train_heartbeat(
                    self.run_log, rnd=rnd, total_rounds=cfg.n_trees,
                    checkpoint_round=(rnd + 1
                                      if self.checkpoint_dir is not None
                                      else None),
                    ms_per_round=dt * 1e3,
                    rows_per_s=(R / dt if dt > 0 else None))
            if self.checkpoint_every >= 1 \
                    and (rnd + 1) % self.checkpoint_every == 0 \
                    and self._wants_repartition():
                # The watchdog's action fires only on the checkpoint
                # CADENCE (with or without a directory): the rotation
                # recompiles every mesh-bound program, so it must be
                # paid at a boundary, never mid-stride. The pending
                # fetch is flushed first — its handle belongs to the
                # pre-rotation mesh.
                if pending is not None:
                    _store(*pending)
                    pending = None
                (data, y_dev, pred, val_data_dev, val_y_dev,
                 val_pred_dev) = self._repartition(
                    rnd, data, y_dev, pred, val_data_dev, val_y_dev,
                    val_pred_dev, C)

        if pending is not None:                # flush the fetch pipeline
            _store(*pending)
            pending = None

        checkpoint.maybe_save(self.checkpoint_dir, ens, cfg,
                              completed_rounds)
        self._finish_run(t_fit0, completed_rounds, counters_start)
        return ens

    def _observe_straggler(self, rnd: int, parts: "dict | None") -> None:
        """One round's flushed partition lanes -> the watchdog (shared
        feed: robustness.watchdog.feed_watchdog — warning + fault
        event). No-op when either side is absent."""
        if self._watchdog is None:
            return
        from ddt_tpu.robustness.watchdog import feed_watchdog

        feed_watchdog(self._watchdog, self.run_log, rnd, parts, log)

    def _wants_repartition(self) -> bool:
        # The 2D (rows x features) mesh repartitions too since ISSUE 11:
        # rotate_row_partitions rolls the ROW axis of the device grid
        # (feature columns preserved), so no feature_partitions guard.
        return (self._watchdog is not None
                and self._watchdog.pending_repartition
                and self.cfg.straggler_repartition
                and getattr(self.backend, "rotate_row_partitions", None)
                is not None)

    def _repartition(self, rnd: int, data, y_dev, pred,
                     val_data, val_y, val_pred, C: int) -> tuple:
        """The watchdog's action: rotate the row-shard -> device
        assignment (backend.rotate_row_partitions — shard contents and
        therefore the model are untouched) and move every live handle
        onto the new mesh. Runs at checkpoint boundaries only; emits a
        `repartition` fault event so the run log shows when lanes
        moved."""
        be = self.backend
        if not be.rotate_row_partitions():
            # Nothing to rotate (single device / multi-process mesh):
            # acknowledge so the watchdog does not re-request every
            # boundary.
            self._watchdog.repartition_done()
            return data, y_dev, pred, val_data, val_y, val_pred
        extra = 1 if C > 1 else 0
        data = be.reshard_data(data)
        y_dev = type(y_dev)(be.reshard_rows(y_dev.y),
                            be.reshard_rows(y_dev.valid))
        pred = be.reshard_rows(pred, extra_dims=extra)
        if val_data is not None:
            val_data = be.reshard_data(val_data)
        if val_y is not None:
            val_y = type(val_y)(be.reshard_rows(val_y.y),
                                be.reshard_rows(val_y.valid))
        if val_pred is not None:
            val_pred = be.reshard_rows(val_pred, extra_dims=extra)
        log.warning("repartitioned at round %d: rotated row shards off "
                    "the straggling device", rnd + 1)
        if self.run_log is not None:
            self.run_log.emit("fault", kind="repartition", round=rnd + 1,
                              rotation=1)
        self._watchdog.repartition_done()
        return data, y_dev, pred, val_data, val_y, val_pred

    def _fit_fused(self, data, y_dev, pred, ens: TreeEnsemble,
                   start_round: int, C: int,
                   eval_state: tuple | None = None,
                   early_stopping_rounds: int | None = None,
                   colsample_features: int | None = None,
                   coll_bytes_round: int = 0
                   ) -> TreeEnsemble:
        """Block loop over backend.grow_rounds: K rounds per dispatch,
        K x C trees per fetch. Blocks break at checkpoint_every boundaries
        so the checkpoint cadence (and resume bit-exactness) is identical
        to the granular path. With eval_state, validation scoring runs
        inside the scan (grow_rounds_eval) and a [K] scores vector rides
        the same fetch; early stopping replays the stopping rule over
        that vector after the fetch — identical models to the granular
        path (trees past the stop point are simply discarded), with
        blocks capped at the patience so at most one patience-worth of
        rounds is grown beyond the stop."""
        cfg = self.cfg
        metric_name = None
        if eval_state is not None:
            val_data, val_pred, val_y, metric_name, sign = eval_state
            best = -np.inf
        # Coarse phase breakdown for telemetry runs: the block dispatch is
        # async (enqueue returns immediately), so "grow_block" measures
        # dispatch + whatever back-pressures, and "fetch_tree" — the
        # np.asarray barrier — carries the block's device wallclock.
        ph = phase_ctx(self.timer)
        rnd = start_round
        while rnd < cfg.n_trees:
            K = min(cfg.n_trees - rnd, cfg.fused_block_rounds)
            if self.checkpoint_dir is not None:
                nxt = (rnd // self.checkpoint_every + 1) * \
                    self.checkpoint_every
                K = min(K, nxt - rnd)
            if early_stopping_rounds is not None:
                K = min(K, max(early_stopping_rounds, 1))
            if self._window is not None:
                # xprof window: break blocks at the capture edges (the
                # checkpoint-boundary treatment) so start/stop land on
                # true round boundaries, then open the window if this
                # block enters it.
                K = self._window.block_cap(rnd, K)
                self._window.round_start(rnd)
            t0 = time.perf_counter()
            fmasks = None
            if colsample_features is not None:
                F = colsample_features
                fmasks = np.zeros((K, C, F), bool)
                for k in range(K):
                    for c in range(C):
                        fmasks[k, c] = self._draw_colsample_mask(
                            rnd + k, c, F)
            with ph("grow_block"):
                if eval_state is not None:
                    trees_h, pred, losses_h, val_pred, scores_h = \
                        self.backend.grow_rounds_eval(
                            data, pred, y_dev, K,
                            val_data, val_pred, val_y, metric_name,
                            first_round=rnd, fmasks=fmasks)
                elif fmasks is not None:
                    trees_h, pred, losses_h = \
                        self.backend.grow_rounds_masked(
                            data, pred, y_dev, K, fmasks, first_round=rnd)
                else:
                    trees_h, pred, losses_h = self.backend.grow_rounds(
                        data, pred, y_dev, K, first_round=rnd)
            # Flight recorder: per-device completion of the whole block
            # (one lane sample per device per block; the probe is the
            # block barrier, so the fetch below materialises already-done
            # transfers). Inert unless distributed + run log.
            part_rec = self._part_rec
            if part_rec is not None:
                part_rec.observe("grow_block", trees_h, t0)
            with ph("fetch_tree"):
                if eval_state is not None:
                    scores = np.asarray(scores_h)  # [K] — same fetch wave
                trees = np.asarray(trees_h)     # [K, C, 5, N] — ONE fetch
                losses = np.asarray(losses_h)
            dt = time.perf_counter() - t0
            if self._window is not None:
                # The fetch above was the block's barrier: the captured
                # trace now holds every dispatch of rounds [rnd, rnd+K).
                self._window.round_end(rnd + K - 1)
            if part_rec is not None:
                # Watchdog feed on the fused path too — detection only
                # (the repartition action needs the granular loop, which
                # cfg.straggler_repartition forces).
                self._observe_straggler(
                    rnd, part_rec.flush_round(rnd, n_rounds=K))
            tele_counters.record_d2h(trees.nbytes + losses.nbytes)
            if coll_bytes_round:
                tele_counters.record_collective(coll_bytes_round * K)
            tele_counters.record_grad_stream(self._grad_bytes_round * K)
            if cfg.grad_dtype != "f32":
                tele_counters.record_grad_quant_round(K)
            for k in range(K):
                for c in range(C):
                    slot = (rnd + k) * C + c
                    p = trees[k, c]
                    ens.feature[slot] = p[0].astype(np.int32)
                    ens.threshold_bin[slot] = p[1].astype(np.int32)
                    ens.is_leaf[slot] = p[2].astype(bool)
                    ens.leaf_value[slot] = p[3]
                    ens.split_gain[slot] = p[4]
                    ens.default_left[slot] = p[5].astype(bool)
                r = rnd + k
                val_score = None
                if eval_state is not None:
                    val_score = float(scores[k])
                    if sign * val_score > best:
                        best = sign * val_score
                        self.best_round = r
                        self.best_score = val_score
                self._recorder.record(
                    r, dt * 1e3 / K, val_score,
                    lambda k=k: float(losses[k]))
                tele_counters.record_train_round()
                if self._status is not None:  # live-ops plane (ISSUE 20)
                    self._status.round_end(
                        r, dt * 1e3 / K,
                        self.history[-1]
                        if (self.history
                            and self.history[-1].get("round") == r + 1)
                        else RoundRecorder.make_record(
                            r, dt * 1e3 / K, None))
                if early_stopping_rounds is not None:
                    if self.best_round is None:
                        raise ValueError(
                            f"validation {metric_name} has been NaN since "
                            "round 1 (degenerate eval_set — e.g. constant "
                            "scores or a single-class slice); cannot "
                            "early-stop on it"
                        )
                    if r - self.best_round >= early_stopping_rounds:
                        log.info(
                            "early stop at round %d (best %s=%.6f at "
                            "round %d)", r + 1, metric_name,
                            self.best_score, self.best_round + 1,
                        )
                        emit_early_stop(self.run_log, r + 1, metric_name,
                                        self.best_round + 1,
                                        self.best_score)
                        ens = ens.truncate((self.best_round + 1) * C)
                        checkpoint.maybe_save(self.checkpoint_dir, ens,
                                              cfg, self.best_round + 1)
                        return ens
            rnd += K
            if rnd < cfg.n_trees:
                checkpoint.maybe_save(self.checkpoint_dir, ens, cfg, rnd,
                                      self.checkpoint_every)
                if self._status is not None \
                        and self.checkpoint_dir is not None \
                        and rnd % self.checkpoint_every == 0:
                    self._status.checkpoint_saved(rnd)
            # Heartbeat when this block CROSSED a cadence boundary: with
            # a checkpoint dir, blocks break exactly at checkpoint_every
            # boundaries (the K cap above) so these are the granular
            # path's heartbeat rounds; without one, block ends are the
            # only true round boundaries the fused dispatch has, so the
            # heartbeat lands on the first block end past the mark.
            if self.run_log is not None and self.checkpoint_every >= 1 \
                    and (rnd // self.checkpoint_every
                         > (rnd - K) // self.checkpoint_every):
                emit_train_heartbeat(
                    self.run_log, rnd=rnd - 1, total_rounds=cfg.n_trees,
                    checkpoint_round=(rnd
                                      if self.checkpoint_dir is not None
                                      and rnd < cfg.n_trees
                                      and rnd % self.checkpoint_every == 0
                                      else None),
                    ms_per_round=dt * 1e3 / K)
        checkpoint.maybe_save(self.checkpoint_dir, ens, cfg, cfg.n_trees)
        return ens
