"""Training configuration for the TPU-native distributed decision-tree trainer.

Capability contract: SURVEY.md §5 ("Config/flag system") — a single TrainConfig
dataclass mirrored by CLI flags, including the [BASELINE]-required backend flag
(FPGA vs TPU selectable by flag in the reference; here cpu/tpu, with fpga
present-but-stubbed so the flag surface matches).
"""

from __future__ import annotations

import dataclasses
from typing import Optional


LOSSES = ("logloss", "mse", "softmax")
BACKENDS = ("cpu", "tpu", "fpga")  # fpga is a stub: flag parity with reference


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Hyper-parameters and system knobs for GBDT training.

    Mirrors the reference's flag set (depth, trees, bins, backend, partitions)
    as recovered in SURVEY.md §2 "CLI / config".

    Frozen: backend instances are cached keyed on config fields
    (backends/__init__.py), so a mutable config could desynchronize a cached
    backend from its cache key. Use .replace() to derive variants.
    """

    # --- model ---
    n_trees: int = 100          # boosting rounds (x n_classes trees for softmax)  # ddtlint: trace-inert — host-side loop bound only: every round traces the same program, and resume replays to the recorded round regardless of the target
    max_depth: int = 6          # levels of splits; complete heap tree layout
    n_bins: int = 255           # [BASELINE] "255 bins named explicitly"
    learning_rate: float = 0.1
    loss: str = "logloss"       # logloss | mse | softmax
    n_classes: int = 2          # used when loss == "softmax"

    # --- regularisation (XGBoost-style gain formula) ---
    reg_lambda: float = 1.0     # L2 on leaf weights
    min_child_weight: float = 1e-3   # min hessian sum per child
    min_split_gain: float = 0.0      # split only if gain > this

    # --- stochastic training (LightGBM/XGBoost-style bagging) ---
    subsample: float = 1.0           # row fraction per boosting round
    colsample_bytree: float = 1.0    # feature fraction per tree

    # --- missing values ---
    # "zero": NaN maps to bin 0 (v1 policy, no model change).
    # "learn": the TOP bin (n_bins-1) is reserved for NaN and every split
    #   learns a default direction for missing rows (left/right by gain),
    #   the standard histogram-GBDT treatment (LightGBM/XGBoost).
    missing_policy: str = "zero"

    # --- categorical features ---
    # Feature indices treated as CATEGORICAL (bin = category id from the
    # CategoricalEncoder): their split candidates are one-vs-rest
    # ("bin == k goes left") scored by one-hot gain, instead of ordinal
    # "bin <= t" — the Criteo-config treatment beyond frequency-ordinal
    # (SURVEY.md §2 "one-hot-gain variant"). Tuple (hashable: it keys
    # compiled programs). Categorical columns must be integer-coded
    # (never NaN).
    cat_features: tuple = ()

    # --- system ---
    backend: str = "tpu"        # cpu | tpu | fpga(stub)
    n_partitions: int = 1       # row partitions (data parallel over mesh axis)
    feature_partitions: int = 1  # column partitions (TP-analog mesh axis)
    # Declarative 2D mesh shape (Pr, Pf) — the ROADMAP item 2 spelling
    # of the (rows x features) layout (--mesh-shape Pr,Pf on the CLI).
    # When set it NORMALIZES into n_partitions/feature_partitions at
    # construction and then resets to None — a pure constructor-time
    # input, so both spellings of the same mesh produce byte-identical
    # configs (equal run-id digests, backend cache keys, checkpoint
    # fingerprints; `.replace()` never false-conflicts against a stale
    # stored pair). Setting it alongside a CONFLICTING explicit
    # n_partitions/feature_partitions raises — two sources of truth
    # for the mesh shape is a silent-wrong-mesh bug, not a
    # convenience.
    mesh_shape: "Optional[tuple]" = None  # ddtlint: trace-inert — describes the machine, not the model: the backend cache is process-local (one live mesh per process) and checkpoints must resume on a different topology
    host_partitions: int = 1    # cross-slice "hosts" mesh axis (DCN): row
    #   shards span hosts x rows; histogram psum phases ICI-first then DCN.
    #   Total devices used = host_partitions x n_partitions x
    #   feature_partitions.
    hist_impl: str = "auto"     # auto | matmul | segment | pallas
    # Sibling-subtraction trick in the level loop (ops/grow.
    # level_histograms): levels >= 1 build histograms only for LEFT
    # children and recover each right child as parent - left — half the
    # kernel work and half the allreduce payload per level. "auto"
    # enables it only on a real TPU chip (ops/grow.
    # resolve_hist_subtraction): right-child sums differ from a direct
    # build by f32 ULPs, which model quality never sees but the CPU
    # suites' streamed == in-memory BITWISE contracts would; "on"/"off"
    # force either side (tests use "on" with interpret-mode kernels).
    hist_subtraction: str = "auto"  # auto | on | off
    # Split-finding collective (parallel/comms.py, docs/PERF.md
    # "Histogram comms"). "allreduce": the classic full-histogram psum —
    # every device receives every feature's bins and runs the same
    # argmax. "reduce_scatter": each of the P row shards merges only its
    # F/P feature slab, finds its slab's best splits locally, and the
    # tiny per-shard winner tuples are all_gathered — per-level
    # collective payload drops from O(F·B) to O(F·B/P) + O(P·nodes).
    # "auto" picks reduce_scatter exactly when a row mesh is live (and
    # the feature axis is not separately sharded); trees are
    # structure-identical either way (comms.combine_shard_winners
    # reproduces the single-device argmax tie-break exactly).
    split_comms: str = "auto"   # auto | allreduce | reduce_scatter
    # Wire dtype of the histogram collective (parallel/comms.py
    # hist_reduce; NEVER on by default): "bf16" halves payload bytes at
    # ~2^-9 relative rounding per partial; "int32_fixed" reduces on a
    # shared fixed-point grid with an INTEGER sum — order-independent,
    # so N-partition merges are bit-stable where f32 psum order was not.
    # Both carry a computed error bound (comms.comms_error_bound) held
    # by the split-agreement contract tests.
    hist_comms_dtype: str = "f32"   # f32 | bf16 | int32_fixed
    # Slab-pipelined comms overlap: split each level's histogram
    # build + collective into N feature slabs so slab k+1's histogram
    # kernels dispatch while slab k's collective is still on the wire
    # (XLA's async collectives hide DCN latency behind VPU work).
    # f32/bf16 collectives are elementwise, so slab phasing is
    # bit-identical to the monolithic form by construction (tested);
    # int32_fixed computes its fixed-point scale per collective, so
    # each SLAB quantizes on its own (tighter) grid — deterministic,
    # within the same error bound, but the slab count is part of that
    # mode's numerics (split agreement still holds; tested). 0 =
    # auto: pipelined only on a real TPU mesh (where a wire exists to
    # hide); 1 = off; N >= 2 forces N slabs (tests).
    hist_comms_slabs: int = 0   # 0 = auto | 1 = off | N slabs
    # Batch-scoring traversal implementation (ops/predict.py dispatch):
    # "auto" takes the Pallas VMEM traversal kernel on binned data when a
    # real TPU backs the computation and the shape fits its VMEM budget,
    # falling back to the one-hot compare+reduce path; "pallas"/"onehot"
    # force one side (pallas off-TPU runs the interpreter — tests only).
    # "lut" is the TreeLUT-style int8 quantized traversal
    # (ops/predict_lut.py — the low-latency serving opt-in, `--quantized`
    # on the CLI): int8 thresholds + fp16 leaf tables, ~4x less HBM
    # traffic per request, leaf values within the tables' documented
    # max-abs-error bound of f32; auto-falls back to the f32 path when
    # the shape exceeds the kernel's VMEM budget (predict_lut_fits).
    # "lut4" is the bit-packed int4 tier (`--quantized int4`): leaf
    # tables two-nibbles-per-byte with per-tree scales (thresholds join
    # the pack on <= 15-bin models), halving the int8 tier's resident
    # bytes again; falls back int4 -> int8 -> f32 down the same guard
    # ladder (predict_lut4_fits / predict_lut_fits).
    predict_impl: str = "auto"  # auto | pallas | onehot | lut | lut4
    seed: int = 0
    # Cap on boosting rounds per fused device dispatch (Driver._fit_fused).
    # One block already amortizes dispatch latency to nothing, so bigger
    # buys no throughput — but an UNBOUNDED block turns long configs into
    # one multi-minute device program with zero host interaction, which
    # (a) a watchdogged runtime can kill as hung (the full 500-round
    # depth-8 Covertype config took an earlier chip host's worker down as
    # a single dispatch) and (b) starves
    # checkpoint and progress-log cadence. The default's ~1-2 device-
    # minutes-per-block headroom is deployment-specific — deeper/wider
    # configs on watchdogged runtimes tune it DOWN (--fused-block-rounds).
    fused_block_rounds: int = 100

    # --- numerics ---
    # Histogram accumulators are always float32 (preferred_element_type on the
    # MXU); this knob controls the one-hot matmul INPUT dtype — bfloat16 rides
    # the systolic array at full rate, float32 forces exact accumulation.
    matmul_input_dtype: str = "bfloat16"
    # Quantized-gradient training (ops/grad.py; docs/PERF.md "Quantized
    # gradients"; NEVER on by default): "int8"/"int16" discretize g/h
    # once per (tree, output dim) onto a shared power-of-two grid —
    # per-dim scale from psum'd max|g|/sum|g|, SEEDED stochastic
    # rounding (unbiased, chaos-replayable: a pure function of (seed,
    # tree, global row), never per retry attempt) — and the whole
    # histogram pipeline then runs INTEGER: int32 VMEM accumulation,
    # exact sibling subtraction (hist_subtraction 'auto' resolves ON
    # everywhere — the f32-ULP caveat is gone), bit-stable int32
    # cross-shard/chunk merges, one dequantize after the last merge.
    # Cuts the per-level g/h HBM stream 4x (int8) / 2x (int16) and
    # halves every level >= 1's collective payload on platforms where
    # f32 subtraction was gated off. Split gains come from dequantized
    # totals with a computed worst-case bound
    # (ops/grad.grad_quant_error_bound — witnessed, not hoped).
    # Composes with every mesh/streaming path EXCEPT the host-backend
    # streaming loop (refused loudly) and the CPU oracle backend.
    grad_dtype: str = "f32"     # f32 | int16 | int8

    # --- robustness (docs/ROBUSTNESS.md) ---
    # Path to a JSON fault-injection plan (robustness/faultplan.py); the
    # chaos harness. None (the default) compiles every injection seam to
    # a single module-global read — the telemetry disabled-path bar.
    fault_plan: Optional[str] = None  # ddtlint: trace-inert — chaos-harness knob: injected faults must be invisible to config identity so an injected run's checkpoints resume clean
    # Act on the straggler watchdog: when the flight recorder's
    # per-round partition attribution shows one device persistently past
    # the skew threshold, rotate the row-shard -> device assignment at
    # the next checkpoint boundary (shard contents untouched — the model
    # is unchanged by construction). Detection events are always emitted
    # on telemetry mesh runs; this flag gates the ACTION, and it forces
    # the granular Driver path (repartitioning needs round-boundary
    # control a fused block does not yield).
    straggler_repartition: bool = False  # ddtlint: trace-inert — host-side scheduling action (shard->device rotation); the model is unchanged by construction, so no contract may key on it
    # Watchdog trip point: a device whose per-round phase total exceeds
    # the MEDIAN OF THE OTHER lanes by this factor is a straggler
    # candidate (excluding the candidate keeps the default meaningful
    # even on a 2-lane mesh — robustness/watchdog.py).
    straggler_skew_threshold: float = 2.0  # ddtlint: trace-inert — watchdog trip point on the detection side only; never read inside a trace and never shapes the trained model

    def __post_init__(self) -> None:
        if self.loss not in LOSSES:
            raise ValueError(f"loss must be one of {LOSSES}, got {self.loss!r}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}"
            )
        if not (1 <= self.n_bins <= 256):
            raise ValueError("n_bins must be in [1, 256] (uint8 binned data)")
        if self.max_depth < 1:
            raise ValueError("max_depth must be >= 1")
        if self.loss == "softmax" and self.n_classes < 2:
            raise ValueError("softmax needs n_classes >= 2")
        if self.mesh_shape is not None:
            ms = tuple(int(v) for v in self.mesh_shape)
            if len(ms) != 2 or any(v < 1 for v in ms):
                raise ValueError(
                    f"mesh_shape must be a (Pr >= 1, Pf >= 1) pair, got "
                    f"{self.mesh_shape!r}")
            pr, pf = ms
            if self.n_partitions not in (1, pr):
                raise ValueError(
                    f"mesh_shape={ms} conflicts with n_partitions="
                    f"{self.n_partitions}; set one, not both")
            if self.feature_partitions not in (1, pf):
                raise ValueError(
                    f"mesh_shape={ms} conflicts with feature_partitions="
                    f"{self.feature_partitions}; set one, not both")
            object.__setattr__(self, "n_partitions", pr)
            object.__setattr__(self, "feature_partitions", pf)
        # CANONICALIZE to None after normalizing: mesh_shape is a pure
        # constructor-time input, so both spellings of the same mesh
        # produce byte-IDENTICAL configs (equal run-id digests, backend
        # cache keys, checkpoint fingerprints) and `.replace(
        # n_partitions=...)` on a mesh_shape-built config cannot
        # false-conflict against a stale stored pair. Consumers read
        # the normalized n_partitions/feature_partitions fields.
        object.__setattr__(self, "mesh_shape", None)
        if (self.n_partitions < 1 or self.feature_partitions < 1
                or self.host_partitions < 1):
            raise ValueError("partition counts must be >= 1")
        if self.fused_block_rounds < 1:
            raise ValueError(
                f"fused_block_rounds must be >= 1, got "
                f"{self.fused_block_rounds}")
        if not (0.0 < self.subsample <= 1.0):
            raise ValueError("subsample must be in (0, 1]")
        if not (0.0 < self.colsample_bytree <= 1.0):
            raise ValueError("colsample_bytree must be in (0, 1]")
        if self.hist_subtraction not in ("auto", "on", "off"):
            raise ValueError(
                f"hist_subtraction must be auto|on|off, got "
                f"{self.hist_subtraction!r}"
            )
        if self.split_comms not in ("auto", "allreduce", "reduce_scatter"):
            raise ValueError(
                f"split_comms must be auto|allreduce|reduce_scatter, got "
                f"{self.split_comms!r}"
            )
        if self.hist_comms_dtype not in ("f32", "bf16", "int32_fixed"):
            raise ValueError(
                f"hist_comms_dtype must be f32|bf16|int32_fixed, got "
                f"{self.hist_comms_dtype!r}"
            )
        if self.hist_comms_slabs < 0:
            raise ValueError(
                f"hist_comms_slabs must be >= 0 (0 = auto), got "
                f"{self.hist_comms_slabs}"
            )
        if self.grad_dtype not in ("f32", "int16", "int8"):
            raise ValueError(
                f"grad_dtype must be f32|int16|int8, got "
                f"{self.grad_dtype!r}"
            )
        if self.grad_dtype != "f32" and self.hist_comms_dtype != "f32":
            # Refuse-loudly (ISSUE 14): quantized-gradient histograms are
            # ALREADY integer partials on one shared grid — compressing
            # the collective on top (bf16 rounding or int32_fixed's
            # per-collective re-quantize) would DOUBLE-quantize, voiding
            # the grad_quant error bound while buying nothing (the
            # integer merge is bit-stable without help). Same guard at
            # the wire in parallel/comms.hist_reduce.
            raise ValueError(
                f"grad_dtype={self.grad_dtype!r} with hist_comms_dtype="
                f"{self.hist_comms_dtype!r} would double-quantize the "
                "histogram collective: quantized-gradient partials are "
                "integer values on one shared grid and merge bit-stably "
                "as-is; keep hist_comms_dtype='f32'"
            )
        if self.predict_impl not in ("auto", "pallas", "onehot", "lut",
                                     "lut4"):
            raise ValueError(
                f"predict_impl must be auto|pallas|onehot|lut|lut4, got "
                f"{self.predict_impl!r}"
            )
        if self.missing_policy not in ("zero", "learn"):
            raise ValueError(
                f"missing_policy must be zero|learn, got "
                f"{self.missing_policy!r}"
            )
        if self.missing_policy == "learn" and self.n_bins < 3:
            raise ValueError(
                "missing_policy='learn' reserves the top bin; n_bins >= 3"
            )
        if self.straggler_skew_threshold <= 1.0:
            raise ValueError(
                "straggler_skew_threshold must be > 1.0 (1.0 is a "
                f"perfectly balanced mesh), got "
                f"{self.straggler_skew_threshold}"
            )
        # Normalize unconditionally: a list (even an empty one) must
        # become a tuple or the backend cache key is unhashable.
        object.__setattr__(
            self, "cat_features",
            tuple(sorted(int(f) for f in self.cat_features)))
        if self.cat_features:
            if self.cat_features[0] < 0:
                raise ValueError("cat_features indices must be >= 0")
            if self.missing_policy == "learn":
                raise ValueError(
                    "cat_features with missing_policy='learn' is not "
                    "supported: the reserved NaN bin would silently merge "
                    "the encoder's top category id into its neighbor "
                    "(categorical columns are integer-coded and never "
                    "NaN, so use missing_policy='zero')"
                )

    @property
    def n_nodes_total(self) -> int:
        """Heap-layout node count for a complete tree of `max_depth` levels."""
        return 2 ** (self.max_depth + 1) - 1

    @property
    def n_leaves_max(self) -> int:
        return 2 ** self.max_depth

    @property
    def missing_bin_value(self) -> int:
        """Bin index reserved for NaN rows under missing_policy='learn',
        -1 otherwise (the single home of the reserved-bin convention —
        every routing/traversal site reads this)."""
        return self.n_bins - 1 if self.missing_policy == "learn" else -1

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_file(cls, path: str) -> "TrainConfig":
        """TrainConfig from a YAML or JSON file (SURVEY.md §5 "Config/flag
        system": the optional file form of the flag set). Unknown keys
        fail loudly — a typo'd hyperparameter silently training with its
        default is worse than an error."""
        return cls(**load_config_file(path))


def load_config_file(path: str) -> dict:
    """Dict of TrainConfig fields from a .yaml/.yml/.json file, key-
    validated. The CLI overlays these onto flag-built configs (file wins
    for the fields it names)."""
    import json

    with open(path) as f:
        if path.endswith((".yaml", ".yml")):
            import yaml

            d = yaml.safe_load(f)
        else:
            d = json.load(f)
    if not isinstance(d, dict):
        raise ValueError(f"{path} must contain a mapping, got {type(d)}")
    fields = {f.name for f in dataclasses.fields(TrainConfig)}
    unknown = sorted(set(d) - fields)
    if unknown:
        raise ValueError(
            f"{path} has unknown TrainConfig keys {unknown}; "
            f"valid: {sorted(fields)}"
        )
    if "cat_features" in d:
        d["cat_features"] = tuple(d["cat_features"])
    if d.get("mesh_shape") is not None:
        d["mesh_shape"] = tuple(d["mesh_shape"])
    return d
