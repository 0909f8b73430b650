"""DeviceBackend: the L5→L4 operator-boundary contract.

SURVEY.md §1: "The Driver sees only DeviceBackend.{upload, build_histograms,
best_splits, apply_split/partition, predict}. Everything below L4 is swappable
per backend; everything above is backend-agnostic." The reference pairs a host
`Driver` with an `FPGADevice` behind this interface [BASELINE]; the north star
is a `TPUDevice` slotting in beside it with the tree loop unchanged. This
module is that interface, TPU-first:

- The granular kernels (`build_histograms`, `best_splits`) stay on the
  interface as the parity surface — tests drive each backend's kernels
  against the NumPy oracle through exactly these methods.
- The Driver's per-tree call is the *fused* `grow_tree`: on TPU a whole tree
  (all levels: histograms → allreduce → gains → split → row routing) is ONE
  device dispatch (ops/grow.py), because crossing the host boundary per kernel
  per level — the reference's FPGA calling convention — would serialise
  hundreds of dispatch latencies per tree. Backends that cannot fuse (the
  NumPy CPU reference) implement grow_tree as the plain level loop.
- Boosting state (raw predictions) lives where the backend wants it: opaque
  `pred` handles flow Driver → grad_hess → grow_tree → apply_delta without
  ever forcing a host round-trip. Only the grown tree's node arrays (a few KB)
  come back per tree.

Backend registry + flag selection lives in backends/__init__.py
([BASELINE] "backend selectable by flag").
"""

from __future__ import annotations

import abc
from typing import Any

import numpy as np

from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import TreeEnsemble


#: The device stamp of work done in NumPy / native code on the host.
HOST_STAMP = {"platform": "cpu", "device_kind": "host", "n_devices": 1}


class HostTree(dict):
    """One grown tree, host-side: np arrays feature/threshold_bin/is_leaf/
    leaf_value, each [n_nodes_total]. Plain dict subclass for clarity."""


class DeviceBackend(abc.ABC):
    """Uniform device API for histogram-GBDT training and inference."""

    name: str = "abstract"

    def __init__(self, cfg: TrainConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ #
    # data plane
    # ------------------------------------------------------------------ #

    @abc.abstractmethod
    def upload(self, Xb: np.ndarray) -> Any:
        """Ship the binned uint8 matrix [R, F] to the device (row-sharded when
        distributed). Returns an opaque handle accepted by the kernels."""

    @abc.abstractmethod
    def upload_labels(self, y: np.ndarray,
                      sample_weight: np.ndarray | None = None) -> Any:
        """Ship labels [R] (row-sharded alongside the data when
        distributed), with optional per-row instance weights — they scale
        gradients, hessians, and the training loss's numerator AND
        denominator (weighted means)."""

    # ------------------------------------------------------------------ #
    # L3 kernels (granular contract: parity tests + bench drive these)
    # ------------------------------------------------------------------ #

    @abc.abstractmethod
    def build_histograms(
        self,
        data: Any,
        g: Any,
        h: Any,
        node_index: Any,
        n_nodes: int,
    ) -> Any:
        """Per-(node, feature, bin) (g, h) sums: [n_nodes, F, n_bins, 2] f32.

        `node_index` is the level-local node per row (int32, -1 = frozen).
        When distributed this INCLUDES the cross-partition allreduce — the
        result is the global histogram, as the reference's fabric allreduce
        delivers it to split selection [BASELINE].
        """

    @abc.abstractmethod
    def best_splits(self, hist: Any) -> tuple[Any, Any, Any]:
        """SplitGain: per-node (gain f32, feature i32, threshold_bin i32)."""

    # ------------------------------------------------------------------ #
    # fused training ops (what the Driver actually calls per tree)
    # ------------------------------------------------------------------ #

    @abc.abstractmethod
    def init_pred(self, y: Any, base: float) -> Any:
        """Initial raw scores: [R] filled with `base` (or [R, C] zeros for
        softmax). Opaque device array."""

    @abc.abstractmethod
    def load_pred(self, raw: np.ndarray) -> Any:
        """Adopt host raw scores [R] / [R, C] as the boosting state (used by
        checkpoint resume). Opaque device array, padded/sharded as needed."""

    @abc.abstractmethod
    def grad_hess(self, pred: Any, y: Any) -> tuple[Any, Any]:
        """Loss gradients/hessians at `pred`: float32 [R] or [R, C]."""

    def sync(self, x: Any) -> None:
        """Barrier on x's producer chain, for phase profiling. No-op on
        host-resident backends (numpy arrays are already materialised);
        device backends block until x has actually been computed."""

    def apply_row_mask(self, g: Any, h: Any, mask: np.ndarray):
        """(g * mask, h * mask) — per-round row bagging (cfg.subsample).
        `mask` is a host bool [R]; device backends upload + fuse the
        multiply. Default: NumPy elementwise."""
        m = mask.astype(np.float32)
        if getattr(g, "ndim", 1) == 2:
            m = m[:, None]
        return g * m, h * m

    @abc.abstractmethod
    def grow_tree(self, data: Any, g: Any, h: Any,
                  feature_mask: np.ndarray | None = None,
                  tree_id: int = 0) -> tuple[Any, Any]:
        """Grow one complete-heap tree from (sharded) data + grads.
        feature_mask (host bool [F], or None) excludes features from split
        selection — cfg.colsample_bytree. `tree_id` is the absolute tree
        index (round * n_classes + class) — the quantized-gradient
        stochastic-rounding key on backends that honor cfg.grad_dtype
        (ignored elsewhere).

        Returns (tree_handle, delta): a backend-opaque handle to the tree's
        node arrays (resolve with fetch_tree), and the per-row raw-score
        increment lr * leaf_value[leaf_of_row] as an opaque device array
        aligned with `pred` (used by apply_delta). For softmax, g/h are the
        single class column being boosted.

        The handle lets device backends defer the device→host copy: the
        Driver resolves it one round later, hiding the transfer round-trip
        under the next tree's compute. CPU-resident backends just return
        the HostTree itself.
        """

    def fetch_tree(self, handle: Any) -> HostTree:
        """Resolve a grow_tree handle to host node arrays. Default: the
        handle already is the HostTree (CPU-resident backends)."""
        return handle

    @abc.abstractmethod
    def apply_delta(self, pred: Any, delta: Any, class_idx: int) -> Any:
        """pred updated by delta (into column class_idx when pred is [R, C])."""

    @abc.abstractmethod
    def loss_value(self, pred: Any, y: Any) -> float:
        """Mean training loss at `pred` (host float; may sync). Logging only."""

    # ------------------------------------------------------------------ #
    # inference
    # ------------------------------------------------------------------ #

    @abc.abstractmethod
    def predict_raw(self, ens: TreeEnsemble, Xb: np.ndarray,
                    compiled=None) -> np.ndarray:
        """Batch ensemble scoring on binned data (TreeEnsemble.predict path,
        [BASELINE]): raw margins [R] or [R, C], on host. `compiled` is an
        optional models/tree.CompiledEnsemble already built for THIS ens
        (the serving tier holds one per model version); backends that
        keep device-resident scoring caches use it to skip the per-call
        content hash, others may ignore it."""

    def links_on_device(self, ens) -> bool:
        """Whether `predict_raw(ens, Xb, link=True)` answers this model's
        probabilities, the link function taken by the backend's own scoring
        program. Default: never; the caller takes it
        (`utils/metrics.predict_proba_np`)."""
        return False

    # ------------------------------------------------------------------ #

    def device_stamp(self) -> dict:
        """{"platform", "device_kind", "n_devices"} of what this backend
        computes on — stamped into every printed result and run-log
        manifest. Default: the host (backends that never touch JAX)."""
        return dict(HOST_STAMP)

    def __repr__(self) -> str:  # pragma: no cover
        return f"<{type(self).__name__} backend={self.name!r}>"
