"""CPUDevice: the NumPy reference backend behind the DeviceBackend boundary.

The reference ships a CPU reference implementation of (at least) the histogram
kernel and compares device throughput against it [BASELINE: "≥5× the repo's
CPU-reference histogram throughput"]. This backend wraps the M0 oracle trainer
(reference/numpy_trainer.py) behind the L4 interface so:

- backend-parity tests can drive CPU vs TPU through the identical call
  surface (SURVEY.md §4 "Backend parity"), and
- a CPU baseline can be measured on the same contract as the TPU path.

When the native C++ kernel (ddt_tpu/native) is built, `build_histograms` uses
it (that's the honest CPU baseline — a compiled kernel, like the reference's);
otherwise the NumPy np.add.at path runs. Both match the oracle bit-for-bit.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import numpy as np

from ddt_tpu.backends.base import DeviceBackend, HostTree
from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import TreeEnsemble
from ddt_tpu.reference import numpy_trainer as ref


class CPULabels(NamedTuple):
    """Labels + optional instance weights — the opaque `y` handle (per-
    dataset state lives in handles, not on the cached backend instance;
    mirrors TPUDevice.LabelHandle)."""

    y: np.ndarray
    w: np.ndarray | None


class CPUDevice(DeviceBackend):
    """NumPy (optionally native-C++-accelerated) reference backend."""

    name = "cpu"

    def __init__(self, cfg: TrainConfig, use_native: bool | None = None):
        super().__init__(cfg)
        if cfg.grad_dtype != "f32":
            # This backend defines the f32 ground truth the quantized
            # path's agreement contracts are measured against — running
            # it quantized would be circular (and the numpy oracle has
            # no integer histogram path). Refuse loudly.
            raise NotImplementedError(
                f"grad_dtype={cfg.grad_dtype!r} is not supported on the "
                "CPU oracle backend; use backend='tpu' (runs on CPU XLA "
                "too)")
        self._native = None          # histogram kernel
        self._native_split = None    # split-gain kernel (plain contract)
        self._native_split_full = None  # full contract (mask/missing/cat)
        self._native_traverse = None  # batch predict traversal
        if use_native is not False:
            try:
                from ddt_tpu.native import (
                    histogram_native, split_gain_full_native,
                    split_gain_native, traverse_native)

                self._native = histogram_native
                self._native_split = split_gain_native
                self._native_split_full = split_gain_full_native
                self._native_traverse = traverse_native
            except Exception:
                if use_native:  # explicitly requested → surface the failure
                    raise

    # ------------------------------------------------------------------ #

    def upload(self, Xb: np.ndarray) -> np.ndarray:
        Xb = np.ascontiguousarray(Xb)
        if Xb.dtype != np.uint8:
            raise TypeError(f"binned data must be uint8, got {Xb.dtype}")
        return Xb

    def upload_labels(self, y: np.ndarray,
                      sample_weight: np.ndarray | None = None
                      ) -> "CPULabels":
        return CPULabels(
            np.asarray(y),
            None if sample_weight is None
            else np.asarray(sample_weight, np.float32),
        )

    # ------------------------------------------------------------------ #

    def build_histograms(self, data, g, h, node_index, n_nodes) -> np.ndarray:
        if self._native is not None:
            return self._native(
                data, g, h, node_index, n_nodes, self.cfg.n_bins
            )
        return ref.build_histograms(
            data, g, h, node_index, n_nodes, self.cfg.n_bins
        )

    def best_splits(self, hist):
        # Granular L4 surface: 3-tuple contract (missing-direction handling
        # lives in the grow path, which calls ref.best_splits directly).
        if self._native_split is not None:
            return self._native_split(
                hist, self.cfg.reg_lambda, self.cfg.min_child_weight
            )
        return ref.best_splits(
            hist, self.cfg.reg_lambda, self.cfg.min_child_weight
        )[:3]

    # ------------------------------------------------------------------ #

    def init_pred(self, y, base: float):
        R = y.y.shape[0]
        if self.cfg.loss == "softmax":
            return np.zeros((R, self.cfg.n_classes), np.float32)
        return np.full(R, base, np.float32)

    def load_pred(self, raw: np.ndarray):
        return np.array(raw, np.float32)

    def grad_hess(self, pred, y):
        g, h = ref.grad_hess(pred, y.y, self.cfg.loss)
        if y.w is not None:
            w = y.w[:, None] if g.ndim == 2 else y.w
            g = g * w
            h = h * w
        return g, h

    def grow_tree(self, data, g, h,
                  feature_mask=None, tree_id: int = 0) -> tuple[HostTree, Any]:
        # tree_id is the quantized-gradient rounding key — unused here:
        # this backend IS the f32 oracle (cfg.grad_dtype != "f32" is
        # refused at construction).
        split_full = None
        if self._native_split_full is not None:
            def split_full(hist, fm, missing, cm):
                return self._native_split_full(
                    hist, self.cfg.reg_lambda, self.cfg.min_child_weight,
                    feature_mask=fm, missing_bin=missing, cat_mask=cm)
        tree = ref.grow_tree(
            data, g, h, self.cfg,
            hist_fn=self.build_histograms,
            feature_mask=feature_mask, split_full_fn=split_full,
        )
        delta = (
            self.cfg.learning_rate * tree["leaf_value"][tree["leaf_of_row"]]
        ).astype(np.float32)
        host = HostTree(
            feature=tree["feature"],
            threshold_bin=tree["threshold_bin"],
            is_leaf=tree["is_leaf"],
            leaf_value=tree["leaf_value"],
            split_gain=tree["split_gain"],
            default_left=tree["default_left"],
        )
        return host, delta

    def apply_delta(self, pred, delta, class_idx: int):
        if pred.ndim == 2:
            pred[:, class_idx] += delta
        else:
            pred += delta
        return pred

    def loss_value(self, pred, yh) -> float:
        loss = self.cfg.loss
        y = yh.y
        w = yh.w

        def wmean(per_row):
            if w is None:
                return float(np.mean(per_row))
            return float(np.average(per_row, weights=w))

        if loss == "logloss":
            p = 1.0 / (1.0 + np.exp(-pred.astype(np.float64)))
            p = np.clip(p, 1e-12, 1 - 1e-12)
            return wmean(-(y * np.log(p) + (1 - y) * np.log(1 - p)))
        if loss == "mse":
            return wmean((pred - y) ** 2)
        z = pred - pred.max(axis=1, keepdims=True)
        logp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
        return wmean(-logp[np.arange(y.shape[0]), y.astype(np.int64)])

    # ------------------------------------------------------------------ #

    def predict_raw(self, ens: TreeEnsemble, Xb: np.ndarray,
                    compiled=None) -> np.ndarray:
        # `compiled` is accepted for interface parity (the serving tier
        # passes it unconditionally); the CPU traversal reads the
        # ensemble heap directly, so there is nothing to seed.
        if self._native_traverse is None or not isinstance(ens,
                                                           TreeEnsemble):
            # a node list's or an oblivious ensemble's own NumPy walk (the
            # C++ traversal reads heaps)
            return ens.predict_raw(Xb, binned=True)
        # C++ batch traversal (the CPU twin of the device gather+compare
        # path); routing-flag derivation lives in ONE place
        # (TreeEnsemble._traverse_native), aggregation shared with
        # TreeEnsemble.predict_raw.
        leaf = ens._traverse_native(Xb)                         # [T, R]
        if leaf is None:                    # library unavailable after all
            return ens.predict_raw(Xb, binned=True)
        return ens.aggregate_leaves(leaf)
