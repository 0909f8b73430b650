"""Backend registry + flag selection.

[BASELINE]: "FPGA vs TPU backend selectable by flag" — the reference picks its
DeviceBackend by a runtime flag. Here the registry maps flag values to
implementations: "cpu" (NumPy/native reference), "tpu" (JAX/XLA — the north
star), and "fpga" (present for flag-surface parity, unavailable in this
build: we have no FPGA shell to drive, and stubbing silently would be lying
about capability).
"""

from __future__ import annotations

from ddt_tpu.backends.base import DeviceBackend, HostTree
from ddt_tpu.config import TrainConfig


class FPGADevice(DeviceBackend):
    """Flag-parity stub for the reference's FPGA backend (not in this build)."""

    name = "fpga"

    def __init__(self, cfg: TrainConfig):
        raise NotImplementedError(
            "The FPGA backend exists in this framework's flag surface for "
            "parity with the reference, but this build targets TPU: no FPGA "
            "shell/runtime is present. Use --backend=tpu or --backend=cpu."
        )

    # Abstract methods are never reachable (init always raises); satisfy the
    # ABC so the class itself is constructible up to the NotImplementedError.
    upload = upload_labels = build_histograms = best_splits = None  # type: ignore
    init_pred = load_pred = grad_hess = grow_tree = apply_delta = None  # type: ignore
    loss_value = predict_raw = None  # type: ignore


# Backend instances are cached on the config fields that shape their traced
# programs: a TPUDevice's jitted grow/grad/predict functions live on the
# instance, and recompiling them costs seconds — far more than any
# training round. Fields like
# n_trees never enter a trace, so two train() calls differing only there
# share one compiled backend. subsample and seed DO enter the fused trace
# since round 5 (the in-scan counter-based bagging hash bakes both in);
# a cached instance reused across them would train with the wrong masks.
# seed is trace-relevant ONLY under bagging, so the key normalises it to
# 0 when subsample == 1.0 — a seed sweep over deterministic/colsample-only
# configs (whose masks are host data, not trace constants) keeps sharing
# one compiled backend instead of paying a recompile per seed.
_JIT_FIELDS = (
    "backend", "n_partitions", "feature_partitions", "host_partitions",
    "max_depth", "n_bins", "learning_rate", "loss", "n_classes",
    "reg_lambda", "min_child_weight", "min_split_gain",
    "hist_impl", "predict_impl", "matmul_input_dtype", "missing_policy",
    "cat_features", "subsample",
    # Trace-shaping comms + kernel-phasing knobs: the resolved collective
    # mode/dtype/slab count and the sibling-subtraction flag all bake
    # into the compiled grow/stream programs — a cached instance reused
    # across them would train with the wrong collectives (the comms
    # parity tests flip exactly these).
    "hist_subtraction", "split_comms", "hist_comms_dtype",
    "hist_comms_slabs",
    # Quantized-gradient training (ISSUE 14): the integer histogram
    # programs differ from f32 at every level — a cached f32 instance
    # reused under grad_dtype='int8' would silently train unquantized.
    "grad_dtype",
)


def _cache_key(cfg: TrainConfig) -> tuple:
    # seed is trace-relevant under bagging (in-scan counter hash) AND
    # under quantized gradients (the stochastic-rounding key bakes it
    # into the grow programs) — normalise to 0 only when neither is on.
    seed_live = cfg.subsample < 1.0 or cfg.grad_dtype != "f32"
    return tuple(getattr(cfg, f) for f in _JIT_FIELDS) + (
        cfg.seed if seed_live else 0,
    )
# LRU-bounded: each cached TPUDevice pins its compiled executables (and any
# upload-derived device state) for its lifetime, so a hyperparameter sweep
# over many configs must evict old entries. TrainConfig is frozen, so a
# cached instance's cfg can never drift from the key it was cached under.
_CACHE_MAX = 8
_CACHE: "dict" = {}


def get_backend(cfg: TrainConfig, use_cache: bool = True,
                **kwargs) -> DeviceBackend:
    """Instantiate (or reuse) the backend named by cfg.backend (the flag)."""
    key = None
    if use_cache and not kwargs:
        key = _cache_key(cfg)
        hit = _CACHE.pop(key, None)
        if hit is not None:
            _CACHE[key] = hit      # re-insert: most-recently-used
            return hit
    if cfg.backend == "cpu":
        from ddt_tpu.backends.cpu import CPUDevice

        be: DeviceBackend = CPUDevice(cfg, **kwargs)
    elif cfg.backend == "tpu":
        from ddt_tpu.backends.tpu import TPUDevice

        be = TPUDevice(cfg, **kwargs)
    elif cfg.backend == "fpga":
        return FPGADevice(cfg)
    else:
        raise ValueError(f"unknown backend {cfg.backend!r}")
    if key is not None:
        _CACHE[key] = be
        while len(_CACHE) > _CACHE_MAX:
            _CACHE.pop(next(iter(_CACHE)))    # evict least-recently-used
    return be


__all__ = ["DeviceBackend", "HostTree", "FPGADevice", "get_backend"]
