"""Public API (layer L8): ddt.train / ddt.predict.

SURVEY.md §1 L8: "`ddt.train()`, `ddt.predict()`, `python -m ddt_tpu.cli
train --backend=tpu`". Thin orchestration over the layers below: quantize
(L7) → Driver.fit against the flag-selected backend (L5/L4) → TreeEnsemble
(L6); predict routes through the backend's gather+compare scorer.
"""

from __future__ import annotations

import dataclasses
import logging

import numpy as np

from ddt_tpu.backends import get_backend
from ddt_tpu.backends.base import DeviceBackend
from ddt_tpu.config import TrainConfig
from ddt_tpu.data.quantizer import (BinMapper, feature_bincounts,
                                    fit_bin_mapper)
from ddt_tpu.driver import Driver
from ddt_tpu.models.tree import TreeEnsemble, ensemble_from_dict
from ddt_tpu.utils.atomic import atomic_savez

log = logging.getLogger("ddt_tpu.api")


@dataclasses.dataclass
class TrainResult:
    ensemble: TreeEnsemble
    mapper: BinMapper | None      # None when the caller passed binned data
    history: list[dict]           # {round, ms_per_round, train_loss @ log
    #   cadence, valid_<metric> every round when an eval_set was given}
    best_round: int | None = None   # 0-based; set when an eval_set was given
    best_score: float | None = None
    # api.train never fits a categorical encoder itself (it sees only the
    # numeric/pre-encoded matrix); a caller who encoded categorical columns
    # sets this so save() produces a complete artifact.
    encoder: "object | None" = None
    # Provenance stamped into saved artifacts' embedded manifests: the
    # telemetry run_id (present when the run had a run log or capture
    # window — the registry's cross-reference to the training run) and
    # the training config (fingerprinted, never embedded whole).
    run_id: str | None = None
    cfg: TrainConfig | None = None

    def save(self, path: str) -> None:
        """Persist the model artifact: ensemble + bin mapper + categorical
        encoder if one was attached (see the `encoder` field), manifest
        embedded (docs/REGISTRY.md)."""
        save_model(path, self.ensemble, mapper=self.mapper,
                   encoder=self.encoder, run_id=self.run_id, cfg=self.cfg)


@dataclasses.dataclass
class ModelBundle:
    """A loaded model artifact: the ensemble plus the preprocessing state
    (bin mapper, categorical encoder) it was trained with. Scoring new data
    MUST reuse this state — refitting a mapper on the scoring set silently
    produces wrong bins whenever its distribution differs from training
    (round-1 verdict, Weak #2)."""

    ensemble: TreeEnsemble
    mapper: BinMapper | None = None
    encoder: "object | None" = None   # data.categorical.CategoricalEncoder
    # Embedded manifest (schema version, content digest, run_id, git
    # rev — registry/manifest.py), digest-VERIFIED by load_model; None
    # for legacy manifest-less files, which stay loadable.
    manifest: dict | None = None


def save_model(path, ens: TreeEnsemble, mapper: BinMapper | None = None,
               encoder=None, *, run_id: str | None = None,
               cfg: TrainConfig | None = None) -> None:
    """Write one .npz holding the ensemble and, when given, the BinMapper
    and CategoricalEncoder fitted at training time. The file remains loadable
    by plain `TreeEnsemble.load` (extra keys are ignored there).

    An embedded manifest (registry/manifest.py: schema version, content
    digest over every payload array, the training `run_id`, a config
    fingerprint, git rev) rides under the `manifest_json` key —
    load_model verifies the digest so a torn or bit-rotted artifact is
    rejected loudly instead of serving silently wrong trees.

    Written tmp-then-os.replace (the atomic-artifact-write contract,
    docs/ROBUSTNESS.md): a process killed mid-save leaves the previous
    model intact, never a torn npz a serving loader would choke on."""
    from ddt_tpu.registry import manifest as manifest_mod

    d = ens.to_dict()
    if mapper is not None:
        # Reuse the classes' own save() dicts under a key prefix so any
        # future field (e.g. a missing-value bin) flows through here
        # without a second serialization site.
        d.update({f"mapper_{k}": v for k, v in mapper.save().items()})
    if encoder is not None:
        d.update({f"cat_{k}": v for k, v in encoder.save().items()})
    manifest_mod.embed_npz_manifest(
        d, kind="model_bundle", run_id=run_id,
        config_fingerprint=(
            manifest_mod.config_fingerprint_digest(cfg)
            if cfg is not None else None))
    # deterministic: model artifacts are content-addressed by the
    # registry — identical models must produce identical bytes.
    atomic_savez(path, compressed=True, deterministic=True, **d)


def load_model(path, *, verify: bool = True) -> ModelBundle:
    """Load a model artifact written by save_model (or a bare
    TreeEnsemble.save file — mapper/encoder come back None then; or an
    XGBoost model's `.json`, `models/xgboost_io.py`, with the mapper its
    own thresholds give). When
    the file carries an embedded manifest, its content digest is
    verified — a mismatch raises registry.IntegrityError (a ValueError)
    rather than returning silently corrupt trees; manifest-less legacy
    files load exactly as before. `verify=False` skips the digest pass
    for callers that already proved the file bytes (the registry loader
    restores behind an artifact-level sha256)."""
    from ddt_tpu.registry import manifest as manifest_mod

    if str(path).endswith((".json", ".ubj")):
        # an XGBoost model as the library saved it (`.ubj` refused by
        # name), the data taken to hold missing values; its thresholds
        # ranked into 256 bins: the mapper that scores raw rows
        from ddt_tpu.models.lightgbm_io import threshold_bin_mapper
        from ddt_tpu.models.xgboost_io import load_xgboost

        ens = load_xgboost(path)
        return ModelBundle(ensemble=ens,
                           mapper=threshold_bin_mapper(ens, n_bins=256))
    with np.load(path) as z:
        d = dict(z)
    manifest = manifest_mod.read_npz_manifest(d, verify=verify,
                                              source=str(path))
    ens = ensemble_from_dict(d)     # a heap, or a node list
    mapper = None
    if "mapper_edges" in d:
        mapper = BinMapper.load(
            {k[len("mapper_"):]: v for k, v in d.items()
             if k.startswith("mapper_")})
    encoder = None
    if "cat_n_cols" in d:
        from ddt_tpu.data.categorical import CategoricalEncoder

        encoder = CategoricalEncoder.load(
            {k[len("cat_"):]: v for k, v in d.items()
             if k.startswith("cat_")})
    return ModelBundle(ensemble=ens, mapper=mapper, encoder=encoder,
                       manifest=manifest)


def train(
    X: np.ndarray,
    y: np.ndarray,
    cfg: TrainConfig | None = None,
    *,
    binned: bool = False,
    mapper: BinMapper | None = None,
    backend: DeviceBackend | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 25,
    log_every: int = 10,
    eval_set: tuple[np.ndarray, np.ndarray] | None = None,
    eval_metric: str | None = None,
    early_stopping_rounds: int | None = None,
    sample_weight: np.ndarray | None = None,
    profile: bool = False,
    run_log=None,
    profiler_window=None,
    status=None,
    **cfg_overrides,
) -> TrainResult:
    """Train a GBDT. `X` is float features (quantized here) unless
    `binned=True` (uint8 bin indices). `cfg_overrides` are TrainConfig fields
    (e.g. train(X, y, n_trees=50, backend="cpu")). `backend` accepts either
    the flag string (a TrainConfig field) or a pre-built DeviceBackend
    instance (e.g. one holding a specific mesh). `run_log` (a JSONL path or
    a telemetry.RunLog) attaches the structured telemetry stream — run
    manifest, per-round records, phase timings, counters, XLA cost
    analysis — rendered by `python -m ddt_tpu.cli report`
    (docs/OBSERVABILITY.md). `profiler_window` (a
    telemetry.profiler.CaptureWindow) captures a programmatic xprof trace
    around a selected round range, cross-referenced into the manifest.
    `status` (a telemetry.statusd.TrainStatus) attaches the live
    training operations plane — the trainer updates it at round
    boundaries and `cli train --status-port` serves it over HTTP; None
    (the default) keeps the trainer statusd-free entirely."""
    if isinstance(backend, str):
        cfg_overrides["backend"] = backend
        backend = None
    if cfg is None:
        cfg = TrainConfig(**cfg_overrides)
    elif cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)

    if binned:
        Xb = np.asarray(X)
        if Xb.dtype != np.uint8:
            raise TypeError("binned=True requires uint8 bin indices")
    else:
        if mapper is None:
            mapper = fit_bin_mapper(np.asarray(X), n_bins=cfg.n_bins,
                                    seed=cfg.seed,
                                    missing_policy=cfg.missing_policy,
                                    cat_features=cfg.cat_features)
        elif cfg.missing_policy == "learn" and not mapper.missing_bin:
            raise ValueError(
                "missing_policy='learn' requires a BinMapper fitted with "
                "the same policy (its top bin must be the NaN bin)"
            )
        if cfg.cat_features:
            # A mapper fitted WITHOUT these columns gave them quantile
            # edges, which merge/permute category ids before the
            # one-vs-rest splits see them — silently wrong models.
            not_identity = mapper.non_identity_columns(cfg.cat_features)
            if not_identity:
                raise ValueError(
                    f"cat_features {not_identity} were not identity-binned "
                    "by this BinMapper; refit it with "
                    f"cat_features={tuple(sorted(cfg.cat_features))} so "
                    "category ids survive binning"
                )
        Xb = mapper.transform(np.asarray(X))
        # Drift reference capture (ISSUE 19): the per-feature bin
        # histogram of the TRAINING matrix, attached to the mapper so it
        # rides the artifact (save_model's mapper_* channel) into the
        # serve tier's divergence scorer. Raw counts — sample size stays
        # visible; the scorer owns normalization. binned=True training
        # has no mapper, so no reference (drift simply stays disabled).
        mapper.ref_counts = feature_bincounts(Xb, mapper.n_bins)

    if eval_set is not None:
        # eval_set binned-ness follows the training data's `binned` flag —
        # never inferred from dtype (raw uint8 features are a real thing).
        Xv, yv = eval_set
        Xv = np.asarray(Xv)
        if binned:
            if Xv.dtype != np.uint8:
                raise TypeError(
                    "training data is pre-binned; eval_set must be uint8 "
                    f"bin indices too, got {Xv.dtype}"
                )
        else:
            Xv = mapper.transform(Xv)
        eval_set = (Xv, np.asarray(yv))

    be = backend if backend is not None else get_backend(cfg)
    driver = Driver(
        be, cfg,
        log_every=log_every,
        checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
        profile=profile,
        run_log=run_log,
        profiler_window=profiler_window,
        status=status,
    )
    ens = driver.fit(
        Xb, np.asarray(y),
        eval_set=eval_set,
        eval_metric=eval_metric,
        early_stopping_rounds=early_stopping_rounds,
        sample_weight=sample_weight,
    )
    if mapper is not None:
        from ddt_tpu.reference.numpy_trainer import _fill_raw_thresholds

        _fill_raw_thresholds(ens, mapper)
    return TrainResult(
        ensemble=ens, mapper=mapper, history=driver.history,
        best_round=driver.best_round, best_score=driver.best_score,
        run_id=getattr(driver, "run_id", None), cfg=cfg,
    )


# Memoised row-mesh scoring backends, one per partition count: an explicit
# mesh bypasses the get_backend instance cache, and rebuilding the backend
# per call would discard its compiled-ensemble device cache (the very
# thing the predict overhaul keeps resident).
_ROW_MESH_BACKENDS: dict[int, DeviceBackend] = {}


def _row_mesh_backend(n_partitions: int) -> DeviceBackend:
    be = _ROW_MESH_BACKENDS.get(n_partitions)
    if be is None:
        from ddt_tpu.parallel.mesh import make_row_mesh

        be = get_backend(
            TrainConfig(backend="tpu", n_partitions=n_partitions),
            mesh=make_row_mesh(n_partitions))
        _ROW_MESH_BACKENDS[n_partitions] = be
    return be


def validate_mapper_model(mapper: BinMapper, ens: TreeEnsemble) -> None:
    """The mapper-vs-model scoring contract, ONE home (api.predict per
    call, ServableModel once per model version): the NaN policy must
    match and the model's categorical columns must have been
    identity-binned by this mapper — both failures silently corrupt
    every affected row otherwise. The categorical edge scan is memoized
    on the mapper (BinMapper.non_identity_columns), so repeat calls are
    O(1) — the "binning prologue rebuilt per call even on cache hit"
    fix (ISSUE 8 satellite)."""
    if mapper.missing_bin != ens.missing_bin:
        # A policy mismatch silently misroutes every NaN row (the
        # reserved bin vs bin 0); same guard as train-time.
        raise ValueError(
            f"mapper.missing_bin={mapper.missing_bin} but the "
            f"ensemble was trained with missing_bin="
            f"{ens.missing_bin}; use the training-time mapper "
            "(api.load_model returns it)"
        )
    if ens.has_cat_splits and ens.cat_features is None:
        # A node list's category SETS are over the bins of the mapper the
        # model's own sets made (lightgbm_io.threshold_bin_mapper).
        asked = set(np.unique(ens.feature[ens.cat_nodes]).tolist())
        lacks = sorted(asked - set(mapper.category_ids or ()))
        if lacks:
            raise ValueError(
                f"the ensemble asks category sets of features {lacks} but "
                "this BinMapper has no category table for them; use the "
                "mapper models/lightgbm_io.threshold_bin_mapper made of "
                "this model")
    elif ens.has_cat_splits:
        # Same loud-failure contract as missing_bin: the model's
        # categorical columns must have been identity-binned by
        # this mapper or every "bin == k" comparison is garbage.
        not_identity = mapper.non_identity_columns(ens.cat_features)
        if not_identity:
            raise ValueError(
                f"the ensemble splits features {not_identity} "
                "categorically but this BinMapper did not "
                "identity-bin them; use the training-time mapper "
                "(api.load_model returns it)"
            )


def predict(
    ens: "TreeEnsemble | ModelBundle",
    X: np.ndarray,
    *,
    binned: bool = False,
    mapper: BinMapper | None = None,
    raw: bool = False,
    backend: DeviceBackend | None = None,
    cfg: TrainConfig | None = None,
    n_partitions: int | None = None,
) -> np.ndarray:
    """Score a batch. Routes through the device gather+compare path when a
    backend is given (or cfg selects one); NumPy otherwise. A ModelBundle
    (api.load_model's return) is accepted directly — its training-time
    mapper is used unless one is passed explicitly. NOTE: the bundle's
    CategoricalEncoder is NOT applied here (this API never sees which
    columns are categorical-raw — api.train's contract is that callers
    encode); X must carry categorical columns already encoded with
    bundle.encoder.transform, exactly as at training time. The CLI predict
    path does that re-encoding itself.

    `n_partitions > 1` makes multi-chip scoring a FLAG: a 1-D row mesh is
    built via parallel.mesh.make_row_mesh and the batch is row-sharded
    over it — trees replicate, each chip traverses its own rows, no
    collectives (the MULTICHIP dryrun's phase-4 path, now public).
    Ignored when an explicit `backend`/`cfg` already selects one.

    An averaged forest (a `NodeListEnsemble` with vector leaves, `loss`
    "mean": `models/sklearn_io.from_sklearn`) answers float32
    `[rows, classes]`, the mean over the trees of the reached leaves'
    vectors, with `raw` or without (it has no link function); its argmax
    is the forest's class. A node list of several classes (`loss`
    "softmax": an XGBoost or LightGBM multiclass model too deep for the
    heap, `models/xgboost_io.from_xgboost_json`) answers float32
    `[rows, classes]` class probabilities, the softmax taken by the
    device's own program (`raw`: the margins); so does an oblivious
    ensemble of vector leaves (CatBoost's `MultiClass`,
    `models/catboost_io.from_catboost_json`)."""
    if n_partitions is not None and n_partitions > 1 \
            and backend is None and cfg is None:
        backend = _row_mesh_backend(n_partitions)
    if isinstance(ens, ModelBundle):
        if mapper is None:
            mapper = ens.mapper
        ens = ens.ensemble
    X = np.asarray(X)
    if not binned:
        if mapper is not None:
            validate_mapper_model(mapper, ens)
            X = mapper.transform(X)
            binned = True
        elif not ens.has_raw_thresholds:
            raise ValueError(
                "predict on raw features needs a mapper or an ensemble with "
                "raw thresholds; or pass binned=True with uint8 bins"
            )
    if backend is None and cfg is not None:
        backend = get_backend(cfg)
    if binned and X.dtype != np.uint8:
        raise TypeError(
            f"binned=True requires uint8 bin indices, got {X.dtype}"
        )
    if backend is not None and binned:
        if not raw and backend.links_on_device(ens):
            # softmax's round-major trees as a node list, or an oblivious
            # ensemble's vector leaves: the program ends in the link, on
            # the device (stage `predict:link`)
            return backend.predict_raw(ens, X, link=True)
        out = backend.predict_raw(ens, X)
        if raw:
            return out
        # Probability transform on HOST numpy (formula-identical to
        # TreeEnsemble.predict): the old device predict_proba round-trip
        # re-uploaded the fetched [R]-sized scores and dispatched a
        # sigmoid per call — pure prologue cost on every served request,
        # visible as a ddt:predict:upload share drop in `report` now
        # that it is gone (ISSUE 8 satellite).
        from ddt_tpu.utils.metrics import predict_proba_np

        return predict_proba_np(out, ens.loss)
    return ens.predict_raw(X, binned=binned) if raw else ens.predict(
        X, binned=binned
    )
