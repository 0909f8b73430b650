"""Zero-retrace servable restore from a registry artifact.

`load_servable(root, ref)` turns a digest-addressed artifact back into
a model the serve engine can publish directly (`ServeEngine(model)` /
`engine.swap(model)`): the integrity-checked read (registry/store.py)
hands over the object directory, model.npz restores the ensemble +
mapper + encoder (its embedded manifest digest-verified on the way in),
and the per-bucket StableHLO blobs deserialize into the scoring
callables — the model is never re-TRACED in this process; each bucket
pays exactly one XLA compile of the shipped program, at load time,
which `make registry-smoke`'s jit_compiles witness pins at zero during
serving.

Fallback ladder (the "same artifact serves on chip or host" contract):

1. requested variant's AOT blobs cover this platform -> RestoredModel
   (zero retrace);
2. quantized serving requested but the lut blobs were lowered for a
   different platform -> rebuild the LUT path from the CARRIED
   quantized tables (lut_tables.npz) through the normal backend — a
   retrace, but the int8 representation and its error bound are the
   exported ones, bit-for-bit;
3. no usable blobs at all (foreign platform, pre-AOT artifact) ->
   plain ServableModel build from model.npz — full prologue, correct
   everywhere.

Every restore emits an `artifact` run-log event (schema v5) carrying
the digest, the mode the ladder chose, and the training run_id — the
provenance join `cli report`'s registry section renders.
"""

from __future__ import annotations

import dataclasses
import logging
import os

import numpy as np

from ddt_tpu.registry.manifest import IntegrityError
from ddt_tpu.registry.store import DIGEST_LEN, Registry, RegistryError
from ddt_tpu.serve.engine import (TIER_IMPL, ServableModel,
                                  default_buckets)

log = logging.getLogger("ddt_tpu.registry.loader")


class RestoredModel(ServableModel):
    """A ServableModel whose dispatch seam is a deserialized AOT
    program per bucket shape — everything above `_invoke` (bucket
    padding, oversize chunking, per-request binning, probability
    transform) is inherited, so restored and freshly-built models obey
    identical shape/semantics contracts."""

    aot = True

    def __init__(self, bundle, manifest: dict, digest: str,
                 fns: dict, operands: tuple, *, quantized: bool,
                 raw: bool, tier: "str | None" = None):
        # Deliberately NOT calling ServableModel.__init__: this model
        # must never touch a backend or re-trace — its build cost was
        # paid in the exporting process.
        self.ens = bundle.ensemble
        self.mapper = bundle.mapper
        self.backend = None
        self.buckets = tuple(sorted(int(b) for b in manifest["buckets"]))
        self.raw = bool(raw)
        self.quantized = bool(quantized)
        self.quantize_tier = tier
        # The tier is PINNED by what was deserialized — there is no
        # backend ladder to consult (ServableModel.predict_impl), and a
        # restored program cannot silently fall anywhere.
        self._impl_override = TIER_IMPL.get(tier, "f32")
        self.compiled = None
        self.tables = None
        self.token = manifest["model_token"]
        self.artifact_digest = digest
        self.max_abs_err = float(
            (manifest.get("quantized") or {}).get("max_abs_err", 0.0)
            if quantized else 0.0)
        self._fns = dict(fns)           # bucket -> jitted Exported.call
        self._ops = tuple(operands)     # device-resident operand arrays

    def _invoke(self, Xb: np.ndarray) -> np.ndarray:
        # score_binned already padded to a manifest bucket, so the
        # lookup cannot miss; each callable is jax.jit(exported.call) —
        # compiled once at warmup, a cache hit forever after.
        return np.asarray(self._fns[Xb.shape[0]](*self._ops, Xb))


@dataclasses.dataclass
class LoadReport:
    """What the restore ladder actually did (surfaced by the CLI and
    asserted by the smoke: 'it worked' is not enough — the smoke needs
    to know it worked WITHOUT retracing)."""

    digest: str
    mode: str            # aot-f32 | aot-lut | tables-fallback | rebuild
    model: ServableModel
    manifest: dict


def _emit_artifact_event(run_log, action: str, digest: str, man: dict,
                         mode: str | None = None) -> None:
    if run_log is None:
        return
    from ddt_tpu.telemetry.events import RunLog

    rl = RunLog.coerce(run_log)
    rl.emit("artifact", action=action, digest=digest,
            kind=man.get("kind"), run_id=man.get("run_id"),
            model_token=(man.get("model_token") or "")[:12] or None,
            mode=mode)


def load_servable(root, ref: str, *, quantize=None,
                  raw: bool = False, backend=None, cfg=None,
                  run_log=None) -> LoadReport:
    """Restore a servable model from registry reference `ref` (digest,
    `name`, `name@version`, or `name@tag`). `quantize=None` follows the
    artifact (quantized exports serve quantized, at the TIER they were
    exported with — int8 or int4); True serves the artifact's exported
    tier; "int8"/"int4" demand that specific tier and refuse a
    mismatched artifact (the carried tables ARE the representation — a
    different grid would make the manifest's error bound a lie).
    `backend`/`cfg` are only consulted when the ladder has to fall back
    to an in-process build — `backend` is a DeviceBackend, or a backend
    NAME (the CLI's --backend) to combine with the model-derived config
    here. File I/O and deserialization all happen HERE, on the caller's
    thread — never inside the engine's dispatch loop (the
    serve-blocking-io contract)."""
    import jax

    from ddt_tpu import api
    from ddt_tpu.export import aot
    from ddt_tpu.serve.engine import normalize_quantize
    from ddt_tpu.telemetry.events import RunLog

    # Coerce ONCE: per-event coercion would restart seq at 0 for every
    # emit and leak a file handle per restore. A log we opened here from
    # a path closes with the restore (`_done`); a caller's RunLog
    # instance stays the caller's to close.
    own_log = isinstance(run_log, str)
    run_log = RunLog.coerce(run_log)

    def _done(report: LoadReport) -> LoadReport:
        if own_log:
            run_log.close()
        return report

    reg = root if isinstance(root, Registry) else Registry(root)
    art_dir, man, digest = reg.get(ref)
    if man.get("kind") != "servable":
        raise RegistryError(
            f"{ref!r} ({digest}) is a {man.get('kind')!r} artifact, not "
            "a servable export")
    # reg.get's verifying read already sha256'd model.npz against the
    # artifact manifest — skip the embedded digest's second full pass.
    bundle = api.load_model(os.path.join(art_dir, aot.MODEL_FILE),
                            verify=False)
    ce = bundle.ensemble.compile(tree_chunk=int(man["tree_chunk"]))
    if ce.token != man["model_token"]:
        raise IntegrityError(
            f"{digest}: model.npz rebuilds to token {ce.token[:12]} but "
            f"the manifest pins {str(man['model_token'])[:12]} — the "
            "model file and the exported programs disagree")
    qmeta = man.get("quantized")
    # Pre-int4 artifacts carry no "tier" key — they are the int8 tier.
    art_tier = (qmeta.get("tier", "int8") if qmeta else None)
    if quantize is None:
        tier = art_tier                  # follow the artifact
    elif quantize is True:
        # "serve quantized, whatever tier was exported" — an
        # unquantized artifact still fails loudly below.
        tier = art_tier or "int8"
    else:
        tier = normalize_quantize(quantize)
    if tier and qmeta is None:
        raise ValueError(
            f"{ref!r} was exported without the quantized variant; "
            f"re-push with --quantize={tier} to serve the LUT path")
    if tier and tier != art_tier:
        raise RegistryError(
            f"{ref!r} carries the {art_tier!r} quantized tier but "
            f"{tier!r} was requested — the carried tables are the "
            f"representation that serves; re-push with "
            f"--quantize={tier}")

    platform = jax.default_backend()
    buckets = tuple(sorted(int(b) for b in man["buckets"]))
    variant, blob_tpl = {
        None: ("aot-f32", aot.F32_BLOB),
        "int8": ("aot-lut", aot.LUT_BLOB),
        "int4": ("aot-lut4", aot.LUT4_BLOB),
    }[tier]
    covered = man.get("lut_platforms" if tier else "platforms") or []

    if platform in covered:
        if tier == "int4":
            tables = _load_tables(art_dir, man)
            host_ops = tables.pack_int4().ops
        elif tier:
            tables = _load_tables(art_dir, man)
            from ddt_tpu.ops.predict_lut import lut_device_operands

            host_ops = lut_device_operands(tables)
        else:
            host_ops = ce.arrays()
        import jax.numpy as jnp

        operands = tuple(jnp.asarray(a) for a in host_ops)
        fns = {}
        for b in buckets:
            path = os.path.join(art_dir, aot.AOT_DIR,
                                blob_tpl.format(bucket=b))
            with open(path, "rb") as f:
                exp = aot.deserialize_blob(f.read())
            fns[b] = jax.jit(exp.call)
        model = RestoredModel(bundle, man, digest, fns, operands,
                              quantized=tier is not None, raw=raw,
                              tier=tier)
        _emit_artifact_event(run_log, "load", digest, man, mode=variant)
        log.info("restored %s from %s (%s, buckets %s, zero retrace)",
                 man["model_token"][:12], digest, variant, list(buckets))
        return _done(LoadReport(digest=digest, mode=variant, model=model,
                                manifest=man))

    # ---- fallback: the artifact is still fully servable, just not
    # zero-retrace on this platform ------------------------------------
    mode = "tables-fallback" if tier else "rebuild"
    log.warning(
        "artifact %s carries no %s AOT program for platform %r "
        "(covered: %s); rebuilding the scoring path in-process", digest,
        variant, platform, covered or "none")
    be = None if isinstance(backend, str) else backend
    if be is None:
        from ddt_tpu.backends import get_backend
        from ddt_tpu.config import TrainConfig

        if cfg is None:
            cfg = TrainConfig(
                backend=backend if isinstance(backend, str) else "tpu",
                loss=bundle.ensemble.loss,
                n_classes=max(bundle.ensemble.n_classes, 2),
                predict_impl=TIER_IMPL.get(tier, "auto"))
        be = get_backend(cfg)
    # tables-fallback serves the CARRIED quantized representation
    # (token-pinned), not a re-quantization — the manifest's error
    # bound keeps describing what actually serves even across version
    # skew.
    model = ServableModel(bundle, be, quantize=tier,
                          buckets=buckets, raw=raw,
                          tables=_load_tables(art_dir, man)
                          if tier else None)
    model.artifact_digest = digest
    _emit_artifact_event(run_log, "load", digest, man, mode=mode)
    return _done(LoadReport(digest=digest, mode=mode, model=model,
                            manifest=man))


def _load_tables(art_dir: str, man: dict):
    """The carried quantized tables (lut_tables.npz), token-checked
    against the manifest. Registry.get's verifying read has already
    proven the file exists and matches its manifest hash — a pruned or
    torn file raises IntegrityError upstream, never reaches here."""
    from ddt_tpu.export import aot

    path = os.path.join(art_dir, aot.LUT_TABLES_FILE)
    with np.load(path) as z:
        tables = aot.tables_from_arrays(dict(z))
    if tables.token != man["model_token"]:
        raise IntegrityError(
            f"{path}: quantized tables carry token "
            f"{tables.token[:12]} but the manifest pins "
            f"{str(man['model_token'])[:12]}")
    return tables


def push_servable(root, bundle, *, name: str | None = None,
                  max_batch: int = 256, quantize=False,
                  raw: bool = False, tree_chunk: int = 64,
                  run_id: str | None = None, tag: str | None = None,
                  run_log=None) -> dict:
    """Export + publish in one call (the `cli registry push` body and
    the tests' entry): stage a servable artifact for the engine's
    power-of-two bucket ladder up to `max_batch`, then push it.
    `quantize` is the tier (False | True/"int8" | "int4" — see
    aot.stage_servable). Returns the store's {digest, name, version}."""
    from ddt_tpu.export import aot
    from ddt_tpu.telemetry.events import RunLog

    if tag is not None and name is None:
        raise RegistryError(
            "a tag needs a name to live under (tags are rows of the "
            "name index); pass name= alongside tag=")
    reg = root if isinstance(root, Registry) else Registry(root)
    stage = reg.stage()
    try:
        aot.stage_servable(
            stage, bundle, buckets=default_buckets(max_batch),
            quantize=quantize, raw=raw, tree_chunk=tree_chunk,
            run_id=run_id)
        # stage_servable hashed every file into the manifest moments
        # ago in this process — skip the verifying re-read's second
        # full sha256 pass.
        return reg.push(stage, name, tag=tag,
                        run_log=RunLog.coerce(run_log),
                        verify_files=False)
    except BaseException:
        import shutil

        shutil.rmtree(stage, ignore_errors=True)
        raise


def short_digest(digest: str) -> str:
    return digest[:DIGEST_LEN]
