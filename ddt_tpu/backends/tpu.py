"""TPUDevice: the JAX/XLA execution backend (the north-star deliverable).

Realises [BASELINE]: "the per-feature HistogramBuilder and SplitGain kernels
are re-expressed as jax.vmap'd XLA ops, and the cross-partition histogram
allreduce that today runs over the FPGA network fabric becomes jax.lax.psum
over TPU ICI. The host-side Driver/DeviceBackend abstraction gains a TPUDevice
implementation alongside FPGADevice."

Design, TPU-first (SURVEY.md §1 L2–L4):

- **One dispatch per tree.** `grow_tree` jit-compiles the whole level-unrolled
  growth program (ops/grow.py) once per (shape, config) and reuses it for all
  trees; only ~KBs of node arrays cross the host boundary per tree. The
  reference's per-kernel host↔device calling convention would serialise
  6 × depth × trees dispatch latencies — fused instead.
- **Distribution = mesh axis, not message passing.** With n_partitions > 1 the
  backend builds a 1-D `jax.sharding.Mesh` over axis "rows", row-shards the
  binned matrix/labels/boosting state with NamedSharding, and traces the same
  growth program under `jax.shard_map` with axis_name="rows" — the histogram
  allreduce appears as `jax.lax.psum` riding ICI. Tree arrays come out
  replicated (every shard deterministically grows the identical tree); the
  per-row state stays sharded and never moves.
- **Static shapes.** Rows are padded to a multiple of the partition count;
  padded rows are masked out of gradients (g = h = 0) so they contribute to
  no histogram, no leaf sum, and no loss.

This class runs unmodified on CPU XLA (tests use an 8-virtual-device CPU
mesh — SURVEY.md §4 "Distributed without a cluster") and on real TPU; "tpu"
names the design target, and the flag surface matches the reference's
fpga/tpu selection [BASELINE].
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ddt_tpu.backends.base import DeviceBackend, HostTree
from ddt_tpu.config import TrainConfig
from ddt_tpu.models.tree import TreeEnsemble
from ddt_tpu.ops import grad as grad_ops
from ddt_tpu.ops import grow as grow_ops
from ddt_tpu.ops import histogram as hist_ops
from ddt_tpu.ops import predict as predict_ops
from ddt_tpu.ops import split as split_ops
from ddt_tpu.parallel import comms as comms_lib
from ddt_tpu.parallel import mesh as mesh_lib
from ddt_tpu.robustness import emit_fault, faultplan
from ddt_tpu.telemetry import counters as tele_counters
from ddt_tpu.telemetry.annotations import (note_root, phase_span,
                                           stage_program)
from ddt_tpu.telemetry.costmodel import costed
from ddt_tpu.utils import device
from ddt_tpu.utils import retry as retry_lib

log = logging.getLogger("ddt_tpu.backends.tpu")

# Mesh axis names are OWNED by parallel/mesh.py (the ddtlint
# axis-name-literal contract): the backend aliases the constants, never
# the strings, so a rename there cannot silently desynchronize here.
AXIS = mesh_lib.ROWS_AXIS       # data-parallel axis (SURVEY.md §2)
FAXIS = mesh_lib.FEATURES_AXIS  # optional TP-analog column axis
HAXIS = mesh_lib.HOSTS_AXIS  # cross-slice DCN axis (SURVEY.md §5
#   "Distributed comm backend"): row shards span (hosts, rows); the
#   histogram allreduce becomes psum over BOTH axes, which XLA phases as
#   an ICI-local reduce followed by a DCN allreduce.


def _axis_allreduce(axis):
    """Collective-or-identity reducer over `axis` (None = single shard):
    (x, op) with op in sum|min|max — the ONE home of the psum/pmin/pmax
    dispatch the metric twins and loss reductions share (collectives
    themselves spelled in parallel/comms.py, the one-home module)."""
    def allreduce(x, op="sum"):
        return {"sum": comms_lib.psum, "min": comms_lib.pmin,
                "max": comms_lib.pmax}[op](x, axis)

    return allreduce


def _local_row_offset(axis, rows_axis_size: int, n_local: int):
    """This shard's first row within the padded global batch — the
    flattened (hosts, rows) shard index times the local row count; the
    global-row-id base every in-trace bagging hash derives from (ONE
    home: fused grow_rounds and the streamed ops must agree bit-for-bit
    with the host twin's ids). `rows_axis_size` is the "rows" axis
    extent (needed to flatten the 2-axis case; ignored otherwise)."""
    if axis is None:
        return jnp.int32(0)
    if isinstance(axis, tuple):
        idx = (jax.lax.axis_index(axis[0]) * rows_axis_size
               + jax.lax.axis_index(axis[1]))
    else:
        idx = jax.lax.axis_index(axis)
    return (idx * n_local).astype(jnp.int32)


def _pack_tree(tree) -> "jax.Array":
    """Stack a grown tree's node arrays into one [6, N] f32 array (single
    device→host fetch; int32/bool values are exact in f32)."""
    return jnp.stack([
        tree.feature.astype(jnp.float32),
        tree.threshold_bin.astype(jnp.float32),
        tree.is_leaf.astype(jnp.float32),
        tree.leaf_value,
        tree.split_gain,
        tree.default_left.astype(jnp.float32),
    ])


class LabelHandle(NamedTuple):
    """Labels + per-row WEIGHT mask, row-sharded — the opaque `y` handle
    the Driver threads through grad_hess/loss_value. `valid` is float32:
    0 on pad rows, the instance weight elsewhere (1.0 without
    sample_weight) — one mask multiplication weights gradients, hessians,
    loss numerators AND the loss denominator (weighted means) everywhere,
    granular and fused paths alike. Per-dataset state lives here, NOT on
    the backend instance (instances are cached and shared)."""

    y: jax.Array
    valid: jax.Array


#: Where compiled programs are kept when the environment names no place:
#: `<checkout>/.jax_cache`, from this file's own path — the SAME path on
#: every run of one checkout (the directory is part of the cache's key,
#: so one that moves never hits). Git-ignored.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_compile_cache() -> None:
    """Keep compiled programs across processes. Compiling the fused grow
    program costs seconds and the 1000-tree scoring program about a
    minute; the cache makes every process after the first skip it.

    Where $JAX_COMPILATION_CACHE_DIR is set JAX has already read it into
    `jax_compilation_cache_dir` and nothing is set here; so too when a
    directory was configured in code. Otherwise the cache is
    DEFAULT_COMPILE_CACHE_DIR.

    Mutates process-global JAX config, so the LIBRARY never calls it
    implicitly: our own entry points (cli, benchmark/run.py,
    chip_smoke, __graft_entry__) do, and embedders opt in by calling it or by
    setting the variable."""
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)


class TPUDevice(DeviceBackend):
    """XLA backend; single-chip or row-sharded over a device mesh."""

    name = "tpu"

    def __init__(
        self,
        cfg: TrainConfig,
        devices: list | None = None,
        mesh: jax.sharding.Mesh | None = None,
    ):
        super().__init__(cfg)
        self.n_partitions = max(1, cfg.n_partitions)
        self.feature_partitions = max(1, cfg.feature_partitions)
        self.host_partitions = max(1, cfg.host_partitions)
        if mesh is not None:
            self.mesh = mesh
            names = mesh.axis_names
            self.feature_partitions = (
                mesh.shape[FAXIS] if FAXIS in names else 1)
            self.host_partitions = mesh.shape[HAXIS] if HAXIS in names else 1
            self.n_partitions = mesh.devices.size // (
                self.feature_partitions * self.host_partitions)
        elif (self.n_partitions > 1 or self.feature_partitions > 1
              or self.host_partitions > 1):
            # Declarative 2D (rows x features) mesh — ONE constructor
            # (parallel/mesh.make_mesh_2d): hosts outermost (DCN,
            # slowest), rows middle, features innermost (ICI-adjacent) —
            # the feature winner gather per level is latency-sensitive;
            # the hosts hop happens once per reduction.
            self.mesh = mesh_lib.make_mesh_2d(
                self.n_partitions, self.feature_partitions,
                n_hosts=self.host_partitions, devices=devices)
        else:
            self.mesh = None
        self.distributed = self.mesh is not None
        # Row shards span (hosts x rows); every row-dimension sharding spec
        # and row-axis psum uses this (a tuple axis entry when the pod axis
        # exists, the plain "rows" name otherwise).
        self.row_shards = self.host_partitions * self.n_partitions
        self._row_axes = (
            (HAXIS, AXIS) if self.host_partitions > 1 else AXIS)
        # The declarative operand->PartitionSpec layout (parallel/mesh.
        # SpecLayout + match_partition_rules): every shard_map below
        # resolves its in/out specs through this table by operand name,
        # so the mesh's axis story lives in ONE rule table.
        self.layout = mesh_lib.SpecLayout(
            row_axes=self._row_axes if self.distributed else None,
            feature_axis=FAXIS if self.feature_partitions > 1 else None)
        self._input_dtype = jnp.dtype(cfg.matmul_input_dtype)
        # Split-finding comms, resolved ONCE at backend construction so
        # every program this backend builds — fused, granular, streamed —
        # and the telemetry payload model all read the same answer.
        # Reduce-scatter now COMPOSES with a sharded feature axis (the
        # scatter runs over the row axes within each feature slab), so
        # the resolver keys on whether a ROW wire exists.
        self.split_comms = comms_lib.resolve_split_comms(
            cfg.split_comms, distributed=self.distributed,
            feature_partitions=self.feature_partitions,
            row_shards=self.row_shards)
        # Host-FETCH histogram surfaces (the granular build_histograms
        # and the streamed hist ops) return the table to the host; under
        # reduce_scatter that output is row-sharded, which a
        # multi-process mesh cannot np.asarray (shards span other
        # processes' devices). Those surfaces therefore fall back to
        # allreduce on multi-process meshes — the fused in-trace path
        # keeps the scatter (its histograms never leave the program).
        self.stream_hist_comms = (
            self.split_comms if jax.process_count() == 1 else "allreduce")
        self.comms_slabs = comms_lib.resolve_comms_slabs(
            cfg.hist_comms_slabs, distributed=self.distributed)
        # Quantized-gradient training (cfg.grad_dtype; ops/grad.py): one
        # resolved bool every program builder below reads — the grow
        # programs quantize in-trace, the streamed ops take per-round
        # scales, and the byte models report the integer path.
        self._grad_quant = cfg.grad_dtype != "f32"
        # Sticky position on the histogram OOM-degradation ladder
        # (build_histograms below): 0 = the configured impl.
        self._hist_degrade = 0

    def collective_bytes_per_tree(self, n_features: int,
                                  streamed: bool = False) -> int:
        """Effective per-tree histogram-collective payload estimate for
        THIS backend's resolved comms configuration (mode, wire dtype,
        sibling subtraction) — the one home the Driver and the streaming
        trainers record into `hist_allreduce_bytes` (telemetry.counters
        documents the model). `streamed=True` reads the host-fetch
        surfaces' mode (stream_hist_comms — allreduce on multi-process
        meshes). Zero on single-device backends."""
        if not self.distributed:
            return 0
        from ddt_tpu.ops.grow import resolve_hist_subtraction

        return tele_counters.hist_allreduce_bytes(
            self.cfg.max_depth, n_features, self.cfg.n_bins,
            partitions=self.row_shards,
            feature_partitions=self.feature_partitions,
            mode=self.stream_hist_comms if streamed else self.split_comms,
            comms_dtype=self.cfg.hist_comms_dtype,
            subtraction=resolve_hist_subtraction(
                self.cfg.hist_subtraction, integer_hists=self._grad_quant),
            grad_dtype=self.cfg.grad_dtype,
        )

    # ------------------------------------------------------------------ #
    # sharding helpers
    # ------------------------------------------------------------------ #

    def _row_sharding(self, extra_dims: int = 0):
        """NamedSharding for a row-sharded [R, ...] operand, resolved
        through the declarative layout (row_vector / row_matrix — the
        ddtlint handbuilt-partition-spec contract: the backend never
        hand-builds a PartitionSpec). Trailing dims past the spec are
        replicated by PartitionSpec semantics."""
        lay = self.layout
        return self._named(
            lay.row_vector() if extra_dims == 0 else lay.row_matrix())

    def _named(self, spec):
        """NamedSharding from a SpecLayout-resolved PartitionSpec (None
        on single-device backends — device_put picks the default)."""
        if not self.distributed:
            return None
        return jax.sharding.NamedSharding(self.mesh, spec)

    @staticmethod
    def _put(a: np.ndarray, sh) -> jax.Array:
        """device_put that also works on a MULTI-PROCESS mesh: device_put
        cannot place shards on devices this process does not own, so when
        the sharding spans other processes' devices each process
        materialises its addressable shards from the (identical-everywhere)
        global host array via the sharding's index map. Single-process
        meshes keep the plain device_put fast path."""
        # Telemetry: every host->device transfer funnels through here —
        # ONE integer add per upload feeds the run log's h2d counter
        # (telemetry.counters; no device interaction, ~ns).
        tele_counters.record_h2d(a.nbytes)
        if sh is None:
            return jax.device_put(a)
        if not sh.is_fully_addressable:
            return jax.make_array_from_callback(
                a.shape, sh, lambda idx: a[idx])
        return jax.device_put(a, sh)

    def _pad_rows(self, a: np.ndarray) -> np.ndarray:
        """Pad axis 0 to a multiple of the (hosts x rows) shard count."""
        R = a.shape[0]
        Rp = -(-R // self.row_shards) * self.row_shards
        if Rp == R:
            return a
        pad = [(0, Rp - R)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, pad)

    def _put_rows(self, a: np.ndarray, extra_dims: int = 0) -> jax.Array:
        a = self._pad_rows(np.ascontiguousarray(a))
        return self._put(a, self._row_sharding(extra_dims))

    # ------------------------------------------------------------------ #
    # data plane
    # ------------------------------------------------------------------ #

    def upload(self, Xb: np.ndarray) -> jax.Array:
        if Xb.dtype != np.uint8:
            raise TypeError(f"binned data must be uint8, got {Xb.dtype}")
        R = Xb.shape[0]
        if self.feature_partitions > 1:
            # Column-shard over the feature axis (pad F to a multiple; padded
            # columns are all-zeros => their best gain is exactly 0 with an
            # empty right child, so they are never chosen as splits).
            F = Xb.shape[1]
            Fp = -(-F // self.feature_partitions) * self.feature_partitions
            if Fp != F:
                Xb = np.pad(Xb, ((0, 0), (0, Fp - F)))
            Xp = self._pad_rows(np.ascontiguousarray(Xb))
            data = self._put(Xp, self._named(self.layout.binned_data()))
        else:
            data = self._put_rows(Xb, extra_dims=1)
        return data

    def upload_row_shards(self, parts: list, total_rows: int) -> jax.Array:
        """Host-sharded chunk upload (ROADMAP item 2's ingest half):
        assemble a row-sharded [R, F] uint8 device array from THIS
        process's contiguous row block — `parts` are the sub-shards this
        process owns (data.chunks.HostShardedChunks), in global order;
        other processes' rows are NEVER materialized on this host.

        Single-process meshes (where every sub-shard is local) simply
        concatenate and take the normal padded upload — identical device
        layout, so the two paths are interchangeable per process count.
        Multi-process meshes use jax.make_array_from_process_local_data:
        each process contributes exactly its addressable devices' rows,
        replacing the single-controller make_array_from_callback that
        forced every host to hold the full global chunk. Row padding (to
        the shard count) lands in the LAST process's block, matching
        _pad_rows' global layout; uneven blocks raise — the chunk writer
        cuts uniform sub-shards (shard_arrays / shard_stress_chunks)."""
        local = (np.ascontiguousarray(np.concatenate(parts))
                 if len(parts) > 1 else np.ascontiguousarray(parts[0]))
        if local.dtype != np.uint8:
            raise TypeError(
                f"binned data must be uint8, got {local.dtype}")
        if not self.distributed or jax.process_count() == 1:
            return self.upload(local)
        if self.feature_partitions > 1:
            # The streamed path is row-parallel only (the stream ops
            # raise too); saying so HERE keeps the multi-process branch
            # from silently skipping upload()'s feature-axis column
            # padding if that contract ever loosens.
            raise NotImplementedError(
                "host-sharded uploads are row-parallel only; "
                "feature_partitions > 1 does not stream")
        n_proc = jax.process_count()
        Rp = -(-total_rows // self.row_shards) * self.row_shards
        if Rp % n_proc:
            raise ValueError(
                f"padded rows {Rp} do not split over {n_proc} processes")
        block = Rp // n_proc
        pad = block - local.shape[0]
        if pad < 0 or (pad > 0 and jax.process_index() != n_proc - 1):
            raise ValueError(
                f"process {jax.process_index()} holds {local.shape[0]} "
                f"rows but its block is {block}; host-sharded chunks "
                "need uniform sub-shard sizes (re-cut the shards)")
        if pad:
            local = np.pad(local, ((0, pad), (0, 0)))
        tele_counters.record_h2d(local.nbytes)
        sh = self._named(self.layout.binned_data())
        return jax.make_array_from_process_local_data(
            sh, local, (Rp, local.shape[1]))

    def upload_labels(self, y: np.ndarray,
                      sample_weight: np.ndarray | None = None
                      ) -> "LabelHandle":
        # The pad-row weight mask travels WITH the labels (not on the
        # backend instance): backend instances are cached and shared across
        # fits, so per-dataset state must live in the opaque handles the
        # Driver threads through grad_hess/loss_value.
        y = np.asarray(y)
        valid = np.zeros(self._pad_rows(y).shape[0], np.float32)
        valid[: y.shape[0]] = (
            1.0 if sample_weight is None
            else np.asarray(sample_weight, np.float32))
        return LabelHandle(self._put_rows(y), self._put_rows(valid))

    # ------------------------------------------------------------------ #
    # granular L3 kernels (parity surface)
    # ------------------------------------------------------------------ #

    @functools.cached_property
    def _hist_fns(self) -> dict:
        # (impl, row_chunk) -> dispatcher; one entry per degrade-ladder
        # step actually reached (almost always just the first).
        return {}

    def _hist_fn_for(self, impl: str, row_chunk: int):
        key = (impl, row_chunk)
        fn = self._hist_fns.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg

        if self.feature_partitions > 1:
            def unsupported(*a, **k):
                raise NotImplementedError(
                    "the granular build_histograms surface is row-parallel "
                    "only; feature_partitions > 1 is handled inside "
                    "grow_tree (the Driver path)"
                )
            self._hist_fns[key] = unsupported
            return unsupported

        rax = self._row_axes
        rs = self.stream_hist_comms == "reduce_scatter"

        def hist(Xb, g, h, node_index, *, n_nodes):
            # impl resolution happens inside build_histograms with the full
            # shape (pallas only when its VMEM working set fits).
            out = hist_ops.build_histograms(
                Xb, g, h, node_index, n_nodes, cfg.n_bins,
                impl=impl, row_chunk=row_chunk,
                input_dtype=self._input_dtype,
            )
            if self.distributed:
                # The fabric-allreduce analog (parallel/comms.py); over
                # (hosts, rows) XLA phases it ICI-reduce first, then the
                # cross-slice DCN hop. Under split_comms=reduce_scatter
                # each shard keeps only its merged F/P slab on device —
                # the host reassembles the full table from the sharded
                # output at D2H time, so the WIRE pays the scatter cost
                # while the caller contract is unchanged.
                if rs:
                    out = comms_lib.pad_to_multiple(out, 1, self.row_shards)
                out = comms_lib.hist_reduce(
                    out, rax,
                    mode="reduce_scatter" if rs else "allreduce",
                    comms_dtype=cfg.hist_comms_dtype, scatter_dim=1)
            return out

        if self.distributed:
            lay = self.layout

            def sharded(Xb, g, h, node_index, *, n_nodes):
                out_specs = (lay.level_hist_scattered() if rs
                             else lay.replicated())
                f = jax.shard_map(
                    functools.partial(hist, n_nodes=n_nodes),
                    mesh=self.mesh,
                    in_specs=lay.specs("data", "grad", "hess",
                                       "node_index"),
                    out_specs=out_specs,
                )
                out = f(Xb, g, h, node_index)
                if rs and out.shape[1] != Xb.shape[1]:
                    out = out[:, :Xb.shape[1]]   # drop scatter pad columns
                return out
            self._hist_fns[key] = sharded
            return sharded
        self._hist_fns[key] = hist
        return hist

    # Graceful-degradation ladder for the granular/streamed histogram
    # surface (docs/ROBUSTNESS.md): a RESOURCE_EXHAUSTED from the
    # resolved impl (the Pallas VMEM kernel pins its working set; a
    # config past the budget predicate's model can still OOM on a busy
    # chip) steps DOWN — matmul at the default row chunk, matmul at a
    # small row chunk (a quarter of the one-hot working set), finally
    # the scatter path — instead of discarding the run. The step is
    # STICKY per backend instance (the same shape would OOM again) and
    # each step emits a fault event + the hist_oom_degrades counter.
    _HIST_DEGRADE_ROW_CHUNK = 8192

    @functools.cached_property
    def _hist_ladder(self) -> list:
        default_rc = 32_768
        ladder = [(self.cfg.hist_impl, default_rc)]
        for step in (("matmul", default_rc),
                     ("matmul", self._HIST_DEGRADE_ROW_CHUNK),
                     ("segment", default_rc)):
            # Membership (not just last-entry) dedup: hist_impl=
            # "segment" must yield [segment, matmul, matmul@8k], never
            # re-climb to a hungrier impl only to re-try the one that
            # just OOM'd. (segment IS the floor for scatter-friendly
            # platforms, but matmul's bounded row chunks are the only
            # lower-VMEM option left when scatter itself blew up.)
            if step not in ladder:
                ladder.append(step)
        return ladder

    def build_histograms(self, data, g, h, node_index, n_nodes):
        g = g if isinstance(g, jax.Array) else self._put_rows(np.asarray(g))
        h = h if isinstance(h, jax.Array) else self._put_rows(np.asarray(h))
        if not isinstance(node_index, jax.Array):
            node_index = self._put_rows(
                self._pad_rows_index(np.asarray(node_index))
            )
        while True:
            impl, row_chunk = self._hist_ladder[self._hist_degrade]
            try:
                faultplan.inject("hist.build")
                return self._hist_fn_for(impl, row_chunk)(
                    data, g, h, node_index, n_nodes=n_nodes)
            except Exception as e:
                if not faultplan.is_resource_exhausted(e) \
                        or self._hist_degrade + 1 >= len(self._hist_ladder):
                    raise
                self._hist_degrade += 1
                nxt, nxt_rc = self._hist_ladder[self._hist_degrade]
                tele_counters.record_hist_oom_degrade()
                emit_fault("hist_oom_degrade", from_impl=impl,
                           to_impl=nxt, row_chunk=nxt_rc)
                log.warning(
                    "histogram build RESOURCE_EXHAUSTED under impl=%s "
                    "(row_chunk=%d); degrading to impl=%s (row_chunk=%d) "
                    "for the rest of this process: %s",
                    impl, row_chunk, nxt, nxt_rc, str(e)[:200])

    def _pad_rows_index(self, idx: np.ndarray) -> np.ndarray:
        """Pad a node-index vector with -1 (frozen) so pad rows are inert."""
        R = idx.shape[0]
        Rp = -(-R // self.row_shards) * self.row_shards
        if Rp == R:
            return idx
        return np.concatenate(
            [idx, np.full(Rp - R, -1, idx.dtype)]
        )

    def best_splits(self, hist):
        # The granular L4 surface keeps the 3-tuple contract (no missing
        # handling — that lives in the fused grow path with the config flag).
        return split_ops.best_splits(
            jnp.asarray(hist), self.cfg.reg_lambda, self.cfg.min_child_weight
        )[:3]

    # ------------------------------------------------------------------ #
    # fused training ops
    # ------------------------------------------------------------------ #

    def init_pred(self, y, base: float):
        Rp = y.y.shape[0]
        if self.cfg.loss == "softmax":
            z = np.zeros((Rp, self.cfg.n_classes), np.float32)
            sh = self._row_sharding(extra_dims=1)
        else:
            z = np.full(Rp, base, np.float32)
            sh = self._row_sharding()
        return self._put(z, sh)

    def load_pred(self, raw: np.ndarray):
        extra = 1 if raw.ndim == 2 else 0
        return self._put_rows(raw.astype(np.float32), extra_dims=extra)

    @functools.cached_property
    def _grad_fn(self):
        loss = self.cfg.loss

        @jax.jit
        def f(pred, y, valid):
            g, h = grad_ops.grad_hess(pred, y, loss)
            if g.ndim == 2:
                v = valid[:, None]
            else:
                v = valid
            return g * v, h * v  # pad rows contribute nothing anywhere

        return costed("grad", phase="grad")(f)

    def grad_hess(self, pred, y):
        return self._grad_fn(pred, y.y, y.valid)

    @functools.cached_property
    def _grow_fn(self):
        return self._build_grow_fn(with_mask=False)

    @functools.cached_property
    def _grow_masked_fn(self):
        return self._build_grow_fn(with_mask=True)

    def _build_grow_fn(self, with_mask: bool):
        cfg = self.cfg
        axis = self._row_axes if self.distributed else None
        faxis = FAXIS if self.feature_partitions > 1 else None
        quant = self._grad_quant
        # Platform-resolved ONCE at program build (trace-time static) —
        # the fused and granular paths must agree or their bit-exactness
        # contract breaks. Integer hists (quantized grads) subtract
        # exactly, so 'auto' resolves ON regardless of platform there.
        subtract = grow_ops.resolve_hist_subtraction(
            cfg.hist_subtraction, integer_hists=quant)

        def grow_full(Xb, g, h, fmask=None, tid=None):
            tree = grow_ops.grow_tree(
                Xb, g, h,
                max_depth=cfg.max_depth,
                n_bins=cfg.n_bins,
                reg_lambda=cfg.reg_lambda,
                min_child_weight=cfg.min_child_weight,
                min_split_gain=cfg.min_split_gain,
                hist_impl=cfg.hist_impl,   # per-level shape-aware resolution
                input_dtype=self._input_dtype,
                axis_name=axis,
                feature_axis_name=faxis,
                feature_mask=fmask,
                missing_bin=cfg.missing_policy == "learn",
                cat_features=cfg.cat_features,
                hist_subtraction=subtract,
                split_comms=self.split_comms,
                hist_comms_dtype=cfg.hist_comms_dtype,
                comms_slabs=self.comms_slabs,
                grad_dtype=cfg.grad_dtype,
                quant_tree_id=tid,
                quant_seed=cfg.seed,
            )
            delta = grow_ops.tree_predict_delta(tree, cfg.learning_rate)
            # Pack the tiny node arrays into ONE f32 array so the host
            # needs a single device→host fetch per tree (separate
            # np.asarray calls each pay the full transfer round-trip).
            # int32 features/bins and booleans are exact in f32 (values
            # << 2^24).
            packed = _pack_tree(tree)
            return packed, delta

        # One positional jit signature per (mask?, quant?) combination:
        # the quantized programs take the traced tree id (the stochastic-
        # rounding key) as a real operand so tree k+1 never retraces.
        if with_mask and quant:
            grow = grow_full
        elif with_mask:
            def grow(Xb, g, h, fmask):
                return grow_full(Xb, g, h, fmask, None)
        elif quant:
            def grow(Xb, g, h, tid):
                return grow_full(Xb, g, h, None, tid)
        else:
            def grow(Xb, g, h):
                return grow_full(Xb, g, h, None, None)

        if self.distributed:
            lay = self.layout
            in_specs = lay.specs("data", "grad", "hess")
            if with_mask:
                in_specs = in_specs + lay.specs("mask")   # replicated
            if quant:
                in_specs = in_specs + lay.specs("scalar")  # tree id
            grow = jax.shard_map(
                grow,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=(lay.replicated(), lay.row_vector()),
                # Feature-parallel growth replicates every output across the
                # feature axis BIT-IDENTICALLY by construction (split triples
                # come out of an all_gather + argmax every shard computes the
                # same way; node totals/leaf aggregates reduce feature-axis-
                # replicated row vectors with identical programs on every
                # shard; routing values ride a psum).
                # The static VMA checker cannot see through the gathered
                # argmax, so it is disabled for that path — and for
                # reduce-scatter split finding, whose winner combine is
                # the same gathered-argmax shape over the row axes.
                check_vma=(faxis is None
                           and self.split_comms != "reduce_scatter"),
            )
        # Cost observatory registration: on telemetry runs the first call
        # per shape pulls XLA's cost/memory analysis for the whole
        # per-tree growth program (telemetry/costmodel.py); inert wrapper
        # otherwise.
        return costed("grow", phase="grow")(jax.jit(grow))

    def grow_tree(self, data, g, h,
                  feature_mask=None, tree_id: int = 0) -> tuple[Any, Any]:
        """Returns (device packed-tree handle, delta) — no host sync here;
        the Driver resolves the handle via fetch_tree one round later.
        `tree_id` (absolute tree index) keys the quantized-gradient
        stochastic rounding when cfg.grad_dtype != 'f32' — a traced
        operand, so every round shares one compiled program."""
        tid = (np.int32(tree_id),) if self._grad_quant else ()
        if feature_mask is None:
            return self._grow_fn(data, g, h, *tid)
        # Pad the host mask to the (padded, global) feature count; padded
        # columns stay masked out.
        Fg = data.shape[1]
        m = np.zeros(Fg, bool)
        m[: feature_mask.shape[0]] = feature_mask
        return self._grow_masked_fn(data, g, h, jax.device_put(m), *tid)

    def sync(self, x) -> None:
        device.device_sync(x)

    def device_stamp(self) -> dict:
        return device.device_stamp()

    @property
    def host_index(self) -> int:
        """This process's index in the pod (0 single-process) — stamped
        into run manifests so cross-host log merges (telemetry.merge)
        can label lanes."""
        return int(jax.process_index())

    def partition_ready_ms(self, handle) -> "list | None":
        """Per-device completion times of a dispatched output handle —
        [(device_id, perf_counter time)], the flight recorder's probe
        (telemetry.events.PartitionRecorder rides this; the probe is a
        barrier on the handle, so it runs only on mesh runs WITH a run
        log attached)."""
        return mesh_lib.shard_ready_times(handle)

    # Compiled callables and caches that close over self.mesh — every
    # entry must be dropped when the mesh changes (rotate_row_partitions)
    # or a stale program would keep placing shards on the old devices.
    _MESH_BOUND_CACHES = (
        "_hist_fns", "_grow_fn", "_grow_masked_fn", "_grad_fn",
        "_rounds_fns", "_rounds_masked_fns", "_rounds_eval_fns",
        "_eval_fns", "_stream_cache", "_apply_fn", "_row_mask_fn",
        "_loss_fn", "_predict_cache",
    )

    def rotate_row_partitions(self) -> bool:
        """Static row re-partitioning, rotation form (the straggler
        watchdog's action — docs/ROBUSTNESS.md): rebuild the mesh with
        the device order rotated by one, so each row shard moves to the
        next physical device. Shard CONTENTS are untouched — same global
        padded row layout, same psum structure — so the trained model is
        unchanged by construction; what moves is which device does which
        shard's work (the right response to a slow device; a no-op for
        pure data skew). Costs a recompile of every mesh-bound program
        plus the caller's reshard of live handles (reshard_rows) — why
        the Driver only triggers it at checkpoint boundaries. Returns
        False (and does nothing) on single-device backends and
        multi-process meshes (rotating a pod's global device list needs
        every process to agree; that is ROADMAP item 3's elastic
        rework)."""
        if not self.distributed or jax.process_count() > 1:
            return False
        # Rotate along the ROW axis of the device grid, feature (and
        # host) coordinates preserved: on a 2D (rows x features) mesh a
        # flat-list rotation would move devices ACROSS feature columns
        # — scrambling which device owns which column slab and forcing
        # an F-axis reshuffle of the data itself. Rolling the rows axis
        # moves every row shard to the next device IN ITS COLUMN, which
        # degenerates to the classic flat rotation on a pure row mesh.
        grid = self.mesh.devices
        rows_ax = list(self.mesh.axis_names).index(AXIS)
        rotated = np.roll(grid, 1, axis=rows_ax)
        # Mesh(ndarray) — NOT jax.make_mesh: make_mesh routes through
        # mesh_utils.create_device_mesh, whose TPU branch rebuilds the
        # order from physical torus coordinates of the device SET and
        # silently discards the rotation (the CPU branch preserves it,
        # which is why only a chip run would have noticed). The explicit
        # ndarray constructor keeps the caller's order everywhere.
        self.mesh = jax.sharding.Mesh(rotated, self.mesh.axis_names)
        for attr in self._MESH_BOUND_CACHES:
            self.__dict__.pop(attr, None)
        log.info("rotated row partitions: shard 0 now on device %s",
                 rotated.flat[0].id)
        return True

    def reshard_rows(self, handle, extra_dims: int = 0):
        """Move a live row-sharded handle onto the CURRENT mesh (after
        rotate_row_partitions) — a device-to-device copy, values
        untouched."""
        if handle is None or not self.distributed:
            return handle
        return jax.device_put(handle, self._row_sharding(extra_dims))

    def reshard_data(self, handle):
        """reshard_rows for the binned data handle: the 2D layout's
        COLUMN sharding is preserved (a plain row reshard would
        silently replicate every feature slab)."""
        if handle is None or not self.distributed:
            return handle
        return jax.device_put(handle, self._named(self.layout.binned_data()))

    # ------------------------------------------------------------------ #
    # fused multi-round training: a whole block of boosting rounds in ONE
    # device dispatch (lax.scan over rounds): three host calls per round
    # collapse to one dispatch + ONE tree fetch per block. Colsample masks
    # ride the
    # scan as xs; bagging masks are recomputed in-scan from the stateless
    # counter hash (ops/sampling); eval rides via grow_rounds_eval. Only
    # profiling and the bagging+eval combination fall back to the
    # granular path (driver.py fit()).
    # ------------------------------------------------------------------ #

    def grow_rounds(self, data, pred, y: "LabelHandle", n_rounds: int,
                    first_round: int = 0):
        """Run `n_rounds` boosting rounds on device. Returns device handles
        (packed_trees [n_rounds, C, 5, n_nodes] f32, new_pred,
        losses [n_rounds] f32 — loss AFTER each round, matching
        loss_value's semantics). With cfg.subsample < 1, bagging row
        masks are recomputed IN-SCAN from the counter-based hash of
        (cfg.seed, first_round + k, global row id) — ops/sampling — so
        `first_round` (the absolute round index of the block's first
        round) is part of the program's inputs, not its cache key."""
        fn = self._rounds_fns.get(n_rounds)
        if fn is None:
            fn = self._build_rounds_fn(n_rounds)
            self._rounds_fns[n_rounds] = fn
        args = (data, pred, y.y, y.valid)
        if self.cfg.subsample < 1.0 or self._grad_quant:
            args = args + (np.int32(first_round),)
        return fn(*args)

    @staticmethod
    def _pad_fmasks(data, fmasks: np.ndarray) -> np.ndarray:
        """Pad host [K, C, F] colsample masks to the GLOBAL (padded)
        column count; padded columns stay masked out."""
        K, C, F = fmasks.shape
        Fg = data.shape[1]          # jax.Array shape is GLOBAL (padded)
        m = np.zeros((K, C, Fg), bool)
        m[..., :F] = fmasks
        return m

    def grow_rounds_masked(self, data, pred, y: "LabelHandle",
                           n_rounds: int, fmasks: np.ndarray,
                           first_round: int = 0):
        """grow_rounds with per-round/per-class colsample feature masks
        riding the scan as xs: `fmasks` is host bool [n_rounds, C, F]
        (KBs). Composes with in-scan bagging (see grow_rounds)."""
        m = self._pad_fmasks(data, fmasks)
        fn = self._rounds_masked_fns.get(n_rounds)
        if fn is None:
            fn = self._build_rounds_fn(n_rounds, masked=True)
            self._rounds_masked_fns[n_rounds] = fn
        args = (data, pred, y.y, y.valid, m)
        if self.cfg.subsample < 1.0 or self._grad_quant:
            args = args + (np.int32(first_round),)
        return fn(*args)

    @functools.cached_property
    def _rounds_masked_fns(self) -> dict:
        return {}

    def grow_rounds_eval(self, data, pred, y: "LabelHandle", n_rounds: int,
                         val_data, val_pred, val_y: "LabelHandle",
                         metric: str, first_round: int = 0,
                         fmasks: "np.ndarray | None" = None):
        """grow_rounds with validation scoring INSIDE the scan: each
        round's trees are applied to the resident validation predictions
        and the metric's f32 device twin evaluates per round — eval runs
        at fused-dispatch speed (no per-round host round-trips; one [K]
        scores fetch per block). Metric must have a device twin — every
        shipped valid metric/loss combination has one since round 5's
        binned-rank auc (softmax-auc is rejected at fit; a future
        twin-less metric would ride the granular path). Composes with
        colsample (`fmasks`, riding the scan as xs) and bagging
        (in-scan counter masks keyed by first_round — see grow_rounds).
        Returns (packed_trees, new_pred, losses, new_val_pred,
        scores [n_rounds] f32)."""
        key = (n_rounds, metric, fmasks is not None)
        fn = self._rounds_eval_fns.get(key)
        if fn is None:
            fn = self._build_rounds_fn(n_rounds, eval_metric=metric,
                                       masked=fmasks is not None)
            self._rounds_eval_fns[key] = fn
        args = (data, pred, y.y, y.valid,
                val_data, val_pred, val_y.y, val_y.valid)
        if fmasks is not None:
            args = args + (self._pad_fmasks(data, fmasks),)
        if self.cfg.subsample < 1.0 or self._grad_quant:
            args = args + (np.int32(first_round),)
        return fn(*args)

    @functools.cached_property
    def _rounds_eval_fns(self) -> dict:
        return {}

    @functools.cached_property
    def _rounds_fns(self) -> dict:
        return {}

    def _build_rounds_fn(self, K: int, eval_metric: str | None = None,
                         masked: bool = False):
        # One program per (K, eval?, masked?) with bagging cfg-static:
        # every combination of colsample masks, in-scan bagging, and
        # in-scan eval composes in the single scan below (round 5).
        from ddt_tpu.ops import sampling as sampling_ops
        from ddt_tpu.ops import stream as stream_ops
        from ddt_tpu.utils.metrics import device_metric

        cfg = self.cfg
        bagging = cfg.subsample < 1.0
        quant = self._grad_quant
        # Quantized rounds need the absolute round id in-scan too (the
        # stochastic-rounding key is (seed, round * C + class, row)),
        # riding the same xs lane the bagging hash already uses.
        need_rids = bagging or quant
        C = cfg.n_classes if cfg.loss == "softmax" else 1
        axis = self._row_axes if self.distributed else None
        faxis = FAXIS if self.feature_partitions > 1 else None
        input_dtype = self._input_dtype
        mfn = device_metric(eval_metric, n_classes=C) if eval_metric \
            else None
        missing = cfg.missing_policy == "learn"
        subtract = grow_ops.resolve_hist_subtraction(
            cfg.hist_subtraction, integer_hists=quant)

        allreduce = _axis_allreduce(axis)

        def loss_of(pred, ya, valid):
            # Shared loss formulas (ops/grad.mean_loss); reductions psum'd
            # when row shards exist (inside shard_map the plain sums are
            # shard-local).
            return grad_ops.mean_loss(pred, ya, valid, cfg.loss,
                                      allreduce=allreduce)

        hp_n = self.n_partitions

        def rounds(data_a, pred0, ya, valid, *rest):
            rest = list(rest)
            rnd0 = rest.pop() if need_rids else None  # block's first round
            if masked:
                fmasks = rest.pop()           # [K, C, Fg] bool, scan xs
            if mfn is not None:
                val_data, vpred0, vy, vvalid = rest
                cat_vec = split_ops.cat_feature_vec(
                    cfg.cat_features,
                    val_data.shape[1] * self.feature_partitions)

            def one_round(pred, vpred, fmask_r=None, rid=None):
                g, h = grad_ops.grad_hess(pred, ya, cfg.loss)
                v = valid[:, None] if g.ndim == 2 else valid
                g = g * v
                h = h * v
                if bagging:
                    # Counter-based bagging bit per (round, global row) —
                    # exactly the granular path's host-drawn mask
                    # (ops/sampling twins are bit-identical; 0/1 f32
                    # multiplies commute exactly with the valid scaling).
                    keep = sampling_ops.row_keep_jax(
                        rid, _local_row_offset(axis, hp_n, ya.shape[0]),
                        ya.shape[0],
                        seed=cfg.seed, subsample=cfg.subsample)
                    kv = keep[:, None] if g.ndim == 2 else keep
                    g = g * kv
                    h = h * kv
                packs = []
                for c in range(C):
                    gc = g[:, c] if C > 1 else g
                    hc = h[:, c] if C > 1 else h
                    tree = grow_ops.grow_tree(
                        data_a, gc, hc,
                        max_depth=cfg.max_depth,
                        n_bins=cfg.n_bins,
                        reg_lambda=cfg.reg_lambda,
                        min_child_weight=cfg.min_child_weight,
                        min_split_gain=cfg.min_split_gain,
                        hist_impl=cfg.hist_impl,
                        input_dtype=input_dtype,
                        axis_name=axis,
                        feature_axis_name=faxis,
                        feature_mask=(
                            fmask_r[c] if fmask_r is not None else None),
                        missing_bin=missing,
                        cat_features=cfg.cat_features,
                        hist_subtraction=subtract,
                        split_comms=self.split_comms,
                        hist_comms_dtype=cfg.hist_comms_dtype,
                        comms_slabs=self.comms_slabs,
                        grad_dtype=cfg.grad_dtype,
                        quant_tree_id=(rid * C + c) if quant else None,
                        quant_seed=cfg.seed,
                    )
                    delta = grow_ops.tree_predict_delta(
                        tree, cfg.learning_rate)
                    pred = (pred.at[:, c].add(delta) if C > 1
                            else pred + delta)
                    if mfn is not None:
                        vpred = stream_ops.apply_tree_pred(
                            val_data, vpred,
                            tree.feature, tree.threshold_bin,
                            tree.is_leaf, tree.leaf_value,
                            tree.default_left if missing else None,
                            max_depth=cfg.max_depth,
                            learning_rate=cfg.learning_rate,
                            class_idx=c,
                            missing_bin_value=cfg.missing_bin_value,
                            cat_vec=cat_vec,
                            feature_axis_name=faxis,
                        )
                    packs.append(_pack_tree(tree))
                return pred, vpred, jnp.stack(packs), loss_of(
                    pred, ya, valid)

            # Scan xs: the round's colsample masks [C, Fg] and/or its
            # absolute round id (the bagging AND/OR grad-quant rounding
            # hash key) — any combination composes, with or without
            # in-scan eval.
            rids = (jnp.arange(K, dtype=jnp.int32) + rnd0) if need_rids \
                else None
            if masked and need_rids:
                xs = (fmasks, rids)
            elif masked:
                xs = fmasks
            elif need_rids:
                xs = rids
            else:
                xs = None

            def unpack(x):
                if masked and need_rids:
                    return x[0], x[1]
                if masked:
                    return x, None
                if need_rids:
                    return None, x
                return None, None

            if mfn is not None:
                def body(carry, x):
                    pred, vpred = carry
                    fm, rid = unpack(x)
                    pred, vpred, packs, loss = one_round(pred, vpred,
                                                         fm, rid)
                    return (pred, vpred), (
                        packs, loss, mfn(vy, vpred, vvalid, allreduce))

                (predf, vpredf), (trees, losses, scores) = jax.lax.scan(
                    body, (pred0, vpred0), xs,
                    length=K if xs is None else None)
                return trees, predf, losses, vpredf, scores

            def body(carry, x):
                fm, rid = unpack(x)
                pred, _, packs, loss = one_round(carry, None, fm, rid)
                return pred, (packs, loss)

            predf, (trees, losses) = jax.lax.scan(
                body, pred0, xs, length=K if xs is None else None)
            return trees, predf, losses

        if self.distributed:
            lay = self.layout
            pred_name = "pred" if C > 1 else "pred1d"
            pred_spec = lay.spec(pred_name)
            in_specs = lay.specs("data", pred_name, "y", "valid")
            out_specs = (lay.replicated(), pred_spec, lay.replicated())
            if mfn is not None:
                in_specs = in_specs + lay.specs("data", pred_name, "y",
                                                "valid")
                out_specs = out_specs + (pred_spec, lay.replicated())
            if masked:
                in_specs = in_specs + lay.specs("fmasks")   # replicated
            if need_rids:
                in_specs = in_specs + lay.specs("scalar")   # rnd0 repl.
            rounds = jax.shard_map(
                rounds,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                # Same rationale as _build_grow_fn: tree outputs are
                # replicated bit-identically by construction; the static
                # VMA checker cannot see through the gathered argmax
                # (feature-parallel OR reduce-scatter winner combine).
                check_vma=(faxis is None
                           and self.split_comms != "reduce_scatter"),
            )
        # Both block-reassigned prediction buffers are donated (the Driver
        # rebinds pred AND val_pred from the return every block).
        donate = (1, 5) if mfn is not None else (1,)
        # Cost registration for the fused block program (the roofline's
        # grow_block row folds in the fetch_tree barrier that carries the
        # block's device wallclock — telemetry/costmodel.roofline_table).
        return costed("grow_block", phase="grow_block")(
            jax.jit(rounds, donate_argnums=donate))

    # ------------------------------------------------------------------ #
    # device-side eval_set scoring (round-1 verdict, Weak #5): validation
    # predictions stay RESIDENT on device; each round's freshly grown
    # trees (still-on-device packed handles) are applied by the same
    # routing formulation as training, and the metric is computed on
    # device when its f32 twin exists (logloss/rmse/accuracy, plus
    # binary auc via the binned-rank twin since round 5 — one scalar
    # crosses the host boundary per round). The metric=None branch
    # (fetch a replicated raw-score copy for host evaluation) remains
    # as the generic fallback for twin-less metrics; no shipped metric
    # is twin-less anymore, so tests/test_metrics.py's
    # twinless-fallback test forces the registry empty to keep the
    # branch exercised on a pod mesh.
    # ------------------------------------------------------------------ #

    def eval_round(self, val_data, val_pred, handles, val_y: "LabelHandle",
                   metric: str | None):
        """Apply this round's trees (one packed handle per class) to the
        resident validation predictions. Returns (new_val_pred, score):
        score is a device scalar when the metric has an f32 device twin,
        else a REPLICATED copy of the predictions (safe to np.asarray even
        when the resident state spans a multi-host mesh) for host-side
        metric evaluation."""
        fn = self._eval_fns.get((len(handles), metric))
        if fn is None:
            fn = self._build_eval_fn(len(handles), metric)
            self._eval_fns[(len(handles), metric)] = fn
        return fn(val_data, val_pred, val_y.y, val_y.valid, *handles)

    @functools.cached_property
    def _eval_fns(self) -> dict:
        return {}

    def _build_eval_fn(self, C: int, metric: str | None):
        from ddt_tpu.ops import stream as stream_ops
        from ddt_tpu.utils.metrics import device_metric

        cfg = self.cfg
        faxis = FAXIS if self.feature_partitions > 1 else None
        mfn = device_metric(metric, n_classes=C) if metric else None
        missing = cfg.missing_policy == "learn"
        rax = self._row_axes

        def f(Xb, pred, y, valid, *packs):
            cat_vec = split_ops.cat_feature_vec(
                cfg.cat_features, Xb.shape[1] * self.feature_partitions)
            for c, pk in enumerate(packs):
                pred = stream_ops.apply_tree_pred(
                    Xb, pred,
                    pk[0].astype(jnp.int32), pk[1].astype(jnp.int32),
                    pk[2].astype(bool), pk[3],
                    pk[5].astype(bool) if missing else None,
                    max_depth=cfg.max_depth,
                    learning_rate=cfg.learning_rate,
                    class_idx=c,
                    missing_bin_value=cfg.missing_bin_value,
                    cat_vec=cat_vec,
                    feature_axis_name=faxis,
                )
            if mfn is None:
                # Host-metric path (auc): second output is a REPLICATED
                # copy of the predictions — np.asarray on the row-sharded
                # state itself would fail on a multi-host mesh (spans
                # non-addressable devices).
                gathered = (
                    comms_lib.all_gather(pred, rax, axis=0, tiled=True)
                    if self.distributed else pred
                )
                return pred, gathered
            return pred, mfn(y, pred, valid, _axis_allreduce(
                rax if self.distributed else None))

        if self.distributed:
            lay = self.layout
            pred_name = "pred" if C > 1 else "pred1d"
            pred_spec = lay.spec(pred_name)
            in_specs = (lay.specs("data", pred_name, "y", "valid")
                        + lay.specs(*(["tree"] * C)))
            out_specs = (pred_spec, lay.replicated())
            f = jax.shard_map(
                f, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
                # Same rationale as _build_grow_fn: the feature-axis
                # psum-broadcast routing — and the tiled all_gather of the
                # host-metric path — defeat the static VMA checker even
                # though both outputs are replicated by construction.
                check_vma=faxis is None and mfn is not None,
            )
        return costed("eval", phase="eval")(jax.jit(f, donate_argnums=(1,)))

    def apply_row_mask(self, g, h, mask):
        # Upload bool (1 byte/row); the cast to f32 is a free fused device op.
        m = self._put_rows(mask.astype(bool))
        return self._row_mask_fn(g, h, m)

    @functools.cached_property
    def _row_mask_fn(self):
        @jax.jit
        def f(g, h, m):
            m = m.astype(jnp.float32)
            if g.ndim == 2:
                m = m[:, None]
            return g * m, h * m

        return f

    def fetch_tree(self, handle) -> HostTree:
        def _fetch():
            # The per-tree D2H round-trip is the Driver's one recurring
            # host<->device transfer, so it is where a transient runtime
            # fault (UNAVAILABLE/DEADLINE_EXCEEDED) surfaces: retried
            # with backoff; the chaos harness injects here. chip_smoke.py
            # fails if a retry fired.
            faultplan.inject("fetch_tree")
            return np.asarray(handle)                    # ONE fetch

        packed = retry_lib.retry_call(_fetch, seam="fetch_tree")
        tele_counters.record_d2h(packed.nbytes)          # run-log counter
        return HostTree(
            feature=packed[0].astype(np.int32),
            threshold_bin=packed[1].astype(np.int32),
            is_leaf=packed[2].astype(bool),
            leaf_value=packed[3].astype(np.float32),
            split_gain=packed[4].astype(np.float32),
            default_left=packed[5].astype(bool),
        )

    @functools.cached_property
    def _apply_fn(self):
        @functools.partial(jax.jit, static_argnames=("class_idx",), donate_argnums=(0,))
        def f(pred, delta, class_idx):
            if pred.ndim == 2:
                return pred.at[:, class_idx].add(delta)
            return pred + delta

        return f

    def apply_delta(self, pred, delta, class_idx: int):
        return self._apply_fn(pred, delta, class_idx=class_idx)

    @functools.cached_property
    def _loss_fn(self):
        loss = self.cfg.loss

        @jax.jit
        def f(pred, y, valid):
            return grad_ops.mean_loss(pred, y, valid, loss)

        return f

    def loss_value(self, pred, y) -> float:
        return float(self._loss_fn(pred, y.y, y.valid))

    # ------------------------------------------------------------------ #
    # streaming (ops/stream.py): per-(chunk, level) work as one dispatch,
    # partial-tree traversal + grads + histogram on device; the host only
    # accumulates the small histograms and decides splits. Used by
    # streaming.fit_streaming when the backend exposes these.
    # ------------------------------------------------------------------ #

    @functools.cached_property
    def _stream_cache(self) -> dict:
        return {}

    def _stream_fn(self, kind: str, depth: int, class_idx: int,
                   left: bool = False):
        key = (kind, depth, class_idx, left)
        fn = self._stream_cache.get(key)
        if fn is not None:
            return fn
        from ddt_tpu.ops import sampling as sampling_ops
        from ddt_tpu.ops import stream as stream_ops

        cfg = self.cfg
        comms_mode = self.stream_hist_comms
        comms_dtype = cfg.hist_comms_dtype
        if self.feature_partitions > 1:
            raise NotImplementedError(
                "streaming with feature_partitions > 1 is not wired; "
                "stream rows (the long axis) instead"
            )
        axis = self._row_axes if self.distributed else None
        softmax = cfg.loss == "softmax"
        missing_val = cfg.missing_bin_value
        # Bagging ops take 3 extra traced scalars — (round id, chunk row
        # base lo/hi) — and recompute the counter-based keep mask on
        # device per chunk (ops/sampling; O(chunk), no mask shipping).
        # Quantized-gradient ops need the SAME scalars (the stochastic-
        # rounding key is (seed, tree, global row)) plus the round's two
        # host-reduced scales for hist/leaf builds.
        bagged = cfg.subsample < 1.0 and kind != "update"
        quant = self._grad_quant and kind in ("hist", "leaf",
                                              "roundstart", "gradstats")
        takes_rnd = bagged or quant
        takes_scales = quant and kind in ("hist", "leaf")
        hp_n = self.n_partitions
        Cq = cfg.n_classes if softmax else 1

        def parse_extra(extra):
            """(rnd, blo, bhi, gscale, hscale) from the trailing traced
            scalars — appended as (rnd, lo, hi[, gscale, hscale])."""
            it = list(extra)
            gsc = hsc = None
            if takes_scales:
                hsc = it.pop()
                gsc = it.pop()
            rnd = blo = bhi = None
            if takes_rnd:
                bhi = it.pop()
                blo = it.pop()
                rnd = it.pop()
            return rnd, blo, bhi, gsc, hsc

        def row_keep_for(n_rows, rnd, blo, bhi):
            if not bagged:
                return None
            return sampling_ops.row_keep_jax(
                rnd, _local_row_offset(axis, hp_n, n_rows),
                n_rows, seed=cfg.seed, subsample=cfg.subsample,
                row_start_lo=blo, row_start_hi=bhi)

        def quantizer_for(n_rows, rnd, blo, bhi, gsc, hsc):
            """The stream ops' quantize seam: this round's shared scales
            + this chunk's global-row-id base (ops/grad — tree_id =
            rnd * C + class keys the per-output-dim rounding)."""
            def q(gv, hv):
                return grad_ops.quantize_with_scales(
                    gv, hv, gsc, hsc, grad_dtype=cfg.grad_dtype,
                    tree_id=rnd * Cq + class_idx, seed=cfg.seed,
                    local_offset=_local_row_offset(axis, hp_n, n_rows),
                    row_start_lo=blo, row_start_hi=bhi)
            return q

        def cat_vec_for(Xb):
            return split_ops.cat_feature_vec(cfg.cat_features, Xb.shape[1])

        if kind == "hist":
            def f(Xb, pred, y, valid, feat, thr, leaf, dl, *extra):
                rnd, blo, bhi, gsc, hsc = parse_extra(extra)
                return stream_ops.stream_level_hist(
                    Xb, pred, y, valid, feat, thr, leaf, dl,
                    depth=depth, n_bins=cfg.n_bins, loss=cfg.loss,
                    class_idx=class_idx, hist_impl=cfg.hist_impl,
                    input_dtype=self._input_dtype, axis_name=axis,
                    missing_bin_value=missing_val, cat_vec=cat_vec_for(Xb),
                    row_keep=row_keep_for(Xb.shape[0], rnd, blo, bhi),
                    comms_mode=comms_mode, comms_dtype=comms_dtype,
                    build_left=left,
                    quantize=(quantizer_for(Xb.shape[0], rnd, blo, bhi,
                                            gsc, hsc) if quant else None),
                )
        elif kind == "leaf":
            def f(Xb, pred, y, valid, feat, thr, leaf, dl, *extra):
                rnd, blo, bhi, gsc, hsc = parse_extra(extra)
                return stream_ops.stream_leaf_gh(
                    Xb, pred, y, valid, feat, thr, leaf, dl,
                    max_depth=depth, loss=cfg.loss, class_idx=class_idx,
                    axis_name=axis,
                    missing_bin_value=missing_val, cat_vec=cat_vec_for(Xb),
                    row_keep=row_keep_for(Xb.shape[0], rnd, blo, bhi),
                    quantize=(quantizer_for(Xb.shape[0], rnd, blo, bhi,
                                            gsc, hsc) if quant else None),
                )
        elif kind == "update":
            def f(Xb, pred, feat, thr, leaf, val, dl):
                return stream_ops.stream_update_pred(
                    Xb, pred, feat, thr, leaf, val, dl,
                    max_depth=depth, learning_rate=cfg.learning_rate,
                    class_idx=class_idx,
                    missing_bin_value=missing_val, cat_vec=cat_vec_for(Xb),
                )
        elif kind == "gradstats":
            # Quantized streaming's scale-derivation pass: resident
            # pred/labels only — NO Xb operand, no chunk read.
            def f(pred, y, valid, *extra):
                rnd, blo, bhi, _, _ = parse_extra(extra)
                return stream_ops.stream_grad_stats(
                    pred, y, valid, loss=cfg.loss, n_classes=Cq,
                    axis_name=axis,
                    row_keep=row_keep_for(pred.shape[0], rnd, blo, bhi))
        elif kind == "roundstart":
            # `depth` carries the previous round's tree count (= C).
            n_prev = depth

            def f(Xb, pred, y, valid, *rest):
                extra = rest[5 * n_prev:]
                flat = rest[:5 * n_prev]
                rnd, blo, bhi, _, _ = parse_extra(extra)
                trees = tuple(
                    tuple(flat[5 * i: 5 * i + 5]) for i in range(n_prev))
                return stream_ops.stream_round_start(
                    Xb, pred, y, valid, trees,
                    max_depth=cfg.max_depth,
                    learning_rate=cfg.learning_rate,
                    n_bins=cfg.n_bins, loss=cfg.loss,
                    hist_impl=cfg.hist_impl,
                    input_dtype=self._input_dtype, axis_name=axis,
                    missing_bin_value=missing_val, cat_vec=cat_vec_for(Xb),
                    row_keep=row_keep_for(Xb.shape[0], rnd, blo, bhi),
                    comms_mode=comms_mode, comms_dtype=comms_dtype,
                    grad_stats_classes=Cq if quant else 0,
                )
        else:  # pragma: no cover
            raise ValueError(kind)

        if self.distributed:
            lay = self.layout
            # Under split_comms=reduce_scatter the streamed histogram
            # outputs come back F-sharded over the row axes (the wire
            # moved one slab per shard); the trainers slice the scatter
            # pad columns off after fetch.
            hist_spec = (lay.level_hist_scattered()
                         if self.stream_hist_comms == "reduce_scatter"
                         else lay.replicated())
            extra_specs = ()
            if takes_rnd:
                extra_specs = lay.specs("scalar", "scalar", "scalar")
            if takes_scales:
                extra_specs = extra_specs + lay.specs("scalar", "scalar")
            pred_name = "pred" if softmax else "pred1d"
            pred_spec = lay.spec(pred_name)
            if kind == "update":
                in_specs = lay.specs("data", pred_name) + \
                    lay.specs(*(["replicated"] * 5))
                out_specs = pred_spec
            elif kind == "gradstats":
                in_specs = lay.specs(pred_name, "y", "valid") + extra_specs
                out_specs = lay.replicated()
            elif kind == "roundstart":
                in_specs = lay.specs("data", pred_name, "y", "valid") + \
                    lay.specs(*(["replicated"] * (5 * depth))) + extra_specs
                # Quantized roundstart returns tiny replicated stats,
                # not a (possibly scattered) histogram.
                out_specs = (pred_spec,
                             lay.replicated() if quant else hist_spec)
            elif kind == "hist":
                in_specs = lay.specs("data", pred_name, "y", "valid") + \
                    lay.specs(*(["replicated"] * 4)) + extra_specs
                out_specs = hist_spec
            else:
                in_specs = lay.specs("data", pred_name, "y", "valid") + \
                    lay.specs(*(["replicated"] * 4)) + extra_specs
                out_specs = lay.replicated()
            f = jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                              out_specs=out_specs)
        donate = (1,) if kind in ("update", "roundstart") else ()
        # Cost registration per streamed program: op = the stream kind,
        # phase = the fit_streaming phase its dispatches run under
        # (roundstart is the fused round-start inside the hist pass;
        # gradstats is the quantized path's scale pass under the same
        # phase; update applies finished trees to resident predictions —
        # the device loop's predict phase).
        stream_phase = {"hist": "hist", "leaf": "leaf",
                        "roundstart": "hist", "gradstats": "hist",
                        "update": "predict"}[kind]
        fn = costed(f"stream_{kind}", phase=stream_phase)(
            jax.jit(f, donate_argnums=donate))
        self._stream_cache[key] = fn
        return fn

    def _bag_args(self, rnd: int, row_start: int) -> tuple:
        """Traced scalars for the streamed bagging/rounding hashes:
        (round id, chunk global-row base as a uint32 pair — 10B-row
        bases overflow uint32). Empty when neither bagging nor
        quantized gradients need them (the compiled programs take no
        such operands then)."""
        if self.cfg.subsample >= 1.0 and not self._grad_quant:
            return ()
        return (np.int32(rnd),
                np.uint32(row_start & 0xFFFFFFFF),
                np.uint32(row_start >> 32))

    def _scale_args(self, quant_scales) -> tuple:
        """The round's host-reduced quantization scales as traced f32
        scalars (quantized streaming only — streaming.py derives them
        from the round's gradstats pass)."""
        if not self._grad_quant:
            return ()
        if quant_scales is None:
            raise ValueError(
                "grad_dtype != 'f32': the streamed hist/leaf ops need "
                "the round's (gscale, hscale) — derive them from "
                "stream_grad_stats first")
        gs, hs = quant_scales
        return (np.float32(gs), np.float32(hs))

    def stream_level_hist(self, data, pred, y: "LabelHandle", tree,
                          depth: int, class_idx: int = 0,
                          rnd: int = 0, row_start: int = 0,
                          build_left: bool = False, quant_scales=None):
        """Partial histogram [2^depth, F, B, 2] for one uploaded chunk
        (device handle; includes the cross-shard collective — psum, or
        the F/P reduce-scatter under split_comms=reduce_scatter, where
        the handle comes back F-sharded with zero pad columns the caller
        slices off). `tree` is the partial tree's host arrays (feature,
        threshold_bin, is_leaf, default_left). `rnd`/`row_start` feed
        the counter-based bagging mask when cfg.subsample < 1 and the
        quantized-gradient rounding key when cfg.grad_dtype != 'f32'
        (ignored otherwise). `build_left=True` is the streamed sibling-
        subtraction half-build: [2^(depth-1), F, B, 2] LEFT children
        keyed by parent slot (streaming._assemble_subtracted_level
        recovers the right children). `quant_scales` = the round's
        (gscale, hscale) under quantized gradients — the output is then
        the RAW int32 partial (dequantize after the level's last
        chunk)."""
        feat, thr, leaf, dl = tree
        return self._stream_fn("hist", depth, class_idx, left=build_left)(
            data, pred, y.y, y.valid, feat, thr, leaf, dl,
            *self._bag_args(rnd, row_start), *self._scale_args(quant_scales))

    def stream_leaf_gh(self, data, pred, y: "LabelHandle", tree,
                       max_depth: int, class_idx: int = 0,
                       rnd: int = 0, row_start: int = 0,
                       quant_scales=None):
        """Final-level (G, H) aggregates [2^max_depth, 2] for one chunk
        (int32 under quantized gradients — see stream_level_hist)."""
        feat, thr, leaf, dl = tree
        return self._stream_fn("leaf", max_depth, class_idx)(
            data, pred, y.y, y.valid, feat, thr, leaf, dl,
            *self._bag_args(rnd, row_start), *self._scale_args(quant_scales))

    def stream_grad_stats(self, pred, y: "LabelHandle",
                          rnd: int = 0, row_start: int = 0):
        """Per-class quantization stats [C, 4] (max|g|, sum|g|, max|h|,
        sum|h|) for one chunk's resident state — quantized streaming's
        scale-derivation pass (NO data operand: gradients need only
        pred/labels). streaming.py max/sum-reduces the chunks and
        derives the round's scales via ops/grad.quant_scale_np."""
        return self._stream_fn("gradstats", 0, 0)(
            pred, y.y, y.valid, *self._bag_args(rnd, row_start))

    def stream_update_pred(self, data, pred, tree_full, max_depth: int,
                           class_idx: int = 0):
        """pred updated by a finished tree (donated; device-resident).
        `tree_full` = (feature, threshold_bin, is_leaf, leaf_value,
        default_left)."""
        feat, thr, leaf, val, dl = tree_full
        return self._stream_fn("update", max_depth, class_idx)(
            data, pred, feat, thr, leaf, val, dl)

    def stream_round_start(self, data, pred, y: "LabelHandle",
                           prev_trees: list,
                           rnd: int = 0, row_start: int = 0):
        """Fused round-start pass for one chunk: apply the previous
        round's finished class trees to the resident pred, then return the
        NEXT round's class-0 depth-0 histogram — one dispatch, one data
        read (ops/stream.stream_round_start). Returns (new_pred, hist) —
        or (new_pred, [C, 4] quantization stats) under cfg.grad_dtype !=
        'f32' (the scales must exist before ANY of the round's builds, so
        the depth-0 histogram becomes a normal quantized pass).
        `rnd` is the NEW round (its bagging mask feeds the histogram/
        stats; the pred update applies to every row)."""
        flat = [a for t in prev_trees for a in t]
        return self._stream_fn("roundstart", len(prev_trees), 0)(
            data, pred, y.y, y.valid, *flat,
            *self._bag_args(rnd, row_start))

    # ------------------------------------------------------------------ #
    # inference (TreeEnsemble.predict → gather+compare, row-sharded)
    # ------------------------------------------------------------------ #

    # Rows a scoring dispatch takes on each chip: a batch is scored in
    # chunks of this many, so one program and its temporaries serve any
    # row count (a 100M-row call is 50 dispatches; its peak memory is the
    # whole uploaded batch's, PERF.md section 4, not a chunk's).
    PREDICT_ROW_CHUNK = 2_000_000
    # ... and the bytes of rows it takes at most, as HBM holds them (the
    # last dimension in whole tiles of 128 lanes: `predict_chunk_rows`).
    # Up to 128 columns 2M rows are 256 MB and a chunk is PREDICT_ROW_CHUNK
    # rows, as it was; at 968 columns they would be 2.05 GB, with as much
    # again for the slice's copy, and the first piece of the upload, all
    # of it a call exposes, the whole of a 2.3 GB batch: there a chunk is
    # 262,144 rows, and what goes up before the first dispatch 0.5 GB.
    PREDICT_CHUNK_BYTES = 256 * 1024 * 1024
    # What a bin of a dispatched chunk is on the device: api.predict holds
    # its callers to uint8, the batch goes up as it is, and the scoring
    # programs are built (and their stages read) for chunks of it.
    PREDICT_ROW_DTYPE = np.dtype(np.uint8)
    # Chunks a piece of the single-chip big-batch upload (_predict_raw):
    # the first piece's transfer is all of the upload a call exposes (46
    # ms of 156 MB at 2, 88 ms at 5, and it varies by as much less; 31 ms
    # since that piece goes up flat, see _predict_raw), a
    # piece's fixed cost (about 18 ms) is hidden under the compute, and a
    # piece of more than one chunk keeps the device slices, so the peak.
    PREDICT_UPLOAD_CHUNKS = 2
    # ... and the bytes a LEADING piece holds at most (one chunk at least):
    # up to 128 columns two chunks are 112-216 MB and every piece is two,
    # as it was; at 968 and 2000 columns a chunk is 254 and 262 MB, and the
    # first piece is ONE chunk, and so is the second, which has only the
    # first's compute to arrive under. The first piece's transfer is the
    # one a call exposes whole, and on a shared host what a call's wall
    # varies by: of 1.44 s calls over 2000 columns one in twenty took
    # 87-117 ms longer in the evening, all of it the first 524 MB arriving
    # late (PERF.md section 6, PR 39).
    PREDICT_FIRST_PIECE_BYTES = 256 * 1024 * 1024
    # Device-resident CompiledEnsemble slots per backend instance: each
    # entry pins the model's pushed-down node tables on device (~MBs for a
    # 1000-tree model) across predict calls. Small because backend
    # instances are themselves cached and serving stacks typically score
    # a handful of live model versions.
    PREDICT_CACHE_MAX = 4

    # Counters whose movement over one predict_raw call rides on its root
    # span: whether the call compiled, traced or lowered anything, and
    # whether the model was already resident. (Beside them the root carries
    # the host's pauses over the call, telemetry/counters.HOST_COUNTERS:
    # what tells a pause of the process from a wait for the link or the
    # device.)
    _PREDICT_ROOT_COUNTERS = (
        "jit_compiles", "jit_compile_seconds", "jit_trace_seconds",
        "jit_lower_seconds", "compile_cache_hits",
        "compiled_ensemble_cache_hits")
    # The root's counts that make two calls the same work: what a call is
    # held to the calls before it by (annotations.note_root, slow_calls()).
    _PREDICT_ROOT_SHAPE = ("rows", "chunks", "branch", "classes")

    def predict_chunk_rows(self, n_features: int) -> int:
        """Rows a scoring dispatch takes on each chip at this width:
        PREDICT_ROW_CHUNK, or as many as PREDICT_CHUNK_BYTES hold where
        that is fewer. One rule, read from the rows' width alone."""
        lanes = -(-n_features // 128) * 128
        return max(1, min(self.PREDICT_ROW_CHUNK, self.PREDICT_CHUNK_BYTES
                          // (lanes * self.PREDICT_ROW_DTYPE.itemsize)))

    def links_on_device(self, ens) -> bool:
        """Whether `predict_raw(..., link=True)` answers this model's
        probabilities: the link function taken by the scoring program, on
        the device, under the stage `predict:link`. The model's layout says
        which losses' links its program takes (ops/predict.LAYOUTS); every
        other model's is the caller's (`utils/metrics.predict_proba_np`,
        api.predict)."""
        return ens.loss in predict_ops.LAYOUTS[ens.layout].links

    def predict_raw(self, ens: TreeEnsemble, Xb: np.ndarray,
                    compiled=None, link: bool = False) -> np.ndarray:
        """Score binned rows. `compiled` (a models/tree.CompiledEnsemble
        already built for THIS ens) skips the per-call content hash —
        the serving tier holds one per model version, so a micro-batch
        request pays upload + dispatch only (docs/SERVING.md). `link`: the
        class probabilities and not the margins, where `links_on_device`
        says the program takes the link (another program of the same
        model: its own entry of the cache).

        Every call is one root span `ddt:predict` with a child span per
        step (token, ensemble, upload, dispatch, fetch, place: the
        table is in docs/OBSERVABILITY.md); the spans time the host's
        side and add no sync."""
        root = phase_span("predict", rows=int(Xb.shape[0]))
        try:
            with root:
                c0 = tele_counters.snapshot()
                paused = tele_counters.host_pauses()
                try:
                    return self._predict_raw(ens, Xb, compiled, root.counts,
                                             link)
                finally:
                    moved = tele_counters.delta(c0)
                    root.counts.update(
                        {k: moved[k] for k in self._PREDICT_ROOT_COUNTERS})
                    root.counts.update(tele_counters.host_pauses(paused))
        finally:
            note_root(root, self._PREDICT_ROOT_SHAPE)

    def _predict_raw(self, ens: TreeEnsemble, Xb: np.ndarray, compiled,
                     counts: dict, link: bool = False) -> np.ndarray:
        """predict_raw's body; `counts` is the root span's (branch and
        chunks are written as soon as they are known)."""
        R = Xb.shape[0]
        chunk = self.predict_chunk_rows(Xb.shape[1]) * max(
            1, self.row_shards)
        fn, ens_dev, classes, plan = self._predict_entry(ens, compiled,
                                                         link)[:4]
        # (the rows of the executable this call runs: what the stage map is
        # read at, `_stage_scoring_program`)
        self._scoring_rows = min(R, chunk)
        if isinstance(Xb, jax.Array) and (R <= chunk or self.distributed):
            # Device-resident input is only special-cased on the
            # single-chip big-batch loop below (where it skips the bulk
            # upload, isolating device compute for benchmarking); the
            # other paths pad/shard on host.
            Xb = np.asarray(Xb)
        starts = range(0, R, chunk) if R > chunk else (0,)
        counts["chunks"] = len(starts)
        counts["classes"] = classes
        # What the traversal kernel reads of its node tables from HBM over
        # the call: every table block once a row tile where they stream,
        # 0 where one block holds them all (fetched once, resident).
        counts["tables_streamed_bytes"] = 0
        # What the layout's plan says of itself on every call's root.
        counts.update(plan.root_counts())
        if plan.blocks > 1:
            shards = max(1, self.row_shards)
            shard_rows = [-(-min(chunk, R - i) // shards) for i in starts]
            tiles = sum(-(-r // plan.step_rows(r)) for r in shard_rows)
            counts["tables_streamed_bytes"] = (
                shards * tiles * plan.table_bytes)
        if R <= chunk:
            counts["branch"] = "one"
            with phase_span("predict:upload", bytes=Xb.nbytes):
                Xc = self._put_rows(Xb, extra_dims=1)  # uint8, as the kernel takes it
            with phase_span("predict:dispatch", chunk=0):
                out = fn(*ens_dev, Xc)
            with phase_span("predict:fetch", chunk=0) as sp:
                out = np.asarray(out)
                sp.counts["bytes"] = out.nbytes
            tele_counters.record_d2h(out.nbytes)
            return out[:R]
        if self.distributed:
            # Per-chunk host→device upload (each chunk must be laid out
            # over the mesh); ensemble arrays + shard_map fn hoisted.
            counts["branch"] = "mesh"
            outs = []
            for k, i in enumerate(starts):
                part = Xb[i:i + chunk]
                with phase_span("predict:upload", chunk=k,
                                bytes=part.nbytes):
                    Xc = self._put_rows(part, extra_dims=1)
                with phase_span("predict:dispatch", chunk=k):
                    outs.append(fn(*ens_dev, Xc)
                                [:min(chunk, R - i)])  # drop chunk pad rows
            with phase_span("predict:fetch", chunk=0) as sp:
                out = np.asarray(jnp.concatenate(outs))
                sp.counts["bytes"] = out.nbytes
            tele_counters.record_d2h(out.nbytes)
            return out[:R]
        # Single chip: the batch goes up ONCE (uint8 — 4x less
        # host→device traffic than int32), in pieces of
        # PREDICT_UPLOAD_CHUNKS chunks (the leading ones of fewer where
        # they would pass PREDICT_FIRST_PIECE_BYTES), and chunks are sliced
        # on device.
        # A piece's transfer starts when the chunks of the piece before
        # it have been dispatched and that piece has arrived (the wait is
        # the span `predict:upload:wait`: its end is the host's time by
        # which that piece had landed), so it runs under their compute,
        # and only the first piece's is exposed (what that is on the chip,
        # split into the put, the flight and the device's start: PERF.md
        # section 5, "the routed call's arrival", PR 52) where one
        # device_put of a 3.9 GB batch took 644-888 ms before the first
        # chunk could start, and a call's wall varied by all of that
        # (PERF.md sections 5 and 6, PR 31). Two
        # forms that lost there: every piece issued before the loop (the
        # device takes transfers and programs in the host's issue order,
        # so the first chunk waited for them all, 414 ms), and pieces
        # issued without waiting for the one before (two transfers share
        # the host's relayout threads: 110-220 ms for the first). The
        # pieces stay until the call returns and sum to the batch, so
        # the call's peak memory is what the whole batch's was.
        # Each chunk's device→host copy is started as the chunk is
        # dispatched, so finished chunks stream back while later ones
        # compute, and each lands in its rows of ONE result array
        # (`place`) as soon as the host holds it: the copies and the
        # first touch of the result's pages run under the device's work.
        # What is left exposed on the chip is the fetch tail, the last
        # device operation's end to the call's return, which is the last
        # chunk's D2H and its place: 1.9 ms of a 6.05 s 100M-row call
        # and 36 ms of a 19.75 s 30M-row x 7-class call, where one
        # np.concatenate after the loop took 386 and 779 ms (PERF.md
        # section 5, PR 30).
        counts["branch"] = "chunks"
        resident = isinstance(Xb, jax.Array)
        piece = R if resident else chunk * self.PREDICT_UPLOAD_CHUNKS
        first = piece if resident else chunk * max(1, min(
            self.PREDICT_UPLOAD_CHUNKS, self.PREDICT_FIRST_PIECE_BYTES
            // (chunk * Xb.shape[1] * Xb.dtype.itemsize)))
        # The pieces' rows, bounds[p] .. bounds[p + 1]: the leading
        # PREDICT_UPLOAD_CHUNKS pieces of `first` rows, the rest of `piece`.
        lead = min(R, first * self.PREDICT_UPLOAD_CHUNKS)
        bounds = [*range(0, lead, first), *range(lead, R, piece), R]
        Xh = Xb if resident else np.ascontiguousarray(Xb)

        def upload(p):
            if resident:
                with phase_span("predict:upload", piece=p, bytes=0):
                    return Xb
            part = Xh[bounds[p]:bounds[p + 1]]
            with phase_span("predict:upload", piece=p, bytes=part.nbytes):
                if p:   # one transfer at a time; the device has p - 1's chunks
                    with phase_span("predict:upload:wait", piece=p - 1,
                                    bytes=pieces[-1].nbytes):
                        pieces[-1].block_until_ready()  # ddtlint: disable=host-sync
                    return jax.device_put(part)
                # The exposed piece goes up as flat bytes and takes its
                # shape on the device: a 1-D array needs no relayout on
                # the host, whose threads' share of the machine's cores is
                # what a call's wall varied by (156 MB: 30.6 ms, sd 0.56,
                # the device's reshape included, where the 2-D
                # device_put took 34.7, sd 1.99; the rest of a call 1,698
                # ms, sd 1.0). Later pieces keep the host relayout: the
                # compute hides it, and the reshape would be device time.
                return jnp.reshape(jax.device_put(part.reshape(-1)),
                                   part.shape)

        pieces = [upload(0)]
        if not resident:
            tele_counters.record_h2d(Xb.nbytes)
        outs = []
        for k, i in enumerate(starts):
            at = i - bounds[len(pieces) - 1]
            with phase_span("predict:dispatch", chunk=k):
                outs.append(fn(*ens_dev, pieces[-1][at:at + chunk]))
                outs[-1].copy_to_host_async()
            if i + chunk == bounds[len(pieces)] and i + chunk < R:
                pieces.append(upload(len(pieces)))
        # Shape and dtype are the dispatched arrays' (no sync). The places
        # below touch the result's pages for the first time, under the
        # device's work; the last chunk's place is the one nothing hides,
        # so its pages are touched here, while the device is still busy.
        out = np.empty((R,) + outs[0].shape[1:], outs[0].dtype)
        out[starts[-1]:] = 0
        for k, (i, o) in enumerate(zip(starts, outs)):
            with phase_span("predict:fetch", chunk=k, bytes=o.nbytes):
                # Not a per-iter sync: the copy is already in flight
                # (copy_to_host_async above); asarray only materialises.
                part = np.asarray(o)    # ddtlint: disable=host-sync
            # A span of its own: the benchmark's clock anchor is "fetch[k]
            # ends when the host holds chunk k", and no later.
            with phase_span("predict:place", chunk=k, bytes=o.nbytes):
                out[i:i + chunk] = part     # a chunk scores its own rows
        tele_counters.record_d2h(out.nbytes)
        return out

    @functools.cached_property
    def _predict_cache(self) -> dict:
        # token -> (fn, device arrays, classes, table plan, the tier that
        # serves); insertion order = LRU order.
        return {}

    def resolved_predict_impl(self, token: str) -> str:
        """The scoring tier that ACTUALLY serves model `token` after the
        fallback ladder ("lut4" | "lut" | "f32"; "f32" when the model never
        scored here): the last item of its cache entry. The serving tier
        stamps it into /healthz and serve_latency, so that a silent
        VMEM-guard fallback is an observable fact."""
        return self._predict_cache.get(token, ("f32",))[-1]

    def _predict_fn(self, ens: TreeEnsemble, compiled=None):
        """(jittable scoring fn, device-resident compiled-ensemble arrays):
        `_predict_entry` without the class count and the table plan."""
        return self._predict_entry(ens, compiled)[:2]

    def _predict_entry(self, ens: TreeEnsemble, compiled=None,
                       link: bool = False):
        """(jittable scoring fn, device-resident compiled-ensemble arrays,
        the model's class count, the plan of its ops/predict.
        ScoringProgram, the tier that serves: `resolved_predict_impl`).

        The model's scoring layout (`ens.compile()`) and its device copies
        are cached per model version: the key is a content digest of the
        node arrays, so in-place trainer mutation can never serve stale
        trees, and a hit skips the build AND the re-upload. The digest is
        the span `ddt:predict:token`, a miss the span
        `ddt:predict:ensemble` (what each takes: PERF.md section 5); hits
        feed the run log's `compiled_ensemble_cache_hits` counter.
        `compiled` (a compiled form the caller already built) keys the
        cache on its `token` directly, no per-call full-array hash, and
        seeds a miss: the serving tier's request path."""
        if link and not self.links_on_device(ens):
            raise ValueError(
                "predict_raw(link=True): this model's link function is not "
                "taken on the device (links_on_device); ask for the "
                "margins and apply utils.metrics.predict_proba_np")
        if compiled is not None:
            token = compiled.token
        else:
            with phase_span("predict:token"):
                token = ens.cache_token()
        token += ":link" * link         # another program of the same model
        hit = self._predict_cache.pop(token, None)
        if hit is not None:
            self._predict_cache[token] = hit     # most-recently-used
            tele_counters.record_compiled_ensemble_hit()
            return hit
        with phase_span("predict:ensemble") as sp:
            hit = self._build_predict_fn(ens, compiled, link)
            _, ens_dev, _, plan, _ = hit
            sp.counts["bytes"] = sum(a.nbytes for a in ens_dev)
            # (beside the plan's groups: the lanes of them that hold a tree)
            sp.counts["trees"] = ens.n_trees
            sp.counts.update(plan.span_counts())
        self._predict_cache[token] = hit
        while len(self._predict_cache) > self.PREDICT_CACHE_MAX:
            self._predict_cache.pop(next(iter(self._predict_cache)))
        return hit

    def _build_predict_fn(self, ens: TreeEnsemble, compiled,
                          link: bool = False) -> tuple:
        """_predict_entry's cache miss, and its entry of the cache. Three
        stages, children of `ddt:predict:ensemble`: `compile` (models/tree:
        the scoring layout from the model's arrays; nothing where the
        caller hands it in), `pack` (the layout's entry, which returns an
        ops/predict.ScoringProgram: the plan, kernel or twin, the quantized
        tiers' ladder, what is made of the tables on the host) and `upload`
        (`_put_tables`, the entry's fill inside it). Which layout it is,
        the compiled form says; nothing here knows one by name."""
        with phase_span("predict:ensemble:compile", trees=ens.n_trees,
                        nodes=ens.n_nodes) as sp:
            ce = compiled if compiled is not None else ens.compile(
                tree_chunk=64)
            sp.counts.update(getattr(ce, "compile_counts", {}))
        entry = predict_ops.layout_entry(ce.layout)     # the ONE lookup
        impl = self.cfg.predict_impl
        with phase_span("predict:ensemble:pack") as sp:
            prog = entry(ce, ens.n_features, self.PREDICT_ROW_DTYPE, impl,
                         link)
            sp.counts["bytes"] = sum(a.nbytes for a in prog.tables)
        if impl in ("lut", "lut4") and prog.tier != impl:
            log.warning(
                "predict_impl=%r: the %r tier serves this model (its "
                "layout has no quantized form, or its shape exceeds that "
                "kernel's VMEM budget)", impl, prog.tier)
        ens_dev = self._put_tables(prog.fill(prog.tables))
        if prog.entry is not None:
            self._stage_scoring_program(prog.entry, prog.fn, ens_dev,
                                        ens.n_features)
        return (self._row_sharded(prog.fn, len(ens_dev), prog.columns),
                ens_dev, prog.classes, prog.plan, prog.tier)

    def _put_tables(self, tables) -> tuple:
        """A model's tables up, replicated, one at a time (`tables` may
        make each as it is asked for): the span
        `ddt:predict:ensemble:upload`, `bytes` what went up. It ends when
        the transfers are ISSUED: device_put returns before the bytes have
        landed, and the first chunk's dispatch is what waits for them."""
        with phase_span("predict:ensemble:upload") as sp:
            sh = self._named(self.layout.replicated())
            up = []
            for a in tables:
                up.append(self._put(a, sh))
                sp.counts["bytes"] = sp.counts.get("bytes", 0) + a.nbytes
                del a       # a made table goes before the next is made
            return tuple(up)

    def _stage_scoring_program(self, entry, fn0, ens_dev,
                               n_features: int) -> None:
        """Tell telemetry.annotations.device_stages() how to read the
        stages of the programs the single-chip big-batch loop runs: the
        scoring program `entry` (a jitted function of ops/predict.py; fn0
        calls it with the model's static arguments) at the loop's own
        shapes, and the loop's two small programs by what they are for.
        Dict writes: the lowering happens when somebody asks, at the rows
        of the backend's LAST call (a batch of fewer rows than a chunk is
        ONE program of its own rows). A call of another row count runs
        another executable of the same name, whose instructions the map may
        not hold; a mesh's row-sharded wrapper is not named."""
        if self.distributed:
            return
        avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ens_dev]

        def hlo():
            rows = getattr(self, "_scoring_rows", None) \
                or self.predict_chunk_rows(n_features)
            Xc = jax.ShapeDtypeStruct((rows, n_features),
                                      self.PREDICT_ROW_DTYPE)
            return fn0(*avals, Xc, entry=entry.lower).compile().as_text()

        stage_program("jit_" + entry.__name__, hlo)
        loop = "ddt_tpu/backends/tpu.py:%d" % (
            TPUDevice._predict_raw.__code__.co_firstlineno)
        # `pieces[-1][at:at + chunk]` and the first piece's `jnp.reshape`,
        # by the names jax gives the programs of its own eager operations.
        stage_program("jit_dynamic_slice", stage="predict:slice",
                      source=loop)
        stage_program("jit_reshape", stage="predict:unflatten", source=loop)

    def _row_sharded(self, fn, n_rep: int, C: int):
        """`fn(*replicated tables, rows)` as it runs on this backend:
        itself on one chip, row-sharded over the mesh otherwise."""
        if self.distributed:
            # Row-sharded scoring is embarrassingly parallel: trees are
            # replicated, each shard traverses its own rows, no collectives
            # (SURVEY.md §3 predict stack). shard_map makes the row-gather
            # sharding explicit — XLA cannot infer it through the
            # take_along_axis traversal.
            lay = self.layout
            out_spec = lay.row_vector() if C == 1 else lay.row_matrix()
            # jit: a bare shard_map runs eagerly and re-compiles its body
            # on EVERY call (found on the chip, PR 21: 1.4 s a call for
            # the rows=4 smoke against 0.03 s on one device).
            fn = jax.jit(jax.shard_map(
                fn,
                mesh=self.mesh,
                in_specs=(lay.replicated(),) * n_rep
                + (lay.row_matrix(),),     # rows sharded, F replicated:
                # scoring never feature-shards (trees are replicated)
                out_specs=out_spec,
                # predict_raw's scan carry starts replicated (zeros) and
                # becomes row-varying after the first accumulation; the
                # static VMA checker rejects that even though it is sound
                # here (no collectives anywhere in the traversal).
                check_vma=False,
            ))
        return fn
