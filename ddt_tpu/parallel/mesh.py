"""Mesh construction + multi-host initialisation (layers L2/L0 plumbing).

SURVEY.md §5 "Distributed communication backend": the reference's on-FPGA
100G fabric allreduce maps to XLA collectives over mesh axes — psum rides ICI
within a slice; a second ("hosts") axis rides DCN across slices. A GBDT
histogram is KBs–MBs and additive, so the same single psum works over a 1-D
flattened mesh too; the 2-D constructor exists so multi-slice pods lay the
reduce-scatter/all-reduce phases out along the fast axis first (XLA does this
automatically for a 2-D mesh when axes are ordered (hosts, rows)).

Multi-host: standard single-controller JAX — every host runs the same
program, jax.distributed.initialize() wires the DCN bootstrap, and
jax.devices() becomes the global device list. Training code is unchanged:
TPUDevice row-shards over the global mesh and the Driver loop never knows.
"""

from __future__ import annotations

import dataclasses
import logging
import re

import jax
from jax.sharding import AxisType

log = logging.getLogger("ddt_tpu.parallel")

ROWS_AXIS = "rows"
HOSTS_AXIS = "hosts"
FEATURES_AXIS = "features"

P = jax.sharding.PartitionSpec


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Canonical PartitionSpecs for every trainer operand over the
    declarative 2D (rows x features) mesh — the SpecLayout idiom
    (SNIPPETS [3]) applied to histogram GBDT.

    `row_axes` is the row-shard axis name — a ("hosts", "rows") tuple on
    pod meshes, plain "rows" otherwise, or None on single-device
    backends (every spec degenerates to replicated, so single-device
    traces share the callers' code). `feature_axis` is the optional
    column axis ("features"), or None when the feature dimension is
    replicated.

    The layout is the ONE home of "which operand shards how": backends
    resolve in_specs/out_specs through the rule table below
    (match_partition_rules) by operand NAME, so adding a mesh axis is a
    table edit, not a hunt through every shard_map call site."""

    row_axes: "str | tuple[str, ...] | None" = ROWS_AXIS
    feature_axis: "str | None" = None

    # -- canonical per-operand specs ---------------------------------- #

    def binned_data(self) -> P:
        """uint8 [R, F]: rows sharded, columns sharded when the feature
        axis is live (the wide-dataset case ROADMAP item 2 exists for)."""
        if self.row_axes is None:
            return P()
        return P(self.row_axes, self.feature_axis)

    def row_vector(self) -> P:
        """float32/int32 [R]: gradients, hessians, node indices, labels,
        validity masks — row-sharded, feature-replicated."""
        return P() if self.row_axes is None else P(self.row_axes)

    def row_matrix(self) -> P:
        """[R, C] per-class state (softmax pred): rows sharded, classes
        replicated."""
        return P() if self.row_axes is None else P(self.row_axes, None)

    def level_hist_scattered(self) -> P:
        """[n_level, F, B, 2] POST-reduce-scatter level histogram: the
        feature dim sharded over the ROW axes (each row shard merged one
        F/Pr slab — parallel/comms.hist_reduce)."""
        if self.row_axes is None:
            return P()
        return P(None, self.row_axes)

    def replicated(self) -> P:
        """Tree node arrays, split winners, scalars, colsample masks —
        tiny, identical on every shard by construction."""
        return P()

    # -- the declarative rule table ----------------------------------- #

    def rules(self) -> list:
        """[(operand-name regex, PartitionSpec)] — first match wins
        (match_partition_rules). Names are the backends' operand
        vocabulary; `.*` (replicated) is the explicit fallback so a
        typo'd name fails the match audit in tests, not silently."""
        return [
            (r"^(data|binned|Xb)", self.binned_data()),
            (r"^(grad|hess|node_index|labels|valid|row_keep|pred1d|y)$",
             self.row_vector()),
            (r"^(pred|val_pred)$", self.row_matrix()),
            (r"^hist_scattered$", self.level_hist_scattered()),
            (r"^(tree|winners|mask|scalar|fmasks|replicated)",
             self.replicated()),
        ]

    def spec(self, name: str) -> P:
        return match_partition_rules(self.rules(), [name])[0]

    def specs(self, *names: str) -> tuple:
        return match_partition_rules(self.rules(), list(names))


def match_partition_rules(rules, names) -> tuple:
    """PartitionSpec per operand name from a [(regex, spec)] rule table
    — the match_partition_rules idiom (SNIPPETS [1]) on operand names
    instead of parameter-tree paths (a GBDT trainer has a dozen named
    operands, not a parameter pytree). Unmatched names fail loudly: a
    silently-replicated row matrix is a 10x memory bug, not a default."""
    out = []
    for name in names:
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                out.append(spec)
                break
        else:
            raise ValueError(
                f"no partition rule matches operand {name!r}; add it to "
                "SpecLayout.rules()")
    return tuple(out)


def _make_mesh(shape: tuple, names: tuple, devices: list):
    """jax.make_mesh with AUTO axes over exactly `devices`.

    Auto: every sharded program here is a shard_map with explicit specs
    (SpecLayout), and the installed jax makes `Explicit` axes by default,
    under which plain jnp ops on the sharded handles outside shard_map
    raise ShardingTypeError. Device order is jax.make_mesh's: on a TPU it
    follows the physical torus, and it REFUSES a device set that is not a
    contiguous box of it (3 of a 2x2's 4 chips, say) with a bare
    AssertionError — re-raised here with the reason."""
    try:
        return jax.make_mesh(
            shape, names, axis_types=(AxisType.Auto,) * len(shape),
            devices=devices)
    except AssertionError as e:
        raise ValueError(
            f"cannot lay a {dict(zip(names, shape))} mesh over devices "
            f"{[d.id for d in devices]}: on a TPU the device set must be a "
            "contiguous box of the physical torus (jax.make_mesh refused "
            "it)") from e


def make_row_mesh(
    n_partitions: int, devices: list | None = None
) -> jax.sharding.Mesh:
    """1-D mesh over the data-parallel "rows" axis (the GBDT's only
    parallelism dimension — SURVEY.md §2 "Parallelism strategies")."""
    devs = devices if devices is not None else jax.devices()
    if len(devs) < n_partitions:
        raise ValueError(
            f"n_partitions={n_partitions} but only {len(devs)} devices visible"
        )
    return _make_mesh((n_partitions,), (ROWS_AXIS,), devs[:n_partitions])


def make_pod_mesh(
    n_hosts: int | None = None,
    devices_per_host: int | None = None,
    feature_partitions: int = 1,
    devices: list | None = None,
) -> jax.sharding.Mesh:
    """(hosts, rows[, features]) mesh for multi-slice pods: "rows" is the
    intra-slice ICI axis, "hosts" the cross-slice DCN axis (outermost =
    slowest varying, so each host's devices stay ICI-contiguous). Histogram
    reduction becomes psum over (hosts, rows); XLA phases it as ICI-reduce
    then DCN-allreduce.

    Consumed by TPUDevice: pass the result as `TPUDevice(cfg, mesh=...)`
    (it reads the hosts/rows/features axis sizes off the mesh), or just set
    cfg.host_partitions and let TPUDevice build the identical mesh itself."""
    devs = devices if devices is not None else jax.devices()
    if n_hosts is None:
        n_hosts = max(1, jax.process_count())
    if devices_per_host is None:
        devices_per_host = len(devs) // (n_hosts * feature_partitions)
    n_dev = n_hosts * devices_per_host * feature_partitions
    if len(devs) < n_dev:
        raise ValueError(
            f"pod mesh {n_hosts} x {devices_per_host} x "
            f"{feature_partitions} needs {n_dev} devices, "
            f"have {len(devs)}"
        )
    if feature_partitions > 1:
        return _make_mesh(
            (n_hosts, devices_per_host, feature_partitions),
            (HOSTS_AXIS, ROWS_AXIS, FEATURES_AXIS), devs[:n_dev])
    return _make_mesh((n_hosts, devices_per_host), (HOSTS_AXIS, ROWS_AXIS),
                      devs[:n_dev])


def make_mesh_2d(
    row_partitions: int,
    feature_partitions: int = 1,
    n_hosts: int = 1,
    devices: list | None = None,
) -> jax.sharding.Mesh:
    """Declarative 2D (rows x features) mesh — ROADMAP item 2's layout.

    Axis order is (hosts?, rows, features): hosts outermost (DCN,
    slowest-varying, so each host's devices stay ICI-contiguous), rows
    middle, features innermost (ICI-adjacent — the per-level winner
    gather over the feature axis is latency-sensitive; the hosts hop
    happens once per reduction). The features axis is always present on
    the 2-D form (size 1 when unsharded) so partition specs naming it
    resolve on every mesh; the pure pod form (make_pod_mesh) remains the
    (hosts, rows) spelling for row-only multi-slice runs.

    This is the ONE mesh constructor the TPUDevice backend uses; pass
    `cfg.mesh_shape=(Pr, Pf)` (or --mesh-shape Pr,Pf) and the backend
    calls this with those extents."""
    devs = devices if devices is not None else jax.devices()
    n_dev = n_hosts * row_partitions * feature_partitions
    if len(devs) < n_dev:
        raise ValueError(
            f"mesh ({n_hosts} hosts x {row_partitions} rows x "
            f"{feature_partitions} features) needs {n_dev} devices, "
            f"have {len(devs)}"
        )
    if n_hosts > 1:
        return _make_mesh(
            (n_hosts, row_partitions, feature_partitions),
            (HOSTS_AXIS, ROWS_AXIS, FEATURES_AXIS), devs[:n_dev])
    return _make_mesh((row_partitions, feature_partitions),
                      (ROWS_AXIS, FEATURES_AXIS), devs[:n_dev])


def shard_ready_times(arr, poll_interval_s: float = 5e-5,
                      timeout_s: float = 600.0) -> "list | None":
    """Per-device completion times of `arr`'s addressable shards:
    [(device_id, time.perf_counter() at readiness)], device-id sorted.

    The flight recorder's probe (telemetry.events.PartitionRecorder):
    polling each shard's is_ready() records every device's completion
    moment independently — the per-partition wall-time signal a single
    block_until_ready collapses into one number. Shards still pending at
    `timeout_s` are blocked on in device order (their lanes flatten onto
    the running prefix-max). Returns None for values with no shard view
    (host arrays). Only meaningful to call on a handle whose producer
    has been dispatched; the probe IS a barrier on the array."""
    import time as _time

    try:
        shards = arr.addressable_shards
    except AttributeError:
        return None
    pending = {int(s.device.id): s.data for s in shards}
    out: dict[int, float] = {}
    deadline = _time.perf_counter() + timeout_s
    while pending and _time.perf_counter() < deadline:
        for dev in list(pending):
            if pending[dev].is_ready():
                out[dev] = _time.perf_counter()
                del pending[dev]
        if pending:
            _time.sleep(poll_interval_s)
    for dev in sorted(pending):              # timeout residue
        pending[dev].block_until_ready()
        out[dev] = _time.perf_counter()
    return sorted(out.items())


# Args of the successful initialize_multihost call, for the idempotence
# guard below (None = never initialised in this process).
_init_args: dict | None = None


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """jax.distributed.initialize with arguments optional (TPU pods
    auto-discover via the metadata service; explicit args for manual
    bring-up). Call once per process, BEFORE first device use.

    Idempotent-or-loud: a repeat call with the SAME arguments is a logged
    no-op (preemptible-restart loops re-run their whole entry point); a
    repeat call with DIFFERENT arguments raises — jax.distributed cannot
    re-wire a live coordinator, and silently keeping the old topology
    would train on the wrong mesh.

    Transient bootstrap faults (a coordinator that is still coming up, a
    DCN blip — the classic pod bring-up race) are RETRIED with backoff
    before the hard failure below: one slow peer must not abort an
    N-host launch (utils/retry.py, seam "multihost.init"; the chaos
    harness injects its timeout at the same seam)."""
    global _init_args
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if _init_args is not None:
        if _init_args == kwargs:
            log.info("multihost already initialised (process %d/%d); no-op",
                     jax.process_index(), jax.process_count())
            return
        raise RuntimeError(
            f"initialize_multihost already ran with {_init_args}; cannot "
            f"re-initialise with {kwargs} — restart the process to change "
            "the distributed topology"
        )
    from ddt_tpu.robustness import faultplan
    from ddt_tpu.utils import retry

    def _attempt() -> None:
        faultplan.inject("multihost.init")
        jax.distributed.initialize(**kwargs)

    try:
        retry.retry_call(
            _attempt, seam="multihost.init",
            # Bootstrap waits are long: few, slow attempts with a pod-
            # bring-up-sized deadline (vs the default I/O policy's 30 s).
            policy=retry.RetryPolicy(attempts=3, base_s=2.0,
                                     multiplier=2.0, jitter=0.5,
                                     deadline_s=120.0))
    except Exception as e:
        raise RuntimeError(
            f"jax.distributed.initialize({kwargs}) failed — check that the "
            "coordinator address is reachable from every process, that "
            "process_id values are unique in [0, num_processes), and that "
            "no JAX device was touched before this call"
        ) from e
    _init_args = kwargs
    log.info(
        "multihost initialised: process %d/%d, %d global devices",
        jax.process_index(), jax.process_count(), len(jax.devices()),
    )
