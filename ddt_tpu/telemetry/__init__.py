"""Structured telemetry (SURVEY.md §5 "Metrics/logging/observability").

The reference system's operational story is per-phase visibility into the
hist / allreduce / gain / predict pipeline. This package is that story for
the reproduction, in three always-available layers (zero overhead when no
run log is attached — the hot loops never sync, never touch a file, and
pay at most a handful of host integer adds):

- events   — schema-versioned JSONL run logs (`RunLog`): run manifest,
             per-round records, per-phase timings, early-stop decisions,
             fault/recovery events, device counters. An in-memory ring
             buffer mirrors the file so tests (and callers without a
             filesystem) can read events back without parsing JSONL.
- counters — process-wide device counters: jit recompiles (via a
             jax.monitoring listener on the backend-compile duration
             event), host↔device transfer bytes, estimated collective
             payload bytes, device-memory high-water marks.
- annotations — jax.profiler.TraceAnnotation / jax.named_scope wrappers
             that give host PhaseTimer phases and device Perfetto
             timelines the SAME `ddt:<phase>` names, so a trace captured
             with --trace-dir aligns with the run log's phase breakdown.

Since the distributed flight recorder (schema v2) two more consumers sit
on the same stream:

- merge    — joins N per-host JSONL logs of one pod run into a single
             host-0-clock timeline (run_id join key, manifest-estimated
             clock offsets).
- perfetto — converts a (possibly merged) log into Chrome trace-event
             JSON loadable in ui.perfetto.dev: round slices, per-device
             partition lanes, instant markers.

And mesh runs with a run log attached additionally record per-partition
phase completion times (`partition_phases` per round, a `partition_skew`
straggler reduction at run end — events.PartitionRecorder).

Since the device-truth cost observatory (schema v3) three more:

- costmodel — XLA compiled-executable cost/memory analysis captured at
             each jit entry point's first compile (telemetry runs only),
             emitted as `cost_analysis` events and joined against phase
             wall-times into the report's roofline table with a bound-by
             verdict (compute / HBM / recompile / host).
- profiler — programmatic jax.profiler capture windows around a selected
             round range (`train --xprof-dir --xprof-rounds`), cross-
             referenced to the run log through the manifest's
             xprof_dir/xprof_rounds extras and the run_id-named trace dir.
- diffing  — `cli report diff A B`: per-phase / per-counter deltas with
             one-sided excursion flags ("gain +34%, jit_compiles
             12→48, hist bytes-accessed x2.1").

`report` renders a run summary from a JSONL log (`python -m ddt_tpu.cli
report --log run.jsonl`, repeat --log to merge hosts); `trace` exports
the Perfetto JSON; docs/OBSERVABILITY.md documents the schema and
workflow.
"""

from ddt_tpu.telemetry.events import (  # noqa: F401
    EVENT_FIELDS, SCHEMA_VERSION, PartitionRecorder, RoundRecorder,
    RunLog, derive_run_id, partition_skew_summary, validate_event)
from ddt_tpu.telemetry import counters  # noqa: F401
from ddt_tpu.telemetry.annotations import phase_span  # noqa: F401
