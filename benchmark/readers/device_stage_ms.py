"""Device time of a scoring call by the STAGE of the program its operations
belong to, in ms a call.

A device event is named by its HLO instruction (`%fusion.1`, `%copy.5`:
tracefile.py), a name the compiler chose and renumbers with every change of
the program. What the instruction IS the program says itself:
`ddt_tpu.telemetry.annotations.device_stages()` gives, for each program the
scoring call runs, `{instruction: {"stage", "source", "op"}}` read from the
optimized HLO of the executable that ran (the `ddt:predict:*` named scopes in
its metadata), and the whole of the chunk loop's two small programs under the
entry `"*"`. This reader looks every leaf operation of the traced window up
there by (program, instruction). An operation the map does not hold reads
`unscoped`: an instruction the program traced outside every stage, a program
it did not name, or a map made from another executable of the same name
(another model or row count), and the table says which.

args: {"stage": regex on the stage (`^predict:widen$`, `^unscoped$`), or
       "not": regex, every stage it does NOT match, `unscoped` included,
       "program": regex on the scoring program whose map must be there
                  (default `^jit_predict_raw_effective`),
       "per": a divisor the job gives (default "calls")}
Returns ms per divisor, 0.0 where no operation of the window is in the
selected stages, or None (metric left out of the line) where the program
names no device stages: the parent of PR 35, or a model whose scoring
program it does not map. The map comes from `ctx["device_stages"]` when a
test gives it.

Every metric of a run reads ONE table, printed once: stage, ms a call,
events, the three largest instructions of each stage with what they are and
the source line that traced them; and the stages' sum beside the device's
busy time in the window, which it equals unless operations overlap.
"""

from __future__ import annotations

import re
import time

import tracefile

UNSCOPED = "unscoped"
WHOLE_PROGRAM = "*"


def read(ctx: dict, args: dict):
    if "_device_stage_ms" not in ctx:       # four metrics, one table
        ctx["_device_stage_ms"] = table(ctx, args.get(
            "program", "^jit_predict_raw_effective"))
    by_stage = ctx["_device_stage_ms"]
    if by_stage is None:
        return None
    if "not" in args:
        out = re.compile(args["not"])
        ms = sum(v for s, v in by_stage.items() if not out.search(s))
    else:
        keep = re.compile(args["stage"])
        ms = sum(v for s, v in by_stage.items() if keep.search(s))
    return ms / ctx["divisors"][args.get("per", "calls")]


def program_stages(ctx: dict):
    """`{program: {instruction: {"stage", "source", "op"}}}`, or None where
    the program names no device stages. Asking is what makes the program
    build its map (after the window: run.py reads the trace last), so what
    that costs is said here, with the compile listener's movement."""
    if "device_stages" in ctx:
        return ctx["device_stages"]
    try:
        from ddt_tpu.telemetry import counters
        from ddt_tpu.telemetry.annotations import device_stages
    except ImportError:
        return None
    c0, t0 = counters.snapshot(), time.perf_counter()
    stages = device_stages()
    moved = counters.delta(c0)
    say(f"device_stages() took {time.perf_counter() - t0:.3f} s for "
        f"{len(stages)} programs; over it " + " ".join(
            f"{k}={moved.get(k, 0):.3f}" for k in (
                "jit_compiles", "compile_cache_hits", "jit_trace_seconds",
                "jit_lower_seconds", "jit_compile_seconds")))
    return stages


def table(ctx: dict, program: str):
    """{stage: ms over the window, averaged over the chips}, printed."""
    stages = program_stages(ctx)
    if not stages or not any(re.search(program, p) for p in stages):
        say("the program names no device stages"
            + (f" for a program matching {program!r} (it names "
               f"{sorted(stages)})" if stages else ""))
        return None
    trace = ctx["trace"]
    chips = max(1, len(trace.ops))
    calls = ctx["divisors"].get("calls", ctx["jobs"])
    ns: dict = {}           # stage -> [ns, events, {(program, name): ns}]
    unmapped: dict = {}     # program -> [ns, events, names]: not in the map
    for ops in trace.ops.values():
        for o in ops:
            prog = tracefile.program(o.module)
            held = stages.get(prog, {})
            entry = held.get(o.name) or held.get(WHOLE_PROGRAM)
            if entry is None:
                lost = unmapped.setdefault(prog, [0.0, 0, set()])
                lost[0] += o.dur
                lost[1] += 1
                lost[2].add(o.name)
            row = ns.setdefault(entry["stage"] if entry else UNSCOPED,
                                [0.0, 0, {}])
            row[0] += o.dur
            row[1] += 1
            row[2][prog, o.name] = row[2].get((prog, o.name), 0.0) + o.dur

    def ms(x: float) -> float:
        return x / chips / 1e6 / calls

    say(f"ms a call over {calls} calls; the map holds "
        + ", ".join(f"{p} ({len(m)})" for p, m in sorted(stages.items())))
    say(f"{'stage':<24s} {'ms a call':>12s} {'events':>7s}  the largest "
        "instructions: ms a call, what, traced at")
    for stage, (total, events, names) in sorted(ns.items(),
                                                key=lambda kv: -kv[1][0]):
        top = sorted(names.items(), key=lambda kv: -kv[1])[:3]
        say(f"{stage:<24s} {ms(total):12.3f} {events:7d}  " + "; ".join(
            f"{name} {ms(t):.3f} {describe(stages, prog, name)}"
            for (prog, name), t in top))
    for prog, (total, events, names) in sorted(unmapped.items()):
        why = ("the map of this name was made from another executable"
               if prog in stages else "a program the map does not name")
        say(f"NOT in the map, read as {UNSCOPED}: {events} events, "
            f"{ms(total):.3f} ms a call of {len(names)} instructions in "
            f"{prog} ({why}): " + " ".join(sorted(names)[:8]))
    total = sum(ms(row[0]) for row in ns.values())
    busy = trace.busy_s * 1e3 / calls
    say(f"the stages sum to {total:.3f} ms a call beside the device's busy "
        f"time {busy:.3f}, apart by {total - busy:+.3f}")
    return {stage: row[0] / chips / 1e6 for stage, row in ns.items()}


def describe(stages: dict, prog: str, name: str) -> str:
    held = stages.get(prog, {})
    entry = held.get(name) or held.get(WHOLE_PROGRAM)
    if entry is None:
        return f"in {prog}, not in the map"
    what = entry.get("op", "")
    if held.get(name) is None:
        what = f"in {prog}"
    return f"{what} {entry.get('source') or 'no source line'}"


def say(msg: str) -> None:
    print("device_stage_ms: " + msg, flush=True)
