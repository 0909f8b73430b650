"""What the process's first scoring call cost beyond a steady one, in ms: the
first root span `ddt:predict` of the process (the harness's warm-up call)
minus the median root of the window. It is the part of set-up that only the
program can shorten: tracing and lowering (which no compile cache skips),
compiling or loading the programs, building and uploading the ensemble.

args: none. None where the program records no spans, the window's roots are
not the harness's jobs, or the first root has left the ring: span ids are
handed out from 1 in the order spans open and the ring drops the oldest
finished first, so a ring that no longer holds id 1 has dropped something and
its oldest root may not be the process's first.
"""

from __future__ import annotations

import statistics

from readers.call_anatomy import (children, program_spans, roots_named,
                                  window_roots)


def read(ctx: dict, args: dict):
    spans = program_spans(ctx)
    if not spans:
        say("the program recorded no spans")
        return None
    window = window_roots(ctx, spans)
    if window is None:
        return None
    if min(s["id"] for s in spans) != 1:
        say("span 1 has left the ring: the process's first call cannot be "
            "told from a later one")
        return None
    first = roots_named(spans, "predict")[0]
    if any(first["id"] == r["id"] for r in window):
        say("the first root is a job of the window: no warm-up call to read")
        return None
    steady = statistics.median(r["end"] - r["start"] for r in window)
    extra = (first["end"] - first["start"] - steady) / 1e6
    c = first["counts"]
    say(f"first call {(first['end'] - first['start']) / 1e6:.3f} ms, median "
        f"of the window's {len(window)} {steady / 1e6:.3f} ms, extra "
        f"{extra:.3f} ms; over the first call: " + " ".join(
            f"{k}={c.get(k)}" for k in (
                "jit_trace_seconds", "jit_lower_seconds",
                "jit_compile_seconds", "jit_compiles", "compile_cache_hits",
                "compiled_ensemble_cache_hits")))
    for name in ("predict:token", "predict:ensemble"):
        for s in children(first, name):
            say(f"  first call's {name} {(s['end'] - s['start']) / 1e6:.3f} "
                f"ms {s['counts']}")
    return extra


def say(msg: str) -> None:
    print("first_call_extra: " + msg, flush=True)
