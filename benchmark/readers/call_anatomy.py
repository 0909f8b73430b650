"""The anatomy of a scoring call: the program's own host spans (the ring of
`ddt_tpu.telemetry.annotations`, times by `time.perf_counter_ns()`) laid over
the device trace, so that the time in which no device operation ran has names.

args: {"segment": "prologue" | "upload_exposed" | "fetch_tail",
       "program": regex on the scoring program's name in `trace.modules`}
Returns the segment in ms a call, or None (metric left out of the line) where
there is nothing to read: a program without spans (the parent of PR 25),
fewer root spans than jobs, roots that are not the harness's jobs, or, for the
two segments that need both clocks, clocks that cannot be aligned.

Per root span `ddt:predict` of the window (the last `ctx["jobs"]` of them):

    prologue        root start -> `predict:upload` start          host clock
    upload exposed  `predict:upload` start -> first device op     both clocks
    interior idle   gaps between the call's first and last op     device clock
    fetch tail      last device op's end -> root end              both clocks
    outside         between roots, root edges to the window span  host clock

The five sum to the window's span minus the device's busy time, which is what
`score_offdevice_ms` reads from outside.

The clocks. `Op.start` is in the profile's nanoseconds, counted from the start
of the profiler session (looked at on the chip, PR 25), so the offset `d`
(device ns + d = perf_counter_ns) is anchored causally: the k-th execution of
the scoring program inside a call is chunk k; its first device operation
cannot start before `predict:dispatch[k]` started (so d >= d_lo), and
`predict:fetch[k]` cannot end before its last device operation ended (so
d <= d_hi). d = d_hi: the fetch of the fastest chunk returns a D2H-and-wake
latency after the device finished, so d is high by that latency (milliseconds,
against segments of hundreds); upload exposed reads that much too long and the
fetch tail that much too short, their sum exact. d_lo > d_hi means some chunk
breaks an inequality under every offset: not aligned, and the reader says
which chunks. The spans come from `ctx["program_spans"]` when a test gives
them and from the program otherwise.
"""

from __future__ import annotations

import re
import statistics

import tracefile

PREFIX = "ddt:"
SEGMENTS = ("prologue", "upload_exposed", "interior_idle", "fetch_tail",
            "outside")
NEEDS_BOTH_CLOCKS = ("upload_exposed", "fetch_tail")


def read(ctx: dict, args: dict):
    if "_call_anatomy" not in ctx:          # three metrics, one table
        ctx["_call_anatomy"] = anatomy(ctx, args.get(
            "program", "^jit_predict_raw_effective"))
    found = ctx["_call_anatomy"]
    return None if found is None else found.get(args["segment"])


def program_spans(ctx: dict):
    """Finished spans as dicts (name, id, cause, root, start, end, counts),
    oldest first; None where the program records none."""
    if "program_spans" in ctx:
        return sorted(ctx["program_spans"], key=lambda s: s["start"])
    try:
        from ddt_tpu.telemetry.annotations import recent_spans
    except ImportError:
        return None
    return recent_spans()


def roots_named(spans: list, name: str) -> list:
    """Root spans `ddt:<name>`, oldest first, each with its own spans."""
    by_root: dict = {}
    for s in spans:
        by_root.setdefault(s["root"], []).append(s)
    return [dict(s, spans=by_root[s["id"]]) for s in spans
            if s["id"] == s["root"] and s["name"] == PREFIX + name]


def window_roots(ctx: dict, spans: list):
    """The window's calls: the last `jobs` roots `ddt:predict`, each a
    little shorter than the harness's wall of the same job."""
    roots = roots_named(spans, "predict")[-ctx["jobs"]:]
    if len(roots) < ctx["jobs"]:
        say(f"{len(roots)} root spans ddt:predict for {ctx['jobs']} jobs")
        return None
    for r, wall in zip(roots, ctx["walls"]):
        own = (r["end"] - r["start"]) / 1e9
        if not 0.0 <= wall - own <= 0.01 * wall:
            say(f"root span {r['id']} took {own:.6f} s, the harness's job "
                f"{wall:.6f} s: not the same call")
            return None
    return roots


def children(root: dict, name: str) -> list:
    """The root's spans `ddt:<name>`, by their `chunk` count, then start."""
    return sorted((s for s in root["spans"] if s["name"] == PREFIX + name),
                  key=lambda s: (s["counts"].get("chunk", 0), s["start"]))


def self_ns(span: dict, spans: list) -> float:
    """Duration minus what the span's own child spans cover."""
    kids = [(s["start"], s["end"]) for s in spans if s["cause"] == span["id"]]
    return span["end"] - span["start"] - tracefile.union_ns(kids)


def chunk_bounds(roots: list, executions: list):
    """[(root, k, dispatch span, fetch span, device start, device end)] in
    order, or None where spans and executions do not pair up."""
    rows, at = [], 0
    for r in roots:
        dispatch = children(r, "predict:dispatch")
        fetch = children(r, "predict:fetch")
        n = len(dispatch)
        if n == 0 or len(fetch) != n:
            say(f"root {r['id']}: {n} dispatch spans, {len(fetch)} fetch "
                "spans: not the chunk loop this reader knows")
            return None
        for k in range(n):
            if at >= len(executions):
                say(f"{len(executions)} executions of the scoring program "
                    f"for {at + n - k} more chunks")
                return None
            _, start, dur = executions[at]
            rows.append((r, k, dispatch[k], fetch[k], start, start + dur))
            at += 1
    if at != len(executions):
        say(f"{len(executions)} executions of the scoring program, the "
            f"window's spans name {at} chunks")
        return None
    return rows


def clock_offset(rows: list):
    """d with device ns + d = perf_counter_ns, or None (see the top)."""
    lo = max(rows, key=lambda w: w[2]["start"] - w[4])
    hi = min(rows, key=lambda w: w[3]["end"] - w[5])
    d_lo = lo[2]["start"] - lo[4]
    d_hi = hi[3]["end"] - hi[5]
    if d_lo > d_hi:
        say(f"clocks NOT aligned: chunk {lo[1]} of root {lo[0]['id']} needs "
            f"d >= {d_lo:.0f} ns (its device work cannot start before its "
            f"dispatch), chunk {hi[1]} of root {hi[0]['id']} needs d <= "
            f"{d_hi:.0f} ns (its fetch cannot end before its device work): "
            f"no offset satisfies both, short by {(d_lo - d_hi) / 1e6:.3f} "
            "ms; the shared-clock segments are left out")
        return None
    res = sorted(((w[3]["end"] - w[5] - d_hi) / 1e6, w[1]) for w in rows)
    slack = min((w[4] + d_hi - w[2]["start"]) / 1e6 for w in rows)
    say(f"clock: device ns + {d_hi:.0f} = perf_counter_ns, anchored "
        f"causally on {len(rows)} chunks: fetch[k] cannot end before chunk "
        f"k's device work, and chunk {hi[1]} of root {hi[0]['id']} is the "
        "tightest. Residuals fetch end - device end, ms: the five smallest "
        + " ".join(f"{r:.3f} (chunk {k})" for r, k in res[:5])
        + f"; median {statistics.median(r for r, _ in res):.3f}, max "
        f"{res[-1][0]:.3f} (a chunk fetched long after it finished says "
        "nothing; the smallest agree to within the D2H's jitter). Device "
        f"start - dispatch start: at least {slack:.3f} ms for every chunk. "
        "d is high by the tightest chunk's D2H-and-wake latency, "
        f"milliseconds; no lower than {d_lo:.0f} "
        f"({(d_hi - d_lo) / 1e6:.3f} ms less) satisfies every chunk")
    return d_hi


def anatomy(ctx: dict, program: str):
    spans = program_spans(ctx)
    if not spans:
        say("the program recorded no spans")
        return None
    roots = window_roots(ctx, spans)
    if roots is None:
        return None
    trace, jobs = ctx["trace"], ctx["jobs"]
    dev = trace.devices[0]
    ops = sorted(trace.ops[dev], key=lambda o: o.start)
    match = re.compile(program)
    executions = sorted((m for m in trace.modules[dev] if match.search(m[0])),
                        key=lambda m: m[1])
    rows = chunk_bounds(roots, executions)
    d = clock_offset(rows) if rows else None

    seg = {name: 0.0 for name in SEGMENTS}      # ns, summed over the calls
    walls = 0.0
    for r in roots:
        upload = children(r, "predict:upload")
        if not upload:
            say(f"root {r['id']} has no predict:upload span")
            return None
        walls += r["end"] - r["start"]
        seg["prologue"] += upload[0]["start"] - r["start"]
        if d is None:
            continue
        mine = [o for o in ops
                if r["start"] <= o.start + d and o.start + o.dur + d
                <= r["end"]]
        if not mine:
            say(f"no device operation inside root {r['id']}")
            return None
        first = mine[0].start
        last = max(o.start + o.dur for o in mine)
        busy = tracefile.union_ns([(o.start, o.start + o.dur) for o in mine])
        seg["upload_exposed"] += first + d - upload[0]["start"]
        seg["interior_idle"] += last - first - busy
        seg["fetch_tail"] += r["end"] - (last + d)
        describe(r, first + d, last + d)
    seg["outside"] = ctx["span"] * 1e9 - walls

    out = {k: v / 1e6 / jobs for k, v in seg.items()}
    outside_in = (ctx["span"] - trace.busy_s) * 1e3 / jobs
    if d is None:
        for name in NEEDS_BOTH_CLOCKS + ("interior_idle",):
            out[name] = None
        say(f"prologue {out['prologue']:.3f} ms and outside "
            f"{out['outside']:.3f} ms a call (host clock only)")
        return out
    total = sum(out.values())
    say("anatomy, ms a call: " + ", ".join(f"{k} {out[k]:.3f}"
                                           for k in SEGMENTS)
        + f"; sum {total:.3f} beside span minus busy {outside_in:.3f} "
        f"(score_offdevice_ms), apart by {total - outside_in:+.3f}")
    return out


def describe(root: dict, first_host: float, last_host: float) -> None:
    """One call: what its host spans did inside each segment."""
    c = root["counts"]
    say(f"call root {root['id']}: {(root['end'] - root['start']) / 1e6:.3f} "
        f"ms, {c.get('rows')} rows in {c.get('chunks')} chunks, branch "
        f"{c.get('branch')}; root self time "
        f"{self_ns(root, root['spans']) / 1e6:.3f} ms; counters over the "
        "call: " + " ".join(f"{k}={v}" for k, v in c.items()
                            if k not in ("rows", "chunks", "branch")))
    upload = children(root, "predict:upload")[0]
    edges = (("prologue", root["start"], upload["start"]),
             ("upload_exposed", upload["start"], first_host),
             ("between first and last device op", first_host, last_host),
             ("fetch_tail", last_host, root["end"]))
    for label, a, b in edges:
        inside: dict = {}
        for s in root["spans"]:
            if s["id"] == root["id"]:
                continue
            cover = min(b, s["end"]) - max(a, s["start"])
            if cover > 0:
                n, t = inside.get(s["name"], (0, 0.0))
                inside[s["name"]] = (n + 1, t + cover)
        say(f"  {label} {(b - a) / 1e6:.3f} ms: "
            + (", ".join(f"{k[len(PREFIX):]} x{n} {t / 1e6:.3f} ms"
                         for k, (n, t) in inside.items()) or "no span"))
    for name in ("predict:dispatch", "predict:fetch"):
        longest = sorted(children(root, name),
                         key=lambda s: s["start"] - s["end"])[:3]
        say(f"  longest {name}: " + ", ".join(
            f"chunk {s['counts'].get('chunk')} "
            f"{(s['end'] - s['start']) / 1e6:.3f} ms" for s in longest))
    up_bytes = upload["counts"].get("bytes", 0)
    if first_host > upload["start"]:
        say(f"  upload: {up_bytes} B; host side of device_put "
            f"{(upload['end'] - upload['start']) / 1e6:.3f} ms; to the first "
            f"device op {up_bytes / (first_host - upload['start']):.3f} GB/s")
    fetch = children(root, "predict:fetch")
    concat = children(root, "predict:concat")
    if fetch:
        f_bytes = sum(s["counts"].get("bytes", 0) for s in fetch)
        f_ns = fetch[-1]["end"] - fetch[0]["start"]
        say(f"  fetch: {f_bytes} B in {len(fetch)} spans over "
            f"{f_ns / 1e6:.3f} ms ({f_bytes / f_ns:.3f} GB/s, device work "
            "overlapping); the last span ends "
            f"{(fetch[-1]['end'] - last_host) / 1e6:.3f} ms after the last "
            "device op")
    for s in concat:
        ns = s["end"] - s["start"]
        say(f"  concat: {s['counts'].get('bytes', 0)} B in {ns / 1e6:.3f} ms "
            f"({s['counts'].get('bytes', 0) / ns:.3f} GB/s)")


def say(msg: str) -> None:
    print("call_anatomy: " + msg, flush=True)
