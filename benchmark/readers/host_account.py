"""The host's side of a scoring call and of set-up, from the program's own
spans alone (the ring of `ddt_tpu.telemetry.annotations`, the host's clock):
where a call WAITED, and what set-up's stages took. No device trace is needed
for any value; where the context holds one, the device's longest idle gaps
are printed beside the host span open at each.

args: {"value": one of
    "upload_wait"    ms a call in `predict:upload:wait`: the chunk loop's
                     blocking wait for the piece before, whose END is the
                     host's time by which that piece had landed. 0.0 where
                     no call of the window has a later piece (one upload a
                     call: nothing to wait for)
    "unnamed"        ms a call of the root's own self-time, the program's
                     `account(root)["self_ns"]["unnamed"]`: Python between
                     the spans, and any pause that fell there
    "setup_ensemble" ms of the `predict:ensemble` span of the model the
                     window scored: the newest one opened before the
                     window's first call (a job builds its model in set-up,
                     or its warm-up call does)}
Returns None (metric left out of the line) where there is nothing to read: a
program without the span or without `account` (the parent of PR 52), roots
that are not the harness's jobs.

The first value asked prints the whole table, once a run: every window
call's account (every span name's self-time, `unnamed` last, summing to the
root span that `window_roots` matched to the harness's wall) with the root's
pause counts; piece 0's arrival by the host's clock beside `call_anatomy`'s
upload exposed; each piece's bytes over (wait end - put start), a lower bound
on the link's rate; the dispatches that blocked (over ten times the call's
fastest); the ensemble span's children; the idle gaps; and every slow call
the program kept.
"""

from __future__ import annotations

import re

from readers import call_anatomy
from readers.call_anatomy import PREFIX, children, program_spans, window_roots

BLOCKED = 10            # a dispatch this many times its call's fastest
PROGRAM = "^jit_predict_raw_effective"


def read(ctx: dict, args: dict):
    if "_host_account" not in ctx:          # three values, one table
        ctx["_host_account"] = table(ctx)
    return ctx["_host_account"].get(args["value"])


def program_calls():
    """(account, slow_calls) of the program, or (None, None) of one from
    before it had them."""
    try:
        from ddt_tpu.telemetry.annotations import account, slow_calls
    except ImportError:
        return None, None
    return account, slow_calls


def table(ctx: dict) -> dict:
    spans = program_spans(ctx)
    if not spans:
        say("the program recorded no spans")
        return {}
    roots = window_roots(ctx, spans)
    if roots is None:
        return {}
    account, slow_calls = program_calls()
    jobs = ctx["jobs"]
    out = {"setup_ensemble": setup_ensemble(spans, roots[0]["start"])}

    waits, later = [], 0
    for r in roots:
        uploads = children(r, "predict:upload")
        later += max(0, len(uploads) - 1)
        waits += [s for s in r["spans"]
                  if s["name"] == PREFIX + "predict:upload:wait"]
    if later == 0:
        say("no call of the window has a later piece: upload_wait 0.0")
        out["upload_wait"] = 0.0
    elif waits:
        out["upload_wait"] = sum(s["end"] - s["start"]
                                 for s in waits) / 1e6 / jobs
    else:
        say(f"{later} later pieces and no predict:upload:wait span: a "
            "program from before the wait was a span")

    if account is None:
        say("the program has no account(): unnamed left out")
    else:
        unnamed = 0
        for r in roots:
            found = account(r)
            total = sum(found["self_ns"].values())
            say(f"call root {r['id']}: {found['duration_ns'] / 1e6:.3f} ms = "
                + " + ".join(f"{k} {v / 1e6:.3f}"
                             for k, v in found["self_ns"].items())
                + f" (sum {total / 1e6:.3f}, apart by "
                f"{total - (r['end'] - r['start']):.0f} ns); pauses: "
                + " ".join(f"{k}={v}" for k, v in r["counts"].items()
                           if k.endswith(("_ns", "_collections"))))
            unnamed += found["self_ns"]["unnamed"]
        out["unnamed"] = unnamed / 1e6 / jobs

    for r in roots:
        pieces(r)
        blocked(r)
    arrival(ctx, roots, jobs)
    if "trace" in ctx:
        idle_gaps(ctx, roots, spans)
    for rec in slow_calls() if slow_calls else ():
        say(f"slow call kept by the program: {rec}")
    if slow_calls and not slow_calls():
        say("slow calls kept by the program: none in "
            f"{len(call_anatomy.roots_named(spans, 'predict'))} calls of "
            "the ring")
    return out


def setup_ensemble(spans: list, window_start: int):
    built = [s for s in spans if s["name"] == PREFIX + "predict:ensemble"
             and s["start"] < window_start]
    if not built:
        say("no predict:ensemble span before the window")
        return None
    mine = built[-1]
    ms = (mine["end"] - mine["start"]) / 1e6
    say(f"{len(built)} predict:ensemble span(s) before the window; the "
        f"newest took {ms:.3f} ms {mine['counts']}")
    inside = sorted((s for s in spans if s["name"].startswith(
        PREFIX + "predict:ensemble:") and mine["start"] <= s["start"]
        and s["end"] <= mine["end"]), key=lambda s: s["start"])
    for s in inside:
        say(f"  {s['name'][len(PREFIX):]} "
            f"{(s['end'] - s['start']) / 1e6:.3f} ms {s['counts']}")
    covered = sum(s["end"] - s["start"] for s in inside
                  if s["cause"] == mine["id"])
    if inside:
        say(f"  its own {ms - covered / 1e6:.3f} ms: the plan, the "
            "modules' first import, the stage registry")
    return ms


def pieces(root: dict) -> None:
    """Each piece's put start, the end of the wait for it, and its bytes
    over the two: no faster than that did the link move it."""
    uploads = sorted(children(root, "predict:upload"),
                     key=lambda s: s["counts"].get("piece", 0))
    waits = {s["counts"].get("piece"): s for s in root["spans"]
             if s["name"] == PREFIX + "predict:upload:wait"}
    for up in uploads:
        p = up["counts"].get("piece", 0)
        # a later piece's put starts when the wait inside its span ends
        put = max([up["start"]] + [w["end"] for w in waits.values()
                                   if w["cause"] == up["id"]])
        w = waits.get(p)
        if w is None:
            continue
        say(f"  root {root['id']} piece {p}: {up['counts'].get('bytes')} B, "
            f"put at +{(put - root['start']) / 1e6:.3f} ms, host side of "
            f"the put {(up['end'] - put) / 1e6:.3f} ms, waited for from "
            f"+{(w['start'] - root['start']) / 1e6:.3f} for "
            f"{(w['end'] - w['start']) / 1e6:.3f} ms, landed by "
            f"+{(w['end'] - root['start']) / 1e6:.3f}: at least "
            f"{up['counts'].get('bytes', 0) / max(w['end'] - put, 1):.3f} "
            "GB/s")


def blocked(root: dict) -> None:
    """The dispatches that blocked, one line a call: those over BLOCKED
    times the call's FASTEST (not its median: where the runtime holds a
    fixed number of programs in flight, most dispatches of a long call
    block and the median is one of them)."""
    dispatch = children(root, "predict:dispatch")
    if len(dispatch) < 3:
        return
    took = [s["end"] - s["start"] for s in dispatch]
    fastest = max(min(took), 1)
    late = [(s["counts"].get("chunk"), t) for s, t in zip(dispatch, took)
            if t > BLOCKED * fastest]
    if late:
        say(f"  root {root['id']}: {len(late)} of {len(dispatch)} dispatches "
            f"blocked (over {BLOCKED} x the fastest, {fastest / 1e6:.3f} "
            f"ms), the first at chunk {late[0][0]}, "
            f"{sum(t for _, t in late) / 1e6:.3f} ms together, the longest "
            f"{max(t for _, t in late) / 1e6:.3f} ms (chunk "
            f"{max(late, key=lambda x: x[1])[0]})")


def arrival(ctx: dict, roots: list, jobs: int) -> None:
    """Piece 0's arrival by the host's clock, a call: the end of the wait
    for it minus the first upload's start, beside what `call_anatomy` reads
    of the same stretch through the device trace."""
    landed = []
    for r in roots:
        first = children(r, "predict:upload")[:1]
        wait = [s for s in r["spans"]
                if s["name"] == PREFIX + "predict:upload:wait"
                and s["counts"].get("piece") == 0]
        if first and wait:
            landed.append((wait[0]["end"] - first[0]["start"]) / 1e6)
    if not landed:
        return
    exposed = None
    if "trace" in ctx:
        exposed = call_anatomy.read(ctx, {"segment": "upload_exposed"})
    say(f"piece 0 had landed (its reshape run) by {sum(landed) / jobs:.3f} "
        "ms after the first upload's start, a call ("
        + " ".join(f"{v:.3f}" for v in landed) + "), by the host's clock; "
        "call_anatomy's upload exposed (to the first device op, through the "
        f"causal anchor) {exposed}")


def idle_gaps(ctx: dict, roots: list, spans: list, top: int = 10) -> None:
    """The device's longest idle gaps of the window, each with the innermost
    host span open at its start and at its end, through `call_anatomy`'s
    own clock offset."""
    trace = ctx["trace"]
    dev = trace.devices[0]
    match = re.compile(PROGRAM)
    executions = sorted((m for m in trace.modules[dev] if match.search(m[0])),
                        key=lambda m: m[1])
    rows = call_anatomy.chunk_bounds(roots, executions)
    d = call_anatomy.clock_offset(rows) if rows else None
    if d is None:
        say("no clock offset: the idle gaps keep no host span")
        return
    gaps, end = [], None
    for o in sorted(trace.ops[dev], key=lambda o: o.start):
        if end is not None and o.start > end:
            gaps.append((o.start - end, end, o.start))
        end = o.start + o.dur if end is None else max(end, o.start + o.dur)

    def open_at(t: float) -> str:
        inside = [s for s in spans if s["start"] <= t < s["end"]]
        if not inside:
            return "no span (between calls)"
        s = max(inside, key=lambda s: (s["start"], s["id"]))
        return s["name"][len(PREFIX):] + "".join(
            f"[{k} {s['counts'][k]}]" for k in ("chunk", "piece")
            if k in s["counts"])

    for width, a, b in sorted(gaps, reverse=True)[:top]:
        say(f"  idle {width / 1e3:.1f} us: host in {open_at(a + d)} at its "
            f"start, {open_at(b + d)} at its end")


def say(msg: str) -> None:
    print("host_account: " + msg, flush=True)
