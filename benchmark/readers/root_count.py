"""A count that the program writes on its root span `ddt:predict`, summed over
the window's calls, a call.

args: {"count": the key in the root span's counts, "scale": divide by this
       (1e6: bytes -> MB)}
None (metric left out of the line) where the program records no spans, the
window's roots are not the harness's jobs, or no root of the window carries
the count (a program older than the count).
"""

from __future__ import annotations

from readers.call_anatomy import program_spans, window_roots


def read(ctx: dict, args: dict):
    spans = program_spans(ctx)
    if not spans:
        say("the program recorded no spans")
        return None
    roots = window_roots(ctx, spans)
    if roots is None:
        return None
    found = [r["counts"][args["count"]] for r in roots
             if args["count"] in r["counts"]]
    if len(found) != len(roots):
        say(f"{len(found)} of the window's {len(roots)} roots carry "
            f"{args['count']!r}")
        return None
    value = sum(found) / ctx["jobs"] / float(args.get("scale", 1))
    say(f"{args['count']} over {len(roots)} calls: "
        + " ".join(str(v) for v in found) + f" -> {value} a call")
    return value


def say(msg: str) -> None:
    print("root_count: " + msg, flush=True)
