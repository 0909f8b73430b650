"""Device time of the operations a regex names, in ms per tree / call / job.

args: {"match": regex on the HLO instruction name (`%ddt_hist_stream.41`),
       "minus": optional regex taken out of the match,
       "module": optional regex on the program (`jit_rounds(...)`),
       "per": a divisor the job gives ("trees", "calls", "jobs")}
Nothing matched -> None (the metric is left out of the line).
"""


def read(ctx: dict, args: dict):
    seconds, n = ctx["trace"].matched_s(args["match"], args.get("minus"),
                                        args.get("module"))
    if n == 0:
        return None
    return seconds * 1e3 / ctx["divisors"][args.get("per", "jobs")]
