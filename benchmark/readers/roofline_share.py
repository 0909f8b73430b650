"""A kernel's share of its roofline: the least time the chip could take for
the traced jobs' work over the time the kernel's events took, in %.

args: {"match"/"minus"/"module": as trace_scope_ms,
       "opcount": a function -> (operations, bytes) of ONE job, found in
       "opcount_module" (default opcount.py; a later kernel's count is a new
       module beside it, under benchmark/)}
The least time is the larger of operations / peak FLOP/s and bytes / peak
bytes/s (peaks.json, by device kind); which of the two bounds is printed. A
share over 100 % means the count or the reader is wrong: the run fails.
"""

import importlib


def read(ctx: dict, args: dict):
    seconds, n = ctx["trace"].matched_s(args["match"], args.get("minus"),
                                        args.get("module"))
    if n == 0:
        return None
    counts = importlib.import_module(args.get("opcount_module", "opcount"))
    ops, nbytes = getattr(counts, args["opcount"])(ctx["shapes"])
    t_ops = ops * ctx["jobs"] / ctx["peaks"]["flops_per_s"]
    t_mem = nbytes * ctx["jobs"] / ctx["peaks"]["bytes_per_s"]
    share = 100.0 * max(t_ops, t_mem) / seconds
    print(f"roofline {args['opcount']}: {ops:.4g} ops and {nbytes:.4g} B a "
          f"job x {ctx['jobs']} jobs -> {t_ops * 1e3:.3f} ms of matmul, "
          f"{t_mem * 1e3:.3f} ms of HBM at peak; bound by "
          f"{'compute' if t_ops >= t_mem else 'memory'}; kernel took "
          f"{seconds * 1e3:.3f} ms in {n} events -> {share:.3f} %", flush=True)
    if share > 100.0:
        raise RuntimeError(f"roofline share {share:.1f} % > 100 %: the "
                           "operation count or the reader is wrong")
    return share
