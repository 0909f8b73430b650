"""benchwatch — the bench-artifact regression sentinel (`make benchwatch`).

The consumer of BENCH_r*.json / MULTICHIP_r*.json artifacts. It ingests
the artifact history plus a "current" run, computes a robust per-metric band (median ± the
larger of K·MAD and a relative floor), and exits nonzero when the
current run sits ADVERSELY outside the band — a one-sided check, so a
pleasantly fast run never fails the gate.

Why median/MAD with a relative floor instead of mean/σ or MAD alone:
(a) the mean is polluted by outliers a median shrugs off, and (b) with
~5 samples that happen to land close together the raw MAD collapses
toward zero and would flag ordinary run-to-run spread — the REL_FLOOR
(default 20% of the median; the chip's own spread is not measured yet)
keeps the gate wide while a real 30% regression still trips it. Metrics with fewer than MIN_HISTORY samples are
reported as skipped, never guessed at.

Artifact shapes accepted (load_artifact):
- driver-harness wrappers: {"n": .., "rc": .., "tail": .., "parsed":
  {metrics...}} — BENCH_r*.json;
- raw bench.py output: the metrics dict itself (has "metric"/"value");
- multichip dryrun records: {"n_devices", "rc", "ok", "skipped",
  "tail"} — checked as pass/fail facts (ok must be true, rc 0), not
  banded.

Metric directions are EXPLICIT (METRICS below): an unknown numeric
field is skipped, never auto-classified — silently banding a field
whose good direction we guessed wrong would invert the gate. Ordering:
artifacts sort by the harness round number (the wrapper's `n` field,
falling back to the rNN in the filename; a raw bench.py output has
neither and sorts first — point the gate at it with --current, which
is the intended mode for a fresh run). The run_id/git_rev stamps
bench.py writes are identity/provenance — a flagged excursion names
the rev it appeared at — not the sort key.
"""

from __future__ import annotations

import glob as _glob
import json
import os
import re

#: metric -> direction whose LOSS is a regression.
#: "higher": smaller-than-band current value fails; "lower": larger fails.
METRICS: dict[str, str] = {
    "value": "higher",                               # hist Mrows/s/chip
    "vs_baseline": "higher",
    "hist_one_dispatch_mrows_per_sec": "higher",
    "hist_one_dispatch_mrows_per_sec_min": "higher",
    "value_64bin_optin": "higher",
    "ab_ratio_64bin": "higher",
    # hist_fused_roofline_hbm_util is context-only (NOT banded) for the
    # same reason as hist_roofline_hbm_util below: lowering the fused
    # round's bytes-accessed is the design direction, so a drop is an
    # improvement and a "higher" band would invert the gate.
    "hist_fused_mrows_per_sec": "higher",
    "hist_fused_ab_ratio": "higher",
    "hist_fused_roofline_flops_util": "higher",
    # Split-comms A/B (ISSUE 10): losing the reduce-scatter wallclock
    # edge, the scattered arm's throughput, or the deterministic payload
    # reduction are all regressions.
    "hist_comms_ab_ratio": "higher",
    "hist_comms_rs_mrows_per_sec": "higher",
    "hist_comms_payload_ratio": "higher",
    # 2D-mesh A/B (ISSUE 11): losing the (rows x features) layout's
    # wallclock edge at the wide shape, the 2D arm's throughput, or the
    # deterministic second-axis payload reduction are all regressions.
    "hist_2d_ab_ratio": "higher",
    "hist_2d_mrows_per_sec": "higher",
    "hist_2d_payload_ratio": "higher",
    # Quantized-gradient A/B (ISSUE 14): paired f32/int8 wallclock
    # ratio, the quantized arm's throughput, and the deterministic g/h
    # HBM-stream byte ratio — all better when higher.
    "hist_quant_ab_ratio": "higher",
    "hist_quant_mrows_per_sec": "higher",
    "hist_quant_payload_ratio": "higher",
    "e2e_train_s": "lower",
    "e2e_ms_per_tree": "lower",
    "e2e_implied_hist_mrows": "higher",
    "predict_mrows_per_sec": "higher",
    "predict_total_s": "lower",
    "predict_compute_mrows_per_sec": "higher",
    "predict_pallas_mrows_per_sec": "higher",
    "predict_onehot_mrows_per_sec": "higher",
    "predict_pallas_ab_ratio": "higher",
    # Roofline utilization stamps (cost observatory): achieved/peak
    # fractions from XLA's cost model at the measured wallclock — losing
    # utilization is a regression even when absolute throughput spread
    # hides it. hist_roofline_hbm_util is
    # deliberately NOT banded since bench schema v2: the VMEM-streaming
    # histogram kernel LOWERS bytes-accessed by design (the hist verdict
    # flipping hbm -> compute is the kernel campaign's goal), so a drop
    # against pre-rewrite history is the fix landing, not a regression;
    # flops_util stays the banded hist signal.
    "hist_roofline_flops_util": "higher",
    "predict_roofline_flops_util": "higher",
    "predict_roofline_hbm_util": "higher",
    "split_agreement": "higher",
    "auc_delta": "lower",
    # Serving tier (ISSUE 8): LATENCY IS LOWER-IS-BETTER — the first
    # metrics in this table whose regression direction is a rise in
    # milliseconds, stamped from bench_serve_latency's headline QPS
    # point. serve_cold_over_p99 (the acceptance ratio) and the
    # coalesce width band higher: losing either means the admission
    # batcher degenerated even if absolute latency drift hides it.
    # serve_cold_predict_ms is context only (NOT banded): it measures
    # first-call compile cost, which jax version bumps legitimately
    # move in either direction.
    "serve_p50_ms": "lower",
    "serve_p99_ms": "lower",
    "serve_p999_ms": "lower",
    "serve_cold_over_p99": "higher",
    "serve_coalesce_mean": "higher",
    "serve_coalesce_max": "higher",
    # Quantized LUT arm (chip artifacts): throughput and the paired
    # ratio band higher; the witnessed max-abs-error bands LOWER — a
    # quantizer change that widens real error past its documented bound
    # already asserts in-bench, but a creeping (still-in-bound) rise is
    # exactly what a band catches.
    "predict_lut_mrows_per_sec": "higher",
    "predict_lut_ab_ratio": "higher",
    "predict_lut_max_abs_err": "lower",
    # int4 bit-packed tier + express lane (ISSUE 12): same sign
    # conventions — tier throughput/paired-ratio band higher, the
    # witnessed error bands lower, and the express lane's single-row
    # latencies band lower next to the other serve_* milliseconds.
    # express_gain (coalesced-over-express at an empty queue) bands
    # higher: losing it means the lane stopped bypassing the admission
    # window even if absolute latency drift hides it.
    "predict_lut4_mrows_per_sec": "higher",
    "predict_lut4_ab_ratio": "higher",
    "predict_lut4_max_abs_err": "lower",
    "serve_express_empty_p99_ms": "lower",
    "serve_express_saturated_p99_ms": "lower",
    "serve_coalesced_saturated_p99_ms": "lower",
    "serve_express_gain": "higher",
}

#: metric -> minimum bench_schema whose artifacts are comparable. When a
#: metric's MEANING changes (not just its value), bench.py bumps
#: BENCH_SCHEMA and the entry here keeps older artifacts out of that
#: metric's band — banding a redefined quantity against pre-redefinition
#: history would flag the redefinition itself as a regression (and hide
#: real ones behind the semantic shift). Metrics absent here band across
#: every schema. v2: e2e_implied_hist_mrows counts EFFECTIVE levels
#: (1 + (depth-1)/2) when the sibling-subtraction trick is active.
METRIC_MIN_SCHEMA: dict[str, int] = {
    "e2e_implied_hist_mrows": 2,
}

MAD_K = 3.0          # band half-width in MADs...
REL_FLOOR = 0.20     # ...but never narrower than 20% of |median|
MIN_HISTORY = 3      # metrics with fewer samples are skipped, not banded

DEFAULT_GLOBS = ("BENCH_r*.json", "MULTICHIP_r*.json")


def load_artifact(path: str) -> dict:
    """Parse one artifact file into {"path", "kind", "order", "metrics",
    "facts"}. kind: "bench" | "multichip" | "unknown". `order` is the
    history sort key (run_id-stamped artifacts keep their harness round
    as primary order; the stamp makes the identity robust, the round the
    sequence). `facts` are pass/fail booleans (multichip ok/rc)."""
    with open(path, encoding="utf-8") as f:
        raw = json.load(f)
    rec = raw.get("parsed", raw) if isinstance(raw, dict) else {}
    if not isinstance(rec, dict):
        rec = {}
    kind = "unknown"
    facts = {}
    if "metric" in rec or "value" in rec:
        kind = "bench"
    elif "n_devices" in raw or "ok" in raw:
        kind = "multichip"
        facts = {"ok": bool(raw.get("ok", False)),
                 "rc": int(raw.get("rc", 1)),
                 "skipped": bool(raw.get("skipped", False))}
    metrics = {k: float(v) for k, v in rec.items()
               if k in METRICS and isinstance(v, (int, float))
               and not isinstance(v, bool)}
    order = raw.get("n") if isinstance(raw, dict) else None
    if order is None:
        m = re.search(r"r(\d+)", os.path.basename(path))
        order = int(m.group(1)) if m else 0
    schema = rec.get("bench_schema")
    # Chaos-run exclusion (docs/ROBUSTNESS.md): bench.py stamps
    # injected_faults when a fault-injection plan was active, and an
    # attached run log's injected `fault` events count too — numbers
    # measured under injected faults are recovery tests, not
    # performance history, and banding against them would widen (or
    # poison) every band.
    injected = bool(rec.get("injected_faults")) or (
        isinstance(raw, dict) and bool(raw.get("injected_faults")))
    if not injected:
        run_events = rec.get("run_log_events") or (
            raw.get("run_log_events") if isinstance(raw, dict) else None)
        if isinstance(run_events, list):
            injected = any(
                isinstance(e, dict) and e.get("event") == "fault"
                and e.get("kind") == "injected" for e in run_events)
    return {"path": path, "kind": kind, "order": int(order),
            "metrics": metrics, "facts": facts,
            "schema": int(schema) if isinstance(schema, int) else 1,
            "injected_faults": injected,
            "run_id": rec.get("run_id"), "git_rev": rec.get("git_rev")}


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def robust_band(vals: list[float]) -> tuple[float, float]:
    """(median, tolerance): tolerance = max(MAD_K * MAD,
    REL_FLOOR * |median|) — the adverse deviation the gate accepts."""
    med = _median(vals)
    mad = _median([abs(v - med) for v in vals])
    return med, max(MAD_K * mad, REL_FLOOR * abs(med))


def check(history: list[dict], current: dict,
          min_history: int = MIN_HISTORY) -> dict:
    """Band every shared metric of `current` (a load_artifact record of
    kind "bench") against `history` (same-kind records). Returns
    {"regressions": [...], "checked": [...], "skipped": [...]} —
    regressions carry metric, direction, current, median, tolerance."""
    regressions, checked, skipped = [], [], []
    for name, cur in sorted(current["metrics"].items()):
        min_schema = METRIC_MIN_SCHEMA.get(name, 0)
        vals = [h["metrics"][name] for h in history
                if name in h["metrics"]
                and h.get("schema", 1) >= min_schema]
        if len(vals) < min_history:
            skipped.append({"metric": name, "history": len(vals)})
            continue
        med, tol = robust_band(vals)
        direction = METRICS[name]
        delta = cur - med
        adverse = -delta if direction == "higher" else delta
        rec = {"metric": name, "direction": direction,
               "current": cur, "median": round(med, 4),
               "tolerance": round(tol, 4), "n_history": len(vals)}
        if adverse > tol:
            regressions.append(rec)
        else:
            checked.append(rec)
    return {"regressions": regressions, "checked": checked,
            "skipped": skipped}


def check_facts(current: dict) -> list[dict]:
    """Pass/fail facts of a multichip record: a current artifact that
    FAILED (ok false / rc nonzero) is a regression regardless of
    history; a skipped run (no devices) is not."""
    f = current.get("facts") or {}
    if not f or f.get("skipped"):
        return []
    fails = []
    if not f.get("ok", False):
        fails.append({"metric": "multichip.ok", "current": False,
                      "expected": True, "path": current["path"]})
    if f.get("rc", 1) != 0:
        fails.append({"metric": "multichip.rc", "current": f.get("rc"),
                      "expected": 0, "path": current["path"]})
    return fails


def run(paths: list[str], current_path: str | None = None,
        min_history: int = MIN_HISTORY) -> dict:
    """The sentinel over a set of artifact files. Without
    `current_path`, the newest artifact of each kind (by `order`) is the
    current run and the rest are its history — `make benchwatch`'s
    zero-argument mode. Returns the full report dict; "ok" is the exit
    verdict."""
    arts = [load_artifact(p) for p in paths]
    report: dict = {"ok": True, "bench": None, "multichip": [],
                    "files": len(arts)}
    cur_art = None
    if current_path is not None:
        cur_art = load_artifact(current_path)
        report["current"] = current_path
        if cur_art["kind"] == "unknown":
            # A current run the loader cannot classify must FAIL, not
            # silently fall back to re-banding the newest history file
            # as if it were the run under test.
            report["ok"] = False
            report["error"] = (
                f"--current {current_path}: unrecognized artifact shape "
                "(no bench metrics, no multichip facts) — schema drift "
                "or a torn write; nothing was checked")
            return report
    # Injected-fault artifacts (chaos runs) never enter bench history,
    # and a chaos artifact under test is excluded rather than banded —
    # its numbers measure recovery, not performance.
    excluded = [a["path"] for a in arts
                if a["kind"] == "bench" and a.get("injected_faults")]
    if excluded:
        report["excluded_injected"] = excluded
    bench = sorted((a for a in arts if a["kind"] == "bench"
                    and not a.get("injected_faults")),
                   key=lambda a: a["order"])
    if cur_art is not None and cur_art["kind"] == "bench":
        if cur_art.get("injected_faults"):
            report["excluded_injected"] = (
                report.get("excluded_injected", []) + [cur_art["path"]])
            report["bench"] = {
                "skipped_injected": "current artifact carries "
                                    "injected-fault events; not banded"}
            current = None
            history = bench
        else:
            history, current = bench, cur_art
    elif bench:
        history, current = bench[:-1], bench[-1]
    else:
        history = current = None
    if current is not None:
        res = check(history, current, min_history=min_history)
        res["current_path"] = current["path"]
        res["n_history"] = len(history)
        report["bench"] = res
        if res["regressions"]:
            report["ok"] = False
    multichip = [a for a in arts if a["kind"] == "multichip"]
    if cur_art is not None and cur_art["kind"] == "multichip":
        multichip = [cur_art]
    elif multichip:
        multichip = [sorted(multichip, key=lambda a: a["order"])[-1]]
    for a in multichip:
        fails = check_facts(a)
        report["multichip"].append(
            {"path": a["path"], "regressions": fails})
        if fails:
            report["ok"] = False
    return report


def collect_default_paths(root: str = ".") -> list[str]:
    out: list[str] = []
    for g in DEFAULT_GLOBS:
        out.extend(sorted(_glob.glob(os.path.join(root, g))))
    return out
