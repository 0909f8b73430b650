#!/usr/bin/env python3
"""The benchmark's one command. Every run is a new process:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

load -> warm up one whole job (set-up) -> measure for `--seconds` -> check the
window's own outputs against the plain reference -> print ONE result line.

This file holds no cell, configuration, traffic or metric name. It finds
everything by the names in `BENCHMARK.json` (see README.md):

    cell       -> BENCHMARK.json workloads[]  (+ optional workloads/<cell>.json: overrides)
    config     -> the `file` of BENCHMARK.json configs[]
    traffic    -> traffic/<traffic>.json, whose "job" names jobs/<job>.py
    per-layer  -> layer_metrics/<metric>.json, whose "reader" names readers/<reader>.py

Without a TPU (or with fewer chips than the cell asks for) it exits 1 and
prints no result line. `--rehearse` runs the same control flow on the CPU at
1/100 of the rows with the kernels interpreted, prints REHEARSAL, never a
result line, and proves nothing about the chip.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()        # set-up is counted from here

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def resolve_cell(manifest: dict, name: str) -> dict:
    """Everything that belongs to one cell, from the files its names lead to."""
    cell = find(manifest["workloads"], name, "workload")
    cfg_entry = find(manifest["configs"], cell["config"], "configuration")
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    own = os.path.join(HERE, "workloads", name + ".json")
    overrides = load_json(own).get("overrides", {}) if os.path.exists(own) \
        else {}
    return {"name": name, "chips": int(cell["chips"]), "config": config,
            "traffic": traffic, "overrides": overrides}


def metrics_of(manifest: dict, group: str, cell: str) -> list:
    """The group's metrics that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def run_window(job, seconds: float, max_jobs: int | None) -> dict:
    """Closed loop, one client: jobs back to back, each from call to
    host-resident answer. A job STARTED inside the window runs to its end.
    `span` runs from the first job's start to the last job's end by the
    host's clock, so whatever the host does between two jobs is inside it;
    nothing but this loop is (outputs are only kept here and looked at after
    the window). Returns the span, each job's wall, the outputs and the
    number of jobs that raised."""
    walls, outputs, failed = [], [], 0
    t_open = t_end = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        if t0 - t_open >= seconds and walls:
            break
        if max_jobs is not None and len(walls) + failed >= max_jobs:
            break
        try:
            out = job.one_job()
        except Exception as e:                     # a failed job is counted,
            failed += 1                            # never hidden
            t_end = time.perf_counter()
            say(f"job {len(walls) + failed} raised {type(e).__name__}: {e}")
            if failed >= 3:
                break
            continue
        t_end = time.perf_counter()
        walls.append(t_end - t0)
        outputs.append(out)
    return {"walls": walls, "outputs": outputs, "failed": failed,
            "span": t_end - t_open}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, 1/100 of the rows, kernels interpreted; "
                         "prints REHEARSAL, never a result line")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override one TrainConfig field for a CONTROL run "
                         "(e.g. grad_dtype='\"int8\"'); such a run prints "
                         "CONTROL, never a result line")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = resolve_cell(manifest, args.workload)
    control = {}
    for kv in args.set:
        k, _, v = kv.partition("=")
        control[k] = json.loads(v)

    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    try:
        import jax
        import ddt_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"run.py: the system under test is not in this checkout: {e}",
              file=sys.stderr)
        return 1

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif not (os.environ.get("JAX_PLATFORMS")
              or os.environ.get("JAX_PLATFORM_NAME")):
        jax.config.update("jax_platforms", "tpu")   # no chip = an error
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"run.py: JAX found no accelerator: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; cell {cell['name']} asks for {cell['chips']}")
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"run.py: needs {cell['chips']} TPU chip(s), JAX reports "
              f"{len(devices)} x {dev.platform!r}. `--rehearse` debugs the "
              "control flow on a CPU and proves nothing.", file=sys.stderr)
        return 1
    used = devices[:cell["chips"]]

    from ddt_tpu.backends.tpu import enable_persistent_compile_cache
    from ddt_tpu.telemetry import counters

    # The program's own placement: $JAX_COMPILATION_CACHE_DIR, else the
    # fixed <checkout>/.jax_cache. Every program is kept, however fast it
    # compiled, so that a second run of a cell compiles nothing.
    enable_persistent_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = os.path.abspath(jax.config.jax_compilation_cache_dir)
    if not (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or cache_dir.startswith(ROOT + os.sep)):
        raise SystemExit(f"run.py: compile cache {cache_dir} is outside "
                         "the checkout")
    counters.install_jax_listener()

    job_mod = importlib.import_module("jobs." + cell["traffic"]["job"])
    job = job_mod.Job(cell, seed=args.seed, rehearse=args.rehearse,
                      control=control)
    job.setup()
    warm_up = job.one_job()                 # warm-up: one whole job
    c_setup = counters.snapshot()
    setup_s = time.perf_counter() - T_PROCESS
    say(f"set-up {setup_s:.3f} s (compile "
        f"{c_setup['jit_compile_seconds']:.3f} s in "
        f"{c_setup['jit_compiles']} programs, cache {cache_dir})")

    traced_jobs = int(cell["traffic"].get("traced_jobs", 2))
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    if args.trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # Device planes only. The host tracer records one XlaLinearize
        # event for every ~200 B uploaded: 824 MB of trace and 6 s more a
        # call at this benchmark's batch sizes (my chip run, PR 24).
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = counters.snapshot()
    win = run_window(job, args.seconds, traced_jobs if args.trace else None)
    if args.trace:
        jax.profiler.stop_trace()
    in_window = counters.delta(c0)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    say(f"memory: {used[0].memory_stats()}")

    # ---- outside the window: what the window produced, checked ----------
    t_check = time.perf_counter()
    attempted = len(win["walls"]) + win["failed"]
    # [(what, value, limit, ok)]; the warm-up's answer is what a lone job of
    # a short window is held bit-equal to
    checks = job.check(win["outputs"], warm_up)
    for name, limit in (("jit_compiles", 0), ("hist_oom_degrades", 0),
                        ("fault_retries", 0)):
        v = int(in_window.get(name, 0))
        checks.append((f"{name} inside the window", v, limit, v <= limit))
    win["failed"] += job.unsound(win["outputs"])
    checks.append(("jobs that raised or returned a non-finite value",
                   win["failed"], 0, win["failed"] == 0))
    for what, value, limit, ok in checks:
        say(f"check: {what}: {value} (limit {limit}) "
            f"{'ok' if ok else 'FAILED'}")
    correct = bool(win["walls"]) and all(ok for *_, ok in checks)
    say(f"window: {len(win['walls'])} jobs in {win['span']:.3f} s, walls "
        + " ".join(f"{w:.4f}" for w in win["walls"])
        + f"; h2d_bytes={in_window.get('h2d_bytes', 0)} "
        f"d2h_bytes={in_window.get('d2h_bytes', 0)}; check took "
        f"{time.perf_counter() - t_check:.2f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    line = {"correct": correct, "attempted": attempted,
            "failed": win["failed"], "metrics": {}, "device": device}
    if not args.trace:
        values = dict(job.end_to_end(win), setup_s=setup_s)
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    elif args.rehearse:
        say("a CPU's trace has no device plane: the readers are not "
            "rehearsed here (benchmark/tests runs them on a recorded trace)")
    elif win["walls"]:
        import tracefile

        trace = tracefile.load(trace_dir, n_devices=cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": trace, "walls": win["walls"], "span": win["span"],
               "jobs": len(win["walls"]), "shapes": job.shapes,
               "divisors": job.divisors(len(win["walls"])),
               "peaks": tracefile.peaks_for(dev.device_kind, HERE)}
        device["busy_s"] = trace.busy_s
        device["window_s"] = win["span"]
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
            reader = importlib.import_module("readers." + spec["reader"])
            value = reader.read(ctx, spec.get("args", {}))
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        line["breakdown"] = trace.breakdown()
        say(f"trace: device busy {trace.busy_s:.4f} s of "
            f"{device['window_s']:.4f} s in {len(win['walls'])} traced jobs")

    if args.rehearse:
        say("REHEARSAL complete: CPU, interpreted kernels, 1/100 of the "
            f"rows; correct={correct}; this proves nothing about the chip")
        say("rehearsal line: " + json.dumps(line))
        return 0
    if control:
        say(f"CONTROL {control}: correct={correct} (a control has to come "
            "out false); no result line")
        return 0
    say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
