#!/usr/bin/env python
"""What the TPU compiler's FINAL instruction bundles say of a Pallas
kernel's loop body: a static profile, from a compile and no chip.

A TensorCore issues one VLIW bundle a cycle; a v5e's bundle has slots for
three vector loads, ONE vector store, four vector ALU operations (the
most of each that any bundle of these dumps holds), and a push to each
MXU. A `vmatmul` of 16 bf16 rows keeps its MXU for 16 cycles and there are
four MXUs, so a stretch of bundles costs

    max(bundles, vmatmuls x 16 / 4) cycles

and a body whose matmuls are spread evenly runs at the MXU's rate, while
one that bunches its VPU work (a multiplexer, a spill storm) in a stretch
with few matmuls leaves the MXUs idle there. The WINDOW MODEL charges
every window of `--window` bundles that maximum and sums. For the
oblivious kernel at 073a17b it read 26,870 cycles a sub-tile (26,762 to
26,930 at windows of 1,000 to 16 bundles) where the chip read 27,156
(1.1% over). For PR 40's pipelined step, whose matmuls come in bursts, the
answer depends on the window (24,848 a sub-tile at 2,000 bundles, 25,748
at 16) and the chip read 26,086, 1.3% over the SMALL windows' answer:
the MXUs' queues absorb little of a burst, so read the model at 16-64
(PERF.md section 6, PR 40).

The recipe (no chip; libtpu compiles for a described v5e):

    JAX_PLATFORMS=cpu LIBTPU_INIT_ARGS="--xla_jf_dump_to=DIR \\
        --xla_jf_dump_llo_text=true --xla_mosaic_dump_to=DIR" \\
        python scripts/tpu_aot_check.py --only oblivious/epsilon
    python scripts/llo_bundle_profile.py DIR --kernel oblivious

The first process ABORTS after the kernel's dumps are written (a report
template the wheel lacks: rc 134); `*-<kernel>.1-NN-final_bundles.txt`
and `-schedule-analysis_final_bundles.txt` are there by then. A compile,
not a run: what it prints is a count of instructions, never a time on a
device.

The BODY is the deepest loop of the dump (the `>` marks after a bundle's
address give its nesting); `--depth N` picks another level (1 = the grid
loop, with everything inside it): a kernel whose grid step has no inner
loop profiles at depth 1, and so does the pipelined oblivious step, whose
one loop is the flush of a row tile's last resolve. `--cut-tail` ends the
body at its last `vmatmul`: what follows it in that step (the flush, under
a `pl.when`) runs once a row tile, not once a step.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

# One line a bundle: address, an optional control-target mark, the loop
# nesting as '>' marks, the instructions between braces.
_BUNDLE = re.compile(r"^\s*(0x[0-9a-f]+|\d+)\s+(?:[A-Z]{2}:|:)\s*(>*)\s*\{(.*)$")
# The opcodes the table shows, by prefix: `vmatmul.bf16.gmra.mxu1` is a
# vmatmul, `vadd.f32` a vadd, `vld.sshfl` a vld.
COLUMNS = ("vmatmul", "vsel", "vld", "vst", "vpop", "vadd", "vcmp", "vcvt",
           "vxpose")
MXUS = 4
MATMUL_CYCLES = 16
SPILL = "_spill"


def parse_bundles(path: str) -> list[tuple[int, list[str], int]]:
    """[(depth, [opcode, ...], spills)] a bundle of the dump, in order."""
    out = []
    with open(path, errors="replace") as f:
        for line in f:
            m = _BUNDLE.match(line)
            if m is None:
                continue
            depth, body = len(m.group(2)), m.group(3)
            body = re.sub(r"/\*.*?\*/", "", body)
            ops = []
            for inst in body.split(";;"):
                inst = inst.strip().strip("{}").strip()
                if not inst:
                    continue
                rhs = inst.split("=", 1)[1] if "=" in inst.split("[")[0] \
                    else inst
                tok = rhs.strip().split(" ", 1)[0].split(".")[0]
                ops.append(tok)
            out.append((depth, ops, body.count(SPILL)))
    return out


def loop_body(bundles, depth: int | None):
    """The bundles of the FIRST run at nesting >= depth (the deepest
    level there is when depth is None)."""
    if depth is None:
        depth = max(d for d, _, _ in bundles)
    body, inside = [], False
    for d, ops, spills in bundles:
        if d >= depth:
            inside = True
            body.append((ops, spills))
        elif inside and ops:
            break
    return depth, body


def count(body, name: str) -> int:
    return sum(1 for ops, _ in body for op in ops if op.startswith(name))


def window_model(body, window: int) -> tuple[int, int]:
    """(cycles, MXU-idle cycles): every window of `window` bundles costs
    the larger of its bundles and its matmuls' MXU time; the idle cycles
    are what the sum stands over the matmuls' own time."""
    cycles = 0
    for i in range(0, len(body), window):
        chunk = body[i:i + window]
        mm = count(chunk, "vmatmul")
        cycles += max(len(chunk), -(-mm * MATMUL_CYCLES // MXUS))
    mxu = count(body, "vmatmul") * MATMUL_CYCLES // MXUS
    return cycles, cycles - mxu


def profile(path: str, depth: int | None = None, window: int = 128,
            table: int = 500, cut_tail: bool = False) -> dict:
    bundles = parse_bundles(path)
    if not bundles:
        raise SystemExit(f"{path}: no bundle lines")
    depth, body = loop_body(bundles, depth)
    tail = 0
    if cut_tail:
        last = max((i for i, (ops, _) in enumerate(body)
                    if any(op.startswith("vmatmul") for op in ops)),
                   default=len(body) - 1)
        tail, body = len(body) - last - 1, body[:last + 1]
    cycles, idle = window_model(body, window)
    rows = []
    for i in range(0, len(body), table):
        chunk = body[i:i + table]
        rows.append({"from": i, **{c: count(chunk, c) for c in COLUMNS},
                     "spill": sum(s for _, s in chunk)})
    return {
        "file": os.path.basename(path),
        "depth": depth,
        "bundles": len(body),
        "tail_cut": tail,
        **{c: count(body, c) for c in COLUMNS},
        "spill_refs": sum(s for _, s in body),
        "mxu_cycles": count(body, "vmatmul") * MATMUL_CYCLES // MXUS,
        "window": window,
        "model_cycles": cycles,
        "mxu_idle_cycles": idle,
        "model_cycles_by_window": {
            w: window_model(body, w)[0] for w in (16, 64, 128, 500, 2000)},
        "table": rows,
    }


def find_dump(directory: str, kernel: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        directory, f"*{kernel}*-final_bundles.txt")))
    hits = [h for h in hits if "schedule-analysis" not in h]
    if not hits:
        raise SystemExit(
            f"{directory}: no *{kernel}*-final_bundles.txt (the recipe is "
            "in this script's docstring)")
    return hits[-1]


def render(p: dict) -> str:
    lines = [
        f"{p['file']}: loop body at depth {p['depth']}" + (
            f" (cut: {p['tail_cut']} bundles after the last vmatmul)"
            if p["tail_cut"] else ""),
        f"bundles {p['bundles']}  " + "  ".join(
            f"{c} {p[c]}" for c in COLUMNS) + f"  spill refs {p['spill_refs']}",
        f"MXU {p['mxu_cycles']} cycles ({p['vmatmul']} vmatmul x "
        f"{MATMUL_CYCLES} / {MXUS}); window model ({p['window']} bundles) "
        f"{p['model_cycles']} cycles, MXU idle {p['mxu_idle_cycles']} "
        f"({100 * p['mxu_cycles'] / max(p['model_cycles'], 1):.1f}% busy)",
        "model by window: " + "  ".join(
            f"{w}: {c}" for w, c in p["model_cycles_by_window"].items()),
        "from   " + " ".join(f"{c:>7}" for c in COLUMNS) + "   spill",
    ]
    for r in p["table"]:
        lines.append(f"{r['from']:>6} " + " ".join(
            f"{r[c]:>7}" for c in COLUMNS) + f" {r['spill']:>7}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dump", help="a dump directory (--xla_jf_dump_to) or a "
                    "*-final_bundles.txt file")
    ap.add_argument("--kernel", default="oblivious",
                    help="substring of the kernel's name in the file names")
    ap.add_argument("--depth", type=int, default=None,
                    help="loop nesting of the body (default: the deepest)")
    ap.add_argument("--window", type=int, default=128)
    ap.add_argument("--table", type=int, default=500,
                    help="bundles a row of the table")
    ap.add_argument("--cut-tail", action="store_true",
                    help="end the body at its last vmatmul")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    path = args.dump if os.path.isfile(args.dump) else find_dump(
        args.dump, args.kernel)
    p = profile(path, args.depth, args.window, args.table, args.cut_tail)
    print(json.dumps(p) if args.json else render(p))
    return 0


if __name__ == "__main__":
    sys.exit(main())
