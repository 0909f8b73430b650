"""From the profiler's `.xplane.pb` to device events, busy time and idle gaps.

What a trace of this program on a TPU v5e holds (looked at by hand, PR 24):
a plane `/device:TPU:<n>` per chip with the lines `XLA Modules` (one event
per program execution, named `jit_<fn>(<fingerprint>)`), `XLA Ops` (one event
per HLO instruction executed, NESTED: a `%while` spans its body's events) and
`Async XLA Ops`; a plane `/host:CPU` with a line per host thread, which
run.py does not trace. An op event's name is its HLO text, `%<instruction> = <shape> <opcode>(...)`. A
Pallas kernel is a custom-call whose instruction carries the kernel's `name`
(`%ddt_hist_stream.41`, `%ddt_predict_traverse.1`); the `ddt:*` named scopes
are in the HLO's metadata, not on the events, so readers match instruction
names. Times are nanoseconds.

Only leaf events (those that enclose no other event of their line) count as
time in which an operation ran: a `%while` encloses its body's gaps too.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Op:
    name: str          # "%ddt_hist_stream.41" — the instruction, no text
    module: str        # name of the enclosing XLA Modules event, or ""
    start: float       # ns
    dur: float         # ns


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)        # device -> [leaf Op]
    modules: dict = field(default_factory=dict)    # device -> [(name, start, dur)]

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(union_ns([(o.start, o.start + o.dur) for o in ops])
                   for ops in self.ops.values()) / len(self.ops) / 1e9

    def matched_s(self, match: str, minus: str | None = None,
                  module: str | None = None) -> tuple[float, int]:
        """(seconds, events) of leaf ops whose instruction matches `match`
        and not `minus`, inside executions of programs matching `module`;
        averaged over the chips."""
        m, x = re.compile(match), minus and re.compile(minus)
        mod = module and re.compile(module)
        total, n = 0.0, 0
        for ops in self.ops.values():
            for o in ops:
                if mod and not mod.search(o.module):
                    continue
                if m.search(o.name) and not (x and x.search(o.name)):
                    total += o.dur
                    n += 1
        return total / max(1, len(self.ops)) / 1e9, n

    def idle_gaps(self, top: int = 10) -> list:
        """The longest gaps between device operations on the first chip,
        each named by the programs (or, inside one program, the operations)
        on its two sides: what the device had finished and what it was
        waiting to be given. No host events are traced (see run.py)."""
        if not self.ops:
            return []
        ops = sorted(self.ops[self.devices[0]], key=lambda o: o.start)
        gaps, last = [], None
        for o in ops:
            if last is not None and o.start > last.start + last.dur:
                gaps.append((o.start - last.start - last.dur, last, o))
            if last is None or o.start + o.dur > last.start + last.dur:
                last = o
        out = []
        for width, a, b in sorted(gaps, key=lambda g: -g[0])[:top]:
            ma, mb = program(a.module), program(b.module)
            name = (f"in {ma}: after {a.name} before {b.name}" if ma == mb
                    else f"after {ma} before {mb}")
            out.append([name, width / 1e9])
        return out

    def breakdown(self, top: int = 10) -> dict:
        agg: dict = {}
        for ops in self.ops.values():
            for o in ops:
                agg[o.name] = agg.get(o.name, 0.0) + o.dur
        n = max(1, len(self.ops))
        device_ops = sorted(([k, v / n / 1e9] for k, v in agg.items()),
                            key=lambda kv: -kv[1])[:top]
        return {"device_ops": device_ops, "idle_gaps": self.idle_gaps(top)}


def union_ns(intervals: list) -> float:
    return sum(b - a for a, b in merged(intervals))


def merged(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def leaves(events: list) -> list:
    """Of (name, start, dur) events of ONE line, those enclosing no other."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, s, d) in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d \
                and (nxt[1] > s or nxt[2] < d):
            continue                    # encloses the next event: a parent
        out.append((name, s, d))
    return out


def program(module: str) -> str:
    """`jit_rounds(9038123236142063642)` -> `jit_rounds`."""
    return module.split("(", 1)[0] or "no-program"


def instruction(text: str) -> str:
    """`%fusion.225 = f32[...] fusion(...)` -> `%fusion.225`."""
    return text.split(" = ", 1)[0].strip()


def from_planes(planes: list) -> Trace:
    """planes: [{"name":, "lines": [{"name":, "events": [[name, start, dur]]}]}]
    (what `dump` writes and the recorded test trace holds)."""
    tr = Trace()
    for pl in planes:
        dm = DEVICE_PLANE.match(pl["name"])
        if dm:
            dev = int(dm.group(1))
            mods, raw = [], []
            for ln in pl["lines"]:
                if ln["name"] == "XLA Modules":
                    mods = [tuple(e) for e in ln["events"]]
                elif ln["name"] == "XLA Ops":
                    raw = [tuple(e) for e in ln["events"]]
            mods.sort(key=lambda e: e[1])
            ops = []
            for name, s, d in leaves(raw):
                mod = next((m for m, ms, md in mods
                            if ms - 1.0 <= s and s + d <= ms + md + 1.0), "")
                ops.append(Op(instruction(name), mod, s, d))
            tr.ops[dev], tr.modules[dev] = ops, mods
    return tr


def planes_of(xplane_bytes: bytes) -> list:
    """The device planes of a serialized XSpace, their two lines that the
    readers use, as plain lists (jax's own reader)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(xplane_bytes)
    out = []
    for pl in pd.planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        lines = []
        for ln in pl.lines:
            if ln.name not in ("XLA Modules", "XLA Ops"):
                continue
            lines.append({"name": ln.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in ln.events]})
        out.append({"name": pl.name, "lines": lines})
    return out


def load(trace_dir: str, n_devices: int = 1) -> Trace:
    """The newest `.xplane.pb` under a jax.profiler trace directory."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        tr = from_planes(planes_of(f.read()))
    if len(tr.ops) < n_devices:
        raise RuntimeError(f"trace holds {len(tr.ops)} device plane(s), the "
                           f"cell uses {n_devices}")
    if tr.busy_s <= 0:
        raise RuntimeError("no operation ran on the device inside the "
                           "traced window")
    return tr


def load_recorded(path: str) -> Trace:
    """A trace kept as gzipped JSON planes (benchmark/tests)."""
    with gzip.open(path, "rt") as f:
        return from_planes(json.load(f))


def peaks_for(device_kind: str, here: str) -> dict:
    with open(os.path.join(here, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json: add them with their "
                       "source, never a default")
    return table[device_kind]
