"""From a profiler trace to numbers: the device's busy time (the union
of the intervals in which an operation ran), time by operation and by
program, and the longest idle gaps. The reduction works on a plain list
of events, so a recorded slice kept as JSON (tests/benchmark/fixtures)
checks it without the profiler.

An event is [plane, line, name, start_ns, duration_ns]. On a TPU each
chip is a plane `/device:TPU:<n>`; its line `XLA Ops` holds one event per
operation run and `XLA Modules` one per program (`jit_<fn>(<hash>)`).
The slice measured is the span of the host annotation `bench_slice`
that `run.py` holds open while it sleeps: device events are clipped to
it, and its length is the traced window.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SLICE_NAME = "bench_slice"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def events_from_xplane(path: str, host_names=(SLICE_NAME,)) -> list:
    """Device events of every TPU plane, and the host events named in
    `host_names`, as plain lists."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name in host_names:
                    out.append([plane.name, line.name, ev.name,
                                int(ev.start_ns), int(ev.duration_ns)])
    return out


def pack(events: list) -> dict:
    """Events with their strings in tables: a recorded slice small
    enough to keep as a test fixture."""
    tables: dict[str, dict] = {"planes": {}, "lines": {}, "names": {}}

    def idx(table: str, v: str) -> int:
        return tables[table].setdefault(v, len(tables[table]))

    rows = [[idx("planes", p), idx("lines", ln), idx("names", n), s, d]
            for p, ln, n, s, d in events]
    return {**{k: list(v) for k, v in tables.items()}, "events": rows}


def unpack(packed: dict) -> list:
    return [[packed["planes"][p], packed["lines"][ln], packed["names"][n],
             s, d] for p, ln, n, s, d in packed["events"]]


def describe(path: str, top: int = 12) -> dict:
    """Planes, lines and their commonest event names: what to look at
    by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            names: dict[str, list] = {}
            n = 0
            for ev in line.events:
                n += 1
                acc = names.setdefault(ev.name, [0, 0])
                acc[0] += 1
                acc[1] += int(ev.duration_ns)
            best = sorted(names.items(), key=lambda kv: -kv[1][1])[:top]
            lines[line.name] = {"events": n, "top": [
                [k, c, ns / 1e9] for k, (c, ns) in best]}
        out[plane.name] = lines
    return out


def _union(intervals: list[tuple[int, int]]) -> tuple[int, list]:
    """(covered ns, gaps between covered stretches) of sorted
    intervals."""
    busy, gaps = 0, []
    cur_lo = cur_hi = None
    for lo, hi in sorted(intervals):
        if cur_hi is None:
            cur_lo, cur_hi = lo, hi
        elif lo <= cur_hi:
            cur_hi = max(cur_hi, hi)
        else:
            busy += cur_hi - cur_lo
            gaps.append((cur_hi, lo))
            cur_lo, cur_hi = lo, hi
    if cur_hi is not None:
        busy += cur_hi - cur_lo
    return busy, gaps


def program_of(name: str) -> str:
    """`jit_step(123456)` -> `jit_step`."""
    return name.split("(", 1)[0]


def reduce(events: list) -> dict:
    """Busy and window seconds averaged over the device planes, seconds
    by operation and by program (summed over planes, divided by their
    number), and the longest idle gaps of the first plane. Raises where
    the slice holds no device operation: `busy_s: 0` is never printed."""
    slices = [e for e in events if e[2] == SLICE_NAME]
    planes = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane")
    if slices:
        lo = min(e[3] for e in slices)
        hi = max(e[3] + e[4] for e in slices)
        clipped = True
    else:
        dev = [e for e in events if e[0] in planes and e[1] == OPS_LINE]
        if not dev:
            raise ValueError("the trace holds no device operation")
        lo = min(e[3] for e in dev)
        hi = max(e[3] + e[4] for e in dev)
        clipped = False
    if hi <= lo:
        raise ValueError("the traced slice is empty")

    def clip(e):
        a, b = max(e[3], lo), min(e[3] + e[4], hi)
        return (a, b) if b > a else None

    busy_ns = 0
    ops: dict[str, float] = {}
    programs: dict[str, float] = {}
    runs: dict[str, float] = {}
    first_gaps: list = []
    for i, plane in enumerate(planes):
        spans = []
        for e in events:
            if e[0] != plane:
                continue
            c = clip(e)
            if c is None:
                continue
            if e[1] == OPS_LINE:
                spans.append(c)
                ops[e[2]] = ops.get(e[2], 0.0) + (c[1] - c[0]) / 1e9
            elif e[1] == MODULES_LINE:
                name = program_of(e[2])
                programs[name] = programs.get(name, 0.0) \
                    + (c[1] - c[0]) / 1e9
                # a run cut by the slice's edge counts for its share
                runs[name] = runs.get(name, 0.0) \
                    + (c[1] - c[0]) / max(e[4], 1)
        covered, gaps = _union(spans)
        busy_ns += covered
        if i == 0:
            edges = ([(lo, min(s[0] for s in spans))] if spans else []) \
                + gaps \
                + ([(max(s[1] for s in spans), hi)] if spans else [])
            first_gaps = sorted(((b - a) / 1e9, (a - lo) / 1e9)
                                for a, b in edges if b > a)[::-1]
    n = len(planes)
    if busy_ns <= 0:
        raise ValueError("no device operation ran inside the traced "
                         "slice")
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "planes": n,
        "clipped_to_slice": clipped,
        "ops": {k: v / n for k, v in ops.items()},
        "programs": {k: v / n for k, v in programs.items()},
        "program_runs": {k: v / n for k, v in runs.items()},
        "gaps": first_gaps[:10],  # (seconds, offset into the slice)
    }


def matching_seconds(by_name: dict[str, float], patterns: list[str]
                     ) -> float:
    """Seconds of the programs whose name matches one of `patterns`
    (regular expressions, anchored at the start)."""
    regs = [re.compile(p) for p in patterns]
    return sum(s for name, s in by_name.items()
               if any(r.match(name) for r in regs))


def short_op(name: str) -> str:
    """`%fusion.2 = s8[402653184]{...} fusion(...), kind=kCustom, ...`
    -> `fusion.2 s8[402653184] fusion`: the operation, its result shape
    and its opcode."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    shape = re.search(r"\w+\[[\d,]*\]", rest)
    opcode = re.search(r" ([a-z][\w-]*)\(", rest)
    parts = [head.lstrip("%"), shape.group(0) if shape else "",
             opcode.group(1) if opcode else ""]
    return " ".join(x for x in parts if x)[:80]


def breakdown(red: dict) -> dict:
    """The result line's optional `breakdown`: at most ten device
    operations by time, and the longest idle gaps. The program carries
    no host annotations yet, so a gap is named by where it lies."""
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[short_op(k), v] for k, v in top],
            "idle_gaps": [[f"unattributed_at_{off:.3f}s", sec]
                          for sec, off in red["gaps"][:10]]}
