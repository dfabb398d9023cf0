"""Reduce a profiler trace (`.xplane.pb`) to device busy time, time per
jitted program, the costliest device ops, and idle gaps labelled by
what the host was doing.

Device planes are those named "/device:..."; their "XLA Modules" line
holds one event per program execution and "XLA Ops" one per HLO op.
Host annotations (`jax.profiler.TraceAnnotation`) sit on host planes,
on the same clock.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Tuple

MODULES = "XLA Modules"
OPS = "XLA Ops"
_SUFFIX = re.compile(r"\(\d+\)$")
_HLO = re.compile(r"%?([\w.\-]+) = (\S+)")
# control-flow ops whose interval spans the ops of their bodies
_CONTAINERS = ("while", "conditional", "call")


def program_name(event_name: str) -> str:
    """A module event's name without its run id: jit_f(123) -> jit_f."""
    return _SUFFIX.sub("", event_name)


def op_name(event_name: str) -> str:
    """An HLO op event's instruction name and result shape, without the
    rest of its text: "fusion.175 bf16[32,32,128]"."""
    m = _HLO.match(event_name)
    if not m:
        return event_name[:80]
    shape = m.group(2)
    shape = "tuple" if shape.startswith("(") else shape.split("{")[0]
    return f"{m.group(1)} {shape}"


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                yield e


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(pd, labels: Iterable[str] = (),
           window: Optional[Tuple[float, float]] = None) -> dict:
    """Summary of a ProfileData, times in seconds.

    busy_s      union of device op intervals, averaged over devices
    window_s    length of the window the shares are taken over: `window`
                (ns, on the trace clock) if given, else first to last
                device event
    programs    {program: [calls, device seconds]} from XLA Modules
    ops         {"program/op shape": device seconds} from XLA Ops, less
                control-flow ops that span their bodies
    idle        [(label, seconds)] per idle gap, labelled by the host
                annotation (one of `labels`) covering the gap's middle,
                or "none"
    """
    devs = [p for p in pd.planes if p.name.startswith("/device:")]
    hosts = [p for p in pd.planes if not p.name.startswith("/device:")]
    labels = set(labels)
    host_spans = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for p in hosts for line in p.lines for e in line.events
        if e.name in labels)
    programs: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    idle: List[Tuple[str, float]] = []
    spans_all = []
    n_dev = 0
    for p in devs:
        mods = [(e.start_ns, e.start_ns + e.duration_ns,
                 program_name(e.name)) for e in _events(p, MODULES)]
        op_iv = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                 for e in _events(p, OPS)]
        if not mods and not op_iv:
            continue
        n_dev += 1
        for a, b, name in mods:
            c = programs.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (b - a) * 1e-9
        mods.sort()
        starts = [m[0] for m in mods]
        for a, b, name in op_iv:
            op = op_name(name)
            if op.startswith(_CONTAINERS):
                continue
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= a else "?"
            key = f"{prog}/{op}"
            ops[key] = ops.get(key, 0.0) + (b - a) * 1e-9
        iv = [(a, b) for a, b, _ in (op_iv or mods)]
        spans_all.extend(iv)
        lo, hi = window if window is not None else \
            (min(a for a, _ in iv), max(b for _, b in iv))
        busy = union(clip(iv, lo, hi))
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle.append((_label(host_spans, (a + b) / 2), (b - a) * 1e-9))
    if not n_dev:
        return {"busy_s": 0.0, "window_s": 0.0, "programs": {}, "ops": {},
                "idle": [], "devices": 0}
    lo, hi = window if window is not None else \
        (min(a for a, _ in spans_all), max(b for _, b in spans_all))
    return {"busy_s": busy_total / n_dev * 1e-9, "window_s": (hi - lo) * 1e-9,
            "programs": programs, "ops": ops, "idle": idle,
            "devices": n_dev}


def _label(spans, t: float) -> str:
    """The innermost host annotation covering time t."""
    best = None
    for a, b, name in spans:
        if a > t:
            break
        if b >= t and (best is None or a >= best[0]):
            best = (a, b, name)
    return best[2] if best else "none"


def host_window(pd, name: str) -> Optional[Tuple[float, float]]:
    """(start, end) ns of the first host annotation called `name`."""
    for p in pd.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name == name:
                    return e.start_ns, e.start_ns + e.duration_ns
    return None


def breakdown(summary: dict, n: int = 10) -> dict:
    """The traced run's `breakdown`: costliest device ops, and idle time
    summed by what the host was doing, each at most n entries."""
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:n]
    by_label: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for label, s in summary["idle"]:
        by_label[label] = by_label.get(label, 0.0) + s
        count[label] = count.get(label, 0) + 1
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[f"{k} ({count[k]} gaps)", v] for k, v in gaps]}
