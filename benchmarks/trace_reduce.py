"""From the JAX profiler's trace to the numbers the harness reports.

Two steps, so that the second can be checked on a small recorded
trace (benchmarks/tests/fixtures/):

``load_xplane(log_dir, keep)``  reads the newest ``*.xplane.pb`` under
    ``log_dir`` with jax.profiler.ProfileData and keeps, as plain
    JSON-able data, every line of every device plane and, of the host
    planes, the events whose name is in ``keep`` (the harness's own
    TraceAnnotations):
    ``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ...]}]}]}``
``reduce(trace, window)``  gives

    window_s     length of the traced window (the ``window``
                 annotation on the host)
    busy_s       seconds in which a program ran on the device: the
                 union of the intervals of the device's "XLA Modules"
                 line (one event per execution of a jitted program;
                 "XLA Ops" where a trace has no such line), clipped to
                 the window, averaged over the device planes.  The op
                 line is not used where the module line exists: at
                 N=128 it holds a million events an epoch and the
                 device's trace buffers overflow first
    dropped      whether the device said "Trace Buffers Dropped": then
                 the trace's tail is missing and busy_s reads low
    programs     {jitted program name: summed device seconds} from the
                 "XLA Modules" line, all device planes
    device_ops   the ten programs that took most device time
    idle_gaps    the idle time of the first device, split by what the
                 host was doing (the innermost harness annotation that
                 covers each instant), ten longest
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NOTES_LINE = "XLA TraceMe"
DROPPED = "Trace Buffers Dropped"
UNNAMED = "_between_annotations_"

Interval = Tuple[float, float]


def load_xplane(log_dir: str, keep: Iterable[str]) -> Dict:
    from jax.profiler import ProfileData

    paths = glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    keep = set(keep)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        names = {line.name for line in plane.lines}
        wanted = (
            {MODULES_LINE, NOTES_LINE} if MODULES_LINE in names
            else {OPS_LINE, NOTES_LINE}
        )
        for line in plane.lines:
            if device and line.name not in wanted:
                continue
            events = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events
                if device or ev.name in keep
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def program_name(event_name: str) -> str:
    """``jit__pow_fused(1234567890)`` -> ``jit__pow_fused``: the
    profiler appends the program's fingerprint."""
    return event_name.split("(", 1)[0]


def _union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def _clip(intervals: Iterable[Interval], lo: float, hi: float):
    for start, end in intervals:
        start, end = max(start, lo), min(end, hi)
        if end > start:
            yield (start, end)


def _line(plane: Dict, name: str) -> List[List]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _innermost(host_events: Sequence[List], lo: float, hi: float):
    """[(start, end, name)] covering [lo, hi): at every instant the
    innermost host annotation that covers it, UNNAMED where none.  One
    sweep with a stack: the harness's annotations nest or lie apart."""
    spans = sorted(
        (
            (max(s, lo), min(s + d, hi), n)
            for n, s, d in host_events
            if min(s + d, hi) > max(s, lo)
        ),
        key=lambda t: (t[0], t[0] - t[1]),
    )
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []
    cursor = lo

    def emit(to: float) -> None:
        nonlocal cursor
        if to > cursor:
            out.append((cursor, to, stack[-1][1] if stack else UNNAMED))
            cursor = to

    for s, e, n in spans:
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, n))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return out


def reduce(trace: Dict, window: str = "traced_window") -> Dict:
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    hosts = [p for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])]
    host_events = [ev for p in hosts for ln in p["lines"] for ev in ln["events"]]
    marks = [ev for ev in host_events if ev[0] == window]
    if not marks:
        raise ValueError(f"the trace holds no {window!r} annotation")
    lo = min(ev[1] for ev in marks)
    hi = max(ev[1] + ev[2] for ev in marks)
    inner = [ev for ev in host_events if ev[0] != window]

    programs: Dict[str, float] = {}
    busy_each: List[float] = []
    first_busy: List[Interval] = []
    for i, plane in enumerate(devices):
        ops = _line(plane, MODULES_LINE) or _line(plane, OPS_LINE)
        busy = _union(list(_clip(
            ((s, s + d) for _n, s, d in ops), lo, hi
        )))
        busy_each.append(sum(e - s for s, e in busy))
        if i == 0:
            first_busy = busy
        for name, s, d in _line(plane, MODULES_LINE):
            for a, b in _clip([(s, s + d)], lo, hi):
                key = program_name(name)
                programs[key] = programs.get(key, 0.0) + (b - a) * 1e-9

    gaps: Dict[str, float] = {}
    if devices:
        cursor = lo
        idle: List[Interval] = []
        for s, e in first_busy:
            if s > cursor:
                idle.append((cursor, s))
            cursor = max(cursor, e)
        if hi > cursor:
            idle.append((cursor, hi))
        timeline = _innermost(inner, lo, hi)
        j = 0
        for s, e in idle:
            while j < len(timeline) and timeline[j][1] <= s:
                j += 1
            k = j
            while k < len(timeline) and timeline[k][0] < e:
                a, b = max(s, timeline[k][0]), min(e, timeline[k][1])
                if b > a:
                    name = timeline[k][2]
                    gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9
                k += 1

    def top(d: Dict[str, float]) -> List[List]:
        return [
            [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]
        ]

    dropped = any(
        ev[0] == DROPPED for p in devices for ev in _line(p, NOTES_LINE)
    )
    return {
        "dropped": dropped,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (
            sum(busy_each) / len(busy_each) * 1e-9 if busy_each else 0.0
        ),
        "devices": len(devices),
        "programs": programs,
        "device_ops": top(programs),
        "idle_gaps": top(gaps),
    }


__all__ = ["load_xplane", "reduce", "program_name", "UNNAMED"]
