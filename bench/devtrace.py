"""Reduction of a JAX profiler trace to device busy time, top operations
and idle gaps by what the host was doing.

The harness profiles the measured window inside one host annotation,
:data:`WINDOW`. Its start and end put the program's spans and the device's
operations on one clock: a span that starts ``s`` seconds after the window
opened lies at ``window start + s`` on the profiler's clock.

:func:`extract` turns an ``.xplane.pb`` file into plain lists (planes, lines,
``[name, start_ns, duration_ns]`` events) so that the reduction can be
tested on a small recorded fixture without a chip.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Sequence, Tuple

WINDOW = "bench.window"
#: the device-plane line whose events are the programs the chip ran. Its
#: sibling "XLA Ops" holds every operation of every loop trip, thousands per
#: solve under their full HLO text, too many to read inside a run's time.
OPS_LINE = "XLA Modules"
TOP = 10


def extract(path: str) -> Dict[str, object]:
    """The window annotation and the device planes' :data:`OPS_LINE` of an
    ``.xplane.pb`` file as plain data."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        host = plane.name.startswith("/host:")
        lines = []
        for line in plane.lines:
            if host:
                events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                          for ev in line.events if ev.name == WINDOW]
            elif line.name == OPS_LINE:
                events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                          for ev in line.events]
            else:
                continue
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: Mapping) -> List[Mapping]:
    return [p for p in trace["planes"] if p["name"].startswith("/device:TPU:")]


def program_name(event_name: str) -> str:
    """``jit__solve_padded(1239...)`` -> ``jit__solve_padded``."""
    return event_name.split("(", 1)[0]


def window_ns(trace: Mapping) -> Tuple[float, float]:
    """(start, end) of the :data:`WINDOW` annotation on the profiler clock."""
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW:
                    return start, start + dur
    raise ValueError(f"the profile has no {WINDOW!r} annotation")


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


@dataclasses.dataclass
class DeviceSummary:
    window_s: float
    busy_s: float  # mean over the chips of the busy union
    ops: List[List[object]]  # [program, seconds] of the top programs
    idle: List[List[object]]  # [host label, seconds] of chip 0's idle time


def summarize(trace: Mapping, host_segments: Sequence[Tuple[float, float, str]] = ()
              ) -> DeviceSummary:
    """Busy union and top programs of every TPU plane inside the window,
    and chip 0's idle time split by ``host_segments`` ((start s, end s,
    label) on the window's clock, as ``bench.spans.leaf_segments`` gives
    them)."""
    w0, w1 = window_ns(trace)
    planes = device_planes(trace)
    if not planes:
        # the profiler writes no TPU plane when nothing ran on the device
        raise ValueError("the profile has no TPU device plane (did anything "
                         "run on the device?); planes: "
                         + ", ".join(p["name"] for p in trace["planes"]))
    busy_total = 0.0
    op_time: Dict[str, float] = {}
    first_busy: List[Tuple[float, float]] = []
    for plane in planes:
        spans = []
        for line in plane["lines"]:
            if line["name"] != OPS_LINE:
                continue
            for name, start, dur in line["events"]:
                a, b = max(start, w0), min(start + dur, w1)
                if b > a:
                    spans.append((a, b))
                    prog = program_name(name)
                    op_time[prog] = op_time.get(prog, 0.0) + (b - a) / 1e9
        busy = _union(spans)
        busy_total += sum(b - a for a, b in busy) / 1e9
        if not first_busy:
            first_busy = busy
    idle: Dict[str, float] = {}
    j = 0
    cursor = w0
    gaps = []
    for a, b in first_busy + [(w1, w1)]:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    segs = [(w0 + s * 1e9, w0 + e * 1e9, label) for s, e, label in host_segments]
    for g0, g1 in gaps:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b = max(g0, segs[k][0]), min(g1, segs[k][1])
            if b > a:
                idle[segs[k][2]] = idle.get(segs[k][2], 0.0) + (b - a) / 1e9
                covered += b - a
            k += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            idle["(outside spans)"] = idle.get("(outside spans)", 0.0) + rest / 1e9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return DeviceSummary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_total / len(planes),
        ops=[[n, s] for n, s in top_ops], idle=[[n, s] for n, s in top_idle])
