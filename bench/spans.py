"""Reduction of the program's ``repro.obs`` spans to what the readers need.

Works on the tracer's Chrome ``trace_event`` export (``Tracer.to_chrome()``):
complete events in microseconds. The harness marks the window with two
instants, ``bench/window_open`` and ``bench/window_close``, so the window
needs no knowledge of the tracer's clock.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Tuple

OPEN, CLOSE = "bench/window_open", "bench/window_close"
#: label of host time inside the window that no span covers.
UNSPANNED = "event loop"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    t0: float  # seconds since the window opened
    dur: float
    depth: int  # 0 for a span no other span encloses

    @property
    def t1(self) -> float:
        return self.t0 + self.dur


def window(doc: Mapping) -> Tuple[float, float]:
    """(open, close) of the window in the export's seconds."""
    marks = {e["name"]: e["ts"] / 1e6 for e in doc["traceEvents"]
             if e.get("ph") == "i" and e["name"] in (OPEN, CLOSE)}
    if OPEN not in marks or CLOSE not in marks:
        raise ValueError("the trace export has no window marks")
    return marks[OPEN], marks[CLOSE]


def window_spans(doc: Mapping) -> List[Span]:
    """Spans that lie wholly inside the window, on the window's clock, in
    start order, each with its nesting depth (one thread, so spans nest)."""
    t_open, t_close = window(doc)
    raw = sorted(((e["ts"] / 1e6, e["dur"] / 1e6, e["name"])
                  for e in doc["traceEvents"] if e.get("ph") == "X"),
                 key=lambda s: (s[0], -s[1]))
    out: List[Span] = []
    stack: List[float] = []  # end times of the open enclosing spans
    for t0, dur, name in raw:
        while stack and stack[-1] <= t0:
            stack.pop()
        if t_open <= t0 and t0 + dur <= t_close:
            out.append(Span(name, t0 - t_open, dur, len(stack)))
        stack.append(t0 + dur)
    return out


def durations(spans: List[Span], name: str, depth: Optional[int] = None) -> List[float]:
    return [s.dur for s in spans
            if s.name == name and (depth is None or s.depth == depth)]


def leaf_segments(spans: List[Span], length: float) -> List[Tuple[float, float, str]]:
    """The window cut into (start, end, label) pieces, each labelled with the
    innermost span open over it, or :data:`UNSPANNED`."""
    edges = sorted({0.0, length, *(s.t0 for s in spans), *(s.t1 for s in spans)})
    # innermost span at each piece: the deepest one covering its midpoint
    pieces: List[Tuple[float, float, str]] = []
    order = sorted(spans, key=lambda s: s.t0)
    active: List[Span] = []
    i = 0
    for a, b in zip(edges, edges[1:]):
        if b <= a or a >= length:
            continue
        mid = 0.5 * (a + b)
        while i < len(order) and order[i].t0 <= mid:
            active.append(order[i])
            i += 1
        active = [s for s in active if s.t1 > mid]
        label = max(active, key=lambda s: s.depth).name if active else UNSPANNED
        if pieces and pieces[-1][2] == label and pieces[-1][1] == a:
            pieces[-1] = (pieces[-1][0], b, label)
        else:
            pieces.append((a, b, label))
    return pieces


def self_times(spans: List[Span], length: float) -> Dict[str, float]:
    """Seconds of the window spent with each label innermost."""
    out: Dict[str, float] = {}
    for a, b, label in leaf_segments(spans, length):
        out[label] = out.get(label, 0.0) + (b - a)
    return out
