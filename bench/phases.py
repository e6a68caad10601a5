"""Time of a solve's phases, per decision: the ``search`` and ``crossover``
spans that the general non-cooperative solve opens under each ``solve``
span, summed per ``solve``."""
from __future__ import annotations

from typing import List

from bench.spans import Span


def per_solve(spans: List[Span], phase: str) -> List[float]:
    """Seconds of ``phase`` spans inside each ``solve`` span of the window,
    for the solves that have any (a water-filling or reused solve has
    none), in window order."""
    solves = [s for s in spans if s.name == "solve"]
    parts = [s for s in spans if s.name == phase]
    out = []
    i = 0
    for solve in solves:
        total, hit = 0.0, False
        while i < len(parts) and parts[i].t0 < solve.t1:
            if parts[i].t0 >= solve.t0:
                total += parts[i].dur
                hit = True
            i += 1
        if hit:
            out.append(total)
    return out
