"""Median ``solve`` span of the decisions that solved anew (not reused).

Spans and solve records are appended once per decision with active tenants,
in the same order, so the n-th ``solve`` span of the window belongs to the
n-th solve record."""
import numpy as np

from bench.spans import durations


def read(ctx):
    if ctx.spans is None:
        return None
    solves = durations(ctx.spans, "solve")
    recs = [d.record for d in ctx.decisions if d.record is not None]
    if len(solves) != len(recs):
        return None
    fresh = [s * 1e3 for s, r in zip(solves, recs) if not r.reused]
    return float(np.percentile(fresh, 50)) if fresh else None
