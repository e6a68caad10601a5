"""Median time of the general solve's device phase per fresh decision: the
``search`` spans (jitted segments, dispatch and transfer) under each
``solve`` span, summed per decision."""
import numpy as np

from bench.phases import per_solve


def read(ctx):
    if ctx.spans is None:
        return None
    ms = [s * 1e3 for s in per_solve(ctx.spans, "search")]
    return float(np.percentile(ms, 50)) if ms else None
