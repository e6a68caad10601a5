"""Median time of the general solve's host phase per fresh decision: the
``crossover`` spans (exact recovery from the prices and the duality
certificate) under each ``solve`` span, summed per decision."""
import numpy as np

from bench.phases import per_solve


def read(ctx):
    if ctx.spans is None:
        return None
    ms = [s * 1e3 for s in per_solve(ctx.spans, "crossover")]
    return float(np.percentile(ms, 50)) if ms else None
