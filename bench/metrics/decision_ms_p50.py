"""Median host-clock time of every decision in the window, reused or not:
solve, rounding, packing, rates and finish pushes."""
import numpy as np


def read(ctx):
    ms = [d.wall_ms for d in ctx.decisions]
    return float(np.percentile(ms, 50)) if ms else None
