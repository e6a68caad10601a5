"""Median ``placement`` span (rounding and host packing) in the window."""
import numpy as np

from bench.spans import durations


def read(ctx):
    if ctx.spans is None:
        return None
    ms = [s * 1e3 for s in durations(ctx.spans, "placement")]
    return float(np.percentile(ms, 50)) if ms else None
