"""Median ``rates`` span in the window: turning a placement into job rates
and predicted finishes, and rebuilding the running-job snapshot."""
import numpy as np

from bench.spans import durations


def read(ctx):
    if ctx.spans is None:
        return None
    ms = [s * 1e3 for s in durations(ctx.spans, "rates")]
    return float(np.percentile(ms, 50)) if ms else None
