"""Median ``pack`` span in the window: the packer alone
(``RoundingPlacer.place``), without the rounding and the building of the
job requests that the enclosing ``placement`` span also holds."""
import numpy as np

from bench.spans import durations


def read(ctx):
    if ctx.spans is None:
        return None
    ms = [s * 1e3 for s in durations(ctx.spans, "pack")]
    return float(np.percentile(ms, 50)) if ms else None
