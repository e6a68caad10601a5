"""90th percentile of the decision times of ``decision_ms_p50``."""
import numpy as np


def read(ctx):
    ms = [d.wall_ms for d in ctx.decisions]
    return float(np.percentile(ms, 90)) if ms else None
