"""Median ``audit`` span (the fairness audit, ``property_report``, of every
``audit_every``-th solve) in the window. A window holds few audits: their
count goes to standard error."""
import sys

import numpy as np

from bench.spans import durations


def read(ctx):
    if ctx.spans is None:
        return None
    ms = [s * 1e3 for s in durations(ctx.spans, "audit")]
    print(f"audit spans in window: {len(ms)}", file=sys.stderr)
    return float(np.percentile(ms, 50)) if ms else None
