"""Host time in ``advance`` spans (the progress walk over running jobs that
precedes each popped event) per world event, in ms."""
from bench.spans import durations


def read(ctx):
    if ctx.spans is None or not ctx.world_events:
        return None
    walks = durations(ctx.spans, "advance")
    return sum(walks) / ctx.world_events * 1e3 if walks else None
