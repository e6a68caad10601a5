"""Host time outside decisions per world event, in ms: the window less the
time inside top-level ``resolve`` spans, over the world events."""
from bench.spans import durations


def read(ctx):
    if ctx.spans is None or not ctx.world_events:
        return None
    inside = sum(durations(ctx.spans, "resolve", depth=0))
    return (ctx.window_s - inside) / ctx.world_events * 1e3
