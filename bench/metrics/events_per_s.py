"""World events handled per second of the window: trace events plus jobs
that really finished; stale finish predictions and re-solve timers are the
scheduler's own bookkeeping and do not count."""


def read(ctx):
    return ctx.world_events / ctx.window_s if ctx.window_s > 0 else None
