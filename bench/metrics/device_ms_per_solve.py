"""Device busy time in the traced window per decision that solved anew."""


def read(ctx):
    if ctx.device is None:
        return None
    fresh = sum(1 for d in ctx.decisions
                if d.record is not None and not d.record.reused)
    return ctx.device.busy_s * 1e3 / fresh if fresh else None
