"""1 - the device's busy union over the traced window (profiler trace)."""


def read(ctx):
    if ctx.device is None or ctx.device.window_s <= 0:
        return None
    return 1.0 - ctx.device.busy_s / ctx.device.window_s
