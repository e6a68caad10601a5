"""Process start to window open: imports, trace generation, compile or cache
load, and the warm-up replay."""


def read(ctx):
    return ctx.setup_s
