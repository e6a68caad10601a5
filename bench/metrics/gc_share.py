"""Share of the window the cyclic collector held the host: the union of the
``gc/gen<N>`` spans, which nest under the span each pass interrupted, over
the window."""


def read(ctx):
    if ctx.spans is None or ctx.window_s <= 0:
        return None
    passes = sorted((s.t0, s.t1) for s in ctx.spans if s.name.startswith("gc/"))
    if not passes:
        return None
    total, end = 0.0, float("-inf")
    for t0, t1 in passes:
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total / ctx.window_s
