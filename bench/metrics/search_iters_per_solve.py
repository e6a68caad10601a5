"""Device iterations of the general price search per fresh solve:
``SolveRecord.search_iters`` summed over the window's decisions that solved
anew, over their count."""


def read(ctx):
    recs = [d.record for d in ctx.decisions
            if d.record is not None and not d.record.reused]
    iters = [getattr(r, "search_iters", None) for r in recs]
    if not iters or None in iters or sum(iters) == 0:
        return None
    return sum(iters) / len(iters)
