"""Share of the window's solves that reused the previous allocation
(``SolveRecord.reused``): the re-solve layer's work avoided."""


def read(ctx):
    recs = [d.record for d in ctx.decisions if d.record is not None]
    return sum(r.reused for r in recs) / len(recs) if recs else None
