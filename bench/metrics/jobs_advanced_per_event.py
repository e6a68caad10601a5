"""Jobs visited by the progress walks per world event:
``SolveRecord.jobs_advanced`` summed over the window's decisions, over the
world events."""


def read(ctx):
    recs = [d.record for d in ctx.decisions if d.record is not None]
    walked = [getattr(r, "jobs_advanced", None) for r in recs]
    if not walked or None in walked or not ctx.world_events:
        return None
    return sum(walked) / ctx.world_events
