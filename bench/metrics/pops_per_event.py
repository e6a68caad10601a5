"""Event-queue pops per world event: ``SolveRecord.events_popped`` summed
over the window's decisions (each record counts the pops since the one
before, and the window opens and closes on a decision), over the world
events. Stale predicted finishes and re-solve timers are the pops beyond
one per event."""


def read(ctx):
    recs = [d.record for d in ctx.decisions if d.record is not None]
    pops = [getattr(r, "events_popped", None) for r in recs]
    if not pops or None in pops or not ctx.world_events:
        return None
    return sum(pops) / ctx.world_events
