"""The packer's probes per job it searched hosts for:
``SolveRecord.hosts_probed`` (free-slot buckets checked plus hosts taken
from) summed over the window's decisions, over ``SolveRecord.jobs_searched``
summed the same way."""


def read(ctx):
    recs = [d.record for d in ctx.decisions if d.record is not None]
    probed = [getattr(r, "hosts_probed", None) for r in recs]
    searched = [getattr(r, "jobs_searched", None) for r in recs]
    if not recs or None in probed or None in searched or not sum(searched):
        return None
    return sum(probed) / sum(searched)
