"""Scheduler: mean over the window's steps of the blocking
device-to-host reads each made (engine.counters `host_syncs`, one per
`engine.fetch`), reads a step.  A batched on-device sampler would bring
it to about one.  None where the rows lack the count."""


def read(ctx):
    c = ctx["run"]["counters"]
    if not c or any("host_syncs" not in x for x in c):
        return None
    return sum(x["host_syncs"] for x in c) / len(c)
