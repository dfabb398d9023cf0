"""Scheduler: mean over the window's steps of the step's host wall time
outside blocking device-to-host reads (engine.counters `step_ms` less
`fetch_ms`, the time inside `engine.fetch` spans), ms.  The device can
hide this time only while work is queued.  None where the rows lack the
times."""


def read(ctx):
    c = ctx["run"]["counters"]
    if not c or any("step_ms" not in x or "fetch_ms" not in x for x in c):
        return None
    return sum(x["step_ms"] - x["fetch_ms"] for x in c) / len(c)
