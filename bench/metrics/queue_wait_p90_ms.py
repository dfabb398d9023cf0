"""Scheduler: 90th percentile of due time to first admission (the
flight recorder's bind event), requests due in the window, ms."""

from bench import stats


def read(ctx):
    run = ctx["run"]
    if not any(r.bind is not None for r in run["records"]):
        return None
    v = stats.percentile(stats.queue_wait_samples(
        run["records"], run["w0"], run["w1"]), 90)
    return None if v is None else v * 1e3
