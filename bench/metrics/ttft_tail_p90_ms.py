"""Scheduler: 90th percentile of due time to first token over every
request due in the window (bench.stats.ttft_samples), ms.  Per layer
because a window's 40-60 requests leave this tail spreading 15-30% from
run to run, too wide for a bound; it follows admission and the steps
that carry each prompt's chunks."""

from bench import stats


def read(ctx):
    run = ctx["run"]
    v = stats.percentile(stats.ttft_samples(
        run["records"], run["w0"], run["w1"]), 90)
    return None if v is None else v * 1e3
