"""Model steps (the expert layer): the window's load of the busiest
expert of each layer and program over the mean expert's (engine
counters `moe_load_max` and `moe_routed`), a ratio of at least 1.  A
grouped matmul waits on its largest group, so skew lengthens the step.
None where the rows lack the counts."""

from bench.metrics._moe import load_max_over_mean


def read(ctx):
    return load_max_over_mean(ctx)
