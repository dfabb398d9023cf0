"""Kernels (the grouped matmul of the expert layer, ops named `gmm`:
the megablox Pallas kernel the moe programs run on a TPU): least time
of the traced steps' grouped-matmul work (bench.work_moe.gmm_work:
routed pairs and experts hit, from the engine counters) over the
kernel's device time in the decode and chunk programs, %."""

from bench.metrics._moe import gmm_roofline


def read(ctx):
    return gmm_roofline(ctx)
