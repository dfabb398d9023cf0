"""Whole step of an moe cell: model FLOPs (k experts a token,
bench.work_moe) of every token served in the window over window x peak
bf16 FLOP/s, %."""

from bench.metrics._moe import mfu


def read(ctx):
    return mfu(ctx)
