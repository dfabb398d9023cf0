"""Whole step: model FLOPs of every token served in the window over
window x peak bf16 FLOP/s (bench.work), %.  It bounds what the decode
and chunk programs' roofline shares can gain end to end."""

from bench.metrics._common import mfu


def read(ctx):
    return mfu(ctx)
