"""Kernels (the chunk program as a whole): least time of the work of
the traced chunks (bench.work.prefill_work: the chunk's tokens and the
context they attend to) over their device time, %."""

from bench.metrics._common import CHUNK_PROGRAM, roofline


def read(ctx):
    return roofline(ctx, CHUNK_PROGRAM, "prefill")
