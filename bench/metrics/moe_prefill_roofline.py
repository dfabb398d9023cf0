"""Kernels (the chunk program of an moe cell as a whole): least time of
the work of the traced chunks (bench.work_moe.prefill_work: the chunk's
tokens through k experts each, the context they attend to, the experts
the chunks hit) over their device time, %."""

from bench.metrics._common import CHUNK_PROGRAM
from bench.metrics._moe import roofline


def read(ctx):
    return roofline(ctx, CHUNK_PROGRAM, "prefill")
