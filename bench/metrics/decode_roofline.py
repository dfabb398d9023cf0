"""Kernels (the decode program as a whole, until kernels have names):
least time of the work the traced decode steps needed over their device
time, %.  The work (bench.work.decode_work) is every weight read once
plus the KV of the tokens the decoding slots hold, and those tokens'
FLOPs; at these sizes memory bandwidth bounds it."""

from bench.metrics._common import DECODE_PROGRAM, roofline


def read(ctx):
    return roofline(ctx, DECODE_PROGRAM, "decode")
