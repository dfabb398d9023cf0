"""Kernels (the decode program of an moe cell as a whole): least time
of the work the traced decode steps needed over their device time, %.
The work (bench.work_moe.decode_work) is every weight outside the
experts, the experts the decode program hit (engine counter
`moe_decode_experts_hit`) and the slots' KV read once, and k experts'
FLOPs a token; at these sizes memory bandwidth bounds it."""

from bench.metrics._common import DECODE_PROGRAM
from bench.metrics._moe import roofline


def read(ctx):
    return roofline(ctx, DECODE_PROGRAM, "decode")
