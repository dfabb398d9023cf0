"""Model steps: mean device time per call of the decode program, ms."""

from bench.metrics._common import DECODE_PROGRAM, mean_ms


def read(ctx):
    return mean_ms(ctx, DECODE_PROGRAM)
