"""Model steps: mean device time per call of the chunk program, ms."""

from bench.metrics._common import CHUNK_PROGRAM, mean_ms


def read(ctx):
    return mean_ms(ctx, CHUNK_PROGRAM)
