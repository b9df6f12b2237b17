"""Median device time of one execution of the compiled decode step."""
from perfbench.harness import readers


def read(ctx):
    return readers.module_ms(ctx, readers.DECODE_STEP)
