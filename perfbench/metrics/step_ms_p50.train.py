"""Median device time from one start of the compiled train step to the next."""
from perfbench.harness import readers


def read(ctx):
    return readers.module_period_ms(ctx, readers.TRAIN_STEP)
