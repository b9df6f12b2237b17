"""Mean of the health snapshot's slots_occupied, sampled through the window."""
from perfbench.harness import readers


def read(ctx):
    return readers.slots_busy_mean(ctx)
