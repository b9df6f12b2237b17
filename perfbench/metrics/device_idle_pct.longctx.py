"""Share of the traced stretch in which no operation ran on the device."""
from perfbench.harness import readers


def read(ctx):
    return readers.device_idle_pct(ctx)
