"""peak_bytes_in_use of the fullest device after the window, before the reference runs."""
from perfbench.harness import readers


def read(ctx):
    return readers.hbm_peak_gb(ctx)
