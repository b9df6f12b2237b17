"""Claimed prompts that hold a slot and are not resident yet, their own
chunks under way or still behind another prompt's: mean of the health
snapshot's ``prefills_pending`` over the window's snapshots, which the
adapter keeps (``SNAPSHOTS``), as ``slots_busy_mean.longdoc`` reads
``slots_occupied``."""
from perfbench.harness import records


def read(ctx):
    return records.snapshot_mean(ctx, "prefills_pending")
