"""Device time inside the compiled prefill programs (``_prefill_chunk``, one a
chunk bucket) over device busy time."""
from perfbench.harness import readers


def read(ctx):
    return readers.module_share_pct(ctx, readers.PREFILL)
