"""A claimed prompt's wait for its prefill turn, 95th percentile (nearest
rank) over the requests whose wait began in the window: the program's
``serve.prefill_wait`` records, one a request, from its claim to the
dispatch of its first chunk. ``None`` from a program that emits none."""
from perfbench.harness import records


def read(ctx):
    return records.p95_ms(ctx, "serve.prefill_wait")
