"""Median device time of one execution of the chunk program (whole chunks
and the last chunks' smaller buckets alike)."""
from perfbench.harness import readers


def read(ctx):
    return readers.module_ms(ctx, r"_prefill_chunk")
