"""Host time in the program's ``serve.post`` (the per-token bookkeeping and
every result write of a step), a ``serve.step``, over the traced stretch."""
from perfbench.harness import spans


def read(ctx):
    return spans.ms_per(ctx, ("serve.post",), "serve.step")
