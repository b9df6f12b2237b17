"""Executables that the backend built, or read from its persistent cache,
inside the measured window (the program's ``compile.backend`` spans, from
JAX's own monitoring): nothing compiles there, so 0."""
from perfbench.harness import spans


def read(ctx):
    return spans.compiles_in_window(ctx, "train.feed_wait")
