"""Executables that the backend built, or read from its persistent cache,
inside the measured window (the program's ``compile.backend`` spans):
every chunk bucket and the decode step are warmed up before it, so 0."""
from perfbench.harness import spans


def read(ctx):
    return spans.compiles_in_window(ctx, "serve.step")
