"""Host time in the program's ``serve.post`` (the fold of a step's tokens and
the hand-over of its records to the result publisher), a ``serve.step``, over the traced stretch."""
from perfbench.harness import spans


def read(ctx):
    return spans.ms_per(ctx, ("serve.post",), "serve.step")
