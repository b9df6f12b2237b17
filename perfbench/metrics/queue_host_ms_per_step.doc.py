"""Host time in the queue's claim and put_result calls and the serve loop's
host_input phase, for each decode step dispatched, over the traced stretch."""
from perfbench.harness import readers

SPANS = ("queue.claim", "queue.put_result", "profile.serving.host_input")


def read(ctx):
    return readers.spans_ms_per(ctx, SPANS, "profile.serving.dispatch")
