"""Host time in the queue's claim and put_result calls and the serve loop's
host_input phase (here the page allocation of a joining prompt), for each
decode step dispatched, over the traced stretch: the twin from outside of
``serve_post_ms_per_step.longdoc``, as in the ``gpt2_small`` cells."""
from perfbench.harness import readers

SPANS = ("queue.claim", "queue.put_result", "profile.serving.host_input")


def read(ctx):
    return readers.spans_ms_per(ctx, SPANS, "profile.serving.dispatch")
