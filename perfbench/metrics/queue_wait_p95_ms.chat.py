"""The wait between a request's ``enqueue_t`` and its claim by the serve
loop, 95th percentile (nearest rank) over the requests enqueued in the
window: the program's ``serve.queue_wait`` spans."""
from perfbench.harness import spans


def read(ctx):
    return spans.p95_ms(ctx, "serve.queue_wait")
