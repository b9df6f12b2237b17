"""Claim to the first token posted, 95th percentile (nearest rank) over the
requests claimed in the window: the program's ``serve.first_token`` spans.
With ``queue_wait_p95_ms.chat`` it splits ``ttft_p95_ms.chat``, less the
client's sweep."""
from perfbench.harness import spans


def read(ctx):
    return spans.p95_ms(ctx, "serve.first_token")
