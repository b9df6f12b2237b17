"""The closed loop's time to first token, 95th percentile over the requests
due in the window: the whole prompt's chunked prefill and the wait behind
the prompts claimed before it. Recorded here; it decides nothing."""
from perfbench.harness import stats


def read(ctx):
    return stats.percentile(stats.ttft_ms(
        ctx["log"], ctx["t0"], ctx["t1"], ctx["worst_ms"]), 95)
