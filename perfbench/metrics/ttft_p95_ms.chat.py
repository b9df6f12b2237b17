"""The open loop's time to first token, 95th percentile over the requests
due in the window (160), from when each was due. It was meant to decide and
does not: over 160 requests its runs spread by 5-6 % of the median on the
same code (PERF.md, PR 24), more than half of the widest bound allowed."""
from perfbench.harness import stats


def read(ctx):
    return stats.percentile(stats.ttft_ms(
        ctx["log"], ctx["t0"], ctx["t1"], ctx["worst_ms"]), 95)
