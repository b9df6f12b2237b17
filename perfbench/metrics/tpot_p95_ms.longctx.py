"""95th percentile over all gaps between consecutive tokens of the requests
due in the window, as the callers saw them: a gap that holds a chunk of
another stream's prompt is that much longer."""
from perfbench.harness import stats


def read(ctx):
    return stats.percentile(stats.token_gaps_ms(
        ctx["log"], ctx["t0"], ctx["t1"]), 95)
