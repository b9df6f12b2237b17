"""95th percentile of how late the generator sent a request after it was
due, over the requests due in the window."""
from perfbench.harness import stats


def read(ctx):
    return stats.percentile(stats.late_ms(ctx["log"], ctx["t0"], ctx["t1"]),
                            95)
