"""Device time of the collective operations during which nothing else ran
on that chip, a train step (the first chip's trace)."""
import re

from perfbench.harness import readers, tracing


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    plane = sorted(trace["devices"])[0]
    ops = trace["devices"][plane]["ops"]
    rx = re.compile(readers.COLLECTIVE)
    collective = [(s, s + d) for n, s, d in ops if rx.search(n)]
    steps = len(tracing.module_runs(trace, readers.TRAIN_STEP))
    if not collective or not steps:
        return None
    compute = tracing.union([(s, s + d) for n, s, d in ops
                             if not rx.search(n)])
    exposed = 0.0
    for a, b in tracing.union(collective):
        covered = sum(min(b, y) - max(a, x) for x, y in compute
                      if y > a and x < b)
        exposed += (b - a) - covered
    return 1e3 * exposed / steps
