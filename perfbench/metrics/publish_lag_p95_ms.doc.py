"""From the fold of the newest token a written record carries to the moment
the record landed, 95th percentile (nearest rank) over the records whose
token was folded in the window: the program's ``serve.publish_lag`` spans. A
program without a result publisher emits none and reads ``None``."""
from perfbench.harness import spans


def read(ctx):
    return spans.p95_ms(ctx, "serve.publish_lag")
