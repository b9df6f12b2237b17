"""Host time the train loop waited for a batch, a train step, over the
traced epoch: the program's own ``train.feed_wait`` span, the twin of
``data_wait_ms_per_step.train`` (which wraps ``DeviceFeed.__next__`` from
outside)."""
from perfbench.harness import spans


def read(ctx):
    return spans.ms_per(ctx, ("train.feed_wait",), "train_step")
