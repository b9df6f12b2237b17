"""Host time the train loop waited in the device feed's next(), a train step, over the traced epoch."""
from perfbench.harness import readers


def read(ctx):
    return readers.spans_ms_per(ctx, ('feed.next',), 'train_step')
