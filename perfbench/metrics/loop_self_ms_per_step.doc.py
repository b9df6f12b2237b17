"""What is left of the serve loop's iteration when every named stretch is
taken out: a ``serve.step``'s seconds less its direct children's (by
``parent``), a ``serve.step``, over the traced stretch. ``None`` from a
program whose spans carry no parent."""
from perfbench.harness import records


def read(ctx):
    return records.self_ms_per_step(ctx)
