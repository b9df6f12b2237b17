"""Host time of the queue's claim inside the serve loop's iteration: the
program's ``serve.claim`` records whose ancestors (by ``parent``) end in a
``serve.step``, a ``serve.step``, over the traced stretch. ``None`` from a
program whose spans carry no parent."""
from perfbench.harness import records


def read(ctx):
    return records.ms_per_step(ctx, "serve.claim")
