"""Host time of the decode step's dispatch inside the serve loop's
iteration: the ``profile.serving.dispatch`` records that are direct children
of a ``serve.step``, a ``serve.step``, over the traced stretch. ``None`` from
a program whose spans carry no parent."""
from perfbench.harness import records


def read(ctx):
    return records.ms_per_step(ctx, "profile.serving.dispatch", direct=True)
