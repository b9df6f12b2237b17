"""Host time in which the serve loop lets go of the streams whose last step
that was (their pages back, the slot and page-table clears dispatched): the
program's ``serve.evict`` records under a ``serve.step``, a ``serve.step``,
over the traced stretch. ``None`` from a program that emits none."""
from perfbench.harness import records


def read(ctx):
    return records.ms_per_step(ctx, "serve.evict")
