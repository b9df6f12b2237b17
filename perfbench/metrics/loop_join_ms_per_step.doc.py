"""Host time of the joins inside the serve loop's iteration: the program's
``serve.join`` records under a ``serve.step``, each with what it holds (the
prefill's dispatch, ``profile.serving.host_input``), a ``serve.step``, over
the traced stretch. ``None`` from a program whose spans carry no parent."""
from perfbench.harness import records


def read(ctx):
    return records.ms_per_step(ctx, "serve.join")
