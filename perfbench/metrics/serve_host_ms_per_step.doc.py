"""Host time of one serve-loop iteration that dispatched a step, less the
blocking fetch (in which the device is busy): the program's ``serve.step``
minus ``profile.serving.fetch``, a ``serve.step``, over the traced stretch.
The loop is serial, so this times the steps is the device's idle time."""
from perfbench.harness import spans


def read(ctx):
    return spans.ms_per(ctx, ("serve.step",), "serve.step",
                        less=("profile.serving.fetch",))
