"""Host time of one serve-loop iteration that dispatched a step, less the
blocking fetch: the program's ``serve.step`` minus ``profile.serving.fetch``,
a ``serve.step``, over the traced stretch. An iteration that also dispatched
a chunk of a joining prompt holds that dispatch too."""
from perfbench.harness import spans


def read(ctx):
    return spans.ms_per(ctx, ("serve.step",), "serve.step",
                        less=("profile.serving.fetch",))
