"""Host time blocked in the fetch of a step's tokens (the program's
``profile.serving.fetch``), a ``serve.step``, over the traced stretch: the
device's step and whatever was queued before it, waited for with the host
doing nothing, in a serial loop; what is left of it where the step ran
beside the host's iteration."""
from perfbench.harness import spans


def read(ctx):
    return spans.ms_per(ctx, ("profile.serving.fetch",), "serve.step")
