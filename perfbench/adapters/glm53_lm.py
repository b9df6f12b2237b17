"""Between the benchmark and the program's layered decoder
(``capture.decoder.LayeredDecoder`` with GLM-5.3-Flash's layers: gated
delta-rule linear attention with a state and a convolution window a slot,
latent attention without rotary over a selection of pooled key blocks, four
hyper-connected residual streams, clamped experts of which the chip holds a
share) behind ``serving.GenerativeServing``: builds both as the
configuration states and hands the program the benchmark's weights as they
are (the reference's layout is the program's). Everything else is
``glm5_lm``'s and ``sala_lm``'s."""
# at import, so that a checkout whose program lacks the delta-rule layers
# fails before any weight is made
from analytics_zoo_tpu.ops import delta_attention as program_delta  # noqa: F401

from . import glm5_lm

#: the health snapshots of the run so far (``sala_lm`` keeps them)
SNAPSHOTS = glm5_lm.SNAPSHOTS


class Served(glm5_lm.Served):
    """The server thread with its model, and the client's two queues."""
