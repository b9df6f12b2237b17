"""Between the benchmark and the program's layered decoder
(``capture.decoder.LayeredDecoder`` with GLM-5's layers: latent attention
over a latent page pool, a learned selection of single positions, a
feed-forward by layer with sigmoid-routed experts of which the chip holds a
share) behind ``serving.GenerativeServing``: builds both as the
configuration states and hands the program the benchmark's weights as they
are (the reference's layout is the program's: no second copy of 8 GB).
Everything else (the client's side of the queues, the chunk bucket of a
prompt, the kept health snapshots, the release of the device) is
``sala_lm``'s."""
# at import, so that a checkout whose program lacks the latent layers fails
# before any weight is made
from analytics_zoo_tpu.ops import latent_attention as program_latent  # noqa: F401

from . import sala_lm
from .sala_lm import program_decoder

#: the health snapshots of the run so far (``sala_lm`` keeps them)
SNAPSHOTS = sala_lm.SNAPSHOTS


class Served(sala_lm.Served):
    """The server thread with its model, and the client's two queues."""

    def __init__(self, cfg, weights, src):
        from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
        from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
        serving = cfg["serving"]
        self.lm = program_decoder.LayeredDecoder(
            program_decoder.DecoderSpec.from_config(
                cfg, cfg["n_positions"],
                page_len=int(serving["kv_page_len"])),
            prefill_chunk=int(serving["prefill_chunk"]))
        self.lm.set_params(weights)
        self.server = GenerativeServing(ServingConfig(
            data_src=src, slots=serving["slots"],
            max_new_tokens=serving["max_new_tokens"],
            kv_pages=serving["kv_pages"],
            kv_page_len=serving["kv_page_len"]), self.lm)
        self.inputs, self.outputs = InputQueue(src), OutputQueue(src)
        del SNAPSHOTS[:]
