"""Between the benchmark and the program's layered decoder
(``capture.decoder.LayeredDecoder``, MiniCPM-SALA's layers) behind
``serving.GenerativeServing``: builds both as the configuration states and
hands the program the benchmark's weights as they are (the reference's
layout is the program's: no second copy of 10 GB). The client's side of the
queues is ``gpt2_lm``'s. Only the adapters and the runner import the
program."""
import gc
import time

# at import, so that a checkout whose program lacks the decoder fails before
# any weight is made
from analytics_zoo_tpu.capture import decoder as program_decoder

from . import gpt2_lm

#: the health snapshots of the run so far, ``(perf_counter, snapshot)``: the
#: readers of the chunk and selection counters take the window's share
SNAPSHOTS = []


class Served(gpt2_lm.Served):
    """The server thread with its model, and the client's two queues."""

    def __init__(self, cfg, weights, src):
        from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
        from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
        serving = cfg["serving"]
        self.lm = program_decoder.LayeredDecoder(
            program_decoder.DecoderSpec.from_config(cfg, cfg["n_positions"]),
            prefill_chunk=int(serving["prefill_chunk"]))
        self.lm.set_params(weights)
        self.server = GenerativeServing(ServingConfig(
            data_src=src, slots=serving["slots"],
            max_new_tokens=serving["max_new_tokens"],
            kv_pages=serving["kv_pages"],
            kv_page_len=serving["kv_page_len"]), self.lm)
        self.inputs, self.outputs = InputQueue(src), OutputQueue(src)
        del SNAPSHOTS[:]

    def bucket(self, prompt_len):
        """The program that a prompt's last chunk runs (the whole chunks
        before it all run the largest)."""
        return self.lm.chunk_plan(prompt_len - 1)[-1][1]

    def snapshot(self):
        snap = self.server.health_snapshot()
        SNAPSHOTS.append((time.perf_counter(), snap))
        return snap

    def release(self):
        """Stop the server and drop everything it holds on the device:
        the reference needs the room."""
        try:
            self.server.stop()
        finally:
            self.server._caches = self.server._params = None
            self.server._state = self.server._table = None
            self.lm._params = None
            self.server = self.lm = None
            gc.collect()
