"""Between the benchmark and the program's decoder
(``capture.lm.TransformerLM``) behind ``serving.GenerativeServing``: builds
both as the configuration states, with the benchmark's weights, and a pair
of client queues. Only this file and the runner import the program."""
import gc


def to_program(weights, n_layer):
    blocks = [{name: {leaf: value[i] for leaf, value in group.items()}
               for name, group in weights["blocks"].items()}
              for i in range(n_layer)]
    return {"embed": weights["embed"], "pos": weights["pos"],
            "blocks": blocks, "ln_f": weights["ln_f"]}


class Served:
    """The server thread with its model, and the client's two queues."""

    def __init__(self, cfg, weights, src):
        from analytics_zoo_tpu.capture.lm import TransformerLM
        from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
        from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
        serving = cfg["serving"]
        self.lm = TransformerLM(
            vocab_size=cfg["vocab_size"], hidden=cfg["n_embd"],
            n_block=cfg["n_layer"], n_head=cfg["n_head"],
            max_len=cfg["n_positions"], intermediate=cfg["n_inner"])
        self.lm._graph.estimator.set_params(
            to_program(weights, cfg["n_layer"]))
        self.server = GenerativeServing(ServingConfig(
            data_src=src, slots=serving["slots"],
            max_new_tokens=serving["max_new_tokens"],
            kv_pages=serving["kv_pages"],
            kv_page_len=serving["kv_page_len"]), self.lm)
        self.inputs, self.outputs = InputQueue(src), OutputQueue(src)

    def send(self, uri, prompt, max_new):
        self.inputs.enqueue_prompt(uri, prompt, max_new_tokens=max_new)

    def poll(self, uri):
        """``(tokens so far, done, error)`` of one request, or ``None``
        while the server has posted nothing."""
        res = self.outputs.query(uri)
        if res is None:
            return None
        if "error" in res:
            return [], True, str(res["error"])
        done = bool(res.get("done", True))
        return list(res.get("value" if done else "stream") or []), done, None

    def forget(self, uri):
        self.outputs.queue.discard_result(uri)

    def bucket(self, prompt_len):
        from analytics_zoo_tpu.capture.lm import prefill_bucket
        return prefill_bucket(prompt_len - 1, self.lm.max_len)

    def snapshot(self):
        return self.server.health_snapshot()

    def watch(self, spans):
        """For the traced run: the program's spans (its profiler's serving
        phases go to its ``span_hooks``) and the benchmark's own around the
        queue calls of the serve loop. Returns the undo."""
        from analytics_zoo_tpu.common import profiler
        from analytics_zoo_tpu.common import utils as program_utils
        queue = self.server.queue
        plain = queue.claim_batch, queue.put_result
        queue.claim_batch = spans.timed("queue.claim", queue.claim_batch)
        queue.put_result = spans.timed("queue.put_result", queue.put_result)
        was = profiler.enabled()
        profiler.set_enabled(True)
        program_utils.span_hooks.append(spans.add)

        def undo():
            program_utils.span_hooks.remove(spans.add)
            profiler.set_enabled(was)
            queue.claim_batch, queue.put_result = plain
        return undo

    def release(self):
        """Stop the server and drop everything it holds on the device."""
        try:
            self.server.stop()
        finally:
            self.server._caches = self.server._params = None
            self.lm._graph.estimator.params = None
            self.server = self.lm = None
            gc.collect()
