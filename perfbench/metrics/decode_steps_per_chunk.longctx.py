"""Decode steps of the resident streams that ran while a prompt was joining,
for each chunk dispatched, inside the window: the program's counters
``serving.steps_between_chunks_total`` over ``serving.prefill_chunks_total``
(health snapshot). Under 1, decoding starves while prompts join."""
from perfbench.harness import readers_sala


def read(ctx):
    ends = readers_sala.window_counters(ctx)
    if ends is None or "prefill_chunks_total" not in ends[0]:
        return None
    chunks = ends[1]["prefill_chunks_total"] - ends[0]["prefill_chunks_total"]
    steps = ends[1]["steps_between_chunks_total"] \
        - ends[0]["steps_between_chunks_total"]
    return steps / chunks if chunks else None
