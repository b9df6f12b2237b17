"""Distinct experts that a layer's active tokens were routed to in one
decode step, the mean over layers and over the steps inside the window: the
program's histogram ``serving.moe_experts_touched`` (health snapshot: mean
and count). With 6 of 64 a token and n resident streams it lies near
``64 (1 - (1 - 6/64)^n)``."""
from perfbench.harness import readers_smallthinker


def read(ctx):
    return readers_smallthinker.window_mean(ctx, "moe_experts_touched")
