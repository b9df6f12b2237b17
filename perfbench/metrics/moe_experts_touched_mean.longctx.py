"""Distinct held experts that a layer's active tokens were routed to in one
decode step, the mean over the expert layers and the steps inside the
window: the program's histogram ``serving.moe_experts_touched``. With 8 of
256 a token, 16 held and n resident streams it lies near
``16 (1 - (1 - 8/256)^n)``."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.window_mean(ctx, "moe_experts_touched")
