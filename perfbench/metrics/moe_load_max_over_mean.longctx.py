"""Assignments that the busiest held expert of a layer got in one decode
step over the mean of the 16 held, the mean over the expert layers and the
steps inside the window: the program's histogram
``serving.moe_expert_load``. With half an assignment a token landing here
most steps give an expert one token or none."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.window_mean(ctx, "moe_expert_load")
