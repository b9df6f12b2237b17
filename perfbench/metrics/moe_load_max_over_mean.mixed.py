"""Assignments that the busiest expert of a layer got in one decode step
over the mean of all 64, the mean over layers and over the steps inside the
window: the program's histogram ``serving.moe_expert_load``. Routing is
dropless, so this is the imbalance the grouped products really carry."""
from perfbench.harness import readers_smallthinker


def read(ctx):
    return readers_smallthinker.window_mean(ctx, "moe_expert_load")
