"""Positions that a layer's indexer scored for one live stream in one
decode step (the stream's context), the mean over the active slots, the
layers and the steps inside the window: the program's histogram
``serving.dsa_positions_scored`` (health snapshot: mean and count)."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.window_mean(ctx, "dsa_positions_scored")
