"""Latent rows that a layer's sparse read gathered for one live stream in
one decode step, the mean inside the window: the program's histogram
``serving.sparse_positions_read``. 2,048 (``index_topk``) where every
resident stream is longer than that, whatever its context."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.window_mean(ctx, "sparse_positions_read")
