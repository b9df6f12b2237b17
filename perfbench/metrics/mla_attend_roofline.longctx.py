"""The sparse read's share of its roofline in decode: the least time the
traced steps need (min(length, 2048) latent rows of 576 numbers read once a
layer a live stream at 2 bytes, or ``64 x (576 + 512) x 2`` FLOPs a row,
whichever is longer at the peaks; from the request log and the shapes) over
the device time under ``mla_attend`` inside the decode program."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.attend_roofline_pct(ctx)
