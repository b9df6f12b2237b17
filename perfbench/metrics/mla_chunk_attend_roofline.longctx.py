"""The chunk's attention kernel's share of its roofline: the least FLOPs the
traced chunks' attention needs (a query row's scores and weighted sum over
the min(position + 1, 2048) keys it selects, ``64 x (256 + 256) x 2`` FLOPs a
key, in the layers whose attention feeds a later one; every chunk counted
as a prompt's first, a lower bound) at the bf16 peak, over the device time
of the Mosaic calls under ``mla_attend`` inside the chunk programs. The
kernel visits every key up to a row's position and masks those the row did
not select, so at long contexts most of what it computes is not in the
count."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.chunk_attend_roofline_pct(ctx)
