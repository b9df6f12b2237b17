"""Share of device busy time under the program's scope ``attn_full`` (the
full layers' paged read in decode and tiles in a chunk, with their
softmax), in the traced stretch."""
from perfbench.harness import readers_smallthinker


def read(ctx):
    return readers_smallthinker.share_pct(ctx, ("attn_full",))
