"""Share of device busy time under the program's scopes ``delta_conv`` (the
short convolution over q, k and v with its window a slot) and
``delta_attn`` (the gated delta rule: a chunk's WY form, a decode step's
update of every slot's state), in the traced stretch."""
from perfbench.harness import readers_glm53


def read(ctx):
    return readers_glm53.delta_share_pct(ctx)
