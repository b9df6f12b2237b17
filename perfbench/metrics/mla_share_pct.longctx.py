"""Share of device busy time under the program's scopes ``mla_project``
(the five latent products with their norms and rotary) and ``mla_attend``
(the gather of the selected latent rows and the softmax over them in a
decode step, the masked tiles of a chunk), in the traced stretch."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.share_pct(ctx, ("mla_project", "mla_attend"))
