"""Share of device busy time under the program's scope ``mhc`` (each
sublayer's mix coefficients, the Sinkhorn normalisations and the mixing of
the four residual streams), in the traced stretch."""
from perfbench.harness import readers_glm53


def read(ctx):
    return readers_glm53.mhc_share_pct(ctx)
