"""Share of device busy time under the program's scopes ``dsa_index`` (the
indexer's products and its scores over every position so far) and
``dsa_select`` (the exact top-2048), in the traced stretch: decode steps
and chunks alike."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.share_pct(ctx, ("dsa_index", "dsa_select"))
