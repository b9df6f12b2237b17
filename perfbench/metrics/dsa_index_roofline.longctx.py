"""The indexer's and the selection's share of their roofline in decode: the
least time the traced steps need (each live stream's index keys read once a
layer to its length at 2 bytes, or ``2 x 32 x 128`` FLOPs a position,
whichever is longer at the peaks; from the request log and the shapes, not
from what the implementation moves) over the device time under
``dsa_index`` and ``dsa_select`` inside the decode program."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.index_roofline_pct(ctx)
