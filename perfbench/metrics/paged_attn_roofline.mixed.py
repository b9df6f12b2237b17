"""The paged attention's share of its memory roofline in decode: the least
bytes the traced steps need (each live stream's K and V read once a layer,
to its length in a full layer and to 4096 in a window layer; counted from
the request log and the shapes) over the HBM peak, over the device time
under ``attn_full`` and ``attn_window`` inside the decode program."""
from perfbench.harness import readers_smallthinker


def read(ctx):
    return readers_smallthinker.paged_attn_roofline_pct(ctx)
