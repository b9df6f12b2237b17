"""Share of device busy time in the attention block of the train step: the
operations under the program's scope ``attention`` (projections, layout
changes, output projection) and under the kernel's own (``attn_short``,
``attn_flash``, ``attn_reference``), forward and backward."""
from perfbench.harness import scopes

SCOPES = ("attention", "attn_short", "attn_flash", "attn_reference")


def read(ctx):
    return scopes.share_pct(ctx, SCOPES)
