"""Share of device busy time under the program's scopes ``kv_write`` (page
insert, the decode step's write, page copies) and ``kv_gather`` (the dense
gather of every slot's pages), in the traced stretch. The copies that XLA
makes of a whole pool on its own account are under neither: they read as
``unscoped_share_pct``."""
from perfbench.harness import scopes

SCOPES = ("kv_write", "kv_gather")


def read(ctx):
    return scopes.share_pct(ctx, SCOPES)
