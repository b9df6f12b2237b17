"""Share of device busy time under no scope of the program, in the traced
stretch: operations that XLA adds on its own account."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.unscoped_share_pct(ctx)
