"""Share of device busy time under no scope of the program, in the traced
stretch: operations that XLA adds on its own account (the copy of an
argument that was not donated, a layout change between two fusions, the
halves of an asynchronous copy). What the program's scopes cannot name;
``tools/scopes.py`` lists the operations."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.unscoped_share_pct(ctx)
