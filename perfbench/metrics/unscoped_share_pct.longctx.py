"""Share of device busy time under no scope of the program, in the traced
stretch: operations that XLA adds on its own account, and the envelopes of
loops. The compiler's ``ragged-dot`` kernels are the experts' products and
are booked there (``readers_smallthinker.rebooked``), not here."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.unscoped_share_pct(ctx)
