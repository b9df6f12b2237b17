"""Share of device busy time under the program's scopes ``moe_route`` (the
router's product, softmax and top-6) and ``moe_experts`` (the sort by
expert, the grouped products, the weighted combine), in the traced
stretch: decode steps and chunks alike. The grouped products are the
chip compiler's own ``ragged-dot`` kernels, which carry no scope in the
trace and are booked under ``moe_experts`` by name
(``readers_smallthinker.rebooked``)."""
from perfbench.harness import readers_smallthinker


def read(ctx):
    return readers_smallthinker.share_pct(ctx, ("moe_route", "moe_experts"))
