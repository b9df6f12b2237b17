"""Share of device busy time under the program's scopes ``moe_route`` (the
router's product, sigmoid and top-8), ``moe_experts`` (the sort by expert,
the grouped products over the 16 experts held, the weighted combine) and
``moe_shared`` (the shared expert), in the traced stretch. The grouped
products are the chip compiler's own ``ragged-dot`` kernels, which carry no
scope in the trace and are booked under ``moe_experts`` by name
(``readers_smallthinker.rebooked``)."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.share_pct(
        ctx, ("moe_route", "moe_experts", "moe_shared"))
