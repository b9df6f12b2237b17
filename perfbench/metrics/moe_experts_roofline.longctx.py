"""The routed experts' share of their roofline in decode: the least time
the traced steps' experts need (each expert layer's touched held experts'
three matrices read once at the HBM peak, or its assignments' products at
the bf16 peak, whichever is longer; counted from the program's routing
counters and the shapes, not from what the implementation moves) over the
device time under ``moe_experts`` inside the decode program (the compiler's
``ragged-dot`` kernels among it, by name)."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.experts_roofline_pct(ctx)
