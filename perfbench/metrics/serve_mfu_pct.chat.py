"""Forward FLOPs of the positions served in the window, from shapes, over the chip's bf16 peak."""
from perfbench.harness import readers


def read(ctx):
    return readers.serve_mfu_pct(ctx)
