"""Forward FLOPs of the positions served in the window (prompts and outputs of the
requests that finished in it), from shapes (``harness/flops_sala.py``), over
the window and the chip's bf16 peak: the share of the whole step's peak."""
from perfbench.harness import readers_sala


def read(ctx):
    return readers_sala.serve_mfu_pct(ctx)
