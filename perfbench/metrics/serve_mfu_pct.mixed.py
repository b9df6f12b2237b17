"""Forward FLOPs of the positions served in the window (prompts and outputs of the
requests that finished in it), from shapes (``harness/flops_smallthinker.py``:
6 experts a token, the window's cap on a window layer's attention), over the
window and the chip's bf16 peak: the share of the whole step's peak."""
from perfbench.harness import readers_smallthinker


def read(ctx):
    return readers_smallthinker.serve_mfu_pct(ctx)
