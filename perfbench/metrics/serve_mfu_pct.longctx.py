"""Forward FLOPs of the positions served in the window (prompts and outputs of the
requests that finished in it), from shapes (``harness/flops_glm5.py``: the
latent products, the indexer over the context, attention over the 2,048
selected positions at most, the shared expert, and the held experts'
assignments as the program counted them), over the window and the chip's
bf16 peak: the share of the whole step's peak."""
from perfbench.harness import readers_glm5


def read(ctx):
    return readers_glm5.serve_mfu_pct(ctx)
