"""Forward FLOPs of the positions served in the window (prompts and outputs of
the requests that finished in it), from shapes (``harness/flops_glm53.py``:
the KDA layers' products and recurrence, the latent layer's products, the
pooled index scores and attention over the 2,048 positions read at most,
the mHC coefficients, the dense layer, the shared expert and the held
experts' assignments as the program counted them), over the window and the
chip's bf16 peak: the share of the whole step's peak."""
from perfbench.harness import readers_glm53


def read(ctx):
    return readers_glm53.serve_mfu_pct(ctx)
