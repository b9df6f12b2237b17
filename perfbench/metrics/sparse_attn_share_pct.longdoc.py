"""Share of device busy time under the sparse layers' scopes ``sparse_select``
(compressed-key scores, pooling, top-k), ``sparse_attend`` (the gather of
the selected pages and the softmax over them) and ``kv_compress`` (the
compressed-key write), in the traced stretch."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.share_pct(ctx, ("sparse_select", "sparse_attend",
                                  "kv_compress"))
