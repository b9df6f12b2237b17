"""The fused short-sequence attention kernel's share of its roofline on
four chips, as ``attn_short_roofline.train`` reads it on one. There the
backward call is told from the forward one by the name XLA gives the
instruction; per shard both are ``attn_short``, so here the Mosaic calls
under the program's scope ``attn_short`` are taken from the first chip's
trace, and the direction from the scope path (``transpose(...)`` in the
operation's ``op_name``)."""
from perfbench.harness import flops, scopes

KERNEL = "tpu_custom_call"


def read(ctx):
    ops = scopes.of(ctx)
    if not ops:
        return None
    cfg = ctx["cell"].config
    heads = cfg["num_attention_heads"]
    shape = (ctx["batch"] // ctx["chips"], heads, ctx["seq"],
             cfg["hidden_size"] // heads)
    least, spent = 0.0, 0.0
    for backward in (False, True):
        calls = [b - a for text, a, b, names, back in ops
                 if "attn_short" in names and KERNEL in text
                 and back == backward]
        if not calls:
            return None
        work = flops.short_attention_cost(*shape, itemsize=2,
                                          backward=backward)
        least += len(calls) * flops.roofline_seconds(*work, ctx["peaks"])[0]
        spent += sum(calls)
    return 100.0 * least / spent if spent else None
