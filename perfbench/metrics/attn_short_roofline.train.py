"""The fused short-sequence attention kernel's share of its roofline in the
train step: the least time the chip could take for the calls traced (the
larger of FLOPs over the bf16 peak and bytes over the HBM peak, both from
the shapes), over the device time of the kernel's forward and backward
events. A shard of the batch a chip, so the count holds on one chip and on
four."""
from perfbench.harness import flops, tracing

# the program gives its kernels no names yet: in the train step the
# attention kernels are the only Mosaic calls, and the transposed
# (backward) ones carry jax's ``transpose`` prefix
KERNEL = r"custom-call\(.*tpu_custom_call"
FORWARD = r"^%?(?!%?transpose)\S+ = .*" + KERNEL
BACKWARD = r"^%?transpose\S* = .*" + KERNEL


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    cfg = ctx["cell"].config
    heads = cfg["num_attention_heads"]
    shape = (ctx["batch"] // ctx["chips"], heads, ctx["seq"],
             cfg["hidden_size"] // heads)
    least, spent = 0.0, 0.0
    for pattern, backward in ((FORWARD, False), (BACKWARD, True)):
        calls, seconds = tracing.op_seconds(trace, pattern)
        if not calls:
            return None
        work = flops.short_attention_cost(*shape, itemsize=2,
                                          backward=backward)
        least += calls * flops.roofline_seconds(*work, ctx["peaks"])[0]
        spent += seconds
    return 100.0 * least / spent if spent else None
