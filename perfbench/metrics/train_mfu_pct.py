"""The whole train step's share of the chips' bf16 peak: the FLOPs a step
needs by the shapes, over the median device time from one start of the
compiled step to the next, over all chips' peak."""
from perfbench.harness import flops, readers


def read(ctx):
    period_ms = readers.module_period_ms(ctx, readers.TRAIN_STEP)
    if period_ms is None:
        return None
    step = ctx["batch"] * ctx["seq"] * flops.bert_train_flops_per_token(
        ctx["cell"].config, ctx["seq"])
    return 100.0 * step / (period_ms * 1e-3) / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
