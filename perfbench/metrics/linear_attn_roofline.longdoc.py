"""The lightning layers' share of their memory roofline in decode: the least
bytes the steps of the traced stretch need (each live stream's float32
state read once and written once a lightning layer, counted from the
request log and the shapes) over the HBM peak, over the device time under
``linear_attn`` inside the decode program."""
from perfbench.harness import flops_sala, readers_sala


def read(ctx):
    def least(cfg, context):
        return flops_sala.count(cfg, flops_sala.LINEAR) \
            * flops_sala.linear_state_bytes(cfg)
    return readers_sala.roofline_pct(ctx, ("linear_attn",), least)
