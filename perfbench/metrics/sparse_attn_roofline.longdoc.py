"""The sparse layers' share of their memory roofline in decode: the least
bytes the steps of the traced stretch need (for each live stream and
sparse layer the selected positions' K and V, one key/value head's columns
a selection, and the compressed keys it can see, once each, at two bytes
a value; counted from the request log and the shapes, not from what the
program gathers) over the HBM peak, over the device time under
``sparse_select``, ``sparse_attend`` and ``kv_compress`` inside the decode
program."""
from perfbench.harness import flops_sala, readers_sala


def read(ctx):
    def least(cfg, context):
        return flops_sala.count(cfg, flops_sala.SPARSE) \
            * flops_sala.sparse_read_bytes(cfg, context)
    return readers_sala.roofline_pct(
        ctx, ("sparse_select", "sparse_attend", "kv_compress"), least)
