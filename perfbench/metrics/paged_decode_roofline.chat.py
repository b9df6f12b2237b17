"""Decode's share of its memory roofline: the bytes one decode step has to
read (every parameter once, K and V of the live positions only, counted
from the traffic: what the streams in the slots had written when the trace
was taken) over the HBM peak, over the device time of one decode step. It
reads the same work whatever implements decode."""
from perfbench.harness import flops, readers


def read(ctx):
    step_ms = readers.module_ms(ctx, readers.DECODE_STEP)
    window = readers.traced_window(ctx)
    if step_ms is None or window is None:
        return None
    mid = (window[0] + window[1]) / 2
    live = sum(len(r["prompt"]) + sum(1 for t in r["token_times"] if t <= mid)
               for r in ctx["log"]
               if r["sent"] <= mid and r.get("ended", mid + 1) > mid)
    if not live:
        return None
    least = flops.decode_step_bytes(ctx["cell"].config, live) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (step_ms * 1e-3)
