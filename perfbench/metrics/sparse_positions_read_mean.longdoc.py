"""Mean positions that a sparse layer's gather read for one stream in one
decode step, inside the window: the program's histogram
``serving.sparse_positions_read`` (health snapshot: mean and count)."""
from perfbench.harness import readers_sala


def read(ctx):
    ends = readers_sala.window_counters(ctx)
    if ends is None or "sparse_positions_read" not in ends[0]:
        return None
    (a, b) = (e["sparse_positions_read"] for e in ends)
    count = b["window"] - a["window"]
    if not count:
        return None
    return (b["mean"] * b["window"]
            - (a["mean"] or 0.0) * a["window"]) / count
