"""What the readers of the ``minicpm_sala`` cells share: the live streams of
a moment from the request log, device seconds under a scope inside the
decode program, and the window's share of the program's chunk and selection
counters (the adapter keeps the health snapshots that the runner asks for).
Each returns ``None`` where it finds nothing to read."""
from . import flops_sala, readers, scopes, tracing


def live_contexts(ctx, at):
    """Context lengths of the streams that were decoding at ``at``
    (``perf_counter``): those whose first token had come and whose last had
    not, a lower bound on the resident streams."""
    return [len(r["prompt"]) + sum(1 for t in r["token_times"] if t <= at)
            for r in ctx["log"]
            if r["token_times"] and r["token_times"][0] <= at
            < r["token_times"][-1]]


def decode_runs(ctx):
    """``(start, seconds, perf_counter at start)`` of each execution of the
    decode program in the traced window."""
    trace, capture = ctx.get("trace"), ctx.get("capture")
    if not trace or capture is None:
        return []
    offset = capture.sync[0] - trace["t0"]
    return [(s, d, s + offset)
            for s, d in tracing.module_runs(trace, readers.DECODE_STEP)]


def seconds_in_decode(ctx, names):
    """Device seconds of the operations under the scopes ``names`` that ran
    inside executions of the decode program."""
    ops, runs = scopes.of(ctx), decode_runs(ctx)
    if not ops or not runs:
        return None
    inside = tracing.union(
        [(max(a, s), min(b, s + d)) for _, a, b, path, _ in ops
         if set(names).intersection(path)
         for s, d, _ in runs if b > s and a < s + d])
    return sum(b - a for a, b in inside)


def roofline_pct(ctx, names, least_bytes):
    """The least time the decode steps of the traced window need for the
    work under ``names`` (``least_bytes(cfg, context)`` a live stream a
    step, over the HBM peak) against the device time under those scopes."""
    spent = seconds_in_decode(ctx, names)
    if not spent:
        return None
    cfg = ctx["cell"].config
    least = sum(least_bytes(cfg, context)
                for _, _, at in decode_runs(ctx)
                for context in live_contexts(ctx, at))
    if not least:
        return None
    return 100.0 * least / ctx["peaks"]["hbm_bytes_per_s"] / spent


def window_counters(ctx):
    """``(first, last)`` health snapshots inside the window, as the adapter
    kept them; ``None`` where there are fewer than two."""
    kept = getattr(ctx["cell"].adapter(), "SNAPSHOTS", None) or []
    inside = [snap for at, snap in kept if ctx["t0"] <= at < ctx["t1"]]
    return (inside[0], inside[-1]) if len(inside) >= 2 else None


def serve_mfu_pct(ctx):
    """Forward FLOPs of all prompt and output positions processed for the
    requests that finished in the window, from shapes, over the window and
    the chip's bf16 peak."""
    cfg, total = ctx["cell"].config, 0
    for r in ctx["log"]:
        if not r.get("done") or not ctx["t0"] <= r["token_times"][-1] \
                < ctx["t1"]:
            continue
        p, o = len(r["prompt"]), len(r["token_times"])
        total += flops_sala.forward_flops(cfg, p - 1, p / 2, with_head=False)
        total += flops_sala.forward_flops(cfg, o, p + o / 2, with_head=True)
    if not total:
        return None
    return 100.0 * total / (ctx["t1"] - ctx["t0"]) / ctx["peaks"]["bf16_flops"]
