"""What the readers of the ``glm_5`` cells share: the step program's
counters over the window, the serving FLOPs, and the three rooflines of the
decode program. ``readers_sala`` has the live streams of a moment, device
seconds under a scope inside the decode program and the kept snapshots;
``readers_smallthinker`` the window's mean of a histogram and the booking
of the compiler's ``ragged-dot`` kernels under ``moe_experts``. Each
returns ``None`` where it finds nothing to read: a program without the
counters or the scopes (an earlier commit) reads nothing."""
from . import (flops_glm5, readers, readers_sala, readers_smallthinker,
               tracing)

rebooked = readers_smallthinker.rebooked
share_pct = readers_smallthinker.share_pct
unscoped_share_pct = readers_smallthinker.unscoped_share_pct
window_mean = readers_smallthinker.window_mean


def counted_assignments(ctx):
    """Assignments that the experts held here got from one decoded token in
    one expert layer, between the first and the last health snapshot inside
    the window: the program's counter ``serving.moe_assignments_total``
    over the tokens that reached a caller between the two (every output
    token is one slot of one decode step)."""
    kept = getattr(ctx["cell"].adapter(), "SNAPSHOTS", None) or []
    inside = [(at, snap) for at, snap in kept if ctx["t0"] <= at < ctx["t1"]]
    if len(inside) < 2 or "moe_assignments_total" not in inside[0][1]:
        return None
    (a_at, a), (b_at, b) = inside[0], inside[-1]
    tokens = sum(1 for r in ctx["log"] for t in r["token_times"]
                 if a_at <= t < b_at)
    if not tokens:
        return None
    return (b["moe_assignments_total"] - a["moe_assignments_total"]) \
        / tokens / flops_glm5.expert_layers(ctx["cell"].config)


def serve_mfu_pct(ctx):
    """Forward FLOPs of all prompt and output positions processed for the
    requests that finished in the window, from shapes and, for the decoded
    positions, the held experts' assignments as the program counted them,
    over the window and the chip's bf16 peak."""
    cfg, total = ctx["cell"].config, 0
    counted = counted_assignments(ctx)
    for r in ctx["log"]:
        if not r.get("done") or not ctx["t0"] <= r["token_times"][-1] \
                < ctx["t1"]:
            continue
        p, o = len(r["prompt"]), len(r["token_times"])
        total += flops_glm5.forward_flops(cfg, p - 1, p / 2, False)
        total += flops_glm5.forward_flops(cfg, o, p + o / 2, True, counted)
    if not total:
        return None
    return 100.0 * total / (ctx["t1"] - ctx["t0"]) / ctx["peaks"]["bf16_flops"]


def _stream_roofline_pct(ctx, names, least_seconds):
    """The least time the decode steps of the traced window need for the
    work under ``names`` (``least_seconds(cfg, context, peaks)`` a live
    stream a layer a step) against the device time under those scopes
    inside the decode program."""
    rebooked(ctx)
    spent = readers_sala.seconds_in_decode(ctx, names)
    if not spent:
        return None
    cfg = ctx["cell"].config
    least = cfg["num_hidden_layers"] * sum(
        least_seconds(cfg, context, ctx["peaks"])
        for _, _, at in readers_sala.decode_runs(ctx)
        for context in readers_sala.live_contexts(ctx, at))
    return 100.0 * least / spent if least else None


def index_roofline_pct(ctx):
    """Under ``dsa_index`` and ``dsa_select``: each live stream's index keys
    read once a layer to its length, or their scores' products."""
    return _stream_roofline_pct(ctx, ("dsa_index", "dsa_select"),
                                flops_glm5.index_least_seconds)


def attend_roofline_pct(ctx):
    """Under ``mla_attend``: each live stream's selected latent rows read
    once a layer, or their scores and weighted sum."""
    return _stream_roofline_pct(ctx, ("mla_attend",),
                                flops_glm5.attend_least_seconds)


def _stretch_counters(ctx):
    """``((at, snapshot), (at, snapshot))``: the first and the last health
    snapshot taken inside the traced stretch, ``None`` where there are
    fewer than two or they lack the routing counters."""
    capture = ctx.get("capture")
    kept = getattr(ctx["cell"].adapter(), "SNAPSHOTS", None) or []
    if capture is None or len(capture.sync) < 2:
        return None
    inside = [(at, snap) for at, snap in kept
              if capture.sync[0] <= at <= capture.sync[-1]
              and "moe_experts_touched" in snap]
    return (inside[0], inside[-1]) if len(inside) >= 2 else None


def _seconds_in_runs(ctx, keep, runs):
    """Device seconds of the operations of which ``keep(hlo text, scope
    path)`` holds, inside the executions ``runs`` (``(start, seconds)`` on
    the trace's clock)."""
    inside = tracing.union(
        [(max(a, s), min(b, s + d))
         for text, a, b, path, _ in rebooked(ctx) or () if keep(text, path)
         for s, d in runs if b > s and a < s + d])
    return sum(b - a for a, b in inside)


def _between(a, b, name):
    """``(mean, observations)`` of the histogram ``name`` between the
    snapshots ``a`` and ``b`` (``{mean, window}`` in each)."""
    count = b[name]["window"] - a[name]["window"]
    if not count:
        return None, 0
    return (b[name]["mean"] * b[name]["window"]
            - (a[name]["mean"] or 0.0) * a[name]["window"]) / count, count


def experts_roofline_pct(ctx):
    """The least time the routed experts of the traced decode steps need
    (each expert layer's touched held experts' matrices read once, or its
    assignments' products, whichever is longer) against the device time
    under ``moe_experts`` inside the decode program. Both sides are taken
    between the first and the last health snapshot inside the traced
    stretch: the counters' own steps there (how many held experts a layer's
    tokens touched, how many assignments they got) and the decode program's
    executions that began there. The window's means would not do: with half
    an assignment a token landing here the experts touched follow the
    streams resident at the moment."""
    ends = _stretch_counters(ctx)
    if ends is None:
        return None
    (a_at, a), (b_at, b) = ends
    runs = [(s, d) for s, d, at in readers_sala.decode_runs(ctx)
            if a_at <= at < b_at]
    spent = _seconds_in_runs(
        ctx, lambda _, path: "moe_experts" in path, runs)
    touched, steps = _between(a, b, "moe_experts_touched")
    if not spent or not steps:
        return None
    cfg = ctx["cell"].config
    layers = flops_glm5.expert_layers(cfg)
    assignments = (b["moe_assignments_total"]
                   - a["moe_assignments_total"]) / steps / layers
    least = layers * flops_glm5.experts_least_seconds(
        cfg, touched, assignments, ctx["peaks"])
    return 100.0 * least * len(runs) / spent


def chunk_attend_roofline_pct(ctx):
    """The chunk kernel's share of its roofline: the least FLOPs the
    attention of the traced chunks needs over the bf16 peak, against the
    device time of the Mosaic calls under ``mla_attend`` inside the chunk
    programs. A query row needs scores and a weighted sum (``heads x
    (qk_head_dim + v_head_dim) x 2`` FLOPs a key) over the
    ``min(position + 1, index_topk)`` keys it selects, in every layer whose
    attention feeds a later one (a chunk yields no logits, so the last
    layer's does not). The trace does not say where in its prompt a chunk
    started, so each counts as a prompt's first, ``sum_j min(j + 1,
    index_topk)`` over its rows: a lower bound (a later chunk's rows all
    select ``index_topk``, up to twice as much), so the share reads under
    what the kernel reaches and never over 100 %. The kernel itself visits
    every key up to the row's position and masks: the rest of the gap."""
    trace = ctx.get("trace")
    if not trace:
        return None
    runs = tracing.module_runs(trace, readers.PREFILL)
    spent = _seconds_in_runs(
        ctx, lambda text, path: "mla_attend" in path
        and "custom-call(" in text, runs)
    widths = chunk_widths(ctx, len(runs))
    if not spent or not widths:
        return None
    cfg = ctx["cell"].config
    topk = cfg["index_topk"]
    keys = sum(sum(min(j + 1, topk) for j in range(width))
               for width in widths)
    flops = keys * (cfg["num_hidden_layers"] - 1) * 2 \
        * cfg["num_attention_heads"] * (cfg["qk_head_dim"]
                                        + cfg["v_head_dim"])
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / spent


def chunk_widths(ctx, count):
    """The padded widths of the traced stretch's ``count`` chunk programs.
    The trace names a program without its shapes, so: the mean prompt
    positions a chunk fed between the first and last snapshot of the
    stretch (``serving.prompt_tokens_total`` over
    ``serving.prefill_chunks_total``), for every chunk."""
    ends = _stretch_counters(ctx)
    if ends is None or not count:
        return None
    (_, a), (_, b) = ends
    chunks = b.get("prefill_chunks_total", 0) - a.get(
        "prefill_chunks_total", 0)
    if not chunks:
        return None
    fed = (b["prompt_tokens_total"] - a["prompt_tokens_total"]) / chunks
    return [int(fed)] * count
