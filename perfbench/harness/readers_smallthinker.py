"""What the readers of the ``smallthinker_21b`` cells share: the window's
share of the program's routing and page counters (the adapter keeps the
health snapshots that the runner asks for), and the two rooflines of the
decode program. ``readers_sala`` has the live streams of a moment, device
seconds under a scope inside the decode program and the kept snapshots.
Each returns ``None`` where it finds nothing to read: a program without the
counters or the scopes (an earlier commit) reads nothing."""
from . import flops_smallthinker, readers_sala, scopes

#: the TPU compiler's own kernel for ``jax.lax.ragged_dot``: its custom calls
#: reach the trace without an ``op_name``, so no scope can be read off them
RAGGED = "ragged-dot"


def rebooked(ctx):
    """The traced window's operations with their scopes (``scopes.of``),
    the compiler's grouped-product kernels booked under ``moe_experts``:
    ``ops/moe.py experts`` calls them under that scope and nothing else of
    the program calls ``ragged_dot``, but the chip's compiler leaves the
    custom call it makes of one without the ``op_name`` that carries the
    scope (my chip run, PR 31: 1.6 s of a 4.3 s busy stretch read as
    ``unscoped``). Every reader of this cell that looks at scopes asks
    here first, whichever comes first in the line."""
    ops = scopes.of(ctx)
    if ops and not ctx.get("ragged_rebooked"):
        ops = [(text, a, b, ("moe_experts",), back)
               if not path and RAGGED in text else (text, a, b, path, back)
               for text, a, b, path, back in ops]
        ctx["scoped_ops"], ctx["ragged_rebooked"] = ops, True
    return ops


def share_pct(ctx, names):
    rebooked(ctx)
    return scopes.share_pct(ctx, names)


def unscoped_share_pct(ctx):
    rebooked(ctx)
    return scopes.unscoped_share_pct(ctx)


def window_mean(ctx, name):
    """Mean of the histogram ``name`` (``{mean, window}`` in the health
    snapshot) over the observations made inside the window."""
    ends = readers_sala.window_counters(ctx)
    if ends is None or name not in ends[0]:
        return None
    a, b = (e[name] for e in ends)
    count = b["window"] - a["window"]
    if not count:
        return None
    return (b["mean"] * b["window"] - (a["mean"] or 0.0) * a["window"]) / count


def window_pages_per_stream(ctx):
    """Window-layer pages held for each stream that holds any (the resident
    ones and the one prompt being fed), the mean over the health snapshots
    inside the window."""
    kept = getattr(ctx["cell"].adapter(), "SNAPSHOTS", None) or []
    shares = []
    for at, snap in kept:
        pages = (snap.get("kv_pages_in_use") or {}).get("window")
        streams = snap["slots_occupied"] + bool(snap.get("prefills_pending"))
        if ctx["t0"] <= at < ctx["t1"] and pages is not None and streams:
            shares.append(pages / streams)
    return sum(shares) / len(shares) if shares else None


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
        total += flops_smallthinker.forward_flops(cfg, p - 1, p / 2, False)
        total += flops_smallthinker.forward_flops(cfg, o, p + o / 2, True)
    if not total:
        return None
    return 100.0 * total / (ctx["t1"] - ctx["t0"]) / ctx["peaks"]["bf16_flops"]


def experts_roofline_pct(ctx):
    """The least time the experts of the traced decode steps need (each
    layer's touched experts' matrices read once, or its assignments'
    products, whichever is longer; both from the program's counters, the
    window's means a step) against the device time under ``moe_experts``
    inside the decode program."""
    rebooked(ctx)
    spent = readers_sala.seconds_in_decode(ctx, ("moe_experts",))
    runs = readers_sala.decode_runs(ctx)
    touched = window_mean(ctx, "moe_experts_touched")
    ends = readers_sala.window_counters(ctx)
    if not spent or not runs or touched is None:
        return None
    steps = ends[1]["moe_experts_touched"]["window"] \
        - ends[0]["moe_experts_touched"]["window"]
    cfg = ctx["cell"].config
    layers = len(cfg["sliding_window_layout"])
    assignments = (ends[1]["moe_assignments_total"]
                   - ends[0]["moe_assignments_total"]) / steps / layers
    least = layers * flops_smallthinker.experts_least_seconds(
        cfg, touched, assignments, ctx["peaks"])
    return 100.0 * least * len(runs) / spent


def paged_attn_roofline_pct(ctx):
    """The least bytes the attention of the traced decode steps needs (each
    live stream's K and V once a layer, to its length in a full layer and to
    the window in a window layer, from the request log and the shapes) over
    the HBM peak, against the device time under ``attn_full`` and
    ``attn_window`` inside the decode program."""
    rebooked(ctx)
    return readers_sala.roofline_pct(
        ctx, ("attn_full", "attn_window"), flops_smallthinker.kv_read_bytes)
