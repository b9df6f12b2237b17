"""What several per-layer readers share. A reader is ``read(ctx)`` in
``metrics/<name>.py``; it returns a number, or ``None`` where it finds
nothing to read, and the harness then leaves the metric out of the line."""
import statistics

from . import flops, stats, tracing

TRAIN_STEP = r"train_step"
DECODE_STEP = r"_step_paged|_step\b"
PREFILL = r"_prefill"
COLLECTIVE = r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"


def traced_window(ctx):
    """The traced stretch on ``perf_counter``, or ``None`` untraced."""
    capture = ctx.get("capture")
    if capture is None or len(capture.sync) < 2:
        return None
    return capture.sync[0], capture.sync[-1]


def device_idle_pct(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def hbm_peak_gb(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None


def spans_ms_per(ctx, names, per):
    """Host milliseconds in the spans ``names`` for each span ``per``, over
    the traced stretch."""
    window = traced_window(ctx)
    if window is None:
        return None
    count = ctx["spans"].count(per, *window)
    if not count:
        return None
    return 1e3 * ctx["spans"].total(names, *window) / count


def module_period_ms(ctx, pattern):
    """Median device time from one start of the program ``pattern`` to the
    next."""
    trace = ctx.get("trace")
    runs = tracing.module_runs(trace, pattern) if trace else []
    if len(runs) < 3:
        return None
    starts = [s for s, _ in runs]
    return 1e3 * statistics.median(b - a for a, b in zip(starts, starts[1:]))


def module_ms(ctx, pattern):
    """Median device time of one execution of the program ``pattern``."""
    trace = ctx.get("trace")
    runs = tracing.module_runs(trace, pattern) if trace else []
    if not runs:
        return None
    return 1e3 * statistics.median(d for _, d in runs)


def module_share_pct(ctx, pattern):
    """Device time inside executions of ``pattern`` over device busy time."""
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    runs = tracing.module_runs(trace, pattern)
    plane = sorted(trace["devices"])[0]
    busy = trace["devices"][plane]["busy"]
    inside = sum(min(b, s + d) - max(a, s) for s, d in runs for a, b in busy
                 if b > s and a < s + d)
    total = sum(b - a for a, b in busy)
    return 100.0 * inside / total if total else None


def slots_busy_mean(ctx):
    samples = [n for t, n in ctx["slot_samples"]
               if ctx["t0"] <= t < ctx["t1"]]
    return stats.mean(samples)


def serve_mfu_pct(ctx):
    """Forward FLOPs of all prompt and output positions processed for the
    requests that finished in the window, from shapes, over the window and
    the chip's bf16 peak."""
    cfg = ctx["cell"].config
    total = 0
    for r in ctx["log"]:
        if not r.get("done") or not ctx["t0"] <= r["token_times"][-1] < ctx["t1"]:
            continue
        p, o = len(r["prompt"]), len(r["token_times"])
        total += flops.lm_forward_flops(cfg, p - 1, p / 2, with_head=False)
        total += flops.lm_forward_flops(cfg, o, p + o / 2, with_head=True)
    if not total:
        return None
    return 100.0 * total / (ctx["t1"] - ctx["t0"]) / ctx["peaks"]["bf16_flops"]
