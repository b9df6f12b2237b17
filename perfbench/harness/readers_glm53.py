"""What the readers of the ``glm_5_3_flash`` cell share: the serving FLOPs
and the device time's shares of the KDA layers and of the residual
streams' mixing. ``readers_glm5`` has the routing counters over the window
and the shares by scope. Each returns ``None`` where it finds nothing to
read: a program without the scopes ``delta_conv``, ``delta_attn`` and
``mhc`` (an earlier commit) reads nothing."""
from . import flops_glm53, readers_glm5

#: the KDA layers' recurrence and convolution
DELTA = ("delta_conv", "delta_attn")


def serve_mfu_pct(ctx):
    """Forward FLOPs of all prompt and output positions processed for the
    requests that finished in the window, from shapes and, for the decoded
    positions, the held experts' assignments as the program counted them,
    over the window and the chip's bf16 peak."""
    cfg, total = ctx["cell"].config, 0
    counted = readers_glm5.counted_assignments(ctx)
    for r in ctx["log"]:
        if not r.get("done") or not ctx["t0"] <= r["token_times"][-1] \
                < ctx["t1"]:
            continue
        p, o = len(r["prompt"]), len(r["token_times"])
        total += flops_glm53.forward_flops(cfg, p - 1, p / 2, False)
        total += flops_glm53.forward_flops(cfg, o, p + o / 2, True, counted)
    if not total:
        return None
    return 100.0 * total / (ctx["t1"] - ctx["t0"]) / ctx["peaks"]["bf16_flops"]


def _share_pct(ctx, names):
    """``readers_glm5.share_pct``, or ``None`` where no operation of the
    traced stretch lies under any of ``names``."""
    ops = readers_glm5.rebooked(ctx) or ()
    if not any(set(names).intersection(path) for _, _, _, path, _ in ops):
        return None
    return readers_glm5.share_pct(ctx, names)


def delta_share_pct(ctx):
    """Share of device busy time under ``delta_conv`` and ``delta_attn``
    in the traced stretch: the KDA layers' convolution and recurrence, in
    decode steps and chunks alike."""
    return _share_pct(ctx, DELTA)


def mhc_share_pct(ctx):
    """Share of device busy time under ``mhc`` in the traced stretch: the
    coefficients, the Sinkhorn normalisations and the mixing of the four
    residual streams around every sublayer."""
    return _share_pct(ctx, ("mhc",))
