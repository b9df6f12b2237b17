"""What the readers of the program's own spans share (``serve.*``,
``train.feed_wait``, ``compile.*``: docs/observability.md). The spans reach
``ctx["spans"]`` through the program's ``span_hooks``, on ``perf_counter``.
A program that emits none of them (an earlier commit) reads ``None``."""
from . import readers, stats


def lengths_ms(ctx, name, t0, t1):
    """Milliseconds of every span ``name`` that starts in ``[t0, t1)``."""
    return [1e3 * d for n, s, d in list(ctx["spans"].spans)
            if n == name and t0 <= s < t1]


def ms_per(ctx, names, per, less=()):
    """Host milliseconds in the spans ``names``, less those in ``less``, for
    each span ``per``, over the traced stretch."""
    window = readers.traced_window(ctx)
    if window is None:
        return None
    count = ctx["spans"].count(per, *window)
    if not count or not any(n in names for n, _, _ in
                            list(ctx["spans"].spans)):
        return None
    total = ctx["spans"].total(names, *window) \
        - ctx["spans"].total(less, *window)
    return 1e3 * total / count


def p95_ms(ctx, name):
    """95th percentile (nearest rank) of the spans ``name`` that start in
    the window: for a stretch of a request's life, the requests whose
    stretch began in it."""
    return stats.percentile(lengths_ms(ctx, name, ctx["t0"], ctx["t1"]), 95)


def compiles_in_window(ctx, witness):
    """How many executables the backend built or read from its cache
    inside the window (``compile.backend`` spans). ``witness`` is a span
    that a program with the compile listener emits in every traced run:
    without one the program has no listener, and nothing can be said."""
    names = {n for n, _, _ in list(ctx["spans"].spans)}
    if witness not in names:
        return None
    return float(len(lengths_ms(ctx, "compile.backend", ctx["t0"],
                                ctx["t1"])))
