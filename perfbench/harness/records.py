"""What the readers of the program's whole span records share (PR 35). A
record is what ``span_hooks`` gets as a triple with what says where it came
from: ``name``, ``start``, ``seconds``, ``lane`` (the emitting thread by
role, ``None`` for a stretch of a request's life), ``id``, ``parent`` (the
block of the same lane that was open around it), ``request`` and ``args``
(docs/observability.md "Host spans"). The program keeps them in memory
while a hook listens; a reader asks for them here, in the same process,
after the window. A program without them (an earlier commit) reads
``None``, and so does every reader built on this file."""
import importlib

from . import readers, stats, tracing

STEP = "serve.step"


def of(ctx):
    """The program's records as a list, oldest first, or ``None`` where the
    program keeps none. Fetched once a context; a test plants its own under
    ``ctx["records"]``."""
    if "records" not in ctx:
        try:
            program = importlib.import_module(
                "analytics_zoo_tpu.common.utils")
            kept = getattr(program, "span_records", None)
        except ImportError:
            kept = None
        ctx["records"] = None if kept is None else list(kept())
    return ctx["records"]


def starting_in(recs, name, t0, t1):
    """The records ``name`` whose start lies in ``[t0, t1)``."""
    return [r for r in recs if r.name == name and t0 <= r.start < t1]


def children(recs):
    """``{id: [its direct children]}``, by ``parent``."""
    found = {}
    for r in recs:
        if r.parent is not None:
            found.setdefault(r.parent, []).append(r)
    return found


def descendants(rec, kids):
    """Every record under ``rec``, its children's children included."""
    out, stack = [], [rec]
    while stack:
        for child in kids.get(stack.pop().id, ()):
            out.append(child)
            stack.append(child)
    return out


def self_seconds(rec, kids):
    """A block's own time: its length less the part of it that its direct
    children cover. Blocks of one thread lie side by side; a stretch that
    was offered after the fact (a compile inside a dispatch that is itself
    a phase of the profiler's) may lie over a sibling, so it is the union
    that is taken out."""
    end = rec.start + rec.seconds
    covered = tracing.union(tracing.clip(
        [(c.start, c.start + c.seconds) for c in kids.get(rec.id, ())],
        rec.start, end))
    return rec.seconds - sum(b - a for a, b in covered)


def by_request(recs):
    """``{request: [its records]}`` of the records that carry one."""
    found = {}
    for r in recs:
        if r.request is not None:
            found.setdefault(r.request, []).append(r)
    return found


def arg(rec, key):
    """The number ``key`` of a record's ``args``, or ``None``."""
    return dict(rec.args).get(key)


def arg_sum(recs, key):
    """The sum of ``key`` over the records that carry a number there."""
    values = [arg(r, key) for r in recs]
    return sum(v for v in values if isinstance(v, (int, float)))


def lane_of(recs, name=STEP):
    """The lane of the newest record ``name``: the serve loop's, by its
    ``serve.step`` (the records are the process's: an earlier server's
    loop had another name)."""
    for r in reversed(recs):
        if r.name == name and r.lane is not None:
            return r.lane
    return None


def traced_steps(ctx):
    """``(records, the serve.step records that start in the traced
    stretch, children by parent)``, or ``None`` untraced, without records
    or without a step."""
    recs, window = of(ctx), readers.traced_window(ctx)
    if recs is None or window is None:
        return None
    steps = starting_in(recs, STEP, *window)
    if not steps:
        return None
    # what an iteration emits starts inside it
    last = max(s.start + s.seconds for s in steps)
    near = [r for r in recs if window[0] <= r.start <= last]
    return near, steps, children(near)


def ms_per_step(ctx, name, direct=False):
    """Host milliseconds in the records ``name`` under a ``serve.step``
    (its direct children alone, or at any depth: each with what it holds
    itself), a ``serve.step``, over the traced stretch. ``None`` where the
    program emits no record of that name at all."""
    found = traced_steps(ctx)
    if found is None:
        return None
    recs, steps, kids = found
    if not any(r.name == name for r in recs):
        return None
    total = 0.0
    for step in steps:
        under = kids.get(step.id, ()) if direct else descendants(step, kids)
        total += sum(r.seconds for r in under if r.name == name)
    return 1e3 * total / len(steps)


def self_ms_per_step(ctx):
    """``serve.step``'s own milliseconds a step over the traced stretch:
    what is left of an iteration when every named stretch is taken out."""
    found = traced_steps(ctx)
    if found is None:
        return None
    _, steps, kids = found
    return 1e3 * sum(self_seconds(s, kids) for s in steps) / len(steps)


def p95_ms(ctx, name):
    """95th percentile (nearest rank) of the records ``name`` that start
    in the window: for a stretch of a request's life, the requests whose
    stretch began in it."""
    recs = of(ctx)
    if recs is None:
        return None
    return stats.percentile(
        [1e3 * r.seconds for r in starting_in(recs, name, ctx["t0"],
                                              ctx["t1"])], 95)


def fill_pct(ctx, name, used, room):
    """The sum of the arg ``used`` over the sum of the arg ``room`` of the
    records ``name`` in the traced stretch, in %."""
    recs, window = of(ctx), readers.traced_window(ctx)
    if recs is None or window is None:
        return None
    found = starting_in(recs, name, *window)
    width = arg_sum(found, room)
    return 100.0 * arg_sum(found, used) / width if width else None


def snapshot_mean(ctx, key):
    """Mean of the health snapshot's ``key`` over the window's snapshots,
    as the adapter kept them (``SNAPSHOTS``)."""
    kept = getattr(ctx["cell"].adapter(), "SNAPSHOTS", None) or []
    return stats.mean([snap[key] for at, snap in kept
                       if ctx["t0"] <= at < ctx["t1"]
                       and snap.get(key) is not None])
