"""Device seconds by scope: the traced window's operations of the first
device, each booked under the ``jax.named_scope`` names that the program
gave it. The names are taken from the trace, not from a list kept here: a
scope that the program adds tomorrow shows in the table without an edit
(docs/observability.md lists the ones it has today).

Where the chip's trace carries them: not in the event's name (the HLO text
without its ``metadata={...}``), but in the ``tf_op`` stat of the event's
*metadata* in the ``.xplane.pb``, which holds XLA's ``op_name`` and a colon:
``jit(train_step)/transpose(jvp(attention))/dot_general:``.
``jax.profiler.ProfileData`` does not show the stats of event metadata, so
``xplane.py`` reads the file itself. A trace of the CPU has no such stat,
and every operation there is ``unscoped``.

Two kinds of operation are under no scope whatever the program does, and
count as unscoped. One that XLA adds for an argument of the program (the
copy of a buffer that was not donated) carries that argument's path,
``caches[3]['k']``, in place of an ``op_name``: the table shows it as
``arg:caches``. One without any name (a layout copy, the halves of an
asynchronous copy) shows as ``unscoped``."""
import re

from . import tracing, xplane

UNSCOPED = "unscoped"
ARGUMENT = "arg:"
#: what JAX itself puts on an operation's path beside ``jit(<function>)``:
#: control flow, partitioning, rematerialisation. Never a scope of the
#: program; a ``jnp.einsum``'s subscripts are no identifier and drop out too
_JAX_OWN = re.compile(
    r"^(while|body|cond|branch_\d+_fun|shard_map|checkpoint|remat\w*|"
    r"closed_call|core_call|custom_jvp_call|custom_vjp_call\w*|custom_lin|"
    r"pallas_call)$")
_WRAPPED = re.compile(r"^(?:[A-Za-z_]+\()*([^()]*)\)*$")
_IDENTIFIER = re.compile(r"^[A-Za-z_]\w*$")
_ARGUMENT = re.compile(r"^([A-Za-z_]\w*)[\[.]")


def scope_path(op_name):
    """``(names, backward)`` of one ``op_name``: the program's scopes on its
    path, outermost first, with JAX's ``jvp(...)`` and ``transpose(...)``
    taken off, and whether any was transposed. A component is a scope if it
    is an identifier that JAX did not put there (``jit(...)``, ``while``,
    ``shard_map``). An argument's path gives ``("arg:<argument>",)``;
    nothing gives ``()``."""
    text = (op_name or "").rstrip(":")
    if not text:
        return (), False
    if "/" not in text and not text.startswith("jit("):
        found = _ARGUMENT.match(text)
        return (((ARGUMENT + found.group(1)),) if found else ()), False
    names = []
    for part in text.split("/")[:-1]:  # the last one is the primitive
        if "jit(" in part:  # a program or a jitted helper by name
            continue
        inner = _WRAPPED.match(part)
        name = inner.group(1) if inner else ""
        if _IDENTIFIER.match(name) and not _JAX_OWN.match(name):
            names.append(name)
    return tuple(names), "transpose(" in text


def device_ops(path, t0, t1):
    """``[(hlo_text, start_s, end_s, names, backward)]`` of the first
    device's operations, clipped to ``[t0, t1]`` on the trace's clock."""
    planes = xplane.read(path, planes=lambda n: n.startswith("/device:TPU:"),
                         lines=lambda n: n == "XLA Ops")
    planes = sorted((p for p in planes if p["lines"]),
                    key=lambda p: p["name"])
    if not planes:
        return []
    ops, parsed = [], {}
    for e in planes[0]["lines"][0]["events"]:
        start, end = e["start_s"], e["start_s"] + e["seconds"]
        if end <= t0 or start >= t1:
            continue
        meta = e["metadata"]
        op_name = meta["stats"].get("tf_op") or ""
        if op_name not in parsed:
            parsed[op_name] = scope_path(op_name)
        ops.append((meta["name"], max(start, t0), min(end, t1))
                   + parsed[op_name])
    return ops


def _seconds(intervals):
    return sum(b - a for a, b in tracing.union(intervals))


def _scoped(names):
    """Whether a path holds a scope of the program (an argument's name is
    XLA's)."""
    return bool(names) and not names[-1].startswith(ARGUMENT)


def table(ops):
    """Busy seconds, and the seconds of the operations by their innermost
    scope (``unscoped`` where there is none, ``arg:<argument>`` for XLA's
    copy of one). A scope's seconds are the union of its operations'
    intervals, so an operation that contains others (a loop with its body)
    is not counted twice."""
    by_leaf = {}
    for _, a, b, names, _ in ops:
        by_leaf.setdefault(names[-1] if names else UNSCOPED, []).append(
            (a, b))
    return {"busy_s": _seconds([(a, b) for _, a, b, _, _ in ops]),
            "scopes": {k: _seconds(v) for k, v in by_leaf.items()}}


def seconds_under(ops, names, backward=None):
    """Seconds of the operations that have any of ``names`` anywhere in
    their path; ``backward`` true or false keeps one direction only."""
    names = set(names)
    return _seconds([(a, b) for _, a, b, path, back in ops
                     if names.intersection(path)
                     and (backward is None or back == backward)])


def of(ctx):
    """The traced window's operations with their scopes, read once a run:
    ``None`` untraced, or where no operation of the trace is under a scope
    of the program (the CPU's rehearsal; a program that names nothing)."""
    if "scoped_ops" not in ctx:
        trace, capture = ctx.get("trace"), ctx.get("capture")
        ops = None
        if trace and capture is not None:
            ops = device_ops(capture.path(), trace["t0"], trace["t1"])
            if not any(_scoped(names) for _, _, _, names, _ in ops):
                ops = None
        ctx["scoped_ops"] = ops
    return ctx["scoped_ops"]


def share_pct(ctx, names):
    """Device seconds under ``names`` over busy seconds, in per cent."""
    ops = of(ctx)
    if not ops:
        return None
    busy = table(ops)["busy_s"]
    return 100.0 * seconds_under(ops, names) / busy if busy else None


def unscoped_share_pct(ctx):
    """Device seconds under no scope of the program (XLA's copies of an
    argument, operations without any name) over busy seconds, in per
    cent."""
    ops = of(ctx)
    if not ops:
        return None
    busy = table(ops)["busy_s"]
    bare = _seconds([(a, b) for _, a, b, names, _ in ops
                     if not _scoped(names)])
    return 100.0 * bare / busy if busy else None
