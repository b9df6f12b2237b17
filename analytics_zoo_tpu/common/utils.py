"""Small runtime utilities: host spans, tree helpers, file IO.

``time_it`` mirrors the reference's ``Utils.timeIt`` wall-time micro-profiler
(``zoo/.../common/Utils.scala``) used around every hot call
(``tfpark/GraphRunner.scala:112,132``). Here a span is not logged but
offered to whoever listens on ``span_hooks`` (a ``utils.trace`` session, a
benchmark's recorder); while nobody does, a span takes no clock.
"""
from __future__ import annotations

import logging
import time

import jax
import numpy as np

logger = logging.getLogger("analytics_zoo_tpu")

# span observers (a utils/trace.py session registers here while it is
# open); called as fn(name, start_perf_counter, elapsed_seconds). Empty
# while nobody listens, so that emitters pay one truthiness check.
span_hooks: list = []


def offer_span(name: str, start: float, seconds: float) -> None:
    """Hand one finished span to every hook: ``start`` on
    ``time.perf_counter``. For a stretch that is no block of code (a
    request's wait, a duration JAX reports); callers on a hot path check
    ``if span_hooks:`` before they take a clock for it."""
    # iterate a SNAPSHOT: a hook registered/removed concurrently from
    # another thread must not break this in-flight span exit (list
    # mutation during iteration raises / skips entries)
    for hook in tuple(span_hooks):
        hook(name, start, seconds)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _Span:
    __slots__ = ("_name", "_start")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        offer_span(self._name, self._start,
                   time.perf_counter() - self._start)
        return False


NULL_SPAN = _NullSpan()


def time_it(name: str):
    """``with time_it("serve.post"): ...`` offers the block to
    ``span_hooks`` as one span. With no hook registered when the block is
    entered it is a shared no-op: one truthiness check, no clock."""
    if not span_hooks:
        return NULL_SPAN
    return _Span(name)


def wall_clock() -> float:
    """Epoch seconds for stamps that CROSS process boundaries: queue lease
    stamps, request ``enqueue_t``, ``health.json``, client-supplied
    deadlines. Wall-clock is the only clock two hosts share, so these
    genuinely cannot use ``time.monotonic()`` — every other interval or
    deadline in-process must. Routing all cross-process stamps through
    this one audited call keeps the intent explicit and grep-able (the
    ``monotonic-clock`` zoolint pass bans bare ``time.time()``)."""
    return time.time()  # zoolint: disable=monotonic-clock — the one audited wall-clock read; cross-process stamps need epoch time


def tree_size_bytes(tree) -> int:
    """Total byte size of all array leaves in a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(np.prod(l.shape) * l.dtype.itemsize
                   for l in leaves if hasattr(l, "shape")))


def tree_num_params(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(np.prod(l.shape) for l in leaves if hasattr(l, "shape")))


