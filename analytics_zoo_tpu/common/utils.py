"""Small runtime utilities: host spans, tree helpers, file IO.

``time_it`` mirrors the reference's ``Utils.timeIt`` wall-time micro-profiler
(``zoo/.../common/Utils.scala``) used around every hot call
(``tfpark/GraphRunner.scala:112,132``). Here a span is not logged but made
into one :class:`SpanRecord`, kept in memory and offered to whoever listens
on ``span_hooks`` (a ``utils.trace`` session, a benchmark's recorder); while
nobody does, a span takes no clock and leaves no record.
"""
from __future__ import annotations

import collections
import itertools
import logging
import threading
import time
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import numpy as np

logger = logging.getLogger("analytics_zoo_tpu")

# span observers (a utils/trace.py session registers here while it is
# open); called as fn(name, start_perf_counter, elapsed_seconds). Empty
# while nobody listens, so that emitters pay one truthiness check.
span_hooks: list = []


#: what the spans of one request share: its ``trace_id``, or its uri
Request = Union[int, str, None]


class SpanRecord(NamedTuple):
    """One finished span, whole: what a hook gets as a triple, and what
    says where it came from."""
    name: str
    start: float           #: on ``time.perf_counter``
    seconds: float
    #: the emitting thread by role (its live name when the span ended);
    #: ``None`` for a stretch of a request's life that is no block of code
    lane: Optional[str]
    id: int                #: process-wide serial number
    #: the innermost span of the same lane that was open when this one
    #: was made (``None`` at the top, and for a stretch of a request's life)
    parent: Optional[int]
    request: Request
    #: ``(key, number)`` pairs a reader needs and a name cannot hold (a
    #: short string where a name is all the emitter has)
    args: Tuple[Tuple[str, Any], ...]


#: how many records are kept, the oldest dropped first: a traced benchmark
#: run of a 5 ms serve loop (a minute with its ramp) leaves some 220,000
RECORDS_KEPT = 1 << 19

_records: "collections.deque[SpanRecord]" = collections.deque(
    maxlen=RECORDS_KEPT)
_serial = itertools.count(1)
_open = threading.local()  # .spans: this thread's open blocks, outermost first


def span_records() -> Tuple[SpanRecord, ...]:
    """Every record kept, oldest first: made while somebody listened on
    ``span_hooks``, whether or not anybody still does. For a reader in the
    same process, after the window it wants to look at."""
    return tuple(_records.copy())


def _innermost() -> Optional["_Span"]:
    spans = getattr(_open, "spans", None)
    return spans[-1] if spans else None


def _keep(record: SpanRecord, under: Optional["_Span"]) -> None:
    """Under a tentative block a record waits for the block's own end."""
    if under is not None and under._held is not None:
        under._held.append(record)
    else:
        _records.append(record)


def _finish(record: SpanRecord, under: Optional["_Span"]) -> None:
    """Keep a finished record and hand its triple to every hook."""
    _keep(record, under)
    # iterate a SNAPSHOT: a hook registered/removed concurrently from
    # another thread must not break this in-flight span exit (list
    # mutation during iteration raises / skips entries)
    for hook in tuple(span_hooks):
        hook(record.name, record.start, record.seconds)


def offer_span(name: str, start: float, seconds: float, *,
               request: Request = None,
               args: Tuple[Tuple[str, Any], ...] = (),
               life: bool = False) -> None:
    """Record one finished span and hand it to every hook: ``start`` on
    ``time.perf_counter``. For a stretch that is no ``with`` block: a
    duration that JAX or a phase timer reports, which takes the calling
    thread as its lane and that thread's innermost open block as its
    parent, or (``life``) a stretch of a request's life such as its wait in
    the queue, which has neither. Callers on a hot path check
    ``if span_hooks:`` before they take a clock for it."""
    under = None if life else _innermost()
    _finish(SpanRecord(
        name, start, seconds,
        None if life else threading.current_thread().name, next(_serial),
        None if under is None else under.id, request, args), under)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, key: str, value: Any) -> None:
        pass

    def drop(self) -> None:
        pass


class _Span:
    __slots__ = ("id", "_name", "_request", "_args", "_held", "_dropped",
                 "_under", "_start")

    def __init__(self, name: str, request, args, tentative: bool):
        self._name, self._request, self._args = name, request, args
        # a tentative block keeps its children's records until it ends
        self._held = [] if tentative else None
        self._dropped = False

    def __enter__(self):
        spans = getattr(_open, "spans", None)
        if spans is None:
            spans = _open.spans = []
        self.id = next(_serial)
        self._under = spans[-1] if spans else None
        spans.append(self)
        self._start = time.perf_counter()
        return self

    def note(self, key: str, value: Any) -> None:
        """One more ``(key, number)`` of the record's ``args``."""
        self._args += ((key, value),)

    def drop(self) -> None:
        """Leave no record of this (tentative) block: what it held goes to
        the block around it, or to the top."""
        self._dropped = True

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._start
        _open.spans.remove(self)  # the last of a few, but for a generator
        under = self._under
        if self._dropped:
            above = None if under is None else under.id
            for record in self._held or ():
                _keep(record._replace(parent=above), under)
            return False
        if self._held:
            _records.extend(self._held)
        _finish(SpanRecord(
            self._name, self._start, seconds,
            threading.current_thread().name, self.id,
            None if under is None else under.id, self._request,
            self._args), under)
        return False


NULL_SPAN = _NullSpan()


def time_it(name: str, *, request: Request = None,
            args: Tuple[Tuple[str, Any], ...] = (),
            tentative: bool = False):
    """``with time_it("serve.post"): ...`` records the block as one span
    and offers it to ``span_hooks``. With no hook registered when the block
    is entered it is a shared no-op: one truthiness check, no clock, no
    record. ``tentative``: the block may ``drop()`` itself before it ends
    (an iteration that turned out to do nothing) and then leaves no record,
    and what ran inside it counts as run where the block stood."""
    if not span_hooks:
        return NULL_SPAN
    return _Span(name, request, args, tentative)


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


