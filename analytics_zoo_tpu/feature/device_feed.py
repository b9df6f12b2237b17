"""Double-buffered host→device feed.

The reference hides host→engine latency behind cached-RDD iterators and
per-core replica threads; on TPU the equivalent is overlapping host-side work
(shuffle gather, transforms) and ``device_put`` (async dispatch) with the
previous step's compute. ``DeviceFeed`` runs a background producer thread
that keeps ``prefetch`` batches in flight, each already sharded over the
mesh's data axis, so the TPU never waits on the host (SURVEY.md §7 hard
part (c)).

The feed is shape-agnostic: the host iterator may be endless (train) or
finite (eval/predict — the sentinel becomes ``StopIteration``), and
``shard_fn`` decides what of each item lands on device. Two helpers below
cover the evaluation contract: :func:`masked_eval_batches` turns
``FeatureSet.eval_iterator``'s ``(x, y, valid)`` stream into
``((x, y, mask), meta...)`` items with a host-computed float mask, and
:func:`shard_payload` shards only the leading payload of such an item while
per-batch metadata (valid counts) rides along host-side.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Iterator, List, Optional

import numpy as np
from jax.sharding import Mesh

from ..common import faults
from ..common import metrics as _metrics
from ..common import profiler as _profiler
from ..common import utils as _utils
from ..common.config import global_config
from ..parallel.mesh import shard_batch

_SENTINEL = object()

#: accumulated consumer time blocked waiting on the producer — the train
#: loop's "feed stall": nonzero growth here means the host data plane, not
#: the device, is the bottleneck
_M_STALL = _metrics.counter(
    "train.feed_stall_seconds_total",
    "Seconds the DeviceFeed consumer spent blocked on the host producer.")


def masked_eval_batches(it: Iterator[Any], batch_size: int,
                        with_labels: bool = True) -> Iterator[Any]:
    """Adapt an ``eval_iterator`` stream (``(x, y, valid)``) to feed items.

    Yields ``((x, y, mask), valid)`` (or ``((x, mask), valid)`` without
    labels): the payload a jitted masked eval step consumes plus the valid
    count as host-side metadata. The mask marks the real rows of padded
    tail batches, so pad rows contribute nothing on device.
    """
    # masks are content-constant per valid count: the arange is built once
    # and each distinct mask is cached, so the common full-batch case reuses
    # ONE array for the whole pass instead of allocating arange+mask per
    # batch (tail batches add at most a few distinct entries)
    positions = np.arange(batch_size)
    masks: dict = {batch_size: np.ones(batch_size, np.float32)}
    for x, y, valid in it:
        mask = masks.get(valid)
        if mask is None:
            mask = (positions < valid).astype(np.float32)
            masks[valid] = mask
        if with_labels:
            yield (x, y, mask), valid
        else:
            yield (x, mask), valid


def shard_payload(mesh: Mesh, item: Any) -> Any:
    """Shard function for ``(payload, meta...)`` feed items: the payload
    pytree is sharded over the mesh's data axis, everything after it stays
    host-side untouched (per-batch valid counts, record ids, ...)."""
    payload, *meta = item
    return (shard_batch(mesh, payload), *meta)


def _put_until_stopped(q: "queue.Queue", stop: threading.Event,
                       item: Any) -> bool:
    """Blocking put that aborts when ``stop`` is set. True if delivered."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


def _produce(it: Iterator[Any], mesh: Mesh, q: "queue.Queue",
             stop: threading.Event, errbox: List[BaseException],
             shard_fn) -> None:
    # module-level on purpose: the thread must NOT hold a reference to the
    # DeviceFeed, or an abandoned feed could never be garbage-collected and
    # its __del__-triggered stop would never fire
    try:
        for batch in it:
            # chaos site: a firing injection models the data plane dying
            # mid-epoch — it must surface on the CONSUMER thread (errbox),
            # where the estimator's elastic retry can catch it
            faults.inject("feed.produce")
            if not _put_until_stopped(q, stop, shard_fn(mesh, batch)):
                return
    except BaseException as e:  # surfaced on the consumer side
        errbox.append(e)
    finally:
        _put_until_stopped(q, stop, _SENTINEL)


class DeviceFeed:
    """Iterate device-resident sharded batches from a host iterator.

    A daemon producer thread pulls from ``host_iterator``, shards each batch
    onto the mesh (``device_put`` dispatches asynchronously), and parks it in
    a bounded queue of depth ``prefetch`` — so host gather/decode for batch
    N+1..N+k overlaps the consumer's compute on batch N. The producer stops
    at the end of the host iterator or when the feed is ``close()``d or
    garbage-collected; a producer-side exception is re-raised on the consumer
    thread at the point of ``next()``.
    """

    def __init__(self, host_iterator: Iterator[Any], mesh: Mesh,
                 prefetch: Optional[int] = None, shard_fn=None,
                 profile_loop: Optional[str] = None):
        # profile_loop: attribute consumer stalls to that loop's host_input
        # phase (profiler). The train loop does NOT set it — it times its
        # own next() so the phase lands inside the step window instead of
        # being double-counted.
        self._profile_loop = profile_loop
        depth = prefetch if prefetch is not None \
            else global_config().get("data.prefetch")
        self._queue: queue.Queue = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._errbox: List[BaseException] = []
        self._thread = threading.Thread(
            target=_produce,
            args=(host_iterator, mesh, self._queue, self._stop, self._errbox,
                  shard_fn if shard_fn is not None else shard_batch),
            daemon=True, name="device-feed")
        self._thread.start()

    def __iter__(self):
        return self

    def __enter__(self) -> "DeviceFeed":
        return self

    def __exit__(self, *exc) -> None:
        # eval/predict passes routinely abandon a feed mid-stream (early
        # break, consumer exception): the context form guarantees the
        # producer thread stops and prefetched device buffers release
        self.close()

    def __next__(self):
        if self._stop.is_set():  # already exhausted or closed
            raise StopIteration
        t0 = time.perf_counter()
        item = self._queue.get()
        dt = time.perf_counter() - t0
        _M_STALL.inc(dt)
        if _utils.span_hooks:
            _utils.offer_span("train.feed_wait", t0, dt)
        if self._profile_loop is not None:
            _profiler.record_phase(self._profile_loop, "host_input", dt,
                                   start=t0)
        if item is _SENTINEL:
            self._stop.set()
            if self._errbox:
                raise self._errbox[0]
            raise StopIteration
        return item

    def _drain(self) -> None:
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass

    def close(self) -> None:
        """Stop the producer; safe to call more than once."""
        self._stop.set()
        self._drain()  # unblock a producer waiting on a full queue
        self._thread.join(timeout=5)
        # a producer blocked in put() may have delivered one last batch
        # between the drain and the stop check; release it deterministically
        self._drain()

    def __del__(self):
        try:
            self._stop.set()
        except Exception:
            pass
