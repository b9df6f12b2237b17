"""Chrome-trace span recorder + request-lifecycle flow tracing.

The reference stops at aggregate wall-time logs (``Utils.timeIt``,
``zoo/.../common/Utils.scala``; BigDL ``Metrics`` phase totals) — SURVEY §5
notes it has "no sampling profiler / chrome-trace". While a :func:`trace`
session is active, every ``time_it`` span (train_step, device feed waits,
serving phases, checkpoint writes, forked transform-worker tasks) is
recorded and written out in the Chrome ``chrome://tracing`` / Perfetto JSON
array format, so a training or serving run can be inspected on a timeline
per process and thread.

Three capabilities beyond the original recorder:

- **Sessions nest.** An inner ``trace()`` no longer swallows the outer
  session's spans: every active session records every span, so a broad
  "whole run" trace and a narrow "just this phase" trace can coexist.
- **Forked workers show up, pid-correct.** Spans carry the real
  ``os.getpid()``; a span recorded in a forked child (transform workers)
  is spooled to a crash-tolerant per-pid JSONL part file that the parent
  merges at dump time — worker-pool activity lands on the same timeline as
  the threads that consume it. (``time.perf_counter`` is CLOCK_MONOTONIC
  on Linux, shared across processes, so child timestamps line up.)
- **Flow events.** :func:`flow_point` stamps Chrome flow-phase events
  (``s``/``t``/``f``) so one request's lifecycle — enqueue → claim →
  decode → dispatch → result, or for a generated stream enqueue → claim →
  join → first token → result — draws as a single arrowed chain across
  threads and processes in Perfetto. The serving stack calls it with the
  ``trace_id`` the client stamps at enqueue; a generated stream's claim,
  join and first token are drawn from the span records that carry the
  same id as their ``request`` (``serve.queue_wait``, ``serve.join``,
  ``serve.first_token``), so they are stamped by no call of their own.

Thread rows are named by ROLE: a row is a lane of the program's span
records, the emitting thread's live name (``device-feed``,
``zoo-serving-claim``, ``srv1-loop``, ...); :func:`set_thread_label`
renames the current thread for code that runs on an anonymous thread. The
stretches of a request's life (its wait in the queue, its time to a first
token) belong to no thread and lie on the process's ``requests`` row.

Usage::

    from analytics_zoo_tpu.utils.trace import trace
    with trace("/tmp/train_trace.json"):
        estimator.train(fs, batch_size=..., epochs=1)
    # open https://ui.perfetto.dev and load the file

A session keeps no second copy of a span: ``common.utils`` makes one record
a span while anybody listens on ``span_hooks`` (one append a span, the
newest ``RECORDS_KEPT`` kept), the session's hook is on that list only while
a session is open, and the dump draws the records that ended inside it.
Outside a session nothing listens and a ``time_it`` span takes no clock.
"""
from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

from ..common import utils as _utils

#: flow-chain category — one constant so emitters and Perfetto bind on the
#: same (cat, name, id) triple
FLOW_CAT = "request"


def set_thread_label(label: str) -> None:
    """Name the CURRENT thread's trace row by role (producer / server /
    worker / ...). Threads created with an explicit ``name=`` are already
    labeled; this is for code running on threads it did not create."""
    threading.current_thread().name = label


#: the row of a process's timeline for what belongs to no thread: the
#: stretches of a request's life (``SpanRecord.lane`` ``None``)
REQUESTS_ROW = "requests"

#: the records that are a stage of a generated stream's flow chain, with
#: the stage's name and whether it is stamped at the record's end
_FLOW_STAGES = {"serve.queue_wait": ("serving.claim", True),
                "serve.join": ("serving.join", False),
                "serve.first_token": ("serving.first_token", True)}


class _TraceSession:
    """One open ``trace()``. The spans of this process are not recorded
    here: they are drawn at :meth:`dump` from the program's own records
    (``common.utils.span_records``), lanes, requests and all. What the
    session keeps itself are the flow points stamped through
    :func:`flow_point` and, in a forked child, a spool of its spans."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._events: List[dict] = []  # flow points, "lane" for "tid"
        self._names: Dict[Tuple[int, int], str] = {}  # child: (pid, tid)
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None  # set when the session closes
        self.pid = os.getpid()
        # spool for forked children: each foreign pid appends JSONL lines
        # (crash-tolerant — a SIGKILLed worker loses at most a partial
        # final line, which the merge skips)
        self.spool = tempfile.mkdtemp(prefix="zoo_trace_spool_")
        self._part = None        # child-side open part file
        self._part_pid = -1

    # -- recording ------------------------------------------------------------

    def _spool(self, pid: int, ev: dict) -> None:
        """A forked child's event, to its per-pid part file. The file
        handle is re-resolved after any further fork (pid changed)."""
        ev["pid"] = pid
        tid = ev["tid"] = threading.get_ident()
        if self._part is None or self._part_pid != pid:
            try:
                self._part = open(
                    os.path.join(self.spool, f"{pid}.jsonl"), "a")
                self._part_pid = pid
                self._part.write(json.dumps(
                    {"name": "process_name", "ph": "M", "pid": pid,
                     "args": {"name": _process_label()}}) + "\n")
            except OSError:
                return  # spool dir gone (session ended in parent)
        try:
            key = (pid, tid)
            if key not in self._names:
                self._names[key] = threading.current_thread().name
                self._part.write(json.dumps(
                    {"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid,
                     "args": {"name": self._names[key]}}) + "\n")
            self._part.write(json.dumps(ev) + "\n")
            self._part.flush()
        except (OSError, ValueError):
            pass

    def add(self, name: str, start: float, elapsed: float) -> None:
        """The hook's side of a span: nothing in the session's own
        process, whose records the dump reads; a forked child's records
        die with it, so its spans are spooled as they end."""
        pid = os.getpid()
        if pid != self.pid:
            self._spool(pid, self._slice(name, start, elapsed))

    def _slice(self, name: str, start: float, elapsed: float,
               **args) -> dict:
        ev = {"name": name, "ph": "X",  # complete event
              "ts": (start - self.t0) * 1e6,  # microseconds
              "dur": elapsed * 1e6, "cat": "analytics_zoo_tpu"}
        if args:
            ev["args"] = args
        return ev

    def _flow(self, flow_id: int, stage: str, phase: str,
              t: float) -> List[dict]:
        """One flow-chain point: a 2µs anchor slice named ``stage`` plus
        the flow event Perfetto binds to it (same ts, same track)."""
        anchor = self._slice(stage, t, 2e-6, trace_id=flow_id)
        ev = {"name": FLOW_CAT, "cat": FLOW_CAT, "ph": phase,
              "id": flow_id, "ts": anchor["ts"] + 1.0}
        if phase == "f":
            ev["bp"] = "e"  # bind the terminus to the enclosing slice
        return [anchor, ev]

    def add_flow(self, flow_id: int, stage: str, phase: str,
                 t: float) -> None:
        pid = os.getpid()
        events = self._flow(flow_id, stage, phase, t)
        if pid != self.pid:
            for ev in events:
                self._spool(pid, ev)
            return
        lane = threading.current_thread().name
        with self._lock:
            for ev in events:
                ev["lane"] = lane
                self._events.append(ev)

    # -- output ---------------------------------------------------------------

    def _drawn(self) -> List[dict]:
        """The program's records that ended while this session was open,
        as timeline events that name their lane: a block on a thread is a
        slice on the thread's row; a stretch of a request's life is an
        asynchronous slice of the process, so that the waits of many
        requests lie side by side; a stage of a generated stream's flow
        chain is stamped where its record says."""
        t1 = time.perf_counter() if self.t1 is None else self.t1
        events = []
        for r in _utils.span_records():
            end = r.start + r.seconds
            if not self.t0 <= end <= t1:
                continue
            lane = REQUESTS_ROW if r.lane is None else r.lane
            if r.lane is None:
                life = {"name": r.name, "cat": "request_life", "id": r.id,
                        "lane": lane, "args": {"request": r.request}}
                events.append(dict(life, ph="b",
                                   ts=(r.start - self.t0) * 1e6))
                events.append(dict(life, ph="e", ts=(end - self.t0) * 1e6))
            else:
                args = dict(r.args, span=r.id)
                if r.parent is not None:
                    args["parent"] = r.parent
                if r.request is not None:
                    args["request"] = r.request
                events.append(dict(
                    self._slice(r.name, r.start, r.seconds, **args),
                    lane=lane))
            stage = _FLOW_STAGES.get(r.name)
            if stage is not None and isinstance(r.request, int):
                for ev in self._flow(r.request, stage[0], "t",
                                     end if stage[1] else r.start):
                    events.append(dict(ev, lane=lane))
        return events

    def _merge_parts(self) -> List[dict]:
        merged: List[dict] = []
        for part in sorted(glob.glob(os.path.join(self.spool, "*.jsonl"))):
            try:
                with open(part) as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            merged.append(json.loads(line))
                        except ValueError:
                            pass  # torn final line of a killed worker
            except OSError:
                pass
        return merged

    def dump(self, path: str) -> int:
        with self._lock:
            events = list(self._events)
        events.extend(self._drawn())
        # a row a lane; the requests' row, where there is one, is row 0
        lanes = {ev["lane"] for ev in events}
        rows = {REQUESTS_ROW: 0}
        for lane in sorted(lanes - set(rows)):
            rows[lane] = len(rows)
        for ev in events:
            ev["pid"], ev["tid"] = self.pid, rows[ev.pop("lane")]
        meta = [{"name": "process_name", "ph": "M", "pid": self.pid,
                 "args": {"name": _process_label()}}]
        meta += [{"name": "thread_name", "ph": "M", "pid": self.pid,
                  "tid": tid, "args": {"name": lane}}
                 for lane, tid in rows.items() if lane in lanes]
        parts = self._merge_parts()
        meta += [ev for ev in parts if ev.get("ph") == "M"]
        events += [ev for ev in parts if ev.get("ph") != "M"]
        events.sort(key=lambda ev: ev["ts"])
        with open(path, "w") as f:
            json.dump(meta + events, f)
        shutil.rmtree(self.spool, ignore_errors=True)
        return len(events)


def _process_label() -> str:
    import multiprocessing
    name = multiprocessing.current_process().name
    return "main" if name == "MainProcess" else name


#: stack of active sessions — EVERY active session records every span, so
#: nested trace() calls merge instead of the inner silently dropping the
#: outer's spans
_sessions: List[_TraceSession] = []
_sessions_lock = threading.Lock()


def tracing() -> bool:
    """Cheap hot-path check: is any trace session active?"""
    return bool(_sessions)


def _record(name: str, start: float, elapsed: float) -> None:
    for session in tuple(_sessions):
        session.add(name, start, elapsed)


def _open(session: _TraceSession) -> None:
    """The first open session puts the recorder on ``span_hooks``."""
    with _sessions_lock:
        _sessions.append(session)
        if len(_sessions) == 1:
            _utils.span_hooks.append(_record)


def _close(session: _TraceSession) -> None:
    """The last session to close takes the recorder off again."""
    with _sessions_lock:
        try:
            _sessions.remove(session)
        except ValueError:  # pragma: no cover - double-exit safety
            return
        session.t1 = time.perf_counter()
        if not _sessions:
            _utils.span_hooks.remove(_record)


def flow_point(flow_id: Optional[int], stage: str, phase: str) -> None:
    """Stamp one point of a request-lifecycle flow chain in every active
    session. ``phase``: ``"s"`` starts the chain (enqueue), ``"t"`` marks
    an intermediate step (claim / decode / dispatch), ``"f"`` ends it
    (result post). A ``None``/missing ``flow_id`` (request from a client
    that predates trace ids) is skipped silently."""
    if flow_id is None or not _sessions:
        return
    t = time.perf_counter()
    for session in tuple(_sessions):
        session.add_flow(int(flow_id), stage, phase, t)


def new_trace_id() -> int:
    """A fresh flow-chain id (31-bit, collision-unlikely): stamped onto
    serving requests at enqueue so every pipeline stage can tag its spans."""
    return int.from_bytes(os.urandom(4), "big") & 0x7FFFFFFF


@contextlib.contextmanager
def trace(path: str) -> Iterator[_TraceSession]:
    """Record every ``time_it`` span and :func:`flow_point` until exit,
    then write Chrome-trace JSON to ``path``. Sessions NEST by merging:
    spans recorded during an inner session land in both traces."""
    session = _TraceSession()
    _open(session)
    try:
        yield session
    finally:
        _close(session)
        count = session.dump(path)
        _utils.logger.info("trace: wrote %d spans to %s", count, path)
