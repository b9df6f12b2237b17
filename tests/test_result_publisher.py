"""The result publisher of ``GenerativeServing`` (serving/server.py
``_ResultPublisher``): result records leave the serve loop, one thread lands
them, and what a client reads is what the loop itself used to write.

Held here: the order of records a uri (a terminal takes its uri's pending
partial's place, lands before other streams' partials and is never
followed by a partial of its uri), that a slow backend no longer stretches
the decode iteration while a backend that keeps up still gets one write a
token, that a streaming client sees every token once and in order, the
barriers of ``serve_step`` by hand / ``drain`` / ``stop`` / ``handoff``,
back-pressure, failed writes, a publisher that dies, and Redis's
acknowledgement only behind the record that settles a request. The
exactly-one-terminal audits are tests/test_overload.py's and
tests/test_fleet.py's, unchanged."""
import sys
import threading
import time
import types
import uuid

import numpy as np
import pytest

from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
from analytics_zoo_tpu.serving.queues import FileQueue
from analytics_zoo_tpu.serving.server import SHUTDOWN_ERROR

from test_redis_serving import FakeRedis
from test_spans import Listener

_LM = {}


def _lm():
    if "lm" not in _LM:
        from analytics_zoo_tpu.capture.lm import TransformerLM
        rs = np.random.RandomState(0)
        lm = TransformerLM(vocab_size=16, hidden=16, n_block=2, n_head=2,
                           max_len=32, seed=0)
        lm.fit(rs.randint(0, 16, (32, 12)), batch_size=8, epochs=1)
        _LM["lm"] = lm
    return _LM["lm"]


@pytest.fixture(params=["file", "redis"])
def src(request, tmp_path, monkeypatch):
    """A queue address on each backend: a spool directory, or the tests'
    in-memory Redis behind ``RedisQueue``."""
    if request.param == "file":
        return f"dir://{tmp_path}/{uuid.uuid4().hex[:8]}"
    FakeRedis.instances.clear()
    mod = types.ModuleType("redis")
    mod.StrictRedis = FakeRedis
    monkeypatch.setitem(sys.modules, "redis", mod)
    return "fakehost:6379"


@pytest.fixture
def file_src(tmp_path):
    return f"dir://{tmp_path}/{uuid.uuid4().hex[:8]}"


def _server(src, slots=4, max_new_tokens=8, **config):
    srv = GenerativeServing(ServingConfig(
        data_src=src, slots=slots, max_new_tokens=max_new_tokens, **config),
        _lm())
    return srv, InputQueue(src), OutputQueue(src)


class Spy:
    """In the place of ``queue.put_result``: every write in the order it
    landed; ``delay_s`` of sleep a write (a slow backend), a ``gate`` that
    holds writes back until it is set (a wedged one), ``fail`` to refuse
    the writes it is given (by their position)."""

    def __init__(self, queue, delay_s=0.0, gate=None, fail=()):
        self.real, self.delay_s, self.gate = queue.put_result, delay_s, gate
        self.fail, self.landed, self.calls = set(fail), [], 0
        self.threads = set()
        queue.put_result = self

    def __call__(self, uri, value):
        self.threads.add(threading.current_thread().name)
        index, self.calls = self.calls, self.calls + 1
        if self.gate is not None:
            assert self.gate.wait(30)
        if self.delay_s:
            time.sleep(self.delay_s)
        if index in self.fail:
            raise OSError("the backend refused this write")
        self.real(uri, value)
        self.landed.append((uri, dict(value)))

    def of(self, uri):
        return [v for u, v in self.landed if u == uri]


def _terminal(value):
    return "error" in value or value.get("done") is True


def _tokens(record):
    """As a client reads a record (a Redis hash keeps a partial's
    ``stream`` field beside the terminal's ``value``)."""
    return record["value"] if record.get("done") else record["stream"]


def _no_publisher(srv):
    pub = srv._publisher
    with pub._cv:
        return (pub._thread is None and not pub._terminals
                and not pub._partials)


def _wait(condition, timeout_s=20.0):
    deadline = time.monotonic() + timeout_s
    while not condition():
        assert time.monotonic() < deadline, "waited too long"
        time.sleep(0.002)


# -- the order of records ------------------------------------------------------

def test_a_terminal_takes_its_partials_place_and_lands_before_others(
        ctx, src):
    srv, _, _ = _server(src)
    gate = threading.Event()
    spy = Spy(srv.queue, gate=gate)
    pub, now = srv._publisher, time.perf_counter()
    a, b = [1], [7]
    pub.partials([("a", a, 1, None, None)], now)
    _wait(lambda: spy.calls == 1)     # a's first partial is in flight
    a.append(2)
    pub.partials([("b", b, 1, None, None), ("a", a, 2, None, None)], now)
    a.append(3)
    pub.partials([("a", a, 3, 11, None)], now)      # replaces [1, 2]
    pub.terminal("a", {"value": [1, 2, 3, 4], "done": True}, now)
    pub.terminal("c", {"error": "deadline exceeded"}, now)
    assert srv.counters["partials_superseded"] == 2
    assert srv._m_backlog.value() == 3              # a, c, and b's partial
    gate.set()
    pub.close()
    assert [u for u, _ in spy.landed] == ["a", "a", "c", "b"]
    first, last = spy.of("a")
    assert first == {"stream": [1], "done": False}
    assert last == {"value": [1, 2, 3, 4], "done": True}
    assert spy.of("b") == [{"stream": [7], "done": False}]
    assert _no_publisher(srv) and srv._m_backlog.value() == 0


def test_a_partial_carries_the_length_it_was_handed_and_its_seed(ctx, src):
    srv, _, outq = _server(src)
    gate = threading.Event()
    spy = Spy(srv.queue, gate=gate)
    tokens = [4, 5]
    srv._publisher.partials([("s", tokens, 2, 9, None)], time.perf_counter())
    tokens.append(6)                  # the loop folds on while it waits
    gate.set()
    srv._publisher.close()
    assert spy.landed == [("s", {"stream": [4, 5], "done": False,
                                 "seed": 9})]
    got = outq.query("s")
    assert got["stream"] == [4, 5] and got["seed"] == 9


# -- the loop no longer waits for the writes -----------------------------------

def test_a_slow_backend_does_not_stretch_the_iteration(ctx, file_src):
    """16 resident streams and 5 ms a write: written by the loop an
    iteration would be 80 ms; the publisher lands each stream's newest
    record instead and the iteration stays what the step costs."""
    srv, inq, outq = _server(file_src, slots=16, max_new_tokens=24)
    spy = Spy(srv.queue, delay_s=0.005)
    lm = _lm()
    prompts = [[1 + i % 7, 2, 3] for i in range(16)]
    want = [lm.generate(np.asarray([p]), max_new_tokens=24)[0].tolist()
            for p in prompts]
    for i, p in enumerate(prompts):
        inq.enqueue_prompt(f"r{i}", p)
    with Listener() as heard:
        srv.start()
        try:
            for i in range(16):
                res = outq.query(f"r{i}", timeout_s=60)
                while res is not None and not res.get("done"):
                    time.sleep(0.005)
                    res = outq.query(f"r{i}", timeout_s=60)
                assert res["value"] == want[i]
        finally:
            srv.stop()
    steps = sorted(s[2] for s in heard.named("serve.step"))
    assert len(steps) >= 24
    assert steps[len(steps) // 2] < 0.040, steps[len(steps) // 2]
    assert srv.counters["partials_superseded"] > 0
    # fewer writes than tokens, one terminal a request, every write the
    # publisher's
    assert len(spy.landed) < 16 * 24
    assert sum(_terminal(v) for _, v in spy.landed) == 16
    assert spy.threads == {f"{srv.metrics_label}-publisher"}
    # a written record carries what was folded since the last one: the
    # partials of a stream only grow, and end under its terminal
    for i in range(16):
        sizes = [len(_tokens(v)) for v in spy.of(f"r{i}")]
        assert sizes == sorted(set(sizes)) and sizes[-1] == 24
    lags = [s[2] for s in heard.named("serve.publish_lag")]
    assert len(lags) == len(spy.landed) and min(lags) >= 0.005


def test_a_backend_that_keeps_up_gets_one_write_a_token(ctx, file_src):
    srv, inq, outq = _server(file_src, slots=16, max_new_tokens=8)
    spy = Spy(srv.queue)
    claim = srv.queue.claim_batch

    def slow_claim(n):
        # the backend keeps up: every record handed over has landed before
        # the iteration goes on (the publisher's own barrier, not a sleep
        # that a loaded machine's writes outlast)
        srv._publisher.close()
        return claim(n)
    srv.queue.claim_batch = slow_claim
    inq.enqueue_prompt("one", [3, 1, 4])
    srv.start()
    try:
        _wait(lambda: (outq.query("one") or {}).get("done"))
    finally:
        srv.stop()
    assert srv.counters["partials_superseded"] == 0
    sizes = [len(_tokens(v)) for v in spy.of("one")]
    assert sizes == list(range(1, 9))


def test_stream_yields_every_token_once_under_a_slow_backend(ctx, src):
    srv, inq, outq = _server(src, slots=8, max_new_tokens=16)
    Spy(srv.queue, delay_s=0.005)
    lm = _lm()
    prompts = [[2 + i, 5, 1 + i] for i in range(8)]
    want = [lm.generate(np.asarray([p]), max_new_tokens=16)[0].tolist()
            for p in prompts]
    got = [None] * 8

    def consume(i):
        got[i] = list(outq.stream(f"r{i}", timeout_s=60))
    readers = [threading.Thread(target=consume, args=(i,)) for i in range(8)]
    for i, p in enumerate(prompts):
        inq.enqueue_prompt(f"r{i}", p)
    srv.start()
    try:
        for t in readers:
            t.start()
        for t in readers:
            t.join(timeout=90)
            assert not t.is_alive()
    finally:
        srv.stop()
    assert got == want


def test_two_threads_of_load_lose_no_terminal(ctx, file_src):
    """The loop, the publisher and four clients under a switch interval of
    10 us: one terminal a request, the accounting back at nought."""
    srv, inq, outq = _server(file_src, slots=8, max_new_tokens=6)
    spy = Spy(srv.queue)
    uris = [f"r{i}" for i in range(40)]
    done = {}

    def client(mine):
        for uri in mine:
            inq.enqueue_prompt(uri, [1 + len(uri) % 5, 2])
        for uri in mine:
            done[uri] = list(outq.stream(uri, timeout_s=90))
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        srv.start()
        clients = [threading.Thread(target=client, args=(uris[i::4],))
                   for i in range(4)]
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=120)
            assert not t.is_alive()
        srv.drain()
    finally:
        sys.setswitchinterval(was)
        srv.stop()
    assert sorted(done) == sorted(uris)
    assert all(len(v) == 6 for v in done.values())
    terminals = [u for u, v in spy.landed if _terminal(v)]
    assert sorted(terminals) == sorted(uris)
    for uri in uris:  # and nothing of a stream behind its terminal
        assert _terminal(spy.of(uri)[-1])
    snap = srv.health_snapshot()
    assert snap["in_flight"] == 0 and snap["latency_ms"]["window"] == 40
    assert _no_publisher(srv)


# -- the barriers --------------------------------------------------------------

def test_a_step_by_hand_returns_with_its_records_readable(ctx, src):
    srv, inq, outq = _server(src, slots=2, max_new_tokens=5)
    Spy(srv.queue, delay_s=0.003)
    inq.enqueue_prompt("r0", [1, 2, 3])
    inq.enqueue_prompt("r1", [4, 5])
    for step in range(1, 6):
        assert srv.serve_step() == 2
        assert _no_publisher(srv)
        for uri in ("r0", "r1"):
            assert len(_tokens(outq.query(uri))) == step
    assert outq.query("r0")["done"] and outq.query("r1")["done"]
    assert srv.health_snapshot()["in_flight"] == 0


def test_drain_returns_behind_the_last_record(ctx, file_src):
    srv, inq, outq = _server(file_src, slots=4, max_new_tokens=12)
    spy = Spy(srv.queue, delay_s=0.004)
    for i in range(4):
        inq.enqueue_prompt(f"r{i}", [1 + i, 2])
    srv.start()
    _wait(lambda: srv.health_snapshot()["slots_occupied"] == 4)
    srv.drain()
    assert _no_publisher(srv)
    writes = len(spy.landed)
    for i in range(4):
        assert len(outq.query(f"r{i}")["value"]) == 12
        assert _terminal(spy.of(f"r{i}")[-1])
    time.sleep(0.05)
    assert len(spy.landed) == writes     # nothing lands behind drain()


@pytest.mark.parametrize("looping", [True, False], ids=["loop", "by_hand"])
def test_stop_writes_every_shutdown_error_and_joins(ctx, file_src, looping):
    srv, inq, outq = _server(file_src, slots=4, max_new_tokens=25)
    Spy(srv.queue, delay_s=0.004)
    for i in range(4):
        inq.enqueue_prompt(f"r{i}", [1 + i, 2])
    before = set(threading.enumerate())
    if looping:
        srv.start()
        _wait(lambda: srv.counters["partials_superseded"] > 0)
    else:
        srv.serve_step()
    srv.stop()
    for i in range(4):
        assert outq.query(f"r{i}")["error"] == SHUTDOWN_ERROR
    assert _no_publisher(srv)
    assert set(threading.enumerate()) <= before
    assert srv.health_snapshot()["in_flight"] == 0


def test_handoff_writes_nothing_of_a_stream_behind_its_enqueue(
        ctx, tmp_path):
    src = f"dir://{tmp_path}/a"
    srv, inq, _ = _server(src, slots=8, max_new_tokens=25)
    events, elock = [], threading.Lock()
    spy = Spy(srv.queue, delay_s=0.004)
    landed = spy.real

    def stamped(uri, value):          # under the spy: when a write landed
        landed(uri, value)
        with elock:
            events.append(("write", uri))
    spy.real = stamped
    to = FileQueue(f"{tmp_path}/b")
    enqueue = to.enqueue

    def noted(uri, rec):
        with elock:
            events.append(("enqueue", uri))
        return enqueue(uri, rec)
    to.enqueue = noted
    for i in range(8):
        inq.enqueue_prompt(f"r{i}", [1 + i, 2])
    srv.start()
    _wait(lambda: srv.counters["partials_superseded"] > 4)
    assert srv.handoff(to) == 8
    assert _no_publisher(srv)
    time.sleep(0.05)                  # a late write would land by now
    for i in range(8):
        at = events.index(("enqueue", f"r{i}"))
        assert ("write", f"r{i}") not in events[at:]
    # what the streams had decoded went with them, whatever was written
    assert [rec["uri"] for _, rec in to.claim_batch(8)] == [
        f"r{i}" for i in range(8)]
    assert srv.health_snapshot()["in_flight"] == 0
    srv.stop()


# -- back-pressure, failures ---------------------------------------------------

def test_more_terminals_than_slots_block_the_hand_over(ctx, file_src):
    srv, _, outq = _server(file_src, slots=2)
    gate = threading.Event()
    Spy(srv.queue, gate=gate)
    handed = []

    def hand_over():
        for i in range(5):
            srv._publisher.terminal(f"t{i}", {"error": "x"},
                                    time.perf_counter())
            handed.append(i)
    loop = threading.Thread(target=hand_over, daemon=True)
    loop.start()
    # one in flight and slots pending pass; the next waits for the backend
    _wait(lambda: len(handed) == 3)
    time.sleep(0.05)
    assert len(handed) == 3 and loop.is_alive()
    gate.set()
    loop.join(timeout=20)
    assert not loop.is_alive() and len(handed) == 5
    srv._publisher.close()
    assert all(outq.query(f"t{i}")["error"] == "x" for i in range(5))


def test_failed_writes_are_dropped_or_settled(ctx, file_src, caplog):
    """A partial's failed write is logged and the next record carries its
    tokens; a terminal's failed write is logged and settles the
    accounting all the same."""
    srv, inq, outq = _server(file_src, slots=1, max_new_tokens=3)
    spy = Spy(srv.queue, fail={0, 2})
    inq.enqueue_prompt("r", [1, 2, 3])
    with caplog.at_level("ERROR", logger="analytics_zoo_tpu.serving"):
        for _ in range(3):
            srv.serve_step()
    assert [len(v["stream"]) for v in spy.of("r")] == [2]
    assert outq.query("r")["stream"] and not outq.query("r")["done"]
    assert "partial result for r failed" in caplog.text
    assert "posting result for r failed" in caplog.text
    snap = srv.health_snapshot()
    assert snap["in_flight"] == 0 and snap["slots_occupied"] == 0
    assert snap["latency_ms"]["window"] == 1


@pytest.mark.parametrize("looping", [True, False], ids=["loop", "by_hand"])
def test_a_publisher_that_dies_fails_the_health_check(ctx, file_src,
                                                      looping):
    srv, inq, _ = _server(file_src, slots=2, max_new_tokens=2)

    def boom(uri):
        raise ValueError("injected into the publisher")
    srv._settle = boom
    inq.enqueue_prompt("r", [1, 2])
    if looping:
        srv.start()
        _wait(lambda: getattr(srv, "_background_error", None) is not None)
        inq.enqueue_prompt("r2", [3, 4])   # the loop ends at its next record
        srv._thread.join(timeout=20)
        assert not srv._thread.is_alive()
    else:
        srv.serve_step()
        srv.serve_step()
    with pytest.raises(RuntimeError, match="died in the background"):
        srv.check_health()
    assert srv.health_snapshot()["state"] == "crashed"
    with pytest.raises(RuntimeError, match="publisher died"):
        srv._publisher.terminal("late", {"error": "x"}, time.perf_counter())
    with srv._publisher._cv:
        assert srv._publisher._thread is None


# -- Redis: the acknowledgement ------------------------------------------------

def test_redis_acknowledges_behind_the_record_that_settles(
        ctx, tmp_path, monkeypatch):
    FakeRedis.instances.clear()
    mod = types.ModuleType("redis")
    mod.StrictRedis = FakeRedis
    monkeypatch.setitem(sys.modules, "redis", mod)
    srv, inq, outq = _server("fakehost:6379", slots=2, max_new_tokens=4,
                             stream_interval=0)
    db, q = srv.queue.db, srv.queue
    pel = db.groups[(q.STREAM, q.GROUP)]["pel"]
    at_hset = []
    hset = db.hset

    def watched(key, mapping):
        at_hset.append((key, len(pel), threading.current_thread().name))
        return hset(key, mapping)
    db.hset = watched
    inq.enqueue_prompt("r", [1, 2, 3])
    srv.start()
    try:
        _wait(lambda: (outq.query("r") or {}).get("done"))
        _wait(lambda: not pel)
    finally:
        srv.stop()
    # one record, written with the claim still unacknowledged, by the
    # publisher; acknowledged once it had landed
    assert at_hset == [("result:r", 1, f"{srv.metrics_label}-publisher")]
    assert not q._unacked and srv.health_snapshot()["in_flight"] == 0
