"""The serve loop keeps one decode step in flight (docs/serving.md "One step
ahead"): step N+1 goes to the device from the device's own tokens before
step N's are fetched and folded. The same tokens must reach the same
records whichever way the loop is driven:

- ``hand``: ``serve_step()`` with no loop running, which folds its own step
  before it returns;
- ``ahead``: ``serve_step()`` called by the test with the server told that a
  loop runs, so every step is dispatched ahead of the fold of the one
  before it, in an order the test controls;
- ``loop``: ``start()`` / ``drain()``, the loop's own thread.

Over the plain GPT-2-shaped ``TransformerLM`` and the two tiny layered
decoders (MiniCPM-SALA's layers, chunked prefill and a state a slot;
SmallThinker's, window pages and experts)."""
import os
import sys
import time
import uuid

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from analytics_zoo_tpu.common import faults  # noqa: E402
from analytics_zoo_tpu.common import utils as program_utils  # noqa: E402
from analytics_zoo_tpu.serving import (GenerativeServing,  # noqa: E402
                                       ServingConfig)
from analytics_zoo_tpu.serving import server as server_module  # noqa: E402
from analytics_zoo_tpu.serving.client import (InputQueue,  # noqa: E402
                                              OutputQueue)
from analytics_zoo_tpu.serving.queues import make_queue  # noqa: E402
from analytics_zoo_tpu.serving.server import (DEADLINE_ERROR,  # noqa: E402
                                              SHUTDOWN_ERROR)

KINDS = ["plain", "sala", "smallthinker"]
MODES = ["hand", "ahead", "loop"]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


class _Model:
    """One kind's model, the pool its server takes, and what a served
    stream is held to: serial ``generate()`` for the plain LM, the plain
    reference's greedy choice at every served position for a layered
    decoder (which has no ``generate()``)."""

    def __init__(self, kind):
        self.kind = kind
        if kind == "plain":
            from analytics_zoo_tpu.capture.lm import TransformerLM
            rs = np.random.RandomState(0)
            self.lm = TransformerLM(vocab_size=16, hidden=16, n_block=2,
                                    n_head=2, max_len=64, seed=0)
            self.lm.fit(rs.randint(0, 16, (32, 12)), batch_size=8, epochs=1)
            self.pool = dict(kv_pages=24, kv_page_len=8)
            self.vocab, self.long = 16, 9
        else:
            import test_sala_decoder as sala
            import test_smallthinker_decoder as small
            mod = sala if kind == "sala" else small
            self.cfg = mod.tiny_cfg()
            self.ref = mod.ref
            if kind == "sala":
                from analytics_zoo_tpu.capture.decoder import (
                    DecoderSpec, LayeredDecoder)
                self.weights = mod.ref.init_weights(self.cfg, 5)
                self.lm = LayeredDecoder(
                    DecoderSpec.from_config(self.cfg, mod.MAX_LEN),
                    prefill_chunk=64)
                self.lm.set_params(self.weights)
                self.pool = dict(kv_pages=40, kv_page_len=mod.PAGE)
                self.long = 70       # two chunks of 64
            else:
                self.weights, self.lm = mod.build(self.cfg)
                self.pool = dict(kv_page_len=mod.PAGE)
                self.long = 45       # two chunks of 32, beyond the window
            self.vocab = 97

    def prompts(self, seed, lengths):
        rs = np.random.RandomState(seed)
        return [rs.randint(1, self.vocab, (n,)).tolist() for n in lengths]

    def server(self, tmp_path, **more):
        more.setdefault("slots", 2)
        more.setdefault("max_new_tokens", 8)
        for key, value in self.pool.items():
            more.setdefault(key, value)
        src = f"dir://{tmp_path}/{uuid.uuid4().hex[:8]}"
        srv = GenerativeServing(ServingConfig(data_src=src, **more), self.lm)
        return srv, InputQueue(src), OutputQueue(src)

    def serial(self, prompt, n, **sampling):
        assert self.kind == "plain"
        return self.lm.generate(np.asarray([prompt]), max_new_tokens=n,
                                **sampling)[0].tolist()

    def holds(self, prompt, served, n):
        """``served`` is what serial greedy decoding gives for ``prompt``."""
        assert len(served) == n, (len(served), n)
        if self.kind == "plain":
            assert served == self.serial(prompt, n)
            return
        row = np.asarray(list(prompt) + list(served), np.int32)[None]
        out = np.asarray(self.ref.logits(self.cfg, self.weights, row))[0]
        at = len(prompt) - 1 + np.arange(len(served))
        assert float(np.max(out[at].max(axis=1) - out[at, served])) < 2e-6


_MODELS = {}


@pytest.fixture(params=KINDS)
def model(request):
    if request.param not in _MODELS:
        _MODELS[request.param] = _Model(request.param)
    return _MODELS[request.param]


def _terminals(srv, monkeypatch):
    """Count the terminals each uri gets, as the backend sees them."""
    seen = {}
    put = srv.queue.put_result

    def counting(uri, value):
        if "error" in value or value.get("done"):
            seen[uri] = seen.get(uri, 0) + 1
        return put(uri, value)
    monkeypatch.setattr(srv.queue, "put_result", counting)
    return seen


def _steps(srv, mode, n):
    """``n`` iterations by the test's own hand."""
    srv._loop_running = mode == "ahead"
    try:
        return [srv.serve_step() for _ in range(n)]
    finally:
        srv._loop_running = False


def _settle(srv):
    """After stepping ``ahead``: what the loop does as it ends."""
    srv._fold_in_flight()
    srv._publisher.close()


def _finish(srv, mode, outq, uris, timeout_s=120.0):
    """Serve until every uri has its terminal; returns them by uri."""
    if mode == "loop":
        srv.start()
        deadline = time.monotonic() + timeout_s
        done = {}
        while len(done) < len(uris):
            srv.check_health()
            assert time.monotonic() < deadline, f"unanswered: {uris}"
            for uri in uris:
                res = outq.query(uri)
                if res is not None and ("error" in res or res.get("done")):
                    done[uri] = res
            time.sleep(0.005)
        srv.drain()
        return done
    idle = 0
    while idle < 3:
        idle = idle + 1 if _steps(srv, mode, 1) == [0] else 0
    _settle(srv)
    return {uri: outq.query(uri, timeout_s=5) for uri in uris}


def _all_back(srv):
    snap = srv.health_snapshot()
    assert snap["slots_occupied"] == 0 and snap["in_flight"] == 0
    assert snap["kv_pages_free"] == srv.num_pages - 1
    assert snap["kv_pages_in_use"]["window"] in (None, 0)
    assert srv._in_flight_step is None and srv._leaving == []


# -- the same tokens reach the same records -----------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_greedy_streams_equal_serial_decoding(model, tmp_path, mode):
    """Five requests through two slots, joins mid-run, one prompt of two
    chunks: every stream is the serial one; the step program compiled once;
    under a loop the steps went ahead, by hand none did."""
    prompts = model.prompts(3, (4, 1, model.long, 3, 5))
    news = [8, 3, 6, 8, 1]
    srv, inq, outq = model.server(tmp_path)
    for i, (p, n) in enumerate(zip(prompts, news)):
        inq.enqueue_prompt(f"r{i}", p, max_new_tokens=n)
    got = _finish(srv, mode, outq, [f"r{i}" for i in range(5)])
    for i, (p, n) in enumerate(zip(prompts, news)):
        assert got[f"r{i}"].get("done") is True, got[f"r{i}"]
        model.holds(p, got[f"r{i}"]["value"], n)
    _all_back(srv)
    snap = srv.health_snapshot()
    assert snap["overrun_slot_steps_total"] == 0
    assert snap["counters"]["errors"] == 0
    assert srv._step_fn._cache_size() == 1
    if mode == "hand":
        assert snap["steps_ahead_total"] == 0
        assert snap["decode_steps_total"] > 0
    else:
        # no slot-step is wasted on a stream whose budget is spent: the
        # tokens folded are the slot-steps dispatched
        assert 0 < snap["steps_ahead_total"] < snap["decode_steps_total"]
    assert snap["tokens_total"] == sum(news)


@pytest.mark.parametrize("mode", MODES)
def test_seeded_sampling_equals_serial_sampling(tmp_path, mode):
    model = _MODELS.setdefault("plain", _Model("plain"))
    prompts = model.prompts(4, (5, 2, 1, 7))
    seeds = [11, 22, 33, 44]
    sampling = dict(temperature=0.9, top_k=8)
    srv, inq, outq = model.server(tmp_path, **sampling)
    for i, (p, s) in enumerate(zip(prompts, seeds)):
        inq.enqueue_prompt(f"r{i}", p, seed=s)
    got = _finish(srv, mode, outq, [f"r{i}" for i in range(4)])
    for i, (p, s) in enumerate(zip(prompts, seeds)):
        assert got[f"r{i}"]["value"] == model.serial(p, 8, seed=s,
                                                     **sampling)
    _all_back(srv)


# -- how a stream ends ------------------------------------------------------------


def _uncut(model, tmp_path, prompt, n):
    srv, inq, outq = model.server(tmp_path, slots=1)
    inq.enqueue_prompt("uncut", prompt, max_new_tokens=n)
    full = _finish(srv, "hand", outq, ["uncut"])["uncut"]["value"]
    model.holds(prompt, full, n)
    return full


@pytest.mark.parametrize("mode", MODES)
def test_a_stream_that_ends_on_eos(model, tmp_path, mode, monkeypatch):
    """Nothing after the eos reaches the record; under a loop the stream
    has been stepped once more by then, which is counted once; its pages
    come back and the slot's next tenant decodes its own prompt."""
    first, tenant = model.prompts(7, (6, 4))
    full = _uncut(model, tmp_path, first, 10)
    # an eos the stream meets before its budget, at its first occurrence
    cut = next((k for k in range(1, 9) if full[k] not in full[:k]), 0)
    srv, inq, outq = model.server(tmp_path, slots=1, eos_id=full[cut],
                                  max_new_tokens=10)
    seen = _terminals(srv, monkeypatch)
    inq.enqueue_prompt("cut", first)
    inq.enqueue_prompt("tenant", tenant, max_new_tokens=3)
    got = _finish(srv, mode, outq, ["cut", "tenant"])
    assert got["cut"]["value"] == full[:cut + 1]
    # (the tenant's three tokens do not hold the eos: its end is its budget)
    model.holds(tenant, got["tenant"]["value"], 3)
    assert seen == {"cut": 1, "tenant": 1}
    _all_back(srv)
    snap = srv.health_snapshot()
    assert snap["overrun_slot_steps_total"] == (0 if mode == "hand" else 1)
    assert snap["tokens_total"] == cut + 1 + 3
    assert snap["decode_steps_total"] == cut + 1 + 3 + (mode != "hand")


def test_a_budget_ends_a_stream_at_its_dispatch(model, tmp_path,
                                                monkeypatch):
    """One slot, two requests. The step that reaches the first stream's
    budget frees the slot on the host and on the device before the next
    dispatch: the second request joins the slot while that step is still
    in flight, starts from its own prompt's last token, and no slot-step
    is spent on a stream that has ended."""
    first, second = model.prompts(8, (5, 3))
    srv, inq, outq = model.server(tmp_path, slots=1)
    seen = _terminals(srv, monkeypatch)
    inq.enqueue_prompt("a", first, max_new_tokens=2)
    inq.enqueue_prompt("b", second, max_new_tokens=4)
    assert _steps(srv, "ahead", 1) == [1]           # a joins, step 1
    stream = srv._streams[0]
    assert stream.uri == "a" and stream.dispatched == 1
    assert stream.tokens == []                      # in flight, not folded
    assert _steps(srv, "ahead", 1) == [1]           # step 2, fold of step 1
    assert stream.dispatched == 2 and len(stream.tokens) == 1
    assert stream.slot is None and srv._leaving == [stream]
    assert srv._streams[0] is None and not srv._active_host[0]
    assert not bool(np.asarray(srv._state["active"])[0])
    in_flight = srv._in_flight_step
    assert in_flight["streams"] == [(0, stream)]
    assert seen == {}
    assert _steps(srv, "ahead", 1) == [1]           # b joins the slot
    assert srv._streams[0].uri == "b" and stream.ended == "budget"
    assert len(stream.tokens) == 2 and srv._leaving == []
    assert srv._in_flight_step is not in_flight
    got = _finish(srv, "ahead", outq, ["a", "b"])
    model.holds(first, got["a"]["value"], 2)
    model.holds(second, got["b"]["value"], 4)
    assert seen == {"a": 1, "b": 1}
    snap = srv.health_snapshot()
    assert snap["decode_steps_total"] == 2 + 4 == snap["tokens_total"]
    assert snap["overrun_slot_steps_total"] == 0
    _all_back(srv)


# -- what ends a stream from outside, with a step in flight -----------------------


def _two_resident(model, tmp_path, monkeypatch, leaving):
    """Two slots, stepped ahead until stream ``b`` (budget 16) is resident
    and stream ``a`` has been asked for two tokens with the second still in
    flight: where ``leaving``, two is its budget, so it has left its slot;
    else its budget is 8. Returns the two streams too."""
    prompts = dict(zip("ab", model.prompts(9, (4, 6))))
    srv, inq, outq = model.server(tmp_path)
    seen = _terminals(srv, monkeypatch)
    inq.enqueue_prompt("b", prompts["b"], max_new_tokens=16)
    assert _steps(srv, "ahead", 1) == [1]
    inq.enqueue_prompt("a", prompts["a"], max_new_tokens=2 if leaving else 8)
    for _ in range(10):  # a chunked model feeds a after five steps of b
        _steps(srv, "ahead", 1)
        streams = {s.uri: s for s in srv._streams + srv._leaving
                   if s is not None}
        if "a" in streams and streams["a"].dispatched == 2:
            break
    a, b = streams["a"], streams["b"]
    assert len(a.tokens) == 1 and srv._in_flight_step is not None
    assert (a in srv._leaving) == leaving and b.slot is not None
    return srv, inq, outq, seen, prompts, a, b


def test_a_deadline_passes_with_a_step_in_flight(model, tmp_path,
                                                 monkeypatch):
    srv, inq, outq, seen, prompts, a, b = _two_resident(
        model, tmp_path, monkeypatch, leaving=False)
    folded = len(b.tokens)
    assert b.dispatched == folded + 1
    clock = server_module.wall_clock
    b.expires = clock() + 1000.0
    monkeypatch.setattr(server_module, "wall_clock",
                        lambda: clock() + 2000.0)
    _steps(srv, "ahead", 1)
    monkeypatch.setattr(server_module, "wall_clock", clock)
    got = _finish(srv, "ahead", outq, ["a", "b"])
    assert got["b"]["error"] == DEADLINE_ERROR
    assert len(b.tokens) == folded    # the token in flight was dropped
    model.holds(prompts["a"], got["a"]["value"], 8)
    assert seen == {"a": 1, "b": 1}
    assert srv.counters["expired"] == 1
    assert srv.health_snapshot()["overrun_slot_steps_total"] == 0
    _all_back(srv)


def test_a_fault_before_a_dispatch_keeps_what_is_in_flight(
        model, tmp_path, monkeypatch):
    """``_fail_active`` without ``rebuild``: the resident stream errors, the
    stream whose last token is in flight gets its value whole."""
    srv, inq, outq, seen, prompts, a, b = _two_resident(
        model, tmp_path, monkeypatch, leaving=True)
    faults.arm("serving.decode_step", at=1)
    assert _steps(srv, "ahead", 1) == [0]
    got = _finish(srv, "ahead", outq, ["a", "b"])
    assert "FaultInjected" in got["b"]["error"]
    model.holds(prompts["a"], got["a"]["value"], 2)
    assert seen == {"a": 1, "b": 1}
    assert srv.health_snapshot()["kv_pool_rebuilds"] == 0
    _all_back(srv)


def test_a_failed_fetch_drops_what_is_in_flight_with_the_pools(
        model, tmp_path, monkeypatch):
    """``_fail_active`` with ``rebuild``: the resident stream and the one
    that had left its slot both get the error, once; the next request is
    served from pools made anew."""
    srv, inq, outq, seen, prompts, a, b = _two_resident(
        model, tmp_path, monkeypatch, leaving=True)
    fetch = srv._fetch_tokens

    def failing(nxt):
        raise RuntimeError("fetch failed")
    monkeypatch.setattr(srv, "_fetch_tokens", failing)
    assert _steps(srv, "ahead", 1) == [0]
    monkeypatch.setattr(srv, "_fetch_tokens", fetch)
    assert srv._in_flight_step is None and srv._leaving == []
    assert a.ended == b.ended == "gone"
    inq.enqueue_prompt("after", prompts["b"], max_new_tokens=3)
    got = _finish(srv, "ahead", outq, ["a", "b", "after"])
    for uri in "ab":
        assert "fetch failed" in got[uri]["error"]
    model.holds(prompts["b"], got["after"]["value"], 3)
    assert seen == {"a": 1, "b": 1, "after": 1}
    assert srv.health_snapshot()["kv_pool_rebuilds"] == 1
    _all_back(srv)


class _Forwarding:
    """A queue that remembers what a handoff re-enqueued."""

    def __init__(self, queue):
        self.queue, self.records = queue, {}

    def enqueue(self, uri, rec):
        self.records[uri] = rec
        return self.queue.enqueue(uri, rec)


def test_handoff_folds_the_step_in_flight_into_the_prefix(
        model, tmp_path, monkeypatch):
    """Every token the device was asked for is in the prefix that another
    instance adopts, and the adopted streams end as serial decoding does."""
    srv, inq, outq, seen, prompts, a, b = _two_resident(
        model, tmp_path, monkeypatch, leaving=False)
    asked = {"a": a.dispatched, "b": b.dispatched}
    assert asked["a"] == len(a.tokens) + 1
    src2 = f"dir://{tmp_path}/second"
    to_queue = _Forwarding(make_queue(src2))
    assert srv.handoff(to_queue) == 2
    assert {u: len(to_queue.records[u]["prefix"]) for u in "ab"} == asked
    assert seen == {}
    _all_back(srv)
    srv2 = GenerativeServing(ServingConfig(
        data_src=src2, slots=2, max_new_tokens=8, **model.pool), model.lm)
    got = _finish(srv2, "ahead", OutputQueue(src2), ["a", "b"])
    for uri, n in (("a", 8), ("b", 16)):
        model.holds(prompts[uri], got[uri]["value"], n)


@pytest.mark.parametrize("how", ["stop", "drain", "handoff"])
def test_a_running_loop_ends_with_nothing_in_flight(model, tmp_path,
                                                    monkeypatch, how):
    prompts = model.prompts(10, (4, 6))
    srv, inq, outq = model.server(tmp_path, max_new_tokens=24)
    seen = _terminals(srv, monkeypatch)
    srv.start()
    for uri, p in zip("ab", prompts):
        inq.enqueue_prompt(uri, p)
    deadline = time.monotonic() + 120
    while srv.health_snapshot()["tokens_total"] < 2:
        srv.check_health()
        assert time.monotonic() < deadline
        time.sleep(0.002)
    if how == "stop":
        srv.stop()
        for uri in "ab":   # a stream may have run out its budget first
            res = outq.query(uri, timeout_s=5)
            assert res.get("error") == SHUTDOWN_ERROR or res.get("done")
    elif how == "drain":
        srv.drain(timeout_s=120)
        for uri, p in zip("ab", prompts):
            model.holds(p, outq.query(uri, timeout_s=5)["value"], 24)
    else:
        src2 = f"dir://{tmp_path}/second"
        to_queue = _Forwarding(make_queue(src2))
        moved = srv.handoff(to_queue, timeout_s=120)
        srv2 = GenerativeServing(ServingConfig(
            data_src=src2, slots=2, max_new_tokens=24, **model.pool),
            model.lm)
        _finish(srv2, "hand", OutputQueue(src2), list(to_queue.records))
        assert moved == len(to_queue.records)
        for uri, p in zip("ab", prompts):
            res = (OutputQueue(src2).query(uri) if uri in to_queue.records
                   else outq.query(uri, timeout_s=5))
            model.holds(p, res["value"], 24)
    if how != "handoff":
        assert seen == {"a": 1, "b": 1}
    _all_back(srv)


# -- positions are counted as they are dispatched ---------------------------------


def test_window_pages_go_back_by_dispatched_position(tmp_path, monkeypatch):
    """A window layer's query is the position the step being dispatched
    writes, one beyond the tokens folded while a step is in flight."""
    model = _MODELS.setdefault("smallthinker", _Model("smallthinker"))
    prompt = model.prompts(11, (model.long,))[0]
    released = {}
    for mode in ("hand", "ahead"):
        srv, inq, outq = model.server(tmp_path, slots=1, max_new_tokens=30)
        queries = []
        behind = srv._window.behind

        def watching(slot, query, behind=behind, queries=queries, srv=srv):
            stream = srv._streams[slot]
            if stream is not None:   # a resident stream's decode step
                queries.append((query, len(stream.prompt) - 1
                                + stream.dispatched, len(stream.tokens)))
            return behind(slot, query)
        monkeypatch.setattr(srv._window, "behind", watching)
        inq.enqueue_prompt("w", prompt)
        got = _finish(srv, mode, outq, ["w"])
        model.holds(prompt, got["w"]["value"], 30)
        assert len(queries) == 30
        assert all(q == at for q, at, _ in queries)
        lag = {at - (len(prompt) - 1 + have) for _, at, have in queries[1:]}
        assert lag == ({0} if mode == "hand" else {1})
        released[mode] = srv.health_snapshot()["window_pages_released_total"]
        _all_back(srv)
    assert released["hand"] == released["ahead"] > 0


# -- what the loop says of itself ---------------------------------------------------


def test_a_step_ahead_has_its_span_and_its_counter(model, tmp_path):
    """``serve.step_ahead`` once a step that was dispatched while the one
    before it was unfolded, from its dispatch's return to its own fetch;
    the fetch stays inside the ``serve.step`` that makes it."""
    heard = []
    hook = lambda name, start, seconds: heard.append((name, start, seconds))
    prompt = model.prompts(12, (5,))[0]
    srv, inq, outq = model.server(tmp_path, slots=1)
    inq.enqueue_prompt("s", prompt, max_new_tokens=6)
    program_utils.span_hooks.append(hook)
    try:
        _finish(srv, "ahead", outq, ["s"])
    finally:
        program_utils.span_hooks.remove(hook)
    by_name = {}
    for name, start, seconds in heard:
        by_name.setdefault(name, []).append((start, start + seconds))
    steps, ahead = by_name["serve.step"], by_name["serve.step_ahead"]
    assert len(ahead) == 5 == srv.health_snapshot()["steps_ahead_total"]
    assert len(steps) == 7   # six dispatches and the fold of the last
    fetches = by_name["profile.serving.fetch"]
    assert len(fetches) == 6
    for f0, f1 in fetches:
        assert any(s0 <= f0 and f1 <= s1 for s0, s1 in steps)
    # a step ahead ends where its own fetch starts, an iteration later
    for (_, a1), (f0, _) in zip(ahead, fetches[1:]):
        assert a1 <= f0 < a1 + 0.05
    by_hand = []
    hook = lambda name, start, seconds: by_hand.append(name)
    srv, inq, outq = model.server(tmp_path, slots=1)
    inq.enqueue_prompt("h", prompt, max_new_tokens=6)
    program_utils.span_hooks.append(hook)
    try:
        _finish(srv, "hand", outq, ["h"])
    finally:
        program_utils.span_hooks.remove(hook)
    assert "serve.step_ahead" not in by_hand
    assert by_hand.count("serve.step") == 6
