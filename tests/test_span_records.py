"""The whole record of a span (docs/observability.md "Host spans"): lane,
parent, request and args beside the triple that a hook gets; the per-thread
stack that exists only while somebody listens; the serve loop's iteration
tiled by its own spans under one ``serve.step``; a prompt's wait for its
prefill turn; a compile that names the block it happened in; and the trace
session that draws all of it from the same records."""
import json
import os
import sys
import threading
import time
import uuid

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from analytics_zoo_tpu.common import metrics as zoo_metrics  # noqa: E402
from analytics_zoo_tpu.common import utils as zutils  # noqa: E402
from analytics_zoo_tpu.utils import trace as ztrace  # noqa: E402


class Listening:
    """A three-argument hook on ``span_hooks``, as a benchmark hangs one,
    and the records the program made while it listened."""

    def __init__(self):
        self.triples = []

    def add(self, name, start, seconds):
        self.triples.append((name, start, seconds))

    def __enter__(self):
        # ids are given when a block opens, records kept when it ends
        self._after = max((r.id for r in zutils.span_records()), default=0)
        zutils.span_hooks.append(self.add)
        return self

    def __exit__(self, *exc):
        zutils.span_hooks.remove(self.add)

    @property
    def records(self):
        return [r for r in zutils.span_records() if r.id > self._after]

    def named(self, name):
        return [r for r in self.records if r.name == name]


def ancestors(rec, by_id):
    out = []
    while rec.parent is not None:
        rec = by_id[rec.parent]  # a KeyError here is an orphan
        out.append(rec)
    return out


# -- the primitive --------------------------------------------------------------

def test_a_three_argument_hook_still_gets_every_span_as_a_triple():
    with Listening() as heard:
        with zutils.time_it("block", request=7, args=(("rows", 3),)) as span:
            span.note("width", 4)
            zutils.offer_span("phase", 12.5, 0.25)
        zutils.offer_span("wait", 10.0, 2.0, request="r0", life=True)
    assert [t[0] for t in heard.triples] == ["phase", "block", "wait"]
    assert heard.triples[0][1:] == (12.5, 0.25)
    assert heard.triples[2][1:] == (10.0, 2.0)
    # the records say the same, and the rest
    (phase,), (block,), (wait,) = (heard.named(n)
                                   for n in ("phase", "block", "wait"))
    assert (block.name, block.start, block.seconds) == heard.triples[1]
    assert block.request == 7 and block.parent is None
    assert block.args == (("rows", 3), ("width", 4))
    assert block.lane == threading.current_thread().name
    assert phase.parent == block.id and phase.lane == block.lane
    assert (wait.lane, wait.parent, wait.request) == (None, None, "r0")
    assert len({phase.id, block.id, wait.id}) == 3


def test_nobody_listening_no_record_no_stack_no_clock(monkeypatch):
    assert zutils.span_hooks == []
    kept = len(zutils.span_records())
    zutils._open.__dict__.pop("spans", None)

    def no_clock():
        raise AssertionError("a span took the clock with nobody listening")

    monkeypatch.setattr(zutils.time, "perf_counter", no_clock)
    with zutils.time_it("nobody.listens", request=1, tentative=True) as span:
        assert span is zutils.NULL_SPAN
        span.note("rows", 3)   # the emitters' calls are no-ops on it
        span.drop()
        assert not hasattr(zutils._open, "spans")
    assert len(zutils.span_records()) == kept


def test_records_outlive_the_listener_and_are_bounded():
    with Listening() as heard:
        with zutils.time_it("kept"):
            pass
    assert [r.name for r in heard.records] == ["kept"]  # read after the hook
    assert zutils._records.maxlen == zutils.RECORDS_KEPT >= 2 * 220_000
    assert isinstance(zutils.span_records(), tuple)


def test_parent_and_lane_of_nested_blocks_on_two_threads():
    gate = threading.Barrier(2, timeout=10)

    def work(tag):
        with zutils.time_it(f"outer.{tag}"):
            gate.wait()  # both outer blocks are open at once
            with zutils.time_it(f"inner.{tag}"):
                with zutils.time_it(f"leaf.{tag}"):
                    pass
            gate.wait()

    with Listening() as heard:
        threads = [threading.Thread(target=work, args=(tag,),
                                    name=f"lane-{tag}") for tag in "ab"]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
            assert not t.is_alive()
    by_name = {r.name: r for r in heard.records}
    for tag in "ab":
        outer, inner, leaf = (by_name[f"{k}.{tag}"]
                              for k in ("outer", "inner", "leaf"))
        assert outer.parent is None
        assert inner.parent == outer.id and leaf.parent == inner.id
        assert {outer.lane, inner.lane, leaf.lane} == {f"lane-{tag}"}
    assert not hasattr(zutils._open, "spans") or zutils._open.spans == []


def test_a_dropped_tentative_block_leaves_no_record_and_no_orphan():
    with Listening() as heard:
        with zutils.time_it("around"):
            with zutils.time_it("maybe", tentative=True) as maybe:
                with zutils.time_it("child"):
                    with zutils.time_it("grandchild"):
                        pass
                maybe.drop()
        with zutils.time_it("kept", tentative=True):
            with zutils.time_it("child.kept"):
                pass
    by_name = {r.name: r for r in heard.records}
    assert "maybe" not in by_name
    assert [t[0] for t in heard.triples].count("maybe") == 0
    assert by_name["child"].parent == by_name["around"].id
    assert by_name["grandchild"].parent == by_name["child"].id
    assert by_name["child.kept"].parent == by_name["kept"].id
    by_id = {r.id: r for r in heard.records}
    for r in heard.records:
        ancestors(r, by_id)


# -- the serve loop -------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    from analytics_zoo_tpu.capture.lm import TransformerLM
    rs = np.random.RandomState(0)
    lm = TransformerLM(vocab_size=16, hidden=16, n_block=2, n_head=2,
                       max_len=32, seed=0)
    lm.fit(rs.randint(0, 16, (32, 12)), batch_size=8, epochs=1)
    return lm


def _served(tmp_path, lm, **config):
    from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    src = f"dir://{tmp_path}/{uuid.uuid4().hex[:8]}"
    config.setdefault("slots", 4)
    config.setdefault("max_new_tokens", 3)
    srv = GenerativeServing(ServingConfig(data_src=src, **config), lm)
    return srv, InputQueue(src), OutputQueue(src)


def test_an_iteration_is_tiled_under_one_serve_step(ctx, tmp_path, tiny_lm):
    srv, inq, outq = _served(tmp_path, tiny_lm, kv_pages=4 * 8 + 1,
                             kv_page_len=4)
    for i, prompt in enumerate(([1, 2, 3, 4], [5, 6])):
        inq.enqueue_prompt(f"r{i}", prompt, max_new_tokens=2)
    with Listening() as heard:
        # by hand: the second step's dispatch reaches both budgets
        assert srv.serve_step() == 2
        assert srv.serve_step() == 2
    steps = heard.named("serve.step")
    assert len(steps) == 2 and all(s.parent is None for s in steps)
    by_id = {r.id: r for r in heard.records}
    loop = steps[0].lane
    assert loop == threading.current_thread().name
    under = {}
    for r in heard.records:
        if r.lane == loop and r.name != "serve.step":
            top = ancestors(r, by_id)[-1]
            assert top.name == "serve.step", r
            under.setdefault(top.id, set()).add(r.name)
    assert under[steps[0].id] >= {
        "serve.expire", "serve.admit", "serve.claim", "serve.join",
        "profile.serving.host_input", "serve.prepare",
        "profile.serving.dispatch", "profile.serving.fetch", "serve.post"}
    assert under[steps[1].id] >= {
        "serve.claim", "serve.prepare", "profile.serving.dispatch",
        "serve.evict", "profile.serving.fetch", "serve.post"}
    # what a reader's identity rests on: the direct children lie side by
    # side inside the step
    for step in steps:
        # (the first step compiles its programs inside the dispatch, and
        # JAX's report of a compile is no block: it lies over the phase)
        kids = sorted((r for r in heard.records if r.parent == step.id
                       and r.name != "compile.backend"),
                      key=lambda r: r.start)
        assert step.seconds >= sum(k.seconds for k in kids) - 1e-6
        for a, b in zip(kids, kids[1:]):
            assert a.start + a.seconds <= b.start + 1e-6, (a.name, b.name)
    for name, parent in (("serve.claim", "serve.admit"),
                         ("serve.join", "serve.admit"),
                         ("profile.serving.host_input", "serve.join"),
                         ("serve.evict", "serve.step")):
        for r in heard.named(name):
            assert by_id[r.parent].name == parent
    assert {dict(j.args)["bucket"] for j in heard.named("serve.join")} == {16}
    # the publisher is another lane, and its writes nest in nothing
    writes = heard.named("serve.put_result")
    assert len(writes) == 4
    assert {w.lane for w in writes} == {f"{srv.metrics_label}-publisher"}
    assert all(w.parent is None for w in writes)
    assert outq.query("r0")["done"] and outq.query("r1")["done"]


def test_an_iteration_that_steps_nothing_leaves_no_step_and_no_orphan(
        ctx, tmp_path, tiny_lm):
    srv, _, _ = _served(tmp_path, tiny_lm)
    with Listening() as heard:
        assert srv.serve_step() == 0
    assert heard.named("serve.step") == []
    assert "serve.step" not in [t[0] for t in heard.triples]
    by_id = {r.id: r for r in heard.records}
    tops = {r.name for r in heard.records if r.parent is None}
    assert {"serve.expire", "serve.admit"} <= tops
    (claim,) = heard.named("serve.claim")
    assert [a.name for a in ancestors(claim, by_id)] == ["serve.admit"]


def test_one_requests_spans_share_its_request(ctx, tmp_path, tiny_lm):
    srv, inq, outq = _served(tmp_path, tiny_lm)
    inq.enqueue_prompt("r0", [1, 2, 3], max_new_tokens=2)
    # a client that stamps no trace_id: the uri stands in
    srv.queue.enqueue("bare", {"prompt": [4, 5], "max_new_tokens": 2})
    with Listening() as heard:
        for _ in range(3):
            srv.serve_step()
    assert outq.query("r0")["done"] and outq.query("bare")["done"]
    stamped = {}
    for r in heard.records:
        if r.request is not None:
            stamped.setdefault(r.request, []).append(r.name)
    assert set(stamped) >= {"bare"} and len(stamped) == 2
    (trace_id,) = set(stamped) - {"bare"}
    assert isinstance(trace_id, int)
    for request in (trace_id, "bare"):
        names = stamped[request]
        for name in ("serve.queue_wait", "serve.join", "serve.first_token"):
            assert names.count(name) == 1, (request, names)
        assert names.count("serve.publish_lag") == \
            names.count("serve.put_result") == 2
    for name in ("serve.queue_wait", "serve.first_token",
                 "serve.publish_lag"):
        assert {(r.lane, r.parent) for r in heard.named(name)} == \
            {(None, None)}


def test_run_names_the_loops_lane_and_gives_the_thread_its_name_back(
        ctx, tmp_path, tiny_lm):
    srv, inq, outq = _served(tmp_path, tiny_lm)
    inq.enqueue_prompt("r0", [1, 2, 3], max_new_tokens=3)
    mine = threading.current_thread().name

    def drain_when_done():
        for _ in range(4000):
            if (outq.query("r0") or {}).get("done"):
                break
            time.sleep(0.005)
        srv._draining.set()  # run() returns once nothing is in flight

    waiter = threading.Thread(target=drain_when_done)
    with Listening() as heard:
        waiter.start()
        srv.run(poll_interval_s=0.001)
        waiter.join(timeout=30)
    assert not waiter.is_alive()
    assert threading.current_thread().name == mine
    lanes = {r.lane for r in heard.named("serve.step")}
    assert lanes == {f"{srv.metrics_label}-loop"}
    assert {r.lane for r in heard.named("serve.idle")} <= lanes


# -- chunked prefill -------------------------------------------------------------

@pytest.fixture(scope="module")
def chunked_lm():
    from analytics_zoo_tpu.capture.decoder import DecoderSpec, LayeredDecoder
    from perfbench.references import sala_lm as ref
    from test_sala_decoder import MAX_LEN, tiny_cfg
    cfg = tiny_cfg()
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, MAX_LEN),
                        prefill_chunk=64)
    lm.set_params(ref.init_weights(cfg, 5))
    return lm


def test_a_chunked_prompt_leaves_its_wait_and_its_chunks(ctx, tmp_path,
                                                         chunked_lm):
    srv, inq, outq = _served(tmp_path, chunked_lm, slots=3, kv_pages=40,
                             kv_page_len=16, max_new_tokens=4)
    rs = np.random.RandomState(3)
    prompts = {f"p{i}": [int(x) for x in rs.randint(1, 97, n)]
               for i, n in enumerate((150, 70, 33))}
    for uri, prompt in prompts.items():
        inq.enqueue_prompt(uri, prompt, max_new_tokens=4)
    waited = zoo_metrics.metrics_snapshot()[
        "serving.prefill_wait_seconds"]["series"].get(
            f"server={srv.metrics_label}", {"count": 0})["count"]
    assert waited == 0
    pending = []
    with Listening() as heard:
        for _ in range(60):
            srv.serve_step()
            pending.append(srv.health_snapshot()["prefills_pending"])
            if all((outq.query(u) or {}).get("done") for u in prompts):
                break
    assert all(outq.query(u)["done"] for u in prompts)
    assert max(pending) == 3 and pending[-1] == 0
    waits = heard.named("serve.prefill_wait")
    chunks = heard.named("serve.prefill_chunk")
    assert len(waits) == 3 and len({w.request for w in waits}) == 3
    assert all((w.lane, w.parent) == (None, None) for w in waits)
    by_id = {r.id: r for r in heard.records}
    first_seen = {}
    for c in sorted(chunks, key=lambda r: r.start):
        first_seen.setdefault(c.request, c)
    # the first prompt's turn is at once; each later one waits for the
    # chunks before it
    order = sorted(waits, key=lambda w: w.seconds)
    assert order[0].seconds < order[1].seconds < order[2].seconds
    for w in waits:
        first = first_seen[w.request]
        assert dict(first.args)["index"] == 0
        assert w.start + w.seconds == pytest.approx(first.start, abs=1e-6)
    rows = {}
    for c in chunks:
        a = dict(c.args)
        assert set(a) == {"start", "rows", "width", "index", "count"}
        assert 0 <= a["rows"] <= a["width"] and a["index"] < a["count"]
        assert by_id[c.parent].name == "serve.step"
        rows[c.request] = rows.get(c.request, 0) + a["rows"]
    # a prompt's chunks feed all but its last position
    assert sorted(rows.values()) == sorted(len(p) - 1
                                           for p in prompts.values())
    snap = srv.health_snapshot()
    assert snap["prefill_wait_ms"]["window"] == 3
    assert snap["prefill_wait_ms"]["p99"] >= snap["prefill_wait_ms"]["p50"]


# -- compiles -------------------------------------------------------------------

def test_a_fresh_jit_inside_a_block_names_the_block_as_its_parent(ctx):
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.common import context

    context.wire_compilation_cache()
    x = jnp.ones((7, 3))
    fresh = jax.jit(lambda a: jnp.tanh(a) * 5.0 + 23.0)
    with Listening() as heard:
        with zutils.time_it("around.the.compile"):
            fresh(x).block_until_ready()
    (block,) = heard.named("around.the.compile")
    mine = [r for r in heard.named("compile.backend")
            if r.lane == block.lane]
    assert len(mine) == 1
    assert mine[0].parent == block.id
    assert dict(mine[0].args).get("fun_name", "").startswith("jit(")


# -- the trace session draws from the same records -------------------------------

def test_a_trace_session_draws_lanes_lives_and_the_flow_chain(
        ctx, tmp_path, tiny_lm):
    srv, inq, outq = _served(tmp_path, tiny_lm)
    path = tmp_path / "flow.json"
    with ztrace.trace(str(path)):
        inq.enqueue_prompt("r0", [1, 2, 3], max_new_tokens=2)
        for _ in range(4):
            srv.serve_step()
    assert outq.query("r0")["value"]
    events = json.loads(path.read_text())
    rows = {e["tid"]: e["args"]["name"] for e in events
            if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert rows[0] == ztrace.REQUESTS_ROW
    assert f"{srv.metrics_label}-publisher" in rows.values()
    assert threading.current_thread().name in rows.values()
    # the documented chain, in time order, one id throughout
    chain = [(e["name"], e["args"]["trace_id"]) for e in events
             if e.get("ph") == "X" and "trace_id" in e.get("args", {})]
    assert [name for name, _ in chain] == [
        "serving.enqueue", "serving.claim", "serving.join",
        "serving.first_token", "serving.result"]
    assert len({flow for _, flow in chain}) == 1
    flows = [e for e in events if e.get("cat") == ztrace.FLOW_CAT]
    assert [e["ph"] for e in flows] == ["s", "t", "t", "t", "f"]
    assert {e["id"] for e in flows} == {chain[0][1]}
    # a flow event lies inside its anchor slice, on the anchor's row
    anchors = [e for e in events
               if e.get("ph") == "X" and "trace_id" in e.get("args", {})]
    for anchor, flow in zip(anchors, flows):
        assert anchor["tid"] == flow["tid"]
        assert anchor["ts"] < flow["ts"] < anchor["ts"] + anchor["dur"]
    # a block is a slice on its lane's row that names its parent; a stretch
    # of the request's life is an asynchronous pair on the requests' row
    slices = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert slices["serve.claim"]["args"]["parent"] == \
        slices["serve.admit"]["args"]["span"]
    assert rows[slices["serve.put_result"]["tid"]].endswith("-publisher")
    assert slices["serve.join"]["args"]["request"] == chain[0][1]
    lives = [e for e in events if e.get("cat") == "request_life"]
    assert {e["name"] for e in lives} >= {"serve.queue_wait",
                                          "serve.first_token",
                                          "serve.publish_lag"}
    assert {e["tid"] for e in lives} == {0}
    assert sorted(e["ph"] for e in lives) == \
        ["b"] * (len(lives) // 2) + ["e"] * (len(lives) // 2)
    assert not [e for e in events if e.get("ph") == "X"
                and e["name"] in ("serve.queue_wait", "serve.first_token")]
