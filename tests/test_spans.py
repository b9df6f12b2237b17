"""Host spans at the layer boundaries (docs/observability.md "Host spans"):
the one primitive in ``common/utils.py``, the recorder of ``utils/trace.py``
that listens only while a session is open, the serve loop's and the train
loop's spans under a listening hook, and the compile listener."""
import threading
import uuid

import numpy as np
import pytest

from analytics_zoo_tpu.common import metrics as zoo_metrics
from analytics_zoo_tpu.common import profiler
from analytics_zoo_tpu.common import utils as zutils
from analytics_zoo_tpu.utils import trace as ztrace


class Listener:
    """What a benchmark hangs on ``span_hooks``: keeps every span."""

    def __init__(self):
        self.spans = []
        self._lock = threading.Lock()

    def add(self, name, start, seconds):
        with self._lock:
            self.spans.append((name, start, seconds))

    def __enter__(self):
        zutils.span_hooks.append(self.add)
        return self

    def __exit__(self, *exc):
        zutils.span_hooks.remove(self.add)

    def named(self, name):
        return [s for s in self.spans if s[0] == name]


def inside(inner, outer):
    return (outer[1] <= inner[1]
            and inner[1] + inner[2] <= outer[1] + outer[2] + 1e-9)


# -- the primitive --------------------------------------------------------------

def test_no_listener_no_clock_and_nothing_called(monkeypatch):
    assert zutils.span_hooks == []

    def no_clock():
        raise AssertionError("a span took the clock with nobody listening")

    monkeypatch.setattr(zutils.time, "perf_counter", no_clock)
    span = zutils.time_it("nobody.listens")
    assert span is zutils.NULL_SPAN
    with span:
        pass
    with zutils.time_it("nobody.listens"):
        pass


def test_a_listener_gets_name_start_and_seconds():
    with Listener() as heard:
        with zutils.time_it("block"):
            pass
        zutils.offer_span("stretch", 12.5, 0.25)
    with zutils.time_it("after"):
        pass
    assert [s[0] for s in heard.spans] == ["block", "stretch"]
    assert heard.spans[0][2] >= 0.0
    assert heard.spans[1][1:] == (12.5, 0.25)


def test_trace_listens_only_while_a_session_is_open(tmp_path):
    assert ztrace._record not in zutils.span_hooks
    with ztrace.trace(str(tmp_path / "outer.json")):
        assert zutils.span_hooks.count(ztrace._record) == 1
        with ztrace.trace(str(tmp_path / "inner.json")):
            assert zutils.span_hooks.count(ztrace._record) == 1
        # the inner session closed, the outer one still listens
        assert zutils.span_hooks.count(ztrace._record) == 1
    assert ztrace._record not in zutils.span_hooks
    assert zutils.span_hooks == []


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
    srv = GenerativeServing(ServingConfig(
        data_src=src, slots=4, max_new_tokens=3, **config), lm)
    return srv, InputQueue(src), OutputQueue(src)


def test_one_serve_step_under_a_listening_hook(ctx, tmp_path, tiny_lm):
    srv, inq, outq = _served(tmp_path, tiny_lm, kv_pages=4 * 8 + 1,
                             kv_page_len=4)
    for i, prompt in enumerate(([1, 2, 3, 4], [5, 6])):
        inq.enqueue_prompt(f"r{i}", prompt, max_new_tokens=3)
    assert not profiler.enabled()
    phases = profiler._phase_child("serving", "dispatch").count()
    with Listener() as heard:
        assert srv.serve_step() == 2
    (step,) = heard.named("serve.step")
    for name in ("serve.expire", "serve.admit", "profile.serving.dispatch",
                 "profile.serving.fetch", "serve.post"):
        (span,) = heard.named(name)
        assert inside(span, step), name
    (admit,), (post,) = heard.named("serve.admit"), heard.named("serve.post")
    (claim,) = heard.named("serve.claim")
    assert inside(claim, admit)
    joins = heard.named("serve.join")
    assert len(joins) == 2 and all(inside(j, admit) for j in joins)
    # one wait and one first token a request; a wait ends at the claim
    waits, firsts = (heard.named("serve.queue_wait"),
                     heard.named("serve.first_token"))
    assert len(waits) == 2 and len(firsts) == 2
    for wait in waits:
        assert wait[2] >= 0.0
        assert claim[1] <= wait[1] + wait[2] <= admit[1] + admit[2]
    # the first token of each stream was posted: the publisher's two
    # writes, behind the hand-over in the post and before serve_step, with
    # no loop running, returned; a first token ends with the write that
    # carries it, and each written record says how long it waited
    writes = heard.named("serve.put_result")
    assert len(writes) == 2 and all(w[1] >= post[1] for w in writes)
    ends = sorted(w[1] + w[2] for w in writes)
    for first in firsts:
        assert claim[1] <= first[1] <= admit[1] + admit[2]
    assert all(a <= b for a, b in zip(
        ends, sorted(f[1] + f[2] for f in firsts)))
    lags = heard.named("serve.publish_lag")
    assert len(lags) == 2
    assert all(inside((None, lag[1], 0.0), post) for lag in lags)
    assert ends == pytest.approx(sorted(g[1] + g[2] for g in lags),
                                 abs=5e-3)
    # the profiler is off: its phase spans reach the listener all the same
    # (the dispatch, the fetch, a prefill dispatch a join), its histograms
    # take nothing
    assert len(heard.named("profile.serving.host_input")) == 2
    assert profiler._phase_child("serving", "dispatch").count() == phases
    assert srv.health_snapshot()["queue_wait_ms"]["window"] == 2


def test_an_idle_iteration_leaves_no_serve_step(ctx, tmp_path, tiny_lm):
    srv, _, _ = _served(tmp_path, tiny_lm)
    with Listener() as heard:
        assert srv.serve_step() == 0
    assert heard.named("serve.step") == []
    assert len(heard.named("serve.claim")) == 1


def test_the_flow_chain_has_the_generative_stages(ctx, tmp_path, tiny_lm):
    import json
    srv, inq, outq = _served(tmp_path, tiny_lm)
    path = tmp_path / "flow.json"
    with ztrace.trace(str(path)):
        inq.enqueue_prompt("r0", [1, 2, 3], max_new_tokens=2)
        for _ in range(4):
            srv.serve_step()
    assert outq.query("r0")["value"]
    events = json.loads(path.read_text())
    stages = [e["name"] for e in events
              if e.get("args", {}).get("trace_id") is not None]
    for stage in ("serving.claim", "serving.join", "serving.first_token",
                  "serving.result"):
        assert stage in stages, stages
    assert stages.index("serving.join") < stages.index(
        "serving.first_token") < stages.index("serving.result")


# -- the train loop -------------------------------------------------------------

def test_a_listener_alone_adds_no_fence_to_the_train_loop(ctx, monkeypatch):
    import jax
    from analytics_zoo_tpu.estimator import Estimator
    from analytics_zoo_tpu.feature import FeatureSet
    from analytics_zoo_tpu.keras import Sequential, objectives, optimizers
    from analytics_zoo_tpu.keras.layers import Dense
    rs = np.random.RandomState(0)
    x = rs.randn(64, 8).astype(np.float32)
    y = rs.randn(64, 1).astype(np.float32)
    fences = []
    plain = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda tree: fences.append(1) or plain(tree))
    est = Estimator(model=Sequential([Dense(4, activation="tanh"), Dense(1)]),
                    loss_fn=objectives.get("mse"),
                    optimizer=optimizers.Adam(1e-2))
    assert not profiler.enabled()
    with Listener() as heard:
        est.train(FeatureSet.from_ndarrays(x, y, seed=1), batch_size=16,
                  epochs=1)
    assert len(heard.named("train_step")) == 4
    assert len(heard.named("train.feed_wait")) >= 4
    assert not [s for s in heard.spans if s[0].startswith("profile.")]
    assert fences == []


# -- compiles -------------------------------------------------------------------

def test_a_fresh_jit_is_counted_and_leaves_a_span(ctx, tmp_path):
    """With the persistent cache on (the suite turns it off), a program
    seen for the first time is a miss or a hit of the cache and one
    ``compile.backend`` span; the second call of it compiles nothing.
    The counters and the span hook are the process's: what a thread left
    by an earlier test file compiles meanwhile is told apart by its thread
    and taken out of the count."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    from analytics_zoo_tpu.common import context

    context.wire_compilation_cache()
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()

    def counted():
        snap = zoo_metrics.metrics_snapshot()
        return (snap["compile.cache_hits_total"]["value"]
                + snap["compile.cache_misses_total"]["value"],
                snap["compile.backend_seconds"]["summary"]["count"])

    me = threading.get_ident()
    others = [0, 0]  # cache events, backend compiles of other threads

    def others_event(event, **_):
        if threading.get_ident() != me and event in context._CACHE_COUNTERS:
            others[0] += 1

    def others_duration(event, seconds, **_):
        if threading.get_ident() != me and event == context._BACKEND_COMPILE:
            others[1] += 1

    class Mine(Listener):
        def add(self, name, start, seconds):
            if threading.get_ident() == me:
                super().add(name, start, seconds)

    fresh = jax.jit(lambda a: jnp.tanh(a) * 3.0 + 17.0)
    x = jnp.ones((5, 3))  # made here: making it compiles a program too
    jax.monitoring.register_event_listener(others_event)
    jax.monitoring.register_event_duration_secs_listener(others_duration)
    before = counted()
    try:
        with Mine() as heard:
            fresh(x).block_until_ready()
            first = list(heard.spans)
            fresh(x).block_until_ready()
            second = heard.spans[len(first):]
        after = counted()
    finally:
        jax.monitoring.unregister_event_listener(others_event)
        jax.monitoring.unregister_event_duration_listener(others_duration)
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_compilation_cache_dir", was[1])
        cc.reset_cache()
    assert after[0] - others[0] == before[0] + 1
    assert after[1] - others[1] == before[1] + 1
    names = [s[0] for s in first]
    assert names.count("compile.backend") == 1
    assert set(n for n in names if n.startswith("compile.")) == \
        {"compile.backend"}
    backend = next(s for s in first if s[0] == "compile.backend")
    assert backend[2] > 0.0
    assert not [s for s in second if s[0].startswith("compile.")]
