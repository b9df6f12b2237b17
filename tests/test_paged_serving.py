"""The page pool's own mechanisms under the scheduler: int8 pages against
serial generate, copy-on-write shared prefixes (prefilled ONCE),
speculative draft/verify token-identity, page-pool exhaustion chaos
(``serving.page_alloc``), a sharded pool, the pool's metrics plane, and
what a failed program costs now that the pools are donated to it.

Op-level paged invariants live in tests/test_paged_kv.py; the request
lifecycle and greedy / sampled parity with serial generate, on the pool a
server works out for itself and on this file's 16 pages of 8, are
tests/test_generative_serving.py's.
"""
import uuid

import numpy as np
import pytest

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.common import metrics as _metrics
from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
from analytics_zoo_tpu.serving.server import PAGE_SHED_ERROR


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


_LM_CACHE = {}


def _lm(max_len=32, seed=0):
    lm = _LM_CACHE.get((max_len, seed))
    if lm is None:
        from analytics_zoo_tpu.capture.lm import TransformerLM
        rs = np.random.RandomState(seed)
        lm = TransformerLM(vocab_size=16, hidden=16, n_block=2, n_head=2,
                           max_len=max_len, seed=seed)
        lm.fit(rs.randint(0, 16, (32, 12)), batch_size=8, epochs=1)
        _LM_CACHE[(max_len, seed)] = lm
    return lm


def _src(tmp_path):
    return f"dir://{tmp_path}/{uuid.uuid4().hex[:8]}"


def _drive(srv, steps=200):
    idle = 0
    for _ in range(steps):
        if srv.serve_step() == 0:
            idle += 1
            if idle >= 3:
                return
        else:
            idle = 0


def _paged_cfg(src, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("kv_pages", 16)
    kw.setdefault("kv_page_len", 8)
    return ServingConfig(data_src=src, **kw)


class TestPagedParity:
    def test_int8_kv_token_parity(self, ctx, tmp_path):
        """int8 pool error (bounded at the op level) is far inside the
        tiny model's logit margins, so the token streams stay equal."""
        lm = _lm()
        rs = np.random.RandomState(5)
        prompts = [rs.randint(0, 16, (n,)).tolist() for n in (4, 6)]
        serial = [lm.generate(np.asarray([p]), max_new_tokens=8)[0].tolist()
                  for p in prompts]
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src, kv_int8=True), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i, p in enumerate(prompts):
            inq.enqueue_prompt(f"q{i}", p)
        _drive(srv)
        for i, want in enumerate(serial):
            res = outq.query(f"q{i}", timeout_s=5)
            assert res is not None and res["value"] == want


class TestSharedPrefixCoW:
    def test_prefix_prefilled_once_and_bit_identical(self, ctx, tmp_path,
                                                     monkeypatch):
        lm = _lm()
        prefix = [3, 7, 2, 9, 5]                        # 5 tokens: CoW tail
        lasts = [1, 4, 8, 12]
        prompts = [prefix + [t] for t in lasts]
        # serial references FIRST — the call counter below must only see
        # the scheduler's traffic
        serial = [lm.generate(np.asarray([p]), max_new_tokens=8)[0].tolist()
                  for p in prompts]
        calls = []
        orig = lm.prefill_kv
        monkeypatch.setattr(
            lm, "prefill_kv",
            lambda params, tokens: (calls.append(tokens.shape), orig(
                params, tokens))[1])
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src), lm)
        free0 = srv.health_snapshot()["kv_pages_free"]
        srv.register_prefix(prefix)
        assert srv.health_snapshot()["kv_pages_free"] == free0 - 1
        # prompt = prefix + one token joins with NO suffix forward at all:
        # decode reads the registered pages through a CoW tail copy, so
        # the streams are bit-identical to serial generate
        inq, outq = InputQueue(src), OutputQueue(src)
        for i, p in enumerate(prompts):
            inq.enqueue_prompt(f"c{i}", p)
        _drive(srv)
        for i, want in enumerate(serial):
            res = outq.query(f"c{i}", timeout_s=5)
            assert res is not None and res["value"] == want
        # the common prefix went through the transformer EXACTLY once
        # (register time); joins never re-prefilled it
        assert len(calls) == 1
        # registry keeps its permanent page across all retirements
        assert srv.health_snapshot()["kv_pages_free"] == free0 - 1

    def test_divergent_suffixes_only_prefill_the_suffix(self, ctx, tmp_path,
                                                        monkeypatch):
        lm = _lm()
        rs = np.random.RandomState(6)
        prefix = rs.randint(0, 16, (6,)).tolist()
        prompts = [prefix + rs.randint(0, 16, (n,)).tolist()
                   for n in (3, 5, 2)]
        serial = [lm.generate(np.asarray([p]), max_new_tokens=6)[0].tolist()
                  for p in prompts]
        calls, scalls = [], []
        orig, sorig = lm.prefill_kv, lm.prefill_kv_suffix
        monkeypatch.setattr(
            lm, "prefill_kv",
            lambda params, tokens: (calls.append(tokens.shape), orig(
                params, tokens))[1])
        monkeypatch.setattr(
            lm, "prefill_kv_suffix",
            lambda params, tokens, pref, plen: (
                scalls.append(tokens.shape), sorig(params, tokens, pref,
                                                   plen))[1])
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src, max_new_tokens=6), lm)
        srv.register_prefix(prefix)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i, p in enumerate(prompts):
            inq.enqueue_prompt(f"s{i}", p)
        _drive(srv)
        for i, want in enumerate(serial):
            res = outq.query(f"s{i}", timeout_s=5)
            assert res is not None and res["value"] == want
        assert len(calls) == 1          # the register-time prefix forward
        assert len(scalls) >= 1         # joins ran the SUFFIX path only


class TestSpeculative:
    def test_spec_token_identical_to_serial_greedy(self, ctx, tmp_path):
        lm = _lm()
        draft = _lm(max_len=64, seed=1)   # different weights: a REAL draft
        rs = np.random.RandomState(7)
        prompts = [rs.randint(0, 16, (n,)).tolist() for n in (4, 1, 6)]
        serial = [lm.generate(np.asarray([p]), max_new_tokens=8)[0].tolist()
                  for p in prompts]
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src, spec_k=3), lm,
                                draft_lm=draft)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i, p in enumerate(prompts):
            inq.enqueue_prompt(f"v{i}", p)
        _drive(srv)
        for i, want in enumerate(serial):
            res = outq.query(f"v{i}", timeout_s=5)
            assert res is not None and res.get("done") is True
            assert res["value"] == want, f"stream v{i} diverged"
        snap = srv.health_snapshot()
        assert snap["spec_accept_ratio"] is not None
        assert 0.0 <= snap["spec_accept_ratio"] <= 1.0

    def test_spec_eos_terminates_streams(self, ctx, tmp_path):
        lm = _lm()
        draft = _lm(max_len=64, seed=1)
        eos = 1
        rs = np.random.RandomState(8)
        prompts = [rs.randint(0, 16, (n,)).tolist() for n in (4, 3)]
        serial = [lm.generate(np.asarray([p]), max_new_tokens=10,
                              eos_id=eos)[0].tolist() for p in prompts]
        src = _src(tmp_path)
        srv = GenerativeServing(
            _paged_cfg(src, max_new_tokens=10, spec_k=3, eos_id=eos), lm,
            draft_lm=draft)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i, p in enumerate(prompts):
            inq.enqueue_prompt(f"e{i}", p)
        _drive(srv)
        for i, row in enumerate(serial):
            want = row[:row.index(eos) + 1] if eos in row else row
            res = outq.query(f"e{i}", timeout_s=5)
            assert res is not None and res["value"] == want

    def test_spec_refuses_sampling_and_sizes_its_own_pool(self, ctx,
                                                          tmp_path):
        lm = _lm()
        draft = _lm(max_len=64, seed=1)
        src = _src(tmp_path)
        with pytest.raises(ValueError, match="greedy"):
            GenerativeServing(_paged_cfg(src, spec_k=2, temperature=0.8),
                              lm, draft_lm=draft)
        # no kv_pages named: the derived pool covers the spec_k positions
        # a round may write past max_len (34 positions of 16 a slot)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=2, spec_k=2), lm,
            draft_lm=draft)
        assert srv.num_pages == 2 * 3 + 1


class TestPagePoolChaos:
    def test_page_alloc_fault_sheds_join_keeps_serving(self, ctx, tmp_path):
        """The armed ``serving.page_alloc`` site simulates pool exhaustion
        at join: the victim is SHED with its one terminal result and the
        resident stream keeps decoding to its serial-identical end."""
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        serial = lm.generate(np.asarray([[2, 3, 5]]),
                             max_new_tokens=8)[0].tolist()
        inq.enqueue_prompt("alive", [2, 3, 5])
        srv.serve_step()                      # resident stream joins first
        faults.arm("serving.page_alloc", at=1)
        inq.enqueue_prompt("victim", [4, 1])
        _drive(srv)
        assert faults.fire_count("serving.page_alloc") == 1
        res = outq.query("victim", timeout_s=5)
        assert res is not None and res["error"] == PAGE_SHED_ERROR
        assert srv.counters["shed"] == 1
        # the resident stream was untouched by the shed
        assert outq.query("alive", timeout_s=5)["value"] == serial
        # and the NEXT request (fault budget spent) decodes normally
        inq.enqueue_prompt("after", [2, 3, 5])
        _drive(srv)
        assert outq.query("after", timeout_s=5)["value"] == serial

    def test_real_exhaustion_sheds_then_recovers_after_retire(
            self, ctx, tmp_path):
        # 4 usable pages, 2 per stream: the third concurrent join finds
        # an empty pool and is shed; retirement refunds the pages and the
        # next request sails through
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src, slots=3, kv_pages=5), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        serial = lm.generate(np.asarray([[2, 3]]),
                             max_new_tokens=8)[0].tolist()
        for i in range(3):
            inq.enqueue_prompt(f"x{i}", [2, 3])
        _drive(srv)
        errors = [outq.query(f"x{i}", timeout_s=5) for i in range(3)]
        shed = [r for r in errors if r.get("error") == PAGE_SHED_ERROR]
        done = [r for r in errors if r.get("value") == serial]
        assert len(shed) == 1 and len(done) == 2
        assert srv.counters["shed"] == 1
        snap = srv.health_snapshot()
        assert snap["kv_pages_free"] == 4   # refunded at retirement
        inq.enqueue_prompt("x3", [2, 3])
        _drive(srv)
        assert outq.query("x3", timeout_s=5)["value"] == serial

    def test_paged_metrics_exposed(self, ctx, tmp_path):
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src), lm)
        inq = InputQueue(src)
        inq.enqueue_prompt("m0", [5, 2, 8])
        _drive(srv)
        text = _metrics.expose_text()
        for name in ("serving_kv_pages_free",
                     "serving_kv_page_evictions_total",
                     "serving_spec_accept_ratio"):
            assert name in text
        # the retirement refunded this stream's pages as evictions
        snap = srv.health_snapshot()
        assert snap["kv_pages_free"] == 15
        assert snap["spec_accept_ratio"] is None   # not a spec server


class TestShardedPool:
    """``kv_shard``: the page pool's PAGE axis spread across devices —
    decode gathers each stream's pages to the compute device, so the
    sharded scheduler is TOKEN-identical to serial generate (and hence
    to ``kv_shard=1``), while health reports per-shard capacity
    (docs/parallelism.md#sharded-kv-serving)."""

    def test_sharded_decode_token_identical(self, ctx, tmp_path):
        lm = _lm()
        rs = np.random.RandomState(11)
        prompts = [rs.randint(0, 16, (n,)).tolist() for n in (4, 1, 6, 3, 5)]
        serial = [lm.generate(np.asarray([p]), max_new_tokens=8)[0].tolist()
                  for p in prompts]
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src, kv_shard=4), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i, p in enumerate(prompts):
            inq.enqueue_prompt(f"r{i}", p)
        _drive(srv)
        for i, want in enumerate(serial):
            res = outq.query(f"r{i}", timeout_s=5)
            assert res is not None and res.get("done") is True
            assert res["value"] == want, f"sharded stream r{i} diverged"
        snap = srv.health_snapshot()
        assert snap["kv_shards"] == 4
        assert snap["slots_occupied"] == 0
        # every page back in the free list (page 0 stays reserved as the
        # null page) -> shard 0 reports 3 free, the other shards 4
        assert snap["kv_pages_free"] == 15
        assert snap["kv_pages_free_min_shard"] == 3

    def test_shard_must_divide_pool(self, ctx, tmp_path):
        lm = _lm()
        with pytest.raises(ValueError, match="kv shard"):
            GenerativeServing(
                _paged_cfg(_src(tmp_path), kv_pages=15, kv_shard=4), lm)


class TestDonatedPoolFailure:
    """Every program that returns the pools is given them, so a failure at
    or after its dispatch leaves dead handles: each resident stream gets
    its one terminal, the pools, table and allocator start over, the
    registered prefixes are prefilled again, and the next request is
    served as if nothing had happened. A failure raised BEFORE the
    dispatch (the armed ``serving.decode_step`` site) consumes nothing."""

    PREFIX = [3, 7, 2, 9, 5]                            # 5 tokens: CoW tail

    def _server(self, tmp_path, monkeypatch, engine):
        lm = _lm()
        src = _src(tmp_path)
        if engine == "spec":
            srv = GenerativeServing(_paged_cfg(src, spec_k=3), lm,
                                    draft_lm=_lm(max_len=64, seed=1))
        else:
            srv = GenerativeServing(
                _paged_cfg(src, kv_int8=engine == "int8",
                           kv_shard=4 if engine == "shard" else 1), lm)
        terminals = {}
        put = srv.queue.put_result

        def counting(uri, value):
            if "error" in value or value.get("done"):
                terminals[uri] = terminals.get(uri, 0) + 1
            return put(uri, value)
        monkeypatch.setattr(srv.queue, "put_result", counting)
        return lm, srv, InputQueue(src), OutputQueue(src), terminals

    @staticmethod
    def _serial(lm, prompt):
        return lm.generate(np.asarray([prompt]),
                           max_new_tokens=8)[0].tolist()

    def _served_again(self, lm, srv, inq, outq, prompts, free0):
        want = [self._serial(lm, p) for p in prompts]
        for i, p in enumerate(prompts):
            inq.enqueue_prompt(f"after{i}", p)
        _drive(srv)
        for i, w in enumerate(want):
            assert outq.query(f"after{i}", timeout_s=5)["value"] == w
        snap = srv.health_snapshot()
        assert snap["slots_occupied"] == 0 and snap["in_flight"] == 0
        assert snap["kv_pages_free"] == free0
        return snap

    @pytest.mark.parametrize("engine", ["paged", "spec", "shard"])
    def test_failed_fetch_after_dispatch(self, ctx, tmp_path, monkeypatch,
                                         engine):
        lm, srv, inq, outq, terminals = self._server(tmp_path, monkeypatch,
                                                     engine)
        shared = engine != "spec"   # prefixes are not wired into spec
        if shared:
            srv.register_prefix(self.PREFIX)
        free0 = srv.health_snapshot()["kv_pages_free"]
        inq.enqueue_prompt("a", self.PREFIX + [1])
        inq.enqueue_prompt("b", [2, 3, 5])
        assert srv.serve_step() == 2
        given = srv._caches[0]["k"]
        fetch = srv._fetch_tokens

        def failing(nxt):
            raise RuntimeError("fetch failed")
        monkeypatch.setattr(srv, "_fetch_tokens", failing)
        assert srv.serve_step() == 0
        monkeypatch.setattr(srv, "_fetch_tokens", fetch)
        assert given.is_deleted()         # the step was given the pools
        for uri in ("a", "b"):
            assert "fetch failed" in outq.query(uri, timeout_s=2)["error"]
        assert terminals == {"a": 1, "b": 1}
        assert srv.counters["errors"] == 2
        assert srv.health_snapshot()["kv_pages_free"] == free0
        # prefix + one token has no forward of its own: its tokens are
        # right only if the prefix was prefilled again into fresh pages
        snap = self._served_again(lm, srv, inq, outq,
                                  [self.PREFIX + [4], [2, 3, 5]], free0)
        assert len(srv._prefixes) == (1 if shared else 0)
        assert snap["kv_pool_rebuilds"] == 1
        assert terminals == {"a": 1, "b": 1, "after0": 1, "after1": 1}
        assert "serving_kv_pool_rebuilds_total" in _metrics.expose_text()

    @pytest.mark.parametrize("engine,program", [
        ("paged", "_prefill_paged_fn"), ("int8", "_prefill_paged_fn"),
        ("spec", "_prefill_spec_fn"), ("paged", "_prefill_suffix_fn"),
        ("paged", "_copy_fn")])
    def test_failed_prefill_after_dispatch(self, ctx, tmp_path, monkeypatch,
                                           engine, program):
        lm, srv, inq, outq, terminals = self._server(tmp_path, monkeypatch,
                                                     engine)
        shared = program in ("_prefill_suffix_fn", "_copy_fn")
        if shared:
            srv.register_prefix(self.PREFIX)
        free0 = srv.health_snapshot()["kv_pages_free"]
        inq.enqueue_prompt("alive", [2, 3, 5])
        assert srv.serve_step() == 1              # a resident stream
        real = getattr(srv, program)

        def failing(*a, **kw):
            real(*a, **kw)                        # the pools are consumed
            raise RuntimeError("prefill failed")
        monkeypatch.setattr(srv, program, failing)
        # prefix + 3 tokens takes the CoW copy, then the suffix prefill
        victim = self.PREFIX + [4, 1, 6] if shared else [4, 1, 6]
        inq.enqueue_prompt("victim", victim)
        inq.enqueue_prompt("behind", [2, 3, 5])   # claimed with the victim
        srv.serve_step()
        monkeypatch.setattr(srv, program, real)
        for uri in ("alive", "victim"):
            assert "prefill failed" in outq.query(uri, timeout_s=2)["error"]
        _drive(srv)
        assert outq.query("behind", timeout_s=5)["value"] == \
            self._serial(lm, [2, 3, 5])
        assert terminals == {"alive": 1, "victim": 1, "behind": 1}
        snap = self._served_again(lm, srv, inq, outq, [victim, [2, 3, 5]],
                                  free0)
        assert snap["kv_pool_rebuilds"] == 1
        assert srv.counters["errors"] == 2

    def test_fault_before_dispatch_rebuilds_nothing(self, ctx, tmp_path,
                                                    monkeypatch):
        lm, srv, inq, outq, terminals = self._server(tmp_path, monkeypatch,
                                                     "paged")
        srv.register_prefix(self.PREFIX)
        free0 = srv.health_snapshot()["kv_pages_free"]
        inq.enqueue_prompt("hit", self.PREFIX + [1])
        faults.arm("serving.decode_step", at=1)
        assert srv.serve_step() == 0
        assert "FaultInjected" in outq.query("hit", timeout_s=2)["error"]
        assert terminals == {"hit": 1}
        assert not srv._caches[0]["k"].is_deleted()
        snap = self._served_again(lm, srv, inq, outq,
                                  [self.PREFIX + [4], [2, 3, 5]], free0)
        assert snap["kv_pool_rebuilds"] == 0


class TestPagesReadCounter:
    """``serving.paged_pages_read``: what one block's attention read for
    all slots, counted by the step program and fetched behind the tokens.
    One request of 3 tokens in 2 slots of 4 pages of 8: every one of its 4
    decode steps sees length under 8 in one slot and an empty slot."""

    def _serve_one(self, tmp_path, lm, **kw):
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src, max_new_tokens=4, **kw), lm)
        InputQueue(src).enqueue_prompt("r", [2, 3, 5])
        _drive(srv)
        assert len(OutputQueue(src).query("r", timeout_s=5)["value"]) == 4
        return srv.health_snapshot()["paged_pages_read"]

    @pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
    def test_the_xla_form_reads_the_rectangle(self, ctx, tmp_path, int8):
        read = self._serve_one(tmp_path, _lm(), kv_int8=int8)
        assert read == {"mean": 2 * 4.0, "window": 4}
        assert "serving_paged_pages_read" in _metrics.expose_text()

    def test_the_kernel_reads_the_live_pages(self, ctx, tmp_path,
                                             monkeypatch):
        """The server's own step program with the kernel in it (interpret
        mode; 2 heads of 64 so that a stored row is whole lanes): one page
        of the stream and the null page of the empty slot, every step."""
        from jax.experimental.pallas import tpu as pltpu

        from analytics_zoo_tpu.capture.lm import TransformerLM
        from analytics_zoo_tpu.ops import dispatch
        import jax
        lm = TransformerLM(vocab_size=16, hidden=128, n_block=2, n_head=2,
                           max_len=32, seed=0)
        # one device under the parameters, as on one chip (the suite's
        # context spans 8 virtual devices, and no kernel goes there)
        lm._graph.estimator.mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]), ("data",))
        lm.fit(np.random.RandomState(0).randint(0, 16, (8, 12)),
               batch_size=8, epochs=1)
        monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
        monkeypatch.setattr(dispatch, "_seen", set())
        with pltpu.force_tpu_interpret_mode():
            read = self._serve_one(tmp_path, lm)
        assert read == {"mean": 2.0, "window": 4}
        assert not [k for k, _ in dispatch.fallbacks_seen()
                    if k == "paged_decode"]

    def test_speculative_rounds_do_not_observe_it(self, ctx, tmp_path):
        src = _src(tmp_path)
        srv = GenerativeServing(_paged_cfg(src, spec_k=3), _lm(),
                                draft_lm=_lm(max_len=64, seed=1))
        InputQueue(src).enqueue_prompt("r", [2, 3, 5])
        _drive(srv)
        assert OutputQueue(src).query("r", timeout_s=5)["done"]
        assert srv.health_snapshot()["paged_pages_read"] == {
            "mean": None, "window": 0}
