"""Continuous-batching generative serving: the request lifecycle on the
default page pool, decode parity + per-token SLOs.

The load-bearing invariant is BIT-IDENTITY: N requests decoded through the
scheduler — with mid-stream joins and evictions — must produce exactly the
token streams serial ``TransformerLM.generate()`` produces, greedy and
sampled, on the pool a server works out for itself (``kv_pages=None``:
every slot can reach ``max_len``) and on a small named one. Everything
else (per-token deadlines, drain, step chaos, streaming client, metrics)
layers on the exactly-one-terminal rule ClusterServing established. The
pool's own mechanisms (prefixes, int8, speculation, sharding, exhaustion)
are tests/test_paged_serving.py's.
"""
import time
import uuid

import numpy as np
import pytest

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.common import metrics as _metrics
from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
from analytics_zoo_tpu.serving.server import DEADLINE_ERROR, PAGE_SHED_ERROR


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


#: one fitted model per max_len, shared across the file — every test reads
#: params / generates, nothing mutates the model, and reusing it keeps the
#: serial-reference executables warm between tests
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
    """Manual stepping until the scheduler goes idle (deterministic —
    no background thread in the parity tests)."""
    idle = 0
    for _ in range(steps):
        if srv.serve_step() == 0:
            idle += 1
            if idle >= 3:
                return
        else:
            idle = 0


#: the pool a server works out from its slots, and a named one with pages
#: of another length (the two files' parity cases, one test each)
POOLS = pytest.mark.parametrize("pool", [
    pytest.param({}, id="derived"),
    pytest.param({"kv_pages": 16, "kv_page_len": 8}, id="16x8")])


class TestDecodeParity:
    @POOLS
    def test_greedy_bit_identical_with_midstream_joins(self, ctx, tmp_path,
                                                       pool):
        # 5 requests through 2 slots: requests 3..5 join slots mid-run as
        # earlier streams finish and are evicted — the continuous-batching
        # case, not just a static batch
        lm = _lm()
        rs = np.random.RandomState(3)
        prompts = [rs.randint(0, 16, (n,)).tolist() for n in (4, 1, 6, 3, 5)]
        serial = [lm.generate(np.asarray([p]), max_new_tokens=8)[0].tolist()
                  for p in prompts]
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=2, max_new_tokens=8, **pool),
            lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i, p in enumerate(prompts):
            inq.enqueue_prompt(f"r{i}", p)
        _drive(srv)
        for i, want in enumerate(serial):
            res = outq.query(f"r{i}", timeout_s=5)
            assert res is not None and res.get("done") is True
            assert res["value"] == want, f"stream r{i} diverged"
        snap = srv.health_snapshot()
        assert snap["slots_occupied"] == 0
        # every page returned to the pool after the last retirement
        assert snap["kv_pages_free"] == srv.num_pages - 1

    @POOLS
    def test_sampled_bit_identical_per_request_seed(self, ctx, tmp_path,
                                                    pool):
        lm = _lm()
        rs = np.random.RandomState(4)
        prompts = [rs.randint(0, 16, (n,)).tolist() for n in (5, 2, 1, 7)]
        seeds = [11, 22, 33, 44]
        serial = [lm.generate(np.asarray([p]), max_new_tokens=8,
                              temperature=0.9, top_k=8, seed=s)[0].tolist()
                  for p, s in zip(prompts, seeds)]
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=2, max_new_tokens=8,
                          temperature=0.9, top_k=8, **pool), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i, (p, s) in enumerate(zip(prompts, seeds)):
            inq.enqueue_prompt(f"r{i}", p, seed=s)
        _drive(srv)
        for i, want in enumerate(serial):
            res = outq.query(f"r{i}", timeout_s=5)
            assert res is not None and res["value"] == want

    def test_eos_terminates_stream_bit_identically(self, ctx, tmp_path):
        # serial generate pads finished rows with eos; the scheduler
        # retires the stream at its first eos — the stream must equal the
        # serial row truncated one past the first eos
        lm = _lm()
        eos = 1  # the tiny model's attractor token (seen in every run)
        rs = np.random.RandomState(5)
        prompts = [rs.randint(0, 16, (n,)).tolist() for n in (4, 3)]
        serial = [lm.generate(np.asarray([p]), max_new_tokens=10,
                              eos_id=eos)[0].tolist() for p in prompts]
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=2, max_new_tokens=10,
                          eos_id=eos), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i, p in enumerate(prompts):
            inq.enqueue_prompt(f"e{i}", p)
        _drive(srv)
        for i, row in enumerate(serial):
            want = row[:row.index(eos) + 1] if eos in row else row
            res = outq.query(f"e{i}", timeout_s=5)
            assert res is not None and res["value"] == want


class TestDerivedPool:
    def test_every_slot_can_reach_max_len(self, ctx, tmp_path):
        # kv_pages=None: slots * ceil(max_len / kv_page_len) + 1 pages, the
        # null page included, so no join is shed for want of pages
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(ServingConfig(data_src=src, slots=3), lm)
        assert srv.num_pages == 3 * (32 // 16) + 1
        assert srv.health_snapshot()["kv_pages_free"] == 6
        inq, outq = InputQueue(src), OutputQueue(src)
        inq.enqueue_prompt("one", [5, 2, 8], max_new_tokens=4)
        _drive(srv)
        assert len(outq.query("one", timeout_s=5)["value"]) == 4
        assert srv.health_snapshot()["kv_pages_free"] == 6
        lengths = (1, 7, 20)
        for i, t in enumerate(lengths):
            inq.enqueue_prompt(f"full{i}", [3] * t, max_new_tokens=32 - t)
        assert srv.serve_step() == 3        # all resident at once
        assert srv.health_snapshot()["kv_pages_free"] == 0
        _drive(srv)
        for i, t in enumerate(lengths):
            res = outq.query(f"full{i}", timeout_s=5)
            assert res.get("error") != PAGE_SHED_ERROR
            assert len(res["value"]) == 32 - t
        assert srv.counters["shed"] == 0
        assert srv.health_snapshot()["kv_pages_free"] == 6


class TestPerTokenSLO:
    def test_deadline_mid_stream_exactly_one_terminal(self, ctx, tmp_path):
        lm = _lm(max_len=64)
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=2, max_new_tokens=40), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        # warm the prefill-bucket and step compiles so the doomed stream's
        # clock measures decode steps, not tracing
        inq.enqueue_prompt("warmup", [1, 2, 3])
        _drive(srv)
        inq.enqueue_prompt("doomed", [3, 5, 2], deadline_ms=1500)
        # a few tokens stream out before the deadline...
        for _ in range(3):
            srv.serve_step()
        partial = outq.query("doomed")
        assert partial is not None and partial.get("done") is False
        assert len(partial["stream"]) >= 1
        # ...then the per-step deadline check evicts the stream mid-flight
        time.sleep(1.6)
        _drive(srv, steps=10)
        res = outq.query("doomed", timeout_s=2)
        assert res is not None and res["error"] == DEADLINE_ERROR
        assert srv.counters["expired"] == 1
        # exactly one terminal: further steps must not resurrect it
        _drive(srv, steps=5)
        assert outq.query("doomed")["error"] == DEADLINE_ERROR
        assert srv.health_snapshot()["in_flight"] == 0

    def test_expired_at_claim_never_occupies_a_slot(self, ctx, tmp_path):
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=2, max_new_tokens=4), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        inq.enqueue_prompt("stale", [2, 4], deadline_ms=1)
        time.sleep(0.05)
        srv.serve_step()
        res = outq.query("stale", timeout_s=2)
        assert res is not None and res["error"] == DEADLINE_ERROR
        assert srv.health_snapshot()["slots_occupied"] == 0

    def test_over_budget_request_errors_immediately(self, ctx, tmp_path):
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=1, max_new_tokens=4), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        inq.enqueue_prompt("huge", [1] * 30, max_new_tokens=30)
        srv.serve_step()
        res = outq.query("huge", timeout_s=2)
        assert res is not None and "out of range" in res["error"]
        assert srv.counters["errors"] == 1

    def test_drain_finishes_in_flight_streams(self, ctx, tmp_path):
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=2, max_new_tokens=6), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i in range(3):
            inq.enqueue_prompt(f"d{i}", [2, 3, 4])
        srv.start()
        try:
            assert outq.query("d0", timeout_s=30) is not None
            # d0's slot is free; a drain stops admission, so let the loop
            # claim the third request into it first (a few ms, or one
            # compile of the eviction on a cold process)
            deadline = time.monotonic() + 10
            while (srv.health_snapshot()["queue_pending"]
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            srv.drain(timeout_s=30)
            for i in range(3):
                res = outq.query(f"d{i}", timeout_s=5)
                assert res is not None and res.get("done") is True
                assert len(res["value"]) == 6
        finally:
            srv.stop() if srv._thread is not None else None
        assert srv.health_snapshot()["state"] == "drained"


class TestChaosAndStreaming:
    def test_decode_step_fault_errors_streams_keeps_serving(self, ctx,
                                                            tmp_path):
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=2, max_new_tokens=4), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        inq.enqueue_prompt("hit", [2, 3])
        faults.arm("serving.decode_step", at=1)
        srv.serve_step()  # the armed step fails: the stream gets its one
        res = outq.query("hit", timeout_s=2)  # terminal — an error result
        assert res is not None and "FaultInjected" in res["error"]
        assert srv.counters["errors"] == 1
        assert srv.health_snapshot()["slots_occupied"] == 0
        # the scheduler survives: the NEXT request decodes normally
        serial = lm.generate(np.asarray([[2, 3]]),
                             max_new_tokens=4)[0].tolist()
        inq.enqueue_prompt("after", [2, 3])
        _drive(srv)
        assert outq.query("after", timeout_s=5)["value"] == serial

    def test_client_stream_yields_each_token_once(self, ctx, tmp_path):
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=1, max_new_tokens=6), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        serial = lm.generate(np.asarray([[4, 2, 7]]),
                             max_new_tokens=6)[0].tolist()
        inq.enqueue_prompt("s0", [4, 2, 7])
        srv.start()
        try:
            got = list(outq.stream("s0", timeout_s=30))
        finally:
            srv.drain(timeout_s=30)
        assert got == serial

    def test_stream_raises_on_error_terminal(self, ctx, tmp_path):
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=1, max_new_tokens=4), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        inq.enqueue_prompt("bad", [1, 2], deadline_ms=1)
        time.sleep(0.05)
        srv.serve_step()
        with pytest.raises(RuntimeError, match="deadline exceeded"):
            list(outq.stream("bad", timeout_s=5))

    def test_metrics_ttft_tokens_slots(self, ctx, tmp_path):
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=2, max_new_tokens=5), lm)
        inq = InputQueue(src)
        for i in range(2):
            inq.enqueue_prompt(f"m{i}", [3, 1, 4])
        srv.serve_step()
        # both streams produced their first token: TTFT observed, gauge up
        snap = srv.health_snapshot()
        assert snap["slots_occupied"] == 2
        assert snap["ttft_ms"]["window"] == 2
        _drive(srv)
        snap = srv.health_snapshot()
        assert snap["tokens_total"] == 10
        assert snap["slots_occupied"] == 0
        text = _metrics.expose_text()
        for name in ("serving_ttft_seconds", "serving_tokens_total",
                     "serving_slots_occupied"):
            assert name in text

    def test_shutdown_errors_active_streams(self, ctx, tmp_path):
        lm = _lm()
        src = _src(tmp_path)
        srv = GenerativeServing(
            ServingConfig(data_src=src, slots=1, max_new_tokens=20), lm)
        inq, outq = InputQueue(src), OutputQueue(src)
        inq.enqueue_prompt("cut", [2, 5])
        srv.serve_step()  # stream is mid-flight
        srv.stop()
        res = outq.query("cut", timeout_s=2)
        assert res is not None and "shut down" in res["error"]
