"""N-server serving: two ClusterServing consumers against ONE queue must
serve every record exactly once and scale (the reference's cluster serving
is inherently multi-executor, ``ClusterServing.scala:160-259``).

File queue: two REAL processes (the FileQueue's cross-process claim is the
whole point). Redis: two server instances over one locked fake broker
(delivery atomicity is the broker's job; the fake models it faithfully).
"""
import json
import multiprocessing as mp
import sys
import threading
import time
import types

import numpy as np
import pytest


def _file_server_proc(root: str, n_records: int, stall_s: float,
                      tag: str, done_q, wait_go: bool = False):
    """Subprocess: serve from the shared file-queue spool until the done
    flag file appears; report every uri served. ``wait_go``: compile the
    model's program, say READY, and claim nothing before the GO flag file
    appears (a timed caller enqueues everything first)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.serving import ClusterServing, ServingConfig

    def fwd(p, x):
        return x.reshape(x.shape[0], -1).mean(1, keepdims=True)

    im = InferenceModel().load_jax(fwd, {})

    class StallModel:
        """Wraps predict with a host stall so a single server cannot drain
        the queue before the second one claims anything."""

        def predict(self, x):
            time.sleep(stall_s)
            return im.predict(x)

        def predict_async(self, x):
            f = im.predict_async(x)

            def fetch():
                time.sleep(stall_s)
                return f()
            return fetch

    cfg = ServingConfig(data_src=f"dir://{root}", batch_size=4,
                        batch_wait_ms=2, input_dtype="float32")
    srv = ClusterServing(cfg, model=StallModel())
    served = []
    orig_writeback = srv._writeback

    def writeback(uris, probs, elapsed):
        served.extend(uris)
        return orig_writeback(uris, probs, elapsed)

    srv._writeback = writeback
    import os
    if wait_go:  # the first predict compiles: not inside the timed window
        im.predict(np.zeros((cfg.batch_size, 4), np.float32))
    with open(os.path.join(root, f"READY_{tag}"), "w") as f:
        f.write("1")  # model built + queue open: measurement may begin
    deadline = time.time() + 120
    while (wait_go and not os.path.exists(root + "/GO")
           and time.time() < deadline):
        time.sleep(0.005)
    deadline = time.time() + 60
    while time.time() < deadline:
        n = srv.serve_once()
        if not n:
            if os.path.exists(root + "/DONE"):
                break
            time.sleep(0.01)
    done_q.put((tag, served))


def _go_when_ready(root: str, n_servers: int) -> None:
    """Write the GO flag once every ``wait_go`` server has said READY:
    under load one server could otherwise work alone while the other still
    imports jax (seconds, which would also swamp a timed window)."""
    import pathlib
    deadline = time.time() + 120
    while time.time() < deadline and not all(
            pathlib.Path(root, f"READY_s{k}").exists()
            for k in range(n_servers)):
        time.sleep(0.05)
    pathlib.Path(root, "GO").write_text("1")


class TestTwoProcessFileQueue:
    def test_exactly_once_across_two_processes(self, tmp_path):
        from analytics_zoo_tpu.serving import FileQueue
        from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue

        root = str(tmp_path / "spool")
        q = FileQueue(root)  # creates dirs
        n = 48
        inq = InputQueue(f"dir://{root}")
        for i in range(n):
            inq.enqueue_tensor(f"rec{i}", np.full((4,), float(i),
                                                  np.float32))
        ctx = mp.get_context("spawn")
        done_q = ctx.Queue()
        procs = [ctx.Process(target=_file_server_proc,
                             args=(root, n, 0.05, f"s{k}", done_q, True))
                 for k in range(2)]
        for p in procs:
            p.start()
        _go_when_ready(root, 2)
        outq = OutputQueue(f"dir://{root}")
        deadline = time.time() + 120
        while time.time() < deadline:
            if len(outq.dequeue()) >= n:
                break
            time.sleep(0.2)
        (tmp_path / "spool" / "DONE").write_text("1")
        reports = {}
        for _ in procs:
            tag, served = done_q.get(timeout=60)
            reports[tag] = served
        for p in procs:
            p.join(timeout=30)

        all_served = [u for served in reports.values() for u in served]
        expect = {f"rec{i}" for i in range(n)}
        # exactly once: no record served twice, none lost
        assert len(all_served) == len(set(all_served)), "double-served!"
        assert set(all_served) == expect, \
            f"lost: {expect - set(all_served)}"
        # and BOTH servers did real work (the stall guarantees overlap)
        assert all(len(s) > 0 for s in reports.values()), reports
        # results all present
        results = outq.dequeue()
        assert set(results) == expect

    def test_two_server_throughput_scales(self, tmp_path):
        """Aggregate 2-server throughput ≥ 1.5x single-server on a stalling
        model (the stall dominates, so perfect scaling would be 2x). What
        the machine's load moves is kept out of the ratio: each server has
        compiled before it says READY, every record is in the queue before
        GO (so every claim is a full batch: 6 stalls against 3), and a
        stall is long beside a claim and a write-back."""
        from analytics_zoo_tpu.serving import FileQueue
        from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue

        def run(n_servers: int, root: str) -> float:
            import pathlib
            q = FileQueue(root)
            n = 24
            ctx = mp.get_context("spawn")
            done_q = ctx.Queue()
            procs = [ctx.Process(target=_file_server_proc,
                                 args=(root, n, 0.5, f"s{k}", done_q, True))
                     for k in range(n_servers)]
            for p in procs:
                p.start()
            inq = InputQueue(f"dir://{root}")
            for i in range(n):
                inq.enqueue_tensor(f"rec{i}",
                                   np.full((4,), float(i), np.float32))
            _go_when_ready(root, n_servers)
            start = time.time()
            outq = OutputQueue(f"dir://{root}")
            deadline = time.time() + 120
            while time.time() < deadline:
                if len(outq.dequeue()) >= n:
                    break
                time.sleep(0.02)
            elapsed = time.time() - start
            pathlib.Path(root, "DONE").write_text("1")
            for _ in procs:
                done_q.get(timeout=60)
            for p in procs:
                p.join(timeout=30)
            return n / elapsed

        r1 = run(1, str(tmp_path / "one"))
        r2 = run(2, str(tmp_path / "two"))
        assert r2 >= 1.5 * r1, f"single {r1:.2f} rec/s, dual {r2:.2f} rec/s"


class TestDrainAndReloadMultiServer:
    def test_reload_then_drain_leaves_nothing_behind(self, tmp_path):
        """Two in-process servers on one spool: hot-reload one mid-traffic
        (zero dropped requests across the swap), then drain both — every
        uri answered with a value, no claim state or serve threads left."""
        import os

        from analytics_zoo_tpu.common import file_io
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import (ClusterServing, FileQueue,
                                               InputQueue, OutputQueue,
                                               ServingConfig)

        def sum_model():
            return InferenceModel().load_jax(
                lambda p, x: x.reshape(x.shape[0], -1).sum(1, keepdims=True),
                {})

        root = str(tmp_path / "spool")
        FileQueue(root)
        src = f"dir://{root}"
        # only THESE servers' threads are the drain contract (earlier
        # tests' decode pools die on GC, asynchronously)
        pre = set(threading.enumerate())
        servers = [ClusterServing(
            ServingConfig(data_src=src, image_shape=(4,), batch_size=4,
                          batch_wait_ms=5), model=sum_model())
            for _ in range(2)]
        for s in servers:
            s.start()
        inq, outq = InputQueue(src), OutputQueue(src)
        try:
            for i in range(16):
                inq.enqueue_tensor(f"pre{i}", np.full(4, 1.0))
            for i in range(16):
                assert outq.query(f"pre{i}", timeout_s=20.0) is not None
            # hot swap server 0 while server 1 keeps serving the old model
            servers[0].reload_model(model=InferenceModel().load_jax(
                lambda p, x: x.reshape(x.shape[0], -1).mean(
                    1, keepdims=True), {}))
            for i in range(16):
                inq.enqueue_tensor(f"post{i}", np.full(4, 1.0))
            for i in range(16):
                res = outq.query(f"post{i}", timeout_s=20.0)
                assert res is not None and "value" in res
                # whichever server answered, the value is a VALID model's
                # output (sum=4 or mean=1) — never garbage mid-swap
                assert res["value"][0] in (
                    pytest.approx(4.0), pytest.approx(1.0))
        finally:
            for s in servers:
                s.drain(timeout_s=30.0)
        results = outq.dequeue()
        assert len(results) == 32
        assert all("value" in r for r in results.values())  # drain: no errors
        assert servers[0].counters["reloads"] == 1
        assert servers[0].queue.pending_count() == 0
        assert file_io.listdir(file_io.join(root, "claimed")) == []
        leaked = [t.name for t in threading.enumerate()
                  if t not in pre and t.name.startswith("zoo-serving")]
        assert not leaked
        for s in servers:
            assert s.health_snapshot()["state"] == "drained"


class TestTwoServerRedis:
    def test_exactly_once_two_instances_one_stream(self, monkeypatch):
        """Two RedisQueue consumers (distinct consumer names, one group) on
        one stream: XREADGROUP '>' must deliver each entry exactly once
        across both, under concurrent claiming."""
        from tests.test_redis_serving import FakeRedis

        lock = threading.Lock()
        orig = FakeRedis.xreadgroup

        def locked_xreadgroup(self, *a, **k):
            with lock:  # the real broker pops atomically; model that
                return orig(self, *a, **k)

        monkeypatch.setattr(FakeRedis, "xreadgroup", locked_xreadgroup)
        fake_mod = types.ModuleType("redis")
        fake_mod.StrictRedis = FakeRedis
        monkeypatch.setitem(sys.modules, "redis", fake_mod)
        FakeRedis.instances.clear()

        from analytics_zoo_tpu.serving.queues import RedisQueue
        qa = RedisQueue("twosrv", 6379)
        qb = RedisQueue("twosrv", 6379)
        assert qa.consumer != qb.consumer
        n = 200
        for i in range(n):
            qa.enqueue(f"rec{i}", {"tensor": [i]})

        claims = {"a": [], "b": []}

        def drain(q, key):
            while True:
                batch = q.claim_batch(7)
                if not batch:
                    break
                claims[key].extend(u for u, _ in batch)

        ta = threading.Thread(target=drain, args=(qa, "a"))
        tb = threading.Thread(target=drain, args=(qb, "b"))
        ta.start(); tb.start()
        ta.join(30); tb.join(30)
        got = claims["a"] + claims["b"]
        assert len(got) == n
        assert len(set(got)) == n, "double delivery"
        assert set(got) == {f"rec{i}" for i in range(n)}


class TestRemoteSpoolClaims:
    def test_remote_claim_uses_exclusive_marker(self):
        """On a scheme:// spool, claims go through create_exclusive
        markers; a marker that exists means the claim is lost."""
        from fsspec.implementations.memory import MemoryFileSystem

        from analytics_zoo_tpu.common import file_io
        from analytics_zoo_tpu.serving import FileQueue
        import uuid as _uuid
        file_io.register_filesystem("spoolfs", MemoryFileSystem())
        try:
            root = f"spoolfs://q-{_uuid.uuid4().hex[:8]}"
            q1 = FileQueue(root)
            q2 = FileQueue(root)
            q1.enqueue("u1", {"tensor": [1]})
            q1.enqueue("u2", {"tensor": [2]})
            a = q1.claim_batch(10)
            b = q2.claim_batch(10)
            got = [u for u, _ in a] + [u for u, _ in b]
            assert sorted(got) == ["u1", "u2"]
            # claims are exclusive: nothing left to claim
            assert q1.claim_batch(10) == [] and q2.claim_batch(10) == []
        finally:
            file_io.unregister_filesystem("spoolfs")

    def test_expired_remote_claim_is_reaped(self):
        """A consumer that died between claim and cleanup must not wedge
        the record forever: once the lease expires another consumer
        reclaims it (the redis XAUTOCLAIM stance)."""
        from fsspec.implementations.memory import MemoryFileSystem

        from analytics_zoo_tpu.common import file_io
        from analytics_zoo_tpu.serving import FileQueue
        import uuid as _uuid
        file_io.register_filesystem("spoolfs2", MemoryFileSystem())
        try:
            root = f"spoolfs2://q-{_uuid.uuid4().hex[:8]}"
            q1 = FileQueue(root, claim_lease_s=0.2)
            q1.enqueue("u1", {"tensor": [1]})
            # simulate a dead consumer: claim then never clean up
            name = [n for n in file_io.listdir(
                f"{root}/requests", refresh=True)
                if not n.startswith(".")][0]
            assert q1._claim_one(name) is not None
            q2 = FileQueue(root, claim_lease_s=0.2)
            assert q2.claim_batch(10) == []  # lease still live
            time.sleep(0.3)
            got = q2.claim_batch(10)  # expired: reaped + reclaimed
            assert [u for u, _ in got] == ["u1"]
        finally:
            file_io.unregister_filesystem("spoolfs2")

    def test_reap_lock_serializes_reapers(self):
        """Reaping an expired claim is remove+recreate — not atomic — so it
        is guarded by an exclusive-create reap lock: while another consumer
        holds the lock, a racing reaper must claim NOTHING (this is the
        interleaving where two reapers could otherwise both win); a STALE
        lock (reaper died mid-reap) is cleared so a later pass recovers."""
        from fsspec.implementations.memory import MemoryFileSystem

        from analytics_zoo_tpu.common import file_io
        from analytics_zoo_tpu.serving import FileQueue
        import uuid as _uuid
        file_io.register_filesystem("spoolfs3", MemoryFileSystem())
        try:
            root = f"spoolfs3://q-{_uuid.uuid4().hex[:8]}"
            q1 = FileQueue(root, claim_lease_s=0.2)
            q1.enqueue("u1", {"tensor": [1]})
            name = [n for n in file_io.listdir(
                f"{root}/requests", refresh=True)
                if not n.startswith(".")][0]
            assert q1._claim_one(name) is not None  # dead consumer
            time.sleep(0.3)  # lease expires
            # another consumer is mid-reap: fresh reap lock held
            marker = file_io.join(f"{root}/claimed", name + ".claim")
            file_io.create_exclusive(marker + ".reap",
                                     repr(time.time()).encode())
            q2 = FileQueue(root, claim_lease_s=0.2)
            assert q2.claim_batch(10) == []  # must not double-claim
            assert file_io.exists(marker + ".reap")  # fresh lock untouched
            # now the lock itself goes stale (its holder died mid-reap);
            # clearing requires the conservative 2x-lease margin: one pass
            # clears it, the next reclaims the record
            time.sleep(0.45)
            assert q2.claim_batch(10) == []
            assert not file_io.exists(marker + ".reap")
            got = q2.claim_batch(10)
            assert [u for u, _ in got] == ["u1"]
        finally:
            file_io.unregister_filesystem("spoolfs3")

    def test_reap_revalidates_marker_under_lock(self, monkeypatch):
        """Two reapers that both read the same expired stamp must not both
        reclaim: the second one re-reads the marker AFTER winning the reap
        lock and must back off when it finds a fresh claim (simulated here
        by serving it a fresh stamp on the re-validation read)."""
        import io

        from fsspec.implementations.memory import MemoryFileSystem

        from analytics_zoo_tpu.common import file_io
        from analytics_zoo_tpu.serving import FileQueue
        import uuid as _uuid
        file_io.register_filesystem("spoolfs4", MemoryFileSystem())
        try:
            root = f"spoolfs4://q-{_uuid.uuid4().hex[:8]}"
            q1 = FileQueue(root, claim_lease_s=0.2)
            q1.enqueue("u1", {"tensor": [1]})
            name = [n for n in file_io.listdir(
                f"{root}/requests", refresh=True)
                if not n.startswith(".")][0]
            assert q1._claim_one(name) is not None  # dead consumer
            time.sleep(0.3)  # lease expires
            marker = file_io.join(f"{root}/claimed", name + ".claim")
            orig_fopen = file_io.fopen
            marker_reads = []

            def fake_fopen(path, mode="r", **kw):
                if path == marker and "r" in str(mode):
                    marker_reads.append(1)
                    if len(marker_reads) == 2:
                        # re-validation read: another reaper reclaimed it
                        # a moment ago — the stamp is fresh now
                        return io.BytesIO(repr(time.time()).encode())
                return orig_fopen(path, mode, **kw)

            monkeypatch.setattr(file_io, "fopen", fake_fopen)
            q2 = FileQueue(root, claim_lease_s=0.2)
            assert q2._claim_one(name) is None  # backed off under the lock
            assert len(marker_reads) == 2
            assert file_io.exists(marker)  # the fresh claim survived
            assert not file_io.exists(marker + ".reap")  # lock released
        finally:
            file_io.unregister_filesystem("spoolfs4")
