"""Serving tests (reference strategy: config parsing + pre/post processing
unit tests + an in-process end-to-end loop, SURVEY.md §4 'serving unit
tests')."""
import os
import time

import numpy as np
import pytest


class TestQueues:
    def test_file_queue_roundtrip(self, tmp_path):
        from analytics_zoo_tpu.serving import FileQueue
        q = FileQueue(str(tmp_path))
        q.enqueue("a", {"tensor": [1, 2]})
        q.enqueue("b", {"tensor": [3, 4]})
        assert q.pending_count() == 2
        batch = q.claim_batch(10)
        assert [u for u, _ in batch] == ["a", "b"]
        assert q.pending_count() == 0
        q.put_result("a", {"value": [0.5]})
        assert q.get_result("a")["value"] == [0.5]
        assert q.get_result("missing") is None

    def test_trim_backpressure(self, tmp_path):
        from analytics_zoo_tpu.serving import FileQueue
        q = FileQueue(str(tmp_path))
        for i in range(10):
            q.enqueue(f"u{i}", {"tensor": [i]})
        dropped = q.trim(4)
        assert dropped == 6
        assert q.pending_count() == 4
        # oldest were dropped; newest survive
        uris = [u for u, _ in q.claim_batch(10)]
        assert uris == ["u6", "u7", "u8", "u9"]

    def test_enqueue_many_parity_with_singles(self, tmp_path):
        """A batch enqueue must be observationally identical to the same
        records enqueued one by one: same claim order, same payloads."""
        from analytics_zoo_tpu.serving import FileQueue
        recs = [(f"u{i}", {"tensor": [i, i + 1]}) for i in range(6)]
        single = FileQueue(str(tmp_path / "single"))
        for uri, payload in recs:
            single.enqueue(uri, payload)
        batched = FileQueue(str(tmp_path / "batched"))
        batched.enqueue_many(recs[:4])   # one rename publishes all four
        batched.enqueue_many(recs[4:])
        assert batched.claim_batch(10) == single.claim_batch(10)

    def test_enqueue_many_depth_and_trim_accounting(self, tmp_path):
        """pending_count / trim / shed see through batch files: depth is
        records, not files, and trimming drops oldest records first."""
        from analytics_zoo_tpu.serving import FileQueue
        q = FileQueue(str(tmp_path))
        q.enqueue_many([(f"b{i}", {"tensor": [i]}) for i in range(5)])
        q.enqueue("tail", {"tensor": [99]})
        assert q.pending_count() == 6
        dropped = q.trim(3)
        assert dropped == 3
        assert q.pending_count() == 3
        assert [u for u, _ in q.claim_batch(10)] == ["b3", "b4", "tail"]

    def test_make_queue_dispatch(self, tmp_path):
        from analytics_zoo_tpu.serving import FileQueue, make_queue
        assert isinstance(make_queue(f"dir://{tmp_path}"), FileQueue)
        assert isinstance(make_queue(str(tmp_path)), FileQueue)

    def test_image_codec(self):
        from analytics_zoo_tpu.serving.queues import decode_image, encode_image
        rs = np.random.RandomState(0)
        img = rs.randint(0, 255, (16, 16, 3)).astype(np.uint8)
        out = decode_image(encode_image(img))
        assert out.shape == (16, 16, 3)  # jpg is lossy; shape must hold


class TestConfig:
    def test_from_yaml(self, tmp_path):
        from analytics_zoo_tpu.serving import ServingConfig
        cfg_file = tmp_path / "config.yaml"
        cfg_file.write_text(
            "model:\n  path: /m\n  type: zoo\n"
            "data:\n  src: dir:///q\n  image_shape: 8,8,3\n"
            "  filter: topN(3)\n"
            "params:\n  batch_size: 16\n  max_pending: 100\n")
        cfg = ServingConfig.from_yaml(str(cfg_file))
        assert cfg.model_path == "/m"
        assert cfg.image_shape == (8, 8, 3)
        assert cfg.filter_top_n == 3
        assert cfg.batch_size == 16
        assert cfg.max_pending == 100


class TestPostProcessing:
    def test_top_n(self):
        from analytics_zoo_tpu.serving.server import top_n
        probs = np.array([0.1, 0.6, 0.3])
        out = top_n(probs, 2)
        assert out[0] == {"class": 1, "prob": pytest.approx(0.6)}
        assert out[1]["class"] == 2


class TestCompileWarmth:
    def test_prewarm_compiles_once_per_bucket(self, ctx):
        from analytics_zoo_tpu.inference import InferenceModel
        im = InferenceModel().load_jax(
            lambda p, x: x @ p["w"], {"w": np.eye(4, 3, dtype=np.float32)})
        im.prewarm(np.zeros((3, 4), np.float32))  # batch 3 → bucket 4
        assert im.compile_counts == {4: 1}
        assert im.compile_seconds[4] > 0
        out = im.predict(np.ones((3, 4), np.float32))
        assert out.shape == (3, 3)
        # first request hit the prewarmed executable: NO new compile
        assert im.compile_counts == {4: 1}
        im.predict(np.ones((5, 4), np.float32))  # bucket 8: cold, compiles
        assert im.compile_counts == {4: 1, 8: 1}
        im.predict(np.ones((7, 4), np.float32))  # bucket 8 again: warm
        assert im.compile_counts == {4: 1, 8: 1}

    def test_prewarm_multiple_buckets(self, ctx):
        from analytics_zoo_tpu.inference import InferenceModel
        im = InferenceModel().load_jax(lambda p, x: x * 2.0, {})
        im.prewarm(np.zeros((1, 2), np.float32), buckets=(1, 4, 30))
        assert im.compile_counts == {1: 1, 4: 1, 32: 1}

    def test_cluster_serving_startup_prewarm(self, ctx, tmp_path):
        """The server compiles its configured batch bucket at construction;
        the first claimed full batch runs with zero new compiles."""
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import (
            ClusterServing, InputQueue, OutputQueue, ServingConfig)
        im = InferenceModel().load_jax(
            lambda p, x: x.reshape(x.shape[0], -1).mean(1, keepdims=True), {})
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4, 4, 3),
                            batch_size=4, batch_wait_ms=5)
        serving = ClusterServing(cfg, model=im)
        assert serving.prewarmed
        assert im.compile_counts == {4: 1}
        inq = InputQueue(src)
        rs = np.random.RandomState(0)
        for i in range(4):
            inq.enqueue_image(
                f"w{i}", rs.randint(0, 255, (4, 4, 3)).astype(np.uint8))
        served = 0
        for _ in range(10):
            served += serving.serve_once()
            if served >= 4:
                break
        assert served >= 4
        assert OutputQueue(src).query("w3", timeout_s=5.0) is not None
        assert im.compile_counts == {4: 1}  # first traffic: still warm

    @pytest.mark.parametrize("placed_from_outside", [True, False])
    def test_compile_cache_dir_wiring(self, ctx, tmp_path, monkeypatch,
                                      placed_from_outside):
        """Both rules: with JAX_COMPILATION_CACHE_DIR set the program sets
        no cache directory in code; without it the cache goes to the one
        fixed in-tree path (never a temp name, pid or time)."""
        import jax
        from analytics_zoo_tpu.common import context as ctx_mod
        from analytics_zoo_tpu.inference import InferenceModel
        outside = str(tmp_path / "placed-from-outside")
        updates = []
        real_update = jax.config.update

        def spy(name, value):
            updates.append(name)
            real_update(name, value)

        monkeypatch.setattr(ctx_mod, "_cache_wired", False)
        monkeypatch.setattr(jax.config, "update", spy)
        if placed_from_outside:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            InferenceModel()  # construction wires the persistent cache
            in_use = ctx_mod.wire_compilation_cache()
            if placed_from_outside:
                assert in_use == outside
                assert "jax_compilation_cache_dir" not in updates
                assert jax.config.jax_compilation_cache_dir == before
            else:
                repo = os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))
                assert in_use == os.path.join(repo, ".jax_cache")
                assert in_use == ctx_mod.DEFAULT_COMPILE_CACHE_DIR
                assert jax.config.jax_compilation_cache_dir == in_use
                assert updates.count("jax_compilation_cache_dir") == 1
        finally:
            real_update("jax_compilation_cache_dir", before)


def _mean_model():
    from analytics_zoo_tpu.inference import InferenceModel
    return InferenceModel().load_jax(
        lambda p, x: x.reshape(x.shape[0], -1).mean(1, keepdims=True), {})


def _sum_model():
    from analytics_zoo_tpu.inference import InferenceModel
    return InferenceModel().load_jax(
        lambda p, x: x.reshape(x.shape[0], -1).sum(1, keepdims=True), {})


class TestDeadlines:
    def _serving(self, tmp_path, **cfg_kw):
        from analytics_zoo_tpu.serving import ClusterServing, ServingConfig
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4,), batch_size=4,
                            batch_wait_ms=5, **cfg_kw)
        return ClusterServing(cfg, model=_sum_model()), src

    def test_expired_at_claim_gets_deadline_error_not_device_time(
            self, ctx, tmp_path):
        from analytics_zoo_tpu.serving import InputQueue, OutputQueue
        serving, src = self._serving(tmp_path)
        inq = InputQueue(src)
        for i in range(3):
            inq.enqueue_tensor(f"d{i}", np.full(4, 1.0), deadline_ms=1)
        inq.enqueue_tensor("live", np.full(4, 1.0))  # no deadline
        time.sleep(0.05)  # the 1ms budgets are long gone
        served = serving.serve_once()
        assert served == 4  # all four answered
        outq = OutputQueue(src)
        for i in range(3):
            res = outq.query(f"d{i}")
            assert res is not None and res["error"] == "deadline exceeded"
        assert "value" in outq.query("live")
        assert serving.counters["expired"] == 3
        assert serving.records_served == 1  # dead requests never dispatched

    def test_server_side_default_deadline(self, ctx, tmp_path):
        from analytics_zoo_tpu.serving import InputQueue, OutputQueue
        serving, src = self._serving(tmp_path, default_deadline_ms=1)
        inq = InputQueue(src)
        inq.enqueue_tensor("r0", np.full(4, 1.0))  # client stamped no budget
        time.sleep(0.05)
        serving.serve_once()
        res = OutputQueue(src).query("r0")
        assert res is not None and res["error"] == "deadline exceeded"

    def test_expiry_before_dispatch_filters_rows(self, ctx, tmp_path):
        """The last deadline check masks expired rows out of an already-
        stacked batch without disturbing the live ones."""
        serving, src = self._serving(tmp_path)
        uris = ["a", "b", "c"]
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        expiries = [None, time.time() - 1.0, time.time() + 60.0]
        kept_uris, kept_x = serving._expire_before_dispatch(uris, x, expiries)
        assert kept_uris == ["a", "c"]
        np.testing.assert_array_equal(kept_x, x[[0, 2]])
        assert serving.counters["expired"] == 1
        from analytics_zoo_tpu.serving import OutputQueue
        assert OutputQueue(src).query("b")["error"] == "deadline exceeded"


class TestLoadShed:
    def test_shed_posts_error_for_every_dropped_uri(self, ctx, tmp_path):
        """Overload answers the oldest requests with explicit shed errors
        (the silent trim is gone); the newest still serve."""
        from analytics_zoo_tpu.serving import (
            ClusterServing, InputQueue, OutputQueue, ServingConfig)
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4,), batch_size=2,
                            batch_wait_ms=5, max_pending=4)
        serving = ClusterServing(cfg, model=_sum_model())
        inq = InputQueue(src)
        for i in range(10):
            inq.enqueue_tensor(f"u{i}", np.full(4, float(i)))
        served = 0
        for _ in range(20):
            served += serving.serve_once()
            if served >= 4:
                break
        outq = OutputQueue(src)
        results = {u: outq.query(u, timeout_s=5.0) for u in
                   (f"u{i}" for i in range(10))}
        assert all(r is not None for r in results.values())  # none hang
        shed = [u for u, r in results.items() if "error" in r
                and "overloaded" in r["error"]]
        ok = [u for u, r in results.items() if "value" in r]
        assert sorted(shed) == [f"u{i}" for i in range(6)]  # oldest shed
        assert sorted(ok) == [f"u{i}" for i in range(6, 10)]
        assert serving.counters["shed"] == 6

    def test_estimated_wait_shed_knob(self, ctx, tmp_path):
        """With shed_wait_ms set, the allowed depth follows the measured
        service rate: a slow model sheds down to what it can answer in
        time, not to the static max_pending."""
        from analytics_zoo_tpu.serving import (
            ClusterServing, InputQueue, OutputQueue, ServingConfig)
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4,), batch_size=2,
                            batch_wait_ms=5, max_pending=1000,
                            shed_wait_ms=100)
        serving = ClusterServing(cfg, model=_sum_model())
        serving._ewma_record_s = 0.05  # measured: 50ms/record → depth 2
        inq = InputQueue(src)
        for i in range(8):
            inq.enqueue_tensor(f"u{i}", np.full(4, float(i)))
        serving.serve_once()
        outq = OutputQueue(src)
        results = {u: outq.query(u, timeout_s=5.0) for u in
                   (f"u{i}" for i in range(8))}
        shed = [u for u, r in results.items()
                if r and "error" in r and "overloaded" in r["error"]]
        assert sorted(shed) == [f"u{i}" for i in range(6)]
        assert serving.counters["shed"] == 6


class TestDrain:
    def test_drain_finishes_inflight_and_leaves_no_threads(self, ctx,
                                                           tmp_path):
        import threading

        from analytics_zoo_tpu.serving import (
            ClusterServing, InputQueue, OutputQueue, ServingConfig)
        # snapshot BEFORE this server exists: stray decode-pool threads
        # from earlier serve_once-only tests die on GC, asynchronously —
        # only THIS server's threads are this test's drain contract
        pre = set(threading.enumerate())
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4,), batch_size=4,
                            batch_wait_ms=5,
                            health_path=str(tmp_path / "health.json"),
                            health_interval_s=0.0)
        serving = ClusterServing(cfg, model=_sum_model()).start()
        inq, outq = InputQueue(src), OutputQueue(src)
        for i in range(8):
            inq.enqueue_tensor(f"r{i}", np.full(4, float(i)))
        for i in range(8):
            assert outq.query(f"r{i}", timeout_s=20.0) is not None
        serving.drain(timeout_s=20.0)
        # drained = every claimed request answered with a VALUE (a drain
        # never errors in-flight work) and the loop machinery is gone
        results = outq.dequeue()
        assert len(results) == 8
        assert all("value" in r for r in results.values())
        assert serving.health_snapshot()["state"] == "drained"
        assert serving._in_flight == 0
        leaked = [t.name for t in threading.enumerate()
                  if t not in pre and t.name.startswith("zoo-serving")]
        assert not leaked
        # terminal health state landed on disk for the supervisor
        import json
        health = json.loads((tmp_path / "health.json").read_text())
        assert health["state"] == "drained"
        assert health["records_served"] == 8
        assert health["counters"]["shed"] == 0

    def test_drain_is_restartable(self, ctx, tmp_path):
        from analytics_zoo_tpu.serving import (
            ClusterServing, InputQueue, OutputQueue, ServingConfig)
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4,), batch_size=2,
                            batch_wait_ms=5)
        serving = ClusterServing(cfg, model=_sum_model()).start()
        serving.drain(timeout_s=20.0)
        serving.start()  # a drained server can serve again
        try:
            inq = InputQueue(src)
            inq.enqueue_tensor("after", np.full(4, 1.0))
            assert OutputQueue(src).query("after", timeout_s=20.0) is not None
        finally:
            serving.stop()


class TestHotReload:
    def _serving(self, tmp_path, **kw):
        from analytics_zoo_tpu.serving import ClusterServing, ServingConfig
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4,), batch_size=2,
                            batch_wait_ms=5, **kw)
        return ClusterServing(cfg, model=_sum_model()), src

    def test_reload_swaps_model_with_zero_lost_requests(self, ctx, tmp_path):
        from analytics_zoo_tpu.serving import InputQueue, OutputQueue
        serving, src = self._serving(tmp_path)
        serving.start()
        try:
            inq, outq = InputQueue(src), OutputQueue(src)
            inq.enqueue_tensor("pre", np.full(4, 1.0))
            pre = outq.query("pre", timeout_s=20.0)
            assert pre["value"] == [pytest.approx(4.0)]  # sum model
            assert serving.model_version == "inline-0"  # stamped at load
            serving.reload_model(model=_mean_model())
            inq.enqueue_tensor("post", np.full(4, 1.0))
            post = outq.query("post", timeout_s=20.0)
            assert post["value"] == [pytest.approx(1.0)]  # mean model
            assert serving.counters["reloads"] == 1
            # version advanced with the swap and health reports it
            assert serving.model_version == "inline-1"
            assert serving.health_snapshot()["model_version"] == "inline-1"
            serving.check_health()
        finally:
            serving.stop()
        assert len(outq.dequeue()) == 2  # nothing dropped across the swap

    def test_reload_canary_failure_rolls_back(self, ctx, tmp_path):
        """A candidate whose canary predict fails must never reach the
        serve path: the old model keeps serving."""
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import (InputQueue, ModelReloadError,
                                               OutputQueue)
        serving, src = self._serving(tmp_path)
        old = serving.model

        def bad_forward(p, x):
            raise ValueError("incompatible input shape")

        bad = InferenceModel().load_jax(bad_forward, {})
        with pytest.raises(ModelReloadError, match="previous model"):
            serving.reload_model(model=bad)
        assert serving.model is old
        assert serving.counters["reload_failures"] == 1
        # a failed reload must NOT advance the advertised version
        assert serving.model_version == "inline-0"
        assert serving.health_snapshot()["model_version"] == "inline-0"
        # ...and the old model still answers traffic
        InputQueue(src).enqueue_tensor("r0", np.full(4, 1.0))
        serving.serve_once()
        assert OutputQueue(src).query("r0")["value"] == [pytest.approx(4.0)]

    def test_reload_wrong_batch_dim_rolls_back(self, ctx, tmp_path):
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import ModelReloadError
        serving, _ = self._serving(tmp_path)
        old = serving.model
        # collapses the batch dim: the canary's leading-dim gate must trip
        squash = InferenceModel().load_jax(
            lambda p, x: x.reshape(-1).sum(keepdims=True)[None], {})
        with pytest.raises(ModelReloadError):
            serving.reload_model(model=squash)
        assert serving.model is old


class TestDeepHealth:
    def test_snapshot_fields_and_periodic_file(self, ctx, tmp_path):
        import json

        from analytics_zoo_tpu.serving import (
            ClusterServing, InputQueue, ServingConfig)
        src = f"dir://{tmp_path / 'spool'}"
        health = tmp_path / "health.json"
        cfg = ServingConfig(data_src=src, image_shape=(4,), batch_size=2,
                            batch_wait_ms=5, health_path=str(health),
                            health_interval_s=0.0)
        serving = ClusterServing(cfg, model=_sum_model())
        inq = InputQueue(src)
        for i in range(4):
            inq.enqueue_tensor(f"r{i}", np.full(4, float(i)))
        served = 0
        for _ in range(10):
            served += serving.serve_once()
            if served >= 4:
                break
        snap = serving.health_snapshot()
        assert snap["state"] == "idle"
        assert snap["queue_pending"] == 0
        assert snap["in_flight"] == 0
        assert snap["records_served"] == 4
        assert snap["last_claim_age_s"] is not None
        assert snap["latency_ms"]["window"] == 4
        assert snap["latency_ms"]["p50"] <= snap["latency_ms"]["p99"]
        assert snap["counters"]["shed"] == 0
        assert snap["counters"]["expired"] == 0
        assert snap["model_version"] == "inline-0"
        # the same snapshot streams to the health file on the serve path
        on_disk = json.loads(health.read_text())
        assert on_disk["records_served"] >= 2
        assert on_disk["model_version"] == "inline-0"
        serving.stop()
        assert json.loads(health.read_text())["state"] == "stopped"


class TestShutdownErrorPaths:
    def test_force_sentinel_errors_displaced_inflight_item(self, ctx,
                                                           tmp_path):
        """Satellite: a full pipeline queue at shutdown displaces a REAL
        in-flight item to land the sentinel — its requests must get
        explicit shutdown error results, never vanish."""
        import queue as pyqueue

        from analytics_zoo_tpu.serving import (
            ClusterServing, OutputQueue, ServingConfig)
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4,), batch_size=2,
                            batch_wait_ms=5)
        serving = ClusterServing(cfg, model=_sum_model())
        serving._in_flight = 2
        q = pyqueue.Queue(maxsize=1)
        q.put((["lost-a", "lost-b"], object()))  # stuck in-flight batch
        serving._force_sentinel(q)
        outq = OutputQueue(src)
        for uri in ("lost-a", "lost-b"):
            res = outq.query(uri)
            assert res is not None
            assert res["error"].startswith("serving shut down")
        assert q.get_nowait() is None  # the sentinel landed
        assert serving._in_flight == 0
        assert serving.counters["errors"] == 2

    def test_malformed_request_file_under_slo_flow(self, ctx, tmp_path):
        """Satellite: junk in the spool (partial write, foreign producer)
        is dropped without wedging the loop, and the well-formed requests
        around it still get exactly one terminal result each."""
        from analytics_zoo_tpu.serving import (
            ClusterServing, FileQueue, InputQueue, OutputQueue,
            ServingConfig)
        src = f"dir://{tmp_path}"
        q = FileQueue(str(tmp_path))
        (tmp_path / "requests" / "00000000000000000000-junk.json"
         ).write_text("{not json")
        inq = InputQueue(src)
        inq.enqueue_tensor("good0", np.full(4, 1.0))
        inq.enqueue_tensor("good1", np.full(4, 2.0), deadline_ms=60_000)
        cfg = ServingConfig(data_src=src, image_shape=(4,), batch_size=4,
                            batch_wait_ms=5, max_pending=2)
        serving = ClusterServing(cfg, model=_sum_model())
        # max_pending=2 with 3 spool files: the shed pass hits the
        # malformed file FIRST (it sorts oldest) and must drop it without
        # posting a bogus result or crashing
        served = 0
        for _ in range(10):
            served += serving.serve_once()
            if served >= 2:
                break
        outq = OutputQueue(src)
        assert outq.query("good0", timeout_s=5.0)["value"] == \
            [pytest.approx(4.0)]
        assert outq.query("good1", timeout_s=5.0)["value"] == \
            [pytest.approx(8.0)]
        assert q.pending_count() == 0  # junk removed from the spool
        assert len(outq.dequeue()) == 2  # and no phantom result for it

    def test_query_backs_off_exponentially(self, tmp_path, monkeypatch):
        """Satellite: the result poll must not hammer the store at a fixed
        10ms — sleeps grow geometrically (monotonic-deadline bounded)."""
        import time as time_mod

        from analytics_zoo_tpu.serving.client import OutputQueue
        sleeps = []
        monkeypatch.setattr(time_mod, "sleep",
                            lambda s: sleeps.append(s))
        outq = OutputQueue(f"dir://{tmp_path}")
        assert outq.query("missing", timeout_s=0.05) is None
        assert sleeps, "poll loop never slept"
        assert sleeps[0] <= 0.005
        doubling = [b for a, b in zip(sleeps, sleeps[1:]) if b >= a]
        assert len(doubling) >= min(3, len(sleeps) - 1)


class TestEndToEnd:
    def test_serve_loop_tensor_records(self, ctx, tmp_path):
        import jax.numpy as jnp
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import (
            ClusterServing, InputQueue, OutputQueue, ServingConfig)
        w = np.eye(4, 3).astype(np.float32)
        im = InferenceModel().load_jax(
            lambda p, x: jax.nn.softmax(x @ p["w"], axis=-1),
            {"w": jnp.asarray(w)})
        import jax
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4,), filter_top_n=2,
                            batch_size=4, batch_wait_ms=5)
        serving = ClusterServing(cfg, model=im)

        inq = InputQueue(src)
        for i in range(6):
            inq.enqueue_tensor(f"rec{i}", np.eye(4)[i % 4] * (i + 1))
        served = 0
        for _ in range(10):
            served += serving.serve_once()
            if served >= 6:
                break
        assert served >= 6
        outq = OutputQueue(src)
        res = outq.query("rec0", timeout_s=1.0)
        assert res is not None and len(res["topN"]) == 2
        assert res["topN"][0]["class"] == 0
        all_res = outq.dequeue()
        assert len(all_res) == 6

    def test_serve_loop_images_threaded(self, ctx, tmp_path):
        import cv2
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import (
            ClusterServing, InputQueue, OutputQueue, ServingConfig)
        rs = np.random.RandomState(0)
        im = InferenceModel().load_jax(
            lambda p, x: x.reshape(x.shape[0], -1).mean(1, keepdims=True), {})
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(8, 8, 3),
                            batch_size=2, batch_wait_ms=5)
        serving = ClusterServing(cfg, model=im).start()
        try:
            inq = InputQueue(src)
            for i in range(4):
                inq.enqueue_image(
                    f"img{i}", rs.randint(0, 255, (10, 12, 3)).astype(np.uint8))
            outq = OutputQueue(src)
            res = outq.query("img3", timeout_s=10.0)
            assert res is not None and "value" in res
        finally:
            serving.stop()

    def test_pipelined_run_many_batches(self, ctx, tmp_path):
        # the run() pipeline (decode thread / dispatch / writeback thread)
        # must serve every record across many micro-batches and account
        # device time
        import jax
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import (
            ClusterServing, InputQueue, OutputQueue, ServingConfig)
        im = InferenceModel().load_jax(
            lambda p, x: x.reshape(x.shape[0], -1).sum(1, keepdims=True), {})
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4, 4, 3),
                            batch_size=4, batch_wait_ms=5, decode_threads=2)
        serving = ClusterServing(cfg, model=im).start()
        try:
            inq, outq = InputQueue(src), OutputQueue(src)
            rs = np.random.RandomState(1)
            for i in range(17):  # several batches + a ragged tail
                inq.enqueue_image(
                    f"p{i}", rs.randint(0, 255, (4, 4, 3)).astype(np.uint8))
            for i in range(17):
                assert outq.query(f"p{i}", timeout_s=20.0) is not None
        finally:
            serving.stop()
        assert serving.records_served >= 17
        assert serving.device_seconds > 0

    def test_bad_record_gets_error_result(self, ctx, tmp_path):
        import jax.numpy as jnp
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import (
            ClusterServing, FileQueue, OutputQueue, ServingConfig)
        im = InferenceModel().load_jax(lambda p, x: x, {})
        src = f"dir://{tmp_path}"
        q = FileQueue(str(tmp_path))
        q.enqueue("bad", {"image": "not-base64-image!!"})
        cfg = ServingConfig(data_src=src, image_shape=(4, 4, 3),
                            batch_size=1, batch_wait_ms=1)
        serving = ClusterServing(cfg, model=im)
        serving.serve_once()
        res = OutputQueue(src).query("bad")
        assert res is not None and "error" in res
