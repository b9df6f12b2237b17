"""Chaos layer: fault injection exercising every recovery path the
platform claims — elastic step retry, checksum-manifest fallback past a
torn newest snapshot, snapshot retention, SIGTERM preemption with a
resumable marker, transient remote-IO retries, worker-pool self-healing,
producer-thread failures, and serving decode/writeback faults.

The capstone is the soak: a training run with faults armed at EVERY
registered training site must finish and produce final params
BIT-IDENTICAL to the fault-free run — recovery that changes the math is
not recovery."""
import json
import os
import signal
import uuid

import numpy as np
import pytest

from analytics_zoo_tpu.common import faults, file_io
from analytics_zoo_tpu.common.config import global_config
from analytics_zoo_tpu.common.triggers import SeveralIteration
from analytics_zoo_tpu.estimator import (CheckpointCorruptError, Estimator,
                                         PreemptedError)
from analytics_zoo_tpu.feature import FeatureSet, Lambda
from analytics_zoo_tpu.keras import Sequential, objectives, optimizers
from analytics_zoo_tpu.keras.layers import Dense


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()
    for key in ("faults.plan", "data.task_retries", "data.worker_respawns",
                "failure.io_backoff_s", "checkpoint.keep"):
        global_config().unset(key)


def _estimator(lr=0.05):
    model = Sequential([Dense(16, name="d1"), Dense(2, name="d2")])
    return Estimator(
        model=model,
        loss_fn=objectives.get("sparse_categorical_crossentropy"),
        optimizer=optimizers.SGD(lr))


def _data(n=256, d=6, seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(n, d).astype(np.float32),
            rs.randint(0, 2, n).astype(np.float32))


def _fs(n=256, shuffle=True):
    x, y = _data(n)
    return FeatureSet.from_ndarrays(x, y, shuffle=shuffle, seed=7)


def _params_equal(pa, pb):
    import jax
    la, lb = jax.tree_util.tree_leaves(pa), jax.tree_util.tree_leaves(pb)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSnapshotCandidates:
    """Satellite: `_latest_snapshot` filtering is a real suffix check and
    tolerates foreign dirs."""

    def test_skips_writing_staging_and_non_integer_suffixes(self, ctx,
                                                            tmp_path):
        for name in ("snapshot-2", "snapshot-10", "snapshot-7.writing",
                     "snapshot-abc", "snapshot-", "notes", "snapshot-3x"):
            (tmp_path / name).mkdir()
        est = _estimator()
        est.set_checkpoint(str(tmp_path))
        cands = est._snapshot_candidates()
        assert [s for s, _ in cands] == [2, 10]
        assert est._latest_snapshot().endswith("snapshot-10")

    def test_substring_writing_name_not_hidden(self, ctx, tmp_path):
        # the old `".writing" not in d` substring test would hide this
        # perfectly valid published snapshot
        weird = tmp_path / "ck.writing.dir"
        (weird / "snapshot-4").mkdir(parents=True)
        est = _estimator()
        est.set_checkpoint(str(weird))
        assert est._latest_snapshot().endswith("snapshot-4")

    def test_empty_or_missing_dir(self, ctx, tmp_path):
        est = _estimator()
        est.set_checkpoint(str(tmp_path / "nope"))
        assert est._latest_snapshot() is None


class TestChecksumIntegrity:
    def _trained(self, tmp_path, epochs=2):
        est = _estimator()
        est.set_checkpoint(str(tmp_path), SeveralIteration(1))
        est.train(_fs(), batch_size=64, epochs=epochs)
        est._ckpt_writer.wait()
        return est

    def test_manifest_written_and_verified(self, ctx, tmp_path):
        est = self._trained(tmp_path)
        snap = est._latest_snapshot()
        manifest = os.path.join(snap, "zoo_manifest.json")
        assert os.path.exists(manifest)
        files = json.load(open(manifest))["files"]
        assert files  # every data file checksummed
        est2 = _estimator()
        est2.load_checkpoint(snap)  # verifies clean
        assert est2.global_step == est.global_step

    def test_torn_snapshot_rejected_and_fallen_past(self, ctx, tmp_path):
        est = self._trained(tmp_path)
        newest = est._latest_snapshot()
        faults.tear_snapshot(newest)
        est2 = _estimator()
        with pytest.raises(CheckpointCorruptError, match="checksum"):
            est2.load_checkpoint(newest)
        # transparent fallback: restore lands on the next-older snapshot
        est3 = _estimator()
        est3.set_checkpoint(str(tmp_path))
        restored = est3._restore_latest_valid()
        assert restored is not None and restored != newest
        assert est3.global_step == est.global_step - 1

    def test_elastic_retry_falls_back_past_torn_newest(self, ctx, tmp_path):
        """ckpt.corrupt tears the newest published snapshot, the next step
        fails — training must fall back one snapshot and still finish."""
        est = _estimator()
        est.set_checkpoint(str(tmp_path), SeveralIteration(1))
        est.train(_fs(), batch_size=64, epochs=1)  # 4 steps, snapshots 1-4
        faults.arm("ckpt.corrupt", at=1, budget=1)   # tears snapshot-5
        faults.arm("train.step", at=2, budget=1)     # fails step 6 dispatch
        est.train(_fs(), batch_size=64, epochs=3)
        assert faults.fire_count("ckpt.corrupt") == 1
        assert faults.fire_count("train.step") == 1
        assert est.epoch == 4 and est.global_step == 12

    def test_retention_keeps_newest_k(self, ctx, tmp_path):
        global_config().set("checkpoint.keep", 2)
        est = self._trained(tmp_path, epochs=2)  # 8 snapshot writes
        names = sorted(os.listdir(tmp_path))
        assert names == ["snapshot-7", "snapshot-8"]

    def test_verify_can_be_disabled(self, ctx, tmp_path):
        est = self._trained(tmp_path)
        snap = est._latest_snapshot()
        manifest = os.path.join(snap, "zoo_manifest.json")
        data = json.load(open(manifest))
        next(iter(data["files"].values()))[1] ^= 1  # poison a checksum
        json.dump(data, open(manifest, "w"))
        global_config().set("checkpoint.verify", False)
        try:
            _estimator().load_checkpoint(snap)  # tolerated when disabled
        finally:
            global_config().unset("checkpoint.verify")
        with pytest.raises(CheckpointCorruptError):
            _estimator().load_checkpoint(snap)


class TestPreemption:
    def test_preempt_site_writes_snapshot_and_marker(self, ctx, tmp_path):
        est = _estimator()
        est.set_checkpoint(str(tmp_path), SeveralIteration(100))  # no
        # triggered snapshots: the final one must come from preemption
        faults.arm("train.preempt", at=5)
        with pytest.raises(PreemptedError) as ei:
            est.train(_fs(), batch_size=64, epochs=3)
        assert ei.value.snapshot.endswith("snapshot-5")
        marker = Estimator.preemption_marker(str(tmp_path))
        assert marker == {"global_step": 5, "epoch": 2,
                          "snapshot": "snapshot-5", "resumable": True}

    def test_resume_after_preemption_bit_identical(self, ctx, tmp_path):
        est_a = _estimator()
        est_a.train(_fs(), batch_size=64, epochs=3)

        est_b = _estimator()
        est_b.set_checkpoint(str(tmp_path), SeveralIteration(100))
        faults.arm("train.preempt", at=5)
        with pytest.raises(PreemptedError):
            est_b.train(_fs(), batch_size=64, epochs=3)
        faults.reset()

        est_c = _estimator()
        est_c.set_checkpoint(str(tmp_path))
        est_c.load_checkpoint(est_c._latest_snapshot())
        assert est_c.global_step == 5
        est_c.train(_fs(), batch_size=64, epochs=3)
        # marker consumed by the resumed run
        assert Estimator.preemption_marker(str(tmp_path)) is None
        _params_equal(est_a.get_params(), est_c.get_params())

    def test_real_sigterm_is_a_preemption(self, ctx, tmp_path):
        est = _estimator()
        est.set_checkpoint(str(tmp_path), SeveralIteration(100))
        est.train(_fs(), batch_size=64, epochs=1)  # build the step
        real_step = est._train_step
        seen = {"n": 0}

        def step_then_sigterm(*args):
            seen["n"] += 1
            if seen["n"] == 2:
                os.kill(os.getpid(), signal.SIGTERM)
            return real_step(*args)

        est._train_step = step_then_sigterm
        with pytest.raises(PreemptedError, match="preempted"):
            est.train(_fs(), batch_size=64, epochs=3)
        assert Estimator.preemption_marker(str(tmp_path)) is not None
        # the handler was restored: SIGTERM is no longer swallowed
        assert signal.getsignal(signal.SIGTERM) != est._on_sigterm


class TestElasticityExhaustion:
    """Satellite: after `failure.retry_times` consecutive failing steps the
    estimator restores the newest valid checkpoint, THEN re-raises — the
    params stay a usable, known-good state."""

    def test_exhaustion_restores_then_reraises(self, ctx, tmp_path):
        x, y = _data(128)
        fs = FeatureSet.from_ndarrays(x, y)
        est = _estimator()
        est.set_checkpoint(str(tmp_path), SeveralIteration(1))
        est.train(fs, batch_size=64, epochs=1)  # 2 steps, snapshots 1-2
        est._ckpt_writer.wait()
        snap_step = est.global_step

        calls = {"n": 0}

        def always_fails(*args):
            calls["n"] += 1
            raise RuntimeError("permanent failure")

        est._train_step = always_fails
        budget = int(global_config().get("failure.retry_times"))
        with pytest.raises(RuntimeError, match="permanent failure"):
            est.train(fs, batch_size=64, epochs=2)
        assert calls["n"] == budget + 1
        # restored to the newest valid snapshot, not left mid-failure
        assert est.global_step == snap_step
        # ...and usable: a fresh compiled step evaluates finitely
        est._train_step = None
        scores = est.evaluate(fs, batch_size=64)
        assert np.isfinite(list(scores.values())).all()

    def test_exhaustion_skips_torn_newest_on_final_restore(self, ctx,
                                                           tmp_path):
        x, y = _data(128)
        fs = FeatureSet.from_ndarrays(x, y)
        est = _estimator()
        est.set_checkpoint(str(tmp_path), SeveralIteration(1))
        est.train(fs, batch_size=64, epochs=1)
        est._ckpt_writer.wait()
        faults.tear_snapshot(est._latest_snapshot())
        est._train_step = lambda *a: (_ for _ in ()).throw(
            RuntimeError("permanent failure"))
        with pytest.raises(RuntimeError, match="permanent failure"):
            est.train(fs, batch_size=64, epochs=2)
        assert est.global_step == 1  # fell back past torn snapshot-2


class TestRemoteIORetries:
    def _uri(self):
        return f"memory://zoo-chaos-{uuid.uuid4().hex[:10]}"

    def test_transient_failures_absorbed(self, ctx):
        global_config().set("failure.io_backoff_s", 0.001)
        root = self._uri()
        file_io.makedirs(root)
        p = file_io.join(root, "f.txt")
        with file_io.fopen(p, "w") as f:
            f.write("payload")
        # two consecutive injected faults < failure.io_retries (3)
        faults.arm("io.remote", p=1.0, budget=2)
        with file_io.fopen(p) as f:
            assert f.read() == "payload"
        assert faults.fire_count("io.remote") == 2

    def test_retry_budget_exhausts_to_caller(self, ctx):
        global_config().set("failure.io_backoff_s", 0.001)
        root = self._uri()
        file_io.makedirs(root)
        faults.arm("io.remote", p=1.0, budget=50)
        with pytest.raises(faults.FaultInjected):
            file_io.listdir(root)
        # 1 attempt + failure.io_retries retries
        retries = int(global_config().get("failure.io_retries"))
        assert faults.fire_count("io.remote") == retries + 1

    def test_deterministic_errors_not_retried(self, ctx):
        from analytics_zoo_tpu.common.file_io import _retryable
        assert not _retryable(FileNotFoundError("x"))
        assert not _retryable(FileExistsError("x"))
        assert not _retryable(PermissionError("x"))
        assert _retryable(ConnectionError("x"))
        assert _retryable(TimeoutError("x"))
        assert _retryable(faults.FaultInjected("io.remote", 1))
        assert not _retryable(ValueError("x"))

    def test_local_paths_bypass_injection(self, ctx, tmp_path):
        faults.arm("io.remote", p=1.0, budget=100)
        p = tmp_path / "local.txt"
        p.write_text("ok")
        with file_io.fopen(str(p)) as f:  # local: no remote site in path
            assert f.read() == "ok"
        assert faults.fire_count("io.remote") == 0


class TestFeedProduceFault:
    def test_producer_fault_surfaces_on_consumer(self, ctx):
        from analytics_zoo_tpu.feature.device_feed import DeviceFeed
        faults.arm("feed.produce", at=3)
        batches = (np.full((8, 2), i, np.float32) for i in range(6))
        got = []
        with pytest.raises(faults.FaultInjected, match="feed.produce"):
            with DeviceFeed(batches, ctx.mesh) as feed:
                for b in feed:
                    got.append(np.asarray(b))
        assert len(got) == 2  # batches before the fault arrived intact

    def test_estimator_recovers_from_producer_fault(self, ctx, tmp_path):
        est_a = _estimator()
        est_a.train(_fs(), batch_size=64, epochs=2)

        est_b = _estimator()
        est_b.set_checkpoint(str(tmp_path), SeveralIteration(1))
        faults.arm("feed.produce", at=6, budget=1)
        est_b.train(_fs(), batch_size=64, epochs=2)
        assert faults.fire_count("feed.produce") == 1
        assert est_b.epoch == 3 and est_b.global_step == 8
        _params_equal(est_a.get_params(), est_b.get_params())


class TestServingChaos:
    def _serving(self, tmp_path, batch_size=4):
        import jax
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import ClusterServing, ServingConfig
        im = InferenceModel().load_jax(
            lambda p, x: x.reshape(x.shape[0], -1).sum(1, keepdims=True), {})
        src = f"dir://{tmp_path}"
        cfg = ServingConfig(data_src=src, image_shape=(4,),
                            batch_size=batch_size, batch_wait_ms=5)
        return ClusterServing(cfg, model=im), src

    def test_decode_fault_errors_one_record_not_the_loop(self, ctx,
                                                         tmp_path):
        from analytics_zoo_tpu.serving import InputQueue, OutputQueue
        serving, src = self._serving(tmp_path)
        faults.arm("serving.decode", at=2, budget=1)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i in range(4):
            inq.enqueue_tensor(f"r{i}", np.full(4, float(i)))
        served = 0
        for _ in range(10):
            served += serving.serve_once()
            if served >= 4:
                break
        results = [outq.query(f"r{i}", timeout_s=5.0) for i in range(4)]
        assert all(r is not None for r in results)
        errors = [r for r in results if "error" in r]
        values = [r for r in results if "value" in r]
        assert len(errors) == 1 and len(values) == 3
        assert "injected fault" in errors[0]["error"]

    def test_writeback_fault_errors_batch_keeps_draining(self, ctx,
                                                         tmp_path):
        from analytics_zoo_tpu.serving import InputQueue, OutputQueue
        serving, src = self._serving(tmp_path)
        faults.arm("serving.writeback", at=1, budget=1)
        inq, outq = InputQueue(src), OutputQueue(src)
        # in the queue before the loop starts, so that the four are one
        # batch however slow the host is (enqueued beside a running loop,
        # a batch that waits 5 ms took three of them on a loaded machine)
        for i in range(4):
            inq.enqueue_tensor(f"a{i}", np.full(4, float(i)))
        serving.start()
        try:
            first = [outq.query(f"a{i}", timeout_s=10.0) for i in range(4)]
            # the faulted batch's records got ERROR results (not dropped:
            # a client would otherwise poll to its timeout)
            assert all(r is not None and "error" in r for r in first)
            # ...and the loop kept going: the next batch serves normally
            for i in range(4):
                inq.enqueue_tensor(f"b{i}", np.full(4, float(i)))
            second = [outq.query(f"b{i}", timeout_s=10.0) for i in range(4)]
            assert all(r is not None and "value" in r for r in second)
            serving.check_health()
        finally:
            serving.stop()
        assert faults.fire_count("serving.writeback") == 1

    def test_claim_fault_absorbed_and_retried(self, ctx, tmp_path):
        """A transient claim failure (flaky backend) is retried inside the
        loop — no request lost, no loop death."""
        from analytics_zoo_tpu.serving import InputQueue, OutputQueue
        serving, src = self._serving(tmp_path)
        faults.arm("serving.claim", at=1, budget=1)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i in range(4):
            inq.enqueue_tensor(f"r{i}", np.full(4, float(i)))
        served = 0
        for _ in range(10):
            served += serving.serve_once()
            if served >= 4:
                break
        assert served >= 4
        assert all(outq.query(f"r{i}", timeout_s=5.0) is not None
                   for i in range(4))
        assert faults.fire_count("serving.claim") == 1
        assert serving.counters["claim_faults"] == 1

    def test_claim_fault_streak_surfaces_dead_backend(self, ctx, tmp_path):
        """claim_retries consecutive failures = the backend is dead, not
        flaky — the loop must surface it, not spin silently forever."""
        serving, src = self._serving(tmp_path)
        serving.config.claim_retries = 3
        faults.arm("serving.claim", p=1.0, budget=100)
        # the failure STREAK survives across claim windows: however the
        # batch-wait slices the retries, the 4th consecutive one surfaces
        with pytest.raises(faults.FaultInjected):
            for _ in range(10):
                serving.serve_once()
        assert serving.counters["claim_faults"] == 4  # retries + surface

    def test_predict_fault_errors_batch_keeps_serving(self, ctx, tmp_path):
        from analytics_zoo_tpu.serving import InputQueue, OutputQueue
        serving, src = self._serving(tmp_path)
        faults.arm("serving.predict", at=1, budget=1)
        inq, outq = InputQueue(src), OutputQueue(src)
        for i in range(4):
            inq.enqueue_tensor(f"a{i}", np.full(4, float(i)))
        serving.serve_once()
        first = [outq.query(f"a{i}", timeout_s=5.0) for i in range(4)]
        assert all(r is not None and "injected fault" in r["error"]
                   for r in first)
        for i in range(4):
            inq.enqueue_tensor(f"b{i}", np.full(4, float(i)))
        served = 0
        for _ in range(10):
            served += serving.serve_once()
            if served >= 4:
                break
        second = [outq.query(f"b{i}", timeout_s=5.0) for i in range(4)]
        assert all(r is not None and "value" in r for r in second)
        assert faults.fire_count("serving.predict") == 1

    def test_reload_fault_rolls_back_and_serving_continues(self, ctx,
                                                           tmp_path):
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.serving import (InputQueue, ModelReloadError,
                                               OutputQueue)
        serving, src = self._serving(tmp_path)
        old = serving.model
        replacement = InferenceModel().load_jax(
            lambda p, x: x.reshape(x.shape[0], -1).mean(1, keepdims=True), {})
        faults.arm("serving.reload", at=1, budget=1)
        with pytest.raises(ModelReloadError):
            serving.reload_model(model=replacement)
        assert serving.model is old  # rolled back
        assert serving.counters["reload_failures"] == 1
        assert faults.fire_count("serving.reload") == 1
        # the fault budget is spent: the SAME reload now goes through,
        # and traffic flows across the whole episode
        assert serving.reload_model(model=replacement) is replacement
        InputQueue(src).enqueue_tensor("r0", np.full(4, 2.0))
        serving.serve_once()
        res = OutputQueue(src).query("r0", timeout_s=5.0)
        assert res["value"] == [pytest.approx(2.0)]  # the NEW (mean) model


def _soak_record(r):
    # deterministic shape-changing transform, applied in forked workers
    return np.concatenate([r * 1.5, r[:1] + 0.25]).astype(np.float32)


class TestChaosSoak:
    """The capstone: every registered training site armed, one run."""

    N, BATCH, EPOCHS = 512, 64, 3  # 8 steps/epoch, 24 total

    def _run(self, ckpt_root, chaos: bool):
        faults.reset()
        cfg = global_config()
        cfg.set("data.task_retries", 1)       # absorbs worker.task
        cfg.set("failure.io_backoff_s", 0.001)
        if chaos:
            faults.arm("worker.kill", at=2, budget=1)   # one child SIGKILL
            faults.arm("worker.task", at=3, budget=1)   # one task fault
            faults.arm("ckpt.write", at=3, budget=1)    # background write
            # dies before publish (previous snapshot stays newest intact)
            faults.arm("ckpt.corrupt", at=5, budget=1)  # tear a published
            # snapshot (restore falls back past it if it is newest)
            faults.arm("train.step", at=6, budget=1)    # chip step
            # failure — the elastic retry loop's bread and butter
            faults.arm("io.remote", p=0.05, budget=3, seed=13)  # flaky store
            faults.arm("feed.produce", at=18, budget=1)  # data plane dies
            faults.arm("train.preempt", at=16, budget=1)  # SIGTERM notice
        x, y = _data(self.N)
        base = FeatureSet.from_ndarrays(x, y, shuffle=True, seed=7)
        fs = base.transform(Lambda(_soak_record), num_workers=2, mode="mp")
        est = _estimator()
        est.set_checkpoint(ckpt_root, SeveralIteration(1))
        try:
            est.train(fs, batch_size=self.BATCH, epochs=self.EPOCHS)
        except PreemptedError:
            assert Estimator.preemption_marker(ckpt_root) is not None
            est.load_checkpoint(est._latest_snapshot())
            est.train(fs, batch_size=self.BATCH, epochs=self.EPOCHS)
        est._ckpt_writer.wait()
        return est

    def test_soak_bit_identical_to_fault_free(self, ctx, tmp_path):
        clean = self._run(str(tmp_path / "clean"), chaos=False)
        # chaos checkpoints live on a (fake) OBJECT STORE: remote staging
        # uploads, no atomic rename, flaky ops — the production worst case
        remote_root = f"memory://zoo-soak-{uuid.uuid4().hex[:10]}/ck"
        chaotic = self._run(remote_root, chaos=True)

        # every armed site actually fired — a soak that injected nothing
        # proves nothing
        for site in ("worker.kill", "worker.task", "ckpt.write",
                     "ckpt.corrupt", "train.step", "train.preempt"):
            assert faults.fire_count(site) >= 1, f"{site} never fired"
        assert chaotic.epoch == self.EPOCHS + 1
        assert chaotic.global_step == clean.global_step

        _params_equal(clean.get_params(), chaotic.get_params())


class TestServingOverloadSoak:
    """Serving capstone: overload + chaos on every serving fault site
    across two servers sharing one spool. The invariant under test is the
    SLO layer's contract — **every enqueued request receives exactly one
    terminal result (value or error); none hang to client timeout** — and
    the drain/reload paths leave no orphan threads, claim state, or
    unanswered uris behind."""

    N = 96

    def _model(self):
        from analytics_zoo_tpu.inference import InferenceModel
        return InferenceModel().load_jax(
            lambda p, x: x.reshape(x.shape[0], -1).sum(1, keepdims=True), {})

    def _spy_terminal_posts(self, servers):
        """Wrap each server's queue.put_result to record every terminal
        post (server results AND queue-level shed errors ride through the
        same method)."""
        import threading as _threading
        posts = []
        lock = _threading.Lock()
        for s in servers:
            orig = s.queue.put_result

            def wrapped(uri, value, _orig=orig):
                with lock:
                    posts.append(uri)
                return _orig(uri, value)

            s.queue.put_result = wrapped
        return posts

    def _arm_all_serving_sites(self):
        faults.arm("serving.claim", p=0.1, budget=4, seed=3)
        faults.arm("serving.decode", at=7, budget=1)
        faults.arm("serving.predict", at=3, budget=1)
        faults.arm("serving.writeback", at=5, budget=1)

    def _enqueue_overload(self, inq):
        # pre-loaded burst BEYOND max_pending → the first claims must shed
        # the oldest with explicit error results; every 10th request is
        # born with a 1ms budget → guaranteed deadline errors for the
        # survivors of the shed
        rs = np.random.RandomState(0)
        for i in range(self.N):
            inq.enqueue_tensor(f"r{i}", rs.rand(4).astype(np.float32),
                               deadline_ms=1 if i % 10 == 0 else None)

    def _assert_soak_invariants(self, results, posts, servers):
        expect = {f"r{i}" for i in range(self.N)}
        unanswered = expect - set(results)
        assert not unanswered, f"requests hung to timeout: {unanswered}"
        # exactly one terminal post per uri across both servers + sheds
        assert len(posts) == len(set(posts)), "a uri got TWO terminal posts"
        assert set(posts) == expect
        # the soak actually exercised overload + deadlines + chaos
        shed = sum(s.counters["shed"] for s in servers)
        expired = sum(s.counters["expired"] for s in servers)
        assert shed >= 1, "overload never shed"
        assert expired >= 1, "no deadline ever expired"
        for site in ("serving.claim", "serving.decode", "serving.predict",
                     "serving.writeback"):
            assert faults.fire_count(site) >= 1, f"{site} never fired"
        values = sum(1 for r in results.values() if "value" in r)
        errors = sum(1 for r in results.values() if "error" in r)
        assert values + errors == self.N
        assert values >= 1  # the chaos did not take ALL traffic down

    def test_file_queue_multiserver_soak(self, ctx, tmp_path):
        import threading as _threading
        import time as _time

        from analytics_zoo_tpu.common import file_io
        from analytics_zoo_tpu.serving import (ClusterServing, FileQueue,
                                               InputQueue, OutputQueue,
                                               ServingConfig)
        root = str(tmp_path / "spool")
        FileQueue(root)  # create the spool dirs
        src = f"dir://{root}"
        # only THESE servers' threads are the drain contract (earlier
        # tests' decode pools die on GC, asynchronously)
        pre = set(_threading.enumerate())
        servers = []
        for tag in ("a", "b"):
            cfg = ServingConfig(
                data_src=src, image_shape=(4,), batch_size=4,
                batch_wait_ms=5, max_pending=40,
                health_path=str(tmp_path / f"health_{tag}.json"),
                health_interval_s=0.05)
            servers.append(ClusterServing(cfg, model=self._model()))
        posts = self._spy_terminal_posts(servers)
        self._arm_all_serving_sites()
        self._enqueue_overload(InputQueue(src))
        for s in servers:
            s.start()
        outq = OutputQueue(src)
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline:
            if len(outq.dequeue()) >= self.N:
                break
            _time.sleep(0.05)
        # mid-soak reload on server A exercises the swap under live chaos
        from analytics_zoo_tpu.inference import InferenceModel
        servers[0].reload_model(model=InferenceModel().load_jax(
            lambda p, x: x.reshape(x.shape[0], -1).mean(1, keepdims=True),
            {}))
        for s in servers:
            s.drain(timeout_s=30.0)
        self._assert_soak_invariants(outq.dequeue(), posts, servers)
        # drain left nothing behind: no pending spool entries, no claim
        # state, no serve-loop or decode-pool threads, terminal health
        assert servers[0].queue.pending_count() == 0
        assert file_io.listdir(file_io.join(root, "claimed")) == []
        leaked = [t.name for t in _threading.enumerate()
                  if t not in pre and t.name.startswith("zoo-serving")]
        assert not leaked
        for tag, s in zip(("a", "b"), servers):
            assert s._in_flight == 0
            health = json.loads(
                (tmp_path / f"health_{tag}.json").read_text())
            assert health["state"] == "drained"
        assert sum(s.counters["reloads"] for s in servers) == 1

    def test_redis_stub_multiserver_soak(self, ctx, tmp_path, monkeypatch):
        import sys as _sys
        import threading as _threading
        import time as _time
        import types as _types

        from tests.test_redis_serving import FakeRedis

        # the real broker pops/acks atomically across connections; the
        # in-memory fake needs a lock to model that under two serve loops
        lock = _threading.Lock()
        for meth in ("xreadgroup", "xack", "xautoclaim"):
            orig = getattr(FakeRedis, meth)

            def locked(self, *a, _orig=orig, **k):
                with lock:
                    return _orig(self, *a, **k)

            monkeypatch.setattr(FakeRedis, meth, locked)
        fake_mod = _types.ModuleType("redis")
        fake_mod.StrictRedis = FakeRedis
        monkeypatch.setitem(_sys.modules, "redis", fake_mod)
        FakeRedis.instances.clear()

        from analytics_zoo_tpu.serving import (ClusterServing, InputQueue,
                                               OutputQueue, ServingConfig)
        src = "soakredis:6379"
        pre = set(_threading.enumerate())
        servers = []
        for tag in ("a", "b"):
            cfg = ServingConfig(data_src=src, image_shape=(4,),
                                batch_size=4, batch_wait_ms=5,
                                max_pending=40)
            servers.append(ClusterServing(cfg, model=self._model()))
        posts = self._spy_terminal_posts(servers)
        self._arm_all_serving_sites()
        self._enqueue_overload(InputQueue(src))
        for s in servers:
            s.start()
        outq = OutputQueue(src)
        results = {}
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline and len(results) < self.N:
            for i in range(self.N):
                uri = f"r{i}"
                if uri not in results:
                    res = outq.query(uri)
                    if res is not None:
                        results[uri] = res
            _time.sleep(0.05)
        for s in servers:
            s.drain(timeout_s=30.0)
        self._assert_soak_invariants(results, posts, servers)
        assert servers[0].queue.pending_count() == 0
        # ack bookkeeping is complete: nothing stranded in the PEL
        broker = FakeRedis.instances[("soakredis", 6379, 0)]
        assert broker.groups[("image_stream", "serving")]["pel"] == {}
        leaked = [t.name for t in _threading.enumerate()
                  if t not in pre and t.name.startswith("zoo-serving")]
        assert not leaked
