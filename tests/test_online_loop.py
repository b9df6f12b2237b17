"""The online learning loop end-to-end: streaming ingest off a queue
(watermark/epoch semantics, backpressure, bit-reproducible data_state
resume), continual training (train_online), and trainer→server promotion
(canary → fleet, model_version verified live, chaos rollback).

Capstone: a sharded NCF retrains on simulated click feedback *while
serving it* — ISSUE 15 / ROADMAP item 3."""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import analytics_zoo_tpu
from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.feature import FeatureSet
from analytics_zoo_tpu.serving.queues import FileQueue, make_queue

REPO = os.path.dirname(os.path.dirname(
    os.path.abspath(analytics_zoo_tpu.__file__)))

USERS, ITEMS = 40, 36


def _click(rs):
    return {"x": [int(rs.integers(1, USERS + 1)),
                  int(rs.integers(1, ITEMS + 1))],
            "y": int(rs.integers(0, 2)), "ts": 0.0}


def _clicks(n, seed=0):
    rs = np.random.default_rng(seed)
    return [(f"c{i}", _click(rs)) for i in range(n)]


def _stream(q, root, tag="j", **kw):
    kw.setdefault("watermark_s", 0.0)
    kw.setdefault("poll_interval_s", 0.005)
    kw.setdefault("epoch_records", 16)
    return FeatureSet.from_queue(q, os.path.join(root, tag), **kw)


def _ncf(shard=True):
    from analytics_zoo_tpu.models.recommendation.ncf import NeuralCF
    return NeuralCF(USERS, ITEMS, 2, user_embed=8, item_embed=8,
                    hidden_layers=(16, 8), mf_embed=8,
                    shard_embeddings=shard)


def _estimator(model, mesh=None):
    from analytics_zoo_tpu.estimator import Estimator
    from analytics_zoo_tpu.keras import objectives
    from analytics_zoo_tpu.keras.optimizers import SGD
    return Estimator(model=model,
                     loss_fn=objectives.get(
                         "sparse_categorical_crossentropy"),
                     optimizer=SGD(0.1), mesh=mesh, seed=7)


def _params_equal(a, b):
    import jax
    la = jax.tree_util.tree_leaves(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestQueueFeatureSet:
    def test_batches_replay_and_digest(self, tmp_path):
        """Journal order is the data order: a fresh consumer rewound to a
        saved data_state replays the same bytes; a tampered digest is
        rejected; skip_batches fast-forwards identically."""
        root = str(tmp_path)
        q = make_queue(f"dir://{root}/q")
        q.enqueue_many(_clicks(64))
        fs = _stream(q, root)
        list(fs.train_iterator(4))  # epoch 1
        st = fs.data_state()
        epoch2 = list(fs.train_iterator(4))
        assert len(epoch2) == 4

        fs2 = _stream(q, root)
        fs2.set_data_state(st)
        replay = list(fs2.train_iterator(4))
        for (xa, ya), (xb, yb) in zip(epoch2, replay):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

        bad = json.loads(st)
        bad["crc"] ^= 1
        with pytest.raises(ValueError, match="digest"):
            fs2.set_data_state(json.dumps(bad))

        fs3 = _stream(q, root)
        fs3.set_data_state(st)
        tail = list(fs3.train_iterator(4, skip_batches=2))
        assert len(tail) == 2
        np.testing.assert_array_equal(tail[0][0], epoch2[2][0])
        for f in (fs, fs2, fs3):
            f.close()

    def test_throwaway_iterator_loses_nothing(self, tmp_path):
        """The Estimator draws one batch from an abandoned iterator for
        model init; an uncommitted read position dies with its iterator,
        so the real epoch sees every record."""
        root = str(tmp_path)
        q = make_queue(f"dir://{root}/q")
        q.enqueue_many(_clicks(32))
        fs = _stream(q, root)
        sample = next(fs.train_iterator(4))
        first = list(fs.train_iterator(4))[0]
        np.testing.assert_array_equal(sample[0], first[0])
        fs.close()

    def test_watermark_holds_future_records(self, tmp_path):
        """Records younger than the watermark stay out of the journal
        (claimed, buffered, unreleased); old records flow through."""
        from analytics_zoo_tpu.common.utils import wall_clock
        root = str(tmp_path)
        q = make_queue(f"dir://{root}/q")
        rs = np.random.default_rng(1)
        old = [(f"o{i}", _click(rs)) for i in range(8)]
        future = []
        for i in range(4):
            rec = _click(rs)
            rec["ts"] = wall_clock() + 3600.0
            future.append((f"f{i}", rec))
        q.enqueue_many(old + future)
        fs = _stream(q, root, epoch_records=8, watermark_s=1.0,
                     buffer_records=64)
        got = list(fs.train_iterator(4))
        assert len(got) == 2
        # the 4 future records must not have been released. The ingest
        # thread counts a release after its fsync, and the consumer above
        # reads the bytes as soon as they are written: wait for the count
        # too, then let the loop pass over the held records a few times
        deadline = time.monotonic() + 10.0
        while ((q.pending_count() or fs._journal_records < 8)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        time.sleep(0.1)
        assert q.pending_count() == 0
        assert fs._journal_records == 8
        fs.close()

    def test_buffer_full_forces_release_and_backpressure(self, tmp_path):
        """A full buffer (a) force-releases past the watermark so a
        quiet stream never deadlocks, and (b) stops claiming, so
        backpressure shows up as queue depth."""
        from analytics_zoo_tpu.common.utils import wall_clock
        root = str(tmp_path)
        q = make_queue(f"dir://{root}/q")
        rs = np.random.default_rng(2)
        items = []
        for i in range(12):
            rec = _click(rs)
            rec["ts"] = wall_clock() + 3600.0  # all behind the watermark
            items.append((f"b{i}", rec))
        q.enqueue_many(items)
        fs = _stream(q, root, epoch_records=8, watermark_s=1.0,
                     buffer_records=4)
        fs._ensure_ingest()
        # buffer fills to 4, force-releases them, then stops claiming
        deadline = time.monotonic() + 5.0
        while fs._journal_records < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fs._journal_records == 4
        time.sleep(0.1)  # ingest gets every chance to over-claim
        assert q.pending_count() == 8, "backpressure did not hold"
        # consuming drains the backlog and re-opens the claim window
        got = list(fs.train_iterator(4))
        assert len(got) == 2
        fs.close()

    def test_resume_against_wrong_journal_fails(self, tmp_path):
        root = str(tmp_path)
        q = make_queue(f"dir://{root}/q")
        q.enqueue_many(_clicks(32))
        fs = _stream(q, root)
        list(fs.train_iterator(4))
        st = fs.data_state()
        fs.close()
        q2 = make_queue(f"dir://{root}/q2")
        q2.enqueue_many(_clicks(32, seed=9))
        other = _stream(q2, root, tag="j2")
        list(other.train_iterator(4))
        with pytest.raises(ValueError):
            other.set_data_state(st)
        other.close()


class TestOnlineNCFLoop:
    """Capstone: sharded NCF retrains on a click stream WHILE serving it,
    a promotion lands fleet-wide with model_version verified live, and an
    injected canary failure rolls back cleanly."""

    def _servers(self, root, export, names=("canary", "replica")):
        from analytics_zoo_tpu.serving.server import (ClusterServing,
                                                      ServingConfig)
        out = {}
        for name in names:
            cfg = ServingConfig(data_src=f"dir://{root}/srv-{name}",
                                model_path=export, model_type="zoo",
                                image_shape=(2,), batch_size=4,
                                batch_wait_ms=5)
            out[name] = ClusterServing(cfg)
        return out

    @pytest.mark.slow  # re-tiered: heaviest e2e sweep (tier-1 870s budget)
    def test_train_serve_promote_rollback(self, ctx, tmp_path):
        import jax
        from jax.sharding import Mesh

        from analytics_zoo_tpu.online import (Promoter, PromotionError,
                                              export_servable)
        from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue

        root = str(tmp_path)
        clicks = make_queue(f"dir://{root}/clicks")
        clicks.enqueue_many(_clicks(400))

        mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
        ncf = _ncf(shard=True)
        est = _estimator(ncf.build_model(), mesh=mesh)
        fs = _stream(clicks, root, epoch_records=64)

        # v1: a first round of continual training, exported and served
        est.train_online(fs, batch_size=16, max_steps=4,
                         snapshot_interval_s=3600)
        assert est._embed_plan(), "online NCF did not take the sparse path"
        v1 = export_servable(ncf, est, f"{root}/exports/v1")
        servers = self._servers(root, v1)
        for s in servers.values():
            assert s.model_version == "v1"
            assert s.health_snapshot()["model_version"] == "v1"

        # keep training off the stream WHILE the fleet serves it
        inq = InputQueue(f"dir://{root}/srv-canary")
        outq = OutputQueue(f"dir://{root}/srv-canary")
        served = []
        for i in range(6):
            inq.enqueue_tensor(f"u{i}",
                               np.array([1.0 + i % USERS, 2.0], np.float32))
        est.train_online(fs, batch_size=16, max_steps=12,
                         snapshot_interval_s=3600)
        while servers["canary"].serve_once():
            pass
        for i in range(6):
            r = outq.query(f"u{i}", timeout_s=20.0)
            assert r is not None
            served.append(r)
        assert len(served) == 6
        assert est.global_step == 12

        # promotion: canary first, fleet-wide, verified live
        v2 = export_servable(ncf, est, f"{root}/exports/v2")
        prom = Promoter(servers, canary="canary")
        assert prom.promote(v2) == "v2"
        for s in servers.values():
            assert s.health_snapshot()["model_version"] == "v2"
        # the promoted fleet still answers, with the new params
        inq.enqueue_tensor("after", np.array([3.0, 5.0], np.float32))
        while servers["canary"].serve_once():
            pass
        assert outq.query("after", timeout_s=20.0) is not None

        # injected canary failure: nothing may move off v2
        v3 = export_servable(ncf, est, f"{root}/exports/v3")
        faults.reset()
        faults.arm("online.promote", at=1)  # 1-based: dies at the canary
        try:
            with pytest.raises(PromotionError):
                prom.promote(v3)
        finally:
            faults.reset()
        for s in servers.values():
            assert s.model_version == "v2"
            assert s.config.model_path == v2
        fs.close()

    def test_mid_rollout_chaos_rolls_back_with_zero_drops(self, ctx,
                                                          tmp_path):
        """``online.promote`` fires at the second instance: the canary
        (already on the new version) must roll BACK to the prior
        model_version, and every request routed through the fleet during
        the failed rollout still gets exactly one terminal result."""
        from analytics_zoo_tpu.online import Promoter, PromotionError
        from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
        from analytics_zoo_tpu.serving.fleet import (FleetInstance,
                                                     FleetRouter,
                                                     instance_queue)
        from analytics_zoo_tpu.serving.server import (ClusterServing,
                                                      ServingConfig)

        root = str(tmp_path / "fleet")
        ncf = _ncf(shard=False)
        ncf.compile(optimizer="adam",
                    loss="sparse_categorical_crossentropy")
        exports = {}
        for v in ("v1", "v2"):
            ncf.save_model(f"{root}/exports/{v}")
            exports[v] = f"{root}/exports/{v}"

        front = FileQueue(root)
        servers, insts = {}, []
        for name in ("a", "b"):
            qi = instance_queue(root, name)
            hp = str(tmp_path / f"{name}.json")
            cfg = ServingConfig(data_src=root, model_path=exports["v1"],
                                model_type="zoo", image_shape=(2,),
                                batch_size=4, batch_wait_ms=5,
                                health_path=hp, health_interval_s=0.0)
            servers[name] = ClusterServing(cfg, queue=qi)
            insts.append(FleetInstance(name, qi, hp))
        router = FleetRouter(front, insts, stale_after_s=30.0,
                             health_refresh_s=0.0)
        for s in servers.values():
            s._write_health()  # router needs live gauges to place on

        def pump():
            router.route_once()
            moved = 1
            while moved:
                moved = sum(s.serve_once() for s in servers.values())

        inq, outq = InputQueue(root), OutputQueue(root)
        uris = []
        for i in range(4):
            uris.append(f"pre{i}")
            inq.enqueue_tensor(f"pre{i}",
                               np.array([1.0 + i, 2.0], np.float32))
        pump()

        prom = Promoter(servers, canary="a")
        faults.reset()
        faults.arm("online.promote", at=2)  # dies rolling out to "b"
        try:
            with pytest.raises(PromotionError):
                prom.promote(exports["v2"])
        finally:
            faults.reset()
        # fleet consistent on the PRIOR version
        for s in servers.values():
            assert s.model_version == "v1"
            assert s.health_snapshot()["model_version"] == "v1"
        # traffic enqueued across the failed rollout all terminates
        for i in range(4):
            uris.append(f"post{i}")
            inq.enqueue_tensor(f"post{i}",
                               np.array([2.0 + i, 3.0], np.float32))
        pump()
        results = {u: outq.query(u, timeout_s=20.0) for u in uris}
        missing = [u for u, r in results.items() if r is None]
        assert not missing, f"dropped requests: {missing}"
        reloads = sum(s.counters.get("reloads", 0)
                      for s in servers.values())
        assert reloads == 2  # canary forward + canary rollback


_CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from analytics_zoo_tpu.common.context import init_tpu_context, reset_context
reset_context(); init_tpu_context(force_reinit=True)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from online_child_common import build_estimator, build_stream

root = sys.argv[1]
est = build_estimator()
est.set_checkpoint(os.path.join(root, "ckpt"))
fs = build_stream(root)
open(os.path.join(root, "child_up"), "w").write("1")
# more steps than the queue can feed: the child blocks on the stream
# until the parent SIGKILLs it
est.train_online(fs, batch_size=8, max_steps=40, snapshot_interval_s=0.05)
"""

_CHILD_COMMON = r"""
import os
import numpy as np
from analytics_zoo_tpu.estimator import Estimator
from analytics_zoo_tpu.feature import FeatureSet
from analytics_zoo_tpu.keras import objectives
from analytics_zoo_tpu.keras.optimizers import SGD
from analytics_zoo_tpu.models.recommendation.ncf import NeuralCF
from analytics_zoo_tpu.serving.queues import make_queue


def build_estimator():
    model = NeuralCF(40, 36, 2, user_embed=8, item_embed=8,
                     hidden_layers=(16, 8), mf_embed=8,
                     shard_embeddings=False).build_model()
    return Estimator(model=model,
                     loss_fn=objectives.get(
                         "sparse_categorical_crossentropy"),
                     optimizer=SGD(0.1), seed=7)


def build_stream(root):
    q = make_queue(f"dir://{root}/q")
    return FeatureSet.from_queue(q, os.path.join(root, "j"),
                                 epoch_records=16, watermark_s=0.0,
                                 poll_interval_s=0.005)
"""


class TestSigkillResume:
    def test_killed_consumer_resumes_bit_identically(self, tmp_path):
        """SIGKILL the stream consumer mid-run; restart from data_state +
        latest snapshot; final params bit-identical to an uninterrupted
        run over the same click sequence."""
        root = str(tmp_path)
        total_clicks = _clicks(320, seed=3)  # 40 steps of 8
        child_dir = os.path.join(root, "child")
        ref_dir = os.path.join(root, "ref")
        os.makedirs(child_dir)
        os.makedirs(ref_dir)
        with open(os.path.join(root, "online_child_common.py"), "w") as f:
            f.write(_CHILD_COMMON)
        with open(os.path.join(root, "child.py"), "w") as f:
            f.write(_CHILD)

        # the child gets only the first 240 clicks: it can never reach
        # max_steps=40, so the SIGKILL always lands mid-run
        q = make_queue(f"dir://{child_dir}/q")
        q.enqueue_many(total_clicks[:240])
        # the child must see the SAME virtual device mesh as the parent
        # (conftest's XLA_FLAGS ride along in os.environ): different
        # data-parallel widths reduce losses in different float orders
        # and the bitwise comparison would be meaningless
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(root, "child.py"), child_dir],
            env=env, cwd=root, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT)
        try:
            ckpt = os.path.join(child_dir, "ckpt")
            deadline = time.monotonic() + 300.0
            while time.monotonic() < deadline:
                snaps = ([d for d in os.listdir(ckpt)
                          if d.startswith("snapshot-")]
                         if os.path.isdir(ckpt) else [])
                if snaps:  # snapshots publish atomically: listed == whole
                    break
                if proc.poll() is not None:
                    raise AssertionError(
                        f"child exited early with {proc.returncode}")
                time.sleep(0.05)
            else:
                raise AssertionError("child never published a snapshot")
            time.sleep(0.3)  # let a few more steps/snapshots land
            os.kill(proc.pid, signal.SIGKILL)
        finally:
            proc.wait(timeout=60)

        sys.path.insert(0, root)
        try:
            import online_child_common as cc
        finally:
            sys.path.remove(root)

        # resume: feed the remaining clicks, restore snapshot + journal
        # cursor, run to the SAME total step count
        q.enqueue_many(total_clicks[240:])
        est_r = cc.build_estimator()
        est_r.set_checkpoint(os.path.join(child_dir, "ckpt"))
        # the kill may have torn an in-flight async write: restore the
        # newest snapshot that passes checksum validation
        snap = est_r._restore_latest_valid()
        assert snap is not None
        killed_at = est_r.global_step
        assert 0 < killed_at < 40
        fs_r = cc.build_stream(child_dir)
        est_r.train_online(fs_r, batch_size=8, max_steps=40,
                           snapshot_interval_s=3600)
        assert est_r.global_step == 40
        fs_r.close()

        # uninterrupted reference over the identical click sequence
        qr = make_queue(f"dir://{ref_dir}/q")
        qr.enqueue_many(total_clicks)
        est_ref = cc.build_estimator()
        fs_ref = cc.build_stream(ref_dir)
        est_ref.train_online(fs_ref, batch_size=8, max_steps=40,
                             snapshot_interval_s=3600)
        fs_ref.close()

        _params_equal(est_ref.params, est_r.params)
