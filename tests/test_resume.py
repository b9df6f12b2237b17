"""Checkpoint/resume reproducibility: a resumed run must replay the SAME
shuffled data order as an uninterrupted run (SURVEY §7 step 3 — the data
iterator is part of the checkpoint, not just params/opt state)."""
import os

import numpy as np
import pytest

from analytics_zoo_tpu.common.triggers import MaxEpoch, MaxIteration, SeveralIteration
from analytics_zoo_tpu.estimator import Estimator
from analytics_zoo_tpu.feature import FeatureSet
from analytics_zoo_tpu.keras import Sequential, objectives, optimizers
from analytics_zoo_tpu.keras.layers import Activation, Dense
from analytics_zoo_tpu.common.config import global_config


def _data(n=32):
    rs = np.random.RandomState(0)
    return (rs.randn(n, 6).astype(np.float32),
            rs.randint(0, 2, n).astype(np.float32))


def _estimator():
    model = Sequential([Dense(8, name="d1"), Activation("relu"),
                        Dense(2, name="d2")])
    return Estimator(model=model,
                     loss_fn=objectives.get("sparse_categorical_crossentropy"),
                     optimizer=optimizers.SGD(0.05))


def _fs():
    x, y = _data()
    return FeatureSet.from_ndarrays(x, y, shuffle=True, seed=7)


class TestResumeReproducibility:
    def test_epoch_boundary_resume_matches_straight_run(self, tmp_path):
        # straight run: 4 epochs
        est_a = _estimator()
        ra = est_a.train(_fs(), batch_size=8, epochs=4)

        # interrupted run: 2 epochs, checkpoint, then a FRESH estimator
        # resumes from the snapshot with a FRESH FeatureSet
        ck = str(tmp_path / "ck")
        est_b = _estimator()
        est_b.set_checkpoint(ck)
        rb = est_b.train(_fs(), batch_size=8, epochs=2)
        snaps = sorted(os.listdir(ck))
        assert snaps, "no snapshot written"

        est_c = _estimator()
        est_c.set_checkpoint(ck)
        est_c.load_checkpoint(est_c._latest_snapshot())
        assert est_c.epoch == 3 and est_c.global_step == 8
        rc = est_c.train(_fs(), batch_size=8, epochs=4)

        # identical loss trajectory: epochs 3-4 of the straight run
        np.testing.assert_allclose(ra["loss_history"][8:],
                                   rc["loss_history"], rtol=0, atol=0)
        # identical final params, bit for bit
        pa, pc = est_a.get_params(), est_c.get_params()
        np.testing.assert_array_equal(pa["d1"]["kernel"], pc["d1"]["kernel"])
        np.testing.assert_array_equal(pa["d2"]["kernel"], pc["d2"]["kernel"])

    def test_mid_epoch_resume_matches_straight_run(self, tmp_path):
        est_a = _estimator()
        ra = est_a.train(_fs(), batch_size=8, end_trigger=MaxEpoch(3))

        # stop mid-epoch-2 (iteration 6 of 12), snapshotting there
        ck = str(tmp_path / "ck")
        est_b = _estimator()
        est_b.set_checkpoint(ck)
        est_b.train(_fs(), batch_size=8, end_trigger=MaxIteration(6),
                    checkpoint_trigger=SeveralIteration(6))
        est_c = _estimator()
        est_c.load_checkpoint(os.path.join(ck, "snapshot-6"))
        assert est_c.global_step == 6
        rc = est_c.train(_fs(), batch_size=8, end_trigger=MaxEpoch(3))

        np.testing.assert_allclose(ra["loss_history"][6:],
                                   rc["loss_history"], rtol=0, atol=0)
        np.testing.assert_array_equal(est_a.get_params()["d2"]["kernel"],
                                      est_c.get_params()["d2"]["kernel"])

    def test_corrupt_checkpoint_rejected(self, tmp_path):
        import orbax.checkpoint as ocp
        bad = str(tmp_path / "bad")
        ocp.PyTreeCheckpointer().save(bad, {"params": {"d1": np.zeros(3)}})
        est = _estimator()
        with pytest.raises(ValueError, match="not an estimator snapshot"):
            est.load_checkpoint(bad)

    def test_structure_mismatch_rejected(self, tmp_path):
        ck = str(tmp_path / "ck")
        est_a = _estimator()
        est_a.set_checkpoint(ck)
        est_a.train(_fs(), batch_size=8, epochs=1)
        # a DIFFERENT architecture must refuse the snapshot once initialized
        other = Sequential([Dense(4, name="other1"), Dense(2, name="other2")])
        est_b = Estimator(
            model=other,
            loss_fn=objectives.get("sparse_categorical_crossentropy"),
            optimizer=optimizers.SGD(0.1))
        x, y = _data()
        est_b.train(FeatureSet.from_ndarrays(x, y), batch_size=8, epochs=1)
        with pytest.raises(ValueError, match="structure does not match"):
            est_b.load_checkpoint(est_a._latest_snapshot())


class TestElasticRetry:
    """Fault injection for the retry-from-checkpoint loop (reference
    InternalDistriOptimizer retry semantics, Topology.scala:1180-1262)."""

    def test_recovers_from_transient_step_failure(self, ctx, tmp_path):
        rs = np.random.RandomState(0)
        x = rs.rand(256, 4).astype(np.float32)
        y = (x.sum(1) > 2).astype(np.float32)
        est = Estimator(
            model=Sequential([Dense(8, activation="relu"), Dense(2)]),
            loss_fn=objectives.get(
                "sparse_categorical_crossentropy_from_logits"),
            optimizer=optimizers.Adam(1e-2))
        est.set_checkpoint(str(tmp_path), SeveralIteration(2))
        fs = FeatureSet.from_ndarrays(x, y)
        est.train(fs, batch_size=64, epochs=1)  # 4 its; snapshots at 2 and 4

        # inject: the next dispatched step blows up ONCE (transient chip
        # failure), later steps succeed
        real_step = est._train_step
        state = {"failed": False}

        def flaky_step(*args):
            if not state["failed"] and est.global_step == 5:
                state["failed"] = True
                raise RuntimeError("injected transient step failure")
            return real_step(*args)

        est._train_step = flaky_step
        out = est.train(fs, batch_size=64, epochs=2)
        assert state["failed"], "fault was never injected"
        # training completed both epochs after recovering from the snapshot
        # (est.epoch is the 1-based NEXT epoch: 3 == two epochs done)
        assert est.epoch == 3
        assert est.global_step == 8  # no steps lost or duplicated
        assert np.isfinite(out["loss_history"]).all()

    def test_build_failure_is_not_retried(self, ctx, tmp_path):
        """A step function that fails on its FIRST dispatch failed to trace
        or compile: a fault of the program, which no checkpoint cures. It
        surfaces at once instead of after ``failure.retry_times`` restores
        (a TPU compiler refusal would otherwise be retried five times)."""
        rs = np.random.RandomState(0)
        x = rs.rand(128, 4).astype(np.float32)
        y = rs.rand(128, 1).astype(np.float32)
        est = Estimator(model=Sequential([Dense(4), Dense(1)]),
                        loss_fn=objectives.get("mse"),
                        optimizer=optimizers.SGD(0.01))
        est.set_checkpoint(str(tmp_path), SeveralIteration(1))
        fs = FeatureSet.from_ndarrays(x, y)
        est.train(fs, batch_size=64, epochs=1)  # snapshots to restore from
        assert est._snapshot_candidates()

        calls = {"n": 0}

        def refused_by_the_compiler(*args):
            calls["n"] += 1
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        est._build_train_step = lambda: refused_by_the_compiler
        est._train_step = None  # a fresh step function is built and traced
        with pytest.raises(RuntimeError, match="failed to compile"):
            est.train(fs, batch_size=64, epochs=2)
        assert calls["n"] == 1

    def test_retry_budget_exhausts(self, ctx, tmp_path):
        rs = np.random.RandomState(0)
        x = rs.rand(128, 4).astype(np.float32)
        y = rs.rand(128, 1).astype(np.float32)
        est = Estimator(model=Sequential([Dense(4), Dense(1)]),
                        loss_fn=objectives.get("mse"),
                        optimizer=optimizers.SGD(0.01))
        est.set_checkpoint(str(tmp_path), SeveralIteration(1))
        fs = FeatureSet.from_ndarrays(x, y)
        est.train(fs, batch_size=64, epochs=1)

        calls = {"n": 0}

        def always_fails(*args):
            calls["n"] += 1
            raise RuntimeError("permanent failure")

        est._train_step = always_fails
        budget = int(global_config().get("failure.retry_times"))
        with pytest.raises(RuntimeError, match="permanent failure"):
            est.train(fs, batch_size=64, epochs=2)
        # the loop consumed its whole retry budget before surfacing: one
        # initial attempt + `budget` retries from the snapshot
        assert calls["n"] == budget + 1
