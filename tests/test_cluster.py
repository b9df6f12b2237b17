"""PodLauncher multi-process orchestration: 2 coordinated workers on the CPU
backend drive per-host sharding, global-batch training, rank-0 checkpointing,
and failure detection (reference RayOnSpark launch/guard behavior,
``pyzoo/zoo/ray/raycontext.py:190``)."""
import glob
import json
import os

import numpy as np
import pytest

from analytics_zoo_tpu.cluster import PodLaunchError, PodLauncher


class TestPodTraining:
    @pytest.mark.pod(budget_s=60)
    def test_two_process_train(self, tmp_path):
        workdir = str(tmp_path)
        launcher = PodLauncher(num_processes=2, devices_per_process=2,
                               platform="cpu", log_dir=os.path.join(workdir, "logs"))
        results = launcher.run("tests.pod_workers:train_worker",
                               args=[workdir], timeout=600)
        assert [r.returncode for r in results] == [0, 0]

        reports = {}
        for path in glob.glob(os.path.join(workdir, "done_*.json")):
            with open(path) as f:
                r = json.load(f)
            reports[r["process_index"]] = r
        assert set(reports) == {0, 1}

        # per-host shards are disjoint and cover the dataset
        rows0 = set(reports[0]["shard_rows"])
        rows1 = set(reports[1]["shard_rows"])
        assert rows0.isdisjoint(rows1)
        assert rows0 | rows1 == set(float(i) for i in range(32))

        # synchronous data parallelism: both processes observed the same loss
        assert reports[0]["final_loss"] == pytest.approx(
            reports[1]["final_loss"], abs=1e-6)
        assert reports[0]["iterations"] == reports[1]["iterations"] == 8

        # checkpointing is rank-0-only: exactly one process wrote snapshots
        ckpts = glob.glob(os.path.join(workdir, "ckpt", "*"))
        assert ckpts, "rank 0 wrote no checkpoint"

    @pytest.mark.pod(budget_s=30)
    def test_failure_detection_kills_pod(self, tmp_path):
        """One dead worker must fail the job fast, not hang the collective."""
        launcher = PodLauncher(num_processes=2, devices_per_process=1,
                               platform="cpu",
                               log_dir=os.path.join(str(tmp_path), "logs"))
        with pytest.raises(PodLaunchError) as ei:
            launcher.run("tests.pod_workers:failing_worker",
                         args=[str(tmp_path)], timeout=120)
        # rank 1 raised; rank 0 (blocked in allgather) was terminated
        assert "workers failed" in str(ei.value) or "timed out" in str(ei.value)

    def test_bad_target_rejected(self):
        from analytics_zoo_tpu.cluster.bootstrap import resolve_target
        with pytest.raises(ValueError):
            resolve_target("no_colon_here")


class TestBootstrapGuards:
    @pytest.mark.pod(budget_s=10)
    def test_parent_guard_reaps_orphaned_worker(self, tmp_path):
        """The launcher dying must take its workers with it. Model the
        documented race window — launcher dead before the worker's guard
        even starts — by handing bootstrap a ZOO_TPU_PARENT pid that is
        already gone: the ppid watch fires and the worker exits 113
        instead of serving out its 600s target."""
        import subprocess
        import sys
        launcher = subprocess.Popen([sys.executable, "-c", "pass"])
        launcher.wait()  # "launcher" is dead before the worker starts
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
        env.update({
            "ZOO_TPU_PROC_ID": "0", "ZOO_TPU_NPROCS": "1",
            "ZOO_TPU_COORD": "127.0.0.1:1",  # never reached
            "ZOO_TPU_TARGET": "tests.pod_workers:sleep_worker",
            "ZOO_TPU_ARGS": json.dumps([str(tmp_path)]),
            "ZOO_TPU_PARENT": str(launcher.pid),
        })
        worker = subprocess.Popen(
            [sys.executable, "-m", "analytics_zoo_tpu.cluster.bootstrap"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT)
        try:
            assert worker.wait(timeout=30) == 113
        finally:
            if worker.poll() is None:
                worker.kill()

    def test_coordinator_handoff_waits_for_atomic_write(self, tmp_path):
        """read_coordinator polls through absent AND torn states until
        the supervisor's atomic publish lands — the fresh-port-per-
        generation handoff the elastic restart path rides on."""
        import threading
        from analytics_zoo_tpu.cluster.bootstrap import read_coordinator
        coord_file = str(tmp_path / "coordinator.json")
        with open(coord_file, "w") as f:
            f.write('{"coord": ')  # torn: mid-replace snapshot

        def publish():
            import time
            time.sleep(0.3)
            tmp = coord_file + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"coord": "127.0.0.1:4242", "generation": 3}, f)
            os.replace(tmp, coord_file)

        t = threading.Thread(target=publish)
        t.start()
        try:
            assert read_coordinator(coord_file,
                                    timeout_s=10) == "127.0.0.1:4242"
        finally:
            t.join()

    def test_coordinator_handoff_times_out(self, tmp_path):
        from analytics_zoo_tpu.cluster.bootstrap import read_coordinator
        with pytest.raises(RuntimeError, match="no coordinator address"):
            read_coordinator(str(tmp_path / "never.json"), timeout_s=0.3)


class TestOneProcessForEachChip:
    """A chip belongs to one process at a time and a worker on a TPU host
    takes them all: launches that could only fail or hang there are refused
    at once (``require_chip_per_child``); workers held to the CPU are fine."""

    @pytest.mark.parametrize("platform,env,workers,refused", [
        ("cpu", "", 4, None),             # simulation: always fine
        ("", "cpu", 4, None),             # inherited JAX_PLATFORMS=cpu
        ("", "", 2, "contend for the same chips"),
        ("tpu", "cpu", 2, "contend for the same chips"),
        ("", "", 1, None),                # one worker, parent off the chip
    ])
    def test_launch_guard(self, monkeypatch, platform, env, workers,
                          refused):
        from analytics_zoo_tpu.cluster.launcher import require_chip_per_child
        monkeypatch.setenv("JAX_PLATFORMS", env)
        if refused is None:
            require_chip_per_child("launcher", platform, workers)
        else:
            with pytest.raises(PodLaunchError, match=refused):
                require_chip_per_child("launcher", platform, workers)

    def test_parent_holding_the_chip_is_refused(self, monkeypatch):
        import jax
        from analytics_zoo_tpu.cluster.launcher import require_chip_per_child
        jax.devices()  # this process has a backend now ...
        monkeypatch.setenv("JAX_PLATFORMS", "")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # a TPU's
        with pytest.raises(PodLaunchError, match="holds the chip"):
            require_chip_per_child("launcher", "", 1)


class TestLauncherRestarts:
    @pytest.mark.pod(budget_s=45)
    def test_per_worker_retry_and_budget_exhaustion(self, tmp_path):
        """restarts= relaunches a failed rank in place: a first-attempt
        crash succeeds on attempt 2 with its failure's log tail kept;
        a rank that fails every attempt exhausts the budget and surfaces
        every attempt's evidence."""
        launcher = PodLauncher(num_processes=1, devices_per_process=1,
                               platform="cpu", restarts=1,
                               log_dir=os.path.join(str(tmp_path), "logs"))
        results = launcher.run("tests.pod_workers:flaky_worker",
                               args=[str(tmp_path)], timeout=240)
        assert results[0].returncode == 0
        assert results[0].attempts == 2
        assert len(results[0].attempt_tails) == 1
        assert "first attempt dies" in results[0].attempt_tails[0]

        with pytest.raises(PodLaunchError) as ei:
            launcher.run("tests.pod_workers:always_failing_worker",
                         args=[str(tmp_path)], timeout=240)
        (res,) = ei.value.results
        assert res.attempts == 2  # initial + one retry, both failed
        assert len(res.attempt_tails) == 1
        assert "always failing worker" in res.attempt_tails[0]


class TestSubmitCLI:
    def test_submit_runs_example_across_workers(self):
        """The deploy CLI contract: zoo-tpu-submit --nprocs 2 <example>
        --smoke completes with every worker green."""
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "-m", "analytics_zoo_tpu.cluster.submit",
             "--nprocs", "2", "--platform", "cpu", "--devices-per-proc", "2",
             os.path.join(repo, "examples", "recommendation",
                          "ncf_example.py"), "--smoke"],
            capture_output=True, text=True, timeout=600, cwd=repo)
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        assert "worker 0: rc=0" in proc.stdout
        assert "worker 1: rc=0" in proc.stdout

    def test_emit_k8s_manifest(self):
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "-m", "analytics_zoo_tpu.cluster.submit",
             "--nprocs", "3", "--emit", "k8s", "--image", "zoo:v1",
             "train.py", "--epochs", "2"],
            capture_output=True, text=True, timeout=60, cwd=repo)
        assert proc.returncode == 0, proc.stderr[-500:]
        out = proc.stdout
        assert out.count("kind: Job") == 3
        assert "ZOO_TPU_NPROCS, value: '3'" in out
        assert "zoo:v1" in out
        assert "'--epochs', '2'" in out


class TestMultiHostDirectEval:
    @pytest.mark.pod(budget_s=30)
    def test_direct_eval_counts_tails(self, tmp_path):
        launcher = PodLauncher(num_processes=2, devices_per_process=2,
                               platform="cpu",
                               log_dir=os.path.join(str(tmp_path), "logs"))
        launcher.run("tests.pod_workers:direct_eval_tail_worker",
                     args=[str(tmp_path)], timeout=300)
        import json
        losses = []
        for rank in range(2):
            with open(os.path.join(str(tmp_path), f"eval_{rank}.json")) as f:
                losses.append(json.load(f)["loss"])
        # one logical eval: both hosts must agree on the weighted loss
        assert losses[0] == pytest.approx(losses[1])

    @pytest.mark.pod(budget_s=30)
    def test_exact_eval_matches_single_process(self, tmp_path):
        """Per-example masked eval on ragged 2-host shards equals the
        single-process loss over the concatenated data (zero tail bias) —
        the worker asserts the equality in-process; here we also check
        both hosts agreed."""
        launcher = PodLauncher(num_processes=2, devices_per_process=2,
                               platform="cpu",
                               log_dir=os.path.join(str(tmp_path), "logs"))
        launcher.run("tests.pod_workers:exact_eval_worker",
                     args=[str(tmp_path)], timeout=300)
        import json
        vals = []
        for rank in range(2):
            with open(os.path.join(str(tmp_path),
                                   f"exact_{rank}.json")) as f:
                vals.append(json.load(f))
        assert vals[0]["loss"] == pytest.approx(vals[1]["loss"])
        assert vals[0]["loss"] == pytest.approx(vals[0]["expect"],
                                                abs=1e-5)
