"""Multi-process (pod) launcher — the RayOnSpark/RayContext role, TPU-native.

The reference launches a Ray cluster across Spark executors and guards every
spawned process (``pyzoo/zoo/ray/raycontext.py:190``,
``pyzoo/zoo/ray/process.py:51``). A TPU pod is N host processes each driving
its local chips, coordinated by ``jax.distributed``; what the framework owes
the user is (a) spawning/joining those processes with the coordination
service wired up, (b) failure detection — one worker dying must fail the job
fast, not hang the collective — and (c) cleanup, no orphans.

:class:`PodLauncher` does exactly that for N *local* processes (the CI/simulation
story, and the single-host-many-processes story). On a real multi-host pod the
same worker bootstrap runs once per host under the cluster manager (GKE/ssh),
pointed at host 0 as coordinator.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class WorkerResult:
    process_id: int
    returncode: int
    log_path: str
    #: how many times this rank was launched (1 = no retry was needed)
    attempts: int = 1
    #: log tail captured from each FAILED attempt, oldest first (the
    #: final attempt's log is still on disk at ``log_path``)
    attempt_tails: List[str] = field(default_factory=list)

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, "r", errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return "<no log>"


class PodLaunchError(RuntimeError):
    def __init__(self, msg: str, results: Sequence[WorkerResult]):
        super().__init__(msg)
        self.results = list(results)


def require_chip_per_child(who: str, platform: str,
                           num_children: int) -> None:
    """Fail at once where child processes could only fail or hang.

    A TPU chip belongs to one process at a time, and a JAX process on a TPU
    host takes every chip of the host when its backend starts. Children
    held to the CPU (``platform="cpu"``, or an inherited
    ``JAX_PLATFORMS=cpu``) are always fine. Otherwise nothing in this
    package hands a child a chip of its own, so more than one child cannot
    work, and a single child cannot work while this process holds the chips
    itself. One process drives all chips of a host (a mesh over
    ``jax.devices()``); servers run as threads in it."""
    resolved = (platform or os.environ.get("JAX_PLATFORMS", "")).split(",")[0]
    if resolved == "cpu":
        return
    if num_children > 1:
        raise PodLaunchError(
            f"{who}: {num_children} worker processes on the "
            f"{resolved or 'default'} platform would contend for the same "
            f"chips — this launcher gives no worker a chip of its own. "
            f"Pass platform='cpu' (simulation), or drive all chips of the "
            f"host from one process.", [])
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge
        if (xla_bridge.backends_are_initialized()
                and jax.default_backend() == "tpu"):
            raise PodLaunchError(
                f"{who}: this process has initialised JAX on the TPU and "
                f"holds the chip, so a worker process that needs it would "
                f"fail or hang. Start workers before touching JAX here, or "
                f"run the work in this process.", [])


@dataclass
class PodLauncher:
    """Spawn ``num_processes`` coordinated workers and wait for them.

    Args:
      num_processes: worker count (``jax.process_count()`` inside workers).
      devices_per_process: if set, each worker gets that many *virtual CPU*
        devices (simulation/CI only — a count of CPU devices, not chips).
      platform: force a JAX platform inside workers ("cpu" for simulation).
        On any other platform only one worker can run, and only while this
        process stays off the chip: :func:`require_chip_per_child`.
      env: extra environment for workers.
      log_dir: where per-worker stdout/stderr logs go (tempdir default).
      fail_fast: on the first nonzero worker exit, terminate the rest.
      restarts: per-worker retry budget — a rank exiting nonzero is
        relaunched (same rank/env, fresh log) up to this many times
        before its failure is final; each failed attempt's log tail is
        kept on ``WorkerResult.attempt_tails``. Note this retries ONE
        rank into the existing coordination service — right for
        single-process pods and pre-collective crashes; a rank that died
        mid-collective needs the whole-generation restart
        :class:`~analytics_zoo_tpu.cluster.supervisor.ElasticSupervisor`
        provides.
    """

    num_processes: int
    devices_per_process: Optional[int] = None
    platform: str = ""
    env: Dict[str, str] = field(default_factory=dict)
    log_dir: Optional[str] = None
    fail_fast: bool = True
    restarts: int = 0

    def run(self, target: str, args: Sequence[Any] = (),
            timeout: Optional[float] = None) -> List[WorkerResult]:
        """Run ``target`` ("module:function", called with ``*args``) in every
        worker; block until all exit. Raises :class:`PodLaunchError` if any
        worker fails (with log tails for diagnosis)."""
        require_chip_per_child("PodLauncher", self.platform,
                               self.num_processes)
        log_dir = self.log_dir or tempfile.mkdtemp(prefix="zoo_pod_")
        os.makedirs(log_dir, exist_ok=True)
        coord = f"127.0.0.1:{_free_port()}"
        procs: List[subprocess.Popen] = []
        logs: List[str] = []
        base_env = dict(os.environ)
        base_env.update(self.env)
        # workers must resolve imports the way the driver does (repo
        # checkouts on sys.path, the user's creator modules, ...) — same
        # contract as Ray's runtime-env path propagation
        inherited = [p for p in base_env.get("PYTHONPATH", "").split(os.pathsep)
                     if p]
        base_env["PYTHONPATH"] = os.pathsep.join(
            dict.fromkeys([p for p in sys.path if p] + inherited))
        base_env.update({
            "ZOO_TPU_COORD": coord,
            "ZOO_TPU_NPROCS": str(self.num_processes),
            "ZOO_TPU_TARGET": target,
            "ZOO_TPU_ARGS": json.dumps(list(args)),
            "ZOO_TPU_PARENT": str(os.getpid()),
        })
        if self.platform:
            base_env["ZOO_TPU_PLATFORM"] = self.platform
        if self.devices_per_process:
            base_env["ZOO_TPU_DEVICES_PER_PROC"] = str(self.devices_per_process)
        def spawn(pid: int, attempt: int):
            env = dict(base_env)
            env["ZOO_TPU_PROC_ID"] = str(pid)
            suffix = "" if attempt == 1 else f".attempt{attempt}"
            log_path = os.path.join(log_dir, f"worker_{pid}{suffix}.log")
            with open(log_path, "w") as logf:  # child keeps its dup'd fd
                proc = subprocess.Popen(
                    [sys.executable, "-m",
                     "analytics_zoo_tpu.cluster.bootstrap"],
                    env=env, stdout=logf, stderr=subprocess.STDOUT,
                    cwd=os.getcwd())
            return proc, log_path

        try:
            for pid in range(self.num_processes):
                proc, log_path = spawn(pid, 1)
                procs.append(proc)
                logs.append(log_path)
            return self._wait(procs, logs, timeout, spawn)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            deadline = time.monotonic() + 5
            for p in procs:
                if p.poll() is None:
                    try:
                        p.wait(timeout=max(0.1, deadline - time.monotonic()))
                    except subprocess.TimeoutExpired:
                        p.kill()

    def _wait(self, procs, logs, timeout, spawn=None) -> List[WorkerResult]:
        deadline = time.monotonic() + timeout if timeout else None
        n = len(procs)
        attempts = [1] * n
        tails: List[List[str]] = [[] for _ in range(n)]
        while True:
            rcs = [p.poll() for p in procs]
            if spawn is not None and self.restarts > 0:
                # per-worker retry: a failed rank with budget left is
                # relaunched in place (tail captured per attempt) before
                # fail-fast gets to judge it
                for i, rc in enumerate(rcs):
                    if rc not in (None, 0) and attempts[i] <= self.restarts:
                        tails[i].append(WorkerResult(i, rc,
                                                     logs[i]).log_tail())
                        attempts[i] += 1
                        procs[i], logs[i] = spawn(i, attempts[i])
                        rcs[i] = None
            if all(rc is not None for rc in rcs):
                break
            if self.fail_fast and any(rc not in (None, 0) for rc in rcs):
                # failure detection: a dead worker leaves the others blocked
                # in a collective — kill the pod now, surface the failure
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                deadline = time.monotonic() + 5  # reap so returncodes are real
                for p in procs:
                    if p.poll() is None:
                        try:
                            p.wait(timeout=max(0.1, deadline - time.monotonic()))
                        except subprocess.TimeoutExpired:
                            p.kill()
                            p.wait()
                break
            if deadline and time.monotonic() > deadline:
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                results = self._results(procs, logs, attempts, tails)
                raise PodLaunchError(
                    f"pod timed out after {timeout}s", results)
            time.sleep(0.2)
        results = self._results(procs, logs, attempts, tails)
        # -SIGTERM exits are workers WE killed in fail-fast — report them as
        # terminated, not as the failure's cause
        failed = [r for r in results
                  if r.returncode not in (0, -signal.SIGTERM, -signal.SIGKILL)]
        killed = [r for r in results
                  if r.returncode in (-signal.SIGTERM, -signal.SIGKILL)]
        if failed or killed:
            tails = "\n".join(
                f"--- worker {r.process_id} (rc={r.returncode}) ---\n"
                f"{r.log_tail()}" for r in failed)
            note = (f" ({len(killed)} healthy workers terminated by "
                    f"fail-fast)" if killed else "")
            raise PodLaunchError(
                f"{len(failed)}/{self.num_processes} workers failed{note}\n"
                f"{tails}", results)
        return results

    def _results(self, procs, logs, attempts=None,
                 tails=None) -> List[WorkerResult]:
        return [WorkerResult(i, p.poll() if p.poll() is not None else -1,
                             logs[i],
                             attempts=attempts[i] if attempts else 1,
                             attempt_tails=list(tails[i]) if tails else [])
                for i, p in enumerate(procs)]


def run_pod(target: str, num_processes: int, args: Sequence[Any] = (),
            devices_per_process: Optional[int] = None, platform: str = "",
            timeout: Optional[float] = None, **kwargs) -> List[WorkerResult]:
    """One-call form: ``run_pod("pkg.mod:train", 4, args=[...])``."""
    return PodLauncher(num_processes=num_processes,
                       devices_per_process=devices_per_process,
                       platform=platform, **kwargs).run(
        target, args=args, timeout=timeout)
