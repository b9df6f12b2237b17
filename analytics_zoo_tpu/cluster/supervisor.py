"""Elastic pod supervisor: lease-based membership for the training tier,
a demand-driven actuator for the serving tier (docs/cluster.md).

The reference rides a cluster manager that *supervises*: YARN restarts a
dead executor and ``DistriOptimizer`` retries the epoch from the newest
checkpoint within a ``failure.retryTimes`` budget (``Topology.scala:1180``).
Our :class:`~analytics_zoo_tpu.cluster.launcher.PodLauncher` only launches
— one worker dying kills the pod — and the fleet router only *signals*
(``fleet.desired_instances``) without anything acting on it. This module is
the missing supervisor, for both tiers:

- **Training** (:class:`ElasticSupervisor`): every worker registers a
  lease in a shared membership store (file-backed for CI, Redis-backed via
  the same client plumbing as ``serving/queues.py``) and heartbeats on the
  ``cluster.heartbeat_s`` cadence. The supervisor tracks each lease with
  the ``read_health()`` staleness trick — it stamps its OWN
  ``time.monotonic()`` whenever it *observes* a seq change, so expiry is a
  pure monotonic age and an NTP step on any host cannot fake (or mask) a
  death. A worker exiting nonzero OR a lease freezing past
  ``cluster.lease_expiry_s`` (SIGKILLed host; hung process with a live
  pid) triggers the elastic path: hung pids are SIGKILLed, the surviving
  workers are stopped at the restart barrier (they are parked in a
  ``jax.distributed`` collective that can never complete once a member
  died — the whole generation restarts, the cheap and correct form of
  elasticity for an SPMD pod), and after ``cluster.restart_backoff_s``
  the supervisor respawns the next generation against a FRESH coordinator
  port published through the ``ZOO_TPU_COORD_FILE`` handoff. The job
  resumes from the newest snapshot that passes manifest + per-rank seal
  verification (``_restore_latest_valid``) — proven bit-identical to an
  uninterrupted run in ``tests/test_supervisor.py``.
- **Serving** (:class:`FleetSupervisor`): closes the loop on the router's
  ``fleet.desired_instances`` signal by spawning/draining REAL server
  subprocesses. Scale-out registers the new instance's spool with the
  router; scale-in raises a ``DRAIN_<name>`` flag — the server hands its
  unfinished streams back to the front spool (``handoff(to_queue)``) or
  drains and publishes a terminal ``drained`` health state, either way
  the router re-places every request (zero dropped, exactly one
  terminal).

Chaos sites: ``cluster.heartbeat`` (a worker stops beating — hung-host
model), ``cluster.worker_restart`` (a respawn itself fails — backoff and
retry within budget), ``fleet.scale_actuate`` (an actuation tick fails —
retried next tick, never a half-spawn).
"""
from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..common import faults
from ..common import metrics as _metrics
from ..common.config import global_config
from ..common.utils import wall_clock
from ..ops import alerts as ops_alerts
from ..ops import events as ops_events
from ..ops import incident as ops_incident
from .launcher import WorkerResult, _free_port, require_chip_per_child

logger = logging.getLogger("analytics_zoo_tpu.cluster")

_M_LEASES = _metrics.gauge(
    "cluster.leases_alive",
    "Pod workers whose membership lease the supervisor currently "
    "considers live (seq advanced within the expiry window).")
_M_RESTARTS = _metrics.counter(
    "cluster.restarts_total",
    "Elastic pod-generation restarts, by trigger (exit = nonzero worker "
    "exit, lease = expired lease, respawn = failed respawn retried).",
    labels=("reason",))
_M_SCALE_EVENTS = _metrics.counter(
    "fleet.scale_events_total",
    "Fleet supervisor actuations: server subprocesses spawned (out) or "
    "drained (in) to track fleet.desired_instances.",
    labels=("direction",))

#: ops-plane event types (docs/observability.md "Ops plane")
_E_RESTART = ops_events.event_type(
    "cluster.restart",
    "Elastic pod-generation restart (reason=exit|lease|respawn, "
    "generation).")
_E_LEASE = ops_events.event_type(
    "cluster.lease_expired",
    "A worker's membership lease expired with the process still alive "
    "(hung host); the rank was SIGKILLed.")
_E_HANDOFF = ops_events.event_type(
    "cluster.handoff",
    "A fresh coordinator address was published through the coord-file "
    "handoff for the next pod generation.")
_E_SCALE = ops_events.event_type(
    "fleet.scale",
    "Fleet supervisor actuation (direction=out|in, label=instance).")


# -- membership store ---------------------------------------------------------

class FileLeaseStore:
    """Shared-directory lease store (the CI/single-host backend): one
    ``lease-<rank>.json`` per worker, written atomically (tmp + rename) so
    the supervisor never reads a torn lease."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def spec(self) -> str:
        return self.root

    def _path(self, rank: int) -> str:
        return os.path.join(self.root, f"lease-{rank}.json")

    def write(self, rank: int, lease: Dict[str, Any]) -> None:
        tmp = self._path(rank) + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(lease, f)
        os.replace(tmp, self._path(rank))

    def read_all(self) -> Dict[int, Dict[str, Any]]:
        out: Dict[int, Dict[str, Any]] = {}
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for name in names:
            if not (name.startswith("lease-") and name.endswith(".json")):
                continue
            try:
                with open(os.path.join(self.root, name)) as f:
                    lease = json.load(f)
                out[int(name[len("lease-"):-len(".json")])] = lease
            except (OSError, ValueError):
                continue  # torn/garbage lease: same as absent
        return out

    def clear(self) -> None:
        for rank in list(self.read_all()):
            try:
                os.unlink(self._path(rank))
            except OSError:
                pass


class RedisLeaseStore:
    """Redis-hash lease store for real multi-host pods — one HSET field
    per rank, same client plumbing as ``serving.queues.RedisQueue``."""

    def __init__(self, host: str = "localhost", port: int = 6379,
                 namespace: str = "zoo:leases", client=None):
        if client is None:
            import redis  # gated dependency (same as RedisQueue)
            client = redis.StrictRedis(host=host, port=port, db=0)
        self.db = client
        self.host, self.port, self.namespace = host, int(port), namespace

    def spec(self) -> str:
        return f"redis://{self.host}:{self.port}/{self.namespace}"

    def write(self, rank: int, lease: Dict[str, Any]) -> None:
        self.db.hset(self.namespace, mapping={str(rank): json.dumps(lease)})

    def read_all(self) -> Dict[int, Dict[str, Any]]:
        out: Dict[int, Dict[str, Any]] = {}
        for k, v in (self.db.hgetall(self.namespace) or {}).items():
            if isinstance(k, bytes):
                k = k.decode()
            if isinstance(v, bytes):
                v = v.decode()
            if not v:
                continue  # tombstone from clear()
            try:
                out[int(k)] = json.loads(v)
            except ValueError:
                continue
        return out

    def clear(self) -> None:
        # no DEL in the minimal client contract — tombstone every field
        ranks = list(self.read_all())
        if ranks:
            self.db.hset(self.namespace,
                         mapping={str(r): "" for r in ranks})


def make_lease_store(spec: str, client=None):
    """``redis://host:port/namespace`` → :class:`RedisLeaseStore`;
    anything else is a shared directory → :class:`FileLeaseStore`."""
    if spec.startswith("redis://"):
        rest = spec[len("redis://"):]
        hostport, _, namespace = rest.partition("/")
        host, _, port = hostport.partition(":")
        return RedisLeaseStore(host or "localhost", int(port or 6379),
                               namespace or "zoo:leases", client=client)
    return FileLeaseStore(spec)


class LeaseHeartbeat:
    """Worker-side lease pump: a daemon thread bumping this rank's lease
    seq every ``cluster.heartbeat_s``. Started by the bootstrap before
    ``jax.distributed.initialize`` so even a hang INSIDE the collective
    join is visible as lease progress stopping."""

    def __init__(self, store, rank: int, generation: int = 0,
                 heartbeat_s: Optional[float] = None):
        self.store = store
        self.rank = int(rank)
        self.generation = int(generation)
        self.heartbeat_s = (float(heartbeat_s) if heartbeat_s is not None
                            else float(global_config()
                                       .get("cluster.heartbeat_s")))
        self._seq = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat_once(self) -> bool:
        """One lease bump. Returns False when the heartbeat must stop —
        the ``cluster.heartbeat`` chaos site fired (hung-host model: the
        process lives on, the lease freezes)."""
        if faults.inject("cluster.heartbeat"):
            logger.warning("lease heartbeat for rank %d frozen by chaos "
                           "site cluster.heartbeat", self.rank)
            return False
        self._seq += 1
        self.store.write(self.rank, {
            "rank": self.rank, "pid": os.getpid(), "seq": self._seq,
            "generation": self.generation,
            # wall stamp is informational (operator debugging); liveness
            # is judged from seq progress on the SUPERVISOR's monotonic
            # clock, never from arithmetic on this field
            "wall": wall_clock(),
        })
        return True

    def start(self) -> "LeaseHeartbeat":
        self.beat_once()  # register immediately: expiry grace starts now

        def pump():
            while not self._stop.wait(self.heartbeat_s):
                if not self.beat_once():
                    return
        self._thread = threading.Thread(target=pump, daemon=True,
                                        name="lease-heartbeat")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.heartbeat_s + 1)
            self._thread = None


class LeaseTracker:
    """Supervisor-side staleness detector. For every rank it remembers
    the last lease seq it SAW and ``time.monotonic()`` at the moment of
    that observation — the same trick as ``read_health()``'s
    ``health_age_s``, but entirely on the supervisor's clock: a rank is
    expired when *our* monotonic clock says its seq has not advanced for
    ``expiry_s``. Workers that never registered get ``grace_s`` from
    construction (spawn + interpreter start is not a death)."""

    def __init__(self, ranks: Sequence[int], expiry_s: float,
                 grace_s: float):
        now = time.monotonic()
        self.expiry_s = float(expiry_s)
        self.grace_s = float(grace_s)
        self._seen: Dict[int, Tuple[int, float]] = {
            int(r): (-1, now) for r in ranks}

    def update(self, leases: Dict[int, Dict[str, Any]],
               generation: int) -> List[int]:
        """Fold in a fresh store read; returns the ranks whose lease is
        expired NOW. Leases from older generations are ignored (a dead
        rank's stale file must not shadow its replacement)."""
        now = time.monotonic()
        expired: List[int] = []
        for rank, (seq, seen_at) in self._seen.items():
            lease = leases.get(rank)
            cur = (int(lease["seq"])
                   if lease and int(lease.get("generation", 0)) == generation
                   else -1)
            if cur > seq:
                self._seen[rank] = (cur, now)
                continue
            limit = self.expiry_s if seq >= 0 else self.grace_s
            if now - seen_at > limit:
                expired.append(rank)
        return expired

    def alive(self) -> int:
        now = time.monotonic()
        n = 0
        for seq, seen_at in self._seen.values():
            limit = self.expiry_s if seq >= 0 else self.grace_s
            if now - seen_at <= limit:
                n += 1
        return n


# -- training tier ------------------------------------------------------------

class PodSupervisorError(RuntimeError):
    """Raised when the restart budget is exhausted (or the job timed
    out); carries the final generation's :class:`WorkerResult` list."""

    def __init__(self, msg: str, results: Sequence[WorkerResult] = ()):
        super().__init__(msg)
        self.results = list(results)


@dataclass
class SupervisorResult:
    """Outcome of a successful elastic run: the SUCCEEDING generation's
    worker results, plus how much elasticity it took to get there."""
    results: List[WorkerResult]
    generations: int
    restarts: int


@dataclass
class ElasticSupervisor:
    """Run ``target`` ("module:function") across ``num_processes``
    lease-heartbeating workers, restarting the pod generation (with
    backoff, within ``cluster.respawns``) whenever a rank dies or its
    lease expires. Each generation joins a fresh coordinator port
    published through the ``ZOO_TPU_COORD_FILE`` handoff, and the target
    is expected to resume from its newest valid snapshot (the estimator's
    ``_restore_latest_valid`` path)."""

    target: str
    num_processes: int
    args: Sequence[Any] = ()
    devices_per_process: Optional[int] = None
    platform: str = ""
    env: Dict[str, str] = field(default_factory=dict)
    workdir: Optional[str] = None
    lease_store: str = ""  # spec; default = <workdir>/leases file store
    heartbeat_s: Optional[float] = None
    lease_expiry_s: Optional[float] = None
    respawns: Optional[int] = None
    restart_backoff_s: Optional[float] = None
    poll_interval_s: float = 0.05
    #: grace for a rank that never beat yet (interpreter + jax import)
    spawn_grace_s: float = 60.0

    def run(self, timeout: Optional[float] = None) -> SupervisorResult:
        require_chip_per_child("ElasticSupervisor", self.platform,
                               self.num_processes)
        cfg = global_config()
        hb_s = (float(self.heartbeat_s) if self.heartbeat_s is not None
                else float(cfg.get("cluster.heartbeat_s")))
        expiry = (float(self.lease_expiry_s)
                  if self.lease_expiry_s is not None
                  else float(cfg.get("cluster.lease_expiry_s")))
        if expiry <= 0:
            expiry = 6.0 * hb_s
        budget = (int(self.respawns) if self.respawns is not None
                  else int(cfg.get("cluster.respawns")))
        backoff = (float(self.restart_backoff_s)
                   if self.restart_backoff_s is not None
                   else float(cfg.get("cluster.restart_backoff_s")))
        workdir = self.workdir or tempfile.mkdtemp(prefix="zoo_pod_")
        os.makedirs(workdir, exist_ok=True)
        store_spec = self.lease_store or os.path.join(workdir, "leases")
        store = make_lease_store(store_spec)
        coord_file = os.path.join(workdir, "coordinator.json")
        deadline = time.monotonic() + timeout if timeout else None

        generation, restarts = 0, 0
        results: List[WorkerResult] = []
        while True:
            try:
                # chaos site: the respawn (or first spawn) itself fails —
                # a scheduler refusal; back off and retry within budget
                faults.inject("cluster.worker_restart")
                procs, logs = self._spawn_generation(
                    generation, store_spec, coord_file, workdir, hb_s)
            except faults.FaultInjected:
                if restarts >= budget:
                    raise PodSupervisorError(
                        f"pod spawn failed and the respawn budget "
                        f"(cluster.respawns={budget}) is exhausted",
                        results)
                restarts += 1
                _M_RESTARTS.labels(reason="respawn").inc()
                _E_RESTART.emit(reason="respawn", generation=generation)
                logger.warning(
                    "generation %d spawn failed (injected); retrying "
                    "after %.2fs (%d/%d restarts)", generation,
                    backoff, restarts, budget)
                time.sleep(backoff)
                continue
            tracker = LeaseTracker(range(self.num_processes), expiry,
                                   max(self.spawn_grace_s, expiry))
            reason = self._watch_generation(
                procs, tracker, store, generation, deadline)
            if reason is None:  # every rank exited 0: success
                results = self._collect(generation, procs, logs)
                _M_LEASES.set(0)
                return SupervisorResult(results=results,
                                        generations=generation + 1,
                                        restarts=restarts)
            # elastic path: SIGKILL hung ranks, stop the survivors at the
            # restart barrier (they are parked in a collective that can
            # never complete), reap everything, then respawn
            self._stop_generation(procs, reason)
            results = self._collect(generation, procs, logs)
            if reason == "timeout":
                raise PodSupervisorError(
                    f"pod timed out after {timeout}s "
                    f"(generation {generation})", results)
            if restarts >= budget:
                tails = "\n".join(
                    f"--- worker {r.process_id} (rc={r.returncode}) ---\n"
                    f"{r.log_tail()}" for r in results
                    if r.returncode != 0)
                raise PodSupervisorError(
                    f"restart budget (cluster.respawns={budget}) "
                    f"exhausted after generation {generation} "
                    f"({reason})\n{tails}", results)
            restarts += 1
            _M_RESTARTS.labels(reason=reason).inc()
            _E_RESTART.emit(reason=reason, generation=generation)
            logger.warning(
                "generation %d lost a worker (%s); respawning generation "
                "%d after %.2fs (%d/%d restarts)", generation, reason,
                generation + 1, backoff, restarts, budget)
            time.sleep(backoff)
            generation += 1

    # -- internals --------------------------------------------------------

    def _spawn_generation(self, generation: int, store_spec: str,
                          coord_file: str, workdir: str,
                          hb_s: float):
        """Publish a fresh coordinator address through the handoff file,
        then spawn every rank of this generation."""
        coord = f"127.0.0.1:{_free_port()}"
        tmp = coord_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"coord": coord, "generation": generation}, f)
        os.replace(tmp, coord_file)
        _E_HANDOFF.emit(coordinator=coord, generation=generation)

        log_dir = os.path.join(workdir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        base_env = dict(os.environ)
        base_env.update(self.env)
        inherited = [p for p in
                     base_env.get("PYTHONPATH", "").split(os.pathsep) if p]
        base_env["PYTHONPATH"] = os.pathsep.join(
            dict.fromkeys([p for p in sys.path if p] + inherited))
        base_env.update({
            "ZOO_TPU_COORD_FILE": coord_file,
            "ZOO_TPU_NPROCS": str(self.num_processes),
            "ZOO_TPU_TARGET": self.target,
            "ZOO_TPU_ARGS": json.dumps(list(self.args)),
            "ZOO_TPU_PARENT": str(os.getpid()),
            "ZOO_TPU_LEASE_STORE": store_spec,
            "ZOO_TPU_GENERATION": str(generation),
            "ZOO_TPU_HEARTBEAT_S": repr(hb_s),
        })
        base_env.pop("ZOO_TPU_COORD", None)  # the file handoff owns it
        if self.platform:
            base_env["ZOO_TPU_PLATFORM"] = self.platform
        if self.devices_per_process:
            base_env["ZOO_TPU_DEVICES_PER_PROC"] = str(
                self.devices_per_process)
        procs: List[subprocess.Popen] = []
        logs: List[str] = []
        for pid in range(self.num_processes):
            env = dict(base_env)
            env["ZOO_TPU_PROC_ID"] = str(pid)
            log_path = os.path.join(log_dir,
                                    f"gen{generation}_worker{pid}.log")
            logs.append(log_path)
            with open(log_path, "w") as logf:
                procs.append(subprocess.Popen(
                    [sys.executable, "-m",
                     "analytics_zoo_tpu.cluster.bootstrap"],
                    env=env, stdout=logf, stderr=subprocess.STDOUT,
                    cwd=os.getcwd()))
        return procs, logs

    def _watch_generation(self, procs, tracker: LeaseTracker, store,
                          generation: int,
                          deadline: Optional[float]) -> Optional[str]:
        """Poll until the generation succeeds (returns None) or needs a
        restart (returns the reason). Marks hung ranks for the caller by
        SIGKILLing them here, where they are detected."""
        while True:
            rcs = [p.poll() for p in procs]
            if all(rc == 0 for rc in rcs):
                return None
            failed = [i for i, rc in enumerate(rcs)
                      if rc is not None and rc != 0]
            expired = tracker.update(store.read_all(), generation)
            _M_LEASES.set(tracker.alive())
            hung = [r for r in expired if rcs[r] is None]
            for rank in hung:
                _E_LEASE.emit(rank=rank, generation=generation)
                logger.warning(
                    "rank %d lease expired with the process still alive "
                    "(hung host) — SIGKILL pid %d", rank,
                    procs[rank].pid)
                try:
                    procs[rank].kill()
                except OSError:
                    pass
            if failed:
                return "exit"
            if hung:
                return "lease"
            if deadline and time.monotonic() > deadline:
                return "timeout"
            time.sleep(self.poll_interval_s)

    def _stop_generation(self, procs, reason: str) -> None:
        """The restart barrier: no rank of the old generation may survive
        into the new one (a survivor would hold the old coordinator and
        the old mesh). SIGTERM, bounded wait, SIGKILL stragglers."""
        for p in procs:
            if p.poll() is None:
                p.terminate()
        reap_deadline = time.monotonic() + 5
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(0.1,
                                       reap_deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    def _collect(self, generation: int, procs, logs) -> List[WorkerResult]:
        return [WorkerResult(i,
                             p.poll() if p.poll() is not None else -1,
                             logs[i], attempts=generation + 1)
                for i, p in enumerate(procs)]


# -- serving tier -------------------------------------------------------------

def _serve_instance(root: str, name: str, factory_spec: str) -> None:
    """Fleet-instance subprocess body. ``factory_spec`` is a
    "module:function" resolving to ``factory(root, name) -> server`` —
    a ClusterServing/GenerativeServing bound to ``instance_queue(root,
    name)`` with its health file at ``<root>/<name>.health.json``.

    Control files under ``root``: ``READY_<name>`` is raised here once
    serving; ``DRAIN_<name>`` triggers scale-in (generative servers hand
    unfinished streams back to the FRONT spool via ``handoff``, one-shot
    servers drain — either way the terminal health state lets the router
    reclaim the spool); ``DONE`` is fleet-wide shutdown. Every terminal
    this instance posts is journaled to ``<root>/audit/<name>.log`` — the
    exactly-one-terminal evidence chaos tests audit at ``put_result``."""
    from .bootstrap import resolve_target
    factory = resolve_target(factory_spec)
    srv = factory(root, name)

    audit_dir = os.path.join(root, "audit")
    os.makedirs(audit_dir, exist_ok=True)
    audit_path = os.path.join(audit_dir, f"{name}.log")
    queue = srv.queue
    orig_put = queue.put_result

    def audited_put(uri, payload):
        orig_put(uri, payload)
        if isinstance(payload, dict) and ("error" in payload
                                          or "value" in payload):
            with open(audit_path, "a") as f:
                f.write(f"{uri}\n")
    queue.put_result = audited_put

    step = getattr(srv, "serve_once", None) or srv.serve_step
    drain_flag = os.path.join(root, f"DRAIN_{name}")
    done_flag = os.path.join(root, "DONE")
    with open(os.path.join(root, f"READY_{name}"), "w") as f:
        f.write(str(os.getpid()))
    while True:
        if os.path.exists(drain_flag) or os.path.exists(done_flag):
            handoff = getattr(srv, "handoff", None)
            if handoff is not None and not os.path.exists(done_flag):
                # scale-in of a generative server: unfinished streams go
                # back to the front spool with their token prefix so an
                # adopter continues them token-identically
                from ..serving.queues import FileQueue
                handoff(FileQueue(root))
            else:
                srv.drain()
            return
        if not step():
            time.sleep(0.005)


class FleetSupervisor:
    """Actuator for the fleet scale signal: reconciles the live set of
    server subprocesses against ``FleetRouter.desired_instances()``
    (clamped to ``[min_instances, max_instances]``), at most one
    spawn/drain per ``fleet.scale_interval_s`` tick so demand spikes
    produce a ramp, not a thundering herd. Drive :meth:`step` from the
    same loop as ``router.route_once()``."""

    def __init__(self, router, root: str, server_factory: str, *,
                 min_instances: int = 1, max_instances: int = 4,
                 slots: int = 1, scale_interval_s: Optional[float] = None,
                 ready_timeout_s: float = 60.0):
        # instances are spawned processes that inherit this environment:
        # on a TPU host each would claim every chip
        require_chip_per_child("FleetSupervisor", "", int(max_instances))
        self.router = router
        self.root = root
        self.server_factory = server_factory
        self.min_instances = int(min_instances)
        self.max_instances = int(max_instances)
        self.slots = int(slots)
        self.scale_interval_s = (
            float(scale_interval_s) if scale_interval_s is not None
            else float(global_config().get("fleet.scale_interval_s")))
        self.ready_timeout_s = float(ready_timeout_s)
        self._procs: Dict[str, Any] = {}
        self._draining: Dict[str, Any] = {}
        self._counter = 0
        self._last_actuate = -1e18  # monotonic

    # -- observers --------------------------------------------------------

    def instance_names(self) -> List[str]:
        return sorted(self._procs)

    def alive_count(self) -> int:
        return sum(1 for p in self._procs.values() if p.is_alive())

    def status(self) -> Dict[str, Any]:
        """Supervisor-side operational status: fleet shape plus the ops
        plane's active alert/incident state, the same stamp servers put
        in ``health.json`` so every ``read_health()``-style consumer
        sees it."""
        return {
            "instances": self.instance_names(),
            "alive": self.alive_count(),
            "draining": sorted(self._draining),
            "alerts": sorted(ops_alerts.active_alerts()),
            "incident": ops_incident.last_incident(),
        }

    # -- actuation --------------------------------------------------------

    def step(self) -> Optional[str]:
        """One reconcile tick. Returns ``"out:<name>"`` / ``"in:<name>"``
        when an actuation happened, else None."""
        self._reap()
        now = time.monotonic()
        if now - self._last_actuate < self.scale_interval_s:
            return None
        desired = max(self.min_instances,
                      min(self.max_instances,
                          self.router.desired_instances()))
        live = len(self._procs)
        if desired == live:
            return None
        self._last_actuate = now
        try:
            # chaos site: the actuation itself fails (spawn refusal,
            # control-plane hiccup) — the fleet must stay consistent and
            # the tick retried on the next cadence
            faults.inject("fleet.scale_actuate")
        except faults.FaultInjected:
            logger.warning("fleet scale actuation aborted by chaos site "
                           "fleet.scale_actuate; retrying next tick")
            return None
        if desired > live:
            name = self._spawn_instance()
            if name is None:
                return None
            _M_SCALE_EVENTS.labels(direction="out").inc()
            _E_SCALE.emit(label=name, direction="out")
            logger.info("fleet scale-out: %s (%d -> %d)", name, live,
                        live + 1)
            return f"out:{name}"
        name = sorted(self._procs)[-1]  # newest instance drains first
        proc = self._procs.pop(name)
        self._draining[name] = proc
        with open(os.path.join(self.root, f"DRAIN_{name}"), "w") as f:
            f.write("1")
        _M_SCALE_EVENTS.labels(direction="in").inc()
        _E_SCALE.emit(label=name, direction="in")
        logger.info("fleet scale-in: draining %s (%d -> %d)", name, live,
                    live - 1)
        return f"in:{name}"

    def _spawn_instance(self) -> Optional[str]:
        import multiprocessing as mp

        from ..serving.fleet import FleetInstance, instance_queue
        name = f"inst{self._counter}"
        self._counter += 1
        ctx = mp.get_context("spawn")
        proc = ctx.Process(target=_serve_instance,
                           args=(self.root, name, self.server_factory),
                           daemon=True)
        proc.start()
        ready = os.path.join(self.root, f"READY_{name}")
        deadline = time.monotonic() + self.ready_timeout_s
        while not os.path.exists(ready):
            if not proc.is_alive() or time.monotonic() > deadline:
                logger.error("instance %s died before READY", name)
                if proc.is_alive():
                    proc.terminate()
                proc.join(timeout=10)
                return None
            time.sleep(0.02)
        self._procs[name] = proc
        self.router.register_instance(FleetInstance(
            name, instance_queue(self.root, name),
            os.path.join(self.root, f"{name}.health.json"),
            slots=self.slots))
        return name

    def _reap(self) -> None:
        """Collect exited subprocesses. A DRAINING instance exiting is
        the normal end of scale-in (remove it from the router — its spool
        was already reclaimed via the terminal health state). A LIVE
        instance exiting without a drain flag was killed: drop its record
        so the scale signal can respawn capacity; the router's staleness
        path reclaims its spool and fails its streams over."""
        for name, proc in list(self._draining.items()):
            if not proc.is_alive():
                proc.join(timeout=1)
                del self._draining[name]
                self.router.remove_instance(name)
        for name, proc in list(self._procs.items()):
            if not proc.is_alive():
                proc.join(timeout=1)
                del self._procs[name]
                logger.warning("fleet instance %s exited unexpectedly "
                               "(rc=%s)", name, proc.exitcode)

    def shutdown(self, timeout_s: float = 30.0) -> None:
        """Fleet-wide stop: raise DONE (every instance drains in-flight
        work and exits), then reap; stragglers are terminated."""
        with open(os.path.join(self.root, "DONE"), "w") as f:
            f.write("1")
        deadline = time.monotonic() + timeout_s
        procs = dict(self._procs)
        procs.update(self._draining)
        for name, proc in procs.items():
            proc.join(timeout=max(0.1, deadline - time.monotonic()))
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=5)
            self.router.remove_instance(name)
        self._procs.clear()
        self._draining.clear()
