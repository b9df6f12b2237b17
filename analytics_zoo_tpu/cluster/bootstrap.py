"""Worker-process bootstrap for multi-process (pod) execution.

Runs inside each spawned worker before any user code: installs the
parent-death guard (the reference guards executor-side processes the same
way — ``JVMGuard``/``ProcessMonitor`` in
``pyzoo/zoo/ray/process.py:51`` kill the forked runtime when the driver
dies), configures the JAX platform/virtual-device flags *before* the backend
initializes, joins the ``jax.distributed`` coordination service, and only
then imports and calls the user target.
"""
from __future__ import annotations

import importlib
import json
import os
import signal
import sys
import threading
import time


def _install_parent_guard() -> None:
    """Exit if the launcher dies: PR_SET_PDEATHSIG where available, plus a
    ppid-watch against the LAUNCHER's pid passed via env (``os.getppid()``
    captured here could already be init's pid if the launcher died before
    this ran — comparing against the env-passed pid covers that window)."""
    launcher_pid = int(os.environ.get("ZOO_TPU_PARENT", os.getppid()))
    try:
        import ctypes
        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        PR_SET_PDEATHSIG = 1
        libc.prctl(PR_SET_PDEATHSIG, signal.SIGTERM)
    except Exception:
        pass

    def watch():
        import time
        while True:
            if os.getppid() != launcher_pid:
                os._exit(113)  # parent gone: orphaned worker must not linger
            time.sleep(1.0)

    t = threading.Thread(target=watch, daemon=True, name="parent-guard")
    t.start()


def resolve_target(spec: str):
    """``package.module:function`` → callable."""
    mod_name, _, fn_name = spec.partition(":")
    if not fn_name:
        raise ValueError(f"target '{spec}' must be 'module:function'")
    mod = importlib.import_module(mod_name)
    fn = getattr(mod, fn_name)
    if not callable(fn):
        raise TypeError(f"target {spec} is not callable")
    return fn


def read_coordinator(coord_file: str, timeout_s: float = 60.0) -> str:
    """Coordinator-address handoff: poll ``coord_file`` (written
    atomically by the elastic supervisor before each generation's spawn)
    until it yields an address. A file — not a baked env var — because
    every restarted generation needs a FRESH coordinator port while the
    workers' env stays the launch-time one."""
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            with open(coord_file) as f:
                coord = json.load(f).get("coord", "")
            if coord:
                return coord
        except (OSError, ValueError):
            pass  # not written yet / torn mid-replace: retry
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"no coordinator address in {coord_file!r} after "
                f"{timeout_s}s")
        time.sleep(0.05)


def main() -> int:
    _install_parent_guard()
    proc_id = int(os.environ["ZOO_TPU_PROC_ID"])
    nprocs = int(os.environ["ZOO_TPU_NPROCS"])
    target = os.environ["ZOO_TPU_TARGET"]
    args = json.loads(os.environ.get("ZOO_TPU_ARGS", "[]"))
    platform = os.environ.get("ZOO_TPU_PLATFORM", "")
    dev_per_proc = os.environ.get("ZOO_TPU_DEVICES_PER_PROC", "")
    coord_file = os.environ.get("ZOO_TPU_COORD_FILE", "")
    coord = (read_coordinator(coord_file) if coord_file
             else os.environ["ZOO_TPU_COORD"])

    if dev_per_proc:
        # replace (not append) any inherited device-count flag — e.g. the
        # test harness exports an 8-device one; the last flag would win but
        # being explicit avoids depending on parser ordering
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        flags.append(f"--xla_force_host_platform_device_count={dev_per_proc}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    lease_spec = os.environ.get("ZOO_TPU_LEASE_STORE", "")
    if lease_spec:
        # membership lease: start heartbeating BEFORE the distributed
        # join so even a hang inside initialize() shows up as a frozen
        # lease. (Must run after the XLA_FLAGS mutation above — the
        # supervisor module's import chain pulls in jax.)
        from .supervisor import LeaseHeartbeat, make_lease_store
        hb_s = os.environ.get("ZOO_TPU_HEARTBEAT_S", "")
        LeaseHeartbeat(
            make_lease_store(lease_spec), rank=proc_id,
            generation=int(os.environ.get("ZOO_TPU_GENERATION", "0")),
            heartbeat_s=float(hb_s) if hb_s else None).start()
    import jax
    if platform:
        # the launcher's choice wins over an inherited JAX_PLATFORMS
        jax.config.update("jax_platforms", platform)
    if platform == "cpu":
        # XLA:CPU executes multi-process programs only through a cross-
        # process collectives layer; jaxlib ships gloo but defaults it off,
        # which surfaces as "Multiprocess computations aren't implemented
        # on the CPU backend" at the first sharded device_put
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs, process_id=proc_id)
    fn = resolve_target(target)
    try:
        result = fn(*args)
    except Exception:
        # Die NOW, not after interpreter teardown: the jax.distributed
        # atexit shutdown barrier cannot complete while peers sit in the
        # collective this rank just abandoned, and the launcher's failure
        # detection only fires once this process is actually dead.
        import traceback
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    if isinstance(result, int):
        return result
    return 0


if __name__ == "__main__":
    sys.exit(main())
