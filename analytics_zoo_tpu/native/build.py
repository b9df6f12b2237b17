"""On-demand compilation of the native components.

No build step at install time and no binary in the repository: the first
use compiles ``native/<name>.cpp`` with the system ``g++`` into
``native/lib<name>.so`` next to the source (git-ignored, rebuilt when the
source is newer), the way JAX itself JITs its kernels. Where that cannot be
done — no compiler, a read-only install — callers use their pure-Python
implementation, and the log says which one is active and why.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger("analytics_zoo_tpu")

_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_cache = {}


def _build(src: str, out: str) -> Optional[str]:
    """Compile ``src`` to ``out``; returns why it could not, else None.
    Builds beside the target and renames, so a process that starts while
    another one is building never loads half a library."""
    tmp = f"{out[:-3]}.{os.getpid()}.tmp.so"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, src]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ unavailable ({e})"
    if proc.returncode != 0:
        return f"g++ failed:\n{proc.stderr[-2000:]}"
    os.replace(tmp, out)
    return None


def load_library(name: str) -> Optional[ctypes.CDLL]:
    """Load (building if stale or missing) ``native/<name>.cpp`` as a CDLL.
    Returns None when it cannot be built or loaded — callers then use their
    pure-Python implementation. Either way one log line says which."""
    with _lock:
        if name in _cache:
            return _cache[name]
        src = os.path.join(_DIR, f"{name}.cpp")
        out = os.path.join(_DIR, f"lib{name}.so")
        if not os.path.exists(src):
            raise FileNotFoundError(src)
        why = None
        built = (not os.path.exists(out)
                 or os.path.getmtime(out) < os.path.getmtime(src))
        if built:
            why = _build(src, out)
        lib = None
        if why is None:
            try:
                lib = ctypes.CDLL(out)
            except OSError as e:
                why = f"could not load {out} ({e})"
        if lib is not None:
            logger.info("native %s: %s %s", name,
                        "built" if built else "loaded", out)
        else:
            logger.warning("native %s unavailable, pure-Python "
                           "implementation in use: %s", name, why)
        _cache[name] = lib
        return lib
