"""TPU context initialization — the ``init_nncontext`` equivalent.

The reference boots a SparkContext + BigDL engine (``NNContext.initNNContext``,
``zoo/.../common/NNContext.scala:133``; Python ``pyzoo/zoo/common/nncontext.py:109``).
On TPU there is no JVM and no Spark: "context" means the JAX runtime, the device
mesh (ICI topology within a slice, DCN across slices), process/host identity, and
a deterministic RNG root. ``init_tpu_context()`` discovers all of that once and
caches it process-wide, exactly as ``init_nncontext`` memoizes the SparkContext.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from . import metrics as _metrics
from . import utils as _utils
from .config import global_config

logger = logging.getLogger("analytics_zoo_tpu")


@dataclass
class ZooTpuContext:
    """Process-wide runtime context (the NNContext equivalent)."""

    mesh: Mesh
    devices: Sequence[jax.Device]
    process_index: int
    process_count: int
    platform: str
    config: Dict[str, object] = field(default_factory=dict)

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    @property
    def data_axis(self) -> str:
        return self.mesh.axis_names[0]

    @property
    def model_axis(self) -> Optional[str]:
        return self.mesh.axis_names[1] if len(self.mesh.axis_names) > 1 else None

    def local_batch(self, global_batch: int) -> int:
        """Per-process share of a global batch (reference: global batch =
        nodes x cores x per-core batch, ``Topology.scala:1110-1119``)."""
        if global_batch % self.process_count != 0:
            raise ValueError(
                f"global batch {global_batch} not divisible by process count "
                f"{self.process_count}")
        return global_batch // self.process_count


_context_lock = threading.Lock()
_context: Optional[ZooTpuContext] = None
_cache_wired: bool = False
_compiles_counted: bool = False

#: what JAX reports of its own compiles (``jax.monitoring``), counted from
#: :func:`wire_compilation_cache` on. A program that XLA builds and one that
#: is read back from the persistent cache both leave a ``compile.backend``
#: span: either costs time where it happens.
_M_CACHE_HITS = _metrics.counter(
    "compile.cache_hits_total",
    "Compiled programs read back from the persistent compilation cache.")
_M_CACHE_MISSES = _metrics.counter(
    "compile.cache_misses_total",
    "Programs the persistent compilation cache did not hold, compiled by "
    "XLA and written to it.")
_M_BACKEND_COMPILE = _metrics.histogram(
    "compile.backend_seconds",
    "Time to get one executable from the backend: XLA's compile, or the "
    "read from the persistent cache in its place.")
_CACHE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": _M_CACHE_HITS,
    "/jax/compilation_cache/cache_misses": _M_CACHE_MISSES,
}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _on_compile_event(event: str, **_) -> None:
    counter = _CACHE_COUNTERS.get(event)
    if counter is not None:
        counter.inc()


def _on_compile_duration(event: str, seconds: float, **more) -> None:
    if event != _BACKEND_COMPILE:
        return
    _M_BACKEND_COMPILE.observe(seconds)
    if _utils.span_hooks:
        # JAX reports a duration once it is over: the span ends now, on
        # the compiling thread, so the block that is open there (a join,
        # a decode dispatch) is its parent; what JAX says beside the
        # duration (``fun_name``, the program's name) rides as its args
        _utils.offer_span("compile.backend", time.perf_counter() - seconds,
                          seconds, args=tuple(more.items()))


#: where the persistent compilation cache lives when nothing outside the
#: program places it: one fixed directory inside the checkout (git-ignored).
#: The directory is part of every cache key, so it is never built from a
#: temp name, a pid or a time — a cache that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _count_compiles() -> None:
    """Register the listeners behind the ``compile.*`` metrics, once a
    process whatever happens to the cache's wiring: a second registration
    would count every compile twice."""
    global _compiles_counted
    if not _compiles_counted:
        jax.monitoring.register_event_listener(_on_compile_event)
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        _compiles_counted = True


def wire_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed from
    outside: JAX reads the variable itself and this program sets no
    directory in code. Where it is not, the cache goes to
    ``DEFAULT_COMPILE_CACHE_DIR``. Idempotent. Called from context init
    (training) and ``InferenceModel`` construction (serving — which may
    never init a mesh context): a process restart then deserializes
    yesterday's XLA programs from disk instead of recompiling. The
    min-size/min-compile-time thresholds drop to zero so small serving
    programs are cached too (JAX's defaults only persist big, slow
    compiles). The names of a program's operations (``jax.named_scope``
    paths) become part of its key, so that what a device trace names is
    the code that ran; source lines and call stacks are kept out of the
    program, and so out of the key: an edit that only moves lines, another
    entry script or another checkout compiles nothing anew. The first call
    also starts counting JAX's compiles (the ``compile.*`` metrics and the
    ``compile.backend`` span)."""
    global _cache_wired
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not _cache_wired:
        if not placed:
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_COMPILE_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # the cache's key leaves out a program's debug information unless
        # told otherwise, and the executable it hands back carries the
        # ``jax.named_scope`` paths of whatever code compiled it first: a
        # device trace would then show yesterday's names. All of the debug
        # information enters the key or none, so the part that changes with
        # every edit (the Python call stack of each operation, ten frames
        # of file and line) is left out of the program: what stays is the
        # scope path and the primitive of each operation
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        jax.config.update("jax_traceback_in_locations_limit", 0)
        _count_compiles()
        _cache_wired = True
        logger.info("persistent compilation cache: %s (%s)",
                    placed or DEFAULT_COMPILE_CACHE_DIR,
                    "JAX_COMPILATION_CACHE_DIR" if placed else "in-tree")
    return placed or DEFAULT_COMPILE_CACHE_DIR


def _build_mesh(devices: Sequence[jax.Device],
                mesh_shape: Optional[Tuple[int, ...]] = None,
                axis_names: Optional[Tuple[str, ...]] = None) -> Mesh:
    cfg = global_config()
    if axis_names is None:
        if mesh_shape is None or len(mesh_shape) == 1:
            axis_names = (cfg.get("mesh.data_axis"),)
        else:
            axis_names = tuple(
                [cfg.get("mesh.data_axis"), cfg.get("mesh.model_axis")]
                + [f"axis{i}" for i in range(2, len(mesh_shape))])
    if mesh_shape is None:
        mesh_shape = (len(devices),)
    n = int(np.prod(mesh_shape))
    if n != len(devices):
        raise ValueError(f"mesh shape {mesh_shape} needs {n} devices, "
                         f"have {len(devices)}")
    dev_array = np.asarray(devices).reshape(mesh_shape)
    return Mesh(dev_array, axis_names)


def init_tpu_context(mesh_shape: Optional[Tuple[int, ...]] = None,
                     axis_names: Optional[Tuple[str, ...]] = None,
                     conf: Optional[Dict[str, object]] = None,
                     force_reinit: bool = False) -> ZooTpuContext:
    """Initialize (or fetch the cached) runtime context.

    Args:
      mesh_shape: optional logical mesh shape over all addressable devices,
        e.g. ``(8,)`` for pure DP or ``(4, 2)`` for DP x MP. Defaults to a 1-D
        data-parallel mesh over every device.
      axis_names: names for the mesh axes; default ``("data",)`` /
        ``("data", "model", ...)``.
      conf: programmatic config overrides applied to the global registry
        (the ``init_spark_conf`` analogue).
      force_reinit: rebuild even if a context exists (tests only).
    """
    global _context
    with _context_lock:
        if _context is not None and not force_reinit:
            if mesh_shape is not None and tuple(_context.mesh.devices.shape) != tuple(mesh_shape):
                raise ValueError(
                    f"context already initialized with mesh shape "
                    f"{tuple(_context.mesh.devices.shape)}; requested {tuple(mesh_shape)}. "
                    f"Pass force_reinit=True to rebuild.")
            if conf:
                cfg = global_config()
                for k, v in conf.items():
                    cfg.set(k, v)
                _context.config = cfg.as_dict()
            return _context
        cfg = global_config()
        if conf:
            for k, v in conf.items():
                cfg.set(k, v)
        wire_compilation_cache()
        devices = jax.devices()
        mesh = _build_mesh(devices, mesh_shape, axis_names)
        ctx = ZooTpuContext(
            mesh=mesh,
            devices=devices,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            platform=devices[0].platform,
            config=cfg.as_dict(),
        )
        logger.info(
            "init_tpu_context: platform=%s devices=%d mesh=%s process=%d/%d",
            ctx.platform, ctx.num_devices, dict(zip(mesh.axis_names, mesh.devices.shape)),
            ctx.process_index, ctx.process_count)
        _context = ctx
        return ctx


def get_context() -> ZooTpuContext:
    if _context is None:
        return init_tpu_context()
    return _context


def reset_context() -> None:
    """Drop the cached context (tests only)."""
    global _context
    with _context_lock:
        _context = None
