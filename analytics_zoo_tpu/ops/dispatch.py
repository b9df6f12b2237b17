"""Kernel-or-reference dispatch, decided at trace time and never silently.

Every pallas kernel in this package has a lax/XLA reference. Off the TPU
the reference is the only implementation and nothing is logged. ON the TPU
the kernel runs when its shape rule holds; when a rule fails the reference
runs instead, and :func:`note_fallback` logs that once per (kernel, rule)
so a "fused" call that quietly means the reference shows up in the log and
in ``chip_smoke.py``'s output (:func:`fallbacks_seen`).

More than one chip: JAX refuses to lower a Mosaic kernel inside a program
that is partitioned automatically over several devices ("Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a
shard_map"). Whoever owns the mesh a program is traced for (``Estimator``,
``TransformerLM``) therefore says so with :func:`partitioned_over`, and the
kernels' call sites run per shard through :func:`per_shard` — batch over
the ``data`` axis, heads over the tensor-parallel axis — or, where a
dimension does not divide (:func:`shard_rule`), take the reference with
the rule logged. With no scope, or a one-device mesh, nothing changes.
"""
from __future__ import annotations

import contextlib
import logging
import threading
from typing import Callable, List, Optional, Sequence, Set, Tuple

import jax
from jax.sharding import PartitionSpec as P

logger = logging.getLogger("analytics_zoo_tpu.ops")

_seen: Set[Tuple[str, str]] = set()
_scope = threading.local()

#: what a dimension of a kernel operand is, for :func:`per_shard`
BATCH, HEADS = "batch", "heads"


def on_tpu() -> bool:
    """Whether traced code lowers for a TPU. Does not catch: a backend
    that fails to initialise is an error, not a reason to take the
    reference path."""
    return jax.default_backend() == "tpu"


def note_fallback(kernel: str, rule: str) -> None:
    """Record (and log once) that ``kernel`` gave way to its reference on
    the TPU because ``rule`` did not hold."""
    if (kernel, rule) not in _seen:
        _seen.add((kernel, rule))
        logger.warning("%s: reference path on TPU — %s", kernel, rule)


def fallbacks_seen() -> List[Tuple[str, str]]:
    """Every (kernel, rule) noted so far in this process, sorted."""
    return sorted(_seen)


# -- programs partitioned over several devices --------------------------------

@contextlib.contextmanager
def partitioned_over(mesh):
    """Scope in which programs are traced for ``mesh`` (None: unknown, as
    outside any scope). Per thread, re-entrant."""
    stack = _scope.__dict__.setdefault("meshes", [])
    stack.append(mesh)
    try:
        yield
    finally:
        stack.pop()


def _partition_axes(per_shard_code: bool = False) -> Optional[dict]:
    """``{BATCH: (axis, size) | None, HEADS: ...}`` when kernels traced now
    have to be wrapped per shard, else None: no scope, a one-device mesh,
    or code that is already per-shard (inside a ``shard_map``).
    ``per_shard_code`` asks the opposite question, from inside
    :func:`per_shard`'s ``fn``."""
    stack = getattr(_scope, "meshes", None)
    mesh = stack[-1] if stack else None
    if mesh is None or mesh.size == 1:
        return None
    if bool(jax.sharding.get_abstract_mesh().manual_axes) != per_shard_code:
        return None
    from ..common.config import global_config
    names = {BATCH: "data",
             HEADS: str(global_config().get("parallel.tensor_axis"))}
    axes = {"mesh": mesh}
    for dim, name in names.items():
        axes[dim] = ((name, mesh.shape[name])
                     if name in mesh.axis_names and mesh.shape[name] > 1
                     else None)
    return axes


def partitioned() -> bool:
    """Whether kernels traced now sit in a program partitioned
    automatically over several devices (and so need :func:`per_shard`)."""
    return _partition_axes() is not None


def shards(dim: str) -> int:
    """How many ways :func:`per_shard` splits dimension ``dim`` (1 when no
    partitioning is in play)."""
    axes = _partition_axes()
    return 1 if axes is None or axes[dim] is None else axes[dim][1]


def shard_rule(batch: int, heads: Optional[int] = None) -> Optional[str]:
    """Why a kernel over ``batch`` (rows, or batch entries) and ``heads``
    cannot run per shard under the mesh in scope (None: it can, or no
    partitioning is in play)."""
    axes = _partition_axes()
    if axes is None:
        return None
    for dim, n in ((BATCH, batch), (HEADS, heads)):
        if axes[dim] is not None and n is not None and n % axes[dim][1]:
            name, size = axes[dim]
            return (f"{dim} {n} does not divide over mesh axis "
                    f"'{name}' of size {size}, and a Mosaic kernel cannot "
                    f"be partitioned automatically")
    return None


def per_shard(fn: Callable, args: Sequence, in_dims: Sequence,
              out_dims: Sequence):
    """``fn(*args)``, where ``fn`` invokes a Mosaic kernel. Under a
    several-device mesh in scope the call is wrapped in a ``shard_map``:
    ``in_dims``/``out_dims`` give, per operand and per result, a tuple
    naming each dimension ``BATCH``, ``HEADS`` or None (whole). The caller
    has checked :func:`shard_rule`."""
    axes = _partition_axes()
    if axes is None:
        return fn(*args)

    def spec(dims):
        return P(*(axes[d][0] if d is not None and axes[d] is not None
                   else None for d in dims))

    return jax.shard_map(
        fn, mesh=axes["mesh"],
        in_specs=tuple(spec(d) for d in in_dims),
        out_specs=(tuple(spec(d) for d in out_dims)
                   if isinstance(out_dims[0], tuple) else spec(out_dims)),
    )(*args)


def shard_index():
    """From inside :func:`per_shard`'s ``fn``: this shard's linear index
    over the axes operands are split on (0 where nothing is split)."""
    axes = _partition_axes(per_shard_code=True)
    index = 0
    for dim in (BATCH, HEADS):
        if axes is not None and axes[dim] is not None:
            name, size = axes[dim]
            index = index * size + jax.lax.axis_index(name)
    return index
