"""Sharded sparse-embedding engine: all-to-all lookup, segment-sum grads.

The reference pushes embedding tables through the same dense AllReduce as
every other parameter; a "millions of users" vocabulary neither fits HBM
replicated nor trains faster than its dense allreduce. This module shards
the VOCAB axis of a table over a mesh axis and keeps every step sparse:

* **forward** — dedup the local ids (``jnp.unique`` with a static size),
  route each unique id to its owning shard with one ``lax.all_to_all``,
  gather locally, and reverse-exchange the rows. Cost is
  O(ids x dim) exchange bytes, never O(vocab).
* **backward** — a ``custom_vjp`` whose backward ``segment_sum``s the
  output cotangent per unique id, reverse-exchanges the per-unique grads,
  and scatter-adds into *only the touched rows of the local shard*. The
  table cotangent is a GSPMD vocab-sharded array (its aval must match the
  table's), but it is never densified per-id (no one-hot), never
  replicated and never all-reduced.
* **update** — ``apply_row_update`` mirrors the exact optax arithmetic
  (sgd / adagrad / lazy adam) on the touched rows only, so optimizer
  state for untouched rows is neither read nor written.
* **cold tier** — ``HostColdTier`` keeps the coldest rows in a host-DRAM
  shared-memory slab (same machinery as ``feature/worker_pool.py``),
  served through ``pure_callback`` and trained with an eager host-side
  SGD in the backward callback.

The table is sharded over the SAME mesh axis the batch rides (the data
axis by default): each device requests rows for its own batch shard, so
the backward needs no cross-replica psum at all — every device's
scatter-add is complete for its shard once the grad exchange lands.

See docs/embeddings.md for the layout, parity and cold-tier contracts.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..common import metrics as _embed_metrics
from ..common.config import global_config

_M_OOB = _embed_metrics.counter(
    "embed.oob_ids_total",
    "Out-of-range embedding ids clamped by the data.validate_ids=count "
    "policy (keras/layers/embedding.py lookups).")
_M_EXCHANGE = _embed_metrics.counter(
    "embed.exchange_bytes_total",
    "Bytes moved by the sharded-lookup all-to-all exchanges (request ids "
    "+ gathered rows, summed over devices), attributed per train step "
    "from the traced program.")
_M_GRAD = _embed_metrics.counter(
    "embed.grad_bytes_total",
    "Bytes moved by the sharded embedding BACKWARD exchange (per-unique "
    "segment-sum grads, summed over devices), attributed per train step "
    "from the traced program.")
_M_COLD_HITS = _embed_metrics.counter(
    "embed.cold_hits_total",
    "Embedding ids served from the host-DRAM cold tier.")
_M_COLD_BYTES = _embed_metrics.gauge(
    "embed.cold_bytes",
    "Total host-DRAM shared-memory bytes held by live cold tiers.")
_M_TABLE_BYTES = _embed_metrics.gauge(
    "embed.table_bytes",
    "Total GLOBAL bytes of sharded embedding tables (padded vocab x dim; "
    "per-device HBM share is this / shard count).")

#: model-state key prefix under which layers stash the forward exchange
#: blob ("rows") so the estimator's sparse update can reuse the routing
#: without a second all-to-all. Stripped from the state tree by
#: ``pop_stashed_rows`` before the state is carried across steps.
ROWS_PREFIX = "__embed_rows__"

# ---------------------------------------------------------------------------
# default mesh plumbing

_DEFAULT_MESH: Optional[Mesh] = None


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    """Install the mesh layers shard against when they build outside an
    explicit mesh context (the estimator calls this with its own mesh)."""
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def default_mesh() -> Optional[Mesh]:
    if _DEFAULT_MESH is not None:
        return _DEFAULT_MESH
    try:
        from ..common.context import get_context
        return get_context().mesh
    except Exception:
        return None


# ---------------------------------------------------------------------------
# shard spec

@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Static description of one vocab-sharded table (hashable: it rides
    as a ``custom_vjp`` nondiff argument and inside jit closures)."""
    mesh: Mesh
    axis: str            # mesh axis the vocab (and the ids) shard over
    shards: int          # mesh.shape[axis]
    rows_per_shard: int  # padded vocab / shards
    vocab: int           # logical (unpadded) hot vocab
    dim: int

    @property
    def padded(self) -> int:
        """Padded vocab; also the SENTINEL id: it routes to the last
        shard with an out-of-range local row, so gathers fill zeros and
        gradient scatters drop."""
        return self.shards * self.rows_per_shard

    @property
    def table_bytes(self) -> int:
        return self.padded * self.dim * 4

    @property
    def device_bytes(self) -> int:
        return self.rows_per_shard * self.dim * 4


def make_shard_spec(vocab: int, dim: int, mesh: Optional[Mesh] = None,
                    axis: Optional[str] = None) -> Optional[ShardSpec]:
    """Build a ShardSpec for a table, or None when there is nothing to
    shard over (no mesh, or a single-device axis)."""
    mesh = mesh if mesh is not None else default_mesh()
    if mesh is None:
        return None
    if axis is None:
        from .mesh import embedding_axis
        axis = embedding_axis(mesh)
    if axis not in mesh.axis_names:
        return None
    shards = int(mesh.shape[axis])
    if shards <= 1:
        return None
    rps = -(-int(vocab) // shards)  # ceil
    return ShardSpec(mesh=mesh, axis=axis, shards=shards,
                     rows_per_shard=rps, vocab=int(vocab), dim=int(dim))


def can_run(spec: Optional[ShardSpec], n_ids: int) -> bool:
    """The sharded path needs the flat id count divisible by the shard
    count (ids ride the same axis); otherwise callers fall back to the
    dense gather, which computes identical values."""
    return (spec is not None and spec.shards > 1
            and n_ids >= spec.shards and n_ids % spec.shards == 0)


# ---------------------------------------------------------------------------
# trace-time byte attribution (read by the estimator around compilation)

_TRACE_BYTES = {"exchange": 0, "grad": 0}


def reset_trace_bytes() -> None:
    _TRACE_BYTES["exchange"] = 0
    _TRACE_BYTES["grad"] = 0


def take_trace_bytes() -> Tuple[int, int]:
    ex, gr = _TRACE_BYTES["exchange"], _TRACE_BYTES["grad"]
    reset_trace_bytes()
    return ex, gr


def note_exchange_bytes(ex: int, gr: int) -> None:
    """Host-side per-step counter feed (the estimator calls this once per
    dispatched step with the trace-attributed byte totals)."""
    if ex:
        _M_EXCHANGE.inc(float(ex))
    if gr:
        _M_GRAD.inc(float(gr))


_TABLE_SIZES: Dict[str, int] = {}
_COLD_SIZES: Dict[str, int] = {}


def note_table_bytes(key: str, nbytes: int) -> None:
    _TABLE_SIZES[key] = int(nbytes)
    _M_TABLE_BYTES.set(float(sum(_TABLE_SIZES.values())))


def _note_cold_bytes(key: str, nbytes: int) -> None:
    if nbytes:
        _COLD_SIZES[key] = int(nbytes)
    else:
        _COLD_SIZES.pop(key, None)
    _M_COLD_BYTES.set(float(sum(_COLD_SIZES.values())))


# ---------------------------------------------------------------------------
# id validation (satellite: no more silent OOB clamps)

def _note_oob(n) -> None:
    n = int(n)
    if n:
        _M_OOB.inc(n)


def validate_ids(idx, vocab: int, allow_negative: bool = False):
    """Apply the ``data.validate_ids`` policy to a raw id array.

    * ``clamp``: the historical silent ``jnp.take`` clip.
    * ``count`` (default): clamp, but count offenders into
      ``embed.oob_ids_total`` (async debug callback — no dispatch stall).
    * ``raise``: raise ValueError when the ids are concrete (eager layer
      calls, i.e. unit tests); degrades to ``count`` under jit where a
      Python raise cannot see values.

    ``allow_negative`` keeps negative ids intact (SparseEmbedding /
    SparseDense use them as padding and mask them downstream); only the
    upper bound is then validated.
    """
    mode = str(global_config().get("data.validate_ids"))
    if mode not in ("clamp", "count", "raise"):
        raise ValueError(f"data.validate_ids={mode!r}: expected "
                         f"'clamp', 'count' or 'raise'")
    if allow_negative:
        clamped = jnp.minimum(idx, vocab - 1)
        if mode == "clamp":
            return clamped
        bad = idx >= vocab
    else:
        clamped = jnp.clip(idx, 0, vocab - 1)
        if mode == "clamp":
            return clamped
        bad = (idx < 0) | (idx >= vocab)
    n_bad = jnp.sum(bad)
    if mode == "raise" and not isinstance(n_bad, jax.core.Tracer):
        count = int(n_bad)
        if count:
            raise ValueError(
                f"{count} embedding id(s) out of range [0, {vocab}) "
                f"(data.validate_ids=raise)")
        return clamped
    jax.debug.callback(_note_oob, n_bad)
    return clamped


# ---------------------------------------------------------------------------
# per-shard bodies (module-level: policed by scripts/check_hot_path_syncs.py
# — no densified one-hot, no per-row Python loops, no host syncs)

def fused_kernels():
    """Trace-time resolution of the fused local-compute kernels
    (``ops/embedding_kernels.py``). Returns the module when the
    ``kernels.fused_embedding`` knob is on, else None — callers then trace
    the inline lax ops below, the bit-parity reference. The fused CPU path
    traces the SAME ops in the same order, so toggling the knob off-TPU is
    a jaxpr no-op (tests/test_fused_embedding.py pins this bitwise)."""
    if not global_config().get("kernels.fused_embedding"):
        return None
    from ..ops import embedding_kernels as _ek
    return _ek


def _routing(spec, ids):
    """Shared dedup-unique routing: sorted uniques, owning shard, and the
    (destination, slot) address of each unique in the request matrix."""
    n = ids.shape[0]
    u, inv = jnp.unique(ids, size=n, fill_value=spec.padded,
                        return_inverse=True)
    d = jnp.minimum(u // spec.rows_per_shard, spec.shards - 1)
    d = d.astype(jnp.int32)
    local_row = (u - d * spec.rows_per_shard).astype(jnp.int32)
    starts = jnp.searchsorted(d, jnp.arange(spec.shards, dtype=jnp.int32))
    slot = jnp.arange(n, dtype=jnp.int32) - starts[d].astype(jnp.int32)
    return u, inv.ravel(), d, local_row, slot


def _lookup_body(spec, tshard, ids):
    """Per-device forward: unique -> all-to-all id exchange -> local
    gather -> reverse row exchange -> undup. ``recv`` (the local rows
    other shards requested from us, SENTINEL-marked with rows_per_shard)
    is returned so backward and the sparse update reuse the routing."""
    n = ids.shape[0]
    _u, inv, d, local_row, slot = _routing(spec, ids)
    req = jnp.full((spec.shards, n), spec.rows_per_shard, dtype=jnp.int32)
    req = req.at[d, slot].set(local_row)
    recv = lax.all_to_all(req, spec.axis, split_axis=0, concat_axis=0,
                          tiled=True)
    ek = fused_kernels()
    if ek is not None:
        # fused local gather (pallas row-DMA kernel on TPU; identical
        # fill-mode take elsewhere)
        rows = ek.gather_rows(tshard, recv.ravel())
    else:
        rows = jnp.take(tshard, recv.ravel(), axis=0, mode="fill",
                        fill_value=0)
    back = lax.all_to_all(rows.reshape(spec.shards, n, spec.dim), spec.axis,
                          split_axis=0, concat_axis=0, tiled=True)
    out = jnp.take(back[d, slot], inv, axis=0)
    return out, recv


def _lookup_bwd_body(spec, g, ids, recv):
    """Per-device backward: segment-sum the cotangent per unique id,
    reverse-exchange the per-unique grads, scatter-add into only the
    touched rows of the local shard (SENTINEL rows drop)."""
    n = ids.shape[0]
    _u, inv, d, _local_row, slot = _routing(spec, ids)
    ek = fused_kernels()
    if ek is not None:
        # fused segment-sum straight into the request-shaped buffer, and
        # (post-exchange) a fused scatter-add into the row-subset
        # cotangent — [rows_per_shard, dim], never a dense [vocab, dim]
        g_req = ek.segment_grads(g, inv, d, slot, spec.shards)
    else:
        g_u = jax.ops.segment_sum(g, inv, num_segments=n)
        g_req = jnp.zeros((spec.shards, n, spec.dim),
                          g.dtype).at[d, slot].set(g_u)
    g_recv = lax.all_to_all(g_req, spec.axis, split_axis=0, concat_axis=0,
                            tiled=True)
    if ek is not None:
        return ek.scatter_rows(g_recv.reshape(spec.shards * n, spec.dim),
                               recv.ravel(), spec.rows_per_shard)
    ct = jnp.zeros((spec.rows_per_shard, spec.dim), g.dtype)
    ct = ct.at[recv.ravel()].add(g_recv.reshape(spec.shards * n, spec.dim),
                                 mode="drop")
    return ct


def _update_body(kind, hyper, spec, tshard, gshard, recv, *opt):
    """Per-device sparse row-subset optimizer update. Gathers ONLY the
    rows other shards touched this step (``recv``), applies the exact
    optax arithmetic for ``kind``, and scatters the rows back with
    mode=drop (SENTINEL markers vanish; duplicate requests of one row
    read the same summed grad and write identical values)."""
    flat = recv.ravel()
    t_rows = jnp.take(tshard, flat, axis=0, mode="fill", fill_value=0)
    g_rows = jnp.take(gshard, flat, axis=0, mode="fill", fill_value=0)
    lr = hyper["lr"]
    if kind == "sgd":
        # optax.sgd: u = (-lr) * g; p' = (p + u).astype(p.dtype)
        new_rows = (t_rows + (-lr) * g_rows).astype(tshard.dtype)
        return (tshard.at[flat].set(new_rows, mode="drop"),)
    if kind == "adagrad":
        # optax.scale_by_rss: acc' = g^2 + acc; u = rsqrt(acc' + eps) * g
        acc = opt[0]
        acc_rows = jnp.take(acc, flat, axis=0, mode="fill", fill_value=0)
        nu = g_rows * g_rows + acc_rows
        inv_rt = jnp.where(nu > 0, lax.rsqrt(nu + hyper["eps"]),
                           jnp.zeros_like(nu))
        new_rows = (t_rows + (-lr) * (inv_rt * g_rows)).astype(tshard.dtype)
        return (tshard.at[flat].set(new_rows, mode="drop"),
                acc.at[flat].set(nu.astype(acc.dtype), mode="drop"))
    # lazy adam: touched-row moments, global step count (documented as NOT
    # bit-identical to dense adam — stale-row bias correction differs)
    mu, nu, count = opt
    b1, b2 = hyper["b1"], hyper["b2"]
    mu_rows = jnp.take(mu, flat, axis=0, mode="fill", fill_value=0)
    nu_rows = jnp.take(nu, flat, axis=0, mode="fill", fill_value=0)
    new_mu = (1.0 - b1) * g_rows + b1 * mu_rows
    new_nu = (1.0 - b2) * (g_rows * g_rows) + b2 * nu_rows
    new_count = jnp.where(count < jnp.iinfo(jnp.int32).max, count + 1, count)
    c = new_count.astype(g_rows.dtype)
    mu_hat = new_mu / (1.0 - b1 ** c)
    nu_hat = new_nu / (1.0 - b2 ** c)
    step = (-lr) * (mu_hat / (jnp.sqrt(nu_hat) + hyper["eps"]))
    new_rows = (t_rows + step).astype(tshard.dtype)
    return (tshard.at[flat].set(new_rows, mode="drop"),
            mu.at[flat].set(new_mu.astype(mu.dtype), mode="drop"),
            nu.at[flat].set(new_nu.astype(nu.dtype), mode="drop"),
            new_count)


# ---------------------------------------------------------------------------
# lookup: custom_vjp over the shard_map'd bodies

def _lookup_impl(table, ids, spec):
    n_loc = ids.shape[0] // spec.shards
    _TRACE_BYTES["exchange"] += spec.shards * 2 * spec.shards * n_loc * (
        4 + spec.dim * table.dtype.itemsize)
    out, recv = shard_map(
        partial(_lookup_body, spec), mesh=spec.mesh,
        in_specs=(P(spec.axis, None), P(spec.axis)),
        out_specs=(P(spec.axis, None), P(spec.axis, None)))(table, ids)
    return out, recv


def _grad_impl(g, ids, recv, spec):
    n_loc = ids.shape[0] // spec.shards
    _TRACE_BYTES["grad"] += (spec.shards * 2 * spec.shards * n_loc
                             * spec.dim * 4)
    return shard_map(
        partial(_lookup_bwd_body, spec), mesh=spec.mesh,
        in_specs=(P(spec.axis, None), P(spec.axis), P(spec.axis, None)),
        out_specs=P(spec.axis, None))(g, ids, recv)


def _int_zeros(x):
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def sharded_lookup(table, flat_ids, spec):
    """Gather ``table[flat_ids]`` from a vocab-sharded ``[padded, dim]``
    table. Returns ``(rows [n, dim], recv_blob)``; the blob is the
    per-shard touched-row routing, opaque outside this module — feed it
    back to ``apply_row_update``. ids == ``spec.padded`` (SENTINEL) read
    zero rows and receive no gradient."""
    return _lookup_impl(table, flat_ids, spec)


def _lookup_fwd(table, flat_ids, spec):
    out, recv = _lookup_impl(table, flat_ids, spec)
    return (out, recv), (flat_ids, recv)


def _lookup_bwd(spec, res, cts):
    flat_ids, recv = res
    g_out, _g_recv = cts
    ct_table = _grad_impl(g_out, flat_ids, recv, spec)
    return ct_table, _int_zeros(flat_ids)


sharded_lookup.defvjp(_lookup_fwd, _lookup_bwd)


# ---------------------------------------------------------------------------
# sparse row-subset optimizer update

def init_row_state(kind: str, table) -> Dict[str, Any]:
    """Row-wise optimizer state for one sharded table, mirroring the
    corresponding optax init (adagrad: initial_accumulator_value=0.1)."""
    if kind == "sgd":
        return {}
    if kind == "adagrad":
        return {"acc": jnp.full_like(table, 0.1)}
    if kind == "adam":
        return {"mu": jnp.zeros_like(table), "nu": jnp.zeros_like(table),
                "count": jnp.zeros((), jnp.int32)}
    raise ValueError(f"no sparse row update for optimizer kind {kind!r}")


def apply_row_update(kind: str, hyper: Dict[str, float], spec: ShardSpec,
                     table, grad_ct, rows_blob, row_state):
    """Update only the touched rows of one sharded table (and their
    optimizer state) from the dense-but-sharded cotangent + the forward
    exchange blob. Returns ``(new_table, new_row_state)``."""
    spec2 = P(spec.axis, None)
    if kind == "sgd":
        (new_table,) = shard_map(
            partial(_update_body, kind, hyper, spec), mesh=spec.mesh,
            in_specs=(spec2, spec2, spec2), out_specs=(spec2,))(
            table, grad_ct, rows_blob)
        return new_table, {}
    if kind == "adagrad":
        new_table, acc = shard_map(
            partial(_update_body, kind, hyper, spec), mesh=spec.mesh,
            in_specs=(spec2, spec2, spec2, spec2),
            out_specs=(spec2, spec2))(
            table, grad_ct, rows_blob, row_state["acc"])
        return new_table, {"acc": acc}
    if kind == "adam":
        new_table, mu, nu, count = shard_map(
            partial(_update_body, kind, hyper, spec), mesh=spec.mesh,
            in_specs=(spec2, spec2, spec2, spec2, spec2, P()),
            out_specs=(spec2, spec2, spec2, P()))(
            table, grad_ct, rows_blob, row_state["mu"], row_state["nu"],
            row_state["count"])
        return new_table, {"mu": mu, "nu": nu, "count": count}
    raise ValueError(f"no sparse row update for optimizer kind {kind!r}")


def apply_dense_update(kind: str, hyper: Dict[str, float], table, grad,
                       row_state):
    """Fallback when a step produced no exchange blob (the lookup fell back
    to the dense gather): the same optimizer arithmetic as
    ``apply_row_update`` applied to every row. Elementwise, so GSPMD keeps
    the table's vocab sharding; zero-grad rows are bitwise no-ops for
    sgd/adagrad."""
    lr = hyper["lr"]
    if kind == "sgd":
        return (table + (-lr) * grad).astype(table.dtype), {}
    if kind == "adagrad":
        acc = row_state["acc"]
        nu = grad * grad + acc
        inv_rt = jnp.where(nu > 0, lax.rsqrt(nu + hyper["eps"]),
                           jnp.zeros_like(nu))
        return ((table + (-lr) * (inv_rt * grad)).astype(table.dtype),
                {"acc": nu.astype(acc.dtype)})
    if kind == "adam":
        mu, nu, count = row_state["mu"], row_state["nu"], row_state["count"]
        b1, b2 = hyper["b1"], hyper["b2"]
        new_mu = (1.0 - b1) * grad + b1 * mu
        new_nu = (1.0 - b2) * (grad * grad) + b2 * nu
        new_count = jnp.where(count < jnp.iinfo(jnp.int32).max,
                              count + 1, count)
        c = new_count.astype(grad.dtype)
        mu_hat = new_mu / (1.0 - b1 ** c)
        nu_hat = new_nu / (1.0 - b2 ** c)
        step = (-lr) * (mu_hat / (jnp.sqrt(nu_hat) + hyper["eps"]))
        return ((table + step).astype(table.dtype),
                {"mu": new_mu.astype(mu.dtype), "nu": new_nu.astype(nu.dtype),
                 "count": new_count})
    raise ValueError(f"no sparse row update for optimizer kind {kind!r}")


def pop_stashed_rows(model_state):
    """Split the exchange blobs layers stashed under ``ROWS_PREFIX`` out
    of a model-state tree. Returns ``({layer: {param_key: blob}},
    cleaned_state)`` — cleaned_state drops layer entries emptied by the
    pop so the carried state keeps the init-time tree structure."""
    if not isinstance(model_state, dict):
        return {}, model_state
    rows: Dict[str, Dict[str, Any]] = {}
    clean = {}
    for lname, sub in model_state.items():
        if not isinstance(sub, dict):
            clean[lname] = sub
            continue
        keep = {}
        for k, v in sub.items():
            if isinstance(k, str) and k.startswith(ROWS_PREFIX):
                rows.setdefault(lname, {})[k[len(ROWS_PREFIX):]] = v
            else:
                keep[k] = v
        if keep:
            clean[lname] = keep
    return rows, clean


# ---------------------------------------------------------------------------
# host-DRAM cold tier

class HostColdTier:
    """Host-resident tail of an embedding table, in a shared-memory slab
    (same machinery as feature/worker_pool.py so other local processes
    could map it). Rows are served into the jitted forward through
    ``pure_callback`` and trained with an eager SGD inside an ordered
    ``io_callback`` — no device HBM, no optimizer state on device.

    Single-process scope: multi-host training with a cold tier is not
    supported (the slab lives in one host's DRAM).
    """

    _ALIGN = 128

    def __init__(self, rows: int, dim: int, name: str = "cold",
                 lr: Optional[float] = None):
        from multiprocessing import shared_memory
        self.rows = int(rows)
        self.dim = int(dim)
        self.name = name
        self.lr = float(global_config().get("embed.cold_lr")
                        if lr is None else lr)
        nbytes = self.rows * self.dim * 4
        slab = ((nbytes + self._ALIGN - 1) // self._ALIGN) * self._ALIGN
        self._shm = shared_memory.SharedMemory(create=True,
                                               size=max(slab, self._ALIGN))
        self.view = np.ndarray((self.rows, self.dim), dtype=np.float32,
                               buffer=self._shm.buf)
        self.view[:] = 0.0
        self._closed = False
        _note_cold_bytes(self._shm.name, self._shm.size)

    # identity hash/eq (object defaults) — the tier is a custom_vjp
    # nondiff argument and must stay hashable despite the mutable slab

    @property
    def nbytes(self) -> int:
        return self.rows * self.dim * 4

    def fill(self, values) -> None:
        self.view[:] = np.asarray(values, dtype=np.float32)

    def fetch(self, rel_ids) -> np.ndarray:
        """Rows for relative ids; negatives / out-of-range return zeros
        (non-cold positions are masked to -1 by the caller)."""
        rel = np.asarray(rel_ids).ravel()
        ok = (rel >= 0) & (rel < self.rows)
        out = np.zeros((rel.shape[0], self.dim), dtype=np.float32)
        if ok.any():
            out[ok] = self.view[rel[ok]]
            _M_COLD_HITS.inc(int(ok.sum()))
        return out

    def apply_grad(self, rel_ids, g) -> None:
        rel = np.asarray(rel_ids).ravel()
        ok = (rel >= 0) & (rel < self.rows)
        if ok.any():
            np.add.at(self.view, rel[ok],
                      (-self.lr) * np.asarray(g)[ok].astype(np.float32))

    def save(self, path: str) -> None:
        np.save(path, self.view)

    def load(self, path: str) -> None:
        self.view[:] = np.load(path).astype(np.float32)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        _note_cold_bytes(self._shm.name, 0)
        self.view = None
        try:
            self._shm.close()
            self._shm.unlink()
        except Exception:
            pass

    def __del__(self):  # best-effort slab reclaim
        try:
            self.close()
        except Exception:
            pass


def _cold_fetch_impl(tier, rel_ids):
    n = rel_ids.shape[0]
    return jax.pure_callback(
        tier.fetch, jax.ShapeDtypeStruct((n, tier.dim), jnp.float32),
        rel_ids)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def cold_lookup(tier, rel_ids, anchor):
    """Host-DRAM gather: rows for relative cold ids (-1 = not cold ->
    zero row, no gradient). Backward applies an eager host-side SGD to
    the slab (ordered io_callback), so cold rows train without device
    memory or device optimizer state.

    ``anchor`` must be a (cheap, e.g. scalar) value derived from the
    differentiated parameters: without it the autodiff graph has no path
    from the loss inputs through this call, and JAX prunes the backward
    (the cold rows would silently never train). Its cotangent is zero.
    """
    del anchor
    return _cold_fetch_impl(tier, rel_ids)


def _cold_fwd(tier, rel_ids, anchor):
    return _cold_fetch_impl(tier, rel_ids), (rel_ids, anchor)


def _cold_bwd(tier, res, g):
    from jax.experimental import io_callback
    rel_ids, anchor = res
    io_callback(tier.apply_grad, None, rel_ids, g, ordered=True)
    return _int_zeros(rel_ids), jnp.zeros_like(anchor)


cold_lookup.defvjp(_cold_fwd, _cold_bwd)


def exchange_cost_bytes(spec: ShardSpec, n_ids: int) -> Dict[str, float]:
    """Analytic per-step exchange cost for one lookup+grad of ``n_ids``
    ids (for benches / docs — the runtime counters use the traced
    totals). All-device totals, forward ids+rows and backward grads."""
    n_loc = max(n_ids // spec.shards, 1)
    fwd = spec.shards * 2 * spec.shards * n_loc * (4 + spec.dim * 4)
    bwd = spec.shards * 2 * spec.shards * n_loc * spec.dim * 4
    return {"forward_bytes": float(fwd), "grad_bytes": float(bwd),
            "dense_grad_bytes": float(spec.padded * spec.dim * 4
                                      * math.prod(spec.mesh.devices.shape))}
