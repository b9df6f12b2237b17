"""Fused embedding kernels for the recsys hot path (NCF / Wide&Deep).

The gather/scatter-bound embedding path is the measured utilization floor
of the recommendation workloads (bench r02/r03: widedeep MFU 0.0001, ncf
0.0075 — judged by ``hbm_roofline_fraction``, not MFU, since the step does
almost no matmul work). This module collapses the per-table op chains into
single passes:

* **forward** — :func:`gather_pool`: table gather + padding mask + bag
  pooling (sum/mean/sqrtn) in one sweep; :func:`multi_table_lookup` runs
  every table of a tower in one traced call so XLA fuses the per-table
  chains and the feature concat into one dispatch (the unfused layer path
  materializes one intermediate per table).
* **backward** — :func:`segment_grads` + :func:`scatter_rows`: the fused
  segment-sum / scatter-add pair ``parallel/embedding.py`` runs after the
  gradient all-to-all. The cotangent stays the row-subset ``[rows_per_
  shard, dim]`` shard block the sparse row updates expect — never a dense
  ``[vocab, dim]`` materialization, never a one-hot matmul.
* **int8** — :func:`quantize_table` / :func:`gather_pool_int8`: tables
  live symmetric-int8 in HBM using the ``ops/int8_dataflow`` delayed-
  scaling recipe (same running-amax, same scale math), a quarter of the
  f32 table's bytes resident; rows dequantize in-kernel (TPU) or right at
  the gather (lax path). Bound: ``|deq - f32| <= scale / 2`` per element,
  ``<= bag * scale / 2`` after sum pooling (:func:`int8_error_bound`).

On TPU the per-row work runs as pallas kernels (scalar-prefetched ids
driving double-buffered row DMAs out of HBM, VMEM accumulators for the
pooling — see docs/embeddings.md "Fused kernels" for the tiling scheme)
where the TPU compiler accepts them: float32 tables of dim exactly 128,
int8 tables of dim % 128 (``_table_rule`` / ``_scatter_rule`` hold the
rules, tests/test_tpu_compile.py compiles each kernel for a v5e). At any
other shape on the TPU — which includes every width the repo's own recsys
models use (NCF 20..64, Wide&Deep 8) — and everywhere off it, the SAME
functions trace the exact lax ops of the historical unfused layers, in the
same order, so the fused path is bit-identical (f32) to the reference by
construction; tests/test_fused_embedding.py asserts that through real
Estimator training, sharded and unsharded. A kernel that gives way to the
lax path on the TPU says so once in the log (``ops/dispatch.py``).

Everything here is gated by the ``kernels.fused_embedding`` config knob
(docs/configuration.md); the unfused layer code stays in-tree as the
bit-parity reference. The per-row bodies below are policed by
``scripts/check_hot_path_syncs.py`` — no host syncs, no ``one_hot``
densification, no per-row Python loops.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import dispatch
from .int8_dataflow import (dequant_int8, next_amax, quant_int8,
                            scale_of_amax)

#: most rows gathered per pallas grid step; ``_gather_block`` clamps it
#: down to a divisor of the output row count at call time.
DEFAULT_GATHER_BLOCK = 256

#: pallas scatter-add keeps the incoming grads and the whole output shard
#: in VMEM; above this many bytes together the lax scatter (XLA's native
#: s32 scatter-add) runs instead.
SCATTER_VMEM_BYTES = 8 * 1024 * 1024

#: the ids of one call ride scalar prefetch, i.e. sit whole in the chip's
#: 1 MiB of scalar memory next to the compiler's own scalars; above this
#: many bytes of int32 ids the lax path runs instead.
PREFETCH_IDS_BYTES = 512 * 1024

#: int8 tables lie in HBM in (8, 128) tiles of 4-row-packed words, so the
#: smallest block a DMA can address is 8 whole rows.
INT8_ROW_TILE = 8


def fused_enabled() -> bool:
    """The ``kernels.fused_embedding`` config knob (True by default). Off
    means every caller traces the historical unfused op chain — the
    bit-parity reference the fused path is tested against."""
    from ..common.config import global_config
    return bool(global_config().get("kernels.fused_embedding"))


def _prefetch_rule(n_ids: int) -> Optional[str]:
    if n_ids * 4 > PREFETCH_IDS_BYTES:
        return (f"{n_ids} ids exceed the {PREFETCH_IDS_BYTES}-byte scalar "
                f"prefetch budget")
    return None


def _gather_block(n: int) -> Optional[int]:
    """Output rows per grid step: at most ``DEFAULT_GATHER_BLOCK``, a
    divisor of ``n``, and (sublane tiling of the output block) a multiple
    of 8 unless it is all of ``n``. None when no such block exists."""
    if n <= DEFAULT_GATHER_BLOCK:
        return n
    for c in range(DEFAULT_GATHER_BLOCK, 7, -8):
        if n % c == 0:
            return c
    return None


def _table_rule(table, n_ids: int, bag: int = 1) -> Optional[str]:
    """Why the row-DMA kernels cannot take ``table`` for ``n_ids`` ids in
    bags of ``bag`` (None: they can). The rules are the TPU compiler's,
    found by compiling for a v5e (tests/test_tpu_compile.py) —
    docs/embeddings.md "Fused kernels"."""
    rows, dim = table.shape[0], table.shape[-1]
    if _gather_block(n_ids // bag) is None:
        return (f"{n_ids // bag} output rows have no divisor <= "
                f"{DEFAULT_GATHER_BLOCK} that is a multiple of 8")
    if table.dtype == jnp.int8:
        if table.ndim != 2 or dim % 128:
            return (f"int8 table dim {dim} is not a multiple of 128 (TPU "
                    f"lane tiling)")
        if rows % INT8_ROW_TILE:
            return (f"int8 table rows {rows} are not a multiple of "
                    f"{INT8_ROW_TILE} (HBM tile of packed rows)")
    elif table.dtype.itemsize != 4:
        return (f"table dtype {table.dtype} is not 32-bit (packed rows "
                f"cannot be moved or stored one at a time)")
    elif table.ndim != 2 or dim != 128:
        return (f"table dim {dim} is not 128 (one row must be one "
                f"contiguous lane tile in HBM for a single-row DMA)")
    return _prefetch_rule(n_ids)


def _scatter_rule(g_flat, n_ids: int, num_rows: int) -> Optional[str]:
    """Why the VMEM-resident scatter-add cannot run (None: it can)."""
    dim = g_flat.shape[-1]
    if g_flat.ndim != 2 or dim % 128:
        return f"grad dim {dim} is not a multiple of 128 (TPU lane tiling)"
    if g_flat.dtype.itemsize != 4:
        return (f"grad dtype {g_flat.dtype} is not 32-bit (packed rows "
                f"cannot be stored one at a time)")
    resident = (g_flat.shape[0] + num_rows) * dim * 4
    if resident > SCATTER_VMEM_BYTES:
        return (f"grads + output shard are {resident} bytes, over the "
                f"{SCATTER_VMEM_BYTES}-byte VMEM budget")
    if dispatch.partitioned():
        # outside the sharded-embedding engine (whose shard_map makes this
        # per-shard code already) nothing says how a scatter-add splits
        return ("scatter-add under a several-device mesh runs as a kernel "
                "only inside the sharded-embedding engine's shard_map")
    return _prefetch_rule(n_ids)


def _use_pallas(kernel: str, rule: Optional[str]) -> bool:
    """Kernel on the TPU when its rule holds (``rule`` is None); otherwise
    the lax path, and on the TPU that is logged once with the rule."""
    if not dispatch.on_tpu():
        return False
    if rule is not None:
        dispatch.note_fallback(kernel, rule)
    return rule is None


def _gather_rule(table, n_ids: int, bag: int = 1) -> Optional[str]:
    """``_table_rule`` for the ids one shard sees: under a several-device
    mesh the output rows are split over the data axis (``_per_shard``)."""
    return (dispatch.shard_rule(n_ids // bag)
            or _table_rule(table, n_ids // dispatch.shards(dispatch.BATCH),
                           bag))


def _per_shard(call, table, ids, *scalars):
    """``call(table, ids, *scalars)`` with the leading axis of ``ids`` (and
    of the gathered rows) split over the data axis, the table whole."""
    rows = (dispatch.BATCH,) + (None,) * (ids.ndim - 1)
    return dispatch.per_shard(
        call, (table, ids) + scalars,
        ((None, None), rows) + ((),) * len(scalars), (dispatch.BATCH, None))


def _vma_struct(shape, dtype, like):
    """ShapeDtypeStruct carrying the input's varying-manual-axes so
    pallas_call outputs satisfy shard_map's vma check (the sharded lookup
    runs these kernels inside shard_map)."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _int_zeros(x):
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


# ---------------------------------------------------------------------------
# pallas TPU kernels (ids ride scalar prefetch, flat, and drive
# double-buffered per-row DMAs out of HBM). Off the TPU they are traced only
# by tests/test_tpu_compile.py, which compiles each for a described v5e.
# ---------------------------------------------------------------------------


def _gather_kernel(ids_ref, table_ref, out_ref, scratch_ref, sem_ref, *,
                   block: int, clip: bool):
    """One grid step gathers ``block`` rows: the next row's HBM->VMEM DMA
    is in flight while the current one lands (2-slot scratch). ``clip``
    mirrors ``jnp.take``'s default mode; otherwise out-of-range ids (the
    SENTINEL, negative padding) write zero rows — fill semantics."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nrows = table_ref.shape[0]
    base = pl.program_id(0) * block

    def _dma(slot, j):
        row = jnp.clip(ids_ref[base + j], 0, nrows - 1)
        return pltpu.make_async_copy(table_ref.at[pl.ds(row, 1), :],
                                     scratch_ref.at[slot],
                                     sem_ref.at[slot])

    _dma(0, 0).start()

    def _step(j, carry):
        slot = j % 2

        @pl.when(j + 1 < block)
        def _prefetch():
            _dma((j + 1) % 2, j + 1).start()

        _dma(slot, j).wait()
        if clip:
            out_ref[j, :] = scratch_ref[slot, 0]
        else:
            row = ids_ref[base + j]
            ok = (row >= 0) & (row < nrows)
            out_ref[j, :] = jnp.where(ok, scratch_ref[slot, 0],
                                      jnp.zeros_like(scratch_ref[slot, 0]))
        return carry

    lax.fori_loop(0, block, _step, 0)


def _gather_int8_kernel(ids_ref, table_ref, scale_ref, out_ref, scratch_ref,
                        sem_ref, *, block: int):
    """int8 row gather with dequant-in-kernel. A packed int8 row cannot be
    addressed alone in HBM, so each DMA moves the aligned
    ``INT8_ROW_TILE``-row tile that holds the wanted row (1 KiB at dim
    128, against 512 B for one f32 row) and the row is picked out of the
    tile in VMEM, where the ``q * scale`` upcast also happens."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nrows = table_ref.shape[0]
    base = pl.program_id(0) * block
    tile = INT8_ROW_TILE

    def _dma(slot, j):
        row = jnp.clip(ids_ref[base + j], 0, nrows - 1)
        start = pl.multiple_of((row // tile) * tile, tile)
        return pltpu.make_async_copy(table_ref.at[pl.ds(start, tile), :],
                                     scratch_ref.at[slot],
                                     sem_ref.at[slot])

    _dma(0, 0).start()

    def _step(j, carry):
        slot = j % 2

        @pl.when(j + 1 < block)
        def _prefetch():
            _dma((j + 1) % 2, j + 1).start()

        _dma(slot, j).wait()
        row = ids_ref[base + j]
        ok = (row >= 0) & (row < nrows)
        rows = scratch_ref[slot].astype(jnp.float32)  # [tile, dim]
        sub = lax.broadcasted_iota(jnp.int32, rows.shape, 0)
        want = ok & (sub == jnp.clip(row, 0, nrows - 1) % tile)
        out_ref[j, :] = jnp.sum(jnp.where(want, rows, 0.0),
                                axis=0) * scale_ref[0, 0]
        return carry

    lax.fori_loop(0, block, _step, 0)


def _gather_pool_kernel(ids_ref, table_ref, out_ref, acc_ref, cnt_ref,
                        scratch_ref, sem_ref, *, block: int, bag: int,
                        combiner: str):
    """Fused gather + segment pooling: each output row accumulates its
    ``bag`` gathered rows in a VMEM f32 accumulator (padding ids masked,
    valid count kept for mean/sqrtn) and writes once — the unfused
    ``[..., bag, dim]`` intermediate never exists. The ``[n, bag]`` ids
    arrive flattened: a 2-D scalar-prefetch operand is padded out to
    whole (8, 128) words per row and overflows scalar memory."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nrows = table_ref.shape[0]
    base = pl.program_id(0) * block
    total = block * bag

    def _dma(slot, j):
        row = jnp.clip(ids_ref[base * bag + j], 0, nrows - 1)
        return pltpu.make_async_copy(table_ref.at[pl.ds(row, 1), :],
                                     scratch_ref.at[slot],
                                     sem_ref.at[slot])

    _dma(0, 0).start()

    def _step(j, carry):
        slot = j % 2
        b = j // bag
        k = j - b * bag

        @pl.when(j + 1 < total)
        def _prefetch():
            _dma((j + 1) % 2, j + 1).start()

        _dma(slot, j).wait()
        row = ids_ref[base * bag + j]
        ok = (row >= 0) & (row < nrows)

        @pl.when(k == 0)
        def _reset():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            cnt_ref[0, 0] = 0.0

        acc_ref[:] = acc_ref[:] + jnp.where(
            ok, scratch_ref[slot].astype(jnp.float32),
            jnp.zeros_like(acc_ref))
        cnt_ref[0, 0] = cnt_ref[0, 0] + jnp.where(ok, 1.0, 0.0)

        @pl.when(k == bag - 1)
        def _emit():
            denom = jnp.maximum(cnt_ref[0, 0], 1.0)
            if combiner == "mean":
                out_ref[b, :] = (acc_ref[0] / denom).astype(out_ref.dtype)
            elif combiner == "sqrtn":
                out_ref[b, :] = (acc_ref[0]
                                 / jnp.sqrt(denom)).astype(out_ref.dtype)
            else:
                out_ref[b, :] = acc_ref[0].astype(out_ref.dtype)
        return carry

    lax.fori_loop(0, total, _step, 0)


def _scatter_add_kernel(rows_ref, g_ref, out_ref, *, n: int):
    """Row-subset scatter-add: the output shard block lives in VMEM for
    the whole pass; out-of-range rows (SENTINEL markers) drop."""
    from jax.experimental import pallas as pl  # noqa: F401 (grid idiom)

    out_ref[:] = jnp.zeros_like(out_ref)
    limit = out_ref.shape[0]

    def _step(j, carry):
        row = rows_ref[j]
        ok = (row >= 0) & (row < limit)
        safe = jnp.clip(row, 0, limit - 1)
        add = jnp.where(ok, g_ref[j, :], jnp.zeros_like(g_ref[j, :]))
        out_ref[safe, :] = out_ref[safe, :] + add
        return carry

    lax.fori_loop(0, n, _step, 0)


# ---------------------------------------------------------------------------
# pallas_call plumbing


def _gather_call(table, flat_ids, clip: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim = flat_ids.shape[0], table.shape[1]
    block = _gather_block(n)
    return pl.pallas_call(
        functools.partial(_gather_kernel, block=block, clip=clip),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, dim), lambda i, *_: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, 1, dim), table.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=_vma_struct((n, dim), table.dtype, table),
    )(flat_ids.astype(jnp.int32), table)


def _gather_int8_call(qtable, scale, flat_ids):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim = flat_ids.shape[0], qtable.shape[1]
    block = _gather_block(n)
    scale2 = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_gather_int8_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec((block, dim), lambda i, *_: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, INT8_ROW_TILE, dim),
                                       qtable.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=_vma_struct((n, dim), jnp.float32, qtable),
    )(flat_ids.astype(jnp.int32), qtable, scale2)


def _gather_pool_call(table, ids2d, combiner: str):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, bag = ids2d.shape
    dim = table.shape[1]
    block = _gather_block(n)
    return pl.pallas_call(
        functools.partial(_gather_pool_kernel, block=block, bag=bag,
                          combiner=combiner),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, dim), lambda i, *_: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((1, dim), jnp.float32),
                            pltpu.SMEM((1, 1), jnp.float32),
                            pltpu.VMEM((2, 1, dim), table.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=_vma_struct((n, dim), table.dtype, table),
    )(ids2d.astype(jnp.int32).reshape(-1), table)


def _scatter_call(g_flat, rows, num_rows: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim = g_flat.shape
    return pl.pallas_call(
        functools.partial(_scatter_add_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec((n, dim), lambda *_: (0, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((num_rows, dim), lambda *_: (0, 0),
                                   memory_space=pltpu.VMEM)),
        out_shape=_vma_struct((num_rows, dim), g_flat.dtype, g_flat),
    )(rows.astype(jnp.int32), g_flat)


# ---------------------------------------------------------------------------
# fused primitives (the API the engine / layers / bench wire against).
# Off-TPU these trace EXACTLY the unfused reference ops, in the same order
# — bit-parity by construction. Policed: no host syncs, no one_hot, no
# per-row Python loops.
# ---------------------------------------------------------------------------


def gather_rows(table, flat_ids):
    """Fill-mode row gather (out-of-range -> zero row): the local-gather
    half of ``parallel.embedding._lookup_body`` after the id exchange.
    Not differentiated — the sharded lookup owns its backward."""
    if _use_pallas("gather_rows", _gather_rule(table, flat_ids.size)):
        return _per_shard(functools.partial(_gather_call, clip=False),
                          table, flat_ids)
    return jnp.take(table, flat_ids, axis=0, mode="fill", fill_value=0)


def gather_rows_clip(table, ids):
    """Clip-mode row gather (``jnp.take`` default, any ``ids`` shape): the
    dense unsharded lookup. Differentiable: off-TPU it IS ``jnp.take``
    (native autodiff); on TPU a custom_vjp pairs the pallas gather with
    the same scatter-add XLA's take-transpose emits."""
    if _use_pallas("gather_rows_clip", _gather_rule(table, ids.size)):
        return _gather_clip_tpu(table, ids)
    return jnp.take(table, ids, axis=0)


def segment_grads(g, inv, d, slot, shards):
    """Fused backward half 1: segment-sum the output cotangent per unique
    id straight into its (destination, slot) cell of the request-shaped
    exchange buffer (``parallel.embedding._lookup_bwd_body``)."""
    n = inv.shape[0]
    g_u = jax.ops.segment_sum(g, inv, num_segments=n)
    return jnp.zeros((shards, n, g.shape[-1]), g.dtype).at[d, slot].set(g_u)


def scatter_rows(g_flat, rows, num_rows):
    """Fused backward half 2: scatter-add the exchanged per-unique grads
    into the touched rows of the local shard block. The result IS the
    row-subset cotangent the sparse row updates consume — ``[rows_per_
    shard, dim]``, never a dense ``[vocab, dim]``; SENTINEL rows drop."""
    if _use_pallas("scatter_rows",
                   _scatter_rule(g_flat, rows.size, num_rows)):
        return _scatter_call(g_flat, rows, num_rows)
    return jnp.zeros((num_rows, g_flat.shape[-1]), g_flat.dtype).at[
        rows].add(g_flat, mode="drop")


def _gather_pool_ref(table, idx, combiner, mask_negative):
    """The bit-parity reference: verbatim the op chain of the unfused
    ``SparseEmbedding.call`` (mask_negative) / ``_WideLinear.call``
    (pre-validated ids) — same ops, same order, same dtypes."""
    if mask_negative:
        valid = (idx >= 0).astype(table.dtype)[..., None]
        emb = jnp.take(table, jnp.maximum(idx, 0), axis=0) * valid
    else:
        valid = None
        emb = jnp.take(table, idx, axis=0)
    if combiner is None:
        return emb
    total = jnp.sum(emb, axis=-2)
    if combiner == "sum":
        return total
    if valid is not None:
        n = jnp.maximum(jnp.sum(valid, axis=-2), 1.0)
    else:
        n = jnp.full(total.shape[:-1] + (1,), 1.0 * idx.shape[-1],
                     table.dtype)
    if combiner == "mean":
        return total / n
    return total / jnp.sqrt(n)  # sqrtn


def gather_pool(table, idx, combiner=None, mask_negative=True):
    """Fused gather + padding mask + bag pooling over the trailing axis of
    ``idx``. ``mask_negative`` treats negative ids as padding (zero rows,
    excluded from mean/sqrtn counts) exactly like ``SparseEmbedding``;
    with it off, ids must be pre-validated (the ``_WideLinear`` contract).
    Differentiable both ways; pooled variants require ``idx.ndim >= 2``."""
    if _use_pallas("gather_pool", _gather_rule(
            table, idx.size, 1 if combiner is None else idx.shape[-1])):
        return _gather_pool_tpu(table, idx, combiner, mask_negative)
    return _gather_pool_ref(table, idx, combiner, mask_negative)


def gather_pool_int8(qtable, scale, idx, combiner=None, mask_negative=True):
    """:func:`gather_pool` over a :func:`quantize_table` table resident
    int8 in HBM. Rows dequantize in-kernel on TPU (each DMA moves the
    8-row tile holding the row); the lax path dequantizes right at the
    gather, and is the only one that pools. Forward
    only (quantized serving/eval path). Error vs the f32 table:
    ``<= scale/2`` per element, ``<= bag * scale/2`` after sum pooling."""
    rule = (_gather_rule(qtable, idx.size) if combiner is None else
            f"no int8 kernel pools (combiner={combiner!r}); rows are "
            f"dequantized at a lax gather")
    if _use_pallas("gather_pool_int8", rule):
        flat = idx.reshape(-1)
        rows = _per_shard(
            lambda t, i, s: _gather_int8_call(t, s, i), qtable, flat,
            jnp.asarray(scale, jnp.float32))
        out = rows.reshape(idx.shape + (qtable.shape[1],))
        if mask_negative:
            out = out * (idx >= 0).astype(out.dtype)[..., None]
        return out
    if mask_negative:
        valid = (idx >= 0).astype(jnp.float32)[..., None]
        q_rows = jnp.take(qtable, jnp.maximum(idx, 0), axis=0)
        emb = dequant_int8(q_rows, scale, jnp.float32) * valid
    else:
        valid = None
        emb = dequant_int8(jnp.take(qtable, idx, axis=0), scale,
                           jnp.float32)
    if combiner is None:
        return emb
    total = jnp.sum(emb, axis=-2)
    if combiner == "sum":
        return total
    if valid is not None:
        n = jnp.maximum(jnp.sum(valid, axis=-2), 1.0)
    else:
        n = jnp.full(total.shape[:-1] + (1,), 1.0 * idx.shape[-1],
                     jnp.float32)
    if combiner == "mean":
        return total / n
    return total / jnp.sqrt(n)  # sqrtn


# -- wrappers (multi-table dispatch + quantization; not per-row code) -------


def multi_table_lookup(tables: Sequence, indices: Sequence,
                       combiners: Optional[Sequence] = None,
                       mask_negative: bool = True):
    """One traced pass over a whole tower of embedding tables: per-table
    fused gather+pool, then the feature concat — a single dispatch where
    the unfused path pays one per table plus the concat. Pooled tables
    contribute ``[..., dim]``; un-pooled (combiner None) tables must share
    their index shape with the others for the concat to line up."""
    if combiners is None:
        combiners = (None,) * len(tables)
    parts = [gather_pool(t, i, c, mask_negative)
             for t, i, c in zip(tables, indices, combiners)]
    return jnp.concatenate(parts, axis=-1)


def quantize_table(table, running_amax=None):
    """Symmetric int8 quantization of an embedding table with the
    ``ops/int8_dataflow`` delayed-scaling recipe: fast-rise/slow-decay
    running amax (when carried across steps), ``scale = amax / 127``.
    Returns ``(qtable int8, scale, amax)`` — stash ``amax`` and feed it
    back as ``running_amax`` to requantize with delayed scales."""
    seen = jnp.max(jnp.abs(table.astype(jnp.float32)))
    amax = seen if running_amax is None else next_amax(running_amax, seen)
    scale = scale_of_amax(amax)
    return quant_int8(table, scale), scale, amax


def int8_error_bound(scale, bag_size: int = 1):
    """Documented worst-case absolute error of the int8 gather vs the f32
    table: half a quantization step per element, times the bag size for
    sum-pooled bags (mean/sqrtn divide it back down)."""
    return 0.5 * scale * bag_size


# ---------------------------------------------------------------------------
# TPU custom_vjp shims (pallas forward, reference-arithmetic backward) —
# never traced off-TPU.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _gather_clip_tpu(table, ids):
    rows = _per_shard(functools.partial(_gather_call, clip=True), table,
                      ids.reshape(-1))
    return rows.reshape(ids.shape + (table.shape[1],))


def _gather_clip_tpu_fwd(table, ids):
    return _gather_clip_tpu(table, ids), (table, ids)


def _gather_clip_tpu_bwd(res, g):
    table, ids = res
    safe = jnp.clip(ids.reshape(-1), 0, table.shape[0] - 1)
    ct = jnp.zeros_like(table).at[safe].add(
        g.reshape(-1, table.shape[-1]).astype(table.dtype))
    return ct, _int_zeros(ids)


_gather_clip_tpu.defvjp(_gather_clip_tpu_fwd, _gather_clip_tpu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gather_pool_tpu(table, idx, combiner, mask_negative):
    if combiner is None:
        flat = idx.reshape(-1)
        # fill == masked
        rows = _per_shard(
            functools.partial(_gather_call, clip=not mask_negative), table,
            flat)
        return rows.reshape(idx.shape + (table.shape[1],))
    ids2d = idx.reshape(-1, idx.shape[-1])
    if not mask_negative:
        ids2d = jnp.clip(ids2d, 0, table.shape[0] - 1)
    pooled = _per_shard(
        functools.partial(_gather_pool_call, combiner=combiner), table,
        ids2d)
    return pooled.reshape(idx.shape[:-1] + (table.shape[1],))


def _gather_pool_tpu_fwd(table, idx, combiner, mask_negative):
    return _gather_pool_tpu(table, idx, combiner, mask_negative), (table, idx)


def _gather_pool_tpu_bwd(combiner, mask_negative, res, g):
    table, idx = res
    if mask_negative:
        valid = (idx >= 0).astype(table.dtype)[..., None]
        safe = jnp.maximum(idx, 0)
    else:
        valid = jnp.ones(idx.shape + (1,), table.dtype)
        safe = idx
    if combiner is None:
        gk = g * valid
    else:
        if combiner in ("mean", "sqrtn"):
            n = jnp.maximum(jnp.sum(valid, axis=-2), 1.0)
            g = g / (n if combiner == "mean" else jnp.sqrt(n))
        gk = g[..., None, :] * valid
    ct = jnp.zeros_like(table).at[safe.reshape(-1)].add(
        gk.reshape(-1, table.shape[-1]).astype(table.dtype))
    return ct, _int_zeros(idx)


_gather_pool_tpu.defvjp(_gather_pool_tpu_fwd, _gather_pool_tpu_bwd)
