"""Incremental (KV-cached) attention decoding.

New TPU-native capability rounding out the long-context stack: training
runs the flash kernels (``ops/attention.py``), generation runs this cache.
The reference's only generation path is the host-side RNN loop in Seq2seq
(``models/seq2seq``); transformer decoding needs the KV cache to avoid
re-attending the whole prefix per step.

Design for XLA: the cache is a STATIC ``max_len`` buffer pair updated with
``lax.dynamic_update_slice`` — shapes never change, so the per-step program
compiles once; validity is a position mask derived from ``length``. The
whole generate loop is one ``lax.scan`` (single dispatch per sequence, the
only pattern that amortizes dispatch latency on remote-attached chips).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import dispatch
from .attention import _NEG_INF, masked_context
from .int8_dataflow import next_amax, quant_int8, scale_of_amax

KVCache = Dict[str, Any]


def init_kv_cache(batch: int, heads: int, max_len: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    """Empty cache: K/V buffers ``[B, H, max_len, D]`` + write position."""
    return {
        "k": jnp.zeros((batch, heads, max_len, head_dim), dtype),
        "v": jnp.zeros((batch, heads, max_len, head_dim), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def cached_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                     cache: KVCache, scale: Optional[float] = None
                     ) -> Tuple[jax.Array, KVCache]:
    """Append ``k_new``/``v_new`` (``[B, H, T, D]``, T = 1 for decode or the
    prompt length for prefill) at the cache's write position, then attend
    ``q`` against everything cached so far, causally within the new block.

    Returns ``(context [B, H, T, D], updated cache)``. jit-safe: static
    shapes, the step count lives in ``cache["length"]``.
    """
    b, h, t, d = q.shape
    max_len = cache["k"].shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    start = cache["length"]
    # capacity guard: under eager execution (concrete length) overflowing
    # the static buffer raises here; under jit ``length`` is a Tracer so
    # this check SILENTLY SKIPS — the caller owns the budget (max_len -
    # length tokens remain) and overflow would silently corrupt the tail.
    # Use :func:`checked_cached_attention` where the write position is
    # traced and a runtime-checkable guard is wanted.
    import jax.core as _core
    if not isinstance(start, _core.Tracer) and int(start) + t > max_len:
        raise ValueError(
            f"KV cache overflow: writing {t} tokens at position "
            f"{int(start)} exceeds max_len={max_len}")
    with jax.named_scope("kv_write"):
        k_buf = lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, 0, start, 0))
        v_buf = lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, 0, start, 0))
    # visibility: cached prefix [0, start) plus the causal part of the new
    # block [start, start+t)
    key_pos = lax.broadcasted_iota(jnp.int32, (t, max_len), 1)
    row_pos = start + lax.broadcasted_iota(jnp.int32, (t, max_len), 0)
    visible = key_pos <= row_pos
    ctx = masked_context(q, k_buf, v_buf, visible[None, None], scale)
    new_cache = {"k": k_buf, "v": v_buf, "length": start + t}
    return ctx, new_cache


def checked_cached_attention(q: jax.Array, k_new: jax.Array,
                             v_new: jax.Array, cache: KVCache,
                             scale: Optional[float] = None
                             ) -> Tuple[jax.Array, KVCache]:
    """:func:`cached_attention` with a RUNTIME-checkable capacity guard.

    The eager guard in :func:`cached_attention` is skipped whenever
    ``cache["length"]`` is a tracer (i.e. under ``jit`` — exactly where
    every production decode loop runs), so an overflowing write silently
    wraps into ``dynamic_update_slice``'s clamped behavior and corrupts
    the newest cache tail. This variant stages a ``checkify`` predicate
    that travels THROUGH jit and fires at runtime with the offending
    position. Use it by functionalizing the error with
    ``jax.experimental.checkify``::

        from jax.experimental import checkify
        step = jax.jit(checkify.checkify(decode_step))
        err, (ctx, cache) = step(q, k_new, v_new, cache)
        err.throw()   # raises on overflow, no-op otherwise

    The check is metadata riding the jitted program — the decode math and
    cache layout are bit-identical to :func:`cached_attention`.
    """
    from jax.experimental import checkify
    t = q.shape[2]
    max_len = cache["k"].shape[2]
    checkify.check(
        cache["length"] + t <= max_len,
        "KV cache overflow: writing {t} tokens at position {start} "
        "exceeds max_len={max_len}",
        t=jnp.asarray(t, jnp.int32), start=cache["length"],
        max_len=jnp.asarray(max_len, jnp.int32))
    return cached_attention(q, k_new, v_new, cache, scale)


# -- slot rectangles and the slot state --------------------------------------
#
# S independent streams resident in ONE device-shaped cache, a ``max_len``
# rectangle a slot, so a single fused step advances every occupied slot. All
# shapes are static: joining, stepping and evicting only move traced
# indices/masks around, so a step program compiles exactly once no matter how
# streams come and go. The generative scheduler (serving/server.py
# GenerativeServing) keeps its target model's K/V in the page pool further
# down; what keeps these: the DRAFT model of a speculative round decodes off
# slot rectangles (``_step_spec`` / ``_prefill_spec`` there,
# ``generate_speculative`` in capture/lm.py); the slot STATE (lengths +
# active mask: ``init_slot_state`` / ``slot_join`` / ``slot_evict``) is the
# page pool's occupancy too; and ``slot_insert`` / ``slot_attention`` are the
# reference that tests/test_paged_kv.py and tests/test_attention.py hold the
# XLA form of the paged read bit-identical to.

SlotCache = Dict[str, Any]


def init_slot_cache(slots: int, heads: int, max_len: int, head_dim: int,
                    dtype=jnp.float32) -> SlotCache:
    """Per-block K/V buffers ``[S, H, max_len, D]`` for S decode slots.

    Unlike :func:`init_kv_cache` there is no scalar write position: slots
    advance independently, so per-slot lengths live in the scheduler-wide
    slot STATE (:func:`init_slot_state`) shared across blocks."""
    return {"k": jnp.zeros((slots, heads, max_len, head_dim), dtype),
            "v": jnp.zeros((slots, heads, max_len, head_dim), dtype)}


def init_slot_state(slots: int) -> Dict[str, jax.Array]:
    """Scheduler-wide occupancy: per-slot fed-token counts + active mask."""
    return {"length": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool)}


def slot_join(state: Dict[str, jax.Array], slot, length
              ) -> Dict[str, jax.Array]:
    """Mark ``slot`` occupied with ``length`` tokens already fed. Both
    arguments may be traced values — joins never trigger a recompile."""
    length = jnp.asarray(length, jnp.int32)
    return {"length": state["length"].at[slot].set(length),
            "active": state["active"].at[slot].set(True)}


def slot_evict(state: Dict[str, jax.Array], mask) -> Dict[str, jax.Array]:
    """Vacate every slot where ``mask`` [S] is True — one vectorized call
    evicts any number of finished/expired slots per step."""
    mask = jnp.asarray(mask)
    return {"length": jnp.where(mask, 0, state["length"]),
            "active": state["active"] & ~mask}


@jax.named_scope("kv_write")
def slot_insert(cache: SlotCache, slot, k_new: jax.Array, v_new: jax.Array
                ) -> SlotCache:
    """Write a prefilled K/V block ``[H, T, D]`` into ``slot`` at position
    0. ``slot`` may be traced; T is static (length-bucketed by the caller)
    so one compile per bucket covers every join at that bucket."""
    k_buf = lax.dynamic_update_slice(
        cache["k"], k_new[None].astype(cache["k"].dtype), (slot, 0, 0, 0))
    v_buf = lax.dynamic_update_slice(
        cache["v"], v_new[None].astype(cache["v"].dtype), (slot, 0, 0, 0))
    return {"k": k_buf, "v": v_buf}


def slot_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                   cache: SlotCache, lengths: jax.Array,
                   scale: Optional[float] = None
                   ) -> Tuple[jax.Array, SlotCache]:
    """One decode step over ALL slots: write each slot's new K/V at its own
    ``lengths[s]`` position, then attend each slot's query against its
    visible prefix. Mirrors :func:`cached_attention` arithmetic exactly —
    same contractions, mask and softmax — which is what keeps slot-batched
    token streams bit-identical to serial decode rows.

    ``q``/``k_new``/``v_new``: ``[S, H, 1, D]``; ``lengths``: [S] int32
    (tokens fed so far = this step's write position). Returns
    ``(ctx [S, H, 1, D], updated cache)``; the CALLER advances lengths once
    after every block has attended (all blocks see pre-increment lengths).
    """
    _, _, t, d = q.shape
    max_len = cache["k"].shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    write = jax.vmap(
        lambda buf, new, pos: lax.dynamic_update_slice(buf, new,
                                                       (0, pos, 0)))
    with jax.named_scope("kv_write"):
        k_buf = write(cache["k"], k_new.astype(cache["k"].dtype), lengths)
        v_buf = write(cache["v"], v_new.astype(cache["v"].dtype), lengths)
    # visibility per slot: prefix [0, length] inclusive — the just-written
    # position IS visible, exactly as cached_attention's t=1 decode row
    key_pos = lax.broadcasted_iota(jnp.int32, (t, max_len), 1)
    visible = key_pos[None] <= lengths[:, None, None]   # [S, 1, max_len]
    ctx = masked_context(q, k_buf, v_buf, visible[:, None], scale)
    return ctx, {"k": k_buf, "v": v_buf}


# -- paged KV cache (block-granular allocation + per-slot page tables) ------
#
# The slot caches above reserve a contiguous [S, H, max_len, D] rectangle
# per block: HBM pays for max_len whether a stream uses it or not. The page
# pool (vLLM's PagedAttention transplanted onto the traced-index slot
# machinery) puts in the rectangles' place ONE global pool of fixed-size
# pages plus a per-slot page TABLE [S, W] of pool indices in logical order —
# a stream only holds the pages its prompt + budget actually need, and
# identical prompt prefixes can share refcounted pages (copy-on-write,
# managed by the scheduler in serving/server.py).
#
# A pool is STORED as [P, page_len, H*D]: one row of H*D values per token
# position, heads folded into the minor axis. The shape is chosen for the
# TPU's compiler, which tiles the two minor axes of an array by (8, 128).
# Over [P, H, page_len, D] with D = 64 that tile would pad every page to
# twice its size, so the compiler stores such a pool pages-minor-most and
# wraps every write in two pool-sized copies (layout in, layout back). Over
# [page_len, H*D] = [16, 768] the tile fits without padding, the pool keeps
# the plain row-major layout, and the scatter of token rows at
# [page, offset] updates it IN PLACE when the caller DONATES the pool to
# the program (serving/server.py donates it to every program that returns
# it; tests/test_tpu_compile.py compiles those programs for the chip and
# fails on a pool-shaped copy). Callers still hand rows in and get views
# back as [..., H, D]; only the stored shape is flat.
#
# Page 0 is the NULL page: never allocated to a stream, it absorbs the
# writes of inactive slots and of positions past a slot's allocation (the
# same way inactive slots harmlessly write into their own rectangle).
#
# The decode step reads the pool in one of two forms. The XLA form
# (everywhere off the TPU, and on it wherever ``_paged_decode_rule`` names a
# reason) gathers a slot's pages back into logical [max_len] order and runs
# the SAME masked_context arithmetic as slot_attention: garbage beyond a
# slot's length — null-page junk here, stale rectangle tail there — is
# masked to exactly _NEG_INF and contributes exact-zero terms either way.
# Bit-identity with the slot rectangles is a property of THAT form, and the
# tests that hold it run it. On the TPU, over an unquantised pool on one
# device, the T = 1 step runs ``paged_decode_context`` instead: a pallas
# kernel that walks each slot's table row up to its length and reads the
# live [page_len, H*D] pages where they lie, with an online softmax per
# head (a head is a D-lane slice of the stored row, so nothing is gathered
# or laid out again). It computes the same mathematics with products at one
# bfloat16 pass and float32 accumulation, so there the benchmark's
# ``correct`` holds it by ``served_logit_gap_max``, and
# tests/test_paged_decode_kernel.py holds it to the XLA form in interpret
# mode.
#
# All shapes are static: tables, lengths and page ids are DATA, so joins,
# evictions and CoW copies never recompile the step program. The int8
# variant stores the pool as int8 plus a per-token-position f32 scale
# ([P, page_len]) using the delayed-scaling recipe from ops/int8_dataflow
# (quantize with the RUNNING amax — no max pass on the decode hot path).

PagedCache = Dict[str, Any]


def init_paged_pool(num_pages: int, heads: int, page_len: int,
                    head_dim: int, dtype=jnp.float32,
                    int8: bool = False) -> PagedCache:
    """Global K/V page pool ``[P, page_len, H*D]`` (per transformer
    block; the block comment above says why that shape). Page 0 is
    reserved as the null page — allocators hand out ids ``1..P-1``. With
    ``int8=True`` the pool stores int8 payloads plus a per-position f32
    scale ``[P, page_len]`` and per-pool running amax scalars (delayed
    scaling, seeded at 1.0 so the cold-start scale is sane for
    layer-normed activations)."""
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (page 0 is the reserved "
                         f"null page), got {num_pages}")
    if page_len < 1:
        raise ValueError(f"page_len must be >= 1, got {page_len}")
    shape = (num_pages, page_len, heads * head_dim)
    if int8:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "scale_k": jnp.zeros((num_pages, page_len), jnp.float32),
                "scale_v": jnp.zeros((num_pages, page_len), jnp.float32),
                "amax_k": jnp.ones((), jnp.float32),
                "amax_v": jnp.ones((), jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


#: mesh axis the paged pool's page dimension shards over
KV_SHARD_AXIS = "kv"


def shard_paged_pool(caches, n_shard: int,
                     axis_name: str = KV_SHARD_AXIS):
    """Spread each block's page pool across ``n_shard`` devices along the
    PAGE axis (contiguous blocks of ``num_pages/n_shard`` pages per
    device) — the sharded-KV serving tier for models whose cache exceeds
    one device's HBM budget.

    Pure placement, no program change: the decode step's page gather
    pulls each stream's pages to the compute device and the attention
    arithmetic runs on the gathered buffer exactly as it does over a
    single-device pool, so decoded tokens are bit-identical to
    ``n_shard=1`` (asserted by the serving parity tests). Scalars (int8
    running amax) stay replicated. Allocators should hand out pages
    round-robin across shards so writes spread evenly (serving/server.py
    does)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if n_shard < 1 or len(devs) % n_shard:
        raise ValueError(f"kv shard count {n_shard} must divide the local "
                         f"device count {len(devs)}")
    # every pool leaf has the pages first (K/V, a latent or an index pool)
    num_pages = jax.tree_util.tree_leaves(caches[0])[0].shape[0]
    if num_pages % n_shard:
        raise ValueError(f"num_pages {num_pages} must be divisible by the "
                         f"kv shard count {n_shard}")
    import numpy as _np
    # the mesh spans ALL local devices (jit needs one device set across
    # the pool, params, and tables); pages split over the first axis and
    # replicate over the remainder
    mesh = Mesh(_np.asarray(devs).reshape(n_shard, -1),
                (axis_name, "kv_repl"))

    def put(leaf):
        spec = (P(axis_name, *([None] * (leaf.ndim - 1)))
                if leaf.ndim >= 1 and leaf.shape[0] == num_pages else P())
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return [jax.tree_util.tree_map(put, c) for c in caches]


def page_table_set(table: jax.Array, slot, row: jax.Array) -> jax.Array:
    """Install ``row`` [W] as ``slot``'s page table. Both may be traced —
    joins never recompile."""
    return lax.dynamic_update_slice(table, row[None].astype(table.dtype),
                                    (slot, 0))


def page_table_clear(table: jax.Array, mask) -> jax.Array:
    """Zero (→ null page) every table row where ``mask`` [S] is True — the
    paged twin of :func:`slot_evict`, one vectorized call for any number
    of evictions."""
    return jnp.where(jnp.asarray(mask)[:, None], 0, table)


@jax.named_scope("kv_write")
def page_copy(cache: PagedCache, src, dst) -> PagedCache:
    """Copy page ``src`` into page ``dst`` (copy-on-write: a stream that
    would append into a shared, partially-filled prefix tail page gets a
    private copy instead). Indices may be traced. One page moves; the
    pool itself is updated in place when the caller donates it."""
    new = {"k": cache["k"].at[dst].set(cache["k"][src]),
           "v": cache["v"].at[dst].set(cache["v"][src])}
    if "scale_k" in cache:
        new["scale_k"] = cache["scale_k"].at[dst].set(cache["scale_k"][src])
        new["scale_v"] = cache["scale_v"].at[dst].set(cache["scale_v"][src])
        new["amax_k"] = cache["amax_k"]
        new["amax_v"] = cache["amax_v"]
    return new


def _page_positions(table: jax.Array, positions: jax.Array, page_len: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Map logical token ``positions`` [S, T] through per-slot ``table``
    [S, W] rows to (pool page ids, in-page offsets). Positions past a
    table's width land on the null page (id 0)."""
    w = table.shape[1]
    idx = positions // page_len
    page = jnp.take_along_axis(table, jnp.minimum(idx, w - 1), axis=1)
    page = jnp.where(idx < w, page, 0)
    return page, positions % page_len


@jax.named_scope("kv_write")
def _paged_write(cache: PagedCache, pages: jax.Array, offs: jax.Array,
                 k_rows: jax.Array, v_rows: jax.Array,
                 inline_amax: bool) -> PagedCache:
    """Scatter token rows (``[..., H, D]``, leading dims matching
    ``pages``/``offs``) into the pool, one flat ``H*D`` row per
    ``[page, offset]``. int8 pools quantize on the way in:
    ``inline_amax=True`` (prefill/join path, off the token hot loop) folds
    the block's own amax into the scale; ``inline_amax=False`` (decode hot
    path) uses the DELAYED running scale — no max pass over the write."""
    k_rows = k_rows.reshape(pages.shape + (-1,))
    v_rows = v_rows.reshape(pages.shape + (-1,))
    if "scale_k" not in cache:
        return {"k": cache["k"].at[pages, offs].set(
                    k_rows.astype(cache["k"].dtype)),
                "v": cache["v"].at[pages, offs].set(
                    v_rows.astype(cache["v"].dtype))}
    kf = k_rows.astype(jnp.float32)
    vf = v_rows.astype(jnp.float32)
    seen_k = jnp.max(jnp.abs(kf))
    seen_v = jnp.max(jnp.abs(vf))
    amax_k = (jnp.maximum(cache["amax_k"], seen_k) if inline_amax
              else cache["amax_k"])
    amax_v = (jnp.maximum(cache["amax_v"], seen_v) if inline_amax
              else cache["amax_v"])
    sk = scale_of_amax(amax_k)
    sv = scale_of_amax(amax_v)
    return {"k": cache["k"].at[pages, offs].set(quant_int8(kf, sk)),
            "v": cache["v"].at[pages, offs].set(quant_int8(vf, sv)),
            "scale_k": cache["scale_k"].at[pages, offs].set(
                jnp.broadcast_to(sk, pages.shape)),
            "scale_v": cache["scale_v"].at[pages, offs].set(
                jnp.broadcast_to(sv, pages.shape)),
            "amax_k": next_amax(cache["amax_k"], seen_k),
            "amax_v": next_amax(cache["amax_v"], seen_v)}


@jax.named_scope("kv_gather")
def paged_gather(cache: PagedCache, table: jax.Array, heads: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Gather per-slot pages back into logical order: ``table`` [S, C] →
    K/V ``[S, H, C*page_len, D]`` (dequantized to f32 for int8 pools);
    ``heads`` unfolds the pool's flat ``H*D`` rows. This materializes the
    logical view as a TRANSIENT activation — the persistent HBM footprint
    is the pool. It is the read of the XLA form (bit-identical to the slot
    engine's rectangle under ``masked_context``): the decode step on the
    TPU reads the pages in place instead (:func:`paged_decode_context`)
    wherever :func:`_paged_decode_rule` allows."""
    k = jnp.take(cache["k"], table, axis=0)   # [S, C, page_len, H*D]
    v = jnp.take(cache["v"], table, axis=0)
    if "scale_k" in cache:
        sk = jnp.take(cache["scale_k"], table, axis=0)  # [S, C, page_len]
        sv = jnp.take(cache["scale_v"], table, axis=0)
        k = k.astype(jnp.float32) * sk[..., None]
        v = v.astype(jnp.float32) * sv[..., None]
    s, c, pl, hd = k.shape
    k = k.reshape(s, c * pl, heads, hd // heads).transpose(0, 2, 1, 3)
    v = v.reshape(s, c * pl, heads, hd // heads).transpose(0, 2, 1, 3)
    return k, v


@jax.named_scope("kv_write")
def paged_insert(cache: PagedCache, table_row: jax.Array, k_new: jax.Array,
                 v_new: jax.Array, start: int = 0) -> PagedCache:
    """Write a prefilled K/V block ``[H, T, D]`` into the pages named by
    ``table_row`` [W] at logical positions ``start..start+T-1`` — the
    paged twin of :func:`slot_insert`. T is static (length-bucketed), so
    one compile per bucket covers every join; positions past the row's
    width (bucket padding beyond the stream's allocation) fall onto the
    null page. ``start`` is a static offset for shared-prefix suffix
    prefills. The rows go into the pool itself when the caller donates
    it: a prefill moves its own T rows, not the pool."""
    t = k_new.shape[1]
    positions = start + lax.broadcasted_iota(jnp.int32, (1, t), 1)
    pages, offs = _page_positions(table_row[None], positions,
                                  cache["k"].shape[1])
    return _paged_write(cache, pages, offs,
                        k_new.transpose(1, 0, 2)[None],
                        v_new.transpose(1, 0, 2)[None], inline_amax=True)


#: pages of one stream the decode kernel holds in VMEM at a time; it holds
#: two such chunks, the next one's copies in flight while this one is
#: attended (8 pages of [16, 768] float32, K and V, twice: 1.5 MiB)
PAGED_DECODE_CHUNK = 8

#: the page table and the lengths ride scalar prefetch, i.e. sit whole in
#: the chip's scalar memory; a wider table takes the XLA form
PAGED_DECODE_TABLE_BYTES = 256 * 1024


def _paged_decode_rule(cache: PagedCache, table: jax.Array) -> Optional[str]:
    """Why the T = 1 decode step cannot read this pool in place with
    :func:`paged_decode_context` (None: it can). The XLA form then runs,
    and on the TPU that is logged once with the rule."""
    if "scale_k" in cache:
        return ("int8 pool: the XLA form's gather dequantises the pages it "
                "reads")
    if dispatch.partitioned():
        return ("the step program spans several devices (a pool sharded "
                "over pages, a mesh under the parameters), and a Mosaic "
                "kernel cannot be partitioned automatically")
    _, page_len, row = cache["k"].shape
    sublanes = 32 // cache["k"].dtype.itemsize
    if page_len % sublanes or row % 128:
        return (f"a page of [{page_len}, {row}] {cache['k'].dtype} is not "
                f"whole ({sublanes}, 128) tiles")
    if table.size * 4 > PAGED_DECODE_TABLE_BYTES:
        return (f"a page table of {table.size} entries exceeds the "
                f"{PAGED_DECODE_TABLE_BYTES}-byte scalar prefetch budget")
    return None


def _note_xla_form(why: str) -> None:
    """On the TPU, say once that pages are read by gather-then-attend
    where no kernel reads them in place, and why. Off the TPU the XLA
    form is the implementation and not a fallback: nothing is noted."""
    if dispatch.on_tpu():
        dispatch.note_fallback("paged_decode", why)


def _reads_in_place(cache: PagedCache, table: jax.Array) -> bool:
    """The kernel on the TPU when its rule holds; otherwise the XLA
    form."""
    rule = _paged_decode_rule(cache, table)
    if rule is not None:
        _note_xla_form(rule)
    return rule is None and dispatch.on_tpu()


def _paged_decode_kernel(len_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sem, *, heads: int, width: int,
                         product_dtype):
    """All slots of one block's decode step. For slot ``s`` the pages
    ``table[s, 0 .. lengths[s] // page_len]`` come from the pool in HBM
    into VMEM a chunk at a time (one DMA a page for K and one for V; the
    next chunk's, or the next slot's first, are started before this one
    is waited for), and a float32 online softmax runs per head.

    The stored row is ``H*D`` lanes with head ``h`` in lanes ``[h*D,
    (h+1)*D)``. Scores of all heads at once are one product of the chunk
    ``[chunk*page_len, H*D]`` with a query block ``[rows, H*D]`` whose row
    ``h`` holds the query's head ``h`` in its own lanes and zeros
    elsewhere; ``p @ V`` gives every head's weights over all lanes, of
    which row ``h`` keeps its own lanes at the end. Pages past the length
    are neither copied nor counted: the buffer keeps what it held (V is
    zeroed once, so that it is finite), their scores are forced to
    ``_NEG_INF`` and their weights are exactly 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, row = q_ref.shape
    _, chunk, page_len, _ = k_buf.shape
    span = chunk * page_len
    rows = -(-heads // 16) * 16
    lane_head = lax.broadcasted_iota(jnp.int32, (rows, row), 1) // (
        row // heads)
    own = (lane_head == lax.broadcasted_iota(jnp.int32, (rows, row), 0)
           ).astype(jnp.float32)
    v_buf[...] = jnp.zeros_like(v_buf)

    def pages_of(s):
        return jnp.minimum(len_ref[s] // page_len + 1, width)

    def each_page(s, c, buf, act):
        """``act`` on the copies of chunk ``c`` of slot ``s`` into ``buf``."""
        def one(j, carry):
            page = table_ref[s * width + c * chunk + j]
            act(pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf, j],
                                      sem.at[0, buf]))
            act(pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf, j],
                                      sem.at[1, buf]))
            return carry
        lax.fori_loop(0, jnp.minimum(pages_of(s) - c * chunk, chunk), one, 0)

    def start(s, c, buf):
        each_page(s, c, buf, lambda dma: dma.start())

    start(0, 0, 0)

    def slot(s, buf):
        length = len_ref[s]
        chunks = pl.cdiv(pages_of(s), chunk)
        qb = (own * q_ref[pl.ds(s, 1), :]).astype(product_dtype)

        def attend(c, carry):
            buf, m, l, acc = carry
            last = c + 1 == chunks

            @pl.when(jnp.logical_not(last))
            def _next_chunk():
                start(s, c + 1, 1 - buf)

            @pl.when(last & (s + 1 < slots))
            def _next_slot():
                start(s + 1, 0, 1 - buf)

            each_page(s, c, buf, lambda dma: dma.wait())
            k = k_buf[buf].reshape(span, row).astype(product_dtype)
            sc = lax.dot_general(qb, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            pos = c * span + lax.broadcasted_iota(jnp.int32, (rows, span), 1)
            sc = jnp.where(pos <= length, sc, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m - m_new)
            v = v_buf[buf].reshape(span, row).astype(product_dtype)
            acc = acc * corr + jnp.dot(p.astype(product_dtype), v,
                                       preferred_element_type=jnp.float32)
            return (1 - buf, m_new,
                    l * corr + jnp.sum(p, axis=1, keepdims=True), acc)

        buf, _, l, acc = lax.fori_loop(
            0, chunks, attend,
            (buf, jnp.full((rows, 1), _NEG_INF, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, row), jnp.float32)))
        o_ref[pl.ds(s, 1), :] = jnp.sum(own * (acc / l), axis=0,
                                        keepdims=True).astype(o_ref.dtype)
        return buf

    lax.fori_loop(0, slots, slot, 0)


@functools.partial(jax.jit, static_argnames=("scale", "product_dtype"))
@jax.named_scope("kv_attend")
def paged_decode_context(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                         table: jax.Array, lengths: jax.Array, scale: float,
                         product_dtype=jnp.bfloat16) -> jax.Array:
    """Context of one decode step ``q`` [S, H, 1, D] over the pools as
    stored, ``[P, page_len, H*D]``, through ``table`` [S, W]: slot ``s``
    sees positions ``0 .. lengths[s]`` and the kernel brings in the pages
    that hold them and no others, so its cost follows the live pages and
    not the table's width (an evicted slot, length 0 and a cleared row,
    costs the null page). One program for all lengths: table and lengths
    are data (scalar prefetch). ``product_dtype`` is what the two matrix
    products multiply in, accumulating in float32: one bfloat16 pass as
    the TPU's default precision has it; float32 is for holding the
    arithmetic to :func:`~..attention.masked_context` in interpret mode.

    Jitted on its own, so that a step program traces and lowers the kernel
    once for all its blocks and not once a block (half a second each,
    in every process, compile cache or not); the scope is inside the
    ``jit``, innermost, where XLA takes the Mosaic call's name from."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, _, d = q.shape
    _, page_len, row = k_pool.shape
    width = table.shape[1]
    whole = pl.BlockSpec((slots, row), lambda i, *_: (0, 0),
                         memory_space=pltpu.VMEM)
    buf = pltpu.VMEM((2, PAGED_DECODE_CHUNK, page_len, row), k_pool.dtype)
    ctx = pl.pallas_call(
        functools.partial(_paged_decode_kernel, heads=heads, width=width,
                          product_dtype=product_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((slots, row), q.dtype),
    )(lengths.astype(jnp.int32), table.astype(jnp.int32).reshape(-1),
      (q * scale).reshape(slots, row), k_pool, v_pool)
    return ctx.reshape(slots, heads, 1, d)


def paged_pages_read(cache: PagedCache, table: jax.Array,
                     lengths: jax.Array, max_len: int) -> jax.Array:
    """Pages :func:`paged_attention` reads for all slots in one block of
    one step, from the form that runs: the live pages under the kernel,
    the rectangle ``S * max_len // page_len`` under the XLA form. The
    step program returns it beside the tokens
    (``serving.paged_pages_read``)."""
    page_len = cache["k"].shape[1]
    cols = max_len // page_len
    if _reads_in_place(cache, table[:, :cols]):
        return jnp.sum(jnp.minimum(lengths // page_len + 1, cols))
    return jnp.asarray(table.shape[0] * cols, jnp.int32)


def paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    cache: PagedCache, table: jax.Array,
                    lengths: jax.Array, max_len: int,
                    scale: Optional[float] = None
                    ) -> Tuple[jax.Array, PagedCache]:
    """One decode step over ALL slots through the page pool — the paged
    twin of :func:`slot_attention`: write each slot's new K/V at its own
    ``lengths[s]`` position (scattered to the owning page), then attend
    each slot's query against positions ``0 .. lengths[s]``.

    The XLA form gathers the first ``max_len // page_len`` table columns
    back into a logical ``[S, H, max_len, D]`` view and runs the SAME
    :func:`~..attention.masked_context` arithmetic over the SAME key
    length and visibility mask as :func:`slot_attention`: bit-identical to it,
    and what runs off the TPU. On the TPU the updated pool is read in
    place by :func:`paged_decode_context` where :func:`_paged_decode_rule`
    allows; that kernel agrees with the XLA form to the precision of one
    bfloat16 pass, and the benchmark's ``correct`` holds it on the chip
    (``served_logit_gap_max``). The pool returned is the same either way.

    ``q``/``k_new``/``v_new``: ``[S, H, 1, D]``; ``lengths``: [S] int32.
    The caller advances lengths once after every block attended, exactly
    as with the slot rectangles."""
    _, _, t, d = q.shape
    page_len = cache["k"].shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    pages, offs = _page_positions(table, lengths[:, None], page_len)
    cache = _paged_write(cache, pages, offs, k_new.transpose(0, 2, 1, 3),
                         v_new.transpose(0, 2, 1, 3), inline_amax=False)
    cols = table[:, :max_len // page_len]
    if _reads_in_place(cache, cols):
        return paged_decode_context(q, cache["k"], cache["v"], cols,
                                    lengths, scale), cache
    k_buf, v_buf = paged_gather(cache, cols, q.shape[1])
    key_pos = lax.broadcasted_iota(jnp.int32, (t, max_len), 1)
    visible = key_pos[None] <= lengths[:, None, None]   # [S, 1, max_len]
    ctx = masked_context(q, k_buf, v_buf, visible[:, None], scale)
    return ctx, cache


def paged_prefix_kv(cache: PagedCache, row: jax.Array, heads: int,
                    length: int) -> Tuple[jax.Array, jax.Array]:
    """K/V ``[1, H, length, D]`` of a shared prefix from the pages
    ``row`` [W] names, for the dense prefill of a suffix that joins
    behind it (``length`` is static). Always the XLA gather."""
    _note_xla_form("a shared-prefix join gathers the prefix's pages for "
                   "the dense prefill of its suffix")
    k, v = paged_gather(cache, row[None], heads)
    return k[:, :, :length], v[:, :, :length]


def paged_verify_attention(q: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, cache: PagedCache,
                           table: jax.Array, lengths: jax.Array,
                           scale: Optional[float] = None
                           ) -> Tuple[jax.Array, PagedCache]:
    """Speculative VERIFY step: feed T = k+1 tokens per slot in one pass —
    write their K/V at logical positions ``lengths[s]..lengths[s]+T-1``
    (crossing page boundaries as needed; transient positions past the
    allocation fall onto the null page) and attend causally within the new
    block on top of each slot's visible prefix. Same masked_context
    arithmetic as everywhere else; the extra gathered slack columns past
    ``max_len`` are masked to exact zeros. Per-row contexts match serial
    decode rows to float-reduction tolerance (the T-batched matmul may
    vectorize differently than T=1), which is why speculative parity is a
    TOKEN-identity guarantee, not a bit-identity one.

    ``q``/``k_new``/``v_new``: ``[S, H, T, D]``. Lengths advance by the
    caller-side ACCEPTED count, not T — rejected positions hold stale K/V
    that the next round overwrites at the same positions."""
    _, _, t, d = q.shape
    page_len = cache["k"].shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    _note_xla_form("the speculative verify step feeds k + 1 tokens a slot, "
                   "causal among themselves: the kernel is the T = 1 step's")
    positions = (lengths[:, None]
                 + lax.broadcasted_iota(jnp.int32, (q.shape[0], t), 1))
    pages, offs = _page_positions(table, positions, page_len)
    cache = _paged_write(cache, pages, offs, k_new.transpose(0, 2, 1, 3),
                         v_new.transpose(0, 2, 1, 3), inline_amax=False)
    k_buf, v_buf = paged_gather(cache, table, q.shape[1])
    kcols = table.shape[1] * page_len
    key_pos = lax.broadcasted_iota(jnp.int32, (t, kcols), 1)
    row_pos = lax.broadcasted_iota(jnp.int32, (t, kcols), 0)
    visible = key_pos[None] <= lengths[:, None, None] + row_pos[None]
    ctx = masked_context(q, k_buf, v_buf, visible[:, None], scale)
    return ctx, cache


def _decode_loop(step_fn, params, cache, prompt_last_token,
                 max_new_tokens, eos_id, select_fn, xs) -> jax.Array:
    """Shared scan scaffolding for greedy/sampled decoding: feed a token,
    select the next via ``select_fn(logits, x)``, force eos on finished
    rows. One dispatch for the whole sequence."""

    def body(carry, x):
        token, cache, done = carry
        logits, cache = step_fn(params, token, cache)
        nxt = select_fn(logits, x).astype(token.dtype)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.asarray(eos_id, token.dtype), nxt)
            done = done | (nxt == eos_id)
        return (nxt, cache, done), nxt

    done0 = jnp.zeros(prompt_last_token.shape, bool)
    (_, _, _), tokens = lax.scan(
        body, (prompt_last_token, cache, done0), xs,
        length=None if xs is not None else max_new_tokens)
    return jnp.swapaxes(tokens, 0, 1)  # [B, max_new]


def greedy_generate(step_fn: Callable, params: Any, cache: Any,
                    prompt_last_token: jax.Array, max_new_tokens: int,
                    eos_id: Optional[int] = None) -> jax.Array:
    """Single-dispatch greedy decoding loop.

    ``step_fn(params, token [B], cache) -> (logits [B, V], cache)`` is the
    user's per-token forward (typically built on :func:`cached_attention`).
    Each scan step FEEDS a token — i.e. appends its K/V and predicts the
    next — so prefill the prompt EXCLUDING its last token and pass that
    last token here; prefilling the whole prompt would insert the final
    token's K/V twice. The loop runs as ONE ``lax.scan`` of
    ``max_new_tokens`` steps; with ``eos_id``, finished rows keep emitting
    ``eos_id`` (output length stays static — XLA-friendly).

    Returns generated tokens ``[B, max_new_tokens]``.
    """
    return _decode_loop(step_fn, params, cache, prompt_last_token,
                        max_new_tokens, eos_id,
                        lambda logits, _: jnp.argmax(logits, axis=-1), None)


def beam_generate(step_fn: Callable, params: Any, cache: Any,
                  prompt_last_token: jax.Array, max_new_tokens: int,
                  beam_size: int, eos_id: Optional[int] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Beam-search decoding, one ``lax.scan`` dispatch.

    Same ``step_fn(params, token [N], cache) -> (logits [N, V], cache)``
    contract as :func:`greedy_generate`, where N is ``batch * beam_size``
    after tiling. Cache leaves whose leading axis equals the batch size are
    tiled ``beam_size``-fold and reordered by backpointer every step; a
    finished beam (emitted ``eos_id``) keeps its score and pads with eos.

    Returns ``(sequences [B, beam, max_new], scores [B, beam])`` sorted
    best-first by accumulated log-probability.
    """
    b = prompt_last_token.shape[0]
    k = beam_size

    def tile(a):
        if hasattr(a, "ndim") and a.ndim > 0 and a.shape[0] == b:
            return jnp.repeat(a, k, axis=0)
        return a

    caches = jax.tree_util.tree_map(tile, cache)
    tokens = jnp.repeat(prompt_last_token[:, None], k, axis=1)  # [B, K]
    # only beam 0 is live initially so the first expansion picks the top-k
    # distinct continuations instead of k copies of the argmax
    scores = jnp.tile(jnp.asarray([0.0] + [_NEG_INF] * (k - 1)), (b, 1))
    done = jnp.zeros((b, k), bool)
    seqbuf = jnp.zeros((b, k, max_new_tokens), prompt_last_token.dtype)

    def body(carry, i):
        tokens, scores, done, seqbuf, caches = carry
        logits, caches = step_fn(params, tokens.reshape(b * k), caches)
        v = logits.shape[-1]
        logp = jax.nn.log_softmax(
            logits.astype(jnp.float32), axis=-1).reshape(b, k, v)
        if eos_id is not None:
            # a finished beam may only "continue" with eos at zero cost
            eos_row = jnp.full((v,), _NEG_INF).at[eos_id].set(0.0)
            logp = jnp.where(done[..., None], eos_row[None, None], logp)
        cand = (scores[..., None] + logp).reshape(b, k * v)
        scores, idx = lax.top_k(cand, k)                   # [B, K]
        parent = idx // v
        token = (idx % v).astype(tokens.dtype)

        def reorder(a):
            if hasattr(a, "ndim") and a.ndim > 0 and a.shape[0] == b * k:
                ak = a.reshape((b, k) + a.shape[1:])
                sel = jnp.take_along_axis(
                    ak, parent.reshape((b, k) + (1,) * (a.ndim - 1)), axis=1)
                return sel.reshape((b * k,) + a.shape[1:])
            return a

        caches = jax.tree_util.tree_map(reorder, caches)
        seqbuf = jnp.take_along_axis(seqbuf, parent[..., None], axis=1)
        seqbuf = lax.dynamic_update_slice(
            seqbuf, token[..., None], (0, 0, i))
        done = jnp.take_along_axis(done, parent, axis=1)
        if eos_id is not None:
            done = done | (token == eos_id)
        return (token, scores, done, seqbuf, caches), None

    (tokens, scores, done, seqbuf, caches), _ = lax.scan(
        body, (tokens, scores, done, seqbuf, caches),
        jnp.arange(max_new_tokens))
    return seqbuf, scores


def make_logit_filter(temperature: float = 1.0, top_k: Optional[int] = None,
                      top_p: Optional[float] = None
                      ) -> Callable[[jax.Array], jax.Array]:
    """Build the sampling logit filter shared by :func:`sample_generate`
    and the slot-batched generative scheduler (serving/server.py).

    Filters compose in the standard order: temperature scales logits,
    ``top_k`` keeps the k highest, ``top_p`` keeps the smallest prefix of
    the sorted distribution with cumulative probability >= top_p; sampling
    renormalizes over what survives. Both decode paths composing THIS
    filter (not a re-implementation) is part of what keeps slot-batched
    sampled streams bit-identical to serial runs.
    """
    if temperature <= 0:
        raise ValueError("temperature must be > 0 (use greedy_generate "
                         "for deterministic argmax decoding)")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k} "
                         "(pass top_k=None to disable)")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p} "
                         "(pass top_p=None to disable)")

    def filter_logits(logits):
        logits = logits / temperature
        if top_k is not None:
            kth = lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, _NEG_INF, logits)
        if top_p is not None:
            sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep the smallest prefix reaching top_p (always >= 1 token)
            cutoff_idx = jnp.sum((cum - probs) < top_p, axis=-1,
                                 keepdims=True) - 1
            cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
            logits = jnp.where(logits < cutoff, _NEG_INF, logits)
        return logits

    return filter_logits


def sample_generate(step_fn: Callable, params: Any, cache: Any,
                    prompt_last_token: jax.Array, max_new_tokens: int,
                    rng: jax.Array, temperature: float = 1.0,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    eos_id: Optional[int] = None) -> jax.Array:
    """Stochastic decoding (temperature / top-k / nucleus), one scan
    dispatch — same ``step_fn`` contract as :func:`greedy_generate`.
    Filter semantics: :func:`make_logit_filter`. Finished rows keep
    emitting ``eos_id``.
    """
    filter_logits = make_logit_filter(temperature, top_k, top_p)

    def select(logits, step_rng):
        return jax.random.categorical(
            step_rng, filter_logits(logits.astype(jnp.float32)), axis=-1)

    return _decode_loop(step_fn, params, cache, prompt_last_token,
                        max_new_tokens, eos_id, select,
                        jax.random.split(rng, max_new_tokens))


# -- speculative decoding (draft proposes k, target verifies in one pass) ---
#
# Leviathan et al.: the decode step is memory-bandwidth-bound, so a small
# DRAFT model proposes k tokens serially and the TARGET verifies all k in
# ONE batched pass through its (paged) cache — one target dispatch emits
# between 1 and k+1 tokens. The accept/resample rule preserves the target
# distribution exactly; with greedy decoding it degenerates to "accept
# while the draft matches the target argmax", which makes speculative
# greedy TOKEN-IDENTICAL to serial greedy (the parity anchor the tests
# hold). Rejected draft positions leave stale K/V past the accepted
# length — invisible under the length mask and overwritten at the same
# positions next round, so the cache never needs a rollback copy.


def spec_accept_greedy(drafts: jax.Array, target_logits: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Greedy accept rule. ``drafts`` [S, k] are the draft proposals;
    ``target_logits`` [S, k+1, V] are the verify-pass logits (row j
    predicts the token AFTER feeding draft j). Returns ``(emitted [S,
    k+1], n [S])``: the target argmax row per position and how many lead
    entries are valid — ``n = 1 + (leading draft/argmax matches)``, so a
    fully-accepted round emits k+1 tokens (the free "bonus" token)."""
    g = jnp.argmax(target_logits, axis=-1)              # [S, k+1]
    match = (drafts == g[:, :-1]).astype(jnp.int32)
    lead = jnp.cumprod(match, axis=1)
    n = 1 + jnp.sum(lead, axis=1)
    return g, n


def _spec_accept_sampled(drafts, draft_logits, target_logits, key,
                         filter_logits):
    """Standard stochastic accept/resample rule: accept draft token d_i
    with probability min(1, p_i(d_i)/q_i(d_i)); at the first rejection
    resample from norm(max(p - q, 0)); when every draft survives, sample
    the bonus token from the target's k-th distribution (q := 0 there, so
    the residual IS p). Output-distribution-preserving, not run-identical
    to a serial sampled run (different rng consumption)."""
    s, k = drafts.shape
    p = jax.nn.softmax(filter_logits(target_logits.astype(jnp.float32)),
                       axis=-1)                          # [S, k+1, V]
    q = jax.nn.softmax(filter_logits(draft_logits.astype(jnp.float32)),
                       axis=-1)                          # [S, k, V]
    pd = jnp.take_along_axis(p[:, :k], drafts[..., None], axis=-1)[..., 0]
    qd = jnp.take_along_axis(q, drafts[..., None], axis=-1)[..., 0]
    key_u, key_x = jax.random.split(key)
    u = jax.random.uniform(key_u, (s, k))
    accept = (u * qd < pd).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)     # [S] in [0, k]
    q_pad = jnp.concatenate([q, jnp.zeros_like(p[:, :1])], axis=1)
    sel = m[:, None, None]
    pm = jnp.take_along_axis(p, jnp.broadcast_to(sel, (s, 1, p.shape[-1])),
                             axis=1)[:, 0]               # p_{m}  [S, V]
    qm = jnp.take_along_axis(q_pad,
                             jnp.broadcast_to(sel, (s, 1, p.shape[-1])),
                             axis=1)[:, 0]
    resid = jnp.maximum(pm - qm, 0.0)
    total = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(total > 0, resid, pm)  # p == q: residual undefined
    x = jax.random.categorical(
        key_x, jnp.where(resid > 0, jnp.log(resid), _NEG_INF), axis=-1)
    j = lax.broadcasted_iota(jnp.int32, (s, k + 1), 1)
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((s, 1), drafts.dtype)], axis=1)
    emitted = jnp.where(j < m[:, None], drafts_pad,
                        jnp.where(j == m[:, None], x[:, None].astype(
                            drafts.dtype), 0))
    return emitted, m + 1


def speculative_generate(draft_step_fn: Callable, verify_fn: Callable,
                         draft_params: Any, target_params: Any,
                         draft_cache: Any, target_cache: Any,
                         prompt_last_token: jax.Array, lengths: jax.Array,
                         max_new_tokens: int, spec_k: int,
                         eos_id: Optional[int] = None,
                         rng: Optional[jax.Array] = None,
                         temperature: float = 1.0,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None) -> jax.Array:
    """Speculative decoding driver: one ``lax.scan`` of at most
    ``max_new_tokens`` rounds, each round = ``spec_k`` serial DRAFT steps
    + ONE batched target VERIFY + vectorized accept.

    Contracts (lengths are PER-ROW, slot/paged style):

    - ``draft_step_fn(draft_params, tokens [B], lengths [B], draft_cache)
      -> (logits [B, V], draft_cache)``
    - ``verify_fn(target_params, block [B, k+1], lengths [B],
      target_cache) -> (logits [B, k+1, V], target_cache)``

    Greedy when ``rng is None`` (token-identical to serial greedy);
    otherwise samples with the standard accept/resample rule through the
    shared :func:`make_logit_filter` chain. Finished rows (eos / budget)
    freeze and the output pads with ``eos_id``. Returns ``[B,
    max_new_tokens]``."""
    b = prompt_last_token.shape[0]
    sampling = rng is not None
    filter_logits = (make_logit_filter(temperature, top_k, top_p)
                     if sampling else None)

    def round_body(carry, key):
        last, lengths, dcache, tcache, out, cursor, done = carry
        if sampling:
            subkeys = jax.random.split(key, spec_k + 1)

        def draft_body(c, i):
            tok, ln, dc = c
            logits, dc = draft_step_fn(draft_params, tok, ln, dc)
            if sampling:
                nxt = jax.random.categorical(
                    subkeys[i], filter_logits(logits.astype(jnp.float32)),
                    axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            nxt = nxt.astype(tok.dtype)
            return (nxt, ln + 1, dc), (nxt, logits)

        (_, _, dcache), (drafts, dlogits) = lax.scan(
            draft_body, (last, lengths, dcache), jnp.arange(spec_k))
        drafts = jnp.swapaxes(drafts, 0, 1)              # [B, k]
        block = jnp.concatenate([last[:, None], drafts], axis=1)
        tlogits, tcache = verify_fn(target_params, block, lengths, tcache)
        if sampling:
            emitted, n = _spec_accept_sampled(
                drafts, jnp.swapaxes(dlogits, 0, 1), tlogits,
                subkeys[spec_k], filter_logits)
        else:
            emitted, n = spec_accept_greedy(drafts, tlogits)
        emitted = emitted.astype(last.dtype)
        n = jnp.where(done, 0, n)
        n = jnp.minimum(n, max_new_tokens - cursor)       # budget clamp
        j = lax.broadcasted_iota(jnp.int32, (b, spec_k + 1), 1)
        if eos_id is not None:
            iseos = (emitted == eos_id) & (j < n[:, None])
            first = jnp.min(jnp.where(iseos, j, spec_k + 1), axis=1)
            n = jnp.minimum(n, first + 1)
            done = done | jnp.any(iseos, axis=1)
        valid = j < n[:, None]
        pos = jnp.where(valid, cursor[:, None] + j, max_new_tokens)
        rows = lax.broadcasted_iota(jnp.int32, (b, spec_k + 1), 0)
        out = out.at[rows, pos].set(emitted, mode="drop")
        last = jnp.where(
            n > 0,
            jnp.take_along_axis(emitted, jnp.maximum(n - 1, 0)[:, None],
                                axis=1)[:, 0],
            last)
        lengths = lengths + n
        cursor = cursor + n
        done = done | (cursor >= max_new_tokens)
        return (last, lengths, dcache, tcache, out, cursor, done), n

    fill = eos_id if eos_id is not None else 0
    out0 = jnp.full((b, max_new_tokens), fill, prompt_last_token.dtype)
    carry0 = (prompt_last_token, jnp.asarray(lengths, jnp.int32),
              draft_cache, target_cache, out0,
              jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool))
    xs = (jax.random.split(rng, max_new_tokens) if sampling
          else jnp.zeros((max_new_tokens,), jnp.uint32))
    (_, _, _, _, out, _, _), _ = lax.scan(round_body, carry0, xs)
    return out
