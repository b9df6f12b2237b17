"""Causal softmax attention of grouped-query heads over a K/V page pool
(``ops/decode.py``'s layout, ``[P, page_len, KV*D]``), read through page
tables: a **full** layer's query sees every key up to its own position, a
**window** layer's the last ``window`` positions, its own among them. The
same code with two masks, for a decode step (one query a slot, each slot
its own pages) and for a chunk of one stream's prompt (many queries, one
row of pages): tiles of ``tile_pages`` pages with a running softmax, from
the page that holds the first key a query may see to the page of the last
query, so a window layer never touches what lies behind its window and a
full layer stops at the longest live stream. Scores are float32; the
products take the pool's dtype with float32 accumulation.

On one TPU chip the decode step reads each stream's pages from the pool in
place (:func:`_attend_step_kernel`, the kernel of ``ops/decode.py
paged_decode_context`` for grouped-query heads): from the first page its
window touches to the page of its length and no other, so the step's cost
follows the live keys. Elsewhere (off the TPU, a program over several
devices, pages that are no whole tiles) the XLA form above runs, and on the
TPU that is noted once with the rule.

The caller has written the queries' own K and V before it reads. Scopes:
``attn_full`` and ``attn_window`` (docs/observability.md)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import dispatch
from .decode import PAGED_DECODE_CHUNK, _paged_decode_rule

_NEG = -1e30


def window_pages(window: int, page_len: int) -> int:
    """Pages that the ``window`` positions a query sees can lie on."""
    return window // page_len + 1


def first_page(t, window: Optional[int], page_len: int):
    """The page of the first key that the query at position ``t`` sees."""
    if window is None:
        return jnp.zeros_like(t)
    return jnp.maximum(t - window + 1, 0) // page_len


def _attend(q, cache, rows, t, first, n_tiles, window, tile_pages):
    """``q [B, T, KV, G, D]`` (scaled) at positions ``t [B, T]`` over the
    pages ``rows [B, W]``, tiles from page ``first [B]`` on."""
    b, t_len, kv, g, d = q.shape
    page_len = cache["k"].shape[1]
    w = rows.shape[1]
    tile = tile_pages * page_len
    qc = q.astype(cache["k"].dtype)

    def body(i, carry):
        top, total, acc = carry
        at = first[:, None] + i * tile_pages + jnp.arange(tile_pages)
        pages = jnp.take_along_axis(rows, jnp.minimum(at, w - 1), axis=1)
        k = jnp.take(cache["k"], pages, axis=0, mode="clip").reshape(
            b, tile, kv, d)
        v = jnp.take(cache["v"], pages, axis=0, mode="clip").reshape(
            b, tile, kv, d)
        scores = jnp.einsum("btkgd,bnkd->btkgn", qc, k,
                            preferred_element_type=jnp.float32)
        pos = (at[:, :, None] * page_len
               + jnp.arange(page_len)).reshape(b, 1, tile)
        ok = pos <= t[:, :, None]
        if window is not None:
            ok &= pos > t[:, :, None] - window
        ok = ok[:, :, None, None]                       # [B, T, 1, 1, n]
        scores = jnp.where(ok, scores, _NEG)
        new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
        p = jnp.where(ok, jnp.exp(scores - new_top[..., None]), 0.0)
        scale = jnp.exp(top - new_top)
        total = total * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "btkgn,bnkd->btkgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return new_top, total, acc

    init = (jnp.full((b, t_len, kv, g), _NEG, jnp.float32),
            jnp.zeros((b, t_len, kv, g), jnp.float32),
            jnp.zeros((b, t_len, kv, g, d), jnp.float32))
    _, total, acc = lax.fori_loop(0, n_tiles, body, init)
    return acc / jnp.maximum(total, 1e-30)[..., None]


def _scope(window):
    return jax.named_scope("attn_full" if window is None else "attn_window")


def _reads_in_place(cache, table) -> bool:
    """The kernel on one TPU chip where ``ops/decode.py``'s rule for reading
    a pool in place holds (whole tiles, one device, a table that fits the
    scalar memory); otherwise the XLA form, noted once on the TPU."""
    rule = _paged_decode_rule(cache, table)
    if rule is not None and dispatch.on_tpu():
        dispatch.note_fallback("grouped_paged_decode", rule)
    return rule is None and dispatch.on_tpu()


def _step_kernel(len_ref, first_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sem, *, width: int, window: Optional[int]):
    """All slots of one layer's decode step. For slot ``s`` the pages
    ``table[s, first[s] .. lengths[s] // page_len]`` come from the pool in
    HBM into VMEM a chunk at a time (one DMA a page for K and one for V;
    the next chunk's, or the next slot's first, are started before this
    one is waited for), and a float32 online softmax runs for all query
    heads at once.

    The stored row is ``KV*D`` lanes with key/value head ``g`` in lanes
    ``[g*D, (g+1)*D)``. ``q_ref[s]`` is ``[rows, KV*D]``: row ``h`` holds
    query head ``h`` in the lanes of its key/value head and zeros
    elsewhere, so one product with the chunk ``[chunk*page_len, KV*D]``
    gives every head's scores, and ``p @ V`` every head's weights over all
    lanes, of which the caller keeps row ``h``'s own. Pages outside
    ``first .. length`` are neither copied nor counted (V is zeroed once,
    so that what the buffer keeps is finite); positions a query does not
    see score ``_NEG`` and weigh exactly 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, rows, row = q_ref.shape
    _, chunk, page_len, _ = k_buf.shape
    span = chunk * page_len
    v_buf[...] = jnp.zeros_like(v_buf)

    def pages_of(s):
        return jnp.minimum(len_ref[s] // page_len + 1, width) - first_ref[s]

    def each_page(s, c, buf, act):
        """``act`` on the copies of chunk ``c`` of slot ``s`` into ``buf``."""
        def one(j, carry):
            page = table_ref[s * width + first_ref[s] + c * chunk + j]
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
        qb = q_ref[s]

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
            k = k_buf[buf].reshape(span, row)
            sc = lax.dot_general(qb, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            pos = (first_ref[s] + c * chunk) * page_len \
                + lax.broadcasted_iota(jnp.int32, (rows, span), 1)
            seen = pos <= length
            if window is not None:
                seen &= pos > length - window
            sc = jnp.where(seen, sc, _NEG)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.where(seen, jnp.exp(sc - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            v = v_buf[buf].reshape(span, row)
            acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                       preferred_element_type=jnp.float32)
            return (1 - buf, m_new,
                    l * corr + jnp.sum(p, axis=1, keepdims=True), acc)

        buf, _, l, acc = lax.fori_loop(
            0, chunks, attend,
            (buf, jnp.full((rows, 1), _NEG, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, row), jnp.float32)))
        o_ref[s] = acc / jnp.maximum(l, 1e-30)
        return buf

    lax.fori_loop(0, slots, slot, 0)


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_step_kernel(q, k_pool, v_pool, table, lengths, window):
    """:func:`attend_step` with the pool read in place. Jitted on its own,
    so that a step program traces and lowers the kernel once for all its
    layers of a kind; the scope is inside the ``jit``, innermost, where XLA
    takes the Mosaic call's name from."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with _scope(window):
        slots, kv, g, d = q.shape
        _, page_len, row = k_pool.shape
        heads, width = kv * g, table.shape[1]
        rows = -(-heads // 16) * 16
        group = jnp.arange(heads) // g
        own = group[:, None] == jnp.arange(kv)[None]           # [heads, KV]
        qb = (q.reshape(slots, heads, 1, d)
              * own[None, :, :, None]).reshape(slots, heads, row)
        qb = jnp.pad(qb, ((0, 0), (0, rows - heads), (0, 0))).astype(
            k_pool.dtype)
        whole = pl.BlockSpec((slots, rows, row), lambda i, *_: (0, 0, 0),
                             memory_space=pltpu.VMEM)
        buf = pltpu.VMEM((2, PAGED_DECODE_CHUNK, page_len, row),
                         k_pool.dtype)
        out = pl.pallas_call(
            functools.partial(_step_kernel, width=width, window=window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(1,),
                in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=whole,
                scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))]),
            out_shape=jax.ShapeDtypeStruct((slots, rows, row), jnp.float32),
        )(lengths.astype(jnp.int32),
          first_page(lengths, window, page_len).astype(jnp.int32),
          table.astype(jnp.int32).reshape(-1), qb, k_pool, v_pool)
        # row h's own lanes: its key/value head's (take_along_axis: a
        # slice-and-stack over the heads is miscompiled on the chip,
        # PERF.md, PR 27)
        out = out[:, :heads].reshape(slots, heads, kv, d)
        out = jnp.take_along_axis(out, group.reshape(1, heads, 1, 1), axis=2)
        return out[:, :, 0].reshape(slots, kv, g, d)


def attend_step(q: jax.Array, cache, table: jax.Array, lengths: jax.Array,
                active: jax.Array, window: Optional[int] = None,
                tile_pages: int = 16) -> jax.Array:
    """Decode: the query of every slot (``q [S, KV, G, D]`` scaled, at
    position ``lengths [S]``) over that slot's pages ``table [S, W]``. As
    many tiles as the longest active stream's span needs; on one TPU chip
    the kernel, which walks each slot's own span. Returns ``[S, KV, G, D]``
    float32."""
    if _reads_in_place(cache, table):
        return _attend_step_kernel(q, cache["k"], cache["v"], table, lengths,
                                   window)
    with _scope(window):
        page_len = cache["k"].shape[1]
        first = first_page(lengths, window, page_len)
        span = jnp.where(active, lengths - first * page_len + 1, 0)
        tile = tile_pages * page_len
        n_tiles = (jnp.max(span) + tile - 1) // tile
        return _attend(q[:, None], cache, table, lengths[:, None], first,
                       n_tiles, window, tile_pages)[:, 0]


def attend_chunk(q: jax.Array, cache, row: jax.Array, start,
                 window: Optional[int] = None,
                 tile_pages: int = 8) -> jax.Array:
    """Prefill: a chunk's queries (``q [T, KV, G, D]`` scaled, at positions
    ``start ..``) over the stream's pages ``row [W]``, the chunk's own
    among them. Returns ``[T, KV, G, D]`` float32."""
    with _scope(window):
        page_len = cache["k"].shape[1]
        t = start + jnp.arange(q.shape[0], dtype=jnp.int32)
        first = first_page(t[:1], window, page_len)
        tile = tile_pages * page_len
        n_tiles = (t[-1] - first[0] * page_len + tile) // tile
        return _attend(q[None], cache, row[None], t[None], first, n_tiles,
                       window, tile_pages)[0]
