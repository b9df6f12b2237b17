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

On one TPU chip both read the stream's pages from the pool in place. The
decode step (:func:`_attend_step_kernel`, the kernel of ``ops/decode.py
paged_decode_context`` for grouped-query heads) reads from the first page a
slot's window touches to the page of its length and no other, so the step's
cost follows the live keys. The chunk (:func:`_attend_chunk_kernel`) takes
a block of query rows of one key/value head at a time against the key
blocks those rows may see, from the block of the first key the block's
first row sees to the block of its last row: scores, probabilities and the
rescaled accumulator stay in VMEM, and a key block that the causal or the
window mask empties for the whole block of rows is neither fetched nor
computed. The chunk kernel also takes a page mask, which pages each row
reads, for the block-sparse layers of ``ops/sparse_attention.py``.
Elsewhere (off the TPU, a program over several devices, pages, heads or
chunks that are no whole tiles and blocks) the XLA form above runs, and on
the TPU that is noted once with the rule (``grouped_paged_decode``,
``grouped_chunk_attend``).

The caller has written the queries' own K and V before it reads. Scopes:
``attn_full`` and ``attn_window`` (docs/observability.md)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import dispatch
from .decode import (PAGED_DECODE_CHUNK, PAGED_DECODE_TABLE_BYTES,
                     _paged_decode_rule)

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


#: the chunk kernel's blocks: query rows a program, keys a step of its loop
#: (whole pages; positions that are multiples of it bound the blocks)
CHUNK_KERNEL_BLOCKS = (512, 512)


def _chunk_kernel_rule(q, cache, row, blocks,
                       page_mask: bool = False) -> Optional[str]:
    """Why a chunk cannot run :func:`_attend_chunk_kernel` with ``blocks``
    (None: it can): ``ops/decode.py``'s rule for reading a pool in place,
    and queries that are whole blocks of rows and of 128 lanes. Under a
    ``page_mask`` a block of rows is also whole lanes of the mask's block,
    and the table with the key blocks' codes fits the scalar memory."""
    rule = _paged_decode_rule(cache, row)
    if rule is not None:
        return rule
    t, kv, _, d = q.shape
    page_len = cache["k"].shape[1]
    rows, keys = min(blocks[0], t), blocks[1]
    if d % 128 or t % rows or rows % (32 // cache["k"].dtype.itemsize) \
            or keys % page_len or (page_mask and rows % 128 and rows != t):
        return (f"queries [{t}, {d}] over pages of {page_len} are no whole "
                f"blocks of {blocks} rows and keys and 128 lanes")
    if page_mask:
        codes = kv * (t // rows) * -(-row.shape[0] * page_len // keys)
        if (row.shape[0] + codes) * 4 > PAGED_DECODE_TABLE_BYTES:
            return (f"a table row of {row.shape[0]} pages and {codes} key "
                    f"block codes exceed the {PAGED_DECODE_TABLE_BYTES}-byte "
                    f"scalar prefetch budget")
    return None


def _chunk_kernel(start_ref, row_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                  v_buf, sem, qt_ref, acc_ref, m_ref, l_ref, *,
                  window: Optional[int], page_mask=None):
    """One key/value head's block of query rows (positions ``low ..
    high``) against the key blocks it may see, in turn: from the block of
    the first key the block's first row sees (0 in a full layer) to the
    block of ``high``, and no other. A key block's pages come from the pool
    in HBM through ``row`` (one DMA a page for K and one for V, this
    head's ``D`` lanes of the stored row; the next block's are started
    before this one's are waited for) and serve the head's query heads
    (each ``D`` lanes of ``q_ref``) in turn.

    Scores are taken keys by rows (``K q^T``, the queries transposed once a
    program), so that the running softmax's maxima and sums over the keys
    are sums of whole registers and its statistics lie along the lanes:
    the other way round, a reduction along the lanes for every row of
    every block took two fifths of the kernel's time (PERF.md, PR 34).
    The accumulator is ``V^T p`` and is transposed back at the end. A block
    that every row sees whole skips the mask; elsewhere the mask comes
    from the positions. Scores and probabilities never leave VMEM.

    ``page_mask`` (a block-sparse layer, :func:`_page_masked_chunk_kernel`)
    is ``(whole_ref, ok_ref)``: which pages each row reads, ``ok_ref [1,
    key blocks, pages, rows]`` (int8), and for each (head, block of rows,
    key block) whether every row reads every page of it and lies past it
    (``whole_ref``, flat, in scalar memory): that block skips the mask, and
    elsewhere the rows' pages are expanded along their positions and join
    the causal mask."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    head, block = pl.program_id(0), pl.program_id(1)
    rows = q_ref.shape[0]
    _, pages, page_len, d = k_buf.shape
    groups, span, width = m_ref.shape[0], pages * page_len, row_ref.shape[0]
    lanes = pl.ds(pl.multiple_of(head * d, d), d)
    heads = [slice(g * d, (g + 1) * d) for g in range(groups)]
    low = start_ref[0] + block * rows
    high = low + rows - 1
    first = 0 if window is None \
        else jnp.maximum(low - window + 1, 0) // span
    count = high // span + 1 - first

    def each_page(b, buf, act):
        """``act`` on the copies of key block ``first + b`` into ``buf``
        (past the table's width: its last page, as the XLA form reads)."""
        def one(j, carry):
            page = row_ref[jnp.minimum((first + b) * pages + j, width - 1)]
            act(pltpu.make_async_copy(k_hbm.at[page, :, lanes],
                                      k_buf.at[buf, j], sem.at[0, buf]))
            act(pltpu.make_async_copy(v_hbm.at[page, :, lanes],
                                      v_buf.at[buf, j], sem.at[1, buf]))
            return carry
        lax.fori_loop(0, pages, one, 0)

    each_page(0, 0, lambda dma: dma.start())
    for own in heads:
        qt_ref[own, :] = q_ref[:, own].T.astype(qt_ref.dtype)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)

    def attend(buf, seen):
        k = k_buf[buf].reshape(span, d)
        v = v_buf[buf].reshape(span, d)

        def head(g, own):
            sc = jnp.dot(k, qt_ref[own, :],
                         preferred_element_type=jnp.float32)  # [span, rows]
            if seen is not None:
                sc = jnp.where(seen, sc, _NEG)
            m = m_ref[g]
            m_new = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
            p = jnp.exp(sc - m_new)
            if seen is not None:
                p = jnp.where(seen, p, 0.0)
            corr = jnp.exp(m - m_new)
            l_ref[g] = l_ref[g] * corr + jnp.sum(p, axis=0, keepdims=True)
            m_ref[g] = m_new
            acc_ref[own, :] = acc_ref[own, :] * corr + lax.dot_general(
                v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [d, rows]

        if seen is None or page_mask is not None:
            # under a page mask most blocks are masked: unrolled, one
            # head's products run under another's exponentials
            for g, own in enumerate(heads):
                head(g, own)
        else:
            # a masked block comes once or twice a program: a loop over
            # the heads is a tenth slower there and a third of the
            # kernel's code (the chunk programs hold 21 such kernels)
            def one(g, carry):
                head(g, pl.ds(pl.multiple_of(g * d, d), d))
                return carry
            lax.fori_loop(0, groups, one, 0)

    def step(b, buf):
        @pl.when(b + 1 < count)
        def _next_block():
            each_page(b + 1, 1 - buf, lambda dma: dma.start())

        each_page(b, buf, lambda dma: dma.wait())
        base = (first + b) * span
        if page_mask is None:
            whole = base + span - 1 <= low
            if window is not None:
                whole &= base > high - window
        else:
            whole_ref, ok_ref = page_mask
            whole = whole_ref[(head * pl.num_programs(1) + block)
                              * ok_ref.shape[1] + first + b] != 0

        @pl.when(whole)
        def _every_row_sees_every_key():
            attend(buf, None)

        @pl.when(jnp.logical_not(whole))
        def _masked():
            pos = base + lax.broadcasted_iota(jnp.int32, (span, rows), 0)
            at = low + lax.broadcasted_iota(jnp.int32, (span, rows), 1)
            seen = pos <= at
            if window is not None:
                seen &= pos > at - window
            if page_mask is not None:
                ok = ok_ref[0, first + b].astype(jnp.int32)  # [pages, rows]
                seen &= jnp.broadcast_to(
                    ok[:, None], (pages, page_len, rows)).reshape(
                        span, rows) != 0
            attend(buf, seen)
        return 1 - buf

    lax.fori_loop(0, count, step, 0)
    for g, own in enumerate(heads):
        o_ref[:, own] = (acc_ref[own, :] / jnp.maximum(l_ref[g], 1e-30)).T


def _page_masked_chunk_kernel(start_ref, row_ref, whole_ref, q_ref, ok_ref,
                              k_hbm, v_hbm, o_ref, *scratch):
    """:func:`_chunk_kernel` of a full layer whose rows read only some
    pages (``page_mask``)."""
    _chunk_kernel(start_ref, row_ref, q_ref, k_hbm, v_hbm, o_ref, *scratch,
                  window=None, page_mask=(whole_ref, ok_ref))


def _page_codes(allowed, start, rows: int, pages: int, page_len: int):
    """From ``allowed [T, KV, W]`` (which pages each row reads) to the
    kernel's operands: the mask as int8 ``[KV, key blocks, pages, T]`` (a
    key block is ``pages`` pages, the last one padded with pages no row
    reads), and for each (head, block of ``rows`` rows, key block), flat
    in that order, 1 where every row of the block reads every page of the
    key block and lies past its last position (the block is whole), else
    0 (masked)."""
    t, kv, w = allowed.shape
    n = -(-w // pages)
    ok = jnp.pad(allowed, ((0, 0), (0, 0), (0, n * pages - w)))
    ok = ok.reshape(t, kv, n, pages)
    every = jnp.all(ok.reshape(t // rows, rows, kv, n, pages), axis=(1, 4))
    low = start + rows * jnp.arange(t // rows)
    past = (jnp.arange(n) + 1) * pages * page_len - 1 <= low[:, None]
    whole = (every & past[:, None]).transpose(1, 0, 2)   # [KV, T/rows, n]
    return (ok.transpose(1, 2, 3, 0).astype(jnp.int8),
            whole.astype(jnp.int32).reshape(-1))


@functools.partial(jax.jit, static_argnames=("window", "blocks", "scope"))
def _attend_chunk_kernel(q, k_pool, v_pool, row, start, window, blocks,
                         allowed=None, scope=None):
    """:func:`attend_chunk` with the pool read in place and the scores kept
    in VMEM: a grid of (key/value head, block of ``blocks[0]`` query rows),
    each program walking its own key blocks of ``blocks[1]`` positions.
    Jitted on its own with the scope innermost, as
    :func:`_attend_step_kernel` is. ``allowed [T, KV, W]``, where given,
    says which pages each row reads (a block-sparse full layer, scope
    ``scope``): the kernel takes it as a mask a key block
    (:func:`_page_masked_chunk_kernel`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with jax.named_scope(scope) if scope else _scope(window):
        t, kv, g, d = q.shape
        _, page_len, _ = k_pool.shape
        rows, pages = min(blocks[0], t), blocks[1] // page_len
        heads = pl.BlockSpec((rows, g * d), lambda h, i, *_: (i, h),
                             memory_space=pltpu.VMEM)
        buf = pltpu.VMEM((2, pages, page_len, d), k_pool.dtype)
        stat = pltpu.VMEM((g, 1, rows), jnp.float32)
        kernel = functools.partial(_chunk_kernel, window=window)
        scalars = (jnp.asarray(start, jnp.int32).reshape(1),
                   row.astype(jnp.int32))
        in_specs = [heads, pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY)]
        operands = (q.reshape(t, kv * g * d), k_pool, v_pool)
        if allowed is not None:
            ok, whole = _page_codes(allowed, start, rows, pages, page_len)
            kernel = _page_masked_chunk_kernel
            scalars += (whole,)
            in_specs.insert(1, pl.BlockSpec(
                (1, ok.shape[1], pages, rows), lambda h, i, *_: (h, 0, 0, i),
                memory_space=pltpu.VMEM))
            operands = operands[:1] + (ok,) + operands[1:]
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(kv, t // rows),
                in_specs=in_specs,
                out_specs=heads,
                scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                                pltpu.VMEM((g * d, rows), k_pool.dtype),
                                pltpu.VMEM((g * d, rows), jnp.float32),
                                stat, stat]),
            out_shape=jax.ShapeDtypeStruct((t, kv * g * d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=64 * 1024 * 1024),
        )(*scalars, *operands)
        return out.reshape(t, kv, g, d)


def attend_chunk(q: jax.Array, cache, row: jax.Array, start,
                 window: Optional[int] = None,
                 tile_pages: int = 8) -> jax.Array:
    """Prefill: a chunk's queries (``q [T, KV, G, D]`` scaled, at positions
    ``start ..``) over the stream's pages ``row [W]``, the chunk's own
    among them; on one TPU chip the kernel, which visits for each block of
    rows the key blocks it may see. Returns ``[T, KV, G, D]`` float32."""
    if dispatch.on_tpu():
        rule = _chunk_kernel_rule(q, cache, row, CHUNK_KERNEL_BLOCKS)
        if rule is None:
            return _attend_chunk_kernel(q, cache["k"], cache["v"], row,
                                        start, window, CHUNK_KERNEL_BLOCKS)
        dispatch.note_fallback("grouped_chunk_attend", rule)
    with _scope(window):
        page_len = cache["k"].shape[1]
        t = start + jnp.arange(q.shape[0], dtype=jnp.int32)
        first = first_page(t[:1], window, page_len)
        tile = tile_pages * page_len
        n_tiles = (t[-1] - first[0] * page_len + tile) // tile
        return _attend(q[None], cache, row[None], t[None], first, n_tiles,
                       window, tile_pages)[0]
