"""Block-sparse softmax attention over the paged K/V pool (InfLLM-V2's
scheme, as MiniCPM's ``minicpm4`` layers use it).

Up to ``dense_len`` positions of context a query reads every key. Beyond
it, it reads a selection of blocks of ``block_size`` positions (one block is
one page of the pool): the first ``init_blocks``, the ``local_blocks`` that
end with its own (they hold the last ``window_size`` positions), and the
``topk`` highest-scoring others. A block's score comes from *compressed
keys*: the mean of ``kernel_size`` keys every ``kernel_stride`` positions,
kept in a pool of their own beside the K/V pages (``kc``: for each page the
windows that start in it). The query's softmax over the compressed keys it
can see (those that end before it), summed over the heads of its key/value
head's group, is pooled to blocks by the largest value among the windows
that touch a block; one selection a key/value head.

Scopes on the device: ``kv_compress`` (the compressed-key write),
``sparse_select`` (scores, pooling, top-k), ``sparse_attend`` (the gather
of the selected pages and the softmax over them); the page write itself is
``kv_write`` of ``ops/decode.py``. Selection scores are float32.

Two forms of each, as everywhere in the serving path: ``*_step`` for one
position of every slot (decode), ``*_chunk`` for a stretch of one stream's
prompt (chunked prefill). The decode read gathers the selected pages only,
never the table's whole width; a chunk computes block-masked attention over
the stream's pages so far: on one TPU chip with the grouped-query chunk
kernel of ``ops/grouped_attention.py`` under the selection as its page mask
(noted once where its rule refuses: ``sparse_chunk_attend``), elsewhere a
tile of pages at a time."""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import dispatch, grouped_attention

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if self.block_size % self.kernel_stride \
                or self.kernel_size % self.kernel_stride:
            raise ValueError("kernel_stride must divide block_size and "
                             "kernel_size")
        if self.dense_len % self.block_size \
                or self.window_size % self.block_size:
            raise ValueError("block_size must divide dense_len and "
                             "window_size")
        if self.dense_len // self.block_size < self.n_selected:
            raise ValueError(
                f"dense_len {self.dense_len} holds fewer blocks than a "
                f"selection reads ({self.n_selected}): beyond it the "
                f"initial, local and top-k blocks would overlap")

    @property
    def per_block(self) -> int:
        """Compressed-key windows that start in one block."""
        return self.block_size // self.kernel_stride

    @property
    def spans(self) -> int:
        """Windows that hold one position."""
        return self.kernel_size // self.kernel_stride

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size + 1

    @property
    def n_selected(self) -> int:
        return self.init_blocks + self.local_blocks + self.topk

    @property
    def dense_blocks(self) -> int:
        return self.dense_len // self.block_size


def init_sparse_pool(num_pages: int, spec: SparseSpec, kv_heads: int,
                     head_dim: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """K/V pages ``[P, block_size, KV*D]`` (the layout of
    ``ops/decode.py``'s pools; page 0 is the null page) and the
    compressed-key pool ``kc`` ``[P, per_block, KV*D]`` in float32."""
    shape = (num_pages, spec.block_size, kv_heads * head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "kc": jnp.zeros((num_pages, spec.per_block, shape[2]),
                            jnp.float32)}


# -- the compressed-key write ---------------------------------------------------

@jax.named_scope("kv_compress")
def compress_step(spec: SparseSpec, kc: jax.Array, table: jax.Array,
                  lengths: jax.Array, k_new: jax.Array) -> jax.Array:
    """Add each slot's new key ``k_new [S, KV*D]`` (float32, at position
    ``lengths[s]``) to the windows that hold that position. The window that
    starts there is set, not added to: a page that another stream left
    behind needs no zeroing."""
    stride, per = spec.kernel_stride, spec.per_block
    j0 = lengths // stride
    j = j0[:, None] - jnp.arange(spec.spans)[None]            # [S, spans]
    starts = (lengths % stride == 0)[:, None] & (j == j0[:, None])
    w = table.shape[1]
    page = jnp.take_along_axis(table, jnp.clip(j // per, 0, w - 1), axis=1)
    page = jnp.where((j >= 0) & (j // per < w), page, 0)
    slot = jnp.where(j >= 0, j % per, 0)
    old = kc[page, slot]                                      # [S, spans, KVD]
    new = jnp.where(starts[..., None], 0.0, old) \
        + k_new[:, None].astype(jnp.float32) / spec.kernel_size
    return kc.at[page, slot].set(new)


@jax.named_scope("kv_compress")
def compress_chunk(spec: SparseSpec, kc: jax.Array, row: jax.Array, start,
                   k_new: jax.Array, n_valid) -> jax.Array:
    """The windows of one stream's chunk: ``k_new [T, KV*D]`` (float32) at
    positions ``start .. start+T-1``, of which the first ``n_valid`` are
    real; ``start`` and ``T`` are multiples of the block. Windows that start
    in the chunk are set (whole pages of ``kc``); the ``spans - 1`` windows
    that reach in from before it are added to."""
    t, width = k_new.shape
    stride, per, block = spec.kernel_stride, spec.per_block, spec.block_size
    real = jnp.arange(t) < n_valid
    parts = jnp.where(real[:, None], k_new.astype(jnp.float32), 0.0)
    parts = parts.reshape(t // stride, stride, width).sum(axis=1) \
        / spec.kernel_size                                  # [T/stride, KVD]
    padded = jnp.pad(parts, ((0, spec.spans - 1), (0, 0)))
    inside = sum(padded[i:i + t // stride] for i in range(spec.spans))
    first_page = start // block
    pages = lax.dynamic_slice_in_dim(
        jnp.pad(row, (0, t // block)), first_page, t // block)
    kc = kc.at[pages].set(inside.reshape(t // block, per, width))
    for back in range(1, spec.spans):       # windows from before the chunk
        j = start // stride - back
        reach = sum(parts[i] for i in range(spec.spans - back))
        page = jnp.where(j >= 0, row[jnp.clip(j // per, 0,
                                              row.shape[0] - 1)], 0)
        slot = jnp.where(j >= 0, j % per, 0)
        kc = kc.at[page, slot].add(reach)
    return kc


# -- the selection ----------------------------------------------------------------

def _block_scores(spec: SparseSpec, scores: jax.Array, t: jax.Array,
                  n_blocks: int) -> jax.Array:
    """From ``scores [..., G, W]`` (``q . Kc`` of the ``G`` heads of one
    key/value head over ``W`` windows) and the queries' positions ``t
    [...]`` to block scores ``[..., n_blocks]``; a window is seen if it ends
    before the query."""
    stride, per = spec.kernel_stride, spec.per_block
    w = scores.shape[-1]
    seen = (jnp.arange(w) * stride + spec.kernel_size) <= t[..., None]
    seen_h = seen[..., None, :]
    masked = jnp.where(seen_h, scores.astype(jnp.float32), _NEG)
    top = jnp.max(masked, axis=-1, keepdims=True)
    e = jnp.where(seen_h, jnp.exp(masked - top), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    p = jnp.where(seen, jnp.sum(p, axis=-2), -1.0)            # [..., W]
    lead = [(0, 0)] * (p.ndim - 1)
    pad = jnp.pad(p, lead + [(1, per * n_blocks + per)], constant_values=-1.0)
    return jnp.max(jnp.stack(
        [pad[..., i:i + per * n_blocks:per] for i in range(per + 1)]), axis=0)


def _roles(spec: SparseSpec, t: jax.Array, n_blocks: int):
    """``(forced, others)`` masks ``[..., n_blocks]`` for queries at ``t``."""
    b = jnp.arange(n_blocks)
    own = (t // spec.block_size)[..., None]
    forced = (b < spec.init_blocks) | (
        (b > own - spec.local_blocks) & (b <= own))
    return forced, ~forced & (b < own)


@jax.named_scope("sparse_select")
def select_step(spec: SparseSpec, q: jax.Array, kc: jax.Array,
                table: jax.Array, lengths: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Decode: for the query of every slot (``q [S, KV, G, D]``, scaled,
    at position ``lengths[s]``) the blocks it reads beyond ``dense_len``:
    ``(blocks [S, KV, n_selected], valid)``. The compressed keys of a stream
    are read through its table row (the indexer reads every window; they
    are a sixteenth of the keys)."""
    s, kv, g, d = q.shape
    w = table.shape[1]
    keys = jnp.take(kc, table, axis=0, mode="clip")       # [S, W, per, KVD]
    keys = keys.reshape(s, w * spec.per_block, kv, d)
    scores = jnp.einsum("skgd,swkd->skgw", q.astype(jnp.float32), keys)
    t = jnp.broadcast_to(lengths[:, None], (s, kv))
    score = _block_scores(spec, scores, t, w)               # [S, KV, W]
    _, others = _roles(spec, t, w)
    values, index = lax.top_k(jnp.where(others, score, -jnp.inf), spec.topk)
    own = (lengths // spec.block_size)[:, None, None]
    first = jnp.broadcast_to(jnp.arange(spec.init_blocks), (s, kv,
                                                          spec.init_blocks))
    local = own - jnp.arange(spec.local_blocks - 1, -1, -1)
    local = jnp.broadcast_to(local, (s, kv, spec.local_blocks))
    blocks = jnp.concatenate([first, local, index.astype(local.dtype)],
                             axis=-1)
    valid = jnp.concatenate([jnp.ones(first.shape, bool), local >= 0,
                             jnp.isfinite(values)], axis=-1)
    return jnp.clip(blocks, 0, w - 1).astype(jnp.int32), valid


@jax.named_scope("sparse_select")
def select_chunk(spec: SparseSpec, q: jax.Array, kc: jax.Array,
                 row: jax.Array, start) -> jax.Array:
    """Prefill: which blocks of the stream each of the chunk's queries
    reads, ``[T, KV, W]`` (``q [T, KV, G, D]`` scaled, at positions ``start
    ..``): every block up to ``dense_len`` of context, the selection beyond
    it. Causality within a block is the caller's."""
    t_len, kv, g, d = q.shape
    w = row.shape[0]
    keys = jnp.take(kc, row, axis=0, mode="clip")           # [W, per, KVD]
    keys = keys.reshape(w * spec.per_block, kv, d)
    scores = jnp.einsum("tkgd,wkd->tkgw", q.astype(jnp.float32), keys)
    t = jnp.broadcast_to((start + jnp.arange(t_len))[:, None], (t_len, kv))
    score = _block_scores(spec, scores, t, w)
    forced, others = _roles(spec, t, w)
    values, index = lax.top_k(jnp.where(others, score, -jnp.inf),
                              min(spec.topk, w))
    chosen = jnp.any((index[..., None] == jnp.arange(w))
                     & jnp.isfinite(values)[..., None], axis=-2)
    return (t < spec.dense_len)[..., None] | forced | chosen


# -- the read ---------------------------------------------------------------------

def _gather_pages(pool: jax.Array, pages: jax.Array, head_dim: int
                  ) -> jax.Array:
    """``pool [P, L, KV*D]``, ``pages [S, KV, N]`` -> ``[S, KV, N, L, D]``.
    Whole rows of the selected pages are gathered (``mode="clip"``: no
    fill-mode select) and each key/value head then takes its own columns
    along the head axis. Two forms that look simpler are wrong on the chip
    (PERF.md, PR 27): a gather that picks the columns itself makes the
    compiler copy the whole pool into a layout of its liking, and
    ``stack([rows[:, g, ..., g*D:(g+1)*D] ...])`` is miscompiled there:
    every head gets head 0's columns."""
    kv = pages.shape[1]
    rows = jnp.take(pool, pages, axis=0, mode="clip")   # [S, KV, N, L, KV*D]
    rows = rows.reshape(rows.shape[:-1] + (kv, head_dim))
    own = jnp.arange(kv).reshape(1, kv, 1, 1, 1, 1)
    return jnp.take_along_axis(rows, own, axis=4)[:, :, :, :, 0]


def _attend_pages(q, cache, pages, blocks, valid, lengths):
    """Softmax over the positions of the gathered pages that the query may
    see: ``q [S, KV, G, D]``, ``pages``/``blocks``/``valid`` ``[S, KV, N]``.
    The gathered pages are one run of ``N * L`` keys a key/value head: two
    plain batched products (over five axes with two contracted, the chip's
    compiler gave wrong numbers: PERF.md, PR 27). Returns the outputs and
    the positions that were gathered for one stream and key/value head (the
    gathered keys' own extent, an int32 scalar of the program)."""
    s, kv, g, d = q.shape
    page_len = cache["k"].shape[1]
    n = pages.shape[-1]
    k = _gather_pages(cache["k"], pages, d).reshape(s, kv, n * page_len, d)
    v = _gather_pages(cache["v"], pages, d).reshape(s, kv, n * page_len, d)
    scores = jnp.einsum("skgd,skmd->skgm", q.astype(k.dtype), k,
                        preferred_element_type=jnp.float32)
    pos = blocks[..., None] * page_len + jnp.arange(page_len)
    seen = valid[..., None] & (pos <= lengths[:, None, None, None])
    scores = jnp.where(seen.reshape(s, kv, 1, n * page_len), scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("skgm,skmd->skgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out, jnp.int32(k.shape[2])


@jax.named_scope("sparse_attend")
def attend_step(spec: SparseSpec, q: jax.Array, cache: Dict[str, jax.Array],
                table: jax.Array, lengths: jax.Array, active: jax.Array,
                blocks: jax.Array, valid: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Decode: every slot's query over the pages it reads. Where every
    active stream is beyond ``dense_len`` the program gathers the
    ``n_selected`` pages of each selection and nothing else; while any
    stream is still dense, each dense stream reads its own first pages and
    the gather is ``dense_blocks`` wide. ``q [S, KV, G, D]`` scaled;
    returns ``[S, KV, G, D]`` float32 and, from the branch that ran, the
    positions it gathered for a stream (the server's histogram
    ``serving.sparse_positions_read``)."""
    s, kv = q.shape[:2]
    dense = lengths < spec.dense_len                             # [S]

    def pages_of(b):
        return jnp.take_along_axis(table[:, None], b, axis=2)

    def selected(_):
        return _attend_pages(q, cache, pages_of(blocks), blocks, valid,
                             lengths)

    def mixed(_):
        wide = max(spec.dense_blocks, spec.n_selected)
        own = jnp.broadcast_to(jnp.arange(wide), (s, kv, wide))
        grow = wide - blocks.shape[-1]
        b = jnp.where(dense[:, None, None], own,
                      jnp.pad(blocks, ((0, 0), (0, 0), (0, grow))))
        ok = jnp.where(dense[:, None, None],
                       own <= (lengths // spec.block_size)[:, None, None],
                       jnp.pad(valid, ((0, 0), (0, 0), (0, grow))))
        b = jnp.minimum(b, table.shape[1] - 1)
        return _attend_pages(q, cache, pages_of(b), b, ok, lengths)

    return lax.cond(jnp.any(active & dense), mixed, selected, None)


#: the chunk kernel's blocks: query rows a program, keys a step of its loop
CHUNK_KERNEL_BLOCKS = (256, 512)


def attend_chunk(spec: SparseSpec, q: jax.Array, cache: Dict[str, jax.Array],
                 row: jax.Array, start, allowed: jax.Array,
                 tile_pages: int = 16) -> jax.Array:
    """Prefill: the chunk's queries (``q [T, KV, G, D]`` scaled, at
    positions ``start ..``) over the stream's pages so far, the chunk's own
    included (the caller has written them); ``allowed [T, KV, W]`` says
    which blocks a query reads, and within a block a query reads up to its
    own position. On one TPU chip that is the grouped-query chunk kernel
    (``ops/grouped_attention.py _attend_chunk_kernel``) with ``allowed`` as
    its page mask: each block of rows walks the key blocks up to its last
    row's, reads them from the pool in place and keeps its scores in VMEM;
    a key block that every row reads whole, and lies before every row,
    skips the mask. Elsewhere, and where the kernel's rule refuses (noted
    once on the TPU), XLA's tiles of ``tile_pages`` pages with a running
    softmax, as many as the context so far has, not as the table is wide.
    Returns ``[T, KV, G, D]`` float32."""
    if dispatch.on_tpu():
        rule = grouped_attention._chunk_kernel_rule(
            q, cache, row, CHUNK_KERNEL_BLOCKS, page_mask=True)
        if rule is None:
            return grouped_attention._attend_chunk_kernel(
                q, cache["k"], cache["v"], row, start, None,
                CHUNK_KERNEL_BLOCKS, allowed, "sparse_attend")
        dispatch.note_fallback("sparse_chunk_attend", rule)
    with jax.named_scope("sparse_attend"):
        return _attend_chunk_xla(q, cache, row, start, allowed, tile_pages)


def _attend_chunk_xla(q, cache, row, start, allowed, tile_pages):
    """:func:`attend_chunk` as XLA writes it: a tile of pages at a time."""
    t_len, kv, g, d = q.shape
    page_len = cache["k"].shape[1]
    w = row.shape[0]
    w_pad = -(-w // tile_pages) * tile_pages
    row = jnp.pad(row, (0, w_pad - w))
    allowed = jnp.pad(allowed, ((0, 0), (0, 0), (0, w_pad - w)))
    t = start + jnp.arange(t_len)
    qc = q.astype(cache["k"].dtype)
    tile = tile_pages * page_len
    n_tiles = (start + t_len + tile - 1) // tile

    def body(i, carry):
        top, total, acc = carry
        pages = lax.dynamic_slice_in_dim(row, i * tile_pages, tile_pages)
        k = jnp.take(cache["k"], pages, axis=0, mode="clip").reshape(
            tile, kv, d)
        v = jnp.take(cache["v"], pages, axis=0, mode="clip").reshape(
            tile, kv, d)
        scores = jnp.einsum("tkgd,nkd->tkgn", qc, k,
                            preferred_element_type=jnp.float32)
        pos = i * tile + jnp.arange(tile)
        ok = jnp.repeat(lax.dynamic_slice_in_dim(
            allowed, i * tile_pages, tile_pages, axis=2), page_len, axis=2) \
            & (pos[None, None] <= t[:, None, None])            # [T, KV, n]
        ok = ok[:, :, None]
        scores = jnp.where(ok, scores, _NEG)
        new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
        p = jnp.where(ok, jnp.exp(scores - new_top[..., None]), 0.0)
        scale = jnp.exp(top - new_top)
        total = total * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "tkgn,nkd->tkgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return new_top, total, acc

    init = (jnp.full((t_len, kv, g), _NEG, jnp.float32),
            jnp.zeros((t_len, kv, g), jnp.float32),
            jnp.zeros((t_len, kv, g, d), jnp.float32))
    _, total, acc = lax.fori_loop(0, n_tiles, body, init)
    return acc / jnp.maximum(total, 1e-30)[..., None]
