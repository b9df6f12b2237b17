"""Multi-head latent attention (MLA) over a paged **latent** pool, with a
learned sparse selection of single positions (DSA: an indexer that scores
every position so far and keeps the ``index_topk`` best), as the
``mla-dsa`` layers of ``capture/decoder.py`` use it.

What a position leaves in the cache is one row ``[c_kv ; k_rope]``
(``kv_rank + rope_dim`` numbers, shared by all heads) in the pool
``latent [P, page_len, kv_rank + rope_dim]`` and one index key
(``index_dim`` numbers) in the pool ``index [P, page_len, index_dim]``,
both read through the server's one page table. Keys and values of the
heads are products of the latent row with ``kv_b`` and are never stored.

Two attention paths that compute the same function:

- **a decode step** (:func:`attend_step`) is *absorbed*: the query is
  carried through ``kv_b``'s key half (``q' = W_k^T q_nope``), scores are
  taken against the latent rows themselves, and the weighted sum of latent
  rows goes through ``kv_b``'s value half once a head. It reads the
  selected rows of every slot from the pool, ``index_topk`` at most, by a
  gather through the table: the step's cost follows the selection, not the
  context.
- **a chunk** of one stream's prompt (:func:`attend_chunk`) is *plain*:
  the stream's latent rows are expanded to the heads' keys and values and
  every query row is masked to its own selection (``s in S_t``). With
  ``T`` queries to share it the expansion costs a fifth of the scores,
  where the absorbed form would cost 1.7 times the plain one. On one TPU
  chip it is one pallas call a layer (:func:`_attend_chunk_kernel`): the
  loop over the key blocks, their expansion, the mask's blocks and the
  running softmax of all the chunk's rows stay in VMEM, the latent pages
  are read from the pool in place, and key blocks behind every row of a
  sub-block of rows are skipped. Elsewhere XLA's loop over tiles of pages
  runs, whose results are merged by their logs of sums, and on the TPU
  that is noted once with the rule.

The indexer (:func:`index_step`, :func:`index_chunk`): ``I[t, s] = sum_j
w[t, j] relu(q_I[t, j] . k_I[s])`` over ``s <= t``, float32 accumulation,
a tile of pages at a time. The selection is exact: a decode step takes
``lax.top_k`` of each slot's scores (ties to the lower position); a chunk,
whose scores are ``[T, context]``, finds each row's ``index_topk``-th
largest score by a 16-way search on the scores' bits and, among the
positions that tie with it, the lowest ones by the same search on the
position, so a row's mask is ``score > threshold or (score == threshold
and position <= last)``: the set ``lax.top_k`` would return, never an
approximation of it.

A layer may have no rotary part (``rope_dim`` and ``index_rope_dim`` 0:
the row is ``c_kv`` alone) and may pool its index keys (``index_kpool``):
the index pool then holds the float32 sum of each whole block of
``index_kpool`` positions' keys (a decode step adds its key to its block's
sum, a chunk writes whole blocks), scores are taken against the blocks'
means for the blocks wholly before the query, and the selection is the
``index_blocks`` best blocks and the block that holds the query, whose
positions up to the query's own are read.

Scopes on the device (docs/observability.md): ``mla_project`` (the latent
products with their norms and rotary), ``dsa_index`` (the indexer's
products and scores), ``dsa_select`` (the top-k), ``mla_attend`` (the
gather of the selected rows and the softmax over them, or the chunk's
masked tiles), ``kv_write`` (both pools' scatter)."""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import dispatch
from .decode import PAGED_DECODE_TABLE_BYTES

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """The sizes of an ``mla-dsa`` layer; widths are the model's own."""
    heads: int = 64
    q_rank: int = 2048
    kv_rank: int = 512
    nope_dim: int = 192
    rope_dim: int = 64
    v_dim: int = 256
    index_heads: int = 32
    index_dim: int = 128
    index_topk: int = 2048
    index_rope_dim: int = 64
    index_norm_eps: float = 1e-6
    #: positions an index key stands for (their sum, scored as their mean;
    #: 1: one a position); over pooled keys the block that holds the query
    #: is read whatever its score, and is never scored
    index_kpool: int = 1
    #: positions of a tile of the decode step's scoring loop, of a chunk's
    #: scoring and selection loops, and of a chunk's attention loop
    step_tile: int = 2560
    chunk_tile: int = 512
    attend_tile: int = 2048

    def __post_init__(self):
        if self.rope_dim % 2 or self.index_rope_dim % 2 \
                or self.index_rope_dim > self.index_dim:
            raise ValueError("rotary widths must be even and lie inside "
                             "the head")
        if self.index_kpool < 1 or self.index_topk % self.index_kpool:
            raise ValueError(f"index_topk {self.index_topk} is no whole "
                             f"number of pooled blocks of {self.index_kpool}")

    @property
    def index_blocks(self) -> int:
        """Pooled blocks the selection takes by score: ``index_topk`` in
        blocks, less the query's own block, which is read anyway, so that
        at most ``index_topk`` positions are read."""
        return self.index_topk // self.index_kpool - 1

    @property
    def row(self) -> int:
        """Numbers a position leaves in the latent pool."""
        return self.kv_rank + self.rope_dim

    @property
    def pool_row(self) -> int:
        """The latent pool's row: ``row`` in whole tiles of 128 lanes. The
        chip's row-major layout pads a row to that anyway, and for a row
        that is no whole tiles its compiler would rather keep the pool
        pages-minor-most and lay it out anew around every gather of rows
        (two pool-sized copies a layer a step)."""
        return -(-self.row // 128) * 128

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    def shapes(self, hidden: int) -> Tuple[Dict, Dict, Dict]:
        """``(matrices, vectors that start at 1, vectors that start at
        0)`` of one layer's attention and indexer."""
        h = self.heads
        mats = {"q_a": (hidden, self.q_rank),
                "q_b": (self.q_rank, h * self.qk_dim),
                "kv_a": (hidden, self.row),
                "kv_b": (self.kv_rank, h * (self.nope_dim + self.v_dim)),
                "o": (h * self.v_dim, hidden),
                "index_q": (self.q_rank, self.index_heads * self.index_dim),
                "index_k": (hidden, self.index_dim),
                "index_w": (hidden, self.index_heads)}
        ones = {"q_a_norm": (self.q_rank,), "kv_a_norm": (self.kv_rank,),
                "index_k_norm": (self.index_dim,)}
        zeros = {"index_k_bias": (self.index_dim,)}
        return mats, ones, zeros


def init_latent_pool(num_pages: int, page_len: int, spec: LatentSpec,
                     dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """The latent pool ``[P, page_len, pool_row]`` (``kv_rank + rope_dim``
    numbers a position, in whole tiles of 128 lanes) and the index key
    pool ``[P, page_len, index_dim]``, or, where keys are pooled, ``[P,
    page_len / index_kpool, index_dim]`` float32 sums of a block's keys;
    page 0 is the null page."""
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (page 0 is the reserved "
                         f"null page), got {num_pages}")
    if spec.index_kpool > 1:
        if page_len % spec.index_kpool:
            raise ValueError(f"a page of {page_len} is no whole number of "
                             f"pooled blocks of {spec.index_kpool}")
        return {"latent": jnp.zeros((num_pages, page_len, spec.pool_row),
                                    dtype),
                "index": jnp.zeros((num_pages, page_len // spec.index_kpool,
                                    spec.index_dim), jnp.float32)}
    return {"latent": jnp.zeros((num_pages, page_len, spec.pool_row), dtype),
            "index": jnp.zeros((num_pages, page_len, spec.index_dim), dtype)}


# -- the pieces -----------------------------------------------------------------

def _rms_norm(weight, x, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * weight


def _product(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rotary_interleaved(x: jax.Array, positions: jax.Array, theta: float
                       ) -> jax.Array:
    """Rotary positions on the pairs ``(2i, 2i+1)`` of the last axis of
    ``x [..., D]`` (float32); ``positions`` has ``x``'s leading axes or
    broadcasts against them from the left (``[T]`` for ``[T, H, D]``). The
    partner of each number comes from a product with a fixed ``D x D``
    matrix of 0 and +-1 at ``highest``, which is exact and keeps the
    head's numbers in their lanes (a reshape to pairs would not)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    angle = positions.astype(jnp.float32)[..., None] * jnp.repeat(freq, 2)
    angle = angle.reshape(angle.shape[:-1] + (1,) * (x.ndim - angle.ndim)
                          + (d,))
    at = jnp.arange(d)
    # partner[.., 2i] = -x[.., 2i+1], partner[.., 2i+1] = x[.., 2i]
    swap = jnp.where(at[:, None] == (at ^ 1)[None],
                     jnp.where(at[:, None] % 2 == 1, -1.0, 1.0), 0.0)
    partner = jnp.dot(x, swap.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)
    return x * jnp.cos(angle) + partner * jnp.sin(angle)


@jax.named_scope("mla_project")
def project(spec: LatentSpec, p, u: jax.Array, positions: jax.Array,
            theta: float, eps: float):
    """The latent products of ``u [N, d]`` at ``positions [N]``: the scaled
    query halves ``q_nope [N, H, nope]`` and ``q_rope [N, H, rope]``, the
    row ``[c_kv ; k_rope] [N, kv_rank + rope]`` that the position leaves in
    the cache, and the query latent ``c_q [N, q_rank]`` (the indexer reads
    it too)."""
    n = u.shape[0]
    c_q = _rms_norm(p["q_a_norm"], _product(u, p["q_a"]), eps)
    q = _product(c_q, p["q_b"]).reshape(n, spec.heads, spec.qk_dim)
    q = q / math.sqrt(spec.qk_dim)
    q_rope = q[..., spec.nope_dim:]
    if spec.rope_dim:
        q_rope = rotary_interleaved(q_rope, positions, theta)
    kv = _product(u, p["kv_a"])
    c_kv = _rms_norm(p["kv_a_norm"], kv[:, :spec.kv_rank], eps)
    if not spec.rope_dim:   # no rotary part: the row is the latent alone
        return q[..., :spec.nope_dim], q_rope, c_kv, c_q
    k_rope = rotary_interleaved(kv[:, spec.kv_rank:], positions, theta)
    return (q[..., :spec.nope_dim], q_rope,
            jnp.concatenate([c_kv, k_rope], axis=-1), c_q)


@jax.named_scope("dsa_index")
def index_project(spec: LatentSpec, p, u: jax.Array, c_q: jax.Array,
                  positions: jax.Array, theta: float):
    """The indexer's query heads ``[N, IH, ID]`` (from the query latent),
    its one key a position ``[N, ID]`` (a layer norm with weight and bias
    over ``W_kI u``) and the heads' weights ``[N, IH]``; rotary on the
    first ``index_rope_dim`` numbers of queries and keys."""
    n, r = u.shape[0], spec.index_rope_dim
    q = _product(c_q, p["index_q"]).reshape(n, spec.index_heads,
                                            spec.index_dim)
    k = _product(u, p["index_k"])
    mean = jnp.mean(k, axis=-1, keepdims=True)
    k = (k - mean) * lax.rsqrt(
        jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        + spec.index_norm_eps) * p["index_k_norm"] + p["index_k_bias"]
    if r:
        q = jnp.concatenate([rotary_interleaved(q[..., :r], positions,
                                                theta), q[..., r:]], axis=-1)
        k = jnp.concatenate([rotary_interleaved(k[..., :r], positions,
                                                theta), k[..., r:]], axis=-1)
    w = _product(u, p["index_w"]) / math.sqrt(
        spec.index_heads * spec.index_dim)
    return q, k, w


@jax.named_scope("kv_write")
def write(cache: Dict[str, jax.Array], pages: jax.Array, offs: jax.Array,
          rows: jax.Array, keys: jax.Array,
          real: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
    """Scatter the positions' latent ``rows`` and index ``keys`` (leading
    axes as ``pages`` / ``offs``) into their pools, rounded to the pools'
    dtype. Where keys are pooled, a key is added to its block's sum (the
    first position of a block starts it): a decode step's ``[S, 1]``
    positions one at a time, a chunk's ``[1, T]`` (whole blocks from a
    block's start; ``real [T]`` leaves its padding out) a block at a
    time."""
    pad = cache["latent"].shape[-1] - rows.shape[-1]
    rows = jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, pad),))
    latent = cache["latent"].at[pages, offs].set(
        rows.astype(cache["latent"].dtype))
    kpool = cache["latent"].shape[1] // cache["index"].shape[1]
    if kpool == 1:
        return {"latent": latent, "index": cache["index"].at[pages, offs].set(
            keys.astype(cache["index"].dtype))}
    index, keys = cache["index"], keys.astype(jnp.float32)
    if pages.shape[1] == 1:
        old = index[pages, offs // kpool]
        keys = jnp.where((offs % kpool == 0)[..., None], keys, old + keys)
        return {"latent": latent,
                "index": index.at[pages, offs // kpool].set(keys)}
    if real is not None:
        keys = jnp.where(real[:, None], keys, 0.0)
    b, t = pages.shape
    keys = jnp.sum(keys.reshape(b, t // kpool, kpool, -1), axis=2)
    return {"latent": latent, "index": index.at[
        pages[:, ::kpool], offs[:, ::kpool] // kpool].set(keys)}


def _tile_pages(tile: int, page_len: int, width: int) -> int:
    """Pages of a tile of ``tile`` positions over a table ``width`` wide."""
    return max(min(tile // page_len, width), 1)


def _bits_width(spec: LatentSpec, page_len: int, width: int) -> int:
    """Positions of a chunk's buffer of scores: the table's ``width`` pages
    in whole tiles of the scoring loop and of the attention loop alike."""
    unit = math.lcm(
        _tile_pages(spec.chunk_tile, page_len, width) * page_len,
        _tile_pages(spec.attend_tile, page_len, width) * page_len)
    return -(-width * page_len // unit) * unit


def _pages_of(rows: jax.Array, i, tile_pages: int) -> jax.Array:
    """The page ids of tile ``i`` of each table row ``rows [B, W]`` (past
    the row's width: its last page, whose positions the caller masks)."""
    at = i * tile_pages + jnp.arange(tile_pages)
    return jnp.take_along_axis(
        rows, jnp.minimum(at, rows.shape[1] - 1)[None], axis=1)


# -- decode: one position of every slot ---------------------------------------------

@jax.named_scope("dsa_index")
def index_step(spec: LatentSpec, q: jax.Array, w: jax.Array,
               index_pool: jax.Array, table: jax.Array, lengths: jax.Array,
               active: jax.Array, dtype=None) -> jax.Array:
    """Index scores of every slot's query (at position ``lengths[s]``, its
    own key written) against the positions ``0 .. lengths[s]``: ``[S, L]``
    float32, ``-inf`` past the slot's length. Tiles of pages from the first
    to the longest live stream's last; ``L`` is whole tiles over the
    table's width. Over pooled keys see :func:`_index_step_pooled`
    (``dtype``: the products' operands)."""
    if spec.index_kpool > 1:
        return _index_step_pooled(spec, q, w, index_pool, table, lengths,
                                  active, dtype or q.dtype)
    s = q.shape[0]
    page_len = index_pool.shape[1]
    tp = _tile_pages(spec.step_tile, page_len, table.shape[1])
    tile = tp * page_len
    n_max = -(-table.shape[1] // tp)
    qc = q.astype(index_pool.dtype)
    longest = jnp.max(jnp.where(active, lengths, 0))

    def body(i, scores):
        keys = jnp.take(index_pool, _pages_of(table, i, tp), axis=0,
                        mode="clip").reshape(s, tile, -1)
        dots = jnp.einsum("shd,snd->shn", qc, keys,
                          preferred_element_type=jnp.float32)
        got = jnp.einsum("shn,sh->sn", jax.nn.relu(dots), w)
        pos = i * tile + jnp.arange(tile)
        got = jnp.where(pos[None] <= lengths[:, None], got, -jnp.inf)
        return lax.dynamic_update_slice(scores, got, (0, i * tile))
    return lax.fori_loop(0, longest // tile + 1, body,
                         jnp.full((s, n_max * tile), -jnp.inf, jnp.float32))


def _index_step_pooled(spec, q, w, index_pool, table, lengths, active,
                       dtype):
    """:func:`index_step` over pooled keys: each slot's scores of the
    blocks wholly before its query, ``[S, L / index_kpool]``, ``-inf``
    elsewhere (the block that holds the query is not scored)."""
    s, kpool = q.shape[0], spec.index_kpool
    per_page = index_pool.shape[1]                    # blocks a page
    page_len = per_page * kpool
    tp = _tile_pages(spec.step_tile, page_len, table.shape[1])
    tile = tp * per_page                              # blocks a tile
    n_max = -(-table.shape[1] // tp)
    qc = q.astype(dtype)
    whole = lengths // kpool
    longest = jnp.max(jnp.where(active, lengths, 0))

    def body(i, scores):
        keys = jnp.take(index_pool, _pages_of(table, i, tp), axis=0,
                        mode="clip").reshape(s, tile, -1) * (1.0 / kpool)
        dots = jnp.einsum("shd,snd->shn", qc, keys.astype(qc.dtype),
                          preferred_element_type=jnp.float32)
        got = jnp.einsum("shn,sh->sn", jax.nn.relu(dots), w)
        blk = i * tile + jnp.arange(tile)
        got = jnp.where(blk[None] < whole[:, None], got, -jnp.inf)
        return lax.dynamic_update_slice(scores, got, (0, i * tile))
    return lax.fori_loop(0, longest // (tile * kpool) + 1, body,
                         jnp.full((s, n_max * tile), -jnp.inf, jnp.float32))


@jax.named_scope("dsa_select")
def select_step(spec: LatentSpec, scores: jax.Array,
                lengths: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """The ``index_topk`` positions of largest score a slot (ties to the
    lower position; every position while there are fewer): ``(positions
    [S, K], which of them are real [S, K])``. Over pooled keys: the
    positions of the ``index_blocks`` blocks of largest score and of the
    block that holds the query (``lengths [S]``), up to the query's own."""
    if spec.index_kpool == 1:
        k = min(spec.index_topk, scores.shape[1])
        top, at = lax.top_k(scores, k)
        return at.astype(jnp.int32), top > -jnp.inf
    kpool = spec.index_kpool
    top, blk = lax.top_k(scores, min(spec.index_blocks, scores.shape[1]))
    blk = jnp.concatenate([blk, (lengths // kpool)[:, None]], axis=1)
    real = jnp.concatenate([top > -jnp.inf, jnp.ones_like(top[:, :1], bool)],
                           axis=1)
    at = (blk[..., None] * kpool + jnp.arange(kpool)).reshape(
        blk.shape[0], -1).astype(jnp.int32)
    real = jnp.repeat(real, kpool, axis=1) & (at <= lengths[:, None])
    return at, real


@jax.named_scope("mla_attend")
def attend_step(spec: LatentSpec, p, q_nope: jax.Array, q_rope: jax.Array,
                latent_pool: jax.Array, table: jax.Array, at: jax.Array,
                real: jax.Array) -> jax.Array:
    """The absorbed form over each slot's selected positions ``at [S, K]``:
    the rows come from the pool through the table, scores are taken
    against the latent rows themselves, and the weighted latent goes
    through ``kv_b``'s value half. Returns ``[S, H * v_dim]`` float32."""
    s = q_nope.shape[0]
    page_len = latent_pool.shape[1]
    dtype = latent_pool.dtype
    # each position's page by comparison with the table's columns: a gather
    # of single numbers from the table costs the chip 8 ns a number, a
    # fifth of the row gather itself
    width = table.shape[1]
    pages = jnp.sum(jnp.where(
        jnp.minimum(at // page_len, width - 1)[..., None]
        == jnp.arange(width), table[:, None], 0), axis=-1)
    # the pool seen as rows: a gather by page and offset would have the
    # compiler lay the whole pool out anew
    rows = jnp.take(latent_pool.reshape(-1, latent_pool.shape[2]),
                    pages * page_len + at % page_len, axis=0,
                    mode="clip")                          # [S, K, row]
    c, r = rows[..., :spec.kv_rank], rows[..., spec.kv_rank:spec.row]
    w_kv = p["kv_b"].reshape(spec.kv_rank, spec.heads, -1)
    q_lat = jnp.einsum("shn,chn->shc", q_nope.astype(w_kv.dtype),
                       w_kv[..., :spec.nope_dim],
                       preferred_element_type=jnp.float32)
    scores = jnp.einsum("shc,skc->shk", q_lat.astype(dtype), c,
                        preferred_element_type=jnp.float32)
    if spec.rope_dim:
        scores = scores + jnp.einsum("shr,skr->shk", q_rope.astype(dtype), r,
                                     preferred_element_type=jnp.float32)
    scores = jnp.where(real[:, None], scores, _NEG)
    top = jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.where(real[:, None], jnp.exp(scores - top), 0.0)
    probs = probs / jnp.maximum(jnp.sum(probs, axis=-1, keepdims=True),
                                1e-30)
    o_lat = jnp.einsum("shk,skc->shc", probs.astype(dtype), c,
                       preferred_element_type=jnp.float32)
    o = jnp.einsum("shc,chv->shv", o_lat.astype(w_kv.dtype),
                   w_kv[..., spec.nope_dim:],
                   preferred_element_type=jnp.float32)
    return o.reshape(s, -1)


# -- prefill: a chunk of one stream's prompt ------------------------------------------

def _sortable(x: jax.Array) -> jax.Array:
    """Float32 scores as uint32 in the same order; every real score maps
    above 0, which stands for a position that may not be seen."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


@jax.named_scope("dsa_index")
def index_chunk(spec: LatentSpec, q: jax.Array, w: jax.Array,
                index_pool: jax.Array, row: jax.Array, start,
                dtype=None) -> jax.Array:
    """Index scores of the chunk's queries (positions ``start ..
    start+T-1``, their keys written) against the stream's positions so
    far, as sortable bits ``[T, L]`` uint32 (:func:`_sortable`; 0 where
    ``s > t``). Tiles of pages up to the chunk's end. Over pooled keys:
    ``[T, L / index_kpool]``, the blocks wholly before each query
    (``dtype``: the products' operands)."""
    if spec.index_kpool > 1:
        return _index_chunk_pooled(spec, q, w, index_pool, row, start,
                                   dtype or q.dtype)
    t = q.shape[0]
    page_len = index_pool.shape[1]
    tp = _tile_pages(spec.chunk_tile, page_len, row.shape[0])
    tile = tp * page_len
    width = _bits_width(spec, page_len, row.shape[0])
    qc = q.astype(index_pool.dtype)
    at_t = start + jnp.arange(t)

    def body(i, bits):
        keys = jnp.take(index_pool, _pages_of(row[None], i, tp)[0], axis=0,
                        mode="clip").reshape(tile, -1)
        dots = jnp.einsum("thd,nd->thn", qc, keys,
                          preferred_element_type=jnp.float32)
        got = jnp.einsum("thn,th->tn", jax.nn.relu(dots), w)
        pos = i * tile + jnp.arange(tile)
        got = jnp.where(pos[None] <= at_t[:, None], _sortable(got),
                        jnp.uint32(0))
        return lax.dynamic_update_slice(bits, got, (0, i * tile))
    return lax.fori_loop(0, (start + t - 1) // tile + 1, body,
                         jnp.zeros((t, width), jnp.uint32))


def _index_chunk_pooled(spec, q, w, index_pool, row, start, dtype):
    t, kpool = q.shape[0], spec.index_kpool
    per_page = index_pool.shape[1]
    page_len = per_page * kpool
    tp = _tile_pages(spec.chunk_tile, page_len, row.shape[0])
    tile = tp * per_page
    width = _bits_width(spec, page_len, row.shape[0]) // kpool
    qc = q.astype(dtype)
    whole = (start + jnp.arange(t)) // kpool

    def body(i, bits):
        keys = jnp.take(index_pool, _pages_of(row[None], i, tp)[0], axis=0,
                        mode="clip").reshape(tile, -1) * (1.0 / kpool)
        dots = jnp.einsum("thd,nd->thn", qc, keys.astype(dtype),
                          preferred_element_type=jnp.float32)
        got = jnp.einsum("thn,th->tn", jax.nn.relu(dots), w)
        blk = i * tile + jnp.arange(tile)
        got = jnp.where(blk[None] < whole[:, None], _sortable(got),
                        jnp.uint32(0))
        return lax.dynamic_update_slice(bits, got, (0, i * tile))
    return lax.fori_loop(0, (start + t - 1) // (tile * kpool) + 1, body,
                         jnp.zeros((t, width), jnp.uint32))


@jax.named_scope("dsa_select")
def select_chunk(spec: LatentSpec, bits: jax.Array, start
                 ) -> Tuple[jax.Array, jax.Array]:
    """Each query row's selection as ``(threshold [T] uint32, last [T]
    int32)``: the row selects ``s`` where ``bits[t, s] > threshold``, or
    ``bits[t, s] == threshold and s <= last``. The threshold is the row's
    ``index_topk``-th largest score and ``last`` the position up to which
    the scores that tie with it are taken, lowest first: the set
    ``lax.top_k`` returns. Both come from 16-way searches, four bits a
    pass over the tiles of scores up to the chunk's end; a row that sees
    fewer than ``index_topk`` positions gets threshold 0, which selects
    all it sees (the caller masks ``s > t``). Over pooled keys the same
    of blocks, ``index_blocks`` of them."""
    t, width = bits.shape
    # whole tiles of the buffer, the widest of at most 8 scoring tiles
    tile = next(width // m for m in range(1, width + 1)
                if width % m == 0 and width // m <= 8 * spec.chunk_tile)
    if spec.index_kpool > 1:
        n = (start + t - 1) // spec.index_kpool // tile + 1
        k = spec.index_blocks
    else:
        n = (start + t - 1) // tile + 1
        k = spec.index_topk
    if width >= 1 << 16:
        raise ValueError(f"a context of {width} positions does not fit the "
                         f"selection's 16-bit search on the position")
    steps = jnp.arange(1, 16, dtype=jnp.uint32)[:, None]        # [15, 1]

    def counted(holds):
        """``[C, T]``: how many positions of each row ``holds(tile of bits
        [T, tile], their positions [tile])`` ``[C, T, tile]`` is true of."""
        def body(i, acc):
            part = lax.dynamic_slice(bits, (0, i * tile), (t, tile))
            pos = i * tile + jnp.arange(tile, dtype=jnp.int32)
            return acc + jnp.sum(holds(part, pos), axis=-1, dtype=jnp.int32)
        return body

    def search(passes, keeps):
        """The largest value, four bits a pass from the top, of which
        ``keeps(candidates [15, T]) [15, T]`` still holds; it holds of a
        value and of every smaller one."""
        def one(j, found):
            shift = (4 * (passes - 1 - j)).astype(jnp.uint32)
            cands = found[None] | (steps << shift)
            return found | (jnp.sum(keeps(cands), axis=0,
                                    dtype=jnp.uint32) << shift)
        return lax.fori_loop(0, passes, one, jnp.zeros((t,), jnp.uint32))

    def at_least_k(cands):
        body = counted(lambda part, _: part[None] >= cands[:, :, None])
        return lax.fori_loop(0, n, body,
                             jnp.zeros(cands.shape, jnp.int32)) >= k
    threshold = search(8, at_least_k)
    above = lax.fori_loop(
        0, n, counted(lambda part, _: (part > threshold[:, None])[None]),
        jnp.zeros((1, t), jnp.int32))[0]
    need = k - above        # >= 1: fewer than k lie above the k-th largest

    def too_few(cands):
        body = counted(lambda part, pos: (part == threshold[:, None])[None]
                       & (pos[None, None].astype(jnp.uint32)
                          < cands[:, :, None]))
        return lax.fori_loop(0, n, body,
                             jnp.zeros(cands.shape, jnp.int32)) < need
    return threshold, search(4, too_few).astype(jnp.int32)


def _tile_attend_xla(q, k, v, ok):
    """Softmax attention of ``q [H, T, D]`` over one tile of keys ``k [H,
    n, D]`` and values ``v [H, n, V]`` under the mask ``ok [T, n]``: ``(o
    [H, T, V] float32, normalised over the tile; lse [H, T]``, the log of
    the tile's sum of exponentials, ``_NEG`` for a row that sees nothing
    here)``. The XLA form: the scores of the whole tile at once."""
    scores = jnp.einsum("htd,hnd->htn", q, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(ok[None], scores, _NEG)
    top = jnp.max(scores, axis=-1)
    probs = jnp.where(ok[None], jnp.exp(scores - top[..., None]), 0.0)
    total = jnp.sum(probs, axis=-1)
    o = jnp.einsum("htn,hnv->htv", probs.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o / jnp.maximum(total, 1e-30)[..., None], \
        jnp.where(total > 0, top + jnp.log(jnp.maximum(total, 1e-30)), _NEG)


#: the chunk kernel's blocks: query rows a sub-block of a program's rows, keys
#: a step of its outer loop (whole pages)
CHUNK_KERNEL_BLOCKS = (512, 1024)

#: what the kernel may hold in VMEM
CHUNK_KERNEL_VMEM_BYTES = 32 * 1024 * 1024


def seen_from(start, block, sub: int, keys: int):
    """The first sub-block of ``sub`` rows, of a chunk whose first row is at
    position ``start``, of which some row may see key block ``block``
    (positions ``block * keys ..``): sub-block ``i`` ends at the position
    ``start + sub * (i + 1) - 1``. The causal mask empties the block for
    every sub-block before it."""
    return jnp.maximum(block * keys - start, 0) // sub


def blocks_visited(start: int, rows: int, sub: int, keys: int
                   ) -> Tuple[int, int]:
    """``(visited, dense)``: the (sub-block of rows, key block) pairs that
    the chunk kernel computes for a chunk of ``rows`` rows from ``start``,
    and all pairs up to the chunk's last row, which it would compute if it
    skipped nothing."""
    blocks = (start + rows - 1) // keys + 1
    visited = sum(rows // sub - int(seen_from(start, j, sub, keys))
                  for j in range(blocks))
    return visited, blocks * (rows // sub)


def _chunk_kernel_rule(spec: LatentSpec, rows: int, page_len: int,
                       width: int, bits_width: int) -> Optional[str]:
    """Why a chunk of ``rows`` queries cannot run
    :func:`_attend_chunk_kernel` (None: it can): the XLA form then runs,
    and on the TPU that is noted once."""
    if dispatch.partitioned():
        return ("the chunk program spans several devices, and a Mosaic "
                "kernel cannot be partitioned automatically")
    sub, keys = min(CHUNK_KERNEL_BLOCKS[0], rows), CHUNK_KERNEL_BLOCKS[1]
    if rows % sub or sub % 128 or keys % page_len or keys % 128 \
            or bits_width % keys or spec.qk_dim % 128 or spec.v_dim % 128 \
            or spec.kv_rank % 128:
        return (f"{rows} queries of [{spec.qk_dim}, {spec.v_dim}] over "
                f"latent rows of {spec.kv_rank} in pages of {page_len} "
                f"({bits_width} scored) are no whole blocks of "
                f"{CHUNK_KERNEL_BLOCKS} rows and keys and 128 lanes")
    if width * 4 > PAGED_DECODE_TABLE_BYTES:
        return (f"a table row of {width} pages does not fit the scalar "
                f"memory ({PAGED_DECODE_TABLE_BYTES} bytes)")
    return None


def _chunk_kernel(start_ref, row_ref, qt_ref, wk_ref, wv_ref, lat_hbm, ok_hbm,
                  o_ref, lat_buf, ok_buf, sem, k_ref, vt_ref, acc_ref, m_ref,
                  l_ref):
    """All of a chunk's query rows of one head against the stream's key
    blocks in turn, from the first to the block of the chunk's last row. A
    key block's pages come from the latent pool in HBM through ``row`` (one
    DMA a page; the next block's are started before this one's are waited
    for) and are expanded once, in VMEM, through this head's slices of
    ``kv_b``: keys ``[c ; r] W_k`` (``wk_ref``, which carries the shared
    rotary key through an identity, so a key is ``[k_nope ; r]`` with no
    broadcast over the heads) and values transposed, ``W_v^T c^T``, both
    rounded to the pool's dtype as the XLA form rounds them. Then the
    sub-blocks of rows that may see the block attend to it in turn, each
    under its own block of the selection's mask (int8 from HBM, the next
    one's copy in flight): sub-blocks whose every row lies before the
    block are skipped (:func:`seen_from`).

    Scores are taken keys by rows (``k q^T``; the queries arrive
    transposed, which costs XLA nothing where it makes them and the
    kernel 2-10 % where it has to), so the running softmax's maxima and
    sums over the keys are sums of whole registers and its statistics lie
    along the lanes (PERF.md, PR 34 and PR 36); the accumulator is ``v^T
    p``, transposed back once at the end. The running maximum, sum and
    accumulator of all rows stay in VMEM across all key blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_sub, _, sub = acc_ref.shape
    _, pages, page_len, row_len = lat_buf.shape
    keys, width, rank = pages * page_len, row_ref.shape[0], wv_ref.shape[2]
    start = start_ref[0]
    count = (start + n_sub * sub - 1) // keys + 1

    def each_page(j, buf, act):
        """``act`` on the copies of key block ``j`` into ``buf`` (past the
        table's width: its last page, as the XLA form reads)."""
        def one(n, carry):
            page = row_ref[jnp.minimum(j * pages + n, width - 1)]
            act(pltpu.make_async_copy(lat_hbm.at[page], lat_buf.at[buf, n],
                                      sem.at[0, buf]))
            return carry
        lax.fori_loop(0, pages, one, 0)

    def mask_copy(j, i, buf):
        at = pl.ds(pl.multiple_of(j * keys, keys), keys)
        return pltpu.make_async_copy(ok_hbm.at[i, at], ok_buf.at[buf],
                                     sem.at[1, buf])

    each_page(0, 0, lambda dma: dma.start())
    mask_copy(0, 0, 0).start()
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)

    def block(j, bufs):
        buf, ok_at = bufs

        @pl.when(j + 1 < count)
        def _next_block():
            each_page(j + 1, 1 - buf, lambda dma: dma.start())

        each_page(j, buf, lambda dma: dma.wait())
        lat = lat_buf[buf].reshape(keys, row_len)
        k_ref[...] = jnp.dot(
            lat, wk_ref[0],
            preferred_element_type=jnp.float32).astype(k_ref.dtype)
        vt_ref[...] = lax.dot_general(
            wv_ref[0], lat[:, :rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(vt_ref.dtype)

        def rows(i, ok_at):
            @pl.when(i + 1 < n_sub)
            def _next_rows():
                mask_copy(j, i + 1, 1 - ok_at).start()

            @pl.when((i + 1 == n_sub) & (j + 1 < count))
            def _next_blocks_first_rows():
                mask_copy(j + 1, seen_from(start, j + 1, sub, keys),
                          1 - ok_at).start()

            mask_copy(j, i, ok_at).wait()
            sc = jnp.dot(k_ref[...], qt_ref[0, i],
                         preferred_element_type=jnp.float32)  # [keys, sub]
            # a row that has seen nothing yet weighs its unseen keys 1; its
            # first seen key rescales that to nothing, and a row that never
            # sees one is zeroed at the end
            sc = jnp.where(ok_buf[ok_at] != 0, sc, _NEG)
            m = m_ref[i]
            m_new = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m - m_new)
            l_ref[i] = l_ref[i] * corr + jnp.sum(p, axis=0, keepdims=True)
            m_ref[i] = m_new
            acc_ref[i] = acc_ref[i] * corr + jnp.dot(
                vt_ref[...], p.astype(vt_ref.dtype),
                preferred_element_type=jnp.float32)           # [v, sub]
            return 1 - ok_at

        return 1 - buf, lax.fori_loop(seen_from(start, j, sub, keys), n_sub,
                                      rows, ok_at)

    lax.fori_loop(0, count, block, (0, 0))

    def finish(i, carry):
        o = jnp.where(m_ref[i] > 0.5 * _NEG,
                      acc_ref[i] / jnp.maximum(l_ref[i], 1e-30), 0.0)
        o_ref[pl.ds(pl.multiple_of(i * sub, sub), sub), :] = o.T
        return carry
    lax.fori_loop(0, n_sub, finish, 0)


def _selected(bits, pos, threshold, last, at_t):
    """Which of the positions ``pos [n]`` (their scores' ``bits [T, n]``)
    each query row at ``at_t [T]`` selects: :func:`select_chunk`'s set,
    and never a later position than its own."""
    ok = (bits > threshold[:, None]) | (
        (bits == threshold[:, None]) & (pos[None] <= last[:, None]))
    return ok & (pos[None] <= at_t[:, None])


def _selected_pooled(spec, bits, first, threshold, last, at_t):
    """:func:`_selected` over pooled keys: ``bits [T, n]`` of the blocks
    from ``first`` on, the mask ``[T, n x index_kpool]`` of their
    positions. A row selects the blocks :func:`select_chunk` chose among
    those wholly before it and its own block, up to its own position."""
    kpool = spec.index_kpool
    blk = first + jnp.arange(bits.shape[1])
    own = at_t // kpool
    ok = _selected(bits, blk, threshold, last, own - 1) \
        | (blk[None] == own[:, None])
    pos = first * kpool + jnp.arange(bits.shape[1] * kpool)
    return jnp.repeat(ok, kpool, axis=1) & (pos[None] <= at_t[:, None])


@functools.partial(jax.jit, static_argnames=("spec", "blocks"))
def _attend_chunk_kernel(spec, kv_b, q_nope, q_rope, latent_pool, row, start,
                         bits, threshold, last, blocks):
    """:func:`attend_chunk` as one Mosaic call: a grid of heads, each
    program walking the stream's key blocks of ``blocks[1]`` positions for
    all the chunk's rows, ``blocks[0]`` at a time. Around it XLA only lays
    operands out: the queries transposed a sub-block, ``kv_b`` split a head
    into its key slice (with the identity that carries the rotary key) and
    its transposed value slice, and the selection's mask as int8, keys by
    rows a sub-block, over the whole scored width in one pass (a loop up
    to the chunk's end was no faster inside the chunk program: PERF.md, PR
    36). Jitted on its own, so that a chunk program traces and lowers the
    kernel once for all its layers; the scope is inside the ``jit``,
    innermost, where XLA takes the Mosaic call's name from."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with jax.named_scope("mla_attend"):
        t, h = q_nope.shape[:2]
        _, page_len, row_len = latent_pool.shape
        dtype = latent_pool.dtype
        sub, keys = min(blocks[0], t), blocks[1]
        n_sub = t // sub
        qt = jnp.concatenate([q_nope, q_rope], axis=-1).astype(dtype)
        qt = qt.reshape(n_sub, sub, h, spec.qk_dim).transpose(2, 0, 3, 1)
        w_kv = kv_b.reshape(spec.kv_rank, h, -1)
        # [c ; r ; padding] -> [k_nope ; r]: the rotary key passes through
        # an identity, exactly (a product with 1 and sums with 0)
        wk = jnp.zeros((h, row_len, spec.qk_dim), w_kv.dtype)
        wk = wk.at[:, :spec.kv_rank, :spec.nope_dim].set(
            jnp.moveaxis(w_kv[..., :spec.nope_dim], 1, 0))
        if spec.rope_dim:
            wk = wk.at[:, spec.kv_rank:spec.row, spec.nope_dim:].set(
                jnp.eye(spec.rope_dim, dtype=w_kv.dtype))
        wv = w_kv[..., spec.nope_dim:].transpose(1, 2, 0)   # [H, v, rank]
        if spec.index_kpool > 1:
            ok = _selected_pooled(spec, bits, 0, threshold, last,
                                  start + jnp.arange(t))
        else:
            ok = _selected(bits, jnp.arange(bits.shape[1]), threshold, last,
                           start + jnp.arange(t))
        ok = ok.reshape(n_sub, sub, -1).transpose(0, 2, 1).astype(jnp.int8)

        def head(*shape):
            return pl.BlockSpec((1,) + shape,
                                lambda a, *_: (a,) + (0,) * len(shape),
                                memory_space=pltpu.VMEM)
        stat = pltpu.VMEM((n_sub, 1, sub), jnp.float32)
        return pl.pallas_call(
            _chunk_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(h,),
                in_specs=[head(n_sub, spec.qk_dim, sub),
                          head(row_len, spec.qk_dim),
                          head(spec.v_dim, spec.kv_rank),
                          pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((t, spec.v_dim), lambda a, *_: (0, a),
                                       memory_space=pltpu.VMEM),
                scratch_shapes=[
                    pltpu.VMEM((2, keys // page_len, page_len, row_len),
                               dtype),
                    pltpu.VMEM((2, keys, sub), jnp.int8),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((keys, spec.qk_dim), dtype),
                    pltpu.VMEM((spec.v_dim, keys), dtype),
                    pltpu.VMEM((n_sub, spec.v_dim, sub), jnp.float32),
                    stat, stat]),
            out_shape=jax.ShapeDtypeStruct((t, h * spec.v_dim), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=CHUNK_KERNEL_VMEM_BYTES),
        )(jnp.asarray(start, jnp.int32).reshape(1), row.astype(jnp.int32),
          qt, wk, wv, latent_pool, ok)


def attend_chunk(spec: LatentSpec, p, q_nope: jax.Array, q_rope: jax.Array,
                 latent_pool: jax.Array, row: jax.Array, start,
                 bits: jax.Array, threshold: jax.Array, last: jax.Array
                 ) -> jax.Array:
    """The plain form for the chunk's queries, each over its own selection
    (``bits``, ``threshold``, ``last`` of :func:`select_chunk`; ``s <=
    t``): the stream's latent rows are expanded through ``kv_b`` to the
    heads' keys (``[k_nope_h ; k_rope]``) and values and attended under
    the mask ``s in S_t``. On one TPU chip that is one kernel call
    (:func:`_attend_chunk_kernel`); elsewhere, and where its rule refuses
    (noted once on the TPU), XLA's loop over tiles of pages, whose results
    are merged by their logs of sums. Returns ``[T, H * v_dim]``
    float32."""
    if dispatch.on_tpu():
        rule = _chunk_kernel_rule(spec, q_nope.shape[0],
                                  latent_pool.shape[1], row.shape[0],
                                  bits.shape[1] * spec.index_kpool)
        if rule is None:
            return _attend_chunk_kernel(
                spec, p["kv_b"], q_nope, q_rope, latent_pool, row, start,
                bits, threshold, last, CHUNK_KERNEL_BLOCKS)
        dispatch.note_fallback("latent_chunk_attend", rule)
    with jax.named_scope("mla_attend"):
        return _attend_chunk_xla(spec, p["kv_b"], q_nope, q_rope,
                                 latent_pool, row, start, bits, threshold,
                                 last)


def _attend_chunk_xla(spec, kv_b, q_nope, q_rope, latent_pool, row, start,
                      bits, threshold, last):
    """:func:`attend_chunk` as XLA writes it: a tile of the stream's pages
    at a time is expanded and attended (:func:`_tile_attend_xla`)."""
    t = q_nope.shape[0]
    page_len = latent_pool.shape[1]
    dtype = latent_pool.dtype
    tp = _tile_pages(spec.attend_tile, page_len, row.shape[0])
    tile = tp * page_len
    q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(dtype)
    q = jnp.moveaxis(q, 0, 1)                                # [H, T, qk]
    w_kv = kv_b.reshape(spec.kv_rank, spec.heads, -1)
    at_t = start + jnp.arange(t)

    def body(i, carry):
        lse, acc = carry
        rows = jnp.take(latent_pool, _pages_of(row[None], i, tp)[0], axis=0,
                        mode="clip").reshape(tile, -1)
        c, r = rows[:, :spec.kv_rank], rows[:, spec.kv_rank:spec.row]
        kv = jnp.einsum("nc,chd->hnd", c, w_kv,
                        preferred_element_type=jnp.float32).astype(dtype)
        k = jnp.concatenate(
            [kv[..., :spec.nope_dim],
             jnp.broadcast_to(r[None], (spec.heads,) + r.shape)], axis=-1)
        if spec.index_kpool > 1:
            blocks = tile // spec.index_kpool
            ok = _selected_pooled(
                spec, lax.dynamic_slice(bits, (0, i * blocks), (t, blocks)),
                i * blocks, threshold, last, at_t)
        else:
            ok = _selected(lax.dynamic_slice(bits, (0, i * tile), (t, tile)),
                           i * tile + jnp.arange(tile), threshold, last, at_t)
        o, new = _tile_attend_xla(q, k, kv[..., spec.nope_dim:], ok)
        both = jnp.logaddexp(lse, new)
        acc = acc * jnp.exp(lse - both)[..., None] \
            + o * jnp.exp(new - both)[..., None]
        return both, acc

    init = (jnp.full((spec.heads, t), _NEG, jnp.float32),
            jnp.zeros((spec.heads, t, spec.v_dim), jnp.float32))
    _, acc = lax.fori_loop(0, (start + t - 1) // tile + 1, body, init)
    return jnp.moveaxis(acc, 0, 1).reshape(t, -1)
