"""Ring attention — context/sequence parallelism over the mesh ``seq`` axis.

New TPU-native capability (the reference has none — SURVEY §5 "long-context:
absent"): each device holds a ``seq_len / n_seq`` shard of Q, K, V. K/V shards
rotate around the ring via ``lax.ppermute`` over ICI while every device
accumulates flash-style partial softmax statistics for its local Q against
each visiting K/V shard. Communication overlaps the blockwise compute and the
full ``[seq, seq]`` score matrix never exists on any one chip, so max context
scales linearly with the number of devices on the ``seq`` axis.

Use :func:`ring_attention` inside ``shard_map`` (or let
:func:`ring_self_attention` set that up over a mesh). Differentiable: the
backward of ``ppermute`` is the reverse rotation, so gradients ride the same
ring.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import flash_attention, flash_attention_lse, _NEG_INF
from .pipeline import _axis_size, _vary

SEQ_AXIS = "seq"


def _rotate_perm(n: int):
    """Ring rotation: device j sends its K/V shard to device j-1."""
    return [(j, (j - 1) % n) for j in range(n)]


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = SEQ_AXIS,
                   causal: bool = False,
                   scale: Optional[float] = None,
                   q_block: int = 512,
                   kv_block: int = 512) -> jax.Array:
    """Per-shard body: q/k/v are the LOCAL ``[b, h, seq/n, d]`` shards.

    Must run under ``shard_map``/``pmap`` with ``axis_name`` bound. With
    ``causal=True`` the global position of each shard (this device's
    ``axis_index``) masks future tokens across shard boundaries.
    """
    n = _axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def attend(kc, vc, i):
        """Attention of the local Q against one visiting K/V shard,
        returned as (normalized partial out, per-row lse) — each hop runs
        the flash kernel (pallas on TPU), and partials merge by lse."""
        def lse_attend(causal_flag):
            out, lse = flash_attention_lse(q, kc, vc, causal=causal_flag,
                                           scale=scale, q_block=q_block,
                                           kv_block=kv_block)
            # normalize to v.dtype: the pallas path returns q.dtype, the
            # blockwise path v.dtype — lax.switch needs identical avals
            # across branches for mixed-dtype q/v
            return out.astype(v.dtype), lse

        if not causal:
            return lse_attend(False)
        src_rank = (my + i) % n  # which shard's K/V we currently hold

        def full(_):  # visiting shard is entirely in the past
            return lse_attend(False)

        def diag(_):  # own shard: standard causal mask
            return lse_attend(True)

        def skip(_):  # entirely in the future: contributes nothing
            # neutral element derives from q so it stays device-varying
            # under shard_map's vma check
            return ((q * 0).astype(v.dtype),
                    q[..., 0].astype(jnp.float32) * 0 + _NEG_INF)

        idx = jnp.where(src_rank < my, 0, jnp.where(src_rank == my, 1, 2))
        return lax.switch(idx, [full, diag, skip], None)

    def merge(out, lse, out_h, lse_h):
        lse_new = jnp.logaddexp(lse, lse_h)
        w_old = jnp.exp(lse - lse_new)[..., None]
        w_hop = jnp.exp(lse_h - lse_new)[..., None]
        return (out * w_old + out_h.astype(jnp.float32) * w_hop), lse_new

    def hop(carry, i):
        out, lse, kc, vc = carry
        out_h, lse_h = attend(kc, vc, i)
        out, lse = merge(out, lse, out_h, lse_h)
        # rotate k/v to the next device on the ring (overlaps with the next
        # hop's compute under XLA's async collective scheduling)
        perm = _rotate_perm(n)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (out, lse, kc, vc), None

    # accumulators derive from q*0 so they inherit q's varying-axis type —
    # shard_map's vma check requires the scan carry to be device-varying
    init = (q.astype(jnp.float32) * 0.0,
            q[..., 0].astype(jnp.float32) * 0 + _NEG_INF,
            k, v)
    # n-1 rotating hops, then the last visiting shard is folded without the
    # (wasted) final rotation
    (out, lse, kc, vc), _ = lax.scan(hop, init, jnp.arange(n - 1))
    out_h, lse_h = attend(kc, vc, n - 1)
    out, _ = merge(out, lse, out_h, lse_h)
    return out.astype(v.dtype)


def ring_self_attention(mesh: Mesh, q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        scale: Optional[float] = None) -> jax.Array:
    """Global entry: shards the seq axis of [b, h, s, d] over ``mesh['seq']``
    and runs the ring. Batch rides the ``data`` axis if present."""
    from jax import shard_map

    batch_axis = "data" if "data" in mesh.axis_names else None
    spec = P(batch_axis, None, SEQ_AXIS, None)
    fn = shard_map(
        partial(ring_attention, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def ring_masked_context(q: jax.Array, k_blk: jax.Array, v_blk: jax.Array,
                        visible_blk: jax.Array,
                        scale: float,
                        axis_name: str = SEQ_AXIS) -> jax.Array:
    """Per-shard decode-cache attention over a ``ppermute`` ring of KV
    BLOCKS: the 100k+-token context path where no single device holds the
    whole cache. Each device owns one ``[b, h, K/n, d]`` block of the key/
    value buffers plus the matching slice of the visibility mask; ``q``
    (the decode query, small ``t``) is replicated. Every ring step runs
    the literal ``masked_context`` score arithmetic against the visiting
    block — the same ``bhtd,bhkd`` float32 einsum, the same ``_NEG_INF``
    masking — and folds it into running (max, numerator, denominator)
    streaming-softmax statistics; blocks then rotate one hop. After n-1
    rotations every block has visited every device and ``num/den``
    reproduces ``masked_context`` over the full buffer (the reduction is
    blockwise, so parity vs the single-device softmax is documented
    float32 tolerance, not bitwise; a fully-masked row degrades to the
    same uniform average ``softmax`` of an all-``_NEG_INF`` row yields).
    """
    n = _axis_size(axis_name)

    def partial_scores(kc, vis):
        # one ring step == masked_context's score arithmetic, verbatim
        s = jnp.einsum("bhtd,bhkd->bhtk", q, kc,
                       preferred_element_type=jnp.float32) * scale
        return jnp.where(vis, s, _NEG_INF)

    def fold(carry_m, carry_num, carry_den, kc, vc, vis):
        s = partial_scores(kc, vis)
        m_new = jnp.maximum(carry_m, jnp.max(s, axis=-1))
        w_old = jnp.exp(carry_m - m_new)
        p = jnp.exp(s - m_new[..., None])
        num = (carry_num * w_old[..., None]
               + jnp.einsum("bhtk,bhkd->bhtd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32))
        den = carry_den * w_old + jnp.sum(p, axis=-1)
        return m_new, num, den

    def hop(carry, i):
        m, num, den, kc, vc, vis = carry
        m, num, den = fold(m, num, den, kc, vc, vis)
        perm = _rotate_perm(n)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        vis = lax.ppermute(vis, axis_name, perm)
        return (m, num, den, kc, vc, vis), None

    # accumulators derive from q so they inherit its varying-axis type
    m0 = q[..., 0].astype(jnp.float32) * 0 + _NEG_INF
    num0 = q.astype(jnp.float32) * 0.0
    den0 = q[..., 0].astype(jnp.float32) * 0.0
    (m, num, den, kc, vc, vis), _ = lax.scan(
        hop, (m0, num0, den0, k_blk, v_blk, visible_blk),
        jnp.arange(n - 1))
    m, num, den = fold(m, num, den, kc, vc, vis)
    return (num / den[..., None]).astype(q.dtype)


def ring_context(mesh: Mesh, q: jax.Array, k_buf: jax.Array,
                 v_buf: jax.Array, visible: jax.Array,
                 scale: float) -> jax.Array:
    """Global entry: ``masked_context`` semantics with the KEY axis of the
    ``[b, h, K, d]`` K/V buffers (and the matching ``[b, h, t, K]`` mask)
    sharded over ``mesh['seq']`` — the whole cache never materializes on
    one device. Drop-in for ``masked_context(q, k, v, visible, scale)``
    at documented float32 tolerance."""
    from jax import shard_map


    def body(qr, kc, vc, vis):
        ctx = ring_masked_context(_vary(qr, SEQ_AXIS), kc, vc, vis, scale)
        # every device computed the same logical result off the full ring;
        # the masked psum (exact zeros elsewhere) makes that invariance
        # visible to shard_map's replication check without changing values
        return lax.psum(
            jnp.where(lax.axis_index(SEQ_AXIS) == 0, ctx,
                      jnp.zeros_like(ctx)), SEQ_AXIS)

    kv_spec = P(None, None, SEQ_AXIS, None)
    vis_spec = P(None, None, None, SEQ_AXIS)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(), kv_spec, kv_spec, vis_spec),
                   out_specs=P())
    return fn(q, k_buf, v_buf, visible)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = SEQ_AXIS,
                      causal: bool = False,
                      scale: Optional[float] = None) -> jax.Array:
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all swaps the
    sharded axis from sequence to heads, each device computes full-sequence
    attention for ``heads/n`` heads, then all-to-all swaps back. Lower
    latency than the ring when heads ≥ devices and ICI all-to-all is cheap.

    Per-shard body for ``shard_map``; local shapes ``[b, h, seq/n, d]``.
    """
    n = _axis_size(axis_name)
    b, h, sq, d = q.shape
    if h % n:
        raise ValueError(f"heads {h} not divisible by seq-axis size {n}")

    def seq_to_heads(x):  # [b, h, sq, d] -> [b, h/n, sq*n, d]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):  # [b, h/n, sq*n, d] -> [b, h, sq, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # each device now holds the FULL sequence for its heads, so the pallas
    # flash kernel applies directly (blockwise fallback off-TPU)
    out = flash_attention(qh, kh, vh, causal=causal, scale=scale)
    return heads_to_seq(out)
