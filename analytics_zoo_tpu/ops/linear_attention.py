"""Linear attention with a decay a head (the lightning-attention family):

    S_t = lambda_h S_{t-1} + k_t^T v_t,      o_t = q_t S_t

with ``S`` a ``[D, Dv]`` state a head, kept in float32. Two forms of one
recurrence, both under the scope ``linear_attn``:

- :func:`linear_attention_chunk`, the prefill: ``T`` positions at once in
  blocks of ``block``; within a block the causal ``(q k^T * decay) v``,
  across blocks ``q S`` with the decayed carry. Only the first ``n_valid``
  positions move the state, so a chunk padded to a bucket leaves the state
  of its last real position.
- :func:`linear_attention_step`, the decode: one position of every slot,
  the state of all slots updated in one elementwise pass (in place when the
  caller donates it); a slot that is not ``active`` keeps its state, so a
  prompt that is being prefilled chunk by chunk is not disturbed by the
  decode steps between its chunks.

``lambda_h = exp(-s_h)`` with the family's fixed slopes
``s_h = 2^(-8 (h+1) / heads)`` (:func:`lightning_slopes`). ``q`` comes
already scaled.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def lightning_slopes(heads: int) -> jax.Array:
    """``s_h`` of ``lambda_h = exp(-s_h)``, ``[heads]`` float32."""
    return jnp.asarray([2.0 ** (-8.0 * (h + 1) / heads)
                        for h in range(heads)], jnp.float32)


@jax.named_scope("linear_attn")
def linear_attention_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                           state: jax.Array, slopes: jax.Array, n_valid,
                           block: int = 256) -> Tuple[jax.Array, jax.Array]:
    """``q``/``k`` ``[T, H, D]``, ``v`` ``[T, H, Dv]`` (float32), ``state``
    ``[H, D, Dv]``: the outputs ``[T, H, Dv]`` of the ``T`` positions that
    follow ``state``, and the state after the first ``n_valid`` of them
    (traced; the outputs of the positions after those are of no use)."""
    t, h, d = q.shape
    block = min(block, t)
    if t % block:
        raise ValueError(f"chunk of {t} positions is no multiple of the "
                         f"block {block}")
    n = t // block
    at = jnp.arange(block, dtype=jnp.float32)
    s = slopes[:, None, None]                                    # [H,1,1]
    # within a block: position j reads position l <= j decayed j - l times
    lag = at[:, None] - at[None, :]
    within = jnp.where(lag >= 0, jnp.exp(-s * jnp.maximum(lag, 0.0)), 0.0)
    carry_in = jnp.exp(-slopes[:, None] * (at[None] + 1.0))       # [H,B]
    n_valid = jnp.asarray(n_valid, jnp.int32)

    def body(state, xs):
        qb, kb, vb, first = xs              # [B,H,D], [B,H,D], [B,H,Dv], []
        live = jnp.clip(n_valid - first, 0, block)       # real positions
        real = jnp.arange(block) < live
        kb = jnp.where(real[:, None, None], kb, 0.0)
        scores = jnp.einsum("jhd,lhd->hjl", qb, kb) * within
        out = jnp.einsum("hjl,lhv->jhv", scores, vb)
        out = out + jnp.einsum("jhd,hdv->jhv", qb, state) \
            * carry_in.T[:, :, None]
        # the state after the block's last real position
        left = (live - 1).astype(jnp.float32) - at          # decays to come
        keep = jnp.where(real[None], jnp.exp(
            -slopes[:, None] * jnp.maximum(left, 0.0)[None]), 0.0)  # [H,B]
        state = state * jnp.exp(-s * live.astype(jnp.float32)) \
            + jnp.einsum("lhd,lhv->hdv", kb * keep.T[:, :, None], vb)
        return state, out

    split = lambda x: x.reshape((n, block) + x.shape[1:])
    firsts = jnp.arange(n, dtype=jnp.int32) * block
    state, out = lax.scan(body, state.astype(jnp.float32),
                          (split(q), split(k), split(v), firsts))
    return out.reshape((t,) + out.shape[2:]), state


@jax.named_scope("linear_attn")
def linear_attention_step(q: jax.Array, k: jax.Array, v: jax.Array,
                          state: jax.Array, slopes: jax.Array,
                          active: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One position of every slot: ``q``/``k`` ``[S, H, D]``, ``v``
    ``[S, H, Dv]``, ``state`` ``[S, H, D, Dv]`` float32, ``active`` ``[S]``.
    Returns the outputs ``[S, H, Dv]`` and the states; a slot that is not
    active keeps its state as it was."""
    decay = jnp.exp(-slopes)[None, :, None, None]
    moved = decay * state + k[..., :, None] * v[..., None, :]
    state = jnp.where(active[:, None, None, None], moved, state)
    return jnp.einsum("shd,shdv->shv", q, state), state
