"""Dropless top-k routing for the serving path: every token goes to its
``k`` experts whatever the imbalance; there is no capacity and no token is
dropped (``parallel/moe.py`` is the Keras training layer, which drops over
capacity). One code path for a decode step (``T`` = slots) and a prefill
chunk (``T`` = the chunk): the ``T x k`` assignments are sorted by expert
and the three products of a gated expert run as grouped matrix products
over the sorted rows (``jax.lax.ragged_dot``; the TPU's compiler has a
kernel of its own for it that visits only the groups that hold rows), then
the rows go back to their tokens and are summed under their gates.

Expert tables are ``w_gate``/``w_up`` ``[E, d, f]`` and ``w_down``
``[E, f, d]``, stored as they are used so that no step copies them. A chip
that holds a share of the experts is told which (``held``): it computes the
part of its experts and leaves the rest out; the parts of all shares add up
to the whole layer."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


@jax.named_scope("moe_route")
def route(x: jax.Array, w_r: jax.Array, k: int
          ) -> Tuple[jax.Array, jax.Array]:
    """The ``k`` experts of each row of ``x [T, d]`` and their gates:
    ``softmax(x W_r)`` over all experts, kept on the ``k`` largest logits
    (ties to the lower index) and divided by their sum, which is the
    softmax over the chosen logits. Float32 at ``highest``: the product is
    small, and the choice then differs from a float32 reference's only at
    true near-ties. Returns ``choice [T, k]`` int32 and ``gates [T, k]``."""
    logits = jnp.dot(x.astype(jnp.float32), w_r.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    top, choice = lax.top_k(logits, k)
    return choice.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


@jax.named_scope("moe_experts")
def experts(h: jax.Array, choice: jax.Array, gates: jax.Array,
            w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
            held: Optional[Sequence[int]] = None,
            valid: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """``sum_j gates[t, j] W_down,e (relu(W_gate,e h_t) * W_up,e h_t)``,
    ``e = choice[t, j]``, over the experts whose tables were handed in.

    ``held`` names those experts in the tables' order (``None``: all, in
    order); an assignment to any other expert is left out, for the chip
    that holds it to compute. ``valid [T]`` leaves out the rows that stand
    for nothing (empty slots, a chunk's padding): they read no expert.
    Returns ``[T, d]`` float32 and the assignments each held expert got,
    ``[len(held)]`` int32."""
    t, k = choice.shape
    n_held = w_gate.shape[0]
    local = choice
    if held is not None:
        match = choice[..., None] == jnp.asarray(held, jnp.int32)
        local = jnp.where(jnp.any(match, axis=-1),
                          jnp.argmax(match, axis=-1), n_held)
    if valid is not None:
        local = jnp.where(valid[:, None], local, n_held)
    flat = local.reshape(t * k).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True)       # rows left out sort last
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[flat].add(1)[:n_held]
    rows = jnp.take(h, order // k, axis=0).astype(w_gate.dtype)

    def grouped(a, w):
        return lax.ragged_dot(a, w, sizes,
                              preferred_element_type=jnp.float32)
    mid = jax.nn.relu(grouped(rows, w_gate)) * grouped(rows, w_up)
    out = grouped(mid.astype(w_down.dtype), w_down)
    # rows past the last group belong to no expert held here
    kept = jnp.arange(t * k) < jnp.sum(sizes)
    out = jnp.where(kept[:, None], out, 0.0)
    back = jnp.take(out, jnp.argsort(order), axis=0).reshape(t, k, -1)
    return jnp.sum(back * gates[..., None].astype(jnp.float32), axis=1), sizes


def load_stats(sizes: jax.Array) -> jax.Array:
    """``[experts touched, the busiest expert's assignments over the mean,
    assignments]`` of one layer's ``sizes`` (all experts), float32."""
    total = jnp.sum(sizes).astype(jnp.float32)
    mean = total / sizes.shape[0]
    return jnp.stack([
        jnp.sum(sizes > 0).astype(jnp.float32),
        jnp.max(sizes).astype(jnp.float32) / jnp.maximum(mean, 1e-9),
        total])
