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
to the whole layer. Where a chip holds 16 of 256, most assignments land on
experts it does not hold: those rows sort behind the held groups and the
grouped products never visit them.

The router is data (:func:`route`): ``"softmax"`` (the gates are the
softmax over the chosen logits) or ``"sigmoid"`` (sigmoid scores, a
correction bias that moves the choice and not the gate, the gates divided
by their sum over the chosen and scaled). So is the experts' activation
(ReLU or SiLU on the gate product), and so is a clamp of a gated-SiLU
expert (:func:`swiglu`: ``silu(min(gate, L)) * clip(up, -L, L)`` where a
model sets ``swiglu_limit`` ``L``); a model may add a shared expert that
every token reads (:func:`shared`)."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


@jax.named_scope("moe_route")
def route(x: jax.Array, w_r: jax.Array, k: int, scoring: str = "softmax",
          bias: Optional[jax.Array] = None, scale: float = 1.0
          ) -> Tuple[jax.Array, jax.Array]:
    """The ``k`` experts of each row of ``x [T, d]`` and their gates.
    ``"softmax"``: ``softmax(x W_r)`` over all experts, kept on the ``k``
    largest logits (ties to the lower index) and divided by their sum,
    which is the softmax over the chosen logits. ``"sigmoid"``: scores
    ``s = sigmoid(x W_r)``; the chosen are the ``k`` largest of ``s + bias``
    (the correction bias moves the choice alone), their gates ``scale * s /
    sum of the chosen s``. Float32 at ``highest``: the product is small,
    and the choice then differs from a float32 reference's only at true
    near-ties. Returns ``choice [T, k]`` int32 and ``gates [T, k]``."""
    logits = jnp.dot(x.astype(jnp.float32), w_r.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, choice = lax.top_k(scores if bias is None else scores + bias, k)
        kept = jnp.take_along_axis(scores, choice, axis=-1)
        return choice.astype(jnp.int32), \
            scale * kept / jnp.sum(kept, axis=-1, keepdims=True)
    if scoring != "softmax":
        raise ValueError(f"no router scoring named {scoring!r}; have "
                         f"'softmax' and 'sigmoid'")
    top, choice = lax.top_k(logits, k)
    return choice.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def swiglu(gate: jax.Array, up: jax.Array, limit: float) -> jax.Array:
    """``silu(min(gate, limit)) * clip(up, -limit, limit)``: a gated-SiLU
    product whose gate is clamped from above and whose up projection is
    clamped both ways (``swiglu_limit``)."""
    return jax.nn.silu(jnp.minimum(gate, limit)) \
        * jnp.clip(up, -limit, limit)


@jax.named_scope("moe_experts")
def experts(h: jax.Array, choice: jax.Array, gates: jax.Array,
            w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
            held: Optional[Sequence[int]] = None,
            valid: Optional[jax.Array] = None, act=jax.nn.relu,
            limit: float = 0.0) -> Tuple[jax.Array, jax.Array]:
    """``sum_j gates[t, j] W_down,e (act(W_gate,e h_t) * W_up,e h_t)``,
    ``e = choice[t, j]``, over the experts whose tables were handed in
    (``act``: ReLU, or SiLU for a gated-SiLU expert, clamped by
    :func:`swiglu` where ``limit`` is set).

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
    if limit:
        mid = swiglu(grouped(rows, w_gate), grouped(rows, w_up), limit)
    else:
        mid = act(grouped(rows, w_gate)) * grouped(rows, w_up)
    out = grouped(mid.astype(w_down.dtype), w_down)
    # rows past the last group belong to no expert held here
    kept = jnp.arange(t * k) < jnp.sum(sizes)
    out = jnp.where(kept[:, None], out, 0.0)
    back = jnp.take(out, jnp.argsort(order), axis=0).reshape(t, k, -1)
    return jnp.sum(back * gates[..., None].astype(jnp.float32), axis=1), sizes


@jax.named_scope("moe_shared")
def shared(h: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, limit: float = 0.0) -> jax.Array:
    """The shared expert that every token reads: a plain gated-SiLU
    product ``W_down (silu(W_gate h) * W_up h)`` of ``h [T, d]`` (clamped
    by :func:`swiglu` where ``limit`` is set), float32 accumulation. Every
    chip of an expert-parallel layer computes it alike, so the shares of
    the routed experts add up with it counted once."""
    def product(a, w):
        return jnp.dot(a.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)
    if limit:
        return product(swiglu(product(h, w_gate), product(h, w_up), limit),
                       w_down)
    return product(jax.nn.silu(product(h, w_gate)) * product(h, w_up),
                   w_down)


def load_stats(sizes: jax.Array) -> jax.Array:
    """``[experts touched, the busiest expert's assignments over the mean,
    assignments]`` of one layer's ``sizes`` (all experts), float32."""
    total = jnp.sum(sizes).astype(jnp.float32)
    mean = total / sizes.shape[0]
    return jnp.stack([
        jnp.sum(sizes > 0).astype(jnp.float32),
        jnp.max(sizes).astype(jnp.float32) / jnp.maximum(mean, 1e-9),
        total])
