"""Mixture-of-Experts with expert parallelism.

The reference has no MoE (SURVEY §5: no expert parallelism anywhere); this
is new TPU-native capability completing the mesh-axis set (dp/tp/sp/ep).

Design: top-k routing (k=1 "switch", k=2 GShard-style) with DENSE
dispatch — per-token gate probabilities become a one-hot combine matrix
and expert computation is ONE batched einsum over [experts, capacity, d]
regardless of k (per-choice dispatch tensors occupy disjoint capacity
slots and sum into a single dispatch). No gather/scatter with dynamic
shapes, so XLA tiles everything onto the MXU and the `expert` mesh axis
shards the expert dimension of both the parameters and the dispatched
tokens; the all-to-all that moves tokens to their experts is the einsum's
collective, inserted by XLA from the shardings.

``MoE`` is a Keras-engine layer (drop into Sequential/Model); pass
``param_sharding_rules=[moe_sharding_rule]`` to the Estimator to place the
expert axis on the mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..common import metrics as _metrics
from ..keras import initializers
from ..keras.engine import AUX_LOSS_KEY, MOE_DROP_KEY, Layer

EXPERT_AXIS = "expert"

_M_DROPPED = _metrics.counter(
    "parallel.moe_dropped_tokens_total",
    "Tokens whose every top-k expert choice overflowed capacity and rode "
    "the residual path untouched. MoE layers accumulate the count in "
    "model state under the __moe_dropped__ contract; the Estimator "
    "drains it here at its per-epoch sync point — capacity-factor "
    "dropping is never silent.")


def drain_drop_counter(total: int, seen: int) -> int:
    """Host-side hook for the Estimator's per-epoch drain: publish the
    delta between the state-accumulated drop ``total`` and the last
    drained value, returning the new high-water mark."""
    if total > seen:
        _M_DROPPED.inc(int(total - seen))
        return int(total)
    return int(seen)


def _expert_exchange(xin, w_in, b_in, w_out, b_out, act, axis_name):
    """Per-shard expert FFN via the explicit fixed-size exchange — the
    PR 7 embedding-exchange shape (route → local compute → reverse): token
    groups arrive sharded over the expert axis, one ``all_to_all`` swaps
    the sharding from groups to experts (every device sends each peer its
    capacity slots for that peer's experts — fixed-size, so shapes stay
    static and no host sync is needed), each device runs ONLY its local
    experts' FFN, and the reverse ``all_to_all`` sends results home. The
    per-slot arithmetic is identical to the dense einsum path, so the two
    are bit-compatible."""
    routed = lax.all_to_all(xin, axis_name, split_axis=1, concat_axis=0,
                            tiled=True)
    h = act(jnp.einsum("gecd,edh->gech", routed, w_in)
            + b_in[None, :, None, :])
    out = (jnp.einsum("gech,ehd->gecd", h, w_out)
           + b_out[None, :, None, :])
    return lax.all_to_all(out, axis_name, split_axis=0, concat_axis=1,
                          tiled=True)


def _exchange_mesh(g: int, e: int, mode: str):
    """Static routing decision: the mesh to run the explicit all-to-all
    exchange over, or None for the dense-dispatch path. ``alltoall``
    demands it (raising when shapes can't ride the exchange); ``auto``
    falls back to dense when no expert-axis mesh is active or the group/
    expert counts don't divide over it."""
    if mode == "dense":
        return None
    from .embedding import default_mesh
    mesh = default_mesh()
    has_axis = mesh is not None and EXPERT_AXIS in mesh.axis_names
    n = (dict(zip(mesh.axis_names, mesh.devices.shape))[EXPERT_AXIS]
         if has_axis else 0)
    ok = has_axis and n > 0 and g % n == 0 and e % n == 0
    if mode == "alltoall" and not ok:
        raise ValueError(
            f"moe exchange='alltoall' needs a mesh with an '{EXPERT_AXIS}' "
            f"axis whose size divides groups ({g}) and experts ({e}); "
            f"active mesh: {None if mesh is None else mesh.axis_names}")
    return mesh if ok else None


class MoE(Layer):
    """Switch-style MoE feed-forward block: ``y = combine(expert_ffn(
    dispatch(x)))`` with a load-balancing auxiliary loss published through
    the ``AUX_LOSS_KEY`` state contract (the Estimator adds it to the
    objective with a fixed weight).

    Input ``[batch, seq, d]`` (or ``[batch, d]``); each token routes to its
    top-``k`` experts (k=1 switch, k=2 GShard with renormalized gates),
    subject to ``capacity_factor`` per choice — total slots scale with k
    (the GShard ``k * tokens * C / e`` convention); tokens whose every
    choice overflows ride the residual path untouched.
    """

    def __init__(self, num_experts: int, hidden_dim: int,
                 capacity_factor: Optional[float] = None,
                 aux_loss_weight: float = 1e-2,
                 group_size: int = 4096,
                 activation: str = "relu",
                 init: str = "glorot_uniform",
                 k: int = 1,
                 exchange: Optional[str] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        from ..common.config import global_config
        if not 1 <= k <= num_experts:
            raise ValueError(f"k={k} must be in [1, num_experts]")
        self.num_experts = num_experts
        self.hidden_dim = hidden_dim
        if capacity_factor is None:
            capacity_factor = float(
                global_config().get("parallel.moe_capacity_factor"))
        self.capacity_factor = capacity_factor
        # expert dispatch: dense one-hot einsums (XLA derives the
        # collective from the shardings) vs the explicit fixed-size
        # all-to-all exchange; 'auto' takes the exchange whenever an
        # expert-axis mesh is active and the shapes divide over it
        exchange = exchange if exchange is not None else str(
            global_config().get("parallel.moe_exchange"))
        if exchange not in ("dense", "alltoall", "auto"):
            raise ValueError(f"exchange={exchange!r} must be 'dense', "
                             f"'alltoall' or 'auto'")
        self.exchange = exchange
        self.aux_loss_weight = aux_loss_weight
        # routing happens within fixed-size token GROUPS so the dispatch
        # one-hot stays linear in the token count (a single global group
        # would be O(tokens^2) memory)
        self.group_size = group_size
        self.activation = activation
        self.init = initializers.get(init)
        # k=1 is the Switch transformer; k=2 the GShard top-2 router (gates
        # renormalized over the chosen experts, first choices claim
        # capacity before second choices)
        self.k = k

    def build(self, rng, input_shape):
        d = input_shape[-1]
        k1, k2, k3 = jax.random.split(rng, 3)
        params = {
            "gate": self.init(k1, (d, self.num_experts)),
            # expert-major parameter blocks: axis 0 shards over `expert`
            "w_in": self.init(k2, (self.num_experts, d, self.hidden_dim)),
            "b_in": jnp.zeros((self.num_experts, self.hidden_dim)),
            "w_out": self.init(k3, (self.num_experts, self.hidden_dim, d)),
            "b_out": jnp.zeros((self.num_experts, d)),
        }
        # the load-balance loss travels through state under the generic
        # `__aux_loss__` contract (the Estimator adds it to the objective);
        # the drop counter accumulates under `__moe_dropped__` and is
        # drained into parallel.moe_dropped_tokens_total per epoch
        return params, {AUX_LOSS_KEY: jnp.zeros((), jnp.float32),
                        MOE_DROP_KEY: jnp.zeros((), jnp.int32)}

    def call(self, params, state, inputs, *, training=False, rng=None):
        from ..keras.layers.core import get_activation
        act = get_activation(self.activation)
        squeeze = inputs.ndim == 2
        x = inputs[:, None, :] if squeeze else inputs
        b, s, d = x.shape
        n_tok = b * s
        e = self.num_experts

        flat = x.reshape(n_tok, d)
        gsz = min(self.group_size, n_tok)
        pad = (-n_tok) % gsz
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad, d), flat.dtype)])
        g = flat.shape[0] // gsz
        grouped = flat.reshape(g, gsz, d)
        # GShard capacity convention: slots scale with k so second
        # choices aren't starved at the default capacity_factor
        cap = max(1, int(self.k * self.capacity_factor * gsz / e))

        # alignment pad rows must neither consume expert capacity nor
        # count in the balance statistics
        valid = (jnp.arange(g * gsz) < n_tok).reshape(g, gsz)

        logits = jnp.einsum("gtd,de->gte", grouped,
                            params["gate"].astype(flat.dtype)
                            ).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)            # [g, t, e]

        # top-k choices per token (argmax of the remaining probs each round)
        remaining = probs
        onehots, gates = [], []
        for _ in range(self.k):
            idx_c = jnp.argmax(remaining, axis=-1)         # [g, t]
            oh_c = jax.nn.one_hot(idx_c, e, dtype=jnp.float32)  # zoolint: disable=jit-host-sync — expert-count one-hot (e static and small): the GShard dispatch tensor, not a vocab densification
            gates.append(jnp.sum(probs * oh_c, axis=-1))
            onehots.append(oh_c * valid.astype(jnp.float32)[..., None])
            remaining = remaining * (1.0 - oh_c)
        if self.k > 1:  # GShard: gates renormalize over the chosen experts
            gate_sum = sum(gates)
            gates = [gc / jnp.maximum(gate_sum, 1e-9) for gc in gates]
        # k=1 keeps the RAW router probability (Switch transformer: the
        # gate scale is the router's gradient path)

        # capacity accounting: first choices claim slots before second
        # choices (the per-(group, expert) running count carries across
        # rounds), but the slots are DISJOINT, so all rounds merge into one
        # dispatch/combine pair and the expert FFN + all-to-all run ONCE
        claimed = jnp.zeros((g, 1, e), jnp.float32)
        dispatch_total = jnp.zeros((g, gsz, e, cap), flat.dtype)
        combine_total = jnp.zeros((g, gsz, e, cap), flat.dtype)
        any_kept = jnp.zeros(valid.shape, bool)
        onehot0 = onehots[0]  # choice-0 stats feed the balance loss
        for oh_c, gate_c in zip(onehots, gates):
            pos = ((jnp.cumsum(oh_c, axis=1) - 1.0) + claimed) * oh_c
            pos_in_expert = jnp.sum(pos, axis=-1).astype(jnp.int32)
            routed = jnp.sum(oh_c, axis=-1) > 0            # valid tokens
            keep = (pos_in_expert < cap) & routed          # capacity mask
            slot_onehot = jax.nn.one_hot(pos_in_expert, cap,  # zoolint: disable=jit-host-sync — capacity-slot one-hot (cap static and small): the GShard combine layout, not a vocab densification
                                         dtype=flat.dtype)
            dispatch = (oh_c.astype(flat.dtype)[..., None]
                        * slot_onehot[..., None, :]
                        * keep.astype(flat.dtype)[..., None, None])
            dispatch_total = dispatch_total + dispatch
            combine_total = combine_total + dispatch * gate_c.astype(
                flat.dtype)[..., None, None]
            any_kept = any_kept | keep
            claimed = claimed + jnp.sum(oh_c * keep[..., None].astype(
                jnp.float32), axis=1, keepdims=True)

        # expert inputs [g, e, cap, d] — the fixed-size dispatch the
        # exchange routes (dense path: the contraction over tokens is
        # where XLA inserts the all-to-all under expert sharding)
        xin = jnp.einsum("gtec,gtd->gecd", dispatch_total, grouped)
        w_in = params["w_in"].astype(flat.dtype)
        b_in = params["b_in"].astype(flat.dtype)
        w_out = params["w_out"].astype(flat.dtype)
        b_out = params["b_out"].astype(flat.dtype)
        ex_mesh = _exchange_mesh(g, e, self.exchange)
        if ex_mesh is not None:
            from functools import partial
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            from .pipeline import note_collective_bytes
            tok_spec = P(EXPERT_AXIS, None, None, None)
            ex = shard_map(
                partial(_expert_exchange, act=act, axis_name=EXPERT_AXIS),
                mesh=ex_mesh,
                in_specs=(tok_spec, P(EXPERT_AXIS, None, None),
                          P(EXPERT_AXIS, None), P(EXPERT_AXIS, None, None),
                          P(EXPERT_AXIS, None)),
                out_specs=tok_spec)
            # trace-time attribution: route + reverse move the full
            # dispatch buffer across the expert axis once each per step
            note_collective_bytes(2 * xin.size * xin.dtype.itemsize)
            out = ex(xin, w_in, b_in, w_out, b_out)
        else:
            h = act(jnp.einsum("gecd,edh->gech", xin, w_in)
                    + b_in[None, :, None, :])
            out = (jnp.einsum("gech,ehd->gecd", h, w_out)
                   + b_out[None, :, None, :])
        combined = jnp.einsum("gtec,gecd->gtd", combine_total, out)
        # tokens whose every choice was dropped ride the residual path
        y = jnp.where(any_kept[..., None], combined, grouped)
        y = y.reshape(-1, d)[:n_tok].reshape(b, s, d)
        onehot = onehot0  # balance statistics below use the first choice

        # switch-transformer load-balance loss: e * Σ_e (frac_tokens_e *
        # frac_probs_e), averaged over groups; the Estimator consumes it
        # from state via the `__aux_loss__` contract
        denom = jnp.maximum(jnp.sum(valid, axis=1, keepdims=True), 1.0)
        frac_tokens = jnp.sum(onehot, axis=1) / denom      # [g, e]
        vprobs = probs * valid.astype(probs.dtype)[..., None]
        frac_probs = jnp.sum(vprobs, axis=1) / denom
        aux = e * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))
        # tokens whose EVERY choice overflowed: accumulated in state (the
        # Estimator drains the running count per epoch — never silent)
        dropped = jnp.sum(valid & ~any_kept).astype(jnp.int32)
        prev_drops = jnp.asarray(state.get(MOE_DROP_KEY, 0), jnp.int32)
        new_state = {AUX_LOSS_KEY: (aux * self.aux_loss_weight
                                    ).astype(jnp.float32),
                     MOE_DROP_KEY: prev_drops + dropped}
        return (y[:, 0, :] if squeeze else y), new_state

    def compute_output_shape(self, input_shape):
        return input_shape


def moe_sharding_rule(path, leaf):
    """Estimator ``param_sharding_rules`` entry: shard expert-major MoE
    parameter blocks over the ``expert`` mesh axis. Matches the LEAF key
    exactly — substring matching over the joined path would capture
    unrelated params whose names merely contain e.g. ``w_out``."""
    from jax.sharding import PartitionSpec as P
    leaf_key = str(getattr(path[-1], "key", path[-1])) if path else ""
    if leaf_key in ("w_in", "w_out", "b_in", "b_out") and leaf.ndim >= 2:
        return P(EXPERT_AXIS, *([None] * (leaf.ndim - 1)))
    return None
