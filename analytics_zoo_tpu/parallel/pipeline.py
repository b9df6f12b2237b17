"""Pipeline parallelism: GPipe forward streaming + a 1F1B training schedule.

The reference has no pipeline parallelism (SURVEY §5); this completes the
mesh-axis set. TPU-native design: one stage per device along a ``pipe``
mesh axis, activations hop stage→stage via ``lax.ppermute`` inside a
``lax.scan`` over ticks — the classic SPMD pipeline from the scaling
playbook.

Two schedules:

- :func:`pipeline_apply` / :func:`gpipe` — the forward GPipe stream
  (``M + P - 1`` ticks, bubble ``(P-1)/(M+P-1)``), differentiable through
  scan+ppermute autodiff (grads hop backwards for free).
- :func:`make_pipeline_loss` — the TRAINING schedule: a single
  ``lax.scan`` over ``M + 2(P-1)`` ticks where every steady-state tick
  runs one microbatch forward AND one microbatch backward (1F1B). The
  backward recomputes the stage forward from a saved input (``jax.vjp``
  per tick), so in-flight activation storage is bounded by ``2P-1``
  microbatch inputs per stage — O(P), not O(M) — the 1F1B memory bound
  via recompute. Exposed as a ``jax.custom_vjp`` loss so it drops
  straight into ``Estimator.train``'s ``value_and_grad``.

Both schedules' tick bodies are zoolint hot-path policed: loop-free, no
host syncs, no densification.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import metrics as _metrics

PIPE_AXIS = "pipe"

_M_BUBBLE = _metrics.gauge(
    "parallel.pipeline_bubble_ratio",
    "Idle fraction of the compiled pipeline schedule: 2(P-1)/(M+2(P-1)) "
    "for the 1F1B training scan, (P-1)/(M+P-1) for the forward GPipe "
    "stream. Set when the pipelined step is built.")
_M_COLLECTIVE = _metrics.counter(
    "parallel.collective_bytes_total",
    "Estimated bytes moved by model-parallel collectives (pipeline "
    "ppermute hops, MoE all-to-all exchanges, ring-attention KV "
    "rotations), attributed at trace/build time per compiled step — the "
    "same static-attribution convention as embed.exchange_bytes_total.")


def note_collective_bytes(n: int) -> None:
    """Host-side hook: other parallel modules (MoE exchange, ring
    attention) account their per-step collective traffic here."""
    if n > 0:
        _M_COLLECTIVE.inc(int(n))


def bubble_fraction(n_stages: int, n_microbatches: int,
                    schedule: str = "1f1b") -> float:
    """Idle fraction of the pipeline schedule. The 1F1B training scan runs
    ``M + 2(P-1)`` ticks for ``M`` microbatch forwards+backwards; the
    forward-only stream runs ``M + P - 1``."""
    p, m = n_stages, n_microbatches
    if schedule == "1f1b":
        return 2 * (p - 1) / (m + 2 * (p - 1)) if m + 2 * (p - 1) else 0.0
    return (p - 1) / (m + p - 1) if m + p - 1 else 0.0


def note_pipeline_build(n_stages: int, n_microbatches: int,
                        micro_bytes: int = 0,
                        schedule: str = "1f1b") -> None:
    """Publish the schedule's bubble fraction (profiler gauge) and its
    per-step ppermute traffic estimate: every tick each device sends one
    microbatch activation around the forward ring, plus one cotangent
    around the backward ring under 1F1B."""
    _M_BUBBLE.set(bubble_fraction(n_stages, n_microbatches, schedule))
    if micro_bytes:
        ticks = (n_microbatches + 2 * (n_stages - 1) if schedule == "1f1b"
                 else n_microbatches + n_stages - 1)
        rings = 2 if schedule == "1f1b" else 1
        _M_COLLECTIVE.inc(int(ticks * rings * micro_bytes * n_stages))


def stack_stage_params(per_stage_params) -> Any:
    """[stage0_tree, stage1_tree, ...] → one tree with a leading stage axis
    (shard it over the ``pipe`` axis)."""
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves),
                                  *per_stage_params)


def _ring_perm(p: int):
    """Forward ring: stage i sends to stage i+1."""
    return [(i, (i + 1) % p) for i in range(p)]


def _ring_perm_rev(p: int):
    """Backward ring: stage i sends to stage i-1 (cotangent hops)."""
    return [(i, (i - 1) % p) for i in range(p)]


def _axis_size(axis_name: str) -> int:
    """Static size of a bound mesh axis from inside a shard_map body: a
    plain int, usable for perm tables and scan lengths."""
    return lax.axis_size(axis_name)


def _vary(a, axis_name: str):
    """Make ``a`` device-varying over ``axis_name`` — scan carries under
    shard_map must already carry the varying-axis type the ppermute
    introduces."""
    return lax.pcast(a, (axis_name,), to="varying")


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array,
                   n_microbatches: int = 4,
                   axis_name: str = PIPE_AXIS) -> jax.Array:
    """Per-shard body: run ``x [batch, ...]`` through the stage pipeline.

    ``stage_params`` is this device's slice of the stage-stacked tree (a
    leading axis of size 1, from sharding the stage axis over ``pipe``).
    Every stage must preserve the activation SHAPE (classic GPipe constraint
    for the rotating buffer); project before/after the pipelined trunk if
    widths differ.
    """
    p = _axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    leaves = jax.tree_util.tree_leaves(stage_params)
    if leaves and leaves[0].shape[0] != 1:
        raise ValueError(
            f"pipeline_apply expects ONE stage per device; this shard holds "
            f"{leaves[0].shape[0]} stages — the stage count must equal the "
            f"'{axis_name}' mesh axis size")
    local_params = jax.tree_util.tree_map(lambda l: l[0], stage_params)
    m = n_microbatches
    batch = x.shape[0]
    if batch % m:
        raise ValueError(f"batch {batch} not divisible by "
                         f"n_microbatches {m}")
    mb = batch // m
    micro = x.reshape(m, mb, *x.shape[1:])

    # the initial carry must already carry the device-varying type scan
    # requires under shard_map (the ppermute makes later carries varying);
    # derive it from the INPUT (times zero) so it inherits x's varying
    # axes too — under a combined mesh (dp x pp) x is data-varying, and a
    # carry missing that axis fails scan's vma check
    buf0 = _vary(micro[0] * 0, axis_name)
    out_acc0 = _vary(micro * 0, axis_name)
    perm = _ring_perm(p)

    def tick(carry, t):
        buf, out_acc = carry
        # stage 0 ingests microbatch t (while t < m); later stages consume
        # the activation that just hopped in from the previous stage
        feed = micro[jnp.minimum(t, m - 1)]
        inp = jnp.where(stage == 0, feed, buf)
        out = stage_fn(local_params, inp)
        # the LAST stage's output for tick t is microbatch t-(p-1)
        out_idx = t - (p - 1)
        is_valid = jnp.logical_and(stage == p - 1, out_idx >= 0)
        updated = lax.dynamic_update_index_in_dim(
            out_acc, out, jnp.maximum(out_idx, 0), 0)
        out_acc = jnp.where(is_valid, updated, out_acc)
        buf = lax.ppermute(out, axis_name, perm)
        return (buf, out_acc), None

    (_, out_acc), _ = lax.scan(tick, (buf0, out_acc0),
                               jnp.arange(m + p - 1))
    # every device returns the same logical result: broadcast the last
    # stage's accumulator around the ring so out_specs can be replicated
    out_acc = lax.psum(
        jnp.where(stage == p - 1, out_acc, jnp.zeros_like(out_acc)),
        axis_name)
    return out_acc.reshape(batch, *out_acc.shape[2:])


def gpipe(mesh, stage_fn: Callable, per_stage_params,
          n_microbatches: int = 4, axis_name: str = PIPE_AXIS):
    """Global entry: returns ``(stacked_params, fn)`` where ``fn(params, x)``
    runs the pipelined forward over ``mesh[axis_name]`` and is fully
    differentiable (use inside a loss under ``jax.grad``)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_stages = len(per_stage_params)
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    if n_stages != axis_size:
        raise ValueError(f"{n_stages} stages but the '{axis_name}' mesh "
                         f"axis has {axis_size} devices (one stage each)")
    note_pipeline_build(n_stages, n_microbatches, schedule="gpipe")
    stacked = stack_stage_params(per_stage_params)
    fn = shard_map(
        partial(pipeline_apply, stage_fn, n_microbatches=n_microbatches,
                axis_name=axis_name),
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(axis_name), stacked),
                  P()),
        out_specs=P())
    return stacked, fn


# -- the 1F1B training schedule ----------------------------------------------


def _masked_add(acc, upd, keep):
    """acc + upd where ``keep`` (scalar bool), leafwise over trees."""
    return jax.tree_util.tree_map(
        lambda a, u: a + jnp.where(keep, u, jnp.zeros_like(u)), acc, upd)


def _tree_zeros(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def _tree_vary(tree, axis_name):
    return jax.tree_util.tree_map(lambda l: _vary(l, axis_name), tree)


def _pipe_fwd_body(stage_fn, head_loss_fn, n_microbatches, axis_name,
                   stacked, head, x, y):
    """Per-shard PRIMAL body: forward GPipe stream, per-microbatch head
    loss at the last stage, mean loss broadcast to every device."""
    p = _axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    m = n_microbatches
    mb = x.shape[0] // m
    micro_x = x.reshape(m, mb, *x.shape[1:])
    micro_y = y.reshape(m, mb, *y.shape[1:])
    perm = _ring_perm(p)
    buf0 = _vary(micro_x[0] * 0, axis_name)

    def tick(carry, t):
        buf, loss_acc = carry
        fwd_idx = t - stage
        valid_f = jnp.logical_and(fwd_idx >= 0, fwd_idx < m)
        feed = micro_x[jnp.clip(fwd_idx, 0, m - 1)]
        inp = jnp.where(stage == 0, feed, buf)
        out = stage_fn(stacked, inp)
        yb = micro_y[jnp.clip(fwd_idx, 0, m - 1)]
        lm_loss = head_loss_fn(head, out, yb) / m
        take = jnp.logical_and(stage == p - 1, valid_f)
        loss_acc = loss_acc + jnp.where(take, lm_loss, 0.0)
        buf = lax.ppermute(out, axis_name, perm)
        return (buf, loss_acc), None

    loss0 = _vary(jnp.zeros((), jnp.float32), axis_name)
    (_, loss_acc), _ = lax.scan(tick, (buf0, loss0), jnp.arange(m + p - 1))
    return lax.psum(jnp.where(stage == p - 1, loss_acc, 0.0), axis_name)


def _pipe_1f1b_body(stage_fn, head_loss_fn, n_microbatches, axis_name,
                    stacked, head, x, y, g):
    """Per-shard 1F1B body: one scan over ``M + 2(P-1)`` ticks; every tick
    runs one microbatch forward step AND one microbatch backward step
    (``jax.vjp`` recompute from the saved stage input). Stage ``s`` runs
    forward of microbatch ``t - s`` and backward of ``t - 2(P-1) + s`` —
    at the last stage the two indices coincide, so the head-loss cotangent
    computed from this tick's forward output seeds this tick's backward
    directly; upstream stages receive cotangents off the reverse ring.
    Activation inputs live in a rolling buffer of depth ``2P-1``: the 1F1B
    O(P) in-flight bound, independent of the microbatch count."""
    p = _axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    m = n_microbatches
    mb = x.shape[0] // m
    micro_x = x.reshape(m, mb, *x.shape[1:])
    micro_y = y.reshape(m, mb, *y.shape[1:])
    perm_f = _ring_perm(p)
    perm_b = _ring_perm_rev(p)
    depth = 2 * p - 1
    head_vg = jax.value_and_grad(
        lambda h, o, yb: head_loss_fn(h, o, yb) / m, argnums=(0, 1))

    fbuf0 = _vary(micro_x[0] * 0, axis_name)
    bbuf0 = _vary(micro_x[0] * 0, axis_name)
    abuf0 = _vary(jnp.zeros((depth, mb) + x.shape[1:], x.dtype), axis_name)
    dx0 = _vary(micro_x * 0, axis_name)
    dp0 = _tree_vary(_tree_zeros(stacked), axis_name)
    dh0 = _tree_vary(_tree_zeros(head), axis_name)
    loss0 = _vary(jnp.zeros((), jnp.float32), axis_name)

    def tick(carry, t):
        fbuf, bbuf, abuf, dp_acc, dh_acc, dx_buf, loss_acc = carry
        is_last = stage == p - 1
        # -- forward micro-step -------------------------------------------
        fwd_idx = t - stage
        valid_f = jnp.logical_and(fwd_idx >= 0, fwd_idx < m)
        feed = micro_x[jnp.clip(fwd_idx, 0, m - 1)]
        inp = jnp.where(stage == 0, feed, fbuf)
        out = stage_fn(stacked, inp)
        abuf = jnp.where(
            valid_f,
            lax.dynamic_update_index_in_dim(abuf, inp, fwd_idx % depth, 0),
            abuf)
        # head loss + its cotangent for the microbatch the last stage just
        # finished (fwd_idx == bwd_idx there, so it feeds backward now)
        yb = micro_y[jnp.clip(fwd_idx, 0, m - 1)]
        lm_loss, (dhead, dout) = head_vg(head, out, yb)
        take = jnp.logical_and(is_last, valid_f)
        loss_acc = loss_acc + jnp.where(take, lm_loss, 0.0)
        dh_acc = _masked_add(dh_acc, dhead, take)
        # -- backward micro-step ------------------------------------------
        bwd_idx = t - 2 * (p - 1) + stage
        valid_b = jnp.logical_and(bwd_idx >= 0, bwd_idx < m)
        x_saved = lax.dynamic_index_in_dim(
            abuf, jnp.clip(bwd_idx, 0, m - 1) % depth, 0, keepdims=False)
        cot = jnp.where(is_last, dout.astype(x.dtype),
                        bbuf).astype(x.dtype)
        _, stage_vjp = jax.vjp(stage_fn, stacked, x_saved)
        dp, dx = stage_vjp(cot.astype(out.dtype))
        dp_acc = _masked_add(dp_acc, dp, valid_b)
        dx_buf = jnp.where(
            jnp.logical_and(valid_b, stage == 0),
            lax.dynamic_update_index_in_dim(
                dx_buf, dx.astype(x.dtype), jnp.clip(bwd_idx, 0, m - 1), 0),
            dx_buf)
        fbuf = lax.ppermute(out, axis_name, perm_f)
        bbuf = lax.ppermute(dx.astype(x.dtype), axis_name, perm_b)
        return (fbuf, bbuf, abuf, dp_acc, dh_acc, dx_buf, loss_acc), None

    carry0 = (fbuf0, bbuf0, abuf0, dp0, dh0, dx0, loss0)
    (_, _, _, dp_acc, dh_acc, dx_buf, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(m + 2 * (p - 1)))
    # grads of replicated args must come back axis-invariant: the head
    # grads live only on the last stage, dx only on stage 0 — psum the
    # masked values around the ring; stage-sharded dp stays per-stage
    dh_acc = jax.tree_util.tree_map(
        lambda l: lax.psum(jnp.where(stage == p - 1, l, jnp.zeros_like(l)),
                           axis_name), dh_acc)
    dx = lax.psum(
        jnp.where(stage == 0, dx_buf, jnp.zeros_like(dx_buf)), axis_name)
    loss = lax.psum(jnp.where(stage == p - 1, loss_acc, 0.0), axis_name)
    gs = g.astype(jnp.float32)
    dp_acc = jax.tree_util.tree_map(lambda l: l * gs.astype(l.dtype), dp_acc)
    dh_acc = jax.tree_util.tree_map(lambda l: l * gs.astype(l.dtype), dh_acc)
    dx = (dx.reshape(x.shape) * gs.astype(dx.dtype)
          if jnp.issubdtype(x.dtype, jnp.floating)
          else dx.reshape(x.shape))
    return dp_acc, dh_acc, dx, loss


def make_pipeline_loss(stage_fn: Callable, head_loss_fn: Callable, mesh,
                       n_microbatches: int = 4,
                       axis_name: str = PIPE_AXIS) -> Callable:
    """Build the pipelined training loss ``loss(stacked, head, x, y)``.

    - ``stage_fn(local_stacked, x) -> x`` applies this device's stage
      slice (leading local stage axis of 1 retained) to one microbatch,
      preserving the activation shape.
    - ``head_loss_fn(head_params, trunk_out, y_micro) -> scalar`` applies
      the post-trunk head (final norm / logits / objective) to one
      microbatch.

    The primal runs the forward GPipe stream; the custom VJP runs the
    1F1B scan (:func:`_pipe_1f1b_body`), returning stage-sharded grads
    for ``stacked``, replicated grads for ``head``, and the input
    cotangent for ``x`` (so the embedding upstream of the pipelined trunk
    trains normally). Integer ``y`` gets a ``float0`` zero cotangent.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]

    def specs(stacked, head):
        return (jax.tree_util.tree_map(lambda _: P(axis_name), stacked),
                jax.tree_util.tree_map(lambda _: P(), head), P(), P())

    @jax.custom_vjp
    def ploss(stacked, head, x, y):
        fwd = shard_map(
            partial(_pipe_fwd_body, stage_fn, head_loss_fn, n_microbatches,
                    axis_name),
            mesh=mesh, in_specs=specs(stacked, head), out_specs=P())
        return fwd(stacked, head, x, y)

    def ploss_fwd(stacked, head, x, y):
        return ploss(stacked, head, x, y), (stacked, head, x, y)

    def ploss_bwd(res, g):
        stacked, head, x, y = res
        bwd = shard_map(
            partial(_pipe_1f1b_body, stage_fn, head_loss_fn, n_microbatches,
                    axis_name),
            mesh=mesh,
            in_specs=specs(stacked, head) + (P(),),
            out_specs=(jax.tree_util.tree_map(lambda _: P(axis_name),
                                              stacked),
                       jax.tree_util.tree_map(lambda _: P(), head),
                       P(), P()))
        dstacked, dhead, dx, _ = bwd(stacked, head, x, y,
                                     jnp.asarray(g, jnp.float32))
        if not jnp.issubdtype(x.dtype, jnp.floating):
            dx = np.zeros(x.shape, jax.dtypes.float0)
        dy = np.zeros(y.shape, jax.dtypes.float0) \
            if not jnp.issubdtype(y.dtype, jnp.floating) \
            else jnp.zeros_like(y)
        return dstacked, dhead, dx, dy

    ploss.defvjp(ploss_fwd, ploss_bwd)

    def loss_fn(stacked, head, x, y):
        leaves = jax.tree_util.tree_leaves(stacked)
        if leaves and leaves[0].shape[0] != axis_size:
            raise ValueError(
                f"stacked params carry {leaves[0].shape[0]} stages but the "
                f"'{axis_name}' mesh axis has {axis_size} devices")
        if x.shape[0] % n_microbatches:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by n_microbatches "
                f"{n_microbatches}")
        return ploss(stacked, head, x, y)

    return loss_fn
