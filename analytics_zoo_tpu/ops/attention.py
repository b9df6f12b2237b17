"""Attention kernels.

Three tiers, one contract (``[batch, heads, seq, head_dim]`` tensors):

- :func:`dot_product_attention` — plain XLA. The materialized ``[q, kv]``
  score matrix is fine at short lengths; XLA fuses the softmax chain.
- :func:`blockwise_attention` — flash-style streaming softmax over KV chunks
  via ``lax.scan`` (never materializes ``[q, kv]``). Runs everywhere (CPU
  tests, TPU), is differentiable through the scan, and is the building block
  ring attention reuses per hop (``parallel/ring_attention.py``).
- :func:`flash_attention` — pallas TPU kernels for BOTH directions: the
  forward (tiled q/kv blocks in VMEM, running max/denominator in scratch,
  bf16 MXU matmuls with f32 accumulation, per-row logsumexp residual) and a
  two-pass backward (dq grid, then dk/dv grid) that recomputes attention
  probabilities from the saved logsumexp — measured ~6x over autodiff
  through the blockwise scan at seq 4096 on v5e. Falls back to blockwise
  (scan autodiff) off-TPU and for the key-bias variant.

The reference has no long-context machinery (SURVEY §5: absent); this is the
new TPU-native capability that backs ``TransformerLayer``/``BERT`` and the
sequence-parallel mesh axis.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import dispatch

# v5e-tuned (scripts/sweep_flash_blocks.py, seq 4096 fwd+bwd train step,
# dispatch-cancelled differenced timing): 512/1024 is the consistent best
# across sweeps; q blocks >= 2048 overflow VMEM/registers in the exp2
# kernels. Shorter or indivisible sequences clamp via _largest_divisor_leq.
DEFAULT_Q_BLOCK = 512
DEFAULT_KV_BLOCK = 1024
_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() grads finite
_LOG2E = 1.4426950408889634  # the pallas kernels run softmax in exp2 space:
# scale*log2(e) folds into q OUTSIDE the kernel, turning the per-element
# `s*scale` multiply + `exp` into a bare `exp2` — at head_dim 64 the kernels
# are VPU-bound (softmax ops per element rival the 2·64 MXU flops), so every
# elementwise op removed is direct wall-clock
_LN2 = 1.0 / _LOG2E


def _largest_divisor_leq(n: int, cap: int) -> int:
    for c in range(min(n, cap), 0, -1):
        if n % c == 0:
            return c
    return 1


@jax.named_scope("kv_attend")
def masked_context(q: jax.Array, k_buf: jax.Array, v_buf: jax.Array,
                   visible: jax.Array, scale: float) -> jax.Array:
    """THE decode-cache attention arithmetic, shared verbatim by every KV
    engine (``ops/decode.py``: ``cached_attention``, ``slot_attention``,
    ``paged_attention`` and the speculative verify path).

    ``softmax(q k^T * scale  masked to `visible`) v`` with f32 score/context
    accumulation. One shared body is what makes the engines' bit-identity
    guarantees structural rather than coincidental: invisible positions are
    forced to exactly ``_NEG_INF`` so their softmax probability underflows
    to exactly 0.0 — the masked tail contributes exact-zero terms to the
    context sum, which is why buffers that differ only in masked positions
    (contiguous garbage vs paged-pool garbage vs right-padding) still
    produce bit-identical contexts.

    ``q``: ``[B, H, T, D]``; ``k_buf``/``v_buf``: ``[B, H, K, D]``;
    ``visible`` broadcasts against scores ``[B, H, T, K]``.
    """
    s = jnp.einsum("bhtd,bhkd->bhtk", q, k_buf,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(visible, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhtk,bhkd->bhtd", p.astype(v_buf.dtype), v_buf,
                     preferred_element_type=jnp.float32)
    return ctx.astype(q.dtype)


@jax.named_scope("attn_reference")
def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          bias: Optional[jax.Array] = None,
                          causal: bool = False,
                          scale: Optional[float] = None,
                          dropout_rate: float = 0.0,
                          dropout_rng: Optional[jax.Array] = None) -> jax.Array:
    """Reference attention: softmax(q k^T / sqrt(d) + bias) v, with optional
    attention-probability dropout (training regularizer)."""
    *_, q_len, head_dim = q.shape
    kv_len = k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(head_dim)
    scores = jnp.einsum("...qd,...kd->...qk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        scores = scores + bias
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 0)
        ki = lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 1)
        scores = jnp.where(qi >= ki, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = 1.0 - dropout_rate
        mask = jax.random.bernoulli(dropout_rng, keep, probs.shape)
        probs = jnp.where(mask, probs / keep, 0.0)
    return jnp.einsum("...qk,...kd->...qd", probs.astype(v.dtype), v)


@jax.named_scope("attn_reference")
def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        bias: Optional[jax.Array] = None,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        q_block: int = DEFAULT_Q_BLOCK,
                        kv_block: int = DEFAULT_KV_BLOCK,
                        dropout_rate: float = 0.0,
                        dropout_rng: Optional[jax.Array] = None,
                        return_lse: bool = False):
    """Streaming-softmax attention over KV chunks; O(seq) memory.

    ``bias`` broadcasts against ``[batch, heads, q_len, kv_len]``.
    Attention-probability dropout is applied per KV block (the mask derives
    from ``fold_in(rng, block_index)``, so the full [q, kv] probability
    matrix never materializes); the streaming denominator accumulates the
    UNDROPPED weights, making the result exactly standard post-softmax
    dropout. ``return_lse`` also returns the per-row logsumexp
    ``[b, h, q_len]`` (partial-attention merging, ring hops).
    """
    b, h, q_len, d = q.shape
    kv_len = k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = _largest_divisor_leq(q_len, q_block)
    bk = _largest_divisor_leq(kv_len, kv_block)
    n_q, n_kv = q_len // bq, kv_len // bk

    if bias is not None:
        bias = jnp.broadcast_to(bias, (b, h, q_len, kv_len))

    q = q.reshape(b, h, n_q, bq, d)
    k_chunks = k.reshape(b, h, n_kv, bk, d).transpose(2, 0, 1, 3, 4)
    v_chunks = v.reshape(b, h, n_kv, bk, d).transpose(2, 0, 1, 3, 4)
    dropping = dropout_rate > 0.0 and dropout_rng is not None

    def one_q_chunk(args):
        qc, qi = args  # qc: [b, h, bq, d]

        def kv_step(carry, inp):
            acc, m, l = carry
            kc, vc, ki = inp
            s = jnp.einsum("bhqd,bhkd->bhqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            if bias is not None:
                bslice = lax.dynamic_slice(
                    bias, (0, 0, qi * bq, ki * bk), (b, h, bq, bk))
                s = s + bslice
            if causal:
                rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                s = jnp.where(rows >= cols, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            # the softmax DENOMINATOR accumulates the undropped weights, so
            # the result equals standard post-softmax dropout exactly:
            # (Σ dropped_p·v) / (Σ p) = Σ dropout(softmax(s))·v
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            if dropping:
                block_rng = jax.random.fold_in(dropout_rng, qi * n_kv + ki)
                keep = jax.random.bernoulli(block_rng, 1.0 - dropout_rate,
                                            p.shape)
                p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            # p drops to the storage dtype for the MXU (bf16 multiplies with
            # f32 accumulation); f32xf32 would run ~8x slower on v5e
            acc_new = acc * corr + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        # init derives from qc*0 so it inherits qc's varying-axis type when
        # this runs inside shard_map (ulysses/ring sequence parallelism)
        zero_q = qc.astype(jnp.float32) * 0.0
        init = (zero_q, zero_q[..., :1] + _NEG_INF, zero_q[..., :1])
        (acc, m, l), _ = lax.scan(
            kv_step, init, (k_chunks, v_chunks, jnp.arange(n_kv)))
        o = (acc / jnp.maximum(l, 1e-30)).astype(v.dtype)
        if return_lse:
            return o, (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
        return o

    mapped = lax.map(one_q_chunk,
                     (q.transpose(2, 0, 1, 3, 4), jnp.arange(n_q)))
    if return_lse:
        out, lse = mapped
        return (out.transpose(1, 2, 0, 3, 4).reshape(b, h, q_len, d),
                lse.transpose(1, 2, 0, 3).reshape(b, h, q_len))
    return mapped.transpose(1, 2, 0, 3, 4).reshape(b, h, q_len, d)


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, scale: float, causal: bool,
                      bq: int, bk: int, has_bias: bool,
                      has_lse: bool = False):
    from jax.experimental import pallas as pl

    lse_ref = None
    if has_bias:
        bias_ref, o_ref, acc_ref, m_ref, l_ref = rest
    elif has_lse:
        bias_ref = None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        bias_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    run = True
    if causal:
        # skip fully-masked blocks (query rows all before kv cols)
        run = (qi + 1) * bq > ki * bk

    @pl.when(run)
    def _step():
        # inputs stay in their storage dtype (bf16 on the fast path): the
        # MXU natively multiplies bf16 with f32 accumulation — upcasting
        # first would force 8x-slower f32 matmul passes. q arrives
        # pre-multiplied by scale*log2(e), so s/m/l live in exp2 space.
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk], exp2 domain
        if bias_ref is not None:
            # per-key additive bias (padding mask), broadcast over query
            # rows; the bias is natural-log units → exp2 domain
            s = s + bias_ref[0].astype(jnp.float32) * _LOG2E  # [1, bk]
        if causal:
            rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] /
                    jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row logsumexp residual for the backward kernels, converted
            # back to natural-log units (the ring-merge contract)
            lse_ref[0, 0, :] = (m_ref[:, 0] * _LN2
                                + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30)))


def _keybias_block(kv_len: int, kv_block: int) -> Optional[int]:
    """KV block size usable for the bias operand: its (1, bk) VMEM tile must
    have bk divisible by 128 or equal to kv_len (TPU lane tiling). Returns
    None when no such block exists within reasonable VMEM."""
    for c in range(min(kv_len, kv_block), 127, -128):
        if kv_len % c == 0 and c % 128 == 0:
            return c
    if kv_len <= 4096:
        return kv_len  # single block: tiny bias row, k/v tiles still fit
    return None


def _vma_struct(shape, dtype, like):
    """ShapeDtypeStruct carrying the input's varying-manual-axes so
    pallas_call outputs satisfy shard_map's vma check (ulysses/ring run the
    kernel inside shard_map)."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


@jax.named_scope("attn_flash")
def _flash_fwd_pallas(q, k, v, scale: float, causal: bool,
                      q_block: int, kv_block: int,
                      key_bias: Optional[jax.Array] = None,
                      return_lse: bool = False):
    """``key_bias``: optional [batch, kv_len] additive per-key bias (the
    padding-mask form) applied inside the kernel. ``return_lse`` also
    returns the per-row logsumexp ``[b, h, q_len]`` (the backward kernels'
    residual); only supported without ``key_bias``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, q_len, d = q.shape
    kv_len = k.shape[-2]
    bq = _largest_divisor_leq(q_len, q_block)
    bk = _largest_divisor_leq(kv_len, kv_block)
    if key_bias is not None:
        bk = _keybias_block(kv_len, kv_block)
        assert bk is not None  # dispatch checks before routing here
        # bias rides as [b, 1, kv_len] so its block's trailing dims obey the
        # (8, 128) tiling rules with a unit sublane
        key_bias = key_bias.reshape(b, 1, kv_len)
    bh = b * h
    # scale*log2e folds into q here — XLA fuses it into the preceding
    # producer, and the kernel's softmax runs in exp2 space with no
    # per-element multiplies
    qf = (q * (scale * _LOG2E)).astype(q.dtype).reshape(bh, q_len, d)
    kf = k.reshape(bh, kv_len, d)
    vf = v.reshape(bh, kv_len, d)

    grid = (bh, q_len // bq, kv_len // bk)
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, has_bias=key_bias is not None,
                               has_lse=return_lse)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda a, i, j: (a, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), lambda a, i, j: (a, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), lambda a, i, j: (a, j, 0),
                     memory_space=pltpu.VMEM),
    ]
    operands = [qf, kf, vf]
    if key_bias is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda a, i, j, h=h: (a // h, 0, j),
                         memory_space=pltpu.VMEM))
        operands.append(key_bias)
    out_shape = _vma_struct((bh, q_len, d), q.dtype, q)
    out_specs = pl.BlockSpec((1, bq, d), lambda a, i, j: (a, i, 0),
                             memory_space=pltpu.VMEM)
    if return_lse:
        # ride as [bh, 1, q_len]: the (1, bq) trailing block dims satisfy
        # the TPU (8, 128) tiling rules via a unit sublane
        out_shape = (out_shape,
                     _vma_struct((bh, 1, q_len), jnp.float32, q))
        out_specs = (out_specs,
                     pl.BlockSpec((1, 1, bq), lambda a, i, j: (a, 0, i),
                                  memory_space=pltpu.VMEM))
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        # batch·head and query blocks are independent; only the kv axis
        # carries the streaming-softmax accumulator — telling Mosaic lets it
        # overlap DMA and compute across the parallel axes
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    if return_lse:
        o, lse = out
        return o.reshape(b, h, q_len, d), lse.reshape(b, h, q_len)
    return out.reshape(b, h, q_len, d)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                         gl_ref, dq_ref, dq_acc, *, scale: float,
                         causal: bool, bq: int, bk: int):
    """dq = Σ_k ds @ K with ds = p * (dO V^T − D + glse), where glse is the
    cotangent of the lse output (zero when only the attention output is
    used). q arrives pre-scaled by scale*log2e so p = exp2(qk − lse·log2e)
    with no per-element multiplies; the deferred ds·scale lands on the
    [bq, d] result at finalize. Grid (bh, n_q, n_kv); accumulates over the
    innermost kv axis."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = (qi + 1) * bq > ki * bk

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk], exp2 domain
        if causal:
            rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp2(s - lse_ref[0, 0][:, None] * _LOG2E)  # [bq, bk]
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        ds = p * (dp - dd_ref[0, 0][:, None]
                  + gl_ref[0, 0][:, None])  # scale deferred to finalize
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                          gl_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          scale: float, causal: bool, bq: int, bk: int):
    """dv = Σ_q p^T dO; dk = Σ_q ds^T q. Grid (bh, n_kv, n_q); accumulates
    over the innermost query axis."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (qi + 1) * bq > ki * bk

    @pl.when(run)
    def _step():
        q = q_ref[0]  # pre-scaled by scale*log2e
        k = k_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk], exp2 domain
        if causal:
            rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp2(s - lse_ref[0, 0][:, None] * _LOG2E)  # [bq, bk]
        pt = p.astype(do.dtype)
        dv_acc[:] += jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, d]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        # ds against the PRE-SCALED q accumulates scale*log2e·(true dk); one
        # ln2 multiply on the [bk, d] result at finalize undoes the log2e
        # (the caller's q carried the scale, so dk keeps the bare `scale`)
        ds = (p * (dp - dd_ref[0, 0][:, None]
                   + gl_ref[0, 0][:, None])).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, d]

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * _LN2).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                            gl_ref, dq_ref, dk_ref, dv_ref, dq_acc, *,
                            scale: float, causal: bool, bq: int, bk: int,
                            kv_len: int):
    """Single-pass flash backward: K and V ride fully VMEM-resident per
    batch·head; dk/dv accumulate in the f32 output refs across the q sweep
    (their block index is constant within a batch·head, so Mosaic keeps the
    window in VMEM — the standard matmul-accumulator pattern); dq finishes
    within one program via an inner KV loop. Each probability tile is
    computed ONCE (the two-pass design recomputes s and dp in both grids:
    7 matmul passes vs 5 here) and q/k/v/do stream from HBM once instead of
    twice. Causal trip count is bounded per q block, preserving the
    skip-masked-blocks saving."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    n_q = pl.num_programs(1)

    @pl.when(qi == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    dq_acc[:] = jnp.zeros_like(dq_acc)
    q = q_ref[0]  # [bq, d], pre-scaled by scale*log2e
    do = do_ref[0]  # [bq, d]
    lse2 = lse_ref[0, 0][:, None] * _LOG2E  # exp2 domain
    dd = dd_ref[0, 0][:, None]
    gl = gl_ref[0, 0][:, None]
    n_kv = kv_len // bk
    if causal:
        # kv blocks strictly above the diagonal contribute nothing
        j_hi = jnp.minimum(((qi + 1) * bq + bk - 1) // bk, n_kv)
    else:
        j_hi = n_kv

    def body(j, _):
        kc = k_ref[0, pl.ds(j * bk, bk), :]  # [bk, d]
        vc = v_ref[0, pl.ds(j * bk, bk), :]
        s = jax.lax.dot_general(
            q, kc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk], exp2 domain
        if causal:
            rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp2(s - lse2)  # [bq, bk]
        dp = jax.lax.dot_general(
            do, vc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        ds = p * (dp - dd + gl)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(kc.dtype), kc, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        pt = p.astype(do.dtype)
        dv_ref[0, pl.ds(j * bk, bk), :] += jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, d]
        dsc = ds.astype(q.dtype)
        # against the PRE-SCALED q: carries scale*log2e·(true dk); one ln2
        # multiply at the very end restores bare `scale` (see two-pass note)
        dk_ref[0, pl.ds(j * bk, bk), :] += jax.lax.dot_general(
            dsc, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return 0

    lax.fori_loop(0, j_hi, body, 0, unroll=False)
    dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    @pl.when(qi == n_q - 1)
    def _scale_dk():
        dk_ref[...] = dk_ref[...] * _LN2


# VMEM budget for the fused single-pass backward: K/V (storage dtype) +
# dk/dv f32 accumulators resident per batch·head = 2*itemsize + 8 bytes per
# kv·d element; capping the residents at ~6.6MB leaves room for q/do/dq
# tiles and the [bq, bk] f32 loop temporaries inside 16MB. Above it (e.g.
# s=8192 d=128 bf16, or s=4096 d=128 f32) the two-pass design takes over.
_FUSED_BWD_MAX_RESIDENT_BYTES = 6_600_000


def _fused_bwd_applicable(q_len: int, kv_len: int, d: int,
                          q_block: int, itemsize: int = 2) -> bool:
    bq = _largest_divisor_leq(q_len, q_block)
    resident = kv_len * d * (2 * itemsize + 8)
    return (resident <= _FUSED_BWD_MAX_RESIDENT_BYTES
            and (bq % 128 == 0 or bq == q_len))


def _flash_bwd_inputs(q, k, v, o, lse, g, scale, glse):
    """Shared backward-input preamble (fused AND two-pass kernels — they
    must stay interchangeable under the same entry point): q pre-scaled by
    scale*log2e, [bh, ...] reshapes, the D_i = Σ dO·O row reduction, and
    the lse-cotangent row (zero when only the attention output is used)."""
    b, h, q_len, d = q.shape
    kv_len = k.shape[-2]
    bh = b * h
    qf = (q * (scale * _LOG2E)).astype(q.dtype).reshape(bh, q_len, d)
    kf = k.reshape(bh, kv_len, d)
    vf = v.reshape(bh, kv_len, d)
    dof = g.reshape(bh, q_len, d).astype(q.dtype)
    dd = jnp.sum(g.reshape(bh, q_len, d).astype(jnp.float32)
                 * o.reshape(bh, q_len, d).astype(jnp.float32),
                 axis=-1).reshape(bh, 1, q_len)
    lse = lse.reshape(bh, 1, q_len)
    gl = (jnp.zeros((bh, 1, q_len), jnp.float32) if glse is None
          else glse.astype(jnp.float32).reshape(bh, 1, q_len))
    return qf, kf, vf, dof, dd, lse, gl


def _flash_bwd_fused(q, k, v, o, lse, g, scale: float, causal: bool,
                     q_block: int, kv_block: int, glse=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, q_len, d = q.shape
    kv_len = k.shape[-2]
    bq = _largest_divisor_leq(q_len, q_block)
    # inner KV block capped at 512: the loop body holds ~6 live [bq, bk] f32
    # temporaries (s, p, dp, ds, causal iotas); 512x512x4B each keeps them
    # inside the VMEM left over by the resident K/V + dk/dv accumulators
    bk = _largest_divisor_leq(kv_len, min(kv_block, 512))
    bh = b * h
    qf, kf, vf, dof, dd, lse, gl = _flash_bwd_inputs(q, k, v, o, lse, g,
                                                     scale, glse)

    q_spec = pl.BlockSpec((1, bq, d), lambda a, i: (a, i, 0),
                          memory_space=pltpu.VMEM)
    kv_full = pl.BlockSpec((1, kv_len, d), lambda a, i: (a, 0, 0),
                           memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, bq), lambda a, i: (a, 0, i),
                            memory_space=pltpu.VMEM)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, scale=scale,
                          causal=causal, bq=bq, bk=bk, kv_len=kv_len),
        out_shape=(_vma_struct((bh, q_len, d), q.dtype, q),
                   _vma_struct((bh, kv_len, d), jnp.float32, k),
                   _vma_struct((bh, kv_len, d), jnp.float32, v)),
        grid=(bh, q_len // bq),
        in_specs=[q_spec, kv_full, kv_full, q_spec, row_spec, row_spec,
                  row_spec],
        out_specs=(q_spec, kv_full, kv_full),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qf, kf, vf, dof, lse, dd, gl)
    return (dq.reshape(b, h, q_len, d),
            dk.astype(k.dtype).reshape(b, h, kv_len, d),
            dv.astype(v.dtype).reshape(b, h, kv_len, d))


@jax.named_scope("attn_flash")
def _flash_bwd_pallas(q, k, v, o, lse, g, scale: float, causal: bool,
                      q_block: int, kv_block: int, glse=None):
    """Full flash backward on TPU. Preferred path: the fused single-pass
    kernel (:func:`_flash_bwd_fused`) whenever K/V + accumulators fit VMEM;
    otherwise recomputes p from the saved logsumexp in two gridded passes
    (dq; dk+dv), all matmuls in the storage dtype with f32 accumulation."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if _fused_bwd_applicable(q.shape[-2], k.shape[-2], q.shape[-1], q_block,
                             q.dtype.itemsize):
        return _flash_bwd_fused(q, k, v, o, lse, g, scale, causal,
                                q_block, kv_block, glse=glse)

    b, h, q_len, d = q.shape
    kv_len = k.shape[-2]
    bq = _largest_divisor_leq(q_len, q_block)
    bk = _largest_divisor_leq(kv_len, kv_block)
    bh = b * h
    qf, kf, vf, dof, dd, lse, gl = _flash_bwd_inputs(q, k, v, o, lse, g,
                                                     scale, glse)

    q_spec = pl.BlockSpec((1, bq, d), lambda a, i, j: (a, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, bk, d), lambda a, i, j: (a, j, 0),
                           memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, bq), lambda a, i, j: (a, 0, i),
                            memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        out_shape=_vma_struct((bh, q_len, d), q.dtype, q),
        grid=(bh, q_len // bq, kv_len // bk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf, dof, lse, dd, gl)

    # second pass swaps the roles of the two block axes
    q_spec2 = pl.BlockSpec((1, bq, d), lambda a, i, j: (a, j, 0),
                           memory_space=pltpu.VMEM)
    kv_spec2 = pl.BlockSpec((1, bk, d), lambda a, i, j: (a, i, 0),
                            memory_space=pltpu.VMEM)
    row_spec2 = pl.BlockSpec((1, 1, bq), lambda a, i, j: (a, 0, j),
                             memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        out_shape=(_vma_struct((bh, kv_len, d), k.dtype, k),
                   _vma_struct((bh, kv_len, d), v.dtype, v)),
        grid=(bh, kv_len // bk, q_len // bq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                  row_spec2, row_spec2],
        out_specs=(kv_spec2, kv_spec2),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf, dof, lse, dd, gl)
    return (dq.reshape(b, h, q_len, d), dk.reshape(b, h, kv_len, d),
            dv.reshape(b, h, kv_len, d))


# ---------------------------------------------------------------------------
# Fused short-sequence attention (BERT-class shapes)
# ---------------------------------------------------------------------------
#
# At seq <= ~256 the whole [s, s] score matrix fits VMEM, so streaming
# softmax is pure overhead — but XLA's fused path still materializes the f32
# probability chain in HBM several times across fwd+bwd (measured 2.15 GB
# per BERT-base block at b128 s128; the step is HBM-bound). These kernels
# keep the probabilities entirely in VMEM: one program per (batch*head)
# computes exact softmax forward, and ONE backward program recomputes the
# probabilities and emits dq, dk, dv together. Optional per-key bias
# (padding mask) and in-kernel dropout (pltpu PRNG, identically re-seeded in
# the backward so the recomputed mask matches the forward's).


def _fused_short_fwd_kernel(*refs, has_bias: bool, rate: float,
                            causal: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = 0
    seed_ref = None
    if rate > 0.0:
        seed_ref = refs[i]; i += 1
    q_ref, k_ref, v_ref = refs[i:i + 3]; i += 3
    bias_ref = None
    if has_bias:
        bias_ref = refs[i]; i += 1
    o_ref = refs[i]

    # blocks are [G, s, d]: G (batch·head) pairs per program, batched dots
    # (amortizes per-program overhead — G=1 measured 2.8x slower than XLA)
    q = q_ref[...]
    s_ = jax.lax.dot_general(
        q, k_ref[...], (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)  # [G, s, s], exp2 domain
    if bias_ref is not None:
        # pre-broadcast [G, s, s] bf16, already in exp2 units (gridded
        # sub-3D broadcasts crash Mosaic's layout pass)
        s_ = s_ + bias_ref[...].astype(jnp.float32)
    if causal:
        # diagonal stays visible, so no row is ever fully masked and the
        # running max below stays finite
        row = jax.lax.broadcasted_iota(jnp.int32, s_.shape[1:], 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s_.shape[1:], 1)
        s_ = jnp.where((col > row)[None], _NEG_INF, s_)
    m = jnp.max(s_, axis=-1, keepdims=True)
    p = jnp.exp2(s_ - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    if rate > 0.0:
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        bits = pltpu.prng_random_bits(p.shape)
        thresh = min(int(rate * 4294967296.0), 4294967295)
        keep = bits.astype(jnp.uint32) >= jnp.uint32(thresh)
        p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
    o_ref[...] = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[...], (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _fused_short_bwd_kernel(*refs, scale2: float, has_bias: bool,
                            rate: float, causal: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = 0
    seed_ref = None
    if rate > 0.0:
        seed_ref = refs[i]; i += 1
    q_ref, k_ref, v_ref, do_ref = refs[i:i + 4]; i += 4
    bias_ref = None
    if has_bias:
        bias_ref = refs[i]; i += 1
    dq_ref, dk_ref, dv_ref = refs[i:i + 3]

    q = q_ref[...]
    k = k_ref[...]
    do = do_ref[...]
    s_ = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)  # [G, s, s]
    if bias_ref is not None:
        s_ = s_ + bias_ref[...].astype(jnp.float32)  # [G, s, s], exp2 units
    if causal:
        # masking the recomputed scores suffices for the whole backward:
        # p = 0 above the diagonal, so ds, dv and dk contributions vanish
        row = jax.lax.broadcasted_iota(jnp.int32, s_.shape[1:], 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s_.shape[1:], 1)
        s_ = jnp.where((col > row)[None], _NEG_INF, s_)
    m = jnp.max(s_, axis=-1, keepdims=True)
    p = jnp.exp2(s_ - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)  # pre-dropout probabilities
    if rate > 0.0:
        # identical seeding to the forward → identical mask
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        bits = pltpu.prng_random_bits(p.shape)
        thresh = min(int(rate * 4294967296.0), 4294967295)
        keep = bits.astype(jnp.uint32) >= jnp.uint32(thresh)
        inv = 1.0 / (1.0 - rate)
        pd = jnp.where(keep, p * inv, 0.0)  # dropped probs (fwd's p)
    else:
        pd = p
    dv_ref[...] = jax.lax.dot_general(
        pd.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dpd = jax.lax.dot_general(
        do, v_ref[...], (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)  # [G, s, s]
    if rate > 0.0:
        dp = jnp.where(keep, dpd * inv, 0.0)
    else:
        dp = dpd
    # softmax vjp on the NATURAL-domain probabilities (ds carries no ln2:
    # the exp2 fold is compensated in the dq/dk output scales below)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    ds_c = ds.astype(q.dtype)
    # q is pre-scaled by scale·log2e: dq_true = scale·(ds @ k);
    # dk_true = ds^T @ (q·scale·log2e) · ln2/(scale·log2e)·scale = ln2·(ds^T @ q)
    dq_ref[...] = (jax.lax.dot_general(
        ds_c, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale2).astype(dq_ref.dtype)
    dk_ref[...] = (jax.lax.dot_general(
        ds_c, q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * _LN2).astype(dk_ref.dtype)


# the scope is the innermost name around the Mosaic call, on one chip and
# inside dispatch.per_shard's body alike: XLA names the call after it
# (jvp_attn_short_ / transpose_jvp_attn_short__ under a grad on one chip,
# attn_short per shard), so no scope may enclose this one inside a grad
@jax.named_scope("attn_short")
def _fused_short_call(q, k, v, key_bias, scale, rate, seed, causal=False,
                      fwd=True, do=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    bh = b * h
    # G (batch·head) pairs per program: biggest divisor of bh whose [G, s, s]
    # f32 score block keeps the backward's ~7 live copies (s_, p, pd, dpd,
    # dp, ds, mask) plus double-buffered DMAs inside the 16MB VMEM; G=64
    # also fails a Mosaic batched-dot layout check
    G = _largest_divisor_leq(bh, max(1, min(16, (1 << 20) // (s * s * 4))))
    qf = (q * (scale * _LOG2E)).astype(q.dtype).reshape(bh, s, d)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)
    tile = pl.BlockSpec((G, s, d), lambda a: (a, 0, 0),
                        memory_space=pltpu.VMEM)
    in_specs = []
    operands = []
    if rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(seed, jnp.int32).reshape(1))
    in_specs += [tile, tile, tile]
    operands += [qf, kf, vf]
    if do is not None:
        in_specs.append(tile)
        operands.append(do.reshape(bh, s, d).astype(q.dtype))
    has_bias = key_bias is not None
    if has_bias:
        # the bias ships PRE-BROADCAST [bh, s, s] in bf16 and pre-scaled to
        # exp2 units: in-grid sub-3D broadcasts crash Mosaic's layout pass,
        # and a bf16 mask read per program is still ~95% less traffic than
        # the XLA path's f32 probability chain
        kb = (key_bias.astype(jnp.float32) * _LOG2E).astype(jnp.bfloat16)
        kb_full = jnp.broadcast_to(
            jnp.repeat(kb.reshape(b, 1, s), h, axis=0).reshape(bh, 1, s),
            (bh, s, s))
        in_specs.append(pl.BlockSpec((G, s, s), lambda a: (a, 0, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(kb_full)
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel",))
    if fwd:
        out = pl.pallas_call(
            functools.partial(_fused_short_fwd_kernel,
                              has_bias=has_bias, rate=rate, causal=causal),
            out_shape=_vma_struct((bh, s, d), q.dtype, q),
            grid=(bh // G,), in_specs=in_specs, out_specs=tile,
            compiler_params=compiler_params)(*operands)
        return out.reshape(b, h, s, d)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_fused_short_bwd_kernel, scale2=scale,
                          has_bias=has_bias, rate=rate, causal=causal),
        out_shape=(_vma_struct((bh, s, d), q.dtype, q),
                   _vma_struct((bh, s, d), k.dtype, k),
                   _vma_struct((bh, s, d), v.dtype, v)),
        grid=(bh // G,), in_specs=in_specs, out_specs=(tile, tile, tile),
        compiler_params=compiler_params)(*operands)
    return (dq.reshape(b, h, s, d), dk.reshape(b, h, s, d),
            dv.reshape(b, h, s, d))


# operand layouts for dispatch.per_shard: [batch, heads, seq, head_dim]
# tensors, [batch, heads, seq] logsumexp rows, [batch, kv] key bias
_BHSD = (dispatch.BATCH, dispatch.HEADS, None, None)
_BHS = (dispatch.BATCH, dispatch.HEADS, None)
_BK = (dispatch.BATCH, None)


def _kernel_ok(kernel: str, q) -> bool:
    """On the TPU, and (under a several-device mesh) batch and heads
    divide over it so the kernel can run per shard; a failed shard rule is
    logged, since the reference then runs on the TPU."""
    if not dispatch.on_tpu():
        return False
    rule = dispatch.shard_rule(q.shape[0], q.shape[1])
    if rule is not None:
        dispatch.note_fallback(kernel, rule)
    return rule is None


def _fused_short_sharded(q, k, v, key_bias, seed, scale, rate, causal,
                         do=None):
    """``_fused_short_call`` per shard (forward, or backward when ``do`` is
    given). Each shard numbers its grid programs from 0, so the dropout
    seed is moved on by the shard's index times its program count: no two
    programs of one step draw the same mask."""
    def call(seed_, q_, k_, v_, *rest):
        rest = list(rest)
        kb_ = rest.pop(0) if key_bias is not None else None
        do_ = rest.pop(0) if do is not None else None
        if rate > 0.0:
            seed_ = seed_ + dispatch.shard_index() * (
                q_.shape[0] * q_.shape[1])
        return _fused_short_call(q_, k_, v_, kb_, scale, rate, seed_,
                                 causal=causal, fwd=do is None, do=do_)

    args, dims = [seed, q, k, v], [(), _BHSD, _BHSD, _BHSD]
    if key_bias is not None:
        args.append(key_bias)
        dims.append(_BK)
    if do is not None:
        args.append(do)
        dims.append(_BHSD)
    return dispatch.per_shard(call, args, dims,
                              _BHSD if do is None else (_BHSD,) * 3)


# seed rides as a (traced) int32 array argument — it cannot be a
# nondiff_argnum (those must be static) — and gets a None cotangent
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused_short(q, k, v, key_bias, seed, scale, rate, causal):
    return _fused_short_sharded(q, k, v, key_bias, seed, scale, rate,
                                causal)


def _fused_short_fwd(q, k, v, key_bias, seed, scale, rate, causal):
    out = _fused_short_sharded(q, k, v, key_bias, seed, scale, rate, causal)
    return out, (q, k, v, key_bias, seed)


def _fused_short_bwd(scale, rate, causal, residuals, g):
    q, k, v, key_bias, seed = residuals
    dq, dk, dv = _fused_short_sharded(q, k, v, key_bias, seed, scale, rate,
                                      causal, do=g)
    dbias = None if key_bias is None else jnp.zeros_like(key_bias)
    return dq, dk, dv, dbias, None


_fused_short.defvjp(_fused_short_fwd, _fused_short_bwd)

# VMEM budget for the fused kernel's [s, s] f32 score block (plus q/k/v/do
# tiles); 512x512 f32 = 1 MB — comfortably resident
FUSED_SHORT_MAX_SEQ = 512


def fused_short_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          key_bias: Optional[jax.Array] = None,
                          scale: Optional[float] = None,
                          dropout_rate: float = 0.0,
                          dropout_rng: Optional[jax.Array] = None,
                          causal: bool = False) -> jax.Array:
    """Exact (non-streaming) fused attention for short sequences:
    probabilities never leave VMEM in either direction, and the backward is
    a single kernel emitting dq/dk/dv. ``key_bias``: optional
    ``[batch, kv_len]`` additive per-key bias (padding mask). ``causal``
    applies the in-kernel lower-triangular mask (the generative prefill
    path — the whole score block is already resident, so the mask is one
    VPU select, not a second kernel). Attention dropout runs in-kernel on
    the TPU PRNG, deterministically re-seeded in the backward pass. The
    bias is a PADDING MASK, not a trained quantity — its gradient is zero
    (same contract as the flash key-bias path); use the XLA paths for
    trainable biases."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    seed = jnp.zeros((), jnp.int32)
    rate = 0.0
    if dropout_rate > 0.0 and dropout_rng is not None:
        rate = float(dropout_rate)  # zoolint: disable=jit-host-sync — static Python hyperparameter, not a tracer
        seed = jax.random.randint(dropout_rng, (), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)
    return _fused_short(q, k, v, key_bias, seed, scale, rate, causal)


def fused_short_applicable(q, k) -> bool:
    """Self-attention over at most ``FUSED_SHORT_MAX_SEQ`` positions, on
    the TPU (``q``, ``k``: ``[batch, heads, seq, head_dim]``)."""
    return (q.shape[-2] == k.shape[-2]
            and k.shape[-2] <= FUSED_SHORT_MAX_SEQ
            and _kernel_ok("fused_short_attention", q))


def _flash_fwd_sharded(q, k, v, scale, causal, q_block, kv_block,
                       key_bias=None, return_lse=False):
    """``_flash_fwd_pallas`` per shard of the mesh in scope."""
    def call(q_, k_, v_, *bias):
        return _flash_fwd_pallas(q_, k_, v_, scale, causal, q_block,
                                 kv_block, key_bias=bias[0] if bias else None,
                                 return_lse=return_lse)
    with_bias = key_bias is not None
    return dispatch.per_shard(
        call, (q, k, v) + ((key_bias,) if with_bias else ()),
        (_BHSD,) * 3 + ((_BK,) if with_bias else ()),
        (_BHSD, _BHS) if return_lse else _BHSD)


def _flash_bwd_sharded(q, k, v, o, lse, g, scale, causal, q_block, kv_block,
                       glse=None):
    """``_flash_bwd_pallas`` per shard of the mesh in scope."""
    def call(q_, k_, v_, o_, lse_, g_, *glse_):
        return _flash_bwd_pallas(q_, k_, v_, o_, lse_, g_, scale, causal,
                                 q_block, kv_block,
                                 glse=glse_[0] if glse_ else None)
    with_glse = glse is not None
    return dispatch.per_shard(
        call, (q, k, v, o, lse, g) + ((glse,) if with_glse else ()),
        (_BHSD,) * 4 + (_BHS, _BHSD) + ((_BHS,) if with_glse else ()),
        (_BHSD,) * 3)


def _flash_primal(q, k, v, scale, causal, q_block, kv_block):
    if _kernel_ok("flash_attention", q):
        return _flash_fwd_sharded(q, k, v, scale, causal, q_block, kv_block)
    return blockwise_attention(q, k, v, None, causal, scale, q_block, kv_block)


_flash = jax.custom_vjp(_flash_primal, nondiff_argnums=(3, 4, 5, 6))


def _lse_tile_ok(q_len: int, q_block: int) -> bool:
    """The lse/D row tiles are (1, 1, bq): legal only when bq is a multiple
    of 128 or spans the whole row (same lane-tiling rule _keybias_block
    enforces for the bias tile)."""
    bq = _largest_divisor_leq(q_len, q_block)
    return bq == q_len or bq % 128 == 0


def _lse_tile_rule(q_len: int, q_block: int) -> str:
    return (f"q tile {_largest_divisor_leq(q_len, q_block)} of q_len "
            f"{q_len} is neither a multiple of 128 nor the whole row, so "
            f"the logsumexp residual has no legal (1, 1, bq) tile")


def _flash_fwd(q, k, v, scale, causal, q_block, kv_block):
    kernel = _kernel_ok("flash_attention", q)
    if kernel and _lse_tile_ok(q.shape[-2], q_block):
        out, lse = _flash_fwd_sharded(q, k, v, scale, causal, q_block,
                                      kv_block, return_lse=True)
        return out, (q, k, v, out, lse)
    if kernel:
        # stated rule: the forward kernel still runs, the backward
        # recomputes through blockwise_attention
        dispatch.note_fallback(
            "flash_attention backward",
            _lse_tile_rule(q.shape[-2], q_block)
            + "; backward differentiates blockwise_attention")
    return _flash_primal(q, k, v, scale, causal, q_block, kv_block), (
        q, k, v, None, None)


def _flash_bwd(scale, causal, q_block, kv_block, residuals, g):
    q, k, v, o, lse = residuals
    if lse is not None:
        return _flash_bwd_sharded(q, k, v, o, lse, g, scale, causal,
                                  q_block, kv_block)
    # off-TPU, or no legal lse tile: recompute through the blockwise path
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, None, causal, scale, q_block, kv_block), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, scale, causal, q_block, kv_block):
    return _flash_lse_fwd(q, k, v, scale, causal, q_block, kv_block)[0]


def _flash_lse_fwd(q, k, v, scale, causal, q_block, kv_block):
    q_len = q.shape[-2]
    kernel = _kernel_ok("flash_attention_lse", q)
    if kernel and _lse_tile_ok(q_len, q_block):
        out, lse = _flash_fwd_sharded(q, k, v, scale, causal, q_block,
                                      kv_block, return_lse=True)
        return (out, lse), (q, k, v, out, lse, True)
    if kernel:
        dispatch.note_fallback(
            "flash_attention_lse",
            _lse_tile_rule(q_len, q_block)
            + "; forward and backward run blockwise_attention")
    out, lse = blockwise_attention(q, k, v, None, causal, scale, q_block,
                                   kv_block, return_lse=True)
    # the fallback backward recomputes via vjp: only q/k/v are needed, so
    # don't pin the forward activations in the residuals
    return (out, lse), (q, k, v, None, None, False)


def _flash_lse_bwd(scale, causal, q_block, kv_block, residuals, gs):
    q, k, v, o, lse, used_pallas = residuals
    go, glse = gs
    if used_pallas:
        return _flash_bwd_sharded(q, k, v, o, lse, go, scale, causal,
                                  q_block, kv_block, glse=glse)
    # off-TPU: autodiff through the blockwise lse path
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, None, causal, scale, q_block, kv_block,
            return_lse=True), q, k, v)
    return vjp((go, glse))


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        q_block: int = DEFAULT_Q_BLOCK,
                        kv_block: int = DEFAULT_KV_BLOCK):
    """Fused attention that ALSO returns the per-row logsumexp
    ``[batch, heads, q_len]`` — the sufficient statistic for merging partial
    attentions over disjoint KV shards (ring hops):

        lse_c = logaddexp(lse_a, lse_b)
        out_c = out_a * exp(lse_a - lse_c) + out_b * exp(lse_b - lse_c)

    Jointly differentiable in both outputs: on TPU the lse cotangent folds
    into the backward kernels' ``ds`` term, off-TPU autodiff flows through
    the blockwise scan."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _flash_lse(q, k, v, scale, causal, q_block, kv_block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_keybias(q, k, v, key_bias, scale, causal, q_block, kv_block):
    if _kernel_ok("flash_attention key-bias", q):
        return _flash_fwd_sharded(q, k, v, scale, causal, q_block, kv_block,
                                  key_bias=key_bias)
    return blockwise_attention(q, k, v, key_bias[:, None, None, :], causal,
                               scale, q_block, kv_block)


def _flash_keybias_fwd(q, k, v, key_bias, scale, causal, q_block, kv_block):
    if dispatch.on_tpu():
        # stated rule: there is no backward kernel for the key-bias form
        dispatch.note_fallback(
            "flash_attention key-bias backward",
            "the backward kernels take no bias operand; backward "
            "differentiates blockwise_attention")
    return (_flash_keybias(q, k, v, key_bias, scale, causal, q_block,
                           kv_block), (q, k, v, key_bias))


def _flash_keybias_bwd(scale, causal, q_block, kv_block, residuals, g):
    q, k, v, key_bias = residuals
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, key_bias[:, None, None, :], causal, scale,
            q_block, kv_block), q, k, v)
    dq, dk, dv = vjp(g)
    # the bias is a padding mask, not a trained quantity
    return dq, dk, dv, jnp.zeros_like(key_bias)


_flash_keybias.defvjp(_flash_keybias_fwd, _flash_keybias_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    bias: Optional[jax.Array] = None,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    q_block: int = DEFAULT_Q_BLOCK,
                    kv_block: int = DEFAULT_KV_BLOCK) -> jax.Array:
    """Fused attention: pallas kernel on TPU, blockwise XLA elsewhere.

    A per-key padding bias in the UNAMBIGUOUS ``[b, 1, 1, kv]`` form (what
    the mask layers build) runs inside the pallas kernel; any other bias
    shape (including 2-D, which has always meant a broadcast ``[q, kv]``
    matrix) falls back to the blockwise path.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if bias is not None:
        kv_len = k.shape[-2]
        if bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1 \
                and bias.shape[0] == q.shape[0] and bias.shape[3] == kv_len \
                and _keybias_block(kv_len, kv_block) is not None:
            return _flash_keybias(q, k, v, bias[:, 0, 0, :], scale, causal,
                                  q_block, kv_block)
        if dispatch.on_tpu():
            dispatch.note_fallback(
                "flash_attention",
                f"bias of shape {tuple(bias.shape)} is not a [b, 1, 1, kv] "
                f"key bias with a legal kv tile; blockwise_attention runs")
        return blockwise_attention(q, k, v, bias, causal, scale,
                                   q_block, kv_block)
    return _flash(q, k, v, scale, causal, q_block, kv_block)
