"""Attention / Transformer / BERT Keras-style layers.

Capability parity with the reference's ``TransformerLayer.scala:1`` (GPT-style
self-attention stack over [tokens, positions]) and ``BERT.scala:66`` (inputs
[token ids, token type ids, position ids, attention mask]; outputs block
states + pooled first-token output). The compute path is TPU-native: heads
are one batched ``[b, h, s, d]`` tensor driving the fused attention kernels
in ``ops/attention.py`` (pallas flash kernel on TPU), bf16-friendly, no
per-head Python loops.

Device operations carry ``jax.named_scope`` names from the vocabulary in
docs/observability.md: ``embeddings``, and per block ``attention``,
``layer_norm``, ``ffn``. The kernel (or its reference) is NOT nested under
``attention``: ``ops/attention.py`` names it ``attn_short`` / ``attn_flash``
/ ``attn_reference`` beside it, because XLA names a Mosaic call after the
innermost scope and JAX wraps ``jvp(...)`` / ``transpose(...)`` around the
outermost scope inside a ``grad`` — with nothing around the kernel's scope
the backward call is still told from the forward one by its name.
"""
from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..engine import Layer
from ...ops.attention import dot_product_attention, flash_attention


def _dense_params(rng, d_in, d_out, init_range):
    wkey, _ = jax.random.split(rng)
    return {"kernel": jax.random.normal(wkey, (d_in, d_out)) * init_range,
            "bias": jnp.zeros((d_out,))}


def _dense(p, x):
    return x @ p["kernel"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _layer_norm_params(dim):
    return {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))}


def _layer_norm(p, x, eps=1e-5):
    x32 = x.astype(jnp.float32)  # stable moments in bf16 pipelines
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def _dropout(x, rate, rng, training):
    if not training or rate <= 0.0 or rng is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


class MultiHeadAttention(Layer):
    """Batched multi-head self/cross attention.

    ``call`` input: one tensor [b, s, hidden] (self-attention) or a list
    [query, key_value]. ``mask``: [b, kv_len] 1/0 valid mask folded into an
    additive bias.
    """

    def __init__(self, n_head: int, hidden_size: Optional[int] = None,
                 attn_drop: float = 0.0, output_drop: float = 0.0,
                 causal: bool = False, init_range: float = 0.02,
                 use_flash: bool = True, name: Optional[str] = None):
        super().__init__(name)
        self.n_head = n_head
        self.hidden_size = hidden_size
        self.attn_drop = attn_drop
        self.output_drop = output_drop
        self.causal = causal
        self.init_range = init_range
        self.use_flash = use_flash

    def build(self, rng, input_shape):
        shape = input_shape[0] if isinstance(input_shape, list) else input_shape
        hidden = self.hidden_size or shape[-1]
        if hidden % self.n_head:
            raise ValueError(f"hidden {hidden} % n_head {self.n_head} != 0")
        self.hidden_size = hidden
        keys = jax.random.split(rng, 4)
        params = {
            "q": _dense_params(keys[0], shape[-1], hidden, self.init_range),
            "k": _dense_params(keys[1], shape[-1], hidden, self.init_range),
            "v": _dense_params(keys[2], shape[-1], hidden, self.init_range),
            "o": _dense_params(keys[3], hidden, hidden, self.init_range),
        }
        return params, {}

    def compute_output_shape(self, input_shape):
        shape = input_shape[0] if isinstance(input_shape, list) else input_shape
        return tuple(shape[:-1]) + (self.hidden_size or shape[-1],)

    def attend(self, params, x_q, x_kv, mask=None, *, training=False,
               rng=None):
        b, sq, _ = x_q.shape
        h, dh = self.n_head, self.hidden_size // self.n_head
        with jax.named_scope("attention"):
            q = _dense(params["q"], x_q).reshape(
                b, sq, h, dh).transpose(0, 2, 1, 3)
            k = _dense(params["k"], x_kv).reshape(
                b, x_kv.shape[1], h, dh).transpose(0, 2, 1, 3)
            v = _dense(params["v"], x_kv).reshape(
                b, x_kv.shape[1], h, dh).transpose(0, 2, 1, 3)
            bias = None
            if mask is not None:
                bias = ((1.0 - mask[:, None, None, :].astype(jnp.float32))
                        * -1e9).astype(x_q.dtype)
        drop_rng = None
        if training and self.attn_drop > 0.0 and rng is not None:
            rng, drop_rng = jax.random.split(rng)
        from ...ops.attention import (
            FUSED_SHORT_MAX_SEQ, fused_short_applicable,
            fused_short_attention)
        if self.use_flash and fused_short_applicable(q, k):
            # short sequences on TPU: single-kernel exact attention — the
            # probability matrix never touches HBM in either direction, and
            # attention dropout runs on the in-kernel PRNG (the BERT-base
            # step is HBM-bound; this path cuts its biggest traffic source)
            key_bias = None if mask is None else bias[:, 0, 0, :]
            ctx = fused_short_attention(
                q, k, v, key_bias=key_bias,
                dropout_rate=self.attn_drop if drop_rng is not None else 0.0,
                dropout_rng=drop_rng, causal=self.causal)
        elif drop_rng is not None:
            # short sequences: the materialized prob matrix is small and the
            # fused-softmax path wins; long ones: streaming + per-block
            # dropout (measured cutover ~512 on v5e)
            if self.use_flash and k.shape[-2] > FUSED_SHORT_MAX_SEQ:
                # streaming attention with per-block dropout: never
                # materializes the [q, kv] probability matrix (equals
                # post-softmax dropout exactly — see blockwise_attention)
                from ...ops import dispatch
                from ...ops.attention import blockwise_attention
                if dispatch.on_tpu():
                    dispatch.note_fallback(
                        "flash_attention",
                        "the streaming kernels have no attention dropout; "
                        f"training at seq > {FUSED_SHORT_MAX_SEQ} with "
                        "attn_drop runs blockwise_attention")
                ctx = blockwise_attention(
                    q, k, v, bias=bias, causal=self.causal,
                    dropout_rate=self.attn_drop, dropout_rng=drop_rng)
            else:
                ctx = dot_product_attention(
                    q, k, v, bias=bias, causal=self.causal,
                    dropout_rate=self.attn_drop, dropout_rng=drop_rng)
        elif self.use_flash and k.shape[-2] > FUSED_SHORT_MAX_SEQ:
            # one shared cutover constant: at or below it the fused short
            # kernel (or, when inapplicable, XLA's fused softmax chain —
            # measured 0.9ms vs 1.5ms fwd+bwd per call at the BERT-base
            # shape) beats the streaming flash kernels
            ctx = flash_attention(q, k, v, bias=bias, causal=self.causal)
        else:
            ctx = dot_product_attention(q, k, v, bias=bias, causal=self.causal)
        with jax.named_scope("attention"):
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, sq, self.hidden_size)
            out = _dense(params["o"], ctx)
            return _dropout(out, self.output_drop, rng, training)

    def call(self, params, state, inputs, *, training=False, rng=None):
        """Inputs: one tensor (self-attention), [q, kv], or [q, kv, mask]."""
        mask = None
        if isinstance(inputs, (list, tuple)):
            x_q, x_kv = inputs[0], inputs[1]
            if len(inputs) > 2:
                mask = inputs[2]
        else:
            x_q = x_kv = inputs
        return self.attend(params, x_q, x_kv, mask, training=training,
                           rng=rng), state


class _TransformerBase(Layer):
    """Shared transformer encoder stack machinery."""

    def __init__(self, n_block: int, n_head: int, hidden_size: int,
                 intermediate_size: int, hidden_drop: float, attn_drop: float,
                 init_range: float, causal: bool, output_all_block: bool,
                 use_flash: bool = True, compute_dtype=None,
                 name: Optional[str] = None):
        super().__init__(name)
        # mixed precision: embeddings cast to this dtype so every block's
        # matmuls hit the MXU in bf16; layer norms still reduce in f32
        self.compute_dtype = compute_dtype
        self.n_block = n_block
        self.n_head = n_head
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.hidden_drop = hidden_drop
        self.attn_drop = attn_drop
        self.init_range = init_range
        self.causal = causal
        self.output_all_block = output_all_block
        self.use_flash = use_flash
        self.attn = MultiHeadAttention(
            n_head, hidden_size, attn_drop, hidden_drop, causal=causal,
            init_range=init_range, use_flash=use_flash,
            name=f"{self.name}_attn")

    def _block_params(self, rng):
        keys = jax.random.split(rng, 3)
        attn_p, _ = self.attn.build(
            keys[0], (None, None, self.hidden_size))
        return {
            "attn": attn_p,
            "ln1": _layer_norm_params(self.hidden_size),
            "ffn_in": _dense_params(keys[1], self.hidden_size,
                                    self.intermediate_size, self.init_range),
            "ffn_out": _dense_params(keys[2], self.intermediate_size,
                                     self.hidden_size, self.init_range),
            "ln2": _layer_norm_params(self.hidden_size),
        }

    def _run_block(self, p, x, mask, training, rng):
        r1, r2 = (jax.random.split(rng) if rng is not None else (None, None))
        a = self.attn.attend(p["attn"], x, x, mask, training=training, rng=r1)
        with jax.named_scope("layer_norm"):
            x = _layer_norm(p["ln1"], x + a)
        with jax.named_scope("ffn"):
            hmid = jax.nn.gelu(_dense(p["ffn_in"], x))
            h = _dropout(_dense(p["ffn_out"], hmid), self.hidden_drop, r2,
                         training)
        with jax.named_scope("layer_norm"):
            return _layer_norm(p["ln2"], x + h)

    def _pooler_params(self, rng):
        return _dense_params(rng, self.hidden_size, self.hidden_size,
                             self.init_range)

    def _pool(self, p, states):
        with jax.named_scope("classifier"):
            return jnp.tanh(_dense(p, states[:, 0]))

    def _stack_output_shape(self, seq):
        states = (None, seq, self.hidden_size)
        pooled = (None, self.hidden_size)
        if self.output_all_block:
            return [states] * self.n_block + [pooled]
        return [states, pooled]


class TransformerLayer(_TransformerBase):
    """GPT-style stack (reference ``TransformerLayer.scala``): inputs
    [token ids [b, s], position ids [b, s]]; outputs block state(s) + pooled.
    """

    def __init__(self, vocab: int, hidden_size: int = 768, n_block: int = 12,
                 n_head: int = 12, seq_len: int = 512,
                 intermediate_size: int = 0, hidden_p_drop: float = 0.1,
                 attn_p_drop: float = 0.1, initializer_range: float = 0.02,
                 bidirectional: bool = False, output_all_block: bool = True,
                 use_flash: bool = True, compute_dtype=None,
                 name: Optional[str] = None):
        super().__init__(n_block, n_head, hidden_size, intermediate_size,
                         hidden_p_drop, attn_p_drop, initializer_range,
                         causal=not bidirectional,
                         output_all_block=output_all_block,
                         use_flash=use_flash, compute_dtype=compute_dtype,
                         name=name)
        self.vocab = vocab
        self.seq_len = seq_len

    def build(self, rng, input_shape):
        keys = jax.random.split(rng, self.n_block + 3)
        params = {
            "word_emb": jax.random.normal(
                keys[0], (self.vocab, self.hidden_size)) * self.init_range,
            "pos_emb": jax.random.normal(
                keys[1], (self.seq_len, self.hidden_size)) * self.init_range,
            "pooler": self._pooler_params(keys[2]),
        }
        for i in range(self.n_block):
            params[f"block_{i}"] = self._block_params(keys[3 + i])
        return params, {}

    def compute_output_shape(self, input_shape):
        shape = input_shape[0] if isinstance(input_shape, list) else input_shape
        return self._stack_output_shape(shape[1])

    def call(self, params, state, inputs, *, training=False, rng=None):
        if isinstance(inputs, (list, tuple)):
            tokens, positions = inputs[0], inputs[1]
        else:
            tokens = inputs
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
        tokens = tokens.astype(jnp.int32)
        positions = positions.astype(jnp.int32)
        with jax.named_scope("embeddings"):
            x = params["word_emb"][tokens] + params["pos_emb"][positions]
            if self.compute_dtype is not None:
                x = x.astype(self.compute_dtype)
        all_states = []
        for i in range(self.n_block):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            x = self._run_block(params[f"block_{i}"], x, None, training, sub)
            all_states.append(x)
        pooled = self._pool(params["pooler"], x)
        outs = (all_states if self.output_all_block else [x]) + [pooled]
        return outs, state


class BERT(_TransformerBase):
    """BERT encoder (reference ``BERT.scala:66``): inputs [token ids,
    token type ids, position ids, attention mask]; outputs block state(s) +
    pooled first-token output."""

    def __init__(self, vocab: int = 40990, hidden_size: int = 768,
                 n_block: int = 12, n_head: int = 12,
                 max_position_len: int = 512, intermediate_size: int = 3072,
                 hidden_p_drop: float = 0.1, attn_p_drop: float = 0.1,
                 initializer_range: float = 0.02,
                 output_all_block: bool = True, use_flash: bool = True,
                 compute_dtype=None, name: Optional[str] = None):
        super().__init__(n_block, n_head, hidden_size, intermediate_size,
                         hidden_p_drop, attn_p_drop, initializer_range,
                         causal=False, output_all_block=output_all_block,
                         use_flash=use_flash, compute_dtype=compute_dtype,
                         name=name)
        self.vocab = vocab
        self.max_position_len = max_position_len

    def build(self, rng, input_shape):
        keys = jax.random.split(rng, self.n_block + 4)
        params = {
            "word_emb": jax.random.normal(
                keys[0], (self.vocab, self.hidden_size)) * self.init_range,
            "pos_emb": jax.random.normal(
                keys[1], (self.max_position_len,
                          self.hidden_size)) * self.init_range,
            "type_emb": jax.random.normal(
                keys[2], (2, self.hidden_size)) * self.init_range,
            "emb_ln": _layer_norm_params(self.hidden_size),
            "pooler": self._pooler_params(keys[3]),
        }
        for i in range(self.n_block):
            params[f"block_{i}"] = self._block_params(keys[4 + i])
        return params, {}

    def compute_output_shape(self, input_shape):
        shape = input_shape[0] if isinstance(input_shape, list) else input_shape
        return self._stack_output_shape(shape[1])

    def call(self, params, state, inputs, *, training=False, rng=None):
        if not isinstance(inputs, (list, tuple)) or len(inputs) < 4:
            raise ValueError("BERT expects [token_ids, token_type_ids, "
                             "position_ids, attention_mask]")
        tokens, types, positions, mask = inputs[:4]
        tokens = tokens.astype(jnp.int32)
        types = types.astype(jnp.int32)
        positions = positions.astype(jnp.int32)
        with jax.named_scope("embeddings"):
            x = (params["word_emb"][tokens] + params["pos_emb"][positions]
                 + params["type_emb"][types])
            if self.compute_dtype is not None:
                x = x.astype(self.compute_dtype)
            x = _layer_norm(params["emb_ln"], x)
            if rng is not None:
                rng, sub = jax.random.split(rng)
                x = _dropout(x, self.hidden_drop, sub, training)
        all_states = []
        for i in range(self.n_block):
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            x = self._run_block(params[f"block_{i}"], x, mask, training, sub)
            all_states.append(x)
        pooled = self._pool(params["pooler"], x)
        outs = (all_states if self.output_all_block else [x]) + [pooled]
        return outs, state
