"""A decoder whose block is data: :class:`DecoderSpec` says which norm
scale, positions, mixer, feed-forward, head and scalings a model has, layer
by layer, and :class:`LayeredDecoder` runs it behind
``serving.GenerativeServing`` (the contract that ``TransformerLM`` offers
the server: ``params``, ``max_len``, cache construction, a paged decode
step, a prefill; here ``paged_state_step`` and ``prefill_chunk``).

What it covers today is what MiniCPM-SALA needs (docs/models.md): RMS
norms, a gated SiLU feed-forward, an untied head, MiniCPM's embedding,
residual and logit scalings, bfloat16 parameters, and a mixer chosen by
layer:

- ``"lightning-attn"``: linear attention with a decay a head
  (``ops/linear_attention.py``), ``qk_norm``, rotary positions, an output
  norm and an output gate. Its cache is a float32 state ``[slots, H, D, D]``.
- ``"minicpm4"``: grouped-query block-sparse softmax attention
  (``ops/sparse_attention.py``), ``qk_norm``, no positions, an output gate.
  Its cache is a K/V page pool with a compressed-key pool beside it.

Both kinds of cache live in the one list the server holds and donates. A
model with a recurrent layer is prefilled in chunks
(:meth:`LayeredDecoder.prefill_chunk`): each chunk carries the states and
the pages on from where the last one left them, so the server can run
decode steps of the resident streams between two chunks of a joining
prompt. ``TransformerLM`` stays as it is for GPT-2-style models (ROADMAP
D6)."""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.decode import _page_positions, _paged_write
from ..ops.linear_attention import (lightning_slopes, linear_attention_chunk,
                                    linear_attention_step)
from ..ops.sparse_attention import (SparseSpec, attend_chunk, attend_step,
                                    compress_chunk, compress_step,
                                    init_sparse_pool, select_chunk,
                                    select_step)

LINEAR, SPARSE = "lightning-attn", "minicpm4"


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """The block as data. Widths are the model's own; ``mixers`` names the
    mixer of each layer in order."""
    vocab_size: int
    hidden: int
    intermediate: int
    mixers: Tuple[str, ...]
    heads: int
    kv_heads: int
    head_dim: int
    linear_heads: int
    linear_head_dim: int
    max_len: int
    sparse: SparseSpec = SparseSpec()
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    embed_scale: float = 1.0       # x_0 = embed_scale * E[token]
    residual_scale: float = 1.0    # x <- x + residual_scale * f(N(x))
    logit_divisor: float = 1.0     # logits = W_head (N(x) / logit_divisor)
    param_dtype: str = "bfloat16"
    init_std: float = 0.02

    def __post_init__(self):
        unknown = set(self.mixers) - {LINEAR, SPARSE}
        if unknown:
            raise ValueError(f"no mixer named {sorted(unknown)}; have "
                             f"{LINEAR!r} and {SPARSE!r}")
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads over "
                             f"{self.kv_heads} key/value heads")
        if self.max_len % self.sparse.block_size:
            raise ValueError(f"max_len {self.max_len} is no whole number of "
                             f"pages of {self.sparse.block_size}")

    @classmethod
    def from_config(cls, cfg: Dict[str, Any], max_len: int) -> "DecoderSpec":
        """From a ``minicpm_sala`` ``config.json`` (its keys as published;
        ``sparse_attention`` holds InfLLM-V2's sizes, which the published
        file leaves to the code)."""
        return cls(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            intermediate=cfg["intermediate_size"],
            mixers=tuple(cfg["mixer_types"]),
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            linear_heads=cfg["lightning_nh"],
            linear_head_dim=cfg["lightning_head_dim"], max_len=max_len,
            sparse=SparseSpec(**cfg.get("sparse_attention", {})),
            rms_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
            embed_scale=cfg["scale_emb"],
            residual_scale=cfg["scale_depth"] / math.sqrt(
                cfg.get("depth_scale_layers", cfg["num_hidden_layers"])),
            logit_divisor=cfg["hidden_size"] / cfg["dim_model_base"],
            param_dtype=cfg.get("param_dtype", "bfloat16"),
            init_std=cfg.get("initializer_range", 0.02))

    def layer_shapes(self, kind: str):
        d, f = self.hidden, self.intermediate
        if kind == LINEAR:
            h = kv = self.linear_heads * self.linear_head_dim
            hd = self.linear_head_dim
        else:
            h, kv, hd = (self.heads * self.head_dim,
                         self.kv_heads * self.head_dim, self.head_dim)
        mats = {"q": (d, h), "k": (d, kv), "v": (d, kv), "o": (h, d),
                "g": (d, h), "gate_proj": (d, f), "up_proj": (d, f),
                "down_proj": (f, d)}
        ones = {"norm1": (d,), "norm2": (d,), "q_norm": (hd,),
                "k_norm": (hd,)}
        if kind == LINEAR:
            ones["o_norm"] = (h,)
        return mats, ones


def _rms_norm(weight, x, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * weight


def _product(x, w):
    """``x @ w`` with both operands in the parameter's dtype and float32
    accumulation."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _rotary(x, positions, theta):
    """Rotate-half over the whole head: ``x [..., H, D]``, ``positions``
    ``[...]``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


class LayeredDecoder:
    """Runs a :class:`DecoderSpec` behind ``GenerativeServing``. Parameters
    are ``{"embed" [V, d], "head" [V, d], "norm_f" [d], "layers": [...]}``,
    a dict a layer (:meth:`DecoderSpec.layer_shapes`); they come from
    :meth:`init_params` or are handed over whole (:meth:`set_params`)."""

    #: the server prefills such a model in chunks, keeps a state a slot
    #: beside the pages, and refuses what would need a snapshot of the
    #: state (shared prefixes, speculative decoding)
    recurrent = True

    def __init__(self, spec: DecoderSpec, seed: int = 0,
                 prefill_chunk: int = 2048):
        self.spec = spec
        #: a prompt is fed in chunks of ``prefill_chunk`` positions, the
        #: last padded to one of these: a closed set of compiled programs
        self.chunk_buckets = (prefill_chunk // 4, prefill_chunk // 2,
                              prefill_chunk)
        if any(b % spec.sparse.block_size for b in self.chunk_buckets):
            raise ValueError(f"chunk buckets {self.chunk_buckets} must be "
                             f"whole pages of {spec.sparse.block_size}")
        self.vocab_size = spec.vocab_size
        self.max_len = spec.max_len
        self.n_head = spec.heads
        self.page_len = spec.sparse.block_size   # one page, one block
        self.prefill_chunk_len = prefill_chunk
        self._seed = seed
        self._params: Optional[Dict[str, Any]] = None

    # -- parameters -----------------------------------------------------------

    def init_params(self, seed: Optional[int] = None) -> Dict[str, Any]:
        spec = self.spec
        dtype = jnp.dtype(spec.param_dtype)
        root = jax.random.PRNGKey(self._seed if seed is None else seed)

        def draw(key, shape):
            return (jax.random.normal(key, shape) * spec.init_std).astype(
                dtype)
        layers = []
        for i, kind in enumerate(spec.mixers):
            mats, ones = spec.layer_shapes(kind)
            keys = jax.random.split(jax.random.fold_in(root, i), len(mats))
            layer = {n: draw(k, s) for k, (n, s) in
                     zip(keys, sorted(mats.items()))}
            layer.update({n: jnp.ones(s, jnp.float32)
                          for n, s in ones.items()})
            layers.append(layer)
        table = (spec.vocab_size, spec.hidden)
        return {"embed": draw(jax.random.fold_in(root, 1000), table),
                "head": draw(jax.random.fold_in(root, 1001), table),
                "norm_f": jnp.ones((spec.hidden,), jnp.float32),
                "layers": layers}

    def set_params(self, params: Dict[str, Any]) -> None:
        if len(params["layers"]) != len(self.spec.mixers):
            raise ValueError(f"{len(params['layers'])} layers of parameters "
                             f"for {len(self.spec.mixers)} mixers")
        self._params = params

    @property
    def params(self) -> Dict[str, Any]:
        if self._params is None:
            self._params = self.init_params()
        return self._params

    def fit(self, *args, **kwargs):
        raise NotImplementedError(
            "LayeredDecoder has no training path: a linear-attention layer "
            "needs the chunked scan's backward, which is not written "
            "(ROADMAP R3.5)")

    # -- caches ---------------------------------------------------------------

    def init_paged_caches(self, num_pages: int, page_len: int,
                          int8: bool = False, slots: int = 1) -> List[Dict]:
        """One cache a layer, in layer order: a sparse layer's K/V page
        pool with its compressed-key pool, a lightning layer's state
        ``[slots, H, D, D]`` in float32."""
        spec = self.spec
        if int8:
            raise NotImplementedError(
                "int8 pages are not wired into the sparse layers' pools "
                "(the selected-page read has no dequantising gather)")
        if page_len != spec.sparse.block_size:
            raise ValueError(f"kv_page_len must be the model's selection "
                             f"block, {spec.sparse.block_size}; got "
                             f"{page_len}")
        caches = []
        for kind in spec.mixers:
            if kind == SPARSE:
                caches.append(init_sparse_pool(
                    num_pages, spec.sparse, spec.kv_heads, spec.head_dim,
                    jnp.dtype(spec.param_dtype)))
            else:
                caches.append({"state": jnp.zeros(
                    (slots, spec.linear_heads, spec.linear_head_dim,
                     spec.linear_head_dim), jnp.float32)})
        return caches

    # -- the block ------------------------------------------------------------

    def _ffn(self, p, x):
        spec = self.spec
        with jax.named_scope("layer_norm"):
            h = _rms_norm(p["norm2"], x, spec.rms_eps)
        with jax.named_scope("ffn"):
            mid = jax.nn.silu(_product(h, p["gate_proj"])) \
                * _product(h, p["up_proj"])
            return x + spec.residual_scale * _product(mid, p["down_proj"])

    def _project(self, p, u, heads, kv_heads, head_dim, positions):
        """q (scaled), k, v of ``u [..., d]`` as ``[..., heads, head_dim]``;
        rotary where ``positions`` is given."""
        spec = self.spec
        lead = u.shape[:-1]
        q = _rms_norm(p["q_norm"], _product(u, p["q"]).reshape(
            lead + (heads, head_dim)), spec.rms_eps)
        k = _rms_norm(p["k_norm"], _product(u, p["k"]).reshape(
            lead + (kv_heads, head_dim)), spec.rms_eps)
        v = _product(u, p["v"]).reshape(lead + (kv_heads, head_dim))
        if positions is not None:
            q = _rotary(q, positions, spec.rope_theta)
            k = _rotary(k, positions, spec.rope_theta)
        return q / math.sqrt(head_dim), k, v

    def _mix_out(self, p, x, u, o):
        """``x + c W_o (o * sigmoid(W_g u))``, ``o [..., H*D]``."""
        gate = jax.nn.sigmoid(_product(u, p["g"]))
        return x + self.spec.residual_scale * _product(o * gate, p["o"])

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return self.spec.embed_scale * jnp.take(
                params["embed"], tokens, axis=0).astype(jnp.float32)

    # -- decode: one position of every slot -----------------------------------

    def paged_state_step(self, params, tokens, lengths, table, caches,
                         active=None):
        """One decode step over all slots: ``tokens [S]`` at positions
        ``lengths [S]``. Returns ``(logits [S, V], caches, read)``, ``read``
        the positions a sparse layer gathered for a stream in this step
        (the mean over the sparse layers, as the device counted them). Only
        an ``active`` slot's state moves."""
        spec = self.spec
        tokens = jnp.asarray(tokens, jnp.int32)
        if active is None:
            active = jnp.ones(tokens.shape, bool)
        x = self._embed(params, tokens)                         # [S, d]
        new_caches, reads = [], []
        for kind, p, cache in zip(spec.mixers, params["layers"], caches):
            with jax.named_scope("layer_norm"):
                u = _rms_norm(p["norm1"], x, spec.rms_eps)
            if kind == LINEAR:
                with jax.named_scope("attention"):
                    q, k, v = self._project(
                        p, u, spec.linear_heads, spec.linear_heads,
                        spec.linear_head_dim, lengths)
                o, state = linear_attention_step(
                    q, k, v, cache["state"], lightning_slopes(
                        spec.linear_heads), active)
                cache = {"state": state}
                with jax.named_scope("attention"):
                    o = _rms_norm(p["o_norm"], o.reshape(o.shape[0], -1),
                                  spec.rms_eps)
                    x = self._mix_out(p, x, u, o)
            else:
                with jax.named_scope("attention"):
                    q, k, v = self._project(p, u, spec.heads, spec.kv_heads,
                                            spec.head_dim, None)
                    s = q.shape[0]
                    q = q.reshape(s, spec.kv_heads, -1, spec.head_dim)
                pages, offs = _page_positions(table, lengths[:, None],
                                              self.page_len)
                pool = _paged_write({"k": cache["k"], "v": cache["v"]},
                                    pages, offs, k[:, None], v[:, None],
                                    inline_amax=False)
                kc = compress_step(spec.sparse, cache["kc"], table, lengths,
                                   k.reshape(s, -1))
                cache = dict(pool, kc=kc)
                blocks, valid = select_step(spec.sparse, q, kc, table,
                                            lengths)
                o, read = attend_step(spec.sparse, q, cache, table, lengths,
                                      active, blocks, valid)
                reads.append(read)
                with jax.named_scope("attention"):
                    x = self._mix_out(p, x, u, o.reshape(s, -1))
            x = self._ffn(p, x)
            new_caches.append(cache)
        read = jnp.mean(jnp.stack(reads).astype(jnp.float32)) if reads \
            else jnp.float32(0)
        return self._head(params, x), new_caches, read

    def _head(self, params, x):
        spec = self.spec
        with jax.named_scope("layer_norm"):
            x = _rms_norm(params["norm_f"], x, spec.rms_eps) \
                / spec.logit_divisor
        with jax.named_scope("head"):
            return jnp.einsum("sd,vd->sv", x.astype(params["head"].dtype),
                              params["head"],
                              preferred_element_type=jnp.float32)

    # -- prefill: a chunk of one stream's prompt --------------------------------

    def chunk_plan(self, fed: int) -> List[Tuple[int, int]]:
        """``[(start, padded length)]`` of the chunks that prefill ``fed``
        positions: whole chunks of ``prefill_chunk_len``, the last padded to
        a bucket; one empty chunk where there is nothing to feed, since a
        join has to overwrite the slot's states."""
        whole = self.prefill_chunk_len
        plan = [(at, whole) for at in range(0, fed - whole + 1, whole)]
        rest = fed - len(plan) * whole
        if rest or not plan:
            plan.append((len(plan) * whole, self.chunk_bucket(rest)))
        return plan

    def chunk_bucket(self, rest: int) -> int:
        return next(b for b in self.chunk_buckets if rest <= b)

    def prefill_chunk(self, params, tokens, caches, row, slot, start,
                      n_valid):
        """Feed ``tokens [1, T]`` at positions ``start .. start+T-1`` of the
        stream in ``slot`` whose pages ``row [W]`` names; the first
        ``n_valid`` are real. ``start`` is a multiple of the page. States
        and pages are carried on from the chunk before (a chunk at 0 starts
        them anew), so the caller may run other programs over the same
        caches between two chunks. Returns the caches."""
        spec = self.spec
        x = self._embed(params, jnp.asarray(tokens, jnp.int32)[0])  # [T, d]
        t = x.shape[0]
        positions = start + jnp.arange(t, dtype=jnp.int32)
        new_caches = []
        for kind, p, cache in zip(spec.mixers, params["layers"], caches):
            with jax.named_scope("layer_norm"):
                u = _rms_norm(p["norm1"], x, spec.rms_eps)
            if kind == LINEAR:
                with jax.named_scope("attention"):
                    q, k, v = self._project(
                        p, u, spec.linear_heads, spec.linear_heads,
                        spec.linear_head_dim, positions)
                with jax.named_scope("linear_attn"):
                    before = jnp.where(start == 0, 0.0, jax.lax.
                                       dynamic_index_in_dim(
                                           cache["state"], slot, 0, False))
                o, after = linear_attention_chunk(
                    q, k, v, before, lightning_slopes(spec.linear_heads),
                    n_valid)
                with jax.named_scope("linear_attn"):
                    cache = {"state": jax.lax.dynamic_update_index_in_dim(
                        cache["state"], after, slot, 0)}
                with jax.named_scope("attention"):
                    o = _rms_norm(p["o_norm"], o.reshape(t, -1),
                                  spec.rms_eps)
                    x = self._mix_out(p, x, u, o)
            else:
                with jax.named_scope("attention"):
                    q, k, v = self._project(p, u, spec.heads, spec.kv_heads,
                                            spec.head_dim, None)
                    q = q.reshape(t, spec.kv_heads, -1, spec.head_dim)
                pages, offs = _page_positions(row[None], positions[None],
                                              self.page_len)
                pool = _paged_write({"k": cache["k"], "v": cache["v"]},
                                    pages, offs, k[None], v[None],
                                    inline_amax=True)
                kc = compress_chunk(spec.sparse, cache["kc"], row, start,
                                    k.reshape(t, -1), n_valid)
                cache = dict(pool, kc=kc)
                allowed = select_chunk(spec.sparse, q, kc, row, start)
                o = attend_chunk(spec.sparse, q, cache, row, start, allowed)
                with jax.named_scope("attention"):
                    x = self._mix_out(p, x, u, o.reshape(t, -1))
            x = self._ffn(p, x)
            new_caches.append(cache)
        return new_caches
