"""A decoder whose block is data: :class:`DecoderSpec` says which norm
scale, positions, mixer, feed-forward, head and scalings a model has, layer
by layer, and :class:`LayeredDecoder` runs it behind
``serving.GenerativeServing`` (the contract that ``TransformerLM`` offers
the server: ``params``, ``max_len``, cache construction, a paged decode
step, a prefill; here ``paged_state_step`` and ``prefill_chunk``).

What it covers today is what MiniCPM-SALA, SmallThinker, GLM-5 and
GLM-5.3-Flash need (docs/models.md): RMS norms, an untied head, MiniCPM's
embedding, residual and logit scalings, bfloat16 parameters, a plain
residual or several residual streams mixed around every sublayer (mHC,
``hc_streams``: a doubly stochastic mix of the streams by Sinkhorn
iterations, under the scope ``mhc``), a feed-forward chosen by the spec,
for the whole model or layer by layer (``"silu"``: gated SiLU; ``"moe"``:
dropless top-k experts, ``ops/moe.py``, under a router that is data too: a
softmax on the layer's input before attention with ReGLU experts, or
sigmoid scores with a correction bias and a scaling on the normed output of
attention, with gated-SiLU experts and a shared expert), and a mixer chosen
by layer:

- ``"lightning-attn"``: linear attention with a decay a head
  (``ops/linear_attention.py``), ``qk_norm``, rotary positions, an output
  norm and an output gate. Its cache is a float32 state ``[slots, H, D, D]``.
- ``"minicpm4"``: grouped-query block-sparse softmax attention
  (``ops/sparse_attention.py``), ``qk_norm``, no positions, an output gate.
  Its cache is a K/V page pool with a compressed-key pool beside it.
- ``"full"``: grouped-query causal softmax attention with no positions,
  every key up to the query's own (``ops/grouped_attention.py``).
- ``"window"``: the same over the last ``window`` positions, with rotary
  positions. Its K/V page pool is a budget of its own: the pages that lie
  wholly behind a stream's window go back to the server's window free list.
- ``"mla-dsa"``: latent attention over a latent page pool with a learned
  selection (``ops/latent_attention.py``), with or without rotary parts,
  over single positions or pooled blocks of keys.
- ``"kda"``: gated delta-rule linear attention with a decay a channel
  (``ops/delta_attention.py``), a short convolution on q, k and v, an
  output norm and a low-rank output gate. Its cache is a float32 state
  ``[slots, H, D, D]`` and the convolution's window a slot.

Every kind of cache lives in the one list the server holds and donates.
The model is prefilled in chunks (:meth:`LayeredDecoder.prefill_chunk`,
``chunked``): each chunk carries the states and the pages on from where the
last one left them, so the server can run decode steps of the resident
streams between two chunks of a joining prompt. ``recurrent`` says whether
a slot also holds a state (a lightning or a kda layer). ``TransformerLM``
stays as it is for GPT-2-style models (ROADMAP D6)."""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import delta_attention, grouped_attention, latent_attention, moe
from ..ops.decode import _page_positions, _paged_write, init_paged_pool
from ..ops.linear_attention import (lightning_slopes, linear_attention_chunk,
                                    linear_attention_step)
from ..ops.sparse_attention import (SparseSpec, attend_chunk, attend_step,
                                    compress_chunk, compress_step,
                                    init_sparse_pool, select_chunk,
                                    select_step)

LINEAR, SPARSE = "lightning-attn", "minicpm4"
FULL, WINDOW = "full", "window"
LATENT = "mla-dsa"
KDA = "kda"
SILU, MOE = "silu", "moe"


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
    #: the feed-forward: ``"silu"`` (gated SiLU, ``intermediate`` wide) or
    #: ``"moe"`` (``experts`` ReGLU experts ``intermediate`` wide,
    #: ``experts_per_token`` a token, routed from the layer's input)
    ffn: str = SILU
    experts: int = 0
    experts_per_token: int = 0
    #: the experts whose tables this chip holds (``None``: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    window: int = 0                # positions a ``"window"`` layer sees
    page_len: int = 0              # 0: one page is one selection block
    #: the feed-forward of each layer in order (``None``: ``ffn`` in all)
    ffns: Optional[Tuple[str, ...]] = None
    #: the experts' width where it is not ``intermediate`` (a model whose
    #: dense layers are wider than its experts)
    expert_width: int = 0
    #: the router: ``"softmax"`` reads the layer's input as it is, before
    #: attention; ``"sigmoid"`` reads the normed output of attention, adds
    #: a correction bias for the choice alone, divides the chosen scores by
    #: their sum and scales them by ``routed_scale``
    router: str = "softmax"
    routed_scale: float = 1.0
    expert_act: str = "relu"       # ``"relu"`` (ReGLU) or ``"silu"``
    shared_width: int = 0          # a shared expert every token reads
    #: the sizes of the ``"mla-dsa"`` layers
    latent: Optional[latent_attention.LatentSpec] = None
    #: the sizes of the ``"kda"`` layers
    delta: Optional[delta_attention.DeltaSpec] = None
    #: residual streams: 1 is ``x <- x + residual_scale * f(N(x))``; n > 1
    #: keeps n streams and mixes them around every sublayer (mHC):
    #: ``x <- H_res x + H_post^T f(H_pre x)``, ``H_res`` doubly stochastic
    #: after ``hc_sinkhorn_iters`` normalisations of rows and columns
    hc_streams: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    #: every gated-SiLU product clamped, ``silu(min(gate, L)) * clip(up,
    #: -L, L)`` (0: none)
    swiglu_limit: float = 0.0

    def __post_init__(self):
        known = (LINEAR, SPARSE, FULL, WINDOW, LATENT, KDA)
        unknown = set(self.mixers) - set(known)
        if unknown:
            raise ValueError(f"no mixer named {sorted(unknown)}; have "
                             f"{', '.join(map(repr, known))}")
        unknown = set(self.layer_ffns) - {SILU, MOE}
        if unknown:
            raise ValueError(f"no feed-forward named {sorted(unknown)}; "
                             f"have {SILU!r} and {MOE!r}")
        if len(self.layer_ffns) != len(self.mixers):
            raise ValueError(f"{len(self.layer_ffns)} feed-forwards for "
                             f"{len(self.mixers)} mixers")
        if self.has_experts \
                and not 0 < self.experts_per_token <= self.experts:
            raise ValueError(f"{self.experts_per_token} experts a token of "
                             f"{self.experts}")
        if self.router not in ("softmax", "sigmoid") \
                or self.expert_act not in ("relu", "silu"):
            raise ValueError(f"router {self.router!r} (softmax, sigmoid) "
                             f"or expert_act {self.expert_act!r} (relu, "
                             f"silu) is not known")
        if LATENT in self.mixers and self.latent is None:
            raise ValueError("an mla-dsa layer needs its sizes (latent)")
        if KDA in self.mixers and self.delta is None:
            raise ValueError("a kda layer needs its sizes (delta)")
        if self.hc_streams > 1 and (self.residual_scale != 1.0
                                    or self.router != "sigmoid"
                                    and self.has_experts):
            raise ValueError("several residual streams are wired for a "
                             "residual scale of 1 and a router that reads "
                             "the normed output of attention")
        if WINDOW in self.mixers and (self.window < 1
                                      or self.window % self.page):
            raise ValueError(f"a window layer's window must be whole pages "
                             f"of {self.page}; got {self.window}")
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads over "
                             f"{self.kv_heads} key/value heads")
        if self.max_len % self.page:
            raise ValueError(f"max_len {self.max_len} is no whole number of "
                             f"pages of {self.page}")

    @property
    def page(self) -> int:
        return self.page_len or self.sparse.block_size

    @property
    def layer_ffns(self) -> Tuple[str, ...]:
        """The feed-forward of each layer in order."""
        return self.ffns if self.ffns is not None \
            else (self.ffn,) * len(self.mixers)

    @property
    def has_experts(self) -> bool:
        return MOE in self.layer_ffns

    @classmethod
    def from_config(cls, cfg: Dict[str, Any], max_len: int,
                    page_len: int = 64) -> "DecoderSpec":
        """From a ``config.json`` with its keys as published: a
        ``minicpm_sala`` one (``mixer_types``; ``sparse_attention`` holds
        InfLLM-V2's sizes, which the published file leaves to the code), a
        SmallThinker one (``sliding_window_layout`` with ``rope_layout``
        and the ``moe_*`` keys; ``page_len`` is the K/V page) or a
        ``glm_moe_dsa`` one (``kv_lora_rank``, ``index_*``,
        ``first_k_dense_replace``, ``n_routed_experts`` with
        ``held_experts`` where the chip holds a share) or a
        ``glm5_next_text`` one (the same, with ``layer_types``,
        ``mlp_layer_types``, ``linear_attn_config``, ``mhc`` with
        ``hc_mult``, ``mla_use_nope``, ``index_kpool`` and
        ``swiglu_limit``)."""
        if "kv_lora_rank" in cfg:
            return cls._from_latent(cfg, max_len, page_len)
        if "sliding_window_layout" in cfg:
            return cls._from_window_layout(cfg, max_len, page_len)
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

    @classmethod
    def _from_window_layout(cls, cfg, max_len, page_len):
        layout = list(cfg["sliding_window_layout"])
        if layout != list(cfg["rope_layout"]) \
                or len(layout) != cfg["num_hidden_layers"]:
            raise ValueError(
                "sliding_window_layout and rope_layout must name the same "
                "num_hidden_layers layers: a window layer has rotary "
                "positions and a full layer has none")
        return cls(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            intermediate=cfg["moe_ffn_hidden_size"],
            mixers=tuple(WINDOW if w else FULL for w in layout),
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            linear_heads=0, linear_head_dim=0, max_len=max_len,
            rms_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
            param_dtype=cfg.get("param_dtype", "bfloat16"),
            init_std=cfg.get("initializer_range", 0.02), ffn=MOE,
            experts=cfg["moe_num_primary_experts"],
            experts_per_token=cfg["moe_num_active_primary_experts"],
            window=cfg["sliding_window_size"], page_len=page_len)

    @classmethod
    def _from_latent(cls, cfg, max_len, page_len):
        n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
        held = cfg.get("held_experts")
        mixers, ffns = (LATENT,) * n, (SILU,) * dense + (MOE,) * (n - dense)
        if "layer_types" in cfg:
            named = {"linear_attention": KDA,
                     "deepseek_sparse_attention": LATENT}
            mlp = {"dense": SILU, "sparse": MOE}
            unknown = (set(cfg["layer_types"]) - set(named)) | (
                set(cfg["mlp_layer_types"]) - set(mlp))
            if unknown or len(cfg["layer_types"]) != n:
                raise ValueError(f"layer_types and mlp_layer_types must name "
                                 f"{n} layers of {sorted(named)} and "
                                 f"{sorted(mlp)}; got {sorted(unknown)}")
            mixers = tuple(named[t] for t in cfg["layer_types"])
            ffns = tuple(mlp[t] for t in cfg["mlp_layer_types"])
        lin = cfg.get("linear_attn_config")
        delta = None if lin is None else delta_attention.DeltaSpec(
            heads=lin["num_heads"], head_dim=lin["head_dim"],
            conv=lin["short_conv_kernel_size"],
            gate_lower_bound=lin["gate_lower_bound"], rank=lin["head_dim"])
        more = {}
        if cfg.get("mhc"):
            more.update(hc_streams=cfg["hc_mult"],
                        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
                        hc_eps=cfg["hc_eps"])
        if cfg.get("swiglu_limit"):
            more["swiglu_limit"] = float(cfg["swiglu_limit"])
        pooled = {}
        if cfg.get("index_kpool", 1) > 1:
            if not (cfg.get("index_kpool_compress")
                    and cfg.get("index_kpool_always_select_tail")):
                raise ValueError("pooled index keys are wired as their mean "
                                 "with the query's own block always read "
                                 "(index_kpool_compress and "
                                 "index_kpool_always_select_tail)")
            pooled = dict(index_kpool=cfg["index_kpool"])
        return cls(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            intermediate=cfg["intermediate_size"],
            mixers=mixers, heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["qk_head_dim"], linear_heads=0, linear_head_dim=0,
            max_len=max_len, rms_eps=cfg["rms_norm_eps"],
            rope_theta=cfg.get("rope_parameters", {}).get(
                "rope_theta", 10000.0),
            param_dtype=cfg.get("param_dtype", "bfloat16"),
            init_std=cfg.get("initializer_range", 0.02),
            ffns=ffns, delta=delta, **more,
            experts=cfg.get("n_routed_experts_published",
                            cfg["n_routed_experts"]),
            experts_per_token=cfg["num_experts_per_tok"],
            held_experts=None if held is None else tuple(held),
            expert_width=cfg["moe_intermediate_size"],
            router=("sigmoid" if cfg["scoring_func"] == "sigmoid"
                    else "softmax"),
            routed_scale=cfg["routed_scaling_factor"], expert_act="silu",
            shared_width=cfg["n_shared_experts"]
            * cfg["moe_intermediate_size"], page_len=page_len,
            latent=latent_attention.LatentSpec(
                heads=cfg["num_attention_heads"],
                q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
                nope_dim=cfg["qk_nope_head_dim"],
                rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
                index_heads=cfg["index_n_heads"],
                index_dim=cfg["index_head_dim"],
                index_topk=cfg["index_topk"],
                index_rope_dim=cfg["qk_rope_head_dim"], **pooled))

    def layer_shapes(self, kind: str, ffn: Optional[str] = None):
        """``(matrices, vectors that start at 1)`` of a layer of mixer
        ``kind`` and feed-forward ``ffn`` (``None``: the model's one);
        :meth:`layer_zeros` has the vectors that start at 0."""
        d, f = self.hidden, self.intermediate
        ffn = ffn or self.ffn
        ones = {"norm1": (d,), "norm2": (d,)}
        if kind == LATENT:
            mats, more, _ = self.latent.shapes(d)
            mats, ones = dict(mats), dict(ones, **more)
        elif kind == KDA:
            mats, more, _ = self.delta.shapes(d)
            mats, ones = dict(mats), dict(ones, **more)
        else:
            if kind == LINEAR:
                h = kv = self.linear_heads * self.linear_head_dim
                hd = self.linear_head_dim
            else:
                h, kv, hd = (self.heads * self.head_dim,
                             self.kv_heads * self.head_dim, self.head_dim)
            mats = {"q": (d, h), "k": (d, kv), "v": (d, kv), "o": (h, d)}
        if kind in (LINEAR, SPARSE):    # qk_norm and an output gate
            mats["g"] = (d, h)
            ones.update(q_norm=(hd,), k_norm=(hd,))
        if kind == LINEAR:
            ones["o_norm"] = (h,)
        if ffn == MOE:
            e = self.experts if self.held_experts is None \
                else len(self.held_experts)
            w = self.expert_width or f
            mats.update(router=(d, self.experts), w_gate=(e, d, w),
                        w_up=(e, d, w), w_down=(e, w, d))
            if self.shared_width:
                mats.update(shared_gate=(d, self.shared_width),
                            shared_up=(d, self.shared_width),
                            shared_down=(self.shared_width, d))
        else:
            mats.update(gate_proj=(d, f), up_proj=(d, f), down_proj=(f, d))
        n = self.hc_streams
        if n > 1:   # each sublayer's mHC: pre, post and the n x n mix
            for part in ("attn", "ffn"):
                mats[f"hc_{part}_phi"] = (n * d, n * (n + 2))
                ones[f"hc_{part}_alpha"] = (3,)
        return mats, ones

    def layer_zeros(self, kind: str, ffn: Optional[str] = None):
        """The vectors of such a layer that start at 0: the indexer's key
        norm's bias, a kda layer's ``A_log`` and decay bias, a sigmoid
        router's correction bias, the mHC biases."""
        zeros = dict(self.latent.shapes(self.hidden)[2]) \
            if kind == LATENT else {}
        if kind == KDA:
            zeros.update(self.delta.shapes(self.hidden)[2])
        if (ffn or self.ffn) == MOE and self.router == "sigmoid":
            zeros["router_bias"] = (self.experts,)
        n = self.hc_streams
        if n > 1:
            for part in ("attn", "ffn"):
                zeros[f"hc_{part}_bias"] = (n * (n + 2),)
        return zeros


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

    #: the server prefills such a model in chunks (:meth:`prefill_chunk`)
    chunked = True

    def __init__(self, spec: DecoderSpec, seed: int = 0,
                 prefill_chunk: int = 2048):
        self.spec = spec
        #: a slot holds a state beside its pages (a lightning or a kda
        #: layer): the server refuses what would need a snapshot of it
        self.recurrent = LINEAR in spec.mixers or KDA in spec.mixers
        #: positions a window layer sees, 0 where the model has none: the
        #: server then keeps a second page budget (:meth:`window_pages`)
        self.window_len = spec.window if WINDOW in spec.mixers else 0
        #: what ``paged_state_step`` returns third, by name, in order: what
        #: the spec has of routed experts, sparse reads and an indexer
        reads = SPARSE in spec.mixers or LATENT in spec.mixers
        self.step_stats = (
            (("moe_experts_touched", "moe_expert_load", "moe_assignments")
             if spec.has_experts else ())
            + (("sparse_positions_read",)
               if reads or not spec.has_experts else ())
            + (((("dsa_blocks_scored",) if spec.latent.index_kpool > 1
                 else ("dsa_positions_scored",)))
               if LATENT in spec.mixers else ()))
        #: a prompt is fed in chunks of ``prefill_chunk`` positions, the
        #: last padded to one of these: a closed set of compiled programs
        self.chunk_buckets = (prefill_chunk // 4, prefill_chunk // 2,
                              prefill_chunk)
        if any(b % spec.page for b in self.chunk_buckets):
            raise ValueError(f"chunk buckets {self.chunk_buckets} must be "
                             f"whole pages of {spec.page}")
        self.vocab_size = spec.vocab_size
        self.max_len = spec.max_len
        self.n_head = spec.heads
        self.page_len = spec.page   # a sparse layer's page is its block
        self.page_is = "selection block" if SPARSE in spec.mixers else "page"
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
        for i, (kind, ffn) in enumerate(zip(spec.mixers, spec.layer_ffns)):
            mats, ones = spec.layer_shapes(kind, ffn)
            keys = jax.random.split(jax.random.fold_in(root, i), len(mats))
            layer = {n: draw(k, s) for k, (n, s) in
                     zip(keys, sorted(mats.items()))}
            layer.update({n: jnp.ones(s, jnp.float32)
                          for n, s in ones.items()})
            layer.update({n: jnp.zeros(s, jnp.float32)
                          for n, s in spec.layer_zeros(kind, ffn).items()})
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
            "LayeredDecoder has no training path: it has no loss, and a "
            "linear-attention layer needs the chunked scan's backward, "
            "which is not written (ROADMAP R3.5)")

    # -- caches ---------------------------------------------------------------

    def init_paged_caches(self, num_pages: int, page_len: int,
                          int8: bool = False, slots: int = 1) -> List[Dict]:
        """One cache a layer, in layer order: a sparse layer's K/V page
        pool with its compressed-key pool, a full layer's K/V page pool
        (``num_pages`` each), a window layer's (:meth:`window_pages`), a
        lightning layer's state ``[slots, H, D, D]`` in float32, a latent
        layer's latent pool with its index-key pool (``num_pages`` each), a
        kda layer's state ``[slots, H, D, D]`` and convolution window."""
        spec = self.spec
        if int8:
            raise NotImplementedError(
                "int8 pages are not wired into this decoder's pools (the "
                "paged reads have no dequantising gather)")
        if page_len != spec.page:
            raise ValueError(f"kv_page_len must be the model's "
                             f"{self.page_is}, {spec.page}; got {page_len}")
        dtype = jnp.dtype(spec.param_dtype)
        caches = []
        for kind in spec.mixers:
            if kind == SPARSE:
                caches.append(init_sparse_pool(
                    num_pages, spec.sparse, spec.kv_heads, spec.head_dim,
                    dtype))
            elif kind in (FULL, WINDOW):
                caches.append(init_paged_pool(
                    num_pages if kind == FULL else self.window_pages(slots),
                    spec.kv_heads, page_len, spec.head_dim, dtype))
            elif kind == LATENT:
                caches.append(latent_attention.init_latent_pool(
                    num_pages, page_len, spec.latent, dtype))
            elif kind == KDA:
                caches.append(delta_attention.init_delta_cache(
                    slots, spec.delta))
            else:
                caches.append({"state": jnp.zeros(
                    (slots, spec.linear_heads, spec.linear_head_dim,
                     spec.linear_head_dim), jnp.float32)})
        return caches

    def window_pages(self, slots: int) -> int:
        """Pages of the window layers' budget: what ``slots`` decoding
        streams hold at most (the pages their window can lie on), what the
        one prompt being fed holds beyond that while a chunk runs, and the
        null page. 0 where the model has no window layer."""
        if not self.window_len:
            return 0
        return (slots * grouped_attention.window_pages(self.window_len,
                                                       self.page_len)
                + self.prefill_chunk_len // self.page_len + 1)

    # -- the block ------------------------------------------------------------

    def _residual(self, x, y):
        """``x + residual_scale * y``; ``y`` alone where ``x`` is ``None``
        (several residual streams: the mix adds it, :meth:`_hc_leave`)."""
        return y if x is None else x + self.spec.residual_scale * y

    def _ffn(self, p, x, residual=True):
        """``x + ffn(N(x))``, or ``ffn(N(x))`` alone (several residual
        streams: the mix adds it)."""
        spec = self.spec
        with jax.named_scope("layer_norm"):
            h = _rms_norm(p["norm2"], x, spec.rms_eps)
        with jax.named_scope("ffn"):
            if spec.swiglu_limit:
                mid = moe.swiglu(_product(h, p["gate_proj"]),
                                 _product(h, p["up_proj"]), spec.swiglu_limit)
            else:
                mid = jax.nn.silu(_product(h, p["gate_proj"])) \
                    * _product(h, p["up_proj"])
            return self._residual(x if residual else None,
                                  _product(mid, p["down_proj"]))

    # -- several residual streams (mHC) ---------------------------------------

    def _hc_enter(self, p, part, x):
        """``(input of the sublayer, what its output is added to, the
        mix)``: with one stream ``(x, x, None)``; with n, ``x [N, n, d]``
        gives ``H_pre x [N, d]``, ``None`` and ``(H_post [N, n], H_res [N,
        n, n])``, all from ``RMSNorm(vec x)`` through the part's ``phi``
        (float32 products at highest), its ``alpha`` and its bias."""
        spec, n = self.spec, self.spec.hc_streams
        if n == 1:
            return x, x, None
        with jax.named_scope("mhc"):
            flat = x.reshape(x.shape[0], -1)
            flat = flat * jax.lax.rsqrt(
                jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
                + spec.rms_eps)
            coef = jnp.dot(flat, p[f"hc_{part}_phi"].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)
            alpha, bias = p[f"hc_{part}_alpha"], p[f"hc_{part}_bias"]
            pre = jax.nn.sigmoid(alpha[0] * coef[:, :n] + bias[:n])
            post = 2.0 * jax.nn.sigmoid(alpha[1] * coef[:, n:2 * n]
                                        + bias[n:2 * n])
            res = jnp.exp(alpha[2] * coef[:, 2 * n:] + bias[2 * n:]).reshape(
                -1, n, n)
            # sums over n written as adds of slices: elementwise, so that
            # the compiler fuses all the iterations into one pass
            for _ in range(spec.hc_sinkhorn_iters):
                res = res / (sum(res[:, :, j] for j in range(n))[:, :, None]
                             + spec.hc_eps)
                res = res / (sum(res[:, i] for i in range(n))[:, None]
                             + spec.hc_eps)
            h = sum(pre[:, i, None] * x[:, i] for i in range(n))
        return h, None, (post, res)

    def _hc_leave(self, x, y, mix):
        """The residual after a sublayer whose output is ``y``: ``y`` itself
        with one stream (the sublayer added it), else ``H_res x + H_post^T
        y`` (float32, elementwise)."""
        if mix is None:
            return y
        post, res = mix
        with jax.named_scope("mhc"):
            mixed = sum(res[:, :, j, None] * x[:, None, j]
                        for j in range(self.spec.hc_streams))
            return mixed + post[..., None] * y[:, None]

    def _hc_streams(self, x):
        """The embedding copied into every stream."""
        n = self.spec.hc_streams
        if n == 1:
            return x
        with jax.named_scope("mhc"):
            return jnp.broadcast_to(x[:, None], (x.shape[0], n, x.shape[1]))

    # -- kda layers -----------------------------------------------------------

    def _delta_project(self, p, u):
        """The q, k, v projections side by side ``[N, 3C]`` (before their
        convolution), the write strength ``b [N, H]``, the log-decay ``g
        [N, H, D] = lower_bound * sigmoid(exp(A_log) (W_f2 W_f1 u +
        dt_bias))`` and the output gate ``sigmoid(W_g2 W_g1 u) [N, C]``."""
        dl = self.spec.delta
        n = u.shape[0]
        with jax.named_scope("attention"):
            qkv = _product(u, p["qkv"])
            beta = jax.nn.sigmoid(_product(u, p["beta"]))
            f = _product(_product(u, p["f_a"]), p["f_b"]) + p["dt_bias"]
            g = dl.gate_lower_bound * jax.nn.sigmoid(
                jnp.exp(p["A_log"])[:, None]
                * f.reshape(n, dl.heads, dl.head_dim))
            gate = jax.nn.sigmoid(_product(_product(u, p["g_a"]), p["g_b"]))
        return qkv, beta, g, gate

    def _delta_heads(self, mixed):
        """q (L2-normalised, scaled by ``D^-1/2``), k (L2-normalised) and v
        ``[N, H, D]`` from the convolved projections."""
        dl = self.spec.delta
        with jax.named_scope("attention"):
            q, k, v = (part.reshape(-1, dl.heads, dl.head_dim)
                       for part in jnp.split(mixed, 3, axis=-1))

            def l2(a):
                return a * jax.lax.rsqrt(
                    jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
            return l2(q) / math.sqrt(dl.head_dim), l2(k), v

    def _delta_out(self, p, x, o, gate):
        """``x + W_o (RMSNorm_head(o) * gate)``."""
        with jax.named_scope("attention"):
            o = _rms_norm(p["o_norm"], o, self.spec.rms_eps)
            return self._residual(
                x, _product(o.reshape(o.shape[0], -1) * gate, p["o"]))

    def _project(self, p, u, heads, kv_heads, head_dim, positions):
        """q (scaled), k, v of ``u [..., d]`` as ``[..., heads, head_dim]``;
        rotary where ``positions`` is given."""
        spec = self.spec
        lead = u.shape[:-1]
        q = _product(u, p["q"]).reshape(lead + (heads, head_dim))
        if "q_norm" in p:   # a lightning or a sparse layer's qk_norm
            q = _rms_norm(p["q_norm"], q, spec.rms_eps)
        k = _product(u, p["k"]).reshape(lead + (kv_heads, head_dim))
        if "k_norm" in p:
            k = _rms_norm(p["k_norm"], k, spec.rms_eps)
        v = _product(u, p["v"]).reshape(lead + (kv_heads, head_dim))
        if positions is not None:
            q = _rotary(q, positions, spec.rope_theta)
            k = _rotary(k, positions, spec.rope_theta)
        return q / math.sqrt(head_dim), k, v

    def _mix_out(self, p, x, u, o):
        """``x + c W_o (o * sigmoid(W_g u))``, ``o [..., H*D]``."""
        gate = jax.nn.sigmoid(_product(u, p["g"]))
        return self._residual(x, _product(o * gate, p["o"]))

    def _attend_paged(self, kind, p, x, u, cache, table, positions, attend):
        """A full or window layer over ``u [T, d]``: project, write the new
        K and V through ``table``, read (``attend(q, cache)``), project out.
        A step hands ``table [S, W]`` and ``positions [S, 1]``, a chunk
        ``table [1, W]`` and ``positions [1, T]``."""
        spec = self.spec
        with jax.named_scope("attention"):
            q, k, v = self._project(
                p, u, spec.heads, spec.kv_heads, spec.head_dim,
                positions.reshape(-1) if kind == WINDOW else None)
            q = q.reshape(q.shape[0], spec.kv_heads, -1, spec.head_dim)
        pages, offs = _page_positions(table, positions, self.page_len)
        cache = _paged_write(cache, pages, offs,
                             k.reshape(positions.shape + k.shape[1:]),
                             v.reshape(positions.shape + v.shape[1:]),
                             inline_amax=False)
        o = attend(q, cache)
        with jax.named_scope("attention"):
            x = self._residual(x, _product(o.reshape(o.shape[0], -1),
                                           p["o"]))
        return x, cache

    def _moe(self, p, x, valid, routed=None, residual=True):
        """``x + experts(h)`` (and the shared expert's ``shared(h)`` where
        the model has one; the sum alone where ``residual`` is false), ``h
        = N(x)``, under the routing ``routed``
        (``(choice, gates)``, made from the layer's input before
        attention) or, where there is none, a routing made from ``h``
        itself; also each held expert's assignments."""
        spec = self.spec
        with jax.named_scope("layer_norm"):
            h = _rms_norm(p["norm2"], x, spec.rms_eps)
        choice, gates = routed or moe.route(
            h, p["router"], spec.experts_per_token, spec.router,
            p.get("router_bias"), spec.routed_scale)
        y, sizes = moe.experts(
            h, choice, gates, p["w_gate"], p["w_up"], p["w_down"],
            spec.held_experts, valid,
            jax.nn.silu if spec.expert_act == "silu" else jax.nn.relu,
            spec.swiglu_limit)
        if spec.shared_width:
            y = y + moe.shared(h, p["shared_gate"], p["shared_up"],
                               p["shared_down"], spec.swiglu_limit)
        return self._residual(x if residual else None, y), sizes

    def _attend_latent(self, p, x, u, cache, table, positions, attend,
                       real=None):
        """An ``mla-dsa`` layer over ``u [N, d]`` at ``positions`` (``[S,
        1]`` of a step, ``[1, T]`` of a chunk, whose ``real`` positions
        alone add to a pooled index key): the latent products, the row and
        the index key written through ``table``, the indexer, the selection
        and the read (``attend(q_nope, q_rope, q_index, w_index, cache)``
        gives ``(o, stats)``), the output product."""
        spec = self.spec
        lat, at = spec.latent, positions.reshape(-1)
        q_nope, q_rope, row, c_q = latent_attention.project(
            lat, p, u, at, spec.rope_theta, spec.rms_eps)
        q_i, k_i, w_i = latent_attention.index_project(
            lat, p, u, c_q, at, spec.rope_theta)
        pages, offs = _page_positions(table, positions, self.page_len)
        cache = latent_attention.write(
            cache, pages, offs, row.reshape(positions.shape + (-1,)),
            k_i.reshape(positions.shape + (-1,)),
            real if lat.index_kpool > 1 else None)
        o, stats = attend(q_nope, q_rope, q_i, w_i, cache)
        with jax.named_scope("mla_project"):
            x = self._residual(x, _product(o, p["o"]))
        return x, cache, stats

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return self.spec.embed_scale * jnp.take(
                params["embed"], tokens, axis=0).astype(jnp.float32)

    # -- decode: one position of every slot -----------------------------------

    def paged_state_step(self, params, tokens, lengths, table, caches,
                         active=None):
        """One decode step over all slots: ``tokens [S]`` at positions
        ``lengths [S]``. ``table`` is the page table, or ``(full, window)``
        where the model has window layers. Returns ``(logits [S, V],
        caches, stats)``, ``stats`` what :attr:`step_stats` names: the
        positions a sparse layer gathered for a stream in this step (the
        mean over the sparse layers, as the device counted them), or, for
        routed experts, the distinct experts a layer used, its busiest
        expert's assignments over the mean (both means over the layers) and
        the assignments of all layers, the active slots' alone (of the
        experts held here, where the chip holds a share); a model with
        latent layers gives the experts' three, the positions its sparse
        read gathered and the positions its indexer scored for a live
        stream (means over the active slots and the layers). Only an
        ``active`` slot's state moves, and only an active slot's token
        reads an expert."""
        spec = self.spec
        tokens = jnp.asarray(tokens, jnp.int32)
        if active is None:
            active = jnp.ones(tokens.shape, bool)
        table, window_table = table if isinstance(table, (tuple, list)) \
            else (table, table)
        x = self._hc_streams(self._embed(params, tokens))   # [S, (n,) d]
        new_caches, reads, loads, scored = [], [], [], []
        for kind, ffn, p, cache in zip(spec.mixers, spec.layer_ffns,
                                       params["layers"], caches):
            # a softmax router sees the layer's input as it is
            routed = moe.route(x, p["router"], spec.experts_per_token) \
                if ffn == MOE and spec.router == "softmax" else None
            streams = x
            x, base, mix = self._hc_enter(p, "attn", streams)
            with jax.named_scope("layer_norm"):
                u = _rms_norm(p["norm1"], x, spec.rms_eps)
            if kind == LATENT:
                x, cache, (read, seen) = self._attend_latent(
                    p, base, u, cache, table, lengths[:, None],
                    lambda *q: self._latent_step(p, *q, table, lengths,
                                                 active))
                reads.append(read)
                scored.append(seen)
            elif kind == KDA:
                qkv, beta, g, gate = self._delta_project(p, u)
                mixed, window = delta_attention.conv_step(
                    qkv, cache["conv"], p["conv"], active)
                q, k, v = self._delta_heads(mixed)
                o, state = delta_attention.delta_attention_step(
                    q, k, v, g, beta, cache["state"], active)
                cache = {"state": state, "conv": window}
                x = self._delta_out(p, base, o, gate)
            elif kind in (FULL, WINDOW):
                window = spec.window if kind == WINDOW else None
                tab = window_table if kind == WINDOW else table
                x, cache = self._attend_paged(
                    kind, p, base, u, cache, tab, lengths[:, None],
                    lambda q, c: grouped_attention.attend_step(
                        q, c, tab, lengths, active, window))
            elif kind == LINEAR:
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
                    x = self._mix_out(p, base, u, o)
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
                    x = self._mix_out(p, base, u, o.reshape(s, -1))
            x = self._hc_leave(streams, x, mix)
            streams = x
            x, _, mix = self._hc_enter(p, "ffn", streams)
            if ffn == MOE:
                x, sizes = self._moe(p, x, active, routed, mix is None)
                loads.append(moe.load_stats(sizes))
            else:
                x = self._ffn(p, x, mix is None)
            x = self._hc_leave(streams, x, mix)
            new_caches.append(cache)
        parts = []  # what :attr:`step_stats` names, in its order
        if loads:
            by_layer = jnp.stack(loads)                         # [layers, 3]
            parts.append(jnp.concatenate([jnp.mean(by_layer[:, :2], axis=0),
                                          jnp.sum(by_layer[:, 2:], axis=0)]))
        if reads or not loads:
            parts.append(
                jnp.mean(jnp.stack(reads).astype(jnp.float32)) if reads
                else jnp.float32(0))
        if scored:
            parts.append(jnp.mean(jnp.stack(scored)))
        read = parts[0] if len(parts) == 1 else jnp.concatenate(
            [jnp.atleast_1d(part) for part in parts])
        return self._head(params, x), new_caches, read

    def _latent_step(self, p, q_nope, q_rope, q_i, w_i, cache, table,
                     lengths, active):
        """The indexer, the selection and the absorbed read of a decode
        step; also the positions read and scored for a live stream (the
        means over the active slots)."""
        lat = self.spec.latent
        scores = latent_attention.index_step(
            lat, q_i, w_i, cache["index"], table, lengths, active,
            cache["latent"].dtype)
        at, real = latent_attention.select_step(lat, scores, lengths)
        o = latent_attention.attend_step(lat, p, q_nope, q_rope,
                                         cache["latent"], table, at, real)
        live = jnp.maximum(jnp.sum(active), 1).astype(jnp.float32)
        read = jnp.sum(jnp.where(active[:, None], real, False)) / live
        # positions scored (the context), or the pooled blocks wholly
        # before the query
        seen = lengths + 1 if lat.index_kpool == 1 \
            else lengths // lat.index_kpool
        seen = jnp.sum(jnp.where(active, seen, 0)) / live
        return o, (read, seen.astype(jnp.float32))

    def _head(self, params, x):
        spec = self.spec
        if spec.hc_streams > 1:   # the streams summed before the final norm
            with jax.named_scope("mhc"):
                x = jnp.sum(x, axis=1)
        with jax.named_scope("layer_norm"):
            x = _rms_norm(params["norm_f"], x, spec.rms_eps) \
                / spec.logit_divisor
        with jax.named_scope("head"):
            return jnp.einsum("sd,vd->sv", x.astype(params["head"].dtype),
                              params["head"],
                              preferred_element_type=jnp.float32)

    # -- prefill: a chunk of one stream's prompt ------------------------------

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
        stream in ``slot`` whose pages ``row [W]`` names (``(full, window)``
        rows where the model has window layers); the first ``n_valid`` are
        real. ``start`` is a multiple of the page. States
        and pages are carried on from the chunk before (a chunk at 0 starts
        them anew), so the caller may run other programs over the same
        caches between two chunks. Returns the caches."""
        spec = self.spec
        x = self._embed(params, jnp.asarray(tokens, jnp.int32)[0])  # [T, d]
        t = x.shape[0]
        positions = start + jnp.arange(t, dtype=jnp.int32)
        row, window_row = row if isinstance(row, (tuple, list)) \
            else (row, row)
        real = jnp.arange(t) < n_valid
        x = self._hc_streams(x)
        new_caches = []
        for kind, ffn, p, cache in zip(spec.mixers, spec.layer_ffns,
                                       params["layers"], caches):
            routed = moe.route(x, p["router"], spec.experts_per_token) \
                if ffn == MOE and spec.router == "softmax" else None
            streams = x
            x, base, mix = self._hc_enter(p, "attn", streams)
            with jax.named_scope("layer_norm"):
                u = _rms_norm(p["norm1"], x, spec.rms_eps)
            if kind == LATENT:
                x, cache, _ = self._attend_latent(
                    p, base, u, cache, row[None], positions[None],
                    lambda *q: (self._latent_chunk(p, *q, row, start), None),
                    real)
            elif kind == KDA:
                x, cache = self._delta_chunk(p, base, u, cache, slot, start,
                                             n_valid)
            elif kind in (FULL, WINDOW):
                window = spec.window if kind == WINDOW else None
                pages = window_row if kind == WINDOW else row
                x, cache = self._attend_paged(
                    kind, p, base, u, cache, pages[None], positions[None],
                    lambda q, c: grouped_attention.attend_chunk(
                        q, c, pages, start, window))
            elif kind == LINEAR:
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
                    x = self._mix_out(p, base, u, o)
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
                    x = self._mix_out(p, base, u, o.reshape(t, -1))
            x = self._hc_leave(streams, x, mix)
            streams = x
            x, _, mix = self._hc_enter(p, "ffn", streams)
            if ffn == MOE:
                x, _ = self._moe(p, x, real, routed, mix is None)
            else:
                x = self._ffn(p, x, mix is None)
            x = self._hc_leave(streams, x, mix)
            new_caches.append(cache)
        return new_caches

    def _delta_chunk(self, p, x, u, cache, slot, start, n_valid):
        """A kda layer over a chunk: the slot's state and window carried on
        from the chunk before (a chunk at 0 starts both anew)."""
        dl = self.spec.delta
        qkv, beta, g, gate = self._delta_project(p, u)
        with jax.named_scope("delta_conv"):
            window = jnp.where(start == 0, 0.0, jax.lax.dynamic_index_in_dim(
                cache["conv"], slot, 0, False))
        mixed, window = delta_attention.conv_chunk(qkv, window, p["conv"],
                                                   n_valid)
        q, k, v = self._delta_heads(mixed)
        with jax.named_scope("delta_attn"):
            before = jnp.where(start == 0, 0.0, jax.lax.dynamic_index_in_dim(
                cache["state"], slot, 0, False))
        o, after = delta_attention.delta_attention_chunk(
            q, k, v, g, beta, before, n_valid, dl)
        with jax.named_scope("delta_attn"):
            cache = {"state": jax.lax.dynamic_update_index_in_dim(
                         cache["state"], after, slot, 0),
                     "conv": jax.lax.dynamic_update_index_in_dim(
                         cache["conv"], window, slot, 0)}
        return self._delta_out(p, x, o, gate), cache

    def _latent_chunk(self, p, q_nope, q_rope, q_i, w_i, cache, row, start):
        """The indexer, the selection and the plain masked read of a chunk's
        queries, each over its own selection."""
        lat = self.spec.latent
        bits = latent_attention.index_chunk(lat, q_i, w_i, cache["index"],
                                            row, start, cache["latent"].dtype)
        threshold, last = latent_attention.select_chunk(lat, bits, start)
        return latent_attention.attend_chunk(
            lat, p, q_nope, q_rope, cache["latent"], row, start, bits,
            threshold, last)
