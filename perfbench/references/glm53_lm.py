"""Plain reference of GLM-5.3-Flash's decoder (``configs/glm_5_3_flash.json``
states the source, the equations and what is assumed): RMS norms, an untied
head, no rotary anywhere, and four residual streams. Each sublayer ``F``
(attention or feed-forward, each with its own RMS norm inside) is
hyper-connected (mHC): from ``x~ = RMSNorm(vec x)`` (no weight),
``H_pre = sigmoid(a_pre x~ phi_pre + b_pre)``, ``H_post = 2 sigmoid(a_post
x~ phi_post + b_post)``, ``H_res`` = 20 Sinkhorn normalisations (rows, then
columns, ``hc_eps`` in the denominators) of ``exp(a_res mat(x~ phi_res) +
b_res)``, and ``x <- H_res x + H_post^T F(H_pre x)``; the embedding is copied
into the streams and they are summed before the final norm.

A layer's mixer is one of two:

- **KDA** (``linear_attention``): q, k, v = SiLU(causal depthwise conv of 4
  taps over ``W x``), q and k L2-normalised a head and q scaled by
  ``D^-1/2``, ``b = sigmoid(W_b x)`` a head, the log-decay a channel ``g =
  lower_bound sigmoid(exp(A_log) (W_f2 W_f1 x + dt_bias))``, and the gated
  delta rule ``S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T``, ``o_t =
  S_t^T q_t``, as a **scan over single positions** (not the program's
  chunked form); ``y = W_o (RMSNorm_head(o) * sigmoid(W_g2 W_g1 x))``.
- **sparse latent attention without rotary** (``deepseek_sparse_attention``):
  GLM-5's latent products with ``qk_rope_head_dim`` 0 (scale
  ``1/sqrt(qk_head_dim)``), in the plain form, over the positions that an
  indexer over **pooled keys** selects: index keys are the means of whole
  blocks of ``index_kpool`` positions, scored ``sum_j w_j relu(q_j . K_b)``
  for the blocks wholly before the query, by brute force; a query takes the
  ``index_topk / index_kpool - 1`` blocks of largest score (``lax.top_k``,
  ties to the lower block) and the block that holds it, up to its own
  position.

Then a gated-SiLU feed-forward (the leading dense layer) or sigmoid-routed
gated-SiLU experts with a shared expert (``glm5_lm``'s router), every
gated product clamped: ``silu(min(gate, L)) * clip(up, -L, L)``.

Float32 products at ``highest``; no cache, no chunks, no kernels. Departures
from a published implementation, each assumed in the configuration's
``assumed``: the low-rank widths of the decay and the output gate (the head
size), the gate's form under ``gate_lower_bound``, mHC's norm without a
weight, the reading of the pooled selection, and the clamp's form. Nothing
of the program is imported.

The comparison's number is a mean over runs of served positions
(:func:`gaps_below_best`), not the widest single gap.

Weights come from the seed here, bfloat16 matrices a layer at a time, and
are handed to the program in its own layout."""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C
from . import glm5_lm as G

ROWS = G.ROWS        # query rows of the indexer and of attention at a time
HEADS = G.HEADS      # heads of attention computed at a time
BUCKET = G.BUCKET    # a row is computed at its length rounded up to this
ROWS_KDA = 2048      # rows of a KDA layer's projections made at a time
GAP_BLOCK = 128      # served positions a compared gap is the mean over

#: the keys that decide what a layer computes: the key of each compiled piece
USED = ("vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_attention_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "v_head_dim", "index_n_heads",
        "index_head_dim", "index_topk", "index_kpool", "n_routed_experts",
        "n_routed_experts_published", "held_experts", "n_shared_experts",
        "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
        "initializer_range", "param_dtype", "router_bias_spread",
        "index_norm_eps", "linear_attn_config", "hc_mult",
        "hc_sinkhorn_iters", "hc_eps", "swiglu_limit", "mhc_draw",
        "kda_draw")


def _key(cfg):
    return json.dumps({k: cfg.get(k) for k in USED}, sort_keys=True)


def _cfg(key):
    return json.loads(key)


def kinds(cfg):
    """``(mixer, feed-forward)`` a layer: ``"kda"`` or ``"dsa"``,
    ``"dense"`` or ``"moe"``."""
    mixer = {"linear_attention": "kda", "deepseek_sparse_attention": "dsa"}
    ffn = {"dense": "dense", "sparse": "moe"}
    return [(mixer[a], ffn[b]) for a, b in zip(cfg["layer_types"],
                                               cfg["mlp_layer_types"])]


def layer_shapes(cfg, mixer, ffn):
    """``(matrices, vectors that start at 1, vectors that start at 0)``."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    n = cfg["hc_mult"]
    mats = {f"hc_{part}_phi": (n * d, n * (n + 2)) for part in ("attn", "ffn")}
    ones = {"norm1": (d,), "norm2": (d,)}
    zeros = {}
    if mixer == "kda":
        lin = cfg["linear_attn_config"]
        c, r = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
        mats.update(qkv=(d, 3 * c),
                    conv=(lin["short_conv_kernel_size"], 3 * c), o=(c, d),
                    beta=(d, lin["num_heads"]), f_a=(d, r), f_b=(r, c),
                    g_a=(d, r), g_b=(r, c))
        ones["o_norm"] = (lin["head_dim"],)
        zeros.update(A_log=(lin["num_heads"],), dt_bias=(c,))
    else:
        qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
        nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
        ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
        mats.update(q_a=(d, qr), q_b=(qr, h * nope), kv_a=(d, kr),
                    kv_b=(kr, h * (nope + v)), o=(h * v, d),
                    index_q=(qr, ih * idim), index_k=(d, idim),
                    index_w=(d, ih))
        ones.update(q_a_norm=(qr,), kv_a_norm=(kr,), index_k_norm=(idim,))
        zeros["index_k_bias"] = (idim,)
    if ffn == "moe":
        e, f = len(G.table_of(cfg)), cfg["moe_intermediate_size"]
        s = cfg["n_shared_experts"] * f
        mats.update(router=(d, G.routed(cfg)), w_gate=(e, d, f),
                    w_up=(e, d, f), w_down=(e, f, d), shared_gate=(d, s),
                    shared_up=(d, s), shared_down=(s, d))
        zeros["router_bias"] = (G.routed(cfg),)
    else:
        f = cfg["intermediate_size"]
        mats.update(gate_proj=(d, f), up_proj=(d, f), down_proj=(f, d))
    for part in ("attn", "ffn"):
        ones[f"hc_{part}_alpha"] = (3,)
        zeros[f"hc_{part}_bias"] = (n * (n + 2),)
    return mats, ones, zeros


@functools.lru_cache(maxsize=None)
def _layer_maker(key, mixer, ffn):
    cfg = _cfg(key)
    mats, ones, zeros = layer_shapes(cfg, mixer, ffn)
    dtype = jnp.dtype(cfg["param_dtype"])
    std = cfg["initializer_range"]
    n = cfg["hc_mult"]
    hc, kda = cfg["mhc_draw"], cfg["kda_draw"]

    def make(rng):
        keys = jax.random.split(rng, len(mats) + 8)
        out = {name: (jax.random.normal(k, shape) * std).astype(dtype)
               for k, (name, shape) in zip(keys, sorted(mats.items()))}
        out.update({n_: jnp.ones(s, jnp.float32) for n_, s in ones.items()})
        out.update({n_: jnp.zeros(s, jnp.float32) for n_, s in zeros.items()})
        more = iter(keys[len(mats):])
        # ``assumed.mhc_draw``: alphas, and biases that make H_res neither
        # the identity nor uniform
        spread = jnp.concatenate([jnp.full((n,), hc["pre_bias_spread"]),
                                  jnp.full((n,), hc["post_bias_spread"]),
                                  jnp.full((n * n,), hc["res_bias_spread"])])
        for part in ("attn", "ffn"):
            out[f"hc_{part}_alpha"] = jnp.full((3,), hc["alpha"], jnp.float32)
            out[f"hc_{part}_bias"] = jax.random.normal(
                next(more), (n * (n + 2),)) * spread
        if "router_bias" in zeros:
            out["router_bias"] = jax.random.normal(
                next(more), zeros["router_bias"]) * float(
                    cfg.get("router_bias_spread") or 0.0)
        if mixer == "kda":   # ``assumed.kda_draw``
            bound = kda["conv_bound"]
            out["conv"] = jax.random.uniform(
                next(more), mats["conv"], minval=-bound,
                maxval=bound).astype(dtype)
            out["A_log"] = jnp.log(jax.random.uniform(
                next(more), zeros["A_log"], minval=kda["A_low"],
                maxval=kda["A_high"]))
            out["dt_bias"] = kda["dt_bias_mean"] + kda["dt_bias_std"] \
                * jax.random.normal(next(more), zeros["dt_bias"])
        return out
    return jax.jit(make)


def init_weights(cfg, seed):
    """Every weight from ``seed``, one jitted call a layer: ``embed`` and
    ``head`` ``[vocab, hidden]`` (untied), ``norm_f``, and ``layers``, a
    list of one dict a layer (the program's own layout)."""
    key = _key(cfg)
    root = jax.random.PRNGKey(seed % (2 ** 31))
    layers = [_layer_maker(key, mixer, ffn)(jax.random.fold_in(root, i))
              for i, (mixer, ffn) in enumerate(kinds(cfg))]
    return {"embed": G._table_maker(key)(jax.random.fold_in(root, 1000)),
            "head": G._table_maker(key)(jax.random.fold_in(root, 1001)),
            "norm_f": jnp.ones((cfg["hidden_size"],), jnp.float32),
            "layers": layers}


# -- the pieces --------------------------------------------------------------

rms_norm = G.rms_norm


def _sinkhorn(m, iters, eps):
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hyper(cfg, p, part, x, sublayer, iters=None):
    """``H_res x + H_post^T F(H_pre x)`` over the streams ``x [s, n, d]``
    (``iters``: the Sinkhorn normalisations, the configuration's where
    ``None``; the tests plant fewer)."""
    s, n, _ = x.shape
    flat = x.reshape(s, -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1,
                                         keepdims=True) + cfg["rms_norm_eps"])
    coef = jnp.dot(flat, p[f"hc_{part}_phi"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    alpha, bias = p[f"hc_{part}_alpha"], p[f"hc_{part}_bias"]
    pre = jax.nn.sigmoid(alpha[0] * coef[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * coef[:, n:2 * n] + bias[n:2 * n])
    res = _sinkhorn(jnp.exp(alpha[2] * coef[:, 2 * n:] + bias[2 * n:])
                    .reshape(s, n, n),
                    cfg["hc_sinkhorn_iters"] if iters is None else iters,
                    cfg["hc_eps"])
    y = sublayer(jnp.einsum("si,sid->sd", pre, x,
                            precision=jax.lax.Precision.HIGHEST))
    return jnp.einsum("sij,sjd->sid", res, x,
                      precision=jax.lax.Precision.HIGHEST) \
        + post[..., None] * y[:, None]


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def kda(cfg, p, u, precision, delta=True):
    """The KDA mixer of ``u [s, d]`` (normed), the recurrence position by
    position (``delta=False`` drops the ``- b k k^T`` term: the tests'
    planted fault). The projections are made ``ROWS_KDA`` rows at a time,
    the state and the convolution's last inputs carried from one block of
    rows to the next, so that a long row fits beside the weights."""
    lin = cfg["linear_attn_config"]
    h, dim = lin["num_heads"], lin["head_dim"]
    taps = p["conv"].astype(jnp.float32)
    width = taps.shape[0]
    s = u.shape[0]
    rows = min(ROWS_KDA, s)
    pad = -s % rows

    def block(carry, u_b):
        state, tail = carry
        b = u_b.shape[0]
        full = jnp.concatenate(
            [tail, C.einsum("sd,dn->sn", u_b, p["qkv"], precision)], axis=0)
        mixed = jax.nn.silu(sum(full[j:j + b] * taps[j]
                                for j in range(width)))
        q, k, v = (a.reshape(b, h, dim) for a in jnp.split(mixed, 3, -1))
        q, k = _l2(q) / math.sqrt(dim), _l2(k)
        beta = jax.nn.sigmoid(C.einsum("sd,dh->sh", u_b, p["beta"],
                                       precision))
        f = C.einsum("sr,rc->sc", C.einsum("sd,dr->sr", u_b, p["f_a"],
                                           precision),
                     p["f_b"], precision) + p["dt_bias"]
        g = lin["gate_lower_bound"] * jax.nn.sigmoid(
            jnp.exp(p["A_log"])[:, None] * f.reshape(b, h, dim))

        def step(state, xs):                            # state [H, Dk, Dv]
            q_t, k_t, v_t, g_t, b_t = xs
            state = jnp.exp(g_t)[..., None] * state
            if delta:
                recalled = jnp.sum(state * k_t[..., None], axis=1)  # [H, Dv]
                state = state - b_t[:, None, None] * k_t[..., None] \
                    * recalled[:, None]
            state = state + b_t[:, None, None] * k_t[..., None] \
                * v_t[:, None]
            return state, jnp.sum(state * q_t[..., None], axis=1)
        state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
        o = rms_norm(p["o_norm"], o, cfg["rms_norm_eps"]).reshape(b, -1)
        gate = jax.nn.sigmoid(C.einsum(
            "sr,rc->sc", C.einsum("sd,dr->sr", u_b, p["g_a"], precision),
            p["g_b"], precision))
        return (state, full[b:]), C.einsum("sc,cd->sd", o * gate, p["o"],
                                           precision)
    init = (jnp.zeros((h, dim, dim), jnp.float32),
            jnp.zeros((width - 1, 3 * h * dim), jnp.float32))
    _, y = jax.lax.scan(block, init, jnp.pad(u, ((0, pad), (0, 0))).reshape(
        -1, rows, u.shape[1]))
    return y.reshape(-1, y.shape[-1])[:s]


def indexer(cfg, p, u, c_q, precision):
    """The indexer's queries ``[s, IH, ID]``, keys ``[s, ID]`` (a layer
    norm with weight and bias) and head weights ``[s, IH]``; no rotary."""
    s, ih, idim = u.shape[0], cfg["index_n_heads"], cfg["index_head_dim"]
    q = C.einsum("sd,dn->sn", c_q, p["index_q"], precision).reshape(
        s, ih, idim)
    k = C.einsum("sd,dn->sn", u, p["index_k"], precision)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(
        jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        + float(cfg.get("index_norm_eps") or 1e-6)) \
        * p["index_k_norm"] + p["index_k_bias"]
    w = C.einsum("sd,dn->sn", u, p["index_w"], precision) \
        / math.sqrt(ih) / math.sqrt(idim)
    return q, k, w


def block_scores(q_rows, w_rows, blocks, first, kpool, precision):
    """``I[t, b] = sum_j w[t, j] relu(q[t, j] . K_b)`` for the rows ``t =
    first .. first + ROWS - 1`` against the pooled keys ``blocks [nb, ID]``,
    ``-inf`` where block ``b`` is not wholly before ``t``."""
    dots = C.einsum("rhd,bd->rhb", q_rows, blocks, precision)
    scores = jnp.einsum("rhb,rh->rb", jax.nn.relu(dots), w_rows,
                        precision=jax.lax.Precision.HIGHEST)
    t = first + jnp.arange(ROWS)
    return jnp.where(jnp.arange(blocks.shape[0])[None]
                     < (t // kpool)[:, None], scores, -jnp.inf)


def attention(cfg, p, u, precision):
    """``W_o concat_h softmax_{s in S_t}(q_h . k_h / sqrt(qk_head_dim))
    v_h`` over ``u [s, d]``, the plain form with no rotary part; ``S_t`` the
    positions of the chosen blocks and of ``t``'s own, up to ``t``."""
    s, n = u.shape[0], cfg["num_attention_heads"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    kr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    kpool = cfg["index_kpool"]
    chosen = cfg["index_topk"] // kpool - 1
    c_q = rms_norm(p["q_a_norm"],
                   C.einsum("sd,dn->sn", u, p["q_a"], precision), eps)
    c_kv = rms_norm(p["kv_a_norm"],
                    C.einsum("sd,dn->sn", u, p["kv_a"], precision), eps)
    q_i, k_i, w_i = indexer(cfg, p, u, c_q, precision)
    blocks = jnp.mean(k_i[:s - s % kpool].reshape(-1, kpool, k_i.shape[1]),
                      axis=1)
    q_i, w_i, c_q = G._padded(q_i), G._padded(w_i), G._padded(c_q)

    def scores_of(first):
        return block_scores(G._rows_of(q_i, first), G._rows_of(w_i, first),
                            blocks, first, kpool, precision)
    kth, last = jax.lax.map(
        lambda first: G.kth_largest(scores_of(first), chosen),
        jnp.arange(0, s, ROWS))
    kth, last = G._padded(kth.reshape(-1, 1)), G._padded(last.reshape(-1, 1))
    pos = jnp.arange(s)

    def mask_of(first):
        picked = G.selected(scores_of(first), G._rows_of(kth, first),
                            G._rows_of(last, first))         # [ROWS, nb]
        t = first + jnp.arange(ROWS)
        blk = pos // kpool
        own = blk[None] == (t // kpool)[:, None]
        inside = jnp.take(picked, jnp.minimum(blk, picked.shape[1] - 1),
                          axis=1) & (blk[None] < picked.shape[1])
        return (inside | own) & (pos[None] <= t[:, None])

    groups = min(HEADS, n)
    q_b = p["q_b"].reshape(-1, n // groups, groups * nope)
    kv_b = p["kv_b"].reshape(kr, n // groups, groups * (nope + vd))

    def heads(g):
        k = C.einsum("sc,cn->sn", c_kv, kv_b[:, g], precision).reshape(
            s, groups, nope + vd)

        def rows(first):
            q = C.einsum("rc,cn->rn", G._rows_of(c_q, first), q_b[:, g],
                         precision).reshape(ROWS, groups, nope)
            scores = C.einsum("rhd,shd->rhs", q, k[..., :nope],
                              precision) / math.sqrt(nope)
            seen = mask_of(first)[:, None]
            scores = jnp.where(seen, scores, -1e30)
            probs = jnp.where(seen, jax.nn.softmax(scores, axis=-1), 0.0)
            return C.einsum("rhs,shd->rhd", probs, k[..., nope:], precision)
        return jax.lax.map(rows, jnp.arange(0, s, ROWS)).reshape(
            -1, groups * vd)[:s]
    out = jax.lax.map(heads, jnp.arange(n // groups))
    out = jnp.moveaxis(out, 0, 1).reshape(s, n * vd)
    return C.einsum("sn,nd->sd", out, p["o"], precision)


def gated(h, w_gate, w_up, w_down, precision, limit):
    """``W_down (silu(min(W_gate h, L)) * clip(W_up h, -L, L))``."""
    gate = C.einsum("sd,df->sf", h, w_gate, precision)
    up = C.einsum("sd,df->sf", h, w_up, precision)
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return C.einsum("sf,fd->sd", jax.nn.silu(gate) * up, w_down, precision)


def experts(cfg, p, h, choice, gates, precision, held=None):
    """``sum_e g_e E_e(h)`` over the experts held (``held``: expert
    numbers, all those the weights hold where ``None``)."""
    table = G.table_of(cfg)
    limit = cfg.get("swiglu_limit") or 0.0
    which = jnp.asarray([(table.index(e), e)
                         for e in (table if held is None else held)])

    def rows(block, pick, gate):
        def one(total, at):
            g = jnp.sum(jnp.where(pick == at[1], gate, 0.0), axis=-1)
            return total + g[:, None] * gated(
                block, p["w_gate"][at[0]], p["w_up"][at[0]],
                p["w_down"][at[0]], precision, limit), None
        return jax.lax.scan(one, jnp.zeros_like(block), which)[0]
    return G._in_blocks(rows, h.shape[0], h, choice, gates)


def feed_forward(cfg, ffn, p, h, precision, held=None, with_shared=True):
    """``F(h)``: the dense clamped gated product, or the shared expert
    (left out by ``with_shared=False``: the tests count it once over
    several shares) and the held routed experts under the router's
    gates."""
    limit = cfg.get("swiglu_limit") or 0.0
    if ffn == "dense":
        return G._in_blocks(lambda b: gated(b, p["gate_proj"], p["up_proj"],
                                            p["down_proj"], precision,
                                            limit), h.shape[0], h)
    choice, gates = G.route(cfg, p, h, precision)
    out = experts(cfg, p, h, choice, gates, precision, held)
    if not with_shared:
        return out
    return out + G._in_blocks(lambda b: gated(
        b, p["shared_gate"], p["shared_up"], p["shared_down"], precision,
        limit), h.shape[0], h)


def layer(cfg, mixer, ffn, p, x, precision, held=None, iters=None,
          delta=True):
    """Both hyper-connected sublayers of one layer over the streams ``x
    [s, n, d]``."""
    eps = cfg["rms_norm_eps"]

    def mix(h):
        u = rms_norm(p["norm1"], h, eps)
        return kda(cfg, p, u, precision, delta) if mixer == "kda" \
            else attention(cfg, p, u, precision)
    x = hyper(cfg, p, "attn", x, mix, iters)
    return hyper(cfg, p, "ffn", x, lambda h: feed_forward(
        cfg, ffn, p, rms_norm(p["norm2"], h, eps), precision, held), iters)


@functools.lru_cache(maxsize=None)
def _layer_fn(key, mixer, ffn, precision, iters=None, delta=True):
    cfg = _cfg(key)
    return jax.jit(lambda p, x: layer(cfg, mixer, ffn, p, x, precision,
                                      iters=iters, delta=delta))


@functools.lru_cache(maxsize=None)
def _embed_fn(key):
    n = _cfg(key)["hc_mult"]
    return jax.jit(lambda table, tokens: jnp.repeat(
        table[tokens].astype(jnp.float32)[:, None], n, axis=1))


@functools.lru_cache(maxsize=None)
def _head_fn(key, precision, rows):
    """Logits of ``rows`` positions from ``start`` on, the streams summed."""
    cfg = _cfg(key)

    def head(norm_f, table, x, start):
        x = jax.lax.dynamic_slice_in_dim(jnp.sum(x, axis=1), start, rows)
        x = rms_norm(norm_f, x, cfg["rms_norm_eps"])
        return C.einsum("sd,vd->sv", x, table, precision)
    return jax.jit(head)


def hidden(cfg, params, row, precision="highest", iters=None, delta=True):
    """The streams ``[s, n, d]`` after the last layer, for one row of
    tokens ``[s]``, a layer at a time (``iters`` and ``delta``: the tests'
    planted faults)."""
    key = _key(cfg)
    x = _embed_fn(key)(params["embed"], jnp.asarray(row))
    for (mixer, ffn), p in zip(kinds(cfg), params["layers"]):
        x = _layer_fn(key, mixer, ffn, precision, iters, delta)(p, x)
    return x


def logits(cfg, params, tokens, precision="highest", iters=None,
           delta=True):
    """Next-token logits ``[b, s, vocab]`` of ``tokens [b, s]``: the whole
    forward, for the tests' small sizes."""
    key = _key(cfg)
    tokens = np.asarray(tokens)
    head = _head_fn(key, precision, tokens.shape[1])
    return jnp.stack([head(params["norm_f"], params["head"],
                           hidden(cfg, params, row, precision, iters, delta),
                           0) for row in tokens])


def _served_logits(cfg, params, row, precision):
    length, first, count = G._served(cfg, row)
    x = hidden(cfg, params, row[:length], precision)
    out = _head_fn(_key(cfg), precision, count)(
        params["norm_f"], params["head"], x, first)
    return out, first, count


def token_gaps(cfg, params, tokens, chosen):
    """At each served position of ``tokens [b, s]``: how far the
    reference's logit of ``chosen [b, s]`` lies below the reference's best
    logit there; nought at the positions before and after."""
    tokens, chosen = np.asarray(tokens), np.asarray(chosen)
    gaps = np.zeros(tokens.shape, np.float32)
    for i, row in enumerate(tokens):
        out, first, count = _served_logits(cfg, params, row, "highest")
        picked = jnp.take_along_axis(
            out, jnp.asarray(chosen[i, first:first + count])[:, None],
            axis=-1)[:, 0]
        gaps[i, first:first + count] = np.asarray(
            jnp.max(out, axis=-1) - picked)
    return gaps


def block_means(gaps, compared, block=GAP_BLOCK):
    """``gaps [s]`` with its ``compared [s]`` positions taken in order in
    runs of ``block``, each replaced by its run's mean (the last run takes
    the remainder, so that a run holds ``block`` or more unless there are
    fewer); the rest as they are."""
    out = np.array(gaps, np.float32)
    at = np.flatnonzero(compared)
    runs = max(1, len(at) // block)
    for j in range(runs):
        run = at[j * block:(j + 1) * block if j < runs - 1 else len(at)]
        if len(run):
            out[run] = np.mean(out[run])
    return out


def gaps_below_best(cfg, params, tokens, chosen):
    """At each served position of ``tokens [b, s]``, how far the
    reference's logit of ``chosen [b, s]`` lies below the reference's best
    logit (:func:`token_gaps`), as the mean over its run of ``GAP_BLOCK``
    positions (:func:`block_means`); nought before and after. A nought in
    ``chosen`` marks a position that was not served (the runner's padding):
    its gap stays its own and takes no part in a mean, so a program that
    serves noughts is held to every single gap.

    A mean over runs, not the widest single gap: a near-tie among the 288
    router scores that a bfloat16 rounding turns swaps a held expert at one
    token and moves that token's logits by up to a logit; such turns set
    the widest single gaps of a sound program and of a float8 one alike,
    while the means over a run differ by a multiple."""
    tokens, chosen = np.asarray(tokens), np.asarray(chosen)
    gaps = token_gaps(cfg, params, tokens, chosen)
    for i, row in enumerate(tokens):
        _, first, count = G._served(cfg, row)
        compared = np.zeros(row.shape, bool)
        compared[first:first + count] = chosen[i, first:first + count] != 0
        gaps[i] = block_means(gaps[i], compared)
    return gaps


def first_choice(cfg, params, tokens, precision):
    """The token that a forward pass at ``precision`` puts first at each
    served position (the control reads this at ``"fp8"``); nought at the
    positions before and after."""
    tokens = np.asarray(tokens)
    first_of = np.zeros(tokens.shape, np.int32)
    for i, row in enumerate(tokens):
        out, first, count = _served_logits(cfg, params, row, precision)
        first_of[i, first:first + count] = np.asarray(
            jnp.argmax(out, axis=-1))
    return first_of
