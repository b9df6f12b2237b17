"""Plain reference of MiniCPM-SALA's decoder (``configs/minicpm_sala.json``
states the source, the equations and what is assumed): RMS norms, MiniCPM's
embedding, residual and logit scalings, a gated SiLU feed-forward, an untied
head, and a mixer chosen by layer: ``lightning-attn`` (linear attention with
a decay a head, the recurrence as a plain scan over positions) or
``minicpm4`` (InfLLM-V2 block-sparse attention: dense up to ``dense_len``,
beyond it a selection of blocks a query and a masked dense softmax over the
selected blocks). Float32 products at ``highest``; no cache, no chunks, no
batching of requests, no kernels; nothing of the program is imported.

Weights come from the seed here, bfloat16 values a layer at a time, and are
handed to the program. At the published widths a forward pass runs layer by
layer with one layer's weights upcast at a time, the feed-forward and the
sparse layer in blocks of rows, and logits only where tokens were served:
the last ``max_new_tokens`` positions of each row."""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C

ROWS = 128          # query rows of a sparse layer computed at a time
FFN_ROWS = 2048     # rows of the feed-forward computed at a time
BUCKET = 2048       # a row is computed at its length rounded up to this


#: the keys that decide what a layer computes: the key of each compiled piece
USED = ("vocab_size", "hidden_size", "intermediate_size", "head_dim",
        "num_attention_heads", "num_key_value_heads", "lightning_nh",
        "lightning_nkv", "lightning_head_dim", "rms_norm_eps", "rope_theta",
        "scale_emb", "scale_depth", "depth_scale_layers", "dim_model_base",
        "initializer_range", "param_dtype", "sparse_attention")


def _key(cfg):
    return json.dumps({k: cfg[k] for k in USED}, sort_keys=True)


def _cfg(key):
    return json.loads(key)


def slopes(cfg):
    """``s_h`` of ``lambda_h = exp(-s_h)``: ``2^(-8 (h+1) / heads)``."""
    n = cfg["lightning_nh"]
    return jnp.asarray([2.0 ** (-8.0 * (h + 1) / n) for h in range(n)],
                       jnp.float32)


def layer_shapes(cfg, kind):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    if kind == "lightning-attn":
        h = cfg["lightning_nh"] * cfg["lightning_head_dim"]
        kv = cfg["lightning_nkv"] * cfg["lightning_head_dim"]
        hd = cfg["lightning_head_dim"]
    else:
        h = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        hd = cfg["head_dim"]
    mats = {"q": (d, h), "k": (d, kv), "v": (d, kv), "o": (h, d),
            "g": (d, h), "gate_proj": (d, f), "up_proj": (d, f),
            "down_proj": (f, d)}
    ones = {"norm1": (d,), "norm2": (d,), "q_norm": (hd,), "k_norm": (hd,)}
    if kind == "lightning-attn":
        ones["o_norm"] = (h,)
    return mats, ones


@functools.lru_cache(maxsize=None)
def _layer_maker(key, kind):
    cfg = _cfg(key)
    mats, ones = layer_shapes(cfg, kind)
    std, dtype = cfg["initializer_range"], jnp.dtype(cfg["param_dtype"])

    def make(rng):
        keys = jax.random.split(rng, len(mats))
        out = {name: (jax.random.normal(k, shape) * std).astype(dtype)
               for k, (name, shape) in zip(keys, sorted(mats.items()))}
        out.update({name: jnp.ones(shape, jnp.float32)
                    for name, shape in ones.items()})
        return out
    return jax.jit(make)


@functools.lru_cache(maxsize=None)
def _table_maker(key):
    cfg = _cfg(key)
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return jax.jit(lambda rng: (jax.random.normal(rng, shape)
                                * cfg["initializer_range"]).astype(
                                    jnp.dtype(cfg["param_dtype"])))


def init_weights(cfg, seed):
    """Every weight from ``seed``, one jitted call a layer (all of a layer's
    float32 draws at once would not fit beside the rest): ``embed``
    ``[vocab, hidden]``, ``head`` ``[vocab, hidden]`` (untied), ``norm_f``,
    and ``layers``, a list of one dict a layer."""
    key = _key(cfg)
    root = jax.random.PRNGKey(seed % (2 ** 31))
    layers = [_layer_maker(key, kind)(jax.random.fold_in(root, i))
              for i, kind in enumerate(cfg["mixer_types"])]
    return {"embed": _table_maker(key)(jax.random.fold_in(root, 1000)),
            "head": _table_maker(key)(jax.random.fold_in(root, 1001)),
            "norm_f": jnp.ones((cfg["hidden_size"],), jnp.float32),
            "layers": layers}


# -- the pieces --------------------------------------------------------------

def rms_norm(weight, x, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * weight


def rotary(x, positions, theta):
    """The rotate-half form over the whole head: ``x [s, h, d]``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def _heads(x, n):
    return x.reshape(x.shape[0], n, -1)


def lightning_mixer(cfg, p, u, precision):
    """``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt d) S_t``,
    one position at a time."""
    n, eps = cfg["lightning_nh"], cfg["rms_norm_eps"]
    s = u.shape[0]
    at = jnp.arange(s)
    q = rms_norm(p["q_norm"], _heads(C.einsum("sd,dn->sn", u, p["q"],
                                              precision), n), eps)
    k = rms_norm(p["k_norm"], _heads(C.einsum("sd,dn->sn", u, p["k"],
                                              precision), n), eps)
    v = _heads(C.einsum("sd,dn->sn", u, p["v"], precision), n)
    q = rotary(q, at, cfg["rope_theta"]) / math.sqrt(q.shape[-1])
    k = rotary(k, at, cfg["rope_theta"])
    decay = jnp.exp(-slopes(cfg))[:, None, None]

    def step(state, qkv):
        qt, kt, vt = qkv
        state = decay * state + C.einsum("hk,hv->hkv", kt, vt, precision)
        return state, C.einsum("hk,hkv->hv", qt, state, precision)
    zero = jnp.zeros((n, q.shape[-1], v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, zero, (q, k, v))
    o = rms_norm(p["o_norm"], o.reshape(s, -1), eps)
    gate = jax.nn.sigmoid(C.einsum("sd,dn->sn", u, p["g"], precision))
    return C.einsum("sn,nd->sd", o * gate, p["o"], precision)


def block_selection(cfg, scores, t, n_blocks):
    """The selection of ``configs/minicpm_sala.json`` ``assumed``: from
    ``scores [rows, heads, windows]`` (``q . Kc / sqrt d``) of the queries at
    positions ``t [rows]`` to a mask ``[rows, kv heads, n_blocks]``."""
    sp = cfg["sparse_attention"]
    size, stride, block = sp["kernel_size"], sp["kernel_stride"], \
        sp["block_size"]
    rows, heads, windows = scores.shape
    groups = cfg["num_key_value_heads"]
    per = block // stride                       # windows that start in a block
    seen = (jnp.arange(windows)[None] * stride + size) <= t[:, None]
    masked = jnp.where(seen[:, None], scores, -jnp.inf)
    top = jnp.max(masked, axis=-1, keepdims=True)
    e = jnp.where(seen[:, None], jnp.exp(masked - jnp.where(
        jnp.isfinite(top), top, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    p = p.reshape(rows, groups, heads // groups, windows).sum(axis=2)
    p = jnp.where(seen[:, None], p, -1.0)
    # block b scores the largest p among windows per*b - 1 .. per*b + per - 1
    pad = jnp.pad(p, ((0, 0), (0, 0), (1, per * n_blocks + per)),
                  constant_values=-1.0)
    score = jnp.max(jnp.stack(
        [pad[..., i:i + per * n_blocks:per] for i in range(per + 1)]), axis=0)
    b = jnp.arange(n_blocks)
    own = t // block
    local = sp["window_size"] // block
    forced = (b[None] < sp["init_blocks"]) | (
        (b[None] >= (own - local)[:, None]) & (b[None] <= own[:, None]))
    others = ~forced & (b[None] < own[:, None])
    ranked = jnp.where(others[:, None], score, -jnp.inf)
    values, index = jax.lax.top_k(ranked, min(sp["topk"], n_blocks))
    chosen = jnp.zeros(ranked.shape, bool)
    chosen = jnp.any(jax.nn.one_hot(index, n_blocks, dtype=bool)
                     & jnp.isfinite(values)[..., None], axis=-2) | chosen
    dense = (t < sp["dense_len"])[:, None, None]
    return dense | forced[:, None] | chosen


def sparse_mixer(cfg, p, u, precision):
    n, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sp, eps = cfg["sparse_attention"], cfg["rms_norm_eps"]
    size, stride, block = sp["kernel_size"], sp["kernel_stride"], \
        sp["block_size"]
    s = u.shape[0]
    q = rms_norm(p["q_norm"], _heads(C.einsum("sd,dn->sn", u, p["q"],
                                              precision), n), eps)
    k = rms_norm(p["k_norm"], _heads(C.einsum("sd,dn->sn", u, p["k"],
                                              precision), groups), eps)
    v = _heads(C.einsum("sd,dn->sn", u, p["v"], precision), groups)
    d = q.shape[-1]
    q = q / math.sqrt(d)
    windows = max((s - size) // stride + 1, 1)
    start = jnp.arange(windows) * stride
    kc = jnp.mean(jnp.pad(k, ((0, max(size - s, 0)), (0, 0), (0, 0)))[
        start[:, None] + jnp.arange(size)[None]], axis=1)  # [w, groups, d]
    n_blocks = -(-s // block)
    key_pos = jnp.arange(s)
    grouped = jnp.pad(q, ((0, ROWS), (0, 0), (0, 0))).reshape(
        s + ROWS, groups, n // groups, d)

    def rows(at):
        """The output of the ``ROWS`` queries from position ``at`` on."""
        t = at + jnp.arange(ROWS)
        qr = jax.lax.dynamic_slice_in_dim(grouped, at, ROWS)
        scores_c = C.einsum("rghd,wgd->rghw", qr, kc, precision)
        picked = block_selection(cfg, scores_c.reshape(ROWS, n, windows), t,
                                 n_blocks)
        allowed = jnp.repeat(picked, block, axis=-1)[..., :s] \
            & (key_pos[None, None] <= t[:, None, None])
        scores = C.einsum("rghd,sgd->rghs", qr, k, precision)
        scores = jnp.where(allowed[:, :, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return C.einsum("rghs,sgd->rghd", probs, v, precision)
    out = jax.lax.map(rows, jnp.arange(0, s, ROWS))
    o = out.reshape(-1, n * d)[:s]
    gate = jax.nn.sigmoid(C.einsum("sd,dn->sn", u, p["g"], precision))
    return C.einsum("sn,nd->sd", o * gate, p["o"], precision)


def feed_forward(cfg, p, h, precision):
    def rows(block):
        gate = C.einsum("sd,df->sf", block, p["gate_proj"], precision)
        up = C.einsum("sd,df->sf", block, p["up_proj"], precision)
        return C.einsum("sf,fd->sd", jax.nn.silu(gate) * up, p["down_proj"],
                        precision)
    s = h.shape[0]
    if s <= FFN_ROWS:
        return rows(h)
    pad = -s % FFN_ROWS
    blocks = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, FFN_ROWS, h.shape[1])
    return jax.lax.map(rows, blocks).reshape(-1, h.shape[1])[:s]


def layer(cfg, kind, p, x, precision):
    """``x <- x + c Mixer(N(x))``, ``x <- x + c FFN(N(x))`` over ``[s, d]``."""
    c = cfg["scale_depth"] / math.sqrt(cfg["depth_scale_layers"])
    eps = cfg["rms_norm_eps"]
    mixer = lightning_mixer if kind == "lightning-attn" else sparse_mixer
    x = x + c * mixer(cfg, p, rms_norm(p["norm1"], x, eps), precision)
    return x + c * feed_forward(cfg, p, rms_norm(p["norm2"], x, eps),
                                precision)


@functools.lru_cache(maxsize=None)
def _layer_fn(key, kind, precision):
    cfg = _cfg(key)
    return jax.jit(lambda p, x: layer(cfg, kind, p, x, precision))


@functools.lru_cache(maxsize=None)
def _embed_fn(key):
    cfg = _cfg(key)
    return jax.jit(lambda table, tokens: cfg["scale_emb"]
                   * table[tokens].astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _head_fn(key, precision, rows):
    """Logits of ``rows`` positions from ``start`` on."""
    cfg = _cfg(key)
    scale = cfg["hidden_size"] / cfg["dim_model_base"]

    def head(norm_f, table, x, start):
        x = jax.lax.dynamic_slice_in_dim(x, start, rows)
        x = rms_norm(norm_f, x, cfg["rms_norm_eps"]) / scale
        return C.einsum("sd,vd->sv", x, table, precision)
    return jax.jit(head)


def hidden(cfg, params, row, precision="highest"):
    """The residual stream ``[s, d]`` after the last layer, for one row of
    tokens ``[s]``: a layer at a time, each layer's weights upcast alone."""
    key = _key(cfg)
    x = _embed_fn(key)(params["embed"], jnp.asarray(row))
    for kind, p in zip(cfg["mixer_types"], params["layers"]):
        x = _layer_fn(key, kind, precision)(p, x)
    return x


def logits(cfg, params, tokens, precision="highest"):
    """Next-token logits ``[b, s, vocab]`` of ``tokens [b, s]``: the whole
    forward, for the tests' small sizes."""
    key = _key(cfg)
    tokens = np.asarray(tokens)
    head = _head_fn(key, precision, tokens.shape[1])
    return jnp.stack([head(params["norm_f"], params["head"],
                           hidden(cfg, params, row, precision), 0)
                      for row in tokens])


def _served(cfg, row):
    """``(length computed, first position, positions)`` of the stretch of
    ``row`` that holds every served position: a request's tokens end the
    row before its padding of zeros, and at most ``max_new_tokens`` of them
    were served."""
    width = len(row)
    used = int(np.max(np.nonzero(row)[0])) + 1 if np.any(row) else 1
    most = int(cfg["serving"]["max_new_tokens"])
    count = min(width, most + 16)
    first = min(max(used - 1 - most, 0), width - count)
    length = min(width, BUCKET * -(-min(width, first + count) // BUCKET))
    return length, first, count


def _served_logits(cfg, params, row, precision):
    length, first, count = _served(cfg, row)
    x = hidden(cfg, params, row[:length], precision)
    out = _head_fn(_key(cfg), precision, count)(
        params["norm_f"], params["head"], x, first)
    return out, first, count


def gaps_below_best(cfg, params, tokens, chosen):
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
