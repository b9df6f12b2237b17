"""Plain reference of GLM-5's decoder (``configs/glm_5.json`` states the
source, the equations and what is assumed): RMS norms, no biases but the
indexer's key norm and the router's correction bias, an untied head. Every
layer: multi-head **latent** attention in its plain form (low-rank query and
key/value projections with their norms, interleaved rotary on ``qk_rope``
of the ``qk_head_dim`` numbers of a head, keys and values of every head made
from the latent ``c_kv``; no absorption) over the positions that a learned
**indexer** selects for each query (``index_topk`` single positions of
largest ``sum_j w_j relu(q_j . k)`` among ``s <= t``, by ``lax.top_k`` on
the masked scores, ties to the lower position); then a gated-SiLU
feed-forward (the leading dense layers) or a sigmoid-scored router on the
normed output of attention with a correction bias for the choice alone, the
chosen gates divided by their sum and scaled, gated-SiLU experts and a
shared expert. Float32 products at ``highest``; no cache, no chunks, no
sorting by expert, no kernels: the index scores are a dense product in
blocks of rows, attention a masked dense softmax in blocks of rows and
groups of heads with the mask "``s`` in ``S_t``", the experts a plain loop
over those held with the gate nought where an expert was not chosen.
Nothing of the program is imported.

The chip holds a share of the routed experts (``held_experts``) and a slice
of the vocabulary; what the absent experts would add is left out here as in
the program. ``held`` computes another share, for the tests.

Weights come from the seed here, bfloat16 values a layer at a time, and are
handed to the program. At the published widths a forward pass runs layer by
layer, one expert's weights upcast at a time, and logits only where tokens
were served: the last ``max_new_tokens`` positions of each row."""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C

ROWS = 128          # query rows of the indexer and of attention at a time
HEADS = 16          # heads of attention computed at a time
FFN_ROWS = 2048     # rows of a feed-forward computed at a time
BUCKET = 2048       # a row is computed at its length rounded up to this

#: the keys that decide what a layer computes: the key of each compiled piece
USED = ("vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_attention_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "index_n_heads", "index_head_dim", "index_topk", "n_routed_experts",
        "n_routed_experts_published", "held_experts", "n_shared_experts",
        "num_experts_per_tok", "routed_scaling_factor", "scoring_func",
        "rms_norm_eps", "rope_parameters", "initializer_range",
        "param_dtype", "router_bias_spread", "index_norm_eps")


def _key(cfg):
    return json.dumps({k: cfg.get(k) for k in USED}, sort_keys=True)


def _cfg(key):
    return json.loads(key)


def kinds(cfg):
    """``"dense"`` or ``"moe"`` a layer: the leading dense layers, then the
    expert layers."""
    dense = cfg["first_k_dense_replace"]
    return ["dense"] * dense + ["moe"] * (cfg["num_hidden_layers"] - dense)


def routed(cfg):
    """The router's width: the published number of routed experts."""
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def table_of(cfg):
    """The expert numbers whose tables the weights hold, in the tables'
    order."""
    return list(cfg.get("held_experts") or range(routed(cfg)))


def layer_shapes(cfg, kind):
    """``(matrices, vectors that start at 1, vectors that start at 0)``."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    mats = {"q_a": (d, qr), "q_b": (qr, h * (nope + rope)),
            "kv_a": (d, kr + rope), "kv_b": (kr, h * (nope + v)),
            "o": (h * v, d), "index_q": (qr, ih * idim),
            "index_k": (d, idim), "index_w": (d, ih)}
    ones = {"norm1": (d,), "norm2": (d,), "q_a_norm": (qr,),
            "kv_a_norm": (kr,), "index_k_norm": (idim,)}
    zeros = {"index_k_bias": (idim,)}
    if kind == "moe":
        e, f = len(table_of(cfg)), cfg["moe_intermediate_size"]
        s = cfg["n_shared_experts"] * f
        mats.update(router=(d, routed(cfg)), w_gate=(e, d, f),
                    w_up=(e, d, f), w_down=(e, f, d), shared_gate=(d, s),
                    shared_up=(d, s), shared_down=(s, d))
        zeros["router_bias"] = (routed(cfg),)
    else:
        f = cfg["intermediate_size"]
        mats.update(gate_proj=(d, f), up_proj=(d, f), down_proj=(f, d))
    return mats, ones, zeros


@functools.lru_cache(maxsize=None)
def _layer_maker(key, kind):
    cfg = _cfg(key)
    mats, ones, zeros = layer_shapes(cfg, kind)
    dtype = jnp.dtype(cfg["param_dtype"])
    std = cfg["initializer_range"]

    def make(rng):
        keys = jax.random.split(rng, len(mats) + 1)
        out = {name: (jax.random.normal(k, shape) * std).astype(dtype)
               for k, (name, shape) in zip(keys, sorted(mats.items()))}
        out.update({n: jnp.ones(s, jnp.float32) for n, s in ones.items()})
        out.update({n: jnp.zeros(s, jnp.float32) for n, s in zeros.items()})
        if "router_bias" in zeros:  # ``assumed.router_bias_spread``
            out["router_bias"] = jax.random.normal(
                keys[-1], zeros["router_bias"]) * float(
                    cfg.get("router_bias_spread") or 0.0)
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
    """Every weight from ``seed``, one jitted call a layer: ``embed`` and
    ``head`` ``[vocab, hidden]`` (untied), ``norm_f``, and ``layers``, a
    list of one dict a layer (the program's own layout, so the weights are
    handed over as they are)."""
    key = _key(cfg)
    root = jax.random.PRNGKey(seed % (2 ** 31))
    layers = [_layer_maker(key, kind)(jax.random.fold_in(root, i))
              for i, kind in enumerate(kinds(cfg))]
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
    """Rotary positions on the pairs ``(2i, 2i+1)`` of the last axis
    (``rope_interleave``): ``x [s, ..., d]``, ``positions [s]``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * freq
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                      b * jnp.cos(angle) + a * jnp.sin(angle)],
                     axis=-1).reshape(x.shape)


def _rows_of(a, first):
    return jax.lax.dynamic_slice_in_dim(a, first, ROWS)


def _padded(a):
    return jnp.pad(a, ((0, ROWS),) + ((0, 0),) * (a.ndim - 1))


def indexer(cfg, p, u, c_q, precision):
    """The indexer's queries ``[s, IH, ID]``, keys ``[s, ID]`` (a layer
    norm with weight and bias) and head weights ``[s, IH]``; rotary on the
    first ``qk_rope_head_dim`` numbers of queries and keys."""
    s, ih, idim = u.shape[0], cfg["index_n_heads"], cfg["index_head_dim"]
    r, theta = cfg["qk_rope_head_dim"], cfg["rope_parameters"]["rope_theta"]
    at = jnp.arange(s)
    q = C.einsum("sd,dn->sn", c_q, p["index_q"], precision).reshape(
        s, ih, idim)
    k = C.einsum("sd,dn->sn", u, p["index_k"], precision)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(
        jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        + float(cfg.get("index_norm_eps") or 1e-6)) \
        * p["index_k_norm"] + p["index_k_bias"]
    q = jnp.concatenate([rotary(q[..., :r], at, theta), q[..., r:]], axis=-1)
    k = jnp.concatenate([rotary(k[..., :r], at, theta), k[..., r:]], axis=-1)
    w = C.einsum("sd,dn->sn", u, p["index_w"], precision) \
        / math.sqrt(ih) / math.sqrt(idim)
    return q, k, w


def index_scores(q_rows, w_rows, k, first, precision):
    """``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`` for the rows ``t =
    first .. first + ROWS - 1``, ``-inf`` where ``s > t``: ``[ROWS, s]``."""
    dots = C.einsum("rhd,sd->rhs", q_rows, k, precision)
    scores = jnp.einsum("rhs,rh->rs", jax.nn.relu(dots), w_rows,
                        precision=jax.lax.Precision.HIGHEST)
    t = first + jnp.arange(ROWS)
    return jnp.where(jnp.arange(k.shape[0])[None] <= t[:, None], scores,
                     -jnp.inf)


def kth_largest(scores, topk):
    """Of rows of masked index ``scores [r, s]``, by ``lax.top_k`` (ties to
    the lower position): the ``topk``-th largest score and the last
    position that ``lax.top_k`` took at that score, ``[r, 1]`` each; the
    lowest score the row sees, ``-inf``, while it sees fewer."""
    top, at = jax.lax.top_k(scores, min(topk, scores.shape[1]))
    kth = top[:, -1:]
    return kth, jnp.max(jnp.where(top == kth, at, -1), axis=-1,
                        keepdims=True)


def selected(scores, kth, last):
    """The mask ``s in S_t``: everything above the ``topk``-th largest
    score and the ties with it up to the last position ``lax.top_k`` took,
    which is the set it returned; all the row sees while it sees fewer."""
    pos = jnp.arange(scores.shape[1])[None]
    return ((scores > kth) | ((scores == kth) & (pos <= last))) \
        & (scores > -jnp.inf)


def attention(cfg, p, u, precision):
    """``W_o concat_h softmax_{s in S_t}((q_nope . k_nope + q_rope . k_rope)
    / sqrt(qk_head_dim)) v`` over ``u [s, d]``, the plain form."""
    s, n = u.shape[0], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    theta = cfg["rope_parameters"]["rope_theta"]
    at = jnp.arange(s)
    c_q = rms_norm(p["q_a_norm"],
                   C.einsum("sd,dn->sn", u, p["q_a"], precision), eps)
    kv = C.einsum("sd,dn->sn", u, p["kv_a"], precision)
    c_kv = rms_norm(p["kv_a_norm"], kv[:, :kr], eps)
    k_rope = rotary(kv[:, kr:], at, theta)                      # [s, rope]
    q_i, k_i, w_i = indexer(cfg, p, u, c_q, precision)
    q_i, w_i = _padded(q_i), _padded(w_i)
    c_q = _padded(c_q)
    at_rows = jnp.pad(at, (0, ROWS))

    def scores_of(first):
        return index_scores(_rows_of(q_i, first), _rows_of(w_i, first), k_i,
                            first, precision)
    # the selection of every row once; a block of rows then has its mask
    # from the same scores and these two numbers a row
    kth, last = jax.lax.map(
        lambda first: kth_largest(scores_of(first), cfg["index_topk"]),
        jnp.arange(0, s, ROWS))
    kth, last = _padded(kth.reshape(-1, 1)), _padded(last.reshape(-1, 1))

    def mask_of(first):
        return selected(scores_of(first), _rows_of(kth, first),
                        _rows_of(last, first))

    groups = min(HEADS, n)
    q_b = p["q_b"].reshape(-1, n // groups, groups * (nope + rope))
    kv_b = p["kv_b"].reshape(kr, n // groups, groups * (nope + vd))

    def heads(g):
        k = C.einsum("sc,cn->sn", c_kv, kv_b[:, g], precision).reshape(
            s, groups, nope + vd)

        def rows(first):
            q = C.einsum("rc,cn->rn", _rows_of(c_q, first), q_b[:, g],
                         precision).reshape(ROWS, groups, nope + rope)
            q_r = rotary(q[..., nope:], _rows_of(at_rows, first), theta)
            scores = (C.einsum("rhd,shd->rhs", q[..., :nope], k[..., :nope],
                               precision)
                      + C.einsum("rhd,sd->rhs", q_r, k_rope, precision)) \
                / math.sqrt(nope + rope)
            seen = mask_of(first)[:, None]
            # a padding row sees nothing: keep its softmax finite
            scores = jnp.where(seen, scores, -1e30)
            probs = jnp.where(seen, jax.nn.softmax(scores, axis=-1), 0.0)
            return C.einsum("rhs,shd->rhd", probs, k[..., nope:], precision)
        return jax.lax.map(rows, jnp.arange(0, s, ROWS)).reshape(
            -1, groups * vd)[:s]
    out = jax.lax.map(heads, jnp.arange(n // groups))   # [n/g, s, g*vd]
    out = jnp.moveaxis(out, 0, 1).reshape(s, n * vd)
    return C.einsum("sn,nd->sd", out, p["o"], precision)


def gated(h, w_gate, w_up, w_down, precision):
    """``W_down (silu(W_gate h) * W_up h)``."""
    mid = jax.nn.silu(C.einsum("sd,df->sf", h, w_gate, precision)) \
        * C.einsum("sd,df->sf", h, w_up, precision)
    return C.einsum("sf,fd->sd", mid, w_down, precision)


def _in_blocks(fn, s, *arrays):
    """``fn`` over ``FFN_ROWS`` rows of every array at a time."""
    if s <= FFN_ROWS:
        return fn(*arrays)
    pad = -s % FFN_ROWS

    def blocks(a):
        return jnp.pad(a, ((0, pad), (0, 0))).reshape(
            -1, FFN_ROWS, a.shape[1])
    out = jax.lax.map(lambda a: fn(*a), tuple(blocks(a) for a in arrays))
    return out.reshape(-1, out.shape[-1])[:s]


def route(cfg, p, h, precision):
    """``(choice [s, k], gates [s, k])``: sigmoid scores, the ``k`` largest
    of score + correction bias chosen (ties to the lower index), the chosen
    scores divided by their sum and scaled."""
    scores = jax.nn.sigmoid(C.einsum("sd,de->se", h, p["router"], precision))
    _, choice = jax.lax.top_k(scores + p["router_bias"],
                              cfg["num_experts_per_tok"])
    kept = jnp.take_along_axis(scores, choice, axis=-1)
    return choice, cfg["routed_scaling_factor"] * kept / jnp.sum(
        kept, axis=-1, keepdims=True)


def experts(cfg, p, h, choice, gates, precision, held=None):
    """``sum_e g_e E_e(h)`` over the experts held (``held``: expert
    numbers, all those the weights hold where ``None``): every one in turn
    over all rows, its gate nought where it was not chosen."""
    table = table_of(cfg)
    which = jnp.asarray([(table.index(e), e)
                         for e in (table if held is None else held)])

    def rows(block, pick, gate):
        def one(total, at):
            g = jnp.sum(jnp.where(pick == at[1], gate, 0.0), axis=-1)
            return total + g[:, None] * gated(
                block, p["w_gate"][at[0]], p["w_up"][at[0]],
                p["w_down"][at[0]], precision), None
        return jax.lax.scan(one, jnp.zeros_like(block), which)[0]
    return _in_blocks(rows, h.shape[0], h, choice, gates)


def shared(p, h, precision):
    return _in_blocks(lambda b: gated(b, p["shared_gate"], p["shared_up"],
                                      p["shared_down"], precision),
                      h.shape[0], h)


def feed_forward(cfg, kind, p, h, precision, held=None, with_shared=True):
    """``F_l(h)``: the dense gated product, or the shared expert (left out
    by ``with_shared=False``: the tests count it once over several shares)
    and the held routed experts under the router's gates."""
    if kind == "dense":
        return _in_blocks(lambda b: gated(b, p["gate_proj"], p["up_proj"],
                                          p["down_proj"], precision),
                          h.shape[0], h)
    choice, gates = route(cfg, p, h, precision)
    out = experts(cfg, p, h, choice, gates, precision, held)
    return out + shared(p, h, precision) if with_shared else out


def layer(cfg, kind, p, x, precision, held=None):
    """``a = x + W_o Attn(N1(x))``; ``x' = a + F(N2(a))`` over ``[s, d]``."""
    eps = cfg["rms_norm_eps"]
    a = x + attention(cfg, p, rms_norm(p["norm1"], x, eps), precision)
    return a + feed_forward(cfg, kind, p, rms_norm(p["norm2"], a, eps),
                            precision, held)


@functools.lru_cache(maxsize=None)
def _layer_fn(key, kind, precision):
    cfg = _cfg(key)
    return jax.jit(lambda p, x: layer(cfg, kind, p, x, precision))


@functools.lru_cache(maxsize=None)
def _embed_fn(key):
    return jax.jit(lambda table, tokens: table[tokens].astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _head_fn(key, precision, rows):
    """Logits of ``rows`` positions from ``start`` on."""
    cfg = _cfg(key)

    def head(norm_f, table, x, start):
        x = jax.lax.dynamic_slice_in_dim(x, start, rows)
        x = rms_norm(norm_f, x, cfg["rms_norm_eps"])
        return C.einsum("sd,vd->sv", x, table, precision)
    return jax.jit(head)


def hidden(cfg, params, row, precision="highest"):
    """The residual stream ``[s, d]`` after the last layer, for one row of
    tokens ``[s]``, a layer at a time."""
    key = _key(cfg)
    x = _embed_fn(key)(params["embed"], jnp.asarray(row))
    for kind, p in zip(kinds(cfg), params["layers"]):
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
