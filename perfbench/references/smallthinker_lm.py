"""Plain reference of SmallThinker's decoder (``configs/smallthinker_21b.json``
states the source, the equations and what is assumed): RMS norms, no biases,
an untied head; in every layer a softmax router on the layer's input as it
is (before the norm and before attention), grouped-query causal softmax
attention (a **full** layer: no positions, every key up to the query's; a
**window** layer: rotary positions, the last ``sliding_window_size``
positions, the query's own among them), and ReGLU experts, the chosen few of
each token weighted by their gates. Float32 products at ``highest``; no
cache, no chunks, no sorting, no kernels: attention is a masked dense
softmax in blocks of rows, the experts a plain loop over all of them with
the gate nought where an expert was not chosen. Nothing of the program is
imported.

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

ROWS = 128          # query rows of attention computed at a time
FFN_ROWS = 2048     # rows of the experts computed at a time
BUCKET = 2048       # a row is computed at its length rounded up to this

#: the keys that decide what a layer computes: the key of each compiled piece
USED = ("vocab_size", "hidden_size", "moe_ffn_hidden_size", "head_dim",
        "num_attention_heads", "num_key_value_heads",
        "moe_num_primary_experts", "moe_num_active_primary_experts",
        "sliding_window_size", "rms_norm_eps", "rope_theta",
        "initializer_range", "param_dtype", "router_logit_spread")


def _key(cfg):
    return json.dumps({k: cfg.get(k) for k in USED}, sort_keys=True)


def _cfg(key):
    return json.loads(key)


def kinds(cfg):
    """``"window"`` or ``"full"`` a layer, from ``sliding_window_layout``."""
    return ["window" if w else "full" for w in cfg["sliding_window_layout"]]


def layer_shapes(cfg):
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    e = cfg["moe_num_primary_experts"]
    h = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"q": (d, h), "k": (d, kv), "v": (d, kv), "o": (h, d),
            "router": (d, e), "w_gate": (e, d, f), "w_up": (e, d, f),
            "w_down": (e, f, d)}


def router_std(cfg, index):
    """The spread of layer ``index``'s router rows: ``initializer_range``,
    or with ``router_logit_spread`` in the configuration (its ``assumed``
    says why) that spread over the norm the layer's input was expected to
    have, so that no layer's six gates are all a sixth, as no trained
    router's are."""
    spread = cfg.get("router_logit_spread")
    if not spread:
        return cfg["initializer_range"]
    grown = cfg["initializer_range"] ** 2 + index * 0.03
    return spread / math.sqrt(cfg["hidden_size"] * grown)


@functools.lru_cache(maxsize=None)
def _layer_maker(key, index):
    cfg = _cfg(key)
    mats = layer_shapes(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])
    d = cfg["hidden_size"]

    def make(rng):
        keys = jax.random.split(rng, len(mats))
        out = {}
        for k, (name, shape) in zip(keys, sorted(mats.items())):
            std = router_std(cfg, index) if name == "router" \
                else cfg["initializer_range"]
            out[name] = (jax.random.normal(k, shape) * std).astype(dtype)
        out["norm1"] = jnp.ones((d,), jnp.float32)
        out["norm2"] = jnp.ones((d,), jnp.float32)
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
    layers = [_layer_maker(key, i)(jax.random.fold_in(root, i))
              for i in range(len(cfg["sliding_window_layout"]))]
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


def route(cfg, p, x, precision):
    """``(choice [s, k], gates [s, k])``: the softmax of the router's
    logits over all experts, kept on the ``k`` largest logits (ties to the
    lower index) and divided by their sum."""
    k = cfg["moe_num_active_primary_experts"]
    logits = C.einsum("sd,de->se", x, p["router"], precision)
    _, choice = jax.lax.top_k(logits, k)
    kept = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), choice,
                               axis=-1)
    return choice, kept / jnp.sum(kept, axis=-1, keepdims=True)


def attention(cfg, kind, p, u, precision):
    """``W_o softmax(q k^T / sqrt d + mask) v`` over ``u [s, d]``."""
    n, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, s = cfg["head_dim"], u.shape[0]
    q = C.einsum("sd,dn->sn", u, p["q"], precision).reshape(s, n, d)
    k = C.einsum("sd,dn->sn", u, p["k"], precision).reshape(s, groups, d)
    v = C.einsum("sd,dn->sn", u, p["v"], precision).reshape(s, groups, d)
    at = jnp.arange(s)
    if kind == "window":
        q = rotary(q, at, cfg["rope_theta"])
        k = rotary(k, at, cfg["rope_theta"])
    grouped = jnp.pad(q / math.sqrt(d), ((0, ROWS), (0, 0), (0, 0))).reshape(
        s + ROWS, groups, n // groups, d)

    def rows(first):
        t = first + jnp.arange(ROWS)
        qr = jax.lax.dynamic_slice_in_dim(grouped, first, ROWS)
        seen = at[None] <= t[:, None]
        if kind == "window":
            seen &= at[None] > t[:, None] - cfg["sliding_window_size"]
        scores = C.einsum("rghd,sgd->rghs", qr, k, precision)
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None], scores, -jnp.inf), axis=-1)
        return C.einsum("rghs,sgd->rghd", probs, v, precision)
    out = jax.lax.map(rows, jnp.arange(0, s, ROWS)).reshape(-1, n * d)[:s]
    return C.einsum("sn,nd->sd", out, p["o"], precision)


def experts(cfg, p, h, choice, gates, precision, held=None):
    """``sum_e g_e W_down,e (relu(W_gate,e h) * W_up,e h)``: every expert in
    turn over all rows, its gate nought where it was not chosen. ``held``
    (expert numbers) computes the part of those experts alone."""
    e_all = cfg["moe_num_primary_experts"]
    which = jnp.arange(e_all) if held is None else jnp.asarray(held)

    def rows(block, pick, gate):
        def one(total, e):
            g = jnp.sum(jnp.where(pick == e, gate, 0.0), axis=-1)
            mid = jax.nn.relu(C.einsum("sd,df->sf", block, p["w_gate"][e],
                                       precision)) \
                * C.einsum("sd,df->sf", block, p["w_up"][e], precision)
            return total + g[:, None] * C.einsum(
                "sf,fd->sd", mid, p["w_down"][e], precision), None
        return jax.lax.scan(one, jnp.zeros_like(block), which)[0]
    s = h.shape[0]
    if s <= FFN_ROWS:
        return rows(h, choice, gates)
    pad = -s % FFN_ROWS

    def blocks(a):
        return jnp.pad(a, ((0, pad), (0, 0))).reshape(
            -1, FFN_ROWS, a.shape[1])
    return jax.lax.map(lambda a: rows(*a), (blocks(h), blocks(choice),
                                            blocks(gates))).reshape(
        -1, h.shape[1])[:s]


def layer(cfg, kind, p, x, precision, held=None):
    """``r = W_r x``; ``a = x + Attn(N1(x))``; ``x' = a + Experts(N2(a))``
    under the routing of ``r``, over ``[s, d]``."""
    eps = cfg["rms_norm_eps"]
    choice, gates = route(cfg, p, x, precision)
    a = x + attention(cfg, kind, p, rms_norm(p["norm1"], x, eps), precision)
    return a + experts(cfg, p, rms_norm(p["norm2"], a, eps), choice, gates,
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
