"""Plain reference of the GPT-2 decoder (Radford et al. 2019): learned
positions, pre-LN blocks with a fused qkv projection, causal attention, tanh
GELU, final layer norm, logits against the tied embedding. Float32 products
at ``highest``; no cache, no batching of requests, no kernels. Weights come
from the seed here (blocks stacked on a leading axis) and are handed to the
program."""
import functools

import jax
import jax.numpy as jnp

from . import common as C


def init_weights(cfg, seed):
    """Every weight from ``seed`` in one jitted call, float32 on the device."""
    d, inter, n = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    std = cfg["initializer_range"]

    def make(key):
        ks = iter(jax.random.split(key, 8))
        normal = lambda *shape: jax.random.normal(next(ks), shape) * std
        ln = lambda *lead: {"scale": jnp.ones(lead + (d,)),
                            "bias": jnp.zeros(lead + (d,))}
        shapes = {"qkv": (d, 3 * d), "attn_out": (d, d), "fc1": (d, inter),
                  "fc2": (inter, d)}
        blocks = {name: {"kernel": normal(n, *s),
                         "bias": jnp.zeros((n, s[1]))}
                  for name, s in shapes.items()}
        blocks["ln1"], blocks["ln2"] = ln(n), ln(n)
        return {"embed": normal(cfg["vocab_size"], d),
                "pos": normal(cfg["n_positions"], d),
                "blocks": blocks, "ln_f": ln()}
    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def _block(cfg, precision, bias, x, p):
    eps, h = cfg["layer_norm_epsilon"], cfg["n_head"]
    qkv = C.dense(p["qkv"], C.layer_norm(p["ln1"], x, eps), precision)
    q, k, v = (C.split_heads(t, h) for t in jnp.split(qkv, 3, axis=-1))
    ctx = C.merge_heads(C.attention(q, k, v, bias, precision))
    x = x + C.dense(p["attn_out"], ctx, precision)
    mid = C.gelu_tanh(C.dense(p["fc1"], C.layer_norm(p["ln2"], x, eps),
                              precision))
    return x + C.dense(p["fc2"], mid, precision), None


def logits(cfg, params, tokens, precision="highest"):
    """Next-token logits ``[b, s, vocab]`` of ``tokens [b, s]``."""
    s = tokens.shape[1]
    x = params["embed"][tokens] + params["pos"][:s][None]
    causal = jnp.tril(jnp.ones((s, s), bool))
    bias = jnp.where(causal, 0.0, -1e9)[None, None]
    x, _ = jax.lax.scan(functools.partial(_block, cfg, precision, bias), x,
                        params["blocks"])
    x = C.layer_norm(params["ln_f"], x, cfg["layer_norm_epsilon"])
    return C.einsum("bsd,vd->bsv", x, params["embed"], precision)


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_items):
    cfg = dict(cfg_items)

    def gaps(params, tokens, chosen):
        out = logits(cfg, params, tokens)
        picked = jnp.take_along_axis(out, chosen[..., None], axis=-1)[..., 0]
        return jnp.max(out, axis=-1) - picked
    return jax.jit(gaps)


@functools.lru_cache(maxsize=None)
def _argmax_fn(cfg_items, precision):
    cfg = dict(cfg_items)
    return jax.jit(lambda params, tokens: jnp.argmax(
        logits(cfg, params, tokens, precision), axis=-1))


def gaps_below_best(cfg, params, tokens, chosen):
    """At each position of ``tokens [b, s]``: how far the reference's logit
    of ``chosen [b, s]`` lies below the reference's best logit there."""
    return _gap_fn(C.scalars(cfg))(params, jnp.asarray(tokens),
                                  jnp.asarray(chosen))


def first_choice(cfg, params, tokens, precision):
    """The token that a forward pass at ``precision`` puts first at each
    position (the control reads this at ``"fp8"``)."""
    return _argmax_fn(C.scalars(cfg), precision)(params, jnp.asarray(tokens))
