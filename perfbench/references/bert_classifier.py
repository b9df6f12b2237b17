"""Plain reference of the BERT sequence classifier (Devlin et al. 2018):
post-LN encoder blocks, pooled first token, softmax head, cross entropy on
clipped probabilities, AdamW. Weights come from the seed here, in the
benchmark's own layout (blocks stacked on a leading axis), and are handed to
the program; nothing is taken back from it.

Departures from the published model, which the configuration file states:
those the program under test makes and cannot be told not to (the file's
``program_departures``: layer-norm epsilon 1e-5, the tanh form of GELU; and
probabilities clipped to [1e-7, 1 - 1e-7] before the log), and the
benchmark's own (the file's ``reduced``: no dropout)."""
import functools

import jax
import jax.numpy as jnp

from . import common as C

def init_weights(cfg, seed):
    """Every weight from ``seed`` in one jitted call, float32 on the device."""
    d, inter, n = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    std = cfg["initializer_range"]

    def make(key):
        ks = iter(jax.random.split(key, 16))
        normal = lambda *shape: jax.random.normal(next(ks), shape) * std
        ln = lambda *lead: {"scale": jnp.ones(lead + (d,)),
                            "bias": jnp.zeros(lead + (d,))}
        shapes = {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d),
                  "ffn_in": (d, inter), "ffn_out": (inter, d)}
        blocks = {name: {"kernel": normal(n, *s),
                         "bias": jnp.zeros((n, s[1]))}
                  for name, s in shapes.items()}
        blocks["ln1"], blocks["ln2"] = ln(n), ln(n)
        return {
            "word_emb": normal(cfg["vocab_size"], d),
            "pos_emb": normal(cfg["max_position_embeddings"], d),
            "type_emb": normal(cfg["type_vocab_size"], d),
            "emb_ln": ln(), "blocks": blocks,
            "pooler": {"kernel": normal(d, d), "bias": jnp.zeros((d,))},
            "classifier": {"kernel": normal(d, cfg["num_labels"]),
                           "bias": jnp.zeros((cfg["num_labels"],))}}
    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def _block(cfg, precision, bias, x, p):
    eps, h = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    q, k, v = (C.split_heads(C.dense(p[n], x, precision), h)
               for n in ("q", "k", "v"))
    ctx = C.merge_heads(C.attention(q, k, v, bias, precision))
    x = C.layer_norm(p["ln1"], x + C.dense(p["o"], ctx, precision), eps)
    mid = C.gelu_tanh(C.dense(p["ffn_in"], x, precision))
    return C.layer_norm(p["ln2"], x + C.dense(p["ffn_out"], mid, precision),
                        eps), None


def loss(cfg, params, tokens, labels, precision="highest"):
    """Mean cross entropy of ``tokens [b, s]`` (0 = padding) against
    ``labels [b]``."""
    b, s = tokens.shape
    mask = (tokens != 0).astype(jnp.float32)
    x = (params["word_emb"][tokens] + params["pos_emb"][jnp.arange(s)][None]
         + params["type_emb"][jnp.zeros_like(tokens)])
    x = C.layer_norm(params["emb_ln"], x, cfg["layer_norm_eps"])
    bias = (1.0 - mask[:, None, None, :]) * -1e9
    x, _ = jax.lax.scan(functools.partial(_block, cfg, precision, bias), x,
                        params["blocks"])
    pooled = jnp.tanh(C.dense(params["pooler"], x[:, 0], precision))
    probs = jax.nn.softmax(C.dense(params["classifier"], pooled, precision))
    picked = jnp.take_along_axis(probs, labels.astype(jnp.int32)[:, None], 1)
    return -jnp.mean(jnp.log(jnp.clip(picked, 1e-7, 1.0 - 1e-7)))


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items, precision):  # one compiled program a precision
    cfg = dict(cfg_items)
    return jax.jit(jax.value_and_grad(
        lambda p, t, y: loss(cfg, p, t, y, precision)))


def follow(cfg, params, batches, precision="highest", rows=32):
    """Follow the first ``len(batches)`` training steps. Each batch goes
    through in blocks of ``rows`` rows, whose gradients are averaged, so
    that float32 activations fit beside nothing else on the chip. Returns
    the losses, the first gradient, and the parameters after the last step
    (all in the benchmark's layout)."""
    opt = cfg["optimizer"]
    grad_fn = _grad_fn(C.scalars(cfg), precision)
    state, losses, first_grad = C.adamw_init(params), [], None
    tm = jax.tree_util.tree_map
    for tokens, labels in batches:
        n = len(tokens)
        if n % rows:
            raise ValueError(f"batch of {n} rows is not whole blocks of {rows}")
        total, grads = 0.0, None
        for i in range(0, n, rows):
            value, g = grad_fn(params, jnp.asarray(tokens[i:i + rows]),
                               jnp.asarray(labels[i:i + rows]))
            total = total + value
            grads = g if grads is None else tm(jnp.add, grads, g)
        blocks = n // rows
        grads = tm(lambda g: g / blocks, grads)
        losses.append(float(total / blocks))
        if first_grad is None:
            first_grad = grads
        params, state = C.adamw_step(
            params, grads, state, opt["learning_rate"], opt["beta1"],
            opt["beta2"], opt["epsilon"], opt["weight_decay"])
    return {"losses": losses, "first_grad": first_grad, "params": params}
