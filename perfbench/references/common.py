"""What the plain references share: a matrix product at a stated precision,
layer norm, and the AdamW update. Straightforward ``jax.numpy`` in float32;
nothing here imports the program under test.

``precision`` is ``"highest"`` (float32 products, the reference proper) or
``"fp8"`` (the control: every product's operands, and every gradient that
flows back through it, rounded to float8 e4m3 with a scale per tensor, the
step below the bfloat16 passes that both configurations state)."""
import functools

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round_fp8(x)


_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def scalars(cfg):
    """The configuration's plain values as a key for ``functools.lru_cache``:
    the references compile one program a configuration and precision."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if not isinstance(v, (dict, list))))


def operand(x, precision):
    x = x.astype(jnp.float32)
    return _fp8(x) if precision == "fp8" else x


def einsum(spec, a, b, precision):
    return jnp.einsum(spec, operand(a, precision), operand(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def dense(p, x, precision):
    return einsum("...k,kn->...n", x, p["kernel"], precision) + p["bias"]


def layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def attention(q, k, v, bias, precision):
    """``softmax(q k^T / sqrt(d) + bias) v`` over ``[b, h, s, d]``."""
    scores = einsum("bhqd,bhkd->bhqk", q, k, precision) / (q.shape[-1] ** 0.5)
    probs = jax.nn.softmax(scores + bias, axis=-1)
    return einsum("bhqk,bhkd->bhqd", probs, v, precision)


def split_heads(x, n_head):
    b, s, d = x.shape
    return x.reshape(b, s, n_head, d // n_head).transpose(0, 2, 1, 3)


def merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def adamw_init(params):
    zeros = functools.partial(jax.tree_util.tree_map, jnp.zeros_like)
    return {"mu": zeros(params), "nu": zeros(params), "count": 0}


def adamw_step(params, grads, state, lr, b1, b2, eps, weight_decay):
    """One AdamW update with bias correction and decoupled decay on every
    leaf (what ``optax.adamw`` computes)."""
    t = state["count"] + 1
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = tm(lambda n, g: b2 * n + (1 - b2) * g * g, state["nu"], grads)

    def new(p, m, n):
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(n / (1 - b2 ** t)) + eps)
        return p - lr * (step + weight_decay * p)
    return tm(new, params, mu, nu), {"mu": mu, "nu": nu, "count": t}
