"""Gated delta-rule linear attention with a decay a channel (Kimi Delta
Attention, KDA), as the ``kda`` layers of ``capture/decoder.py`` use it:

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T,
    o_t = S_t^T q_t

with ``S`` a ``[D, D]`` float32 state a head, ``b_t`` a scalar a head and
``g_t`` a log-decay a key channel (``g_t <= 0``). A slot keeps the state
transposed, ``R = S^T`` (``[H, Dv, Dk]``): a decode step then meets every
vector of a key channel along the lanes. Beside it a slot keeps the short
causal convolution's window: the last ``conv - 1`` inputs of the q, k, v
projections (:func:`conv_chunk`, :func:`conv_step`, scope ``delta_conv``).

Two forms of one recurrence, both under the scope ``delta_attn``:

- :func:`delta_attention_chunk`, the prefill: ``T`` positions in blocks
  of ``block``. Within a block the delta rule is solved in the WY (UT)
  form: with ``G`` the decay's running sum inside the block, the block's
  update is ``S_end = Diag(exp G_end) S_0 + (K exp(G_end - G))^T U``,
  ``U = (I + Diag(b) A)^-1 Diag(b) (V - (K exp G) S_0)`` and ``A[t, i] =
  sum_c k_tc k_ic exp(G_tc - G_ic)`` for ``i < t``; the outputs are ``(Q
  exp G) S_0 + B U`` with ``B`` as ``A`` over ``q`` and ``i <= t``. The
  pair matrices are products of keys scaled against a reference point a
  sub-block of rows (every factor stays under ``exp(-lower_bound x
  sub)``, which float32 holds), the inverse is the product ``(I + N)(I +
  N^2)(I + N^4)...`` of the nilpotent ``N = -Diag(b) A``, and everything
  that does not read the state runs for all blocks at once; only the three
  products with the state run block after block. Only the first
  ``n_valid`` positions move the state (a padded position has ``b = 0`` and
  ``g = 0``).
- :func:`delta_attention_step`, the decode: one position of every slot,
  elementwise products and sums in float32 (no pass of the matrix unit
  rounds them), which XLA fuses into one pass over the states, in place
  where the caller donates them. A slot that is not ``active`` keeps its
  state and its window.

``q`` and ``k`` come L2-normalised, ``q`` scaled by ``D^-1/2``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax


_HI = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DeltaSpec:
    """The sizes of a ``kda`` layer; widths are the model's own."""
    heads: int = 64
    head_dim: int = 128
    conv: int = 4                  # the short convolution's taps
    gate_lower_bound: float = -5.0
    rank: int = 128                # the decay's and the output gate's rank
    block: int = 64                # positions of a block of the chunk form

    def __post_init__(self):
        if not self.gate_lower_bound < 0 or self.conv < 2:
            raise ValueError("a kda layer needs gate_lower_bound < 0 and a "
                             "convolution of at least 2 taps")

    @property
    def width(self) -> int:
        return self.heads * self.head_dim

    @property
    def sub(self) -> int:
        """Rows of a sub-block of the chunk form: the most, up to 16, over
        which the decay's sum stays under ``exp(80)``."""
        sub = 16
        while sub > 1 and -self.gate_lower_bound * sub > 80:
            sub //= 2
        return sub

    def shapes(self, hidden: int) -> Tuple[Dict, Dict, Dict]:
        """``(matrices, vectors that start at 1, vectors that start at
        0)`` of one layer: the q, k, v projections side by side, their
        convolution's taps ``[conv, 3 x width]``, the output product, the
        write strength ``b``, the decay's and the output gate's low-rank
        pairs, the output's norm a head, ``A_log`` a head and the decay's
        bias a channel."""
        c, r = self.width, self.rank
        mats = {"qkv": (hidden, 3 * c), "conv": (self.conv, 3 * c),
                "o": (c, hidden), "beta": (hidden, self.heads),
                "f_a": (hidden, r), "f_b": (r, c), "g_a": (hidden, r),
                "g_b": (r, c)}
        return (mats, {"o_norm": (self.head_dim,)},
                {"A_log": (self.heads,), "dt_bias": (c,)})


def init_delta_cache(slots: int, spec: DeltaSpec) -> Dict[str, jax.Array]:
    """A layer's state a slot ``[S, H, Dv, Dk]`` (transposed) and the
    convolution's window ``[S, conv - 1, 3 x width]``, float32 zeros."""
    return {"state": jnp.zeros((slots, spec.heads, spec.head_dim,
                                spec.head_dim), jnp.float32),
            "conv": jnp.zeros((slots, spec.conv - 1, 3 * spec.width),
                              jnp.float32)}


# -- the short convolution ----------------------------------------------------

@jax.named_scope("delta_conv")
def conv_chunk(x: jax.Array, window: jax.Array, taps: jax.Array, n_valid
               ) -> Tuple[jax.Array, jax.Array]:
    """SiLU of the causal depthwise convolution of ``x [T, C]`` after the
    ``window [conv - 1, C]`` of the inputs before it (``taps [conv, C]``,
    the last tap on the position itself); also the window after the first
    ``n_valid`` positions."""
    t, width = x.shape[0], taps.shape[0]
    full = jnp.concatenate([window, x], axis=0)
    taps = taps.astype(jnp.float32)
    y = sum(full[j:j + t] * taps[j] for j in range(width))
    return jax.nn.silu(y), lax.dynamic_slice_in_dim(
        full, jnp.asarray(n_valid, jnp.int32), width - 1)


@jax.named_scope("delta_conv")
def conv_step(x: jax.Array, window: jax.Array, taps: jax.Array,
              active: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One position of every slot: ``x [S, C]``, ``window [S, conv - 1,
    C]``. A slot that is not ``active`` keeps its window."""
    full = jnp.concatenate([window, x[:, None]], axis=1)
    y = jnp.einsum("sjc,jc->sc", full, taps.astype(jnp.float32),
                   precision=_HI)
    return jax.nn.silu(y), jnp.where(active[:, None, None], full[:, 1:],
                                     window)


# -- prefill: a chunk of one stream's prompt ----------------------------------

def _pairs(x, k, g_sum, sub, limit, strict):
    """``P[t, i] = sum_c x_tc k_ic exp(G_tc - G_ic)`` for ``i < t``
    (``strict``) or ``i <= t`` in each block: ``x``, ``k``, ``g_sum`` ``[N,
    H, B, D]`` -> ``[N, H, B, B]``. Each sub-block of ``sub`` rows is scaled
    against the running sum where it starts, so that no factor passes
    ``exp(limit)``."""
    n, h, b, d = x.shape
    rows = g_sum.reshape(n, h, b // sub, sub, d)
    ref = jnp.concatenate([jnp.zeros_like(rows[:, :, :1, 0]),
                           rows[:, :, :-1, -1]], axis=2)   # [N, H, n_sub, D]
    xs = x.reshape(rows.shape) * jnp.exp(rows - ref[:, :, :, None])
    ks = k[:, :, None] * jnp.exp(jnp.minimum(
        ref[:, :, :, None] - g_sum[:, :, None], limit))   # [N, H, n_sub, B, D]
    pairs = jnp.einsum("nhjtd,nhjid->nhjti", xs, ks,
                       precision=_HI).reshape(n, h, b, b)
    at = jnp.arange(b)
    keep = at[None] < at[:, None] if strict else at[None] <= at[:, None]
    return jnp.where(keep, pairs, 0.0)


def _unit_lower_inverse(n: jax.Array) -> jax.Array:
    """``(I - N)^-1`` of strictly lower triangular ``N [..., B, B]``:
    ``(I + N)(I + N^2)(I + N^4)...``, exact since ``N^B = 0``."""
    b = n.shape[-1]
    eye = jnp.eye(b, dtype=n.dtype)
    out, power = eye + n, n
    for _ in range(max(b - 1, 1).bit_length() - 1):
        power = jnp.matmul(power, power, precision=_HI)
        out = jnp.matmul(out, eye + power, precision=_HI)
    return out


@jax.named_scope("delta_attn")
def delta_attention_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                          g: jax.Array, beta: jax.Array, state: jax.Array,
                          n_valid, spec: DeltaSpec
                          ) -> Tuple[jax.Array, jax.Array]:
    """``q``/``k``/``g`` ``[T, H, Dk]``, ``v`` ``[T, H, Dv]``, ``beta [T,
    H]`` (float32), ``state [H, Dv, Dk]`` (transposed): the outputs ``[T, H,
    Dv]`` of the ``T`` positions that follow ``state``, and the state
    (transposed) after the first ``n_valid`` of them."""
    t, h, d = q.shape
    block = min(spec.block, t)
    sub = min(spec.sub, block)
    if t % block or block % sub:
        raise ValueError(f"chunk of {t} positions is no whole number of "
                         f"blocks of {block} in sub-blocks of {sub}")
    real = jnp.arange(t) < jnp.asarray(n_valid, jnp.int32)
    g = jnp.where(real[:, None, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)

    def blocks(x):                          # [T, H, D] -> [N, H, B, D]
        return jnp.moveaxis(x.reshape((t // block, block) + x.shape[1:]), 1, 2)
    q, k, v, g = blocks(q), blocks(k), blocks(v), blocks(g)
    beta = jnp.moveaxis(beta.reshape(t // block, block, h), 1, 2)  # [N, H, B]
    g_sum = jnp.cumsum(g, axis=2)
    g_end = g_sum[:, :, -1:]
    limit = -spec.gate_lower_bound * sub
    a = _pairs(k, k, g_sum, sub, limit, True)
    b_q = _pairs(q, k, g_sum, sub, limit, False)
    # U = T (V - K_bar S_0), T = (I + Diag(b) A)^-1 Diag(b)
    solve = _unit_lower_inverse(-beta[..., None] * a) * beta[..., None, :]
    w = jnp.matmul(solve, k * jnp.exp(g_sum), precision=_HI)
    u0 = jnp.matmul(solve, v, precision=_HI)
    q_bar = q * jnp.exp(g_sum)
    k_tail = k * jnp.exp(g_end - g_sum)
    decay = jnp.exp(g_end)[:, :, 0, :, None]         # [N, H, Dk, 1]

    def body(s, xs):                                 # s [H, Dk, Dv]
        w_b, u0_b, qb_b, bq_b, kt_b, dec_b = xs
        u = u0_b - jnp.matmul(w_b, s, precision=_HI)
        out = jnp.matmul(qb_b, s, precision=_HI) \
            + jnp.matmul(bq_b, u, precision=_HI)
        s = dec_b * s + jnp.einsum("hbk,hbv->hkv", kt_b, u, precision=_HI)
        return s, out
    s, out = lax.scan(body, jnp.swapaxes(state.astype(jnp.float32), 1, 2),
                      (w, u0, q_bar, b_q, k_tail, decay))
    out = jnp.moveaxis(out, 2, 1).reshape(t, h, -1)
    return out, jnp.swapaxes(s, 1, 2)


# -- decode: one position of every slot ---------------------------------------

def delta_attention_step(q: jax.Array, k: jax.Array, v: jax.Array,
                         g: jax.Array, beta: jax.Array, state: jax.Array,
                         active: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One position of every slot: ``q``/``k``/``g`` ``[S, H, Dk]``, ``v``
    ``[S, H, Dv]``, ``beta [S, H]``, ``state [S, H, Dv, Dk]`` (transposed,
    float32), ``active [S]``. Returns the outputs ``[S, H, Dv]`` and the
    states; a slot that is not active keeps its state exactly (its decay is
    1 and its write 0)."""
    with jax.named_scope("delta_attn"):
        on = active[:, None, None]
        r = state * jnp.where(on, jnp.exp(g), 1.0)[..., None, :]
        recalled = jnp.sum(r * k[..., None, :], axis=-1)
        kb = jnp.where(on, beta[..., None] * k, 0.0)
        r = r + (v - recalled)[..., None] * kb[..., None, :]
        return jnp.sum(r * q[..., None, :], axis=-1), r
