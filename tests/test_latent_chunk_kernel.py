"""The chunk's tile kernel of ``ops/latent_attention.py``
(``_tile_attend_kernel``: scores, the selection's mask and a running softmax
in VMEM) against the XLA form ``_tile_attend_xla``, in interpret mode on the
CPU: heads of 128 + 128 numbers, several blocks of query rows and of keys,
masks with rows that see nothing in a tile; then a whole ``attend_chunk``
over three tiles with the kernel in the XLA form's place. What the chip's
compiler makes of it is ``tests/test_tpu_compile.py -k glm5``; what it
computes there is the benchmark's ``correct``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import dispatch
from analytics_zoo_tpu.ops import latent_attention as LA


def _tile(seed, h=3, t=128, n=512, d=128, dv=128, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(h, t, d)) / np.sqrt(d), dtype)
    k = jnp.asarray(rng.normal(size=(h, n, d)), dtype)
    v = jnp.asarray(rng.normal(size=(h, n, dv)), dtype)
    ok = rng.random((t, n)) < 0.3
    ok[5] = False                   # a row that sees nothing in this tile
    ok[9, :] = False
    ok[9, 300] = True               # one that sees a single key
    ok[17, :256] = False            # one whose first block of keys is empty
    return q, k, v, jnp.asarray(ok)


@pytest.mark.parametrize("blocks", [(64, 128), (128, 256), (512, 1024)])
@pytest.mark.parametrize("seed", [1, 2])
def test_kernel_agrees_with_the_xla_form(monkeypatch, seed, blocks):
    q, k, v, ok = _tile(seed)
    monkeypatch.setattr(LA, "TILE_KERNEL_BLOCKS", blocks)
    assert LA._tile_kernel_rule(q, k, v) is None
    want_o, want_lse = LA._tile_attend_xla(q, k, v, ok)
    with pltpu.force_tpu_interpret_mode():
        got_o, got_lse = jax.jit(LA._tile_attend_kernel)(q, k, v, ok)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_lse), np.asarray(want_lse),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got_o)[:, 5].any()
    assert (np.asarray(got_lse)[:, 5] == LA._NEG).all()
    assert np.all(np.isfinite(np.asarray(got_o)))


def test_a_bfloat16_tile_stays_within_one_pass():
    q, k, v, ok = _tile(3, dtype=jnp.bfloat16)
    want_o, want_lse = LA._tile_attend_xla(q, k, v, ok)
    with pltpu.force_tpu_interpret_mode():
        got_o, got_lse = jax.jit(LA._tile_attend_kernel)(q, k, v, ok)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(got_lse), np.asarray(want_lse),
                               atol=2e-2)


def test_the_rule_names_what_the_kernel_cannot_take():
    q, k, v, _ = _tile(4, t=100)
    assert "no whole blocks" in LA._tile_kernel_rule(q, k, v)
    q, k, v, _ = _tile(4, d=64)
    assert "128 lanes" in LA._tile_kernel_rule(q, k, v)


def test_a_whole_chunk_takes_the_kernel_on_the_tpu(monkeypatch):
    """``attend_chunk`` over three tiles of a latent pool with the kernel
    in the XLA form's place gives the XLA form's result, and notes no
    fallback; with heads the kernel cannot take it says so once."""
    lat = LA.LatentSpec(heads=2, q_rank=32, kv_rank=128, nope_dim=64,
                        rope_dim=64, v_dim=128, index_heads=2, index_dim=8,
                        index_topk=40, index_rope_dim=4, chunk_tile=128,
                        attend_tile=128)
    rng = np.random.default_rng(8)
    page, width, t, start = 16, 24, 64, 256
    pool = jnp.asarray(rng.normal(size=(1 + width, page, lat.pool_row)),
                       jnp.float32)
    row = jnp.asarray(1 + rng.permutation(width), jnp.int32)
    p = {"kv_b": jnp.asarray(rng.normal(size=(128, 2 * 192)) / 11.0,
                             jnp.float32)}
    qn = jnp.asarray(rng.normal(size=(t, 2, 64)) / 8, jnp.float32)
    qr = jnp.asarray(rng.normal(size=(t, 2, 64)) / 8, jnp.float32)
    bits = jnp.asarray(rng.integers(0, 3, (t, width * page)), jnp.uint32)
    last = jnp.asarray(rng.integers(0, width * page, t), jnp.int32)
    args = (lat, p, qn, qr, pool, row, start, bits,
            jnp.ones(t, jnp.uint32), last)
    want = LA.attend_chunk(*args)
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(LA, "TILE_KERNEL_BLOCKS", (32, 128))
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(LA.attend_chunk, static_argnums=(0,))(*args)
    assert dispatch.fallbacks_seen() == []
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    small = dataclasses.replace(lat, nope_dim=32, rope_dim=32)
    with pltpu.force_tpu_interpret_mode():
        LA.attend_chunk(small, {"kv_b": p["kv_b"][:, :2 * 160]},
                        qn[..., :32], qr[..., :32], *args[4:])
    assert [kernel for kernel, _ in dispatch.fallbacks_seen()] \
        == ["latent_chunk_attend"]
