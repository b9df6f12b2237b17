"""The block-sparse chunk read on the kernel (``ops/sparse_attention.py
attend_chunk``: the grouped-query chunk kernel of ``ops/grouped_attention.py``
with the selection ``allowed`` as its page mask, the pool read in place and
the scores in VMEM) against the XLA form's tiles, in interpret mode on the
CPU: MiniCPM-SALA's head layout (16 query heads over 2 key/value heads of
128) with pages of 64, blocks of 128 rows x 128 keys, and a ``dense_len`` of
512 in the place of 512 x 512 and 8,192; chunks of 512 / 256 / 128 rows in
the place of 2,048 / 1,024 / 512. Then one whole ``LayeredDecoder.
prefill_chunk`` of a tiny MiniCPM-SALA with the kernel in the XLA form's
place, and the rule's fallbacks by reason. What the chip's compiler makes of
it is ``tests/test_tpu_compile.py -k sala``; what it computes there is the
benchmark's ``correct``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import dispatch
from analytics_zoo_tpu.ops import grouped_attention as GA
from analytics_zoo_tpu.ops import sparse_attention as SA

KV, G, D, PAGE, WIDTH = 2, 16, 128, 64, 32
SPEC = SA.SparseSpec(kernel_size=32, kernel_stride=16, block_size=PAGE,
                     init_blocks=1, window_size=256, topk=2, dense_len=512)
BLOCKS = (128, 128)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pools(rng, dtype=jnp.float32):
    def pool():
        # the null page holds junk, as in a server whose padding writes there
        return jnp.asarray(rng.normal(size=(1 + WIDTH, PAGE, KV * D)),
                           jnp.float32).at[0].mul(30.0).astype(dtype)
    kc = jnp.asarray(rng.normal(size=(1 + WIDTH, SPEC.per_block, KV * D)),
                     jnp.float32) * 0.3
    row = jnp.asarray(1 + rng.permutation(WIDTH), jnp.int32)
    return {"k": pool(), "v": pool(), "kc": kc}, row


def _queries(rng, t):
    return jnp.asarray(rng.normal(size=(t, KV, G, D)), jnp.float32) * D ** -0.5


def _both(monkeypatch, q, cache, row, start, allowed):
    """``attend_chunk`` as the XLA form and, on a patched TPU in interpret
    mode, as the kernel; no fallback may be noted."""
    want = SA.attend_chunk(SPEC, q, cache, row, start, allowed, tile_pages=3)
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(SA, "CHUNK_KERNEL_BLOCKS", BLOCKS)
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(SA.attend_chunk, static_argnums=0)(
            SPEC, q, cache, row, jnp.int32(start), allowed)
    assert dispatch.fallbacks_seen() == []
    return np.asarray(got), np.asarray(want)


def _selection(q, cache, row, start):
    return SA.select_chunk(SPEC, q, cache["kc"], row, start)


# (rows, start): a prompt's first chunk at each width, chunks that end
# before dense_len, that straddle it, and that lie wholly beyond it
CASES = [(512, 0, "first_512"), (256, 0, "first_256"), (128, 0, "first_128"),
         (256, 128, "before_dense"), (128, 256, "before_dense_128"),
         (512, 256, "straddles"), (256, 384, "straddles_256"),
         (512, 1024, "beyond"), (256, 1536, "beyond_256"),
         (128, 1792, "beyond_128")]


@pytest.mark.parametrize("t,start", [c[:2] for c in CASES],
                         ids=[c[2] for c in CASES])
def test_kernel_agrees_with_the_xla_form(monkeypatch, t, start):
    rng = np.random.default_rng(1000 * t + start)
    cache, row = _pools(rng)
    q = _queries(rng, t)
    got, want = _both(monkeypatch, q, cache, row, start,
                      _selection(q, cache, row, start))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_a_chunk_whose_last_rows_are_padding(monkeypatch):
    """A prompt of 900 positions fed as ... + a bucket of 256 from 768 with
    132 real rows: the padding's keys went to the null page and the row
    names no page past the prompt's; every row agrees, padding included,
    and every row is finite."""
    rng = np.random.default_rng(7)
    cache, row = _pools(rng)
    row = row.at[900 // PAGE + 1:].set(0)
    q = _queries(rng, 256)
    got, want = _both(monkeypatch, q, cache, row, 768,
                      _selection(q, cache, row, 768))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.all(np.isfinite(got))


def test_a_block_of_rows_that_reads_no_page_of_a_key_block(monkeypatch):
    """Beyond dense_len, the second block of rows is made to read none of
    the pages of key block 2 (pages 4 and 5, which only a top-k choice
    could bring): that (rows, keys) pair is masked whole, and the rows
    still agree."""
    rng = np.random.default_rng(8)
    cache, row = _pools(rng)
    q = _queries(rng, 256)
    allowed = _selection(q, cache, row, 1024).at[128:, :, 4:6].set(False)
    _, whole = GA._page_codes(allowed, 1024, BLOCKS[0], BLOCKS[1] // PAGE,
                              PAGE)
    assert not np.asarray(allowed)[128:, :, 4:6].any()
    assert np.asarray(whole).reshape(KV, 2, -1)[:, 1, 2].tolist() == [0, 0]
    got, want = _both(monkeypatch, q, cache, row, 1024, allowed)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_a_bfloat16_pool_stays_within_one_pass(monkeypatch):
    rng = np.random.default_rng(10)
    cache, row = _pools(rng, jnp.bfloat16)
    q = _queries(rng, 256)
    got, want = _both(monkeypatch, q, cache, row, 640,
                      _selection(q, cache, row, 640))
    np.testing.assert_allclose(got, want, atol=3e-2)


def test_the_block_codes_follow_the_selection_and_the_causal_limit():
    """Before dense_len every key block that lies before a block of rows is
    whole; beyond it only those that every row of the block reads whole
    are: the local pages that all its rows share, and not every block
    before it."""
    rng = np.random.default_rng(11)
    cache, row = _pools(rng)
    for start, t in ((0, 512), (1024, 512)):
        q = _queries(rng, t)
        allowed = np.asarray(_selection(q, cache, row, start))
        _, whole = GA._page_codes(jnp.asarray(allowed), start, 128, 2, PAGE)
        whole = np.asarray(whole).reshape(KV, t // 128, -1)
        for i in range(t // 128):
            low = start + 128 * i
            past = np.arange(1, whole.shape[2] + 1) * 128 - 1 <= low
            every = allowed[128 * i:128 * (i + 1)].reshape(
                128, KV, -1, 2).all(axis=(0, 3))              # [KV, blocks]
            assert (whole[:, i] == (every & past)).all()
            if start == 0:
                assert (whole[:, i] == past).all()
            else:
                assert whole[:, i, low // 128 - 1].all()
                assert not whole[:, i, :low // 128].all()


# -- a whole chunk program ------------------------------------------------------------

def test_a_whole_prefill_chunk_takes_the_kernel_on_the_tpu(monkeypatch):
    """``LayeredDecoder.prefill_chunk`` of a tiny MiniCPM-SALA (heads of
    128, ``[minicpm4, lightning, lightning, minicpm4]``) over the chunks of
    a prompt past ``dense_len``, the last a bucket with padding: with the
    kernel in the XLA form's place every pool and state holds what it
    held, and no fallback is noted."""
    from analytics_zoo_tpu.capture.decoder import DecoderSpec, LayeredDecoder
    from perfbench.references import sala_lm as ref
    max_len, chunk, prompt = 1024, 256, 700
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "minicpm_sala.json")) as f:
        cfg = json.load(f)
    cfg.update(
        vocab_size=97, hidden_size=64, intermediate_size=96, head_dim=D,
        num_attention_heads=4, num_key_value_heads=2, lightning_nh=4,
        lightning_nkv=4, lightning_head_dim=D, num_hidden_layers=4,
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"],
        n_positions=max_len, param_dtype="float32", dim_model_base=32,
        sparse_attention=dict(kernel_size=32, kernel_stride=16,
                              block_size=PAGE, init_blocks=1,
                              window_size=256, topk=2, dense_len=512))
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, max_len),
                        prefill_chunk=chunk)
    weights = ref.init_weights(cfg, 5)
    lm.set_params(weights)
    rng = np.random.default_rng(12)
    tokens = rng.integers(0, 97, prompt)
    width, held = max_len // PAGE, -(-prompt // PAGE)
    row = jnp.zeros((width,), jnp.int32).at[:held].set(
        jnp.asarray(1 + rng.permutation(held), jnp.int32))

    def prefill():
        caches = lm.init_paged_caches(1 + width, PAGE, slots=2)
        step = jax.jit(lm.prefill_chunk)
        for start, t in lm.chunk_plan(prompt):
            n = min(t, prompt - start)
            padded = np.zeros((1, t), np.int32)
            padded[0, :n] = tokens[start:start + n]
            caches = step(weights, padded, caches, row, 1, start, n)
        return caches

    assert [t for _, t in lm.chunk_plan(prompt)] == [256, 256, 256]
    want = prefill()
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(SA, "CHUNK_KERNEL_BLOCKS", BLOCKS)
    with pltpu.force_tpu_interpret_mode():
        got = prefill()
    assert dispatch.fallbacks_seen() == []
    live = np.asarray(row)[:held]
    for mine, theirs in zip(got, want):
        for name in mine:
            a, b = np.asarray(mine[name]), np.asarray(theirs[name])
            if name != "state":
                a, b = a[live], b[live]
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                       err_msg=name)


# -- the rule ---------------------------------------------------------------------------

@pytest.mark.parametrize("why,q,pool,dtype,width,blocks", [
    ("128 lanes", (128, KV, G, 64), (9, PAGE, KV * 64), jnp.float32, 8,
     BLOCKS),
    ("no whole blocks", (192, KV, G, D), (9, PAGE, KV * D), jnp.float32, 8,
     BLOCKS),
    ("no whole blocks", (128, KV, G, D), (9, PAGE, KV * D), jnp.float32, 8,
     (64, 128)),
    ("no whole blocks", (128, KV, G, D), (9, 48, KV * D), jnp.bfloat16, 8,
     BLOCKS),
    ("not whole (8, 128) tiles", (128, KV, G, D), (9, 4, KV * D),
     jnp.float32, 8, BLOCKS),
    ("scalar prefetch budget", (128, KV, G, D), (9, PAGE, KV * D),
     jnp.float32, 1 << 17, BLOCKS),
    ("key block codes", (128, KV, G, D), (9, PAGE, KV * D), jnp.float32,
     60000, BLOCKS),
], ids=["head_of_64", "rows_no_whole_blocks", "mask_rows_of_64",
        "page_of_48", "page_of_4", "wide_table", "codes_past_scalar_memory"])
def test_rules_of_fallback(monkeypatch, why, q, pool, dtype, width, blocks):
    """Each rule names its reason once, on the TPU only, and the XLA form
    answers."""
    cache = {"k": jnp.zeros(pool, dtype), "v": jnp.zeros(pool, dtype)}
    row = jnp.zeros((width,), jnp.int32)
    q = jnp.zeros(q, jnp.float32)
    allowed = jnp.ones((q.shape[0], KV, width), bool)
    monkeypatch.setattr(SA, "CHUNK_KERNEL_BLOCKS", blocks)
    monkeypatch.setattr(dispatch, "_seen", set())
    SA.attend_chunk(SPEC, q, cache, row, 0, allowed)
    assert dispatch.fallbacks_seen() == []
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    for _ in range(2):
        out = SA.attend_chunk(SPEC, q, cache, row, 0, allowed)
    assert out.shape == q.shape
    (kernel, rule), = dispatch.fallbacks_seen()
    assert kernel == "sparse_chunk_attend" and why in rule, rule


def test_a_program_over_several_devices_takes_the_xla_form(monkeypatch):
    from jax.sharding import Mesh
    pool = jnp.zeros((9, PAGE, KV * D))
    cache = {"k": pool, "v": pool}
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    with dispatch.partitioned_over(mesh):
        SA.attend_chunk(SPEC, jnp.zeros((128, KV, G, D)), cache,
                        jnp.zeros((8,), jnp.int32), 0,
                        jnp.ones((128, KV, 8), bool))
    (kernel, rule), = dispatch.fallbacks_seen()
    assert kernel == "sparse_chunk_attend" and "several devices" in rule
