"""The chunk kernel of ``ops/latent_attention.py`` (``_attend_chunk_kernel``:
one call a layer that walks the stream's key blocks, expands them through
``kv_b`` in VMEM and keeps the running softmax of all the chunk's rows
there) against XLA's loop over tiles (``_attend_chunk_xla``), in interpret
mode on the CPU. Sizes keep the cell's ratios at a quarter: heads of 96 + 32
and 128 numbers over a latent of 128, pages of 16, blocks of 128 rows by 256
keys, chunks of one, two and four sub-blocks of rows (the cell's 512, 1,024
and 2,048). What the chip's compiler makes of it is
``tests/test_tpu_compile.py -k glm5``; what it computes there is the
benchmark's ``correct``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from analytics_zoo_tpu.ops import dispatch
from analytics_zoo_tpu.ops import latent_attention as LA

LAT = LA.LatentSpec(heads=2, q_rank=32, kv_rank=128, nope_dim=96, rope_dim=32,
                    v_dim=128, index_heads=2, index_dim=8, index_topk=40,
                    index_rope_dim=4, chunk_tile=128, attend_tile=256)
PAGE, WIDTH = 16, 48
BLOCKS = (128, 256)


def _chunk(seed, t, start, dtype=jnp.float32):
    """``attend_chunk``'s arguments for a chunk of ``t`` rows from ``start``
    of a stream whose pages lie anywhere in the pool: random scores' bits of
    three values, so that every row ties at the threshold 1 and ``last``
    cuts the ties."""
    rng = np.random.default_rng(seed)
    pool = rng.normal(size=(1 + WIDTH, PAGE, LAT.pool_row))
    pool[..., LAT.row:] = 0.0
    row = 1 + rng.permutation(WIDTH)
    p = {"kv_b": jnp.asarray(rng.normal(size=(128, 2 * 224)) / 11.0, dtype)}
    qn = jnp.asarray(rng.normal(size=(t, 2, 96)) / 8, jnp.float32)
    qr = jnp.asarray(rng.normal(size=(t, 2, 32)) / 8, jnp.float32)
    bits = rng.integers(0, 3, (t, LA._bits_width(LAT, PAGE, WIDTH)))
    last = rng.integers(0, WIDTH * PAGE, t)
    return [LAT, p, qn, qr, jnp.asarray(pool, dtype),
            jnp.asarray(row, jnp.int32), start, jnp.asarray(bits, jnp.uint32),
            jnp.ones(t, jnp.uint32), jnp.asarray(last, jnp.int32)]


def _both(monkeypatch, args, blocks=BLOCKS):
    """``(the XLA loop's result, the kernel's)`` of ``attend_chunk(*args)``;
    the kernel's is taken as on one TPU chip, and no fallback is noted."""
    want = LA._attend_chunk_xla(LAT, args[1]["kv_b"], *args[2:])
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(LA, "CHUNK_KERNEL_BLOCKS", blocks)
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(LA.attend_chunk, static_argnums=(0,))(*args)
    assert dispatch.fallbacks_seen() == []
    assert got.shape == want.shape and got.dtype == jnp.float32
    return np.asarray(want), np.asarray(got)


@pytest.mark.parametrize("start", [0, 128, 448],
                         ids=["first", "one_block_in", "several_in"])
@pytest.mark.parametrize("rows", [128, 256, 512])
def test_kernel_agrees_with_the_xla_loop(monkeypatch, rows, start):
    """Chunks of one, two and four sub-blocks of rows: a prompt's first, one
    that starts a key block in, and one that starts several blocks in at a
    page that is no multiple of the block (sub-blocks then end inside key
    blocks)."""
    if start + rows > WIDTH * PAGE:
        start = WIDTH * PAGE - rows
    want, got = _both(monkeypatch, _chunk(rows + start, rows, start))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.all(np.isfinite(got))


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (256, 384)])
def test_other_blocks_give_the_same_result(monkeypatch, blocks):
    """Square blocks, more rows than keys, and key blocks that the chunk's
    start and the tiles of the XLA loop do not divide."""
    want, got = _both(monkeypatch, _chunk(4, 512, 192), blocks)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_rows_past_the_prompt_read_the_null_page(monkeypatch):
    """A chunk whose last rows are padding: their pages are the null page
    (0) in the table row, and every row, real or not, comes out as the XLA
    loop gives it."""
    args = _chunk(5, 256, 256)
    args[5] = args[5].at[(256 + 150) // PAGE + 1:].set(0)
    want, got = _both(monkeypatch, args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_pages_out_of_order_are_read_through_the_table(monkeypatch):
    """The same stream with its pages in order and scattered over the pool:
    the same result, so the kernel reads by the table row and not by
    address."""
    scattered = _chunk(6, 256, 128)
    ordered = list(scattered)
    ordered[5] = jnp.arange(1, 1 + WIDTH, dtype=jnp.int32)
    pool = np.zeros_like(np.asarray(scattered[4]))
    pool[1:] = np.asarray(scattered[4])[np.asarray(scattered[5])]
    ordered[4] = jnp.asarray(pool)
    assert not np.array_equal(ordered[5], scattered[5])
    _, got = _both(monkeypatch, scattered)
    _, same = _both(monkeypatch, ordered)
    np.testing.assert_array_equal(got, same)


def test_a_bfloat16_pool_stays_within_one_pass(monkeypatch):
    want, got = _both(monkeypatch, _chunk(7, 256, 128, dtype=jnp.bfloat16))
    np.testing.assert_allclose(got, want, atol=2e-2)


def test_ties_at_the_threshold_are_cut_at_last(monkeypatch):
    """Every score ties with the threshold: a row sees exactly the positions
    up to ``last`` (and its own), one row none at all, which gives zeros as
    in the XLA loop."""
    args = _chunk(8, 128, 128)
    args[7] = jnp.ones_like(args[7])
    args[9] = args[9].at[3].set(-1)          # a row that selects nothing
    args[9] = args[9].at[4].set(0)           # one that selects a single key
    want, got = _both(monkeypatch, args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not got[3].any() and got[4].any()


def test_the_selection_of_select_chunk_is_what_the_kernel_attends(
        monkeypatch):
    """Index scores through ``select_chunk`` into the kernel: rows past
    ``index_topk`` positions keep exactly ``index_topk`` keys."""
    args = _chunk(9, 128, 256)
    rng = np.random.default_rng(9)
    scores = jnp.asarray(rng.normal(size=args[7].shape).round(1), jnp.float32)
    pos = np.arange(scores.shape[1])
    at_t = 256 + np.arange(128)
    bits = jnp.where(pos[None] <= at_t[:, None], LA._sortable(scores),
                     jnp.uint32(0))
    threshold, last = LA.select_chunk(LAT, bits, 256)
    seen = LA._selected(bits, jnp.asarray(pos), threshold, last,
                        jnp.asarray(at_t))
    assert (np.asarray(seen).sum(axis=1) == LAT.index_topk).all()
    args[7:] = [bits, threshold, last]
    want, got = _both(monkeypatch, args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_rule_names_what_the_kernel_cannot_take(monkeypatch):
    monkeypatch.setattr(LA, "CHUNK_KERNEL_BLOCKS", BLOCKS)
    width = LA._bits_width(LAT, PAGE, WIDTH)
    assert LA._chunk_kernel_rule(LAT, 256, PAGE, WIDTH, width) is None
    assert "no whole blocks" in LA._chunk_kernel_rule(
        LAT, 200, PAGE, WIDTH, width)
    assert "no whole blocks" in LA._chunk_kernel_rule(
        LAT, 256, 48, WIDTH, width)                  # pages that split a block
    narrow = dataclasses.replace(LAT, nope_dim=32)   # heads of 64 numbers
    assert "128 lanes" in LA._chunk_kernel_rule(narrow, 256, PAGE, WIDTH,
                                                width)
    assert "scalar memory" in LA._chunk_kernel_rule(
        LAT, 256, PAGE, LA.PAGED_DECODE_TABLE_BYTES, width)


def test_a_refused_or_partitioned_chunk_takes_the_xla_loop(monkeypatch):
    """On the TPU a chunk the rule refuses, and any chunk of a program over
    several devices, runs the XLA loop and says so once, by its rule."""
    args = _chunk(10, 64, 64)                       # no whole block of rows
    want = LA.attend_chunk(*args)
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(LA, "CHUNK_KERNEL_BLOCKS", BLOCKS)
    np.testing.assert_array_equal(LA.attend_chunk(*args), want)
    LA.attend_chunk(*args)
    assert [(kernel, "no whole blocks" in why)
            for kernel, why in dispatch.fallbacks_seen()] \
        == [("latent_chunk_attend", True)]
    whole = _chunk(10, 128, 128)
    want = LA._attend_chunk_xla(LAT, whole[1]["kv_b"], *whole[2:])
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    with dispatch.partitioned_over(mesh):
        np.testing.assert_array_equal(LA.attend_chunk(*whole), want)
    assert ["several devices" in why
            for _, why in dispatch.fallbacks_seen()] == [False, True]


@pytest.mark.parametrize("rows,sub,keys", [(2048, 512, 512), (1024, 512, 512),
                                           (512, 512, 512), (2048, 256, 1024),
                                           (256, 128, 64)])
def test_blocks_visited_are_the_blocks_some_row_sees(rows, sub, keys):
    """``blocks_visited`` and ``seen_from`` against the causal mask itself:
    a (sub-block of rows, key block) pair is visited exactly where the mask
    is not empty."""
    for start in (0, 64, 512, 2048, 2112, 8192, 30720):
        at_t = start + np.arange(rows)
        blocks = (start + rows - 1) // keys + 1
        seen = np.arange(blocks * keys)[None] <= at_t[:, None]
        pairs = seen.reshape(rows // sub, sub, blocks, keys).any(axis=(1, 3))
        visited, dense = LA.blocks_visited(start, rows, sub, keys)
        assert (visited, dense) == (int(pairs.sum()), pairs.size)
        for j in range(blocks):
            first = int(LA.seen_from(start, j, sub, keys))
            assert not pairs[:first, j].any() and pairs[first:, j].all()
    # a prompt's first chunk of 2,048 rows in blocks of 512 x 512: 6 of 16
    assert LA.blocks_visited(0, 2048, 512, 512) == (10, 16)


def test_the_decoders_chunks_through_the_kernel(monkeypatch):
    """GLM-5's layers at a tiny size whose heads are whole lanes: a prompt
    of 700 positions fed as a chunk of 512 and one of 256 whose last 68
    rows are padding, with the kernel in every layer's attention, leaves
    the pools that the XLA loop leaves (a later layer's rows and index keys
    come of the earlier layers' attention)."""
    import json
    import os
    from analytics_zoo_tpu.capture.decoder import DecoderSpec, LayeredDecoder
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs", "glm_5.json")) as f:
        cfg = json.load(f)
    cfg.update(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=2,
        num_key_value_heads=2, q_lora_rank=32, kv_lora_rank=128,
        qk_nope_head_dim=96, qk_rope_head_dim=32, qk_head_dim=128,
        v_head_dim=128, index_n_heads=2, index_head_dim=32, index_topk=48,
        n_routed_experts=8, n_routed_experts_published=8, held_experts=None,
        num_experts_per_tok=2, num_hidden_layers=3, first_k_dense_replace=1,
        n_positions=1024, param_dtype="float32")
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, 1024, page_len=PAGE),
                        prefill_chunk=512)
    params = lm.init_params(3)
    tokens = np.random.default_rng(3).integers(0, 97, 700)
    row = np.zeros(1024 // PAGE, np.int32)
    row[:44] = 1 + np.random.default_rng(4).permutation(44)

    def fed():
        caches = lm.init_paged_caches(46, PAGE, slots=1)
        for start, width in lm.chunk_plan(700):
            n = min(width, 700 - start)
            padded = np.zeros((1, width), np.int32)
            padded[0, :n] = tokens[start:start + n]
            caches = jax.jit(lm.prefill_chunk)(
                params, padded, caches, jnp.asarray(row), 0, start, n)
        return caches
    assert [w for _, w in lm.chunk_plan(700)] == [512, 256]
    want = fed()
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(LA, "CHUNK_KERNEL_BLOCKS", BLOCKS)
    with pltpu.force_tpu_interpret_mode():
        got = fed()
    assert dispatch.fallbacks_seen() == []
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(b)[1:], np.asarray(a)[1:],
                                   rtol=1e-4, atol=1e-4)
