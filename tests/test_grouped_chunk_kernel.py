"""The grouped-query chunk kernel (``ops/grouped_attention.py
_attend_chunk_kernel``: the pool read in place through the stream's row of
pages, scores and a running softmax in VMEM, key blocks that no row of a
block of queries sees never visited) against the XLA form of
``attend_chunk``, in interpret mode on the CPU: SmallThinker's head layout
(28 query heads over 4 key/value heads of 128) with pages of 16, blocks of
32 rows x 64 keys and a window of 96 in the place of 64, 512 x 512 and
4,096; then one whole ``LayeredDecoder.prefill_chunk`` of a tiny SmallThinker
with the kernel in the XLA form's place, and the rule's fallbacks by reason.
What the chip's compiler makes of it is ``tests/test_tpu_compile.py -k
smallthinker``; what it computes there is the benchmark's ``correct``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.capture.decoder import DecoderSpec, LayeredDecoder
from analytics_zoo_tpu.ops import dispatch
from analytics_zoo_tpu.ops import grouped_attention as GA

KV, G, D, PAGE, WIDTH, WINDOW = 4, 7, 128, 16, 24, 96
BLOCKS = (32, 64)
MAX_LEN = WIDTH * PAGE
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pools(rng, dtype=jnp.float32, in_order=False):
    def pool():
        # the null page holds junk, as in a server whose padding writes there
        return jnp.asarray(rng.normal(size=(1 + WIDTH, PAGE, KV * D)),
                           jnp.float32).at[0].mul(30.0).astype(dtype)
    pages = np.arange(WIDTH) if in_order else rng.permutation(WIDTH)
    return {"k": pool(), "v": pool()}, jnp.asarray(1 + pages, jnp.int32)


def _queries(rng, t):
    return jnp.asarray(rng.normal(size=(t, KV, G, D)), jnp.float32) * D ** -0.5


def _both(monkeypatch, q, cache, row, start, window):
    """``attend_chunk`` as the XLA form and, on a patched TPU in interpret
    mode, as the kernel; no fallback may be noted."""
    want = GA.attend_chunk(q, cache, row, start, window, tile_pages=3)
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(GA, "CHUNK_KERNEL_BLOCKS", BLOCKS)
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(GA.attend_chunk, static_argnames=("window",))(
            q, cache, row, jnp.int32(start), window=window)
    assert dispatch.fallbacks_seen() == []
    return np.asarray(got), np.asarray(want)


# the three chunk widths (512 / 1,024 / 2,048 as 32 / 64 / 128 rows) at a
# start of 0, inside the window, beyond it, and off the key block's multiples
CASES = [(32, 0), (64, 0), (128, 0), (32, 64), (64, 64), (32, 128),
         (128, 256), (64, 320), (32, 48), (64, 80), (32, 352)]


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("t,start", CASES,
                         ids=[f"{t}_rows_from_{s}" for t, s in CASES])
def test_kernel_agrees_with_the_xla_form(monkeypatch, t, start, window):
    rng = np.random.default_rng(1000 * t + start)
    cache, row = _pools(rng)
    got, want = _both(monkeypatch, _queries(rng, t), cache, row, start,
                      window)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_a_chunk_whose_last_rows_are_padding(monkeypatch, window):
    """A prompt of 200 positions fed as 128 + a bucket of 128 with 72 real
    rows: the padding's keys went to the null page and the row names no
    page past the prompt's; the real rows agree and every row is finite."""
    rng = np.random.default_rng(7)
    cache, row = _pools(rng)
    row = row.at[200 // PAGE + 1:].set(0)
    got, want = _both(monkeypatch, _queries(rng, 128), cache, row, 128,
                      window)
    np.testing.assert_allclose(got[:72], want[:72], rtol=1e-5, atol=1e-5)
    assert np.all(np.isfinite(got))


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_a_chunk_past_the_tables_width_reads_its_last_page(monkeypatch,
                                                           window):
    """Padding may run past the positions the table names: both forms read
    the table's last page there."""
    rng = np.random.default_rng(8)
    cache, row = _pools(rng)
    got, want = _both(monkeypatch, _queries(rng, 64), cache, row,
                      MAX_LEN - 32, window)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("in_order", [True, False],
                         ids=["pages_in_order", "pages_out_of_order"])
def test_the_row_of_pages_is_followed(monkeypatch, in_order):
    rng = np.random.default_rng(9)
    cache, row = _pools(rng, in_order=in_order)
    got, want = _both(monkeypatch, _queries(rng, 64), cache, row, 192,
                      WINDOW)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_a_bfloat16_pool_stays_within_one_pass(monkeypatch, window):
    rng = np.random.default_rng(10)
    cache, row = _pools(rng, jnp.bfloat16)
    got, want = _both(monkeypatch, _queries(rng, 64), cache, row, 128,
                      window)
    np.testing.assert_allclose(got, want, atol=3e-2)


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_blocks_no_row_sees_are_not_visited(window):
    """Pages behind the window of a block's first row and pages after its
    last row hold NaN: a kernel that fetched one, masked or not, would
    carry it into the weighted sum (0 x NaN)."""
    rng = np.random.default_rng(11)
    cache, row = _pools(rng, in_order=True)
    t, start = 64, 256
    low = 0 if window is None else (start - window + 1) // BLOCKS[1] * 4
    dead = np.r_[1:1 + low, 1 + (start + t) // PAGE:1 + WIDTH]
    cache = {name: pool.at[dead].set(jnp.nan)
             for name, pool in cache.items()}
    with pltpu.force_tpu_interpret_mode():
        got = GA._attend_chunk_kernel(_queries(rng, t), cache["k"],
                                      cache["v"], row, start, window, BLOCKS)
    assert np.all(np.isfinite(np.asarray(got)))


# -- a whole chunk program ------------------------------------------------------------

def test_a_whole_prefill_chunk_takes_the_kernel_on_the_tpu(monkeypatch):
    """``LayeredDecoder.prefill_chunk`` of a tiny SmallThinker (heads of
    128, ``[full, window, window, window]``) over three chunks of a prompt,
    the last a smaller bucket with padding: with the kernel in the XLA
    form's place every pool holds what it held, and no fallback is noted."""
    from perfbench.references import smallthinker_lm as ref
    page, max_len, chunk, prompt = 8, 128, 32, 75
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "smallthinker_21b.json")) as f:
        cfg = json.load(f)
    cfg.update(
        vocab_size=97, hidden_size=64, moe_ffn_hidden_size=32, head_dim=128,
        num_attention_heads=4, num_key_value_heads=2,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        sliding_window_size=32, num_hidden_layers=4,
        sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
        n_positions=max_len, param_dtype="float32")
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, max_len, page_len=page),
                        prefill_chunk=chunk)
    weights = ref.init_weights(cfg, 5)
    lm.set_params(weights)
    rng = np.random.default_rng(12)
    tokens = rng.integers(0, 97, prompt)
    width, held = max_len // page, -(-prompt // page)
    # pages that the window layers' smaller pool holds too, out of order
    assert lm.window_pages(2) > held + 2
    row = jnp.zeros((width,), jnp.int32).at[:held + 2].set(
        jnp.asarray(1 + rng.permutation(held + 2), jnp.int32))

    def prefill():
        caches = lm.init_paged_caches(1 + width, page, slots=2)
        step = jax.jit(lm.prefill_chunk)
        for start, t in lm.chunk_plan(prompt):
            n = min(t, prompt - start)
            padded = np.zeros((1, t), np.int32)
            padded[0, :n] = tokens[start:start + n]
            caches = step(weights, padded, caches, (row, row), 1, start, n)
        return caches

    assert [t for _, t in lm.chunk_plan(prompt)] == [32, 32, 16]
    want = prefill()
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(GA, "CHUNK_KERNEL_BLOCKS", (16, 32))
    with pltpu.force_tpu_interpret_mode():
        got = prefill()
    assert dispatch.fallbacks_seen() == []
    live = np.asarray(row)[:held]
    for mine, theirs in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(mine)[live],
                                   np.asarray(theirs)[live],
                                   rtol=2e-4, atol=2e-4)


# -- the rule ---------------------------------------------------------------------------

@pytest.mark.parametrize("why,q,pool,dtype", [
    ("128 lanes", (64, KV, G, 64), (9, PAGE, KV * 64), jnp.float32),
    ("no whole blocks", (48, KV, G, D), (9, PAGE, KV * D), jnp.float32),
    ("no whole blocks", (8, KV, G, D), (9, PAGE, KV * D), jnp.bfloat16),
    ("no whole blocks", (64, KV, G, D), (9, 24, KV * D), jnp.float32),
    ("not whole (8, 128) tiles", (64, KV, G, D), (9, 4, KV * D),
     jnp.float32),
    ("scalar prefetch budget", (64, KV, G, D), (9, PAGE, KV * D),
     jnp.float32),
], ids=["head_of_64", "rows_no_whole_blocks", "bf16_rows_of_8",
        "page_of_24", "page_of_4", "wide_table"])
def test_rules_of_fallback(monkeypatch, why, q, pool, dtype):
    """Each rule names its reason once, on the TPU only, and the XLA form
    answers."""
    cache = {"k": jnp.zeros(pool, dtype), "v": jnp.zeros(pool, dtype)}
    row = jnp.zeros((1 << 17 if "scalar" in why else 8,), jnp.int32)
    q = jnp.zeros(q, jnp.float32)
    monkeypatch.setattr(GA, "CHUNK_KERNEL_BLOCKS", BLOCKS)
    monkeypatch.setattr(dispatch, "_seen", set())
    GA.attend_chunk(q, cache, row, 0, WINDOW)
    assert dispatch.fallbacks_seen() == []
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    for _ in range(2):
        out = GA.attend_chunk(q, cache, row, 0, WINDOW)
    assert out.shape == q.shape
    (kernel, rule), = dispatch.fallbacks_seen()
    assert kernel == "grouped_chunk_attend" and why in rule


def test_a_program_over_several_devices_takes_the_xla_form(monkeypatch):
    from jax.sharding import Mesh
    pool = jnp.zeros((9, PAGE, KV * D))
    cache = {"k": pool, "v": pool}
    monkeypatch.setattr(dispatch, "_seen", set())
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    with dispatch.partitioned_over(mesh):
        GA.attend_chunk(jnp.zeros((64, KV, G, D)), cache,
                        jnp.zeros((8,), jnp.int32), 0, None)
    (kernel, rule), = dispatch.fallbacks_seen()
    assert kernel == "grouped_chunk_attend" and "several devices" in rule
