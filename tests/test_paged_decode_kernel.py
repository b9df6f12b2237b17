"""The paged decode kernel (``ops/decode.py paged_decode_context``) against
the XLA form of ``paged_attention``, in interpret mode on the CPU.

GPT-2 small's shapes (12 heads of 64, pages of 16 positions, float32 pools
stored ``[P, 16, 768]``) cut to a table 12 pages wide, which is a chunk and
a half of the kernel's, so the walk crosses a chunk's end. What the chip's
compiler makes of the kernel is ``tests/test_tpu_compile.py -k page_pools``;
what it computes there is the benchmark's ``correct``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import decode, dispatch

HEADS, DIM, PAGE, WIDTH, PAGES, SLOTS = 12, 64, 16, 12, 80, 6
MAX_LEN = WIDTH * PAGE


def _case(name):
    """``(lengths [S], table [S, W], pool rng seed)`` of one case. Every
    slot's row names distinct pages unless the case says otherwise; the
    null page (0) holds junk in every case, as it does in a server whose
    inactive slots write there."""
    rng = np.random.default_rng(sum(map(ord, name)))
    table = rng.permutation(np.arange(1, PAGES))[:SLOTS * WIDTH].reshape(
        SLOTS, WIDTH)
    lengths = rng.integers(0, MAX_LEN, SLOTS)
    if name.startswith("length_"):
        # the named length in slot 0 and again in the last slot, between
        # slots of other lengths
        lengths[[0, -1]] = int(name.split("_")[1])
    elif name == "evicted_slots":
        # slot_evict + page_table_clear: length 0 and a row of null pages
        gone = np.asarray([1, 2, 4])
        lengths[gone] = 0
        table[gone] = 0
    elif name == "all_evicted":
        lengths[:] = 0
        table[:] = 0
    elif name == "shared_prefix":
        # slots 1 and 3 share their first three pages (a registered
        # prefix) and go on in pages of their own
        table[3, :3] = table[1, :3]
        lengths[[1, 3]] = [5 * PAGE + 3, 3 * PAGE]
    elif name == "rows_end_in_null_pages":
        # a stream holds only the pages its length needs; the rest of its
        # row is the null page
        for s in range(SLOTS):
            table[s, lengths[s] // PAGE + 1:] = 0
    else:
        raise AssertionError(name)
    return (jnp.asarray(lengths, jnp.int32), jnp.asarray(table, jnp.int32),
            rng)


CASES = ["length_0", "length_15", "length_16", "length_17",
         f"length_{MAX_LEN - 1}", "evicted_slots", "all_evicted",
         "shared_prefix", "rows_end_in_null_pages"]


@pytest.mark.parametrize("name", CASES)
def test_kernel_agrees_with_the_xla_form(monkeypatch, name):
    """Contexts agree to float32 tolerance when the kernel multiplies in
    float32, and to one bfloat16 pass's as it runs on the chip; through
    ``paged_attention`` the pool that comes back is the XLA form's bit for
    bit, and the page count is the live pages'."""
    lengths, table, rng = _case(name)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    cache = {"k": normal(PAGES, PAGE, HEADS * DIM).at[0].mul(30.0),
             "v": normal(PAGES, PAGE, HEADS * DIM).at[0].mul(30.0)}
    q, k_new, v_new = (normal(SLOTS, HEADS, 1, DIM) for _ in range(3))
    attend = jax.jit(decode.paged_attention, static_argnames=("max_len",))
    monkeypatch.setattr(dispatch, "_seen", set())
    want_ctx, want_cache = attend(q, k_new, v_new, cache, table, lengths,
                                  max_len=MAX_LEN)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        got_ctx, got_cache = jax.jit(
            decode.paged_attention, static_argnames=("max_len",))(
            q, k_new, v_new, cache, table, lengths, max_len=MAX_LEN)
        exact = jax.jit(decode.paged_decode_context, static_argnames=(
            "scale", "product_dtype"))(
            q, want_cache["k"], want_cache["v"], table, lengths,
            scale=DIM ** -0.5, product_dtype=jnp.float32)
        read = decode.paged_pages_read(cache, table, lengths, MAX_LEN)
    assert dispatch.fallbacks_seen() == []
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_cache[leaf]),
                                      np.asarray(want_cache[leaf]))
    np.testing.assert_allclose(np.asarray(exact), np.asarray(want_ctx),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_ctx), np.asarray(want_ctx),
                               rtol=0, atol=4e-2)
    assert int(read) == int(np.sum(np.asarray(lengths) // PAGE + 1))


def test_the_count_off_the_chip_is_the_rectangle():
    """Off the TPU the XLA form reads every column of every slot."""
    lengths, table, _ = _case("length_17")
    cache = decode.init_paged_pool(PAGES, HEADS, PAGE, DIM)
    assert int(decode.paged_pages_read(cache, table, lengths, MAX_LEN)) \
        == SLOTS * WIDTH


@pytest.mark.parametrize("why,build", [
    ("int8 pool", lambda: (decode.init_paged_pool(
        PAGES, HEADS, PAGE, DIM, int8=True), (SLOTS, WIDTH))),
    ("not whole (8, 128) tiles", lambda: (decode.init_paged_pool(
        PAGES, HEADS, 4, DIM), (SLOTS, WIDTH))),
    ("scalar prefetch budget", lambda: (decode.init_paged_pool(
        PAGES, HEADS, PAGE, DIM), (512, 256))),
], ids=["int8", "page_of_4", "wide_table"])
def test_rules_of_fallback(monkeypatch, why, build):
    """Each rule names its reason once, on the TPU only."""
    cache, table_shape = build()
    table = jnp.zeros(table_shape, jnp.int32)
    monkeypatch.setattr(dispatch, "_seen", set())
    assert not decode._reads_in_place(cache, table)
    assert dispatch.fallbacks_seen() == []
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    for _ in range(2):
        assert not decode._reads_in_place(cache, table)
    (kernel, rule), = dispatch.fallbacks_seen()
    assert kernel == "paged_decode" and why in rule
