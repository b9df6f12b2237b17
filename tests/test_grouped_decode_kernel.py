"""The grouped-query paged decode kernel (``ops/grouped_attention.py
_attend_step_kernel``) against the XLA form of ``attend_step``, in interpret
mode on the CPU: SmallThinker's head layout (28 query heads over 4
key/value heads of 128, bfloat16-sized tiles: pages of 16 rows here) cut to
a table 20 pages wide, two and a half of the kernel's chunks, full and
window masks. What the chip's compiler makes of it is
``tests/test_tpu_compile.py -k smallthinker``; what it computes there is the
benchmark's ``correct``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import dispatch
from analytics_zoo_tpu.ops import grouped_attention as GA

KV, G, D, PAGE, WIDTH, SLOTS, WINDOW = 4, 7, 128, 16, 20, 5, 96
PAGES = 1 + SLOTS * WIDTH
MAX_LEN = WIDTH * PAGE


def _case(name, window):
    rng = np.random.default_rng(sum(map(ord, name)))
    table = 1 + rng.permutation(SLOTS * WIDTH).reshape(SLOTS, WIDTH)
    lengths = rng.integers(0, MAX_LEN, SLOTS)
    active = np.ones(SLOTS, bool)
    if name.startswith("length_"):
        lengths[[0, -1]] = int(name.split("_")[1])
    elif name == "evicted_slots":
        gone = np.asarray([1, 3])
        lengths[gone], table[gone], active[gone] = 0, 0, False
    elif name == "pages_behind_the_window_gone":
        # as the server's window rows: what lies behind the window is the
        # null page, and so is what lies ahead of the length
        for s in range(SLOTS):
            first = max(lengths[s] - (window or MAX_LEN) + 1, 0) // PAGE
            table[s, :first] = 0
            table[s, lengths[s] // PAGE + 1:] = 0
    return (jnp.asarray(lengths, jnp.int32), jnp.asarray(table, jnp.int32),
            jnp.asarray(active), rng)


CASES = ["length_0", "length_15", "length_16", "length_95", "length_96",
         "length_127", "length_128", f"length_{MAX_LEN - 1}",
         "evicted_slots", "pages_behind_the_window_gone"]


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("name", CASES)
def test_kernel_agrees_with_the_xla_form(monkeypatch, name, window):
    lengths, table, active, rng = _case(name, window)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    # the null page holds junk, as in a server whose empty slots write there
    cache = {"k": normal(PAGES, PAGE, KV * D).at[0].mul(30.0),
             "v": normal(PAGES, PAGE, KV * D).at[0].mul(30.0)}
    q = normal(SLOTS, KV, G, D) * D ** -0.5
    monkeypatch.setattr(dispatch, "_seen", set())
    want = GA.attend_step(q, cache, table, lengths, active, window,
                          tile_pages=3)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(GA.attend_step, static_argnames=("window",))(
            q, cache, table, lengths, active, window=window)
    assert dispatch.fallbacks_seen() == []
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.isfinite(np.asarray(got)))


def test_a_bfloat16_pool_stays_within_one_pass():
    lengths, table, active, rng = _case("length_127", WINDOW)
    pool = jnp.asarray(rng.normal(size=(PAGES, PAGE, KV * D)), jnp.bfloat16)
    cache = {"k": pool, "v": pool[::-1]}
    q = jnp.asarray(rng.normal(size=(SLOTS, KV, G, D)), jnp.float32) / 11.3
    want = GA.attend_step(q, cache, table, lengths, active, WINDOW)
    with pltpu.force_tpu_interpret_mode():
        got = GA._attend_step_kernel(q, cache["k"], cache["v"], table,
                                     lengths, WINDOW)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-2)


@pytest.mark.parametrize("why,shape,table", [
    ("not whole (8, 128) tiles", (PAGES, 4, KV * D), (SLOTS, WIDTH)),
    ("not whole (16, 128) tiles", (PAGES, 8, KV * D), (SLOTS, WIDTH)),
    ("scalar prefetch budget", (PAGES, PAGE, KV * D), (512, 256)),
], ids=["page_of_4", "bf16_page_of_8", "wide_table"])
def test_rules_of_fallback(monkeypatch, why, shape, table):
    """Each rule names its reason once, on the TPU only."""
    dtype = jnp.bfloat16 if "16, 128" in why else jnp.float32
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    table = jnp.zeros(table, jnp.int32)
    monkeypatch.setattr(dispatch, "_seen", set())
    assert not GA._reads_in_place(cache, table)
    assert dispatch.fallbacks_seen() == []
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    for _ in range(2):
        assert not GA._reads_in_place(cache, table)
    (kernel, rule), = dispatch.fallbacks_seen()
    assert kernel == "grouped_paged_decode" and why in rule
