"""Paged KV engine, op + LM level: bit-identity with the contiguous slot
engine, int8 pool error bounds, page-table plumbing, the runtime-checkable
overflow guard, and speculative decoding's token-identity guarantee.

The scheduler-level counterparts (paged GenerativeServing parity, CoW
shared prefixes, page-pool chaos) live in tests/test_paged_serving.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.decode import (
    cached_attention, checked_cached_attention, init_kv_cache,
    init_paged_pool, init_slot_cache, page_copy, page_table_clear,
    page_table_set, paged_attention, paged_gather, paged_insert,
    slot_attention, slot_insert, spec_accept_greedy)

H, D, MAX_LEN, PL = 2, 4, 32, 8        # heads, head_dim, max_len, page_len
WIDTH = MAX_LEN // PL                   # table columns


def _private_tables(slots):
    """One table per slot over disjoint pages 1..slots*WIDTH (page 0 is
    the null page, never handed out)."""
    table = np.zeros((slots, WIDTH), np.int32)
    for s in range(slots):
        table[s] = 1 + s * WIDTH + np.arange(WIDTH)
    return jnp.asarray(table)


class TestPagedBitIdentity:
    def test_paged_attention_matches_slot_attention_bitwise(self, ctx):
        """The tentpole invariant: mixed-length decode over the page pool
        is bit-identical to the contiguous slot rectangles — prefill via
        insert, then several steps with one empty slot joining late."""
        rs = np.random.RandomState(0)
        slots = 4
        slot_c = init_slot_cache(slots, H, MAX_LEN, D)
        paged_c = init_paged_pool(1 + slots * WIDTH, H, PL, D)
        table = _private_tables(slots)
        lens = [5, 1, 11, 0]            # slot 3 starts EMPTY (length 0)
        for s, n in enumerate(lens):
            if n == 0:
                continue
            k = jnp.asarray(rs.randn(H, n, D), jnp.float32)
            v = jnp.asarray(rs.randn(H, n, D), jnp.float32)
            slot_c = slot_insert(slot_c, s, k, v)
            paged_c = paged_insert(paged_c, table[s], k, v)
        lengths = jnp.asarray(lens, jnp.int32)
        for step in range(6):
            q = jnp.asarray(rs.randn(slots, H, 1, D), jnp.float32)
            k = jnp.asarray(rs.randn(slots, H, 1, D), jnp.float32)
            v = jnp.asarray(rs.randn(slots, H, 1, D), jnp.float32)
            ctx_s, slot_c = jax.jit(slot_attention)(q, k, v, slot_c,
                                                    lengths)
            ctx_p, paged_c = jax.jit(
                paged_attention, static_argnames=("max_len",))(
                    q, k, v, paged_c, table, lengths, max_len=MAX_LEN)
            np.testing.assert_array_equal(np.asarray(ctx_s),
                                          np.asarray(ctx_p))
            lengths = lengths + 1
        # the pool holds exactly what the rectangles hold, page-gathered
        k_log, v_log = paged_gather(paged_c, table, H)
        np.testing.assert_array_equal(np.asarray(k_log),
                                      np.asarray(slot_c["k"]))
        np.testing.assert_array_equal(np.asarray(v_log),
                                      np.asarray(slot_c["v"]))

    def test_paged_insert_roundtrips_through_gather(self, ctx):
        rs = np.random.RandomState(1)
        cache = init_paged_pool(1 + WIDTH, H, PL, D)
        table = _private_tables(1)
        k = jnp.asarray(rs.randn(H, 13, D), jnp.float32)
        v = jnp.asarray(rs.randn(H, 13, D), jnp.float32)
        cache = paged_insert(cache, table[0], k, v)
        k_log, v_log = paged_gather(cache, table, H)
        np.testing.assert_array_equal(np.asarray(k_log[0, :, :13]),
                                      np.asarray(k))
        np.testing.assert_array_equal(np.asarray(v_log[0, :, :13]),
                                      np.asarray(v))
        # the start offset lands a suffix block at its logical positions
        k2 = jnp.asarray(rs.randn(H, 3, D), jnp.float32)
        cache = paged_insert(cache, table[0], k2, k2, start=13)
        k_log, _ = paged_gather(cache, table, H)
        np.testing.assert_array_equal(np.asarray(k_log[0, :, 13:16]),
                                      np.asarray(k2))
        # positions 0..12 are untouched by the suffix write
        np.testing.assert_array_equal(np.asarray(k_log[0, :, :13]),
                                      np.asarray(k))

    def test_null_page_absorbs_out_of_allocation_writes(self, ctx):
        """Positions past the table width scatter onto page 0 and never
        corrupt an allocated page — the contiguous engine's 'inactive
        slots write harmlessly' contract, transplanted."""
        rs = np.random.RandomState(2)
        cache = init_paged_pool(1 + WIDTH, H, PL, D)
        table = _private_tables(1)
        k = jnp.asarray(rs.randn(H, MAX_LEN, D), jnp.float32)
        cache = paged_insert(cache, table[0], k, k)
        before = np.asarray(cache["k"][1:])
        q = jnp.asarray(rs.randn(1, H, 1, D), jnp.float32)
        kn = jnp.asarray(rs.randn(1, H, 1, D), jnp.float32)
        # write position MAX_LEN + 3: beyond every table column
        _, cache = paged_attention(q, kn, kn, cache, table,
                                   jnp.asarray([MAX_LEN + 3], jnp.int32),
                                   MAX_LEN)
        np.testing.assert_array_equal(np.asarray(cache["k"][1:]), before)


class TestPageTableOps:
    def test_set_and_clear(self, ctx):
        table = jnp.zeros((3, WIDTH), jnp.int32)
        row = jnp.asarray(np.arange(1, WIDTH + 1, dtype=np.int32))
        table = page_table_set(table, 1, row)
        assert np.asarray(table[1]).tolist() == list(range(1, WIDTH + 1))
        assert np.asarray(table[0]).sum() == 0
        table = page_table_clear(table, jnp.asarray([False, True, False]))
        assert np.asarray(table).sum() == 0

    def test_page_copy_f32_and_int8_scales(self, ctx):
        rs = np.random.RandomState(3)
        for int8 in (False, True):
            cache = init_paged_pool(4, H, PL, D, int8=int8)
            k = jnp.asarray(rs.randn(H, PL, D), jnp.float32)
            row = jnp.asarray([1, 0, 0, 0], jnp.int32)
            cache = paged_insert(cache, row, k, k)
            cache = page_copy(cache, 1, 2)
            np.testing.assert_array_equal(np.asarray(cache["k"][2]),
                                          np.asarray(cache["k"][1]))
            if int8:
                np.testing.assert_array_equal(
                    np.asarray(cache["scale_k"][2]),
                    np.asarray(cache["scale_k"][1]))


class TestInt8PagedPool:
    def test_int8_error_bounded_by_quant_step(self, ctx):
        """int8 pool round-trip error is bounded by half a quantization
        step per position (inline amax on prefill writes)."""
        rs = np.random.RandomState(4)
        cache = init_paged_pool(1 + WIDTH, H, PL, D, int8=True)
        table = _private_tables(1)
        k = rs.randn(H, MAX_LEN, D).astype(np.float32)
        v = rs.randn(H, MAX_LEN, D).astype(np.float32)
        cache = paged_insert(cache, table[0], jnp.asarray(k),
                             jnp.asarray(v))
        k_log, v_log = paged_gather(cache, table, H)
        # the inline scale is scalar per write (block amax / 127), so the
        # round-trip error is bounded by half a quantization step
        half_k = max(1.0, np.abs(k).max()) / 127.0 / 2.0
        assert np.abs(np.asarray(k_log[0]) - k).max() <= half_k + 1e-7
        half_v = max(1.0, np.abs(v).max()) / 127.0 / 2.0
        assert np.abs(np.asarray(v_log[0]) - v).max() <= half_v + 1e-7

    @pytest.mark.slow
    def test_int8_decode_context_close_to_f32(self, ctx):
        rs = np.random.RandomState(5)
        f32 = init_paged_pool(1 + 2 * WIDTH, H, PL, D)
        i8 = init_paged_pool(1 + 2 * WIDTH, H, PL, D, int8=True)
        table = _private_tables(2)
        lengths = jnp.asarray([6, 2], jnp.int32)
        for s, n in enumerate((6, 2)):
            k = jnp.asarray(rs.randn(H, n, D), jnp.float32)
            v = jnp.asarray(rs.randn(H, n, D), jnp.float32)
            f32 = paged_insert(f32, table[s], k, v)
            i8 = paged_insert(i8, table[s], k, v)
        for _ in range(4):
            q = jnp.asarray(rs.randn(2, H, 1, D), jnp.float32)
            k = jnp.asarray(rs.randn(2, H, 1, D), jnp.float32)
            v = jnp.asarray(rs.randn(2, H, 1, D), jnp.float32)
            ctx_f, f32 = paged_attention(q, k, v, f32, table, lengths,
                                         MAX_LEN)
            ctx_q, i8 = paged_attention(q, k, v, i8, table, lengths,
                                        MAX_LEN)
            np.testing.assert_allclose(np.asarray(ctx_q),
                                       np.asarray(ctx_f), atol=0.08)
            lengths = lengths + 1


class TestCheckedOverflowGuard:
    def test_eager_guard_still_raises(self, ctx):
        cache = init_kv_cache(1, H, 4, D)
        q = jnp.zeros((1, H, 6, D))
        with pytest.raises(ValueError, match="KV cache overflow"):
            cached_attention(q, q, q, cache)

    def test_overflow_caught_under_jit(self, ctx):
        """The documented gap in cached_attention's guard (tracer lengths
        skip it) is closed by checked_cached_attention + checkify: the
        predicate rides THROUGH jit and throws at runtime."""
        from jax.experimental import checkify
        cache = init_kv_cache(1, H, 8, D)
        q = jnp.zeros((1, H, 1, D))

        @jax.jit
        def step(cache, q):
            err, out = checkify.checkify(checked_cached_attention)(
                q, q, q, cache)
            return err, out

        # in-capacity write: no error, bit-identical to the unchecked op
        cache_ok = dict(cache, length=jnp.asarray(4))
        err, (ctx_c, new_c) = step(cache_ok, q)
        err.throw()                      # no-op
        ctx_u, _ = cached_attention(q, q, q, cache_ok)
        np.testing.assert_array_equal(np.asarray(ctx_c), np.asarray(ctx_u))
        # overflowing write: the SILENT-corruption case without checkify
        cache_bad = dict(cache, length=jnp.asarray(8))
        err, _ = step(cache_bad, q)
        with pytest.raises(Exception, match="KV cache overflow"):
            err.throw()


class TestSpeculative:
    def test_spec_accept_greedy_rule(self, ctx):
        v = 8
        drafts = jnp.asarray([[1, 2, 3], [5, 0, 0], [4, 7, 1]], jnp.int32)
        # target argmax rows: [1,2,9?]: build logits whose argmax is given
        g_want = np.asarray([[1, 2, 3, 6],   # all match -> n=4 (bonus)
                             [5, 1, 0, 0],   # first matches only -> n=2
                             [2, 7, 1, 3]])  # first mismatch -> n=1
        logits = np.full((3, 4, v), -5.0, np.float32)
        for s in range(3):
            for j in range(4):
                logits[s, j, g_want[s, j]] = 5.0
        g, n = spec_accept_greedy(drafts, jnp.asarray(logits))
        np.testing.assert_array_equal(np.asarray(g), g_want)
        assert np.asarray(n).tolist() == [4, 2, 1]

    def _lms(self):
        from analytics_zoo_tpu.capture.lm import TransformerLM
        rs = np.random.RandomState(7)
        lm = TransformerLM(vocab_size=16, hidden=16, n_block=2, n_head=2,
                           max_len=32, seed=0)
        lm.fit(rs.randint(0, 16, (32, 12)), batch_size=8, epochs=1)
        draft = TransformerLM(vocab_size=16, hidden=16, n_block=2,
                              n_head=2, max_len=64, seed=1)
        draft.fit(rs.randint(0, 16, (32, 12)), batch_size=8, epochs=1)
        return lm, draft

    @pytest.mark.slow
    def test_generate_speculative_token_identical_to_greedy(self, ctx):
        lm, draft = self._lms()
        rs = np.random.RandomState(8)
        prompts = np.stack([rs.randint(0, 16, (5,)) for _ in range(3)])
        want = lm.generate(prompts, max_new_tokens=10)
        got = lm.generate_speculative(prompts, draft, max_new_tokens=10,
                                      spec_k=3, page_len=8)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.slow
    def test_generate_speculative_eos_and_one_token_prompt(self, ctx):
        lm, draft = self._lms()
        eos = 1
        prompts = np.asarray([[3], [7]])
        want = lm.generate(prompts, max_new_tokens=12, eos_id=eos)
        got = lm.generate_speculative(prompts, draft, max_new_tokens=12,
                                      spec_k=4, eos_id=eos, page_len=8)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.slow
    def test_generate_speculative_sampled_is_well_formed(self, ctx):
        """Sampled speculative output follows the accept/resample rule —
        distribution-preserving, not run-identical to serial sampling — so
        the assertion is structural: valid tokens, eos-frozen tails."""
        lm, draft = self._lms()
        eos = 1
        out = lm.generate_speculative(
            np.asarray([[2, 5, 3], [9, 4, 6]]), draft, max_new_tokens=10,
            spec_k=3, eos_id=eos, temperature=0.9, top_k=8, seed=11,
            page_len=8)
        assert out.shape == (2, 10)
        assert out.min() >= 0 and out.max() < 16
        for row in out:
            row = row.tolist()
            if eos in row:   # frozen after the first eos (eos padding)
                assert all(x == eos for x in row[row.index(eos):])
