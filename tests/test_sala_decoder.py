"""The layered decoder (MiniCPM-SALA's layers: lightning linear attention
beside block-sparse attention) against its plain reference, at a tiny size
on the CPU: chunked prefill then paged decode agree with the reference's
full forward; the lightning state; the selected blocks; streams joining and
leaving between chunks; what the server refuses for a recurrent model; the
serve loop's chunked prefill (counters, deadlines, page shedding, handoff).

The preset: 4 layers ``[minicpm4, lightning-attn x 3]``, hidden 64, 4 query
heads over 1 key/value head, blocks of 16, a window of 32, top-2, dense up
to 96: beyond 96 positions a selection reads 6 of up to 16 blocks."""
import json
import os
import sys
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from analytics_zoo_tpu.capture.decoder import (DecoderSpec,  # noqa: E402
                                               LayeredDecoder)
from analytics_zoo_tpu.ops import linear_attention as LA  # noqa: E402
from analytics_zoo_tpu.ops import sparse_attention as SA  # noqa: E402
from analytics_zoo_tpu.serving import (GenerativeServing,  # noqa: E402
                                       ServingConfig)
from analytics_zoo_tpu.serving.client import (InputQueue,  # noqa: E402
                                              OutputQueue)
from analytics_zoo_tpu.serving.server import (DEADLINE_ERROR,  # noqa: E402
                                              PAGE_SHED_ERROR)
from perfbench.references import sala_lm as ref  # noqa: E402

PAGE, MAX_LEN, SLOTS = 16, 256, 3


def tiny_cfg():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "minicpm_sala.json")) as f:
        cfg = json.load(f)
    cfg.update(
        vocab_size=97, hidden_size=64, intermediate_size=128, head_dim=16,
        num_attention_heads=4, num_key_value_heads=1, lightning_nh=4,
        lightning_nkv=4, lightning_head_dim=16, num_hidden_layers=4,
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "lightning-attn"],
        dim_model_base=16, n_positions=MAX_LEN, param_dtype="float32",
        sparse_attention=dict(kernel_size=8, kernel_stride=4, block_size=PAGE,
                              init_blocks=1, window_size=32, topk=2,
                              dense_len=96))
    cfg["serving"].update(max_new_tokens=24)
    return cfg


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    weights = ref.init_weights(cfg, 5)
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, MAX_LEN),
                        prefill_chunk=64)
    lm.set_params(weights)
    return cfg, weights, lm, jax.jit(lm.prefill_chunk), \
        jax.jit(lm.paged_state_step)


def _row(first_page, pages):
    row = np.zeros(MAX_LEN // PAGE, np.int32)
    row[:pages] = first_page + np.arange(pages)[::-1]  # not in order
    return row


def _prefill(model, caches, tokens, fed, row, slot, between=None):
    _, weights, lm, chunk, _ = model
    for start, width in lm.chunk_plan(fed):
        n = max(0, min(width, fed - start))
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = tokens[start:start + n]
        caches = chunk(weights, padded, caches, jnp.asarray(row),
                       jnp.int32(slot), jnp.int32(start), jnp.int32(n))
        if between is not None:
            caches = between(caches)
    return caches


def _decode(model, caches, table, lengths, active, feed):
    """One step: ``feed {slot: token}``; returns ``(logits, caches)``."""
    _, weights, _, _, step = model
    tokens = np.zeros(SLOTS, np.int32)
    for slot, token in feed.items():
        tokens[slot] = token
    return step(weights, tokens, jnp.asarray(lengths), jnp.asarray(table),
                caches, jnp.asarray(active))[:2]


# -- chunked prefill then paged decode against the reference's full forward -----

@pytest.mark.parametrize("prompt,new", [
    (150, 20),   # over dense_len; the last chunk off a page boundary
    (129, 8),    # fed = 128: chunk and page boundaries coincide
    (65, 12),    # fed = 64: exactly one whole chunk, under dense_len
    (40, 10),    # one bucketed chunk, under dense_len
    (97, 30),    # crosses dense_len while decoding
    (1, 6),      # nothing to feed: an empty chunk starts the states anew
    (200, 24),   # three whole chunks and a bucket
])
def test_chunked_prefill_then_decode_agrees_with_the_reference(
        model, prompt, new):
    cfg, weights, lm, _, _ = model
    rng = np.random.default_rng(prompt)
    tokens = rng.integers(1, 97, prompt + new).astype(np.int32)
    want = np.asarray(ref.logits(cfg, weights, tokens[None]))[0]
    caches = lm.init_paged_caches(40, PAGE, slots=SLOTS)
    row = _row(3, -(-(prompt + new) // PAGE))
    fed, slot = prompt - 1, 1
    caches = _prefill(model, caches, tokens, fed, row, slot)
    table = np.zeros((SLOTS, MAX_LEN // PAGE), np.int32)
    table[slot] = row
    lengths = np.zeros(SLOTS, np.int32)
    lengths[slot] = fed
    active = np.arange(SLOTS) == slot
    worst = 0.0
    for i in range(new):
        logits, caches = _decode(model, caches, table, lengths, active,
                                 {slot: tokens[fed + i]})
        worst = max(worst, float(np.max(np.abs(
            np.asarray(logits)[slot] - want[fed + i]))))
        lengths[slot] += 1
    assert worst < 2e-6, worst   # logits have a spread of 0.04


def test_two_key_value_heads_and_a_table_that_is_no_whole_tile():
    """Each key/value head reads its own selection and its own columns of
    the gathered rows (the chip's compiler got one form of that wrong:
    ``ops/sparse_attention.py _gather_pages``); a table of 17 pages is
    padded to the chunk attention's tiles of 16."""
    cfg = dict(tiny_cfg(), num_key_value_heads=2, n_positions=272)
    weights = ref.init_weights(cfg, 6)
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, 272),
                        prefill_chunk=64)
    lm.set_params(weights)
    tokens = np.random.default_rng(8).integers(1, 97, 190).astype(np.int32)
    want = np.asarray(ref.logits(cfg, weights, tokens[None]))[0]
    caches = lm.init_paged_caches(30, PAGE, slots=2)
    row = np.zeros(17, np.int32)
    row[:12] = 1 + np.random.default_rng(8).permutation(12)
    chunk, step = jax.jit(lm.prefill_chunk), jax.jit(lm.paged_state_step)
    for start, width in lm.chunk_plan(170):
        n = min(width, 170 - start)
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = tokens[start:start + n]
        caches = chunk(weights, padded, caches, jnp.asarray(row),
                       jnp.int32(1), jnp.int32(start), jnp.int32(n))
    table = np.zeros((2, 17), np.int32)
    table[1] = row
    for i in range(20):
        feed = np.asarray([0, tokens[170 + i]], np.int32)
        logits, caches, read = step(weights, feed,
                                    jnp.asarray([0, 170 + i]),
                                    jnp.asarray(table), caches,
                                    jnp.asarray([False, True]))
        assert float(read) == 6 * PAGE   # beyond dense_len: the selection
        assert float(np.max(np.abs(np.asarray(logits)[1]
                                   - want[170 + i]))) < 2e-6
    # the two heads chose differently somewhere: one selection a head
    spec = lm.spec.sparse
    q = jnp.asarray(np.random.default_rng(1).normal(size=(1, 2, 2, 16)),
                    jnp.float32)
    ids, _ = SA.select_step(spec, q, caches[0]["kc"], jnp.asarray(table[1:]),
                            jnp.asarray([189]))
    assert sorted(np.asarray(ids)[0, 0]) != sorted(np.asarray(ids)[0, 1])


def test_a_bfloat16_model_stays_near_the_float32_reference(model):
    """The published dtype at the tiny size: bfloat16 weights and pages,
    the rest float32; the gap to the reference is rounding, not a fault."""
    cfg = dict(tiny_cfg(), param_dtype="bfloat16")
    weights = ref.init_weights(cfg, 5)
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, MAX_LEN),
                        prefill_chunk=64)
    lm.set_params(weights)
    tokens = np.random.default_rng(2).integers(1, 97, 140).astype(np.int32)
    want = np.asarray(ref.logits(cfg, weights, tokens[None]))[0]
    caches = lm.init_paged_caches(20, PAGE, slots=1)
    assert caches[0]["k"].dtype == jnp.bfloat16
    assert caches[0]["kc"].dtype == caches[1]["state"].dtype == jnp.float32
    row = _row(1, 9)
    for start, width in lm.chunk_plan(130):
        n = min(width, 130 - start)
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = tokens[start:start + n]
        caches = lm.prefill_chunk(weights, padded, caches, jnp.asarray(row),
                                  0, start, n)
    logits, _, _ = lm.paged_state_step(
        weights, tokens[130:131], jnp.asarray([130]), jnp.asarray(row[None]),
        caches, jnp.asarray([True]))
    gap = float(np.max(np.abs(np.asarray(logits)[0] - want[130])))
    assert 0 < gap < 0.02, gap


# -- the lightning state ----------------------------------------------------------

def _plain_scan(q, k, v, slopes):
    decay = jnp.exp(-slopes)[:, None, None]

    def step(state, qkv):
        qt, kt, vt = qkv
        state = decay * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hd,hdv->hv", qt, state)
    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, zero, (q, k, v))


@pytest.mark.parametrize("steps", [1, 7, 33])
def test_the_state_after_n_decode_steps_equals_the_scans(steps):
    rng = np.random.default_rng(steps)
    q, k, v = (jnp.asarray(rng.normal(size=(steps, 4, 16)), jnp.float32)
               for _ in range(3))
    slopes = LA.lightning_slopes(4)
    want_state, want_out = _plain_scan(q, k, v, slopes)
    state = jnp.zeros((2, 4, 16, 16), jnp.float32)
    active = jnp.asarray([True, False])
    for t in range(steps):
        out, state = LA.linear_attention_step(
            jnp.stack([q[t], q[t]]), jnp.stack([k[t], k[t]]),
            jnp.stack([v[t], v[t]]), state, slopes, active)
        np.testing.assert_allclose(out[0], want_out[t], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state[0], want_state, rtol=1e-4, atol=1e-4)
    assert not np.any(np.asarray(state[1]))  # the other slot was not active


@pytest.mark.parametrize("length,valid,block", [
    (64, 64, 16), (64, 37, 16), (64, 0, 16), (32, 16, 32), (48, 47, 16)])
def test_a_padded_chunk_leaves_the_state_of_its_last_real_position(
        length, valid, block):
    rng = np.random.default_rng(length + valid)
    q, k, v = (jnp.asarray(rng.normal(size=(length, 4, 16)), jnp.float32)
               for _ in range(3))
    slopes = LA.lightning_slopes(4)
    before = jnp.asarray(rng.normal(size=(4, 16, 16)), jnp.float32)
    out, after = LA.linear_attention_chunk(q, k, v, before, slopes, valid,
                                           block=block)
    decay = jnp.exp(-slopes)[:, None, None]
    state = before
    for t in range(valid):
        state = decay * state + k[t][:, :, None] * v[t][:, None, :]
        np.testing.assert_allclose(
            out[t], jnp.einsum("hd,hdv->hv", q[t], state), rtol=1e-4,
            atol=1e-4)
    np.testing.assert_allclose(after, state, rtol=1e-4, atol=1e-4)
    assert np.all(np.isfinite(np.asarray(out)))


def test_slopes_are_the_familys():
    slopes = np.asarray(LA.lightning_slopes(32))
    assert slopes[0] == pytest.approx(2 ** -0.25)
    assert slopes[-1] == pytest.approx(2 ** -8)
    assert np.all(np.diff(slopes) < 0)
    np.testing.assert_allclose(slopes, np.asarray(ref.slopes(
        {"lightning_nh": 32})))


# -- the selection ------------------------------------------------------------------

def _selection_inputs(seed, length):
    cfg = tiny_cfg()
    spec = SA.SparseSpec(**cfg["sparse_attention"])
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(length, 1, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(length, 16)), jnp.float32)
    pages = -(-length // PAGE)
    row = np.zeros(MAX_LEN // PAGE, np.int32)
    row[:pages] = 1 + np.random.default_rng(seed).permutation(pages)
    kc = jnp.zeros((20, spec.per_block, 16), jnp.float32)
    kc = SA.compress_chunk(spec, kc, jnp.asarray(row), 0, jnp.pad(
        k, ((0, pages * PAGE - length), (0, 0))), length)
    windows = (length - 8) // 4 + 1
    plain = jnp.stack([k[4 * j:4 * j + 8].mean(axis=0)
                       for j in range(windows)])
    return cfg, spec, q, k, row, kc, plain


@pytest.mark.parametrize("seed,length", [(1, 200), (2, 256), (3, 130),
                                         (4, 97)])
def test_the_selected_blocks_are_the_references(seed, length):
    cfg, spec, q, k, row, kc, plain = _selection_inputs(seed, length)
    blocks = MAX_LEN // PAGE
    t = jnp.arange(length)
    scores = jnp.einsum("tkgd,wd->tgw", q, plain)
    want = np.asarray(ref.block_selection(cfg, scores, t, blocks))[:, 0]
    got = np.asarray(SA.select_chunk(spec, jnp.pad(
        q, ((0, -length % PAGE), (0, 0), (0, 0), (0, 0))), kc,
        jnp.asarray(row), 0))[:length, 0]
    assert (got == want).all()
    # beyond dense_len a query reads 6 of its blocks, and really drops some
    far = np.arange(length) >= spec.dense_len
    assert (got[far].sum(axis=1) == spec.n_selected).all()
    assert (np.arange(length)[far] // PAGE + 1 > spec.n_selected).any()
    # decode's list of blocks holds what the mask holds
    for at in (length - 1, spec.dense_len, spec.dense_len + 17):
        if at >= length:
            continue
        ids, valid = SA.select_step(spec, q[at][None], kc,
                                    jnp.asarray(row)[None],
                                    jnp.asarray([at]))
        assert bool(np.all(valid))
        assert sorted(np.asarray(ids)[0, 0].tolist()) == \
            np.flatnonzero(want[at]).tolist()


def test_compressed_keys_written_step_by_step_equal_the_chunks():
    cfg, spec, q, k, row, kc, plain = _selection_inputs(9, 100)
    table = jnp.asarray(row)[None]
    stepwise = jnp.full(kc.shape, 7.0)   # stale values: nothing is zeroed
    for t in range(100):
        stepwise = SA.compress_step(spec, stepwise, table, jnp.asarray([t]),
                                    k[t][None])
    windows = plain.shape[0]
    for j in range(windows):
        page, slot = row[j // spec.per_block], j % spec.per_block
        np.testing.assert_allclose(stepwise[page, slot], plain[j], atol=1e-6)
        np.testing.assert_allclose(kc[page, slot], plain[j], atol=1e-6)


def test_a_spec_whose_selection_would_overlap_is_refused():
    with pytest.raises(ValueError, match="fewer blocks than a selection"):
        SA.SparseSpec(dense_len=2048)
    with pytest.raises(ValueError, match="kernel_stride must divide"):
        SA.SparseSpec(kernel_stride=24)
    spec = SA.SparseSpec()
    assert spec.n_selected == 98 and spec.dense_blocks == 128


@pytest.mark.parametrize("lengths,active,want", [
    ([170, 200], [True, True], 6 * PAGE),     # every stream beyond dense_len
    ([170, 40], [True, True], 10 * PAGE),     # one still dense: its own pages
    ([170, 40], [True, False], 6 * PAGE),     # the dense slot is not active
])
def test_the_step_counts_the_positions_its_gather_read(lengths, active, want):
    """``attend_step`` returns the extent of the keys it gathered, from the
    branch that ran: the server's ``serving.sparse_positions_read``."""
    spec = SA.SparseSpec(kernel_size=8, kernel_stride=4, block_size=PAGE,
                         init_blocks=1, window_size=32, topk=2, dense_len=160)
    assert (spec.n_selected, spec.dense_blocks) == (6, 10)
    rng = np.random.default_rng(3)
    cache = SA.init_sparse_pool(40, spec, 1, 16, jnp.float32)
    cache = {k: jnp.asarray(rng.normal(size=v.shape), v.dtype)
             for k, v in cache.items()}
    table = jnp.asarray(1 + rng.permutation(32).reshape(2, 16), jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 16)), jnp.float32)
    at = jnp.asarray(lengths)
    blocks, valid = SA.select_step(spec, q, cache["kc"], table, at)
    out, read = jax.jit(SA.attend_step, static_argnums=0)(
        spec, q, cache, table, at, jnp.asarray(active), blocks, valid)
    assert int(read) == want
    assert out.shape == q.shape and np.all(np.isfinite(np.asarray(out)))


# -- streams joining and leaving between chunks --------------------------------------

def test_joins_and_leaves_between_chunks_leave_the_others_logits_unchanged(
        model):
    cfg, weights, lm, _, _ = model
    rng = np.random.default_rng(11)
    a = rng.integers(1, 97, 170).astype(np.int32)
    b = rng.integers(1, 97, 150).astype(np.int32)
    width = MAX_LEN // PAGE

    def run(with_b):
        caches = lm.init_paged_caches(40, PAGE, slots=SLOTS)
        table = np.zeros((SLOTS, width), np.int32)
        lengths = np.zeros(SLOTS, np.int32)
        active = np.zeros(SLOTS, bool)
        row_a, row_b = _row(1, 11), _row(20, 10)
        caches = _prefill(model, caches, a, 140, row_a, 0)
        table[0], lengths[0], active[0] = row_a, 140, True
        seen = []

        def step_a(caches):
            logits, caches = _decode(model, caches, table, lengths, active,
                                     {0: a[lengths[0]]})
            seen.append(np.asarray(logits)[0])
            lengths[0] += 1
            return caches
        if with_b:  # B joins chunk by chunk, A decodes between its chunks
            caches = _prefill(model, caches, b, 120, row_b, 2,
                              between=step_a)
            table[2], lengths[2], active[2] = row_b, 120, True
            for i in range(6):  # both decode, then B leaves
                logits, caches = _decode(
                    model, caches, table, lengths, active,
                    {0: a[lengths[0]], 2: b[lengths[2]]})
                seen.append(np.asarray(logits)[0])
                lengths[[0, 2]] += 1
            table[2], lengths[2], active[2] = 0, 0, False
        else:
            for _ in range(len(lm.chunk_plan(120)) + 6):
                caches = step_a(caches)
        for _ in range(4):
            caches = step_a(caches)
        return np.stack(seen)
    alone, beside = run(False), run(True)
    assert alone.shape == beside.shape
    np.testing.assert_array_equal(alone, beside)


# -- what the server refuses, by name of the reason -----------------------------------

def _src(tmp_path):
    return f"dir://{tmp_path}/{uuid.uuid4().hex[:8]}"


def _server(model, tmp_path, **more):
    _, _, lm, _, _ = model
    more.setdefault("slots", SLOTS)
    more.setdefault("max_new_tokens", 8)
    more.setdefault("kv_pages", 40)
    more.setdefault("kv_page_len", PAGE)
    src = _src(tmp_path)
    return GenerativeServing(ServingConfig(data_src=src, **more), lm), src


@pytest.mark.parametrize("more,reason", [
    (dict(kv_int8=True), "kv_int8 is refused .* no dequantising gather"),
    (dict(spec_k=2), "speculative decoding is refused .* rolled back"),
    (dict(kv_shard=2), "kv_shard is refused .* state a slot"),
    (dict(kv_page_len=8), "kv_page_len must be the model's selection block"),
    (dict(temperature=0.7), "sampling is not wired"),
])
def test_the_server_refuses_what_a_recurrent_model_cannot_have(
        model, tmp_path, more, reason):
    with pytest.raises(ValueError, match=reason):
        _server(model, tmp_path, **more)


def test_no_pool_named_gives_every_slot_its_whole_context(model, tmp_path):
    srv, _ = _server(model, tmp_path, kv_pages=None)
    assert srv.num_pages == SLOTS * (srv.lm.max_len // PAGE) + 1
    assert srv.health_snapshot()["kv_pages_free"] == srv.num_pages - 1


def test_a_draft_model_is_refused_too(model, tmp_path):
    _, _, lm, _, _ = model
    with pytest.raises(ValueError, match="speculative decoding is refused"):
        GenerativeServing(ServingConfig(
            data_src=_src(tmp_path), slots=2, kv_pages=40,
            kv_page_len=PAGE), lm, draft_lm=lm)


def test_register_prefix_and_fit_refuse_by_reason(model, tmp_path):
    srv, _ = _server(model, tmp_path)
    with pytest.raises(RuntimeError, match="snapshot of every layer's state"):
        srv.register_prefix([1, 2, 3])
    with pytest.raises(NotImplementedError, match="chunked scan's backward"):
        srv.lm.fit(np.zeros((2, 8)))
    with pytest.raises(NotImplementedError, match="int8 pages"):
        srv.lm.init_paged_caches(8, PAGE, int8=True)
    with pytest.raises(ValueError, match="selection block"):
        srv.lm.init_paged_caches(8, 8)
    with pytest.raises(ValueError, match="no mixer named"):
        DecoderSpec.from_config(dict(tiny_cfg(), mixer_types=["mamba"]), 64)


# -- the serve loop's chunked prefill ---------------------------------------------------

def _drive(srv, steps=400):
    idle = 0
    for _ in range(steps):
        if srv.serve_step() == 0:
            idle += 1
            if idle >= 3:
                return
        else:
            idle = 0


def _reference_gap(cfg, weights, prompt, served):
    row = np.asarray(list(prompt) + list(served), np.int32)[None]
    out = np.asarray(ref.logits(cfg, weights, row))[0]
    at = len(prompt) - 1 + np.arange(len(served))
    return float(np.max(out[at].max(axis=1) - out[at, served]))


@pytest.mark.parametrize("slots", [2, SLOTS])
def test_requests_through_the_server_follow_the_reference(
        model, tmp_path, slots):
    cfg, weights, _, _, _ = model
    srv, src = _server(model, tmp_path, slots=slots)
    inq, outq = InputQueue(src), OutputQueue(src)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 97, n).tolist() for n in (150, 70, 1, 131, 90)]
    for i, p in enumerate(prompts):
        inq.enqueue_prompt(f"r{i}", p, max_new_tokens=6 + i)
    _drive(srv)
    for i, p in enumerate(prompts):
        got = outq.query(f"r{i}")
        assert got["done"] and len(got["value"]) == 6 + i, got
        assert _reference_gap(cfg, weights, p, got["value"]) < 2e-6
    snap = srv.health_snapshot()
    plans = [len(srv.lm.chunk_plan(len(p) - 1)) for p in prompts]
    assert snap["prefill_chunks_total"] == sum(plans) == 11
    assert snap["prompt_tokens_total"] == sum(len(p) - 1 for p in prompts)
    assert snap["steps_between_chunks_total"] > 0
    assert snap["state_slots_in_use"] == 0 and snap["prefills_pending"] == 0
    # one observation a decode step, of what the step program gathered
    assert 0 < snap["sparse_positions_read"]["window"] <= sum(
        6 + i for i in range(5))
    assert snap["sparse_positions_read"]["mean"] == 6 * PAGE
    assert snap["kv_pages_free"] == 39      # every page came back
    assert srv.counters["prefill_chunks"] == 11
    assert snap["counters"]["errors"] == 0


def test_resident_streams_decode_between_the_chunks_of_a_joining_prompt(
        model, tmp_path):
    from analytics_zoo_tpu.serving.server import DECODE_STEPS_PER_CHUNK as k
    srv, src = _server(model, tmp_path, max_new_tokens=40)
    inq = InputQueue(src)
    inq.enqueue_prompt("first", list(range(1, 30)), max_new_tokens=40)
    for _ in range(k + 1):
        srv.serve_step()
    assert srv.health_snapshot()["slots_occupied"] == 1
    inq.enqueue_prompt("long", list(range(1, 60)) * 4, max_new_tokens=4)
    chunks = []
    before = srv.counters["prefill_chunks"]
    tokens = []
    for _ in range(3 * k + 3):
        srv.serve_step()
        chunks.append(srv.counters["prefill_chunks"] - before)
        tokens.append(len(srv._streams[0].tokens))
    assert len(srv.lm.chunk_plan(59 * 4 - 1)) == 4
    # a chunk, then k decode steps of the resident stream before the next
    # chunk; the stream decodes in every iteration
    assert chunks == ([1] * k + [2] * k + [3] * k + [4] * 3)
    assert np.all(np.diff(tokens) == 1)
    assert srv.counters["steps_between_chunks"] == 3 * k
    _drive(srv)


def test_a_prompt_joins_chunk_after_chunk_where_nothing_is_resident(
        model, tmp_path):
    srv, src = _server(model, tmp_path)
    InputQueue(src).enqueue_prompt("long", list(range(1, 60)) * 4,
                                   max_new_tokens=2)
    chunks = []
    for _ in range(4):
        srv.serve_step()
        chunks.append(srv.counters["prefill_chunks"])
    assert chunks == [1, 2, 3, 4]
    assert srv.health_snapshot()["slots_occupied"] == 1
    _drive(srv)


def test_a_deadline_that_passes_between_chunks_ends_the_prompt(
        model, tmp_path, monkeypatch):
    from analytics_zoo_tpu.serving import server as server_module
    srv, src = _server(model, tmp_path)
    inq, outq = InputQueue(src), OutputQueue(src)
    inq.enqueue_prompt("late", list(range(1, 90)) * 2, max_new_tokens=4,
                       deadline_ms=60000)
    srv.serve_step()
    assert srv.health_snapshot()["prefills_pending"] == 1
    real = server_module.wall_clock
    monkeypatch.setattr(server_module, "wall_clock", lambda: real() + 120)
    srv.serve_step()
    got = outq.query("late")
    assert got["error"] == DEADLINE_ERROR
    snap = srv.health_snapshot()
    assert snap["prefills_pending"] == 0 and snap["kv_pages_free"] == 39
    assert srv.counters["expired"] == 1


def test_a_prompt_that_finds_no_pages_is_shed_and_the_rest_go_on(
        model, tmp_path):
    srv, src = _server(model, tmp_path, kv_pages=14)
    inq, outq = InputQueue(src), OutputQueue(src)
    inq.enqueue_prompt("fits", list(range(1, 60)), max_new_tokens=4)
    inq.enqueue_prompt("too_long", list(range(1, 90)) * 2, max_new_tokens=4)
    _drive(srv)
    assert outq.query("too_long")["error"] == PAGE_SHED_ERROR
    assert len(outq.query("fits")["value"]) == 4
    assert srv.counters["shed"] == 1
    assert srv.health_snapshot()["kv_pages_free"] == 13


def test_a_failed_chunk_answers_everyone_and_the_caches_start_over(
        model, tmp_path):
    srv, src = _server(model, tmp_path)
    inq, outq = InputQueue(src), OutputQueue(src)
    inq.enqueue_prompt("resident", list(range(1, 20)), max_new_tokens=20)
    for _ in range(3):
        srv.serve_step()
    inq.enqueue_prompt("joining", list(range(1, 80)) * 2, max_new_tokens=4)
    inq.enqueue_prompt("waiting", list(range(1, 40)), max_new_tokens=4)
    sound = srv._prefill_chunk_fn

    def broken(*args, **kwargs):
        raise RuntimeError("chunk went wrong")
    srv._prefill_chunk_fn = broken
    for _ in range(4):
        srv.serve_step()
    for uri in ("resident", "joining", "waiting"):
        assert "chunk went wrong" in outq.query(uri)["error"]
    snap = srv.health_snapshot()
    assert snap["kv_pool_rebuilds"] == 1 and snap["kv_pages_free"] == 39
    assert snap["prefills_pending"] == 0 and snap["slots_occupied"] == 0
    srv._prefill_chunk_fn = sound
    inq.enqueue_prompt("after", list(range(1, 50)), max_new_tokens=3)
    _drive(srv)
    assert len(outq.query("after")["value"]) == 3


def test_handoff_gives_a_joining_prompt_back_whole(model, tmp_path):
    from analytics_zoo_tpu.serving.queues import make_queue
    srv, src = _server(model, tmp_path)
    inq = InputQueue(src)
    inq.enqueue_prompt("joining", list(range(1, 80)) * 2, max_new_tokens=4)
    srv.serve_step()
    assert srv.health_snapshot()["prefills_pending"] == 1
    other_src = _src(tmp_path)
    moved = srv.handoff(make_queue(other_src))
    assert moved == 1
    snap = srv.health_snapshot()
    assert snap["prefills_pending"] == 0 and snap["kv_pages_free"] == 39
    other, _ = _server(model, tmp_path)
    other.queue = make_queue(other_src)
    _drive(other)
    assert len(OutputQueue(other_src).query("joining")["value"]) == 4


def test_chunk_plans_are_whole_chunks_and_one_bucket(model):
    _, _, lm, _, _ = model
    assert lm.chunk_plan(0) == [(0, 16)]
    assert lm.chunk_plan(64) == [(0, 64)]
    assert lm.chunk_plan(65) == [(0, 64), (64, 16)]
    assert lm.chunk_plan(150) == [(0, 64), (64, 64), (128, 32)]
    with pytest.raises(ValueError, match="whole pages"):
        LayeredDecoder(lm.spec, prefill_chunk=32)
