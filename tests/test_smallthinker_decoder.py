"""The layered decoder with SmallThinker's layers (a softmax router on the
layer's input, dropless top-k ReGLU experts, full and window attention over
two page budgets) against its plain reference, at a tiny size on the CPU:
chunked prefill then paged decode agree with the reference's full forward;
the chosen experts; dropless under imbalance; the shares of the experts add
up; a window layer's pages go back and may be overwritten; streams joining
and leaving between chunks; what the server refuses, by reason; requests
through the serve loop.

The preset: 4 layers ``[full, window, window, window]``, hidden 64, 4 query
heads over 2 key/value heads of 16, 8 experts of width 32 with 2 a token, a
window of 32, pages of 8, chunks of 32."""
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
from analytics_zoo_tpu.ops import grouped_attention as GA  # noqa: E402
from analytics_zoo_tpu.ops import moe  # noqa: E402
from analytics_zoo_tpu.serving import (GenerativeServing,  # noqa: E402
                                       ServingConfig)
from analytics_zoo_tpu.serving.client import (InputQueue,  # noqa: E402
                                              OutputQueue)
from analytics_zoo_tpu.serving.server import _WindowPages  # noqa: E402
from perfbench.references import smallthinker_lm as ref  # noqa: E402

PAGE, MAX_LEN, SLOTS, WINDOW, CHUNK = 8, 128, 3, 32, 32
WIDTH = MAX_LEN // PAGE


def tiny_cfg(**more):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "smallthinker_21b.json")) as f:
        cfg = json.load(f)
    cfg.update(
        vocab_size=97, hidden_size=64, moe_ffn_hidden_size=32, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        sliding_window_size=WINDOW, num_hidden_layers=4,
        sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
        n_positions=MAX_LEN, param_dtype="float32")
    cfg["serving"].update(max_new_tokens=24, kv_page_len=PAGE,
                          prefill_chunk=CHUNK)
    cfg.update(more)
    return cfg


def build(cfg, seed=5, **spec):
    weights = ref.init_weights(cfg, seed)
    lm = LayeredDecoder(
        DecoderSpec.from_config(cfg, MAX_LEN, page_len=PAGE),
        prefill_chunk=CHUNK)
    lm.set_params(weights)
    return weights, lm


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    weights, lm = build(cfg)
    return cfg, weights, lm, jax.jit(lm.prefill_chunk), \
        jax.jit(lm.paged_state_step)


def _row(first_page, pages):
    row = np.zeros(WIDTH, np.int32)
    row[:pages] = first_page + np.arange(pages)[::-1]  # not in order
    return row


def _prefill(model, caches, tokens, fed, rows, slot, between=None):
    _, weights, lm, chunk, _ = model
    for start, width in lm.chunk_plan(fed):
        n = max(0, min(width, fed - start))
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = tokens[start:start + n]
        caches = chunk(weights, padded, caches,
                       tuple(jnp.asarray(r) for r in rows), slot, start, n)
        if between is not None:
            caches = between(caches, start + width)
    return caches


def _decode(model, caches, tables, lengths, active, feed):
    """Decode ``feed [steps, S]``; returns logits ``[steps, S, V]``."""
    _, weights, _, _, step = model
    lengths = np.array(lengths, np.int32)
    out = []
    for tokens in feed:
        logits, caches, _ = step(
            weights, tokens, jnp.asarray(lengths),
            tuple(jnp.asarray(t) for t in tables), caches,
            jnp.asarray(active))
        out.append(np.asarray(logits))
        lengths = lengths + np.asarray(active, np.int32)
    return np.stack(out), caches


# -- chunked prefill then paged decode against the full forward ------------------------

@pytest.mark.parametrize("prompt,new", [
    (20, 6),      # under the window, one chunk
    (33, 5),      # a chunk's end on a page's end, then one position more
    (61, 40),     # over the window: decode passes two windows' worth
    (100, 9),     # four chunks, the last of another bucket
    (45, 4),      # a chunk's end off a page's end
])
def test_chunked_prefill_then_decode_agrees_with_the_reference(
        model, prompt, new):
    cfg, weights, lm, _, _ = model
    rng = np.random.default_rng(prompt)
    tokens = rng.integers(1, 97, prompt + new).astype(np.int32)
    want = np.asarray(ref.logits(cfg, weights, tokens[None]))[0]
    caches = lm.init_paged_caches(1 + WIDTH * SLOTS, PAGE, slots=SLOTS)
    fed = prompt - 1
    full, window = _row(5, WIDTH), _row(3, WIDTH)
    caches = _prefill(model, caches, tokens, fed, (full, window), 1)
    tables = [np.zeros((SLOTS, WIDTH), np.int32) for _ in range(2)]
    tables[0][1], tables[1][1] = full, window
    feed = np.zeros((new + 1, SLOTS), np.int32)
    feed[:, 1] = tokens[fed:]
    got, _ = _decode(model, caches, tables, [0, fed, 0],
                     [False, True, False], feed)
    np.testing.assert_allclose(got[:, 1], want[fed:], atol=2e-6)


def test_a_bfloat16_model_stays_near_the_float32_reference():
    cfg = tiny_cfg(param_dtype="bfloat16")
    weights, lm = build(cfg)
    model = (cfg, weights, lm, jax.jit(lm.prefill_chunk),
             jax.jit(lm.paged_state_step))
    tokens = np.random.default_rng(3).integers(1, 97, 70).astype(np.int32)
    want = np.asarray(ref.logits(cfg, weights, tokens[None]))[0]
    # the window pool of 3 slots holds 20 pages: room for a whole row
    caches = lm.init_paged_caches(1 + WIDTH, PAGE, slots=3)
    rows = (_row(1, WIDTH), _row(1, WIDTH))
    caches = _prefill(model, caches, tokens, 60, rows, 0)
    feed = tokens[60:, None]
    got, _ = _decode(model, caches, [r[None] for r in rows], [60], [True],
                     feed)
    spread = float(np.std(want[60:]))
    assert np.max(np.abs(got[:, 0] - want[60:])) < 0.1 * spread


# -- routing -----------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_chosen_experts_and_gates_are_the_references(seed):
    cfg = tiny_cfg()
    weights = ref.init_weights(cfg, seed)
    x = jax.random.normal(jax.random.PRNGKey(seed), (50, 64)) * 3.0
    for p in weights["layers"][:2]:
        choice, gates = moe.route(x, p["router"], 2)
        want_choice, want_gates = ref.route(cfg, p, x, "highest")
        np.testing.assert_array_equal(np.asarray(choice),
                                      np.asarray(want_choice))
        np.testing.assert_allclose(np.asarray(gates),
                                   np.asarray(want_gates), atol=1e-6)
        np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 1.0,
                                   atol=1e-6)


def test_ties_go_to_the_lower_index():
    choice, gates = moe.route(jnp.ones((3, 4)), jnp.zeros((4, 8)), 2)
    np.testing.assert_array_equal(np.asarray(choice), [[0, 1]] * 3)
    np.testing.assert_allclose(np.asarray(gates), 0.5)


def _expert_inputs(seed, rows=40):
    cfg = tiny_cfg()
    p = ref.init_weights(cfg, seed)["layers"][1]
    p = dict(p, **{n: p[n] * 10 for n in ("w_gate", "w_up", "w_down")})
    h = jax.random.normal(jax.random.PRNGKey(seed), (rows, 64))
    return cfg, p, h


def test_the_experts_output_is_the_references():
    cfg, p, h = _expert_inputs(4)
    choice, gates = moe.route(h * 3, p["router"], 2)
    got, sizes = moe.experts(h, choice, gates, p["w_gate"], p["w_up"],
                             p["w_down"])
    want = ref.experts(cfg, p, h, choice, gates, "highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert int(sizes.sum()) == 80 and sizes.shape == (8,)
    np.testing.assert_array_equal(
        np.asarray(sizes), np.bincount(np.asarray(choice).ravel(),
                                       minlength=8))


@pytest.mark.parametrize("rows", [1, 7, 64])
def test_dropless_under_imbalance(rows):
    """A router that sends every token to one expert (and its second choice
    to another): no capacity, so every token gets both, as the reference."""
    cfg, p, h = _expert_inputs(6, rows)
    choice = jnp.tile(jnp.asarray([[5, 2]], jnp.int32), (rows, 1))
    gates = jnp.tile(jnp.asarray([[0.75, 0.25]], jnp.float32), (rows, 1))
    got, sizes = moe.experts(h, choice, gates, p["w_gate"], p["w_up"],
                             p["w_down"])
    want = ref.experts(cfg, p, h, choice, gates, "highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert np.asarray(sizes).tolist() == [0, 0, rows, 0, 0, rows, 0, 0]
    stats = np.asarray(moe.load_stats(sizes))
    assert stats.tolist() == [2.0, 4.0, 2.0 * rows]


def test_the_shares_of_the_experts_add_up():
    """A chip that holds experts 0-3 and one that holds 4-7 each compute
    their part; the parts sum to the uncut layer's output."""
    cfg, p, h = _expert_inputs(8)
    choice, gates = moe.route(h * 3, p["router"], 2)
    whole, _ = moe.experts(h, choice, gates, p["w_gate"], p["w_up"],
                           p["w_down"])
    parts = []
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        at = np.asarray(held)
        part, sizes = moe.experts(h, choice, gates, p["w_gate"][at],
                                  p["w_up"][at], p["w_down"][at], held=held)
        want = ref.experts(cfg, p, h, choice, gates, "highest", held=held)
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   atol=1e-5)
        assert sizes.shape == (4,)
        parts.append(part)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(whole), atol=1e-5)


def test_rows_that_stand_for_nothing_read_no_expert():
    cfg, p, h = _expert_inputs(9, 6)
    choice, gates = moe.route(h * 3, p["router"], 2)
    valid = jnp.asarray([True, False, True, False, False, True])
    got, sizes = moe.experts(h, choice, gates, p["w_gate"], p["w_up"],
                             p["w_down"], valid=valid)
    want = ref.experts(cfg, p, h, choice, gates, "highest")
    np.testing.assert_allclose(np.asarray(got)[[0, 2, 5]],
                               np.asarray(want)[[0, 2, 5]], atol=1e-5)
    assert not np.asarray(got)[[1, 3, 4]].any()
    assert int(sizes.sum()) == 6


def test_a_decoder_that_holds_half_the_experts_has_tables_of_half():
    cfg = tiny_cfg()
    spec = DecoderSpec.from_config(cfg, MAX_LEN, page_len=PAGE)
    import dataclasses
    half = dataclasses.replace(spec, held_experts=(4, 5, 6, 7))
    mats, _ = half.layer_shapes("full")
    assert mats["w_gate"] == (4, 64, 32) and mats["router"] == (64, 8)
    assert "g" not in mats and "gate_proj" not in mats


# -- the two masks ------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, WINDOW])
@pytest.mark.parametrize("lengths", [[0, 5, 31], [32, 33, 100], [127, 64, 0]])
def test_a_step_reads_what_the_mask_says(window, lengths):
    rng = np.random.default_rng(7)
    pool = {n: jnp.asarray(rng.normal(size=(1 + 3 * WIDTH, PAGE, 32)),
                           jnp.float32) for n in ("k", "v")}
    table = 1 + rng.permutation(3 * WIDTH).reshape(3, WIDTH).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(3, 2, 2, 16)), jnp.float32)
    lengths = np.asarray(lengths, np.int32)
    got = GA.attend_step(q, pool, jnp.asarray(table), jnp.asarray(lengths),
                         jnp.ones(3, bool), window, tile_pages=3)
    for s in range(3):
        k = np.asarray(pool["k"])[table[s]].reshape(MAX_LEN, 2, 16)
        v = np.asarray(pool["v"])[table[s]].reshape(MAX_LEN, 2, 16)
        t = lengths[s]
        seen = np.arange(MAX_LEN) <= t
        if window:
            seen &= np.arange(MAX_LEN) > t - window
        scores = np.einsum("kgd,nkd->kgn", np.asarray(q[s]), k)
        scores = np.where(seen, scores, -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        want = np.einsum("kgn,nkd->kgd", probs, v)
        np.testing.assert_allclose(np.asarray(got[s]), want, atol=1e-5)


def test_window_pages_are_the_pages_a_window_can_lie_on():
    assert GA.window_pages(4096, 64) == 65 and GA.window_pages(32, 8) == 5
    assert np.asarray(GA.first_page(jnp.asarray([0, 31, 32, 40, 127]), 32,
                                    8)).tolist() == [0, 0, 0, 1, 12]
    assert np.asarray(GA.first_page(jnp.asarray([99]), None, 8)) == 0


# -- the window budget ---------------------------------------------------------------------

def test_a_window_layers_free_list_gets_back_every_page_behind_the_window():
    win = _WindowPages(2, WIDTH, 40, PAGE, WINDOW)
    assert win.in_use() == 0 and len(win.free) == 39
    win.cover(0, 0, 32)                       # a chunk's pages
    assert win.in_use() == 4
    assert win.behind(0, 32) == 0             # query 32 sees 1 .. 32
    win.cover(0, 32, 64)                      # the last chunk: 8 real, padded
    assert win.in_use() == 8
    win.beyond(0, 40)                         # padding past position 40
    assert win.in_use() == 6 and win.rows[0, 5] and not win.rows[0, 6]
    assert win.behind(0, 40) == 1             # query 40 sees 9 .. 40
    assert win.rows[0, 0] == 0 and win.in_use() == 5
    other = _WindowPages(1, WIDTH, 40, PAGE, WINDOW)
    other.cover(0, 0, 64)
    assert other.behind(0, 64) == 4           # query 64 sees 33 .. 64
    assert other.rows[0, :4].tolist() == [0] * 4 and other.in_use() == 4
    held = []
    for t in range(40, 128):                  # decode to the table's end
        win.behind(0, t)
        win.cover(0, t, t + 1)
        held.append(win.in_use())
        first = max(t - WINDOW + 1, 0) // PAGE
        assert win.rows[0, first:t // PAGE + 1].all()
        assert not win.rows[0, :first].any()
    assert max(held) == GA.window_pages(WINDOW, PAGE) == 5
    win.release(0)
    assert win.in_use() == 0 and sorted(win.free) == list(range(1, 40))
    assert not win.table(np.array([True, True])).any()


def test_a_budget_that_runs_out_says_it_is_a_bug():
    win = _WindowPages(1, WIDTH, 3, PAGE, WINDOW)
    with pytest.raises(RuntimeError, match="derived so that it cannot"):
        win.cover(0, 0, 32)


def test_the_logits_do_not_change_when_released_pages_are_overwritten(model):
    """Stream A is fed in chunks and decodes while the pages behind its
    window go back and stream B is written into those very pages."""
    cfg, weights, lm, _, _ = model
    rng = np.random.default_rng(11)
    a = rng.integers(1, 97, 120).astype(np.int32)
    b = rng.integers(1, 97, 40).astype(np.int32)
    want = np.asarray(ref.logits(cfg, weights, a[None]))[0]
    caches = lm.init_paged_caches(1 + 2 * WIDTH, PAGE, slots=2)
    win = _WindowPages(2, WIDTH, lm.window_pages(2), PAGE, WINDOW)
    assert win.num_pages == 2 * 5 + CHUNK // PAGE + 1
    full = [_row(1, WIDTH), _row(1 + WIDTH, WIDTH)]
    fed, given_back = 99, []

    def feed(slot, tokens, n):
        nonlocal caches
        for start, width in lm.chunk_plan(n):
            k = max(0, min(width, n - start))
            padded = np.zeros((1, width), np.int32)
            padded[0, :k] = tokens[start:start + k]
            win.cover(slot, start, start + width)
            before = set(win.rows[slot].tolist())
            caches = model[3](weights, padded, caches,
                              (jnp.asarray(full[slot]),
                               jnp.asarray(win.rows[slot].copy())),
                              slot, start, k)
            last = start + width >= n
            if last:
                win.beyond(slot, n)
            win.behind(slot, n if last else start + width)
            given_back.extend(before - set(win.rows[slot].tolist()))
    feed(0, a, fed)
    assert win.in_use() <= 5 and len(given_back) >= 8
    feed(1, b, 39)              # B takes pages that A gave back
    assert set(win.rows[1].tolist()) & set(given_back)
    tables = [np.stack(full), None]
    lengths, out = np.array([fed, 39], np.int32), []
    for i in range(20):
        for slot in (0, 1):
            win.behind(slot, int(lengths[slot]))
            win.cover(slot, int(lengths[slot]), int(lengths[slot]) + 1)
        tables[1] = win.table(np.array([True, True]))
        tokens = np.array([a[fed + i], b[39] if i == 0 else 1], np.int32)
        logits, caches, _ = model[4](
            weights, tokens, jnp.asarray(lengths),
            tuple(jnp.asarray(t) for t in tables), caches,
            jnp.ones(2, bool))
        out.append(np.asarray(logits)[0])
        lengths = lengths + 1
        assert win.in_use() <= 2 * 5
    np.testing.assert_allclose(np.stack(out), want[fed:fed + 20], atol=2e-6)


def test_joins_and_leaves_between_chunks_leave_the_others_logits_unchanged(
        model):
    cfg, weights, lm, _, _ = model
    rng = np.random.default_rng(13)
    resident = rng.integers(1, 97, 60).astype(np.int32)
    joining = rng.integers(1, 97, 90).astype(np.int32)
    rows = [(_row(1, WIDTH), _row(1, WIDTH)),
            (_row(1 + WIDTH, WIDTH), _row(1 + WIDTH, WIDTH))]

    def run(with_join):
        caches = lm.init_paged_caches(1 + 2 * WIDTH, PAGE, slots=2)
        caches = _prefill(model, caches, resident, 40, rows[0], 0)
        tables = [np.zeros((2, WIDTH), np.int32) for _ in range(2)]
        tables[0][0], tables[1][0] = rows[0]
        state = {"lengths": [40, 0], "at": 40, "out": []}

        def steps(caches, _):
            feed = np.zeros((3, 2), np.int32)
            feed[:, 0] = resident[state["at"]:state["at"] + 3]
            got, caches = _decode(model, caches, tables, state["lengths"],
                                  [True, False], feed)
            state["out"].append(got[:, 0])
            state["at"] += 3
            state["lengths"][0] += 3
            return caches
        if with_join:
            caches = _prefill(model, caches, joining, 89, rows[1], 1,
                              between=steps)
        else:
            for _ in range(3):
                caches = steps(caches, None)
        return np.concatenate(state["out"])
    alone, beside = run(False), run(True)
    assert alone.shape == beside.shape == (9, 97)
    np.testing.assert_array_equal(alone, beside)


# -- through the server ----------------------------------------------------------------------

def _src(tmp_path):
    return f"dir://{tmp_path}/{uuid.uuid4().hex[:8]}"


def _server(model, tmp_path, **more):
    _, _, lm, _, _ = model
    more.setdefault("slots", SLOTS)
    more.setdefault("max_new_tokens", 8)
    more.setdefault("kv_page_len", PAGE)
    src = _src(tmp_path)
    return GenerativeServing(ServingConfig(data_src=src, **more), lm), src


@pytest.mark.parametrize("more,reason", [
    (dict(kv_int8=True), "kv_int8 is refused .* no dequantising gather"),
    (dict(spec_k=2), "speculative decoding is refused .* window layer gives "
                     "back the pages"),
    (dict(kv_shard=2), "kv_shard is refused .* page budget is not sharded"),
    (dict(kv_page_len=16), "kv_page_len must be the model's page, 8"),
    (dict(temperature=0.7), "sampling is not wired"),
])
def test_the_server_refuses_by_reason(model, tmp_path, more, reason):
    with pytest.raises(ValueError, match=reason):
        _server(model, tmp_path, **more)


def test_register_prefix_fit_and_int8_pools_refuse_by_reason(model, tmp_path):
    srv, _ = _server(model, tmp_path)
    with pytest.raises(RuntimeError, match="gives back the pages that lie "
                                           "behind a stream's window"):
        srv.register_prefix([1, 2, 3])
    with pytest.raises(NotImplementedError, match="no training path"):
        srv.lm.fit(np.zeros((2, 8)))
    with pytest.raises(NotImplementedError, match="int8 pages"):
        srv.lm.init_paged_caches(8, PAGE, int8=True)
    with pytest.raises(ValueError, match="rotary positions"):
        DecoderSpec.from_config(dict(tiny_cfg(), rope_layout=[1, 1, 1, 1]),
                                MAX_LEN, page_len=PAGE)
    with pytest.raises(ValueError, match="no feed-forward named"):
        DecoderSpec(vocab_size=9, hidden=8, intermediate=8, mixers=("full",),
                    heads=2, kv_heads=1, head_dim=4, linear_heads=0,
                    linear_head_dim=0, max_len=64, ffn="gelu")
    assert not srv.lm.recurrent and srv.lm.chunked
    assert srv.lm.window_len == WINDOW


def test_both_budgets_are_derived_so_that_every_slot_reaches_max_len(
        model, tmp_path):
    srv, _ = _server(model, tmp_path)
    assert srv.num_pages == SLOTS * WIDTH + 1
    assert srv._window.num_pages == SLOTS * 5 + CHUNK // PAGE + 1
    pools = [c["k"].shape[0] for c in srv._caches]
    assert pools == [srv.num_pages] + [srv._window.num_pages] * 3
    snap = srv.health_snapshot()
    assert snap["kv_pages_in_use"] == {"full": 0, "window": 0}
    assert snap["state_slots_in_use"] is None


def _drive(srv, steps=600):
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
    srv, src = _server(model, tmp_path, slots=slots, max_new_tokens=40)
    inq, outq = InputQueue(src), OutputQueue(src)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 97, n).tolist() for n in (90, 20, 1, 65, 33)]
    news = [30, 6, 40, 8, 7]
    for i, (p, n) in enumerate(zip(prompts, news)):
        inq.enqueue_prompt(f"r{i}", p, max_new_tokens=n)
    most = 0
    for _ in range(400):
        srv.serve_step()
        most = max(most, srv._window.in_use())
        held = (srv._window.rows != 0).sum(axis=1)
        assert np.all(held[srv._active_host] <= 5)
        assert np.all(held <= 5 + CHUNK // PAGE)
    assert 5 < most <= srv._window.num_pages - 1
    for i, (p, n) in enumerate(zip(prompts, news)):
        got = outq.query(f"r{i}")
        assert got["done"] and len(got["value"]) == n, got
        assert _reference_gap(cfg, weights, p, got["value"]) < 2e-6
    snap = srv.health_snapshot()
    assert snap["prefill_chunks_total"] == sum(
        len(srv.lm.chunk_plan(len(p) - 1)) for p in prompts)
    assert snap["kv_pages_in_use"] == {"full": 0, "window": 0}
    assert snap["kv_pages_free"] == srv.num_pages - 1
    assert snap["window_pages_released_total"] > 10
    steps = snap["moe_experts_touched"]["window"]
    assert 0 < steps == snap["moe_expert_load"]["window"] <= sum(news)
    # 2 experts a token of 8, four layers, the active streams' alone
    assert snap["moe_assignments_total"] == 4 * 2 * sum(news)
    assert 1.0 <= snap["moe_experts_touched"]["mean"] <= 2.0 * slots
    assert snap["moe_expert_load"]["mean"] >= 8 / (2 * slots)
    assert snap["sparse_positions_read"]["window"] == 0
    assert snap["counters"]["errors"] == 0


def test_a_stream_that_ends_or_is_dropped_gives_both_rows_back(
        model, tmp_path):
    from analytics_zoo_tpu.serving.server import DECODE_STEPS_PER_CHUNK as k
    srv, src = _server(model, tmp_path, max_new_tokens=40)
    inq, outq = InputQueue(src), OutputQueue(src)
    inq.enqueue_prompt("first", list(range(1, 30)), max_new_tokens=40)
    for _ in range(k + 1):
        srv.serve_step()
    inq.enqueue_prompt("long", list(range(1, 60)) + list(range(1, 50)),
                       max_new_tokens=4)
    srv.serve_step()            # its first chunk
    assert srv.health_snapshot()["prefills_pending"] == 1
    assert (srv._window.rows[1] != 0).sum() == CHUNK // PAGE
    srv._fail_active("gone")    # resident and joining alike
    srv._publisher.close()
    assert srv._window.in_use() == 0 and not srv._window.rows.any()
    assert srv.health_snapshot()["kv_pages_free"] == srv.num_pages - 1
    assert "gone" in outq.query("long")["error"]
    inq.enqueue_prompt("again", list(range(1, 50)), max_new_tokens=3)
    _drive(srv)
    assert len(outq.query("again")["value"]) == 3
