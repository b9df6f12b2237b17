"""The layered decoder with GLM-5's layers (multi-head latent attention over
a latent page pool, a learned indexer that selects single positions, a
feed-forward chosen layer by layer: a leading dense layer, then sigmoid-
routed gated-SiLU experts with a shared expert, of which the chip holds a
share) against its plain reference, at a tiny size on the CPU: chunked
prefill then paged decode agree with the reference's full forward; absorbed
equals plain; the selected sets are the reference's; the correction bias
moves the choice and not the gates; the shares of the experts add up;
streams joining and leaving between chunks; a freed page overwritten; what
the server refuses, by reason; requests through the serve loop.

The preset: 1 dense + 3 expert layers, hidden 64, 4 heads of 12 + 4 / 16,
ranks 32 and 16, an indexer of 2 heads of 8 with top-16, 16 experts of
width 32 with 4 a token and a shared one, pages of 8, chunks of 32; the
tile loops run over tiles of 16 positions."""
import dataclasses
import json
import os
import sys
import time
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
from analytics_zoo_tpu.ops import latent_attention as LA  # noqa: E402
from analytics_zoo_tpu.ops import moe  # noqa: E402
from analytics_zoo_tpu.serving import (GenerativeServing,  # noqa: E402
                                       ServingConfig)
from analytics_zoo_tpu.serving.client import (InputQueue,  # noqa: E402
                                              OutputQueue)
from perfbench.references import glm5_lm as ref  # noqa: E402

PAGE, MAX_LEN, SLOTS, TOPK, CHUNK = 8, 128, 3, 16, 32
WIDTH = MAX_LEN // PAGE
VOCAB, EXPERTS = 97, 16


def tiny_cfg(**more):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "glm_5.json")) as f:
        cfg = json.load(f)
    cfg.update(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=12, qk_rope_head_dim=4, qk_head_dim=16,
        v_head_dim=16, index_n_heads=2, index_head_dim=8, index_topk=TOPK,
        n_routed_experts=EXPERTS, n_routed_experts_published=EXPERTS,
        held_experts=None, num_experts_per_tok=4, num_hidden_layers=4,
        first_k_dense_replace=1, n_positions=MAX_LEN, param_dtype="float32",
        router_bias_spread=0.02)
    cfg["serving"].update(max_new_tokens=24, kv_page_len=PAGE,
                          prefill_chunk=CHUNK, slots=SLOTS, kv_pages=None)
    cfg.update(more)
    return cfg


def tiny_spec(cfg):
    """The spec of ``cfg`` with tiles of 16 and 32 positions, so that every
    tile loop runs several times over the 128 positions."""
    spec = DecoderSpec.from_config(cfg, MAX_LEN, page_len=PAGE)
    return dataclasses.replace(spec, latent=dataclasses.replace(
        spec.latent, step_tile=16, chunk_tile=16, attend_tile=32))


def build(cfg, seed=5):
    weights = ref.init_weights(cfg, seed)
    lm = LayeredDecoder(tiny_spec(cfg), prefill_chunk=CHUNK)
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


def _prefill(model, caches, tokens, fed, row, slot, between=None):
    _, weights, lm, chunk, _ = model
    for start, width in lm.chunk_plan(fed):
        n = max(0, min(width, fed - start))
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = tokens[start:start + n]
        caches = chunk(weights, padded, caches, jnp.asarray(row), slot,
                       start, n)
        if between is not None:
            caches = between(caches, start + width)
    return caches


def _decode(model, caches, table, lengths, active, feed):
    """Decode ``feed [steps, S]``; returns logits ``[steps, S, V]`` and the
    steps' counts ``[steps, 5]``."""
    _, weights, _, _, step = model
    lengths = np.array(lengths, np.int32)
    out, stats = [], []
    for tokens in feed:
        logits, caches, read = step(
            weights, tokens, jnp.asarray(lengths), jnp.asarray(table),
            caches, jnp.asarray(active))
        out.append(np.asarray(logits))
        stats.append(np.asarray(read))
        lengths = lengths + np.asarray(active, np.int32)
    return np.stack(out), caches, np.stack(stats)


# -- chunked prefill then paged decode against the full forward ------------------------

@pytest.mark.parametrize("prompt,new", [
    (10, 4),      # under the top-16: every position is read
    (17, 8),      # a decode that passes the top-16
    (33, 5),      # a chunk's end on a page's end, then one position more
    (61, 40),     # two chunks, a long decode over the selection
    (100, 9),     # four chunks, the last of another bucket
    (45, 4),      # a chunk's end off a page's end
])
def test_chunked_prefill_then_decode_agrees_with_the_reference(
        model, prompt, new):
    cfg, weights, lm, _, _ = model
    rng = np.random.default_rng(prompt)
    tokens = rng.integers(1, VOCAB, prompt + new).astype(np.int32)
    want = np.asarray(ref.logits(cfg, weights, tokens[None]))[0]
    caches = lm.init_paged_caches(1 + WIDTH * SLOTS, PAGE, slots=SLOTS)
    fed = prompt - 1
    row = _row(5, WIDTH)
    caches = _prefill(model, caches, tokens, fed, row, 1)
    table = np.zeros((SLOTS, WIDTH), np.int32)
    table[1] = row
    feed = np.zeros((new + 1, SLOTS), np.int32)
    feed[:, 1] = tokens[fed:]
    got, _, stats = _decode(model, caches, table, [0, fed, 0],
                            [False, True, False], feed)
    np.testing.assert_allclose(got[:, 1], want[fed:], atol=3e-6)
    # the step's counts: positions read (at most the top-16) and scored
    seen = fed + 1 + np.arange(new + 1)
    np.testing.assert_allclose(stats[:, 3], np.minimum(seen, TOPK))
    np.testing.assert_allclose(stats[:, 4], seen)


def test_a_bfloat16_model_stays_near_the_float32_reference():
    cfg = tiny_cfg(param_dtype="bfloat16")
    weights, lm = build(cfg)
    model = (cfg, weights, lm, jax.jit(lm.prefill_chunk),
             jax.jit(lm.paged_state_step))
    tokens = np.random.default_rng(3).integers(1, VOCAB, 70).astype(np.int32)
    want = np.asarray(ref.logits(cfg, weights, tokens[None]))[0]
    caches = lm.init_paged_caches(1 + WIDTH, PAGE, slots=1)
    assert caches[0]["latent"].dtype == jnp.bfloat16
    row = _row(1, WIDTH)
    caches = _prefill(model, caches, tokens, 60, row, 0)
    got, _, _ = _decode(model, caches, row[None], [60], [True],
                        tokens[60:, None])
    spread = float(np.std(want[60:]))
    assert np.max(np.abs(got[:, 0] - want[60:])) < 0.25 * spread


# -- the two attention paths ----------------------------------------------------------

def _latent_inputs(seed, slots=3):
    """A latent pool of random rows, a table, and one layer's weights."""
    cfg = tiny_cfg()
    lat = tiny_spec(cfg).latent
    p = ref.init_weights(cfg, seed)["layers"][0]
    p = dict(p, kv_b=p["kv_b"] * 20)
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(1 + slots * WIDTH, PAGE,
                                        lat.pool_row)), jnp.float32)
    table = 1 + rng.permutation(slots * WIDTH).reshape(
        slots, WIDTH).astype(np.int32)
    return lat, p, rng, pool, table


def _plain(lat, p, q_nope, q_rope, rows, seen):
    """Plain MLA of one query ``[H, .]`` over latent ``rows [n, row]``:
    keys and values of every head made from the latent, in numpy."""
    c, r = rows[:, :lat.kv_rank], rows[:, lat.kv_rank:lat.row]
    kv = (c @ np.asarray(p["kv_b"], np.float64)).reshape(
        len(rows), lat.heads, -1)
    scores = np.einsum("hd,nhd->hn", q_nope, kv[..., :lat.nope_dim]) \
        + np.einsum("hr,nr->hn", q_rope, r)
    scores = np.where(seen[None], scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("hn,nhv->hv", probs, kv[..., lat.nope_dim:]).reshape(-1)


@pytest.mark.parametrize("seed", [1, 2])
def test_absorbed_equals_plain(seed):
    """The decode step's absorbed read of selected rows, the chunk's plain
    masked read and a plain numpy form give one result."""
    lat, p, rng, pool, table = _latent_inputs(seed)
    q_nope = jnp.asarray(rng.normal(size=(3, 4, 12)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(3, 4, 4)), jnp.float32)
    at = np.stack([rng.permutation(MAX_LEN)[:TOPK] for _ in range(3)])
    real = np.ones((3, TOPK), bool)
    real[1, 5:] = False                       # a stream that sees only 5
    got = np.asarray(LA.attend_step(
        lat, p, q_nope, q_rope, pool, jnp.asarray(table),
        jnp.asarray(at, jnp.int32), jnp.asarray(real)))
    flat = np.asarray(pool, np.float64)
    for s in range(3):
        rows = flat[table[s]].reshape(MAX_LEN, -1)
        seen = np.zeros(MAX_LEN, bool)
        seen[at[s][real[s]]] = True
        want = _plain(lat, p, np.asarray(q_nope[s]), np.asarray(q_rope[s]),
                      rows, seen)
        np.testing.assert_allclose(got[s], want, atol=2e-5)
    # the chunk's form: query rows 100.. of slot 0, each over a set given
    # as sortable bits: a row selects the positions marked 2 (above the
    # threshold 1), and of those marked 1 the ones up to ``last``
    t, start = 8, 96
    marks = rng.integers(0, 3, (t, MAX_LEN)).astype(np.uint32)
    last = rng.integers(0, MAX_LEN, t).astype(np.int32)
    pos = np.arange(MAX_LEN)
    qn = jnp.asarray(rng.normal(size=(t, 4, 12)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(t, 4, 4)), jnp.float32)
    got = np.asarray(LA.attend_chunk(
        lat, p, qn, qr, pool, jnp.asarray(table[0]), start,
        jnp.asarray(marks), jnp.ones(t, jnp.uint32), jnp.asarray(last)))
    rows = flat[table[0]].reshape(MAX_LEN, -1)
    for i in range(t):
        seen = ((marks[i] > 1) | ((marks[i] == 1) & (pos <= last[i]))) \
            & (pos <= start + i)
        want = _plain(lat, p, np.asarray(qn[i]), np.asarray(qr[i]), rows,
                      seen)
        np.testing.assert_allclose(got[i], want, atol=2e-5)


# -- the selection ---------------------------------------------------------------------

def _scores(seed, rows, ties):
    """Index scores ``[rows, MAX_LEN]`` of the queries at positions
    ``start ..``, ``-inf`` past each; with ``ties`` drawn from few values
    (zero among them, as a ReLU gives it) so that many positions tie."""
    rng = np.random.default_rng(seed)
    start = int(rng.integers(0, MAX_LEN - rows + 1))
    scores = rng.normal(size=(rows, MAX_LEN)).astype(np.float32)
    if ties:
        scores = rng.choice(np.asarray([-1.5, 0.0, 0.0, 0.25, 3.0],
                                       np.float32), (rows, MAX_LEN))
    t = start + np.arange(rows)
    return np.where(np.arange(MAX_LEN)[None] <= t[:, None], scores,
                    -np.inf).astype(np.float32), start


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_selected_sets_are_the_references(seed, ties):
    """A step's top-k positions and a chunk's threshold-and-last mask are
    the set that ``lax.top_k`` gives the reference (ties to the lower
    position), and all the positions a row sees while it sees fewer."""
    lat = dataclasses.replace(LA.LatentSpec(), index_topk=TOPK,
                              chunk_tile=16, attend_tile=32)
    rows = 32
    scores, start = _scores(seed, rows, ties)
    kth, last = ref.kth_largest(jnp.asarray(scores), TOPK)
    want = np.asarray(ref.selected(jnp.asarray(scores), kth, last))
    seen = np.isfinite(scores)
    few = seen.sum(axis=1) <= TOPK
    np.testing.assert_array_equal(want[few], seen[few])
    assert (want.sum(axis=1) == np.minimum(seen.sum(axis=1), TOPK)).all()
    # a decode step: each row is one slot's scores
    at, real = LA.select_step(lat, jnp.asarray(scores))
    got = np.zeros_like(want)
    for r in range(rows):
        got[r, np.asarray(at)[r][np.asarray(real)[r]]] = True
    np.testing.assert_array_equal(got, want)
    # a chunk: the rows are the queries start .. start + rows - 1
    bits = jnp.where(jnp.asarray(seen), LA._sortable(jnp.asarray(scores)),
                     jnp.uint32(0))
    threshold, upto = LA.select_chunk(lat, bits, start)
    bits, threshold, upto = map(np.asarray, (bits, threshold, upto))
    got = ((bits > threshold[:, None])
           | ((bits == threshold[:, None])
              & (np.arange(MAX_LEN)[None] <= upto[:, None]))) & seen
    np.testing.assert_array_equal(got, want)


def test_sortable_bits_keep_the_order_of_the_scores():
    x = np.asarray([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, 7e8],
                   np.float32)
    bits = np.asarray(LA._sortable(jnp.asarray(x))).astype(np.int64)
    assert (np.diff(bits) >= 0).all() and bits[0] > 0
    assert (np.diff(bits)[[0, 1, 4, 5, 6]] > 0).all()


def test_the_indexers_scores_are_the_references(model):
    """``index_step`` and ``index_chunk`` over a written index pool against
    the reference's dense scores of the same keys."""
    cfg, weights, lm, _, _ = model
    lat, p = lm.spec.latent, weights["layers"][1]
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(MAX_LEN, 64)), jnp.float32)
    c_q = jnp.asarray(rng.normal(size=(MAX_LEN, 32)), jnp.float32)
    q, k, w = ref.indexer(cfg, p, u, c_q, "highest")
    got_q, got_k, got_w = LA.index_project(
        lat, p, u, c_q, jnp.arange(MAX_LEN), lm.spec.rope_theta)
    for got, want in ((got_q, q), (got_k, k), (got_w, w)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
    row = _row(2, WIDTH)
    pool = jnp.zeros((2 + WIDTH, PAGE, 8), jnp.float32).at[
        jnp.asarray(row)].set(k.reshape(WIDTH, PAGE, 8))
    want = np.concatenate([
        np.asarray(ref.index_scores(
            jnp.pad(q, ((0, ref.ROWS), (0, 0), (0, 0)))[f:f + ref.ROWS],
            jnp.pad(w, ((0, ref.ROWS), (0, 0)))[f:f + ref.ROWS], k, f,
            "highest")) for f in range(0, MAX_LEN, ref.ROWS)])[:MAX_LEN]
    lengths = np.asarray([5, 77, 127], np.int32)
    got = np.asarray(LA.index_step(
        lat, q[lengths], w[lengths], pool, jnp.asarray(np.stack([row] * 3)),
        jnp.asarray(lengths), jnp.asarray([True, True, True])))
    np.testing.assert_allclose(got[:, :MAX_LEN], want[lengths], atol=1e-5)
    start = 64
    bits = np.asarray(LA.index_chunk(lat, q[start:start + 32],
                                     w[start:start + 32], pool,
                                     jnp.asarray(row), start))
    seen = np.isfinite(want[start:start + 32])
    np.testing.assert_array_equal(bits[:, :MAX_LEN] != 0, seen)
    np.testing.assert_array_equal(
        bits[:, :MAX_LEN][seen],
        np.asarray(LA._sortable(jnp.asarray(
            np.asarray(LA.index_step(
                lat, q[start:start + 32], w[start:start + 32], pool,
                jnp.asarray(np.stack([row] * 32)),
                jnp.arange(start, start + 32),
                jnp.ones(32, bool)))[:, :MAX_LEN])))[seen])


def test_interleaved_rotary_is_the_references():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(9, 3, 8)), jnp.float32)
    at = jnp.asarray(rng.integers(0, 30000, 9))
    np.testing.assert_allclose(
        np.asarray(LA.rotary_interleaved(x, at, 1e6)),
        np.asarray(ref.rotary(x, at, 1e6)), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(LA.rotary_interleaved(x[:, 0], at, 1e6)),
        np.asarray(ref.rotary(x[:, 0], at, 1e6)), atol=1e-5)


# -- routing and the shares ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_chosen_experts_and_gates_are_the_references(seed):
    cfg = tiny_cfg()
    p = ref.init_weights(cfg, seed)["layers"][1]
    assert float(jnp.std(p["router_bias"])) > 0.01
    h = jax.random.normal(jax.random.PRNGKey(seed), (50, 64)) * 3.0
    choice, gates = moe.route(h, p["router"], 4, "sigmoid",
                              p["router_bias"], 2.5)
    want_choice, want_gates = ref.route(cfg, p, h, "highest")
    np.testing.assert_array_equal(np.asarray(choice),
                                  np.asarray(want_choice))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_gates),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 2.5,
                               atol=1e-5)


def test_the_correction_bias_moves_the_choice_and_not_the_gates():
    cfg = tiny_cfg()
    p = ref.init_weights(cfg, 7)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(7), (40, 64)) * 3.0
    plain, plain_gates = moe.route(h, p["router"], 4, "sigmoid", None, 2.5)
    bias = jnp.zeros(EXPERTS).at[11].set(10.0)  # expert 11 is always chosen
    choice, gates = moe.route(h, p["router"], 4, "sigmoid", bias, 2.5)
    assert (np.asarray(choice)[:, 0] == 11).all()
    assert not (np.asarray(plain) == 11).all(axis=0).any()
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        h, p["router"], precision=jax.lax.Precision.HIGHEST)))
    kept = np.take_along_axis(scores, np.asarray(choice), axis=1)
    # the gates are the chosen scores over their sum, the bias nowhere
    np.testing.assert_allclose(
        np.asarray(gates), 2.5 * kept / kept.sum(axis=1, keepdims=True),
        atol=1e-6)
    assert (np.asarray(gates)[:, 0] < 2.5 * 0.999).all()
    # a token that chose expert 11 before keeps all its gates
    same = (np.sort(np.asarray(plain), axis=1)
            == np.sort(np.asarray(choice), axis=1)).all(axis=1)
    assert same.any() and not same.all()
    for row in np.nonzero(same)[0]:
        np.testing.assert_allclose(np.sort(np.asarray(gates)[row]),
                                   np.sort(np.asarray(plain_gates)[row]),
                                   atol=1e-6)
    with pytest.raises(ValueError, match="no router scoring named"):
        moe.route(h, p["router"], 4, "tanh")


def test_ties_go_to_the_lower_index():
    choice, gates = moe.route(jnp.ones((3, 4)), jnp.zeros((4, 8)), 2,
                              "sigmoid", None, 2.5)
    np.testing.assert_array_equal(np.asarray(choice), [[0, 1]] * 3)
    np.testing.assert_allclose(np.asarray(gates), 1.25)


def _share(lm, held):
    """The decoder of the chip that holds ``held``, with its tables."""
    spec = dataclasses.replace(lm.spec, held_experts=tuple(held))
    part = LayeredDecoder(spec, prefill_chunk=CHUNK)
    at = np.asarray(held)
    part.set_params({**lm.params, "layers": [
        {n: (w[at] if n in ("w_gate", "w_up", "w_down") else w)
         for n, w in p.items()} for p in lm.params["layers"]]})
    return part


def test_the_shares_of_the_experts_add_up(model):
    """Chips that hold experts 0-3, 4-7, 8-11 and 12-15 each compute their
    part of an expert layer (and the shared expert, every one alike); the
    routed parts, with the shared expert counted once, sum to the uncut
    layer's output, and each part is the reference's for that share."""
    cfg, weights, lm, _, _ = model
    p = weights["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(8), (40, 64)) * 2.0
    valid = jnp.ones(40, bool)
    whole, sizes = lm._moe(p, x, valid)
    h = ref.rms_norm(p["norm2"], x, cfg["rms_norm_eps"])
    want = x + ref.feed_forward(cfg, "moe", p, h, "highest")
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=2e-5)
    assert int(sizes.sum()) == 40 * 4 and sizes.shape == (EXPERTS,)
    shared = np.asarray(moe.shared(h, p["shared_gate"], p["shared_up"],
                                   p["shared_down"]))
    total = np.zeros_like(shared)
    for first in range(0, EXPERTS, 4):
        held = tuple(range(first, first + 4))
        part = _share(lm, held)
        assert part.spec.layer_shapes("mla-dsa", "moe")[0]["w_gate"] \
            == (4, 64, 32)
        got, counts = part._moe(part.params["layers"][2], x, valid)
        routed = np.asarray(ref.feed_forward(
            cfg, "moe", p, h, "highest", held=held, with_shared=False))
        np.testing.assert_allclose(np.asarray(got - x) - shared, routed,
                                   atol=2e-5)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(sizes)[first:first + 4])
        total += routed
    assert float(np.abs(total).max()) > 0
    np.testing.assert_allclose(total + shared, np.asarray(whole - x),
                               atol=5e-5)


def test_rows_of_no_held_expert_give_the_shared_experts_output_alone(model):
    """With 16 of 256 held most assignments land on absent experts; a row
    none of whose experts is held, and a row that stands for nothing, read
    no routed expert, and the shared expert is all the layer adds."""
    cfg, weights, lm, _, _ = model
    p = weights["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(9), (30, 64)) * 2.0
    h = ref.rms_norm(p["norm2"], x, cfg["rms_norm_eps"])
    choice, _ = moe.route(h, p["router"], 4, "sigmoid", p["router_bias"],
                          2.5)
    held = (3, 9)
    part = _share(lm, held)
    got, counts = part._moe(part.params["layers"][1], x,
                                  jnp.arange(30) != 4)
    none = ~np.isin(np.asarray(choice), held).any(axis=1)
    none[4] = False
    assert 3 <= none.sum() < 30
    shared = np.asarray(moe.shared(h, p["shared_gate"], p["shared_up"],
                                   p["shared_down"]))
    np.testing.assert_allclose(np.asarray(got - x)[none], shared[none],
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got - x)[4], shared[4], atol=1e-6)
    some = ~none
    some[4] = False
    assert np.abs(np.asarray(got - x)[some] - shared[some]).max() > 1e-3
    assert int(counts.sum()) == int(np.isin(
        np.asarray(choice)[np.arange(30) != 4], held).sum())


def test_a_model_that_holds_a_share_follows_the_reference_with_that_share():
    """The whole decoder with experts 0-3 of 16 held (the cell holds 16 of
    256): prefill and decode agree with the reference given that share."""
    cfg = tiny_cfg(held_experts=[0, 1, 2, 3], n_routed_experts=4)
    weights, lm = build(cfg)
    assert weights["layers"][1]["w_gate"].shape == (4, 64, 32)
    assert weights["layers"][1]["router"].shape == (64, EXPERTS)
    model = (cfg, weights, lm, jax.jit(lm.prefill_chunk),
             jax.jit(lm.paged_state_step))
    tokens = np.random.default_rng(12).integers(1, VOCAB, 50).astype(np.int32)
    want = np.asarray(ref.logits(cfg, weights, tokens[None]))[0]
    caches = lm.init_paged_caches(1 + WIDTH, PAGE, slots=1)
    row = _row(1, WIDTH)
    caches = _prefill(model, caches, tokens, 40, row, 0)
    got, _, stats = _decode(model, caches, row[None], [40], [True],
                            tokens[40:, None])
    np.testing.assert_allclose(got[:, 0], want[40:], atol=3e-6)
    # the counters count what the held experts got: under 4 a token a layer
    assert (stats[:, 2] <= 3 * 4).all() and stats[:, 2].sum() > 0
    assert (stats[:, 0] <= 4).all()


# -- streams beside each other --------------------------------------------------------------

def test_joins_and_leaves_between_chunks_leave_the_others_logits_unchanged(
        model):
    cfg, weights, lm, _, _ = model
    rng = np.random.default_rng(13)
    resident = rng.integers(1, VOCAB, 60).astype(np.int32)
    joining = rng.integers(1, VOCAB, 90).astype(np.int32)
    rows = [_row(1, WIDTH), _row(1 + WIDTH, WIDTH)]

    def run(with_join):
        caches = lm.init_paged_caches(1 + 2 * WIDTH, PAGE, slots=2)
        caches = _prefill(model, caches, resident, 40, rows[0], 0)
        table = np.zeros((2, WIDTH), np.int32)
        table[0] = rows[0]
        state = {"lengths": [40, 0], "at": 40, "out": []}

        def steps(caches, _):
            feed = np.zeros((3, 2), np.int32)
            feed[:, 0] = resident[state["at"]:state["at"] + 3]
            got, caches, _ = _decode(model, caches, table, state["lengths"],
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
    assert alone.shape == beside.shape == (9, VOCAB)
    np.testing.assert_array_equal(alone, beside)


def test_a_freed_page_overwritten_by_another_stream_changes_nothing(model):
    """Stream B ends and its pages go to stream C, which is fed into them
    while stream A decodes; A's logits are the reference's throughout, and
    C's are the reference's on pages that held B's rows."""
    cfg, weights, lm, _, _ = model
    rng = np.random.default_rng(14)
    a = rng.integers(1, VOCAB, 80).astype(np.int32)
    b = rng.integers(1, VOCAB, 70).astype(np.int32)
    c = rng.integers(1, VOCAB, 50).astype(np.int32)
    want_a = np.asarray(ref.logits(cfg, weights, a[None]))[0]
    want_c = np.asarray(ref.logits(cfg, weights, c[None]))[0]
    caches = lm.init_paged_caches(1 + 2 * WIDTH, PAGE, slots=2)
    rows = [_row(1, WIDTH), _row(1 + WIDTH, WIDTH)]
    caches = _prefill(model, caches, a, 60, rows[0], 0)
    caches = _prefill(model, caches, b, 69, rows[1], 1)     # B fills pages
    table = np.stack(rows)
    got, caches, _ = _decode(model, caches, table, [60, 69], [True, True],
                             np.stack([a[60:65], np.r_[b[69], [1] * 4]], 1))
    np.testing.assert_allclose(got[:, 0], want_a[60:65], atol=3e-6)
    # B leaves; C takes the same pages in another order and is fed in
    row_c = rows[1][::-1].copy()
    caches = _prefill(model, caches, c, 45, row_c, 1)
    table = np.stack([rows[0], row_c])
    got, caches, _ = _decode(model, caches, table, [65, 45], [True, True],
                             np.stack([a[65:70], c[45:50]], 1))
    np.testing.assert_allclose(got[:, 0], want_a[65:70], atol=3e-6)
    np.testing.assert_allclose(got[:, 1], want_c[45:50], atol=3e-6)


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
    (dict(spec_k=2), "speculative decoding is refused .* no verify step "
                     "over several positions"),
    (dict(kv_page_len=16), "kv_page_len must be the model's page, 8"),
    (dict(temperature=0.7), "sampling is not wired"),
])
def test_the_server_refuses_by_reason(model, tmp_path, more, reason):
    with pytest.raises(ValueError, match=reason):
        _server(model, tmp_path, **more)


def test_register_prefix_fit_and_int8_pools_refuse_by_reason(model, tmp_path):
    srv, _ = _server(model, tmp_path)
    with pytest.raises(RuntimeError, match="prefilled in chunks: its chunk "
                                           "program has no form that starts "
                                           "from another stream's pages"):
        srv.register_prefix([1, 2, 3])
    with pytest.raises(NotImplementedError, match="no training path"):
        srv.lm.fit(np.zeros((2, 8)))
    with pytest.raises(NotImplementedError, match="int8 pages"):
        srv.lm.init_paged_caches(8, PAGE, int8=True)
    with pytest.raises(ValueError, match="an mla-dsa layer needs its sizes"):
        dataclasses.replace(srv.lm.spec, latent=None)
    with pytest.raises(ValueError, match="3 feed-forwards for 4 mixers"):
        dataclasses.replace(srv.lm.spec, ffns=("silu", "moe", "moe"))
    with pytest.raises(ValueError, match="router 'tanh'"):
        dataclasses.replace(srv.lm.spec, router="tanh")
    assert not srv.lm.recurrent and srv.lm.chunked and not srv.lm.window_len
    assert srv.lm.step_stats == (
        "moe_experts_touched", "moe_expert_load", "moe_assignments",
        "sparse_positions_read", "dsa_positions_scored")


def test_one_budget_is_derived_so_that_every_slot_reaches_max_len(
        model, tmp_path):
    srv, _ = _server(model, tmp_path)
    assert srv.num_pages == SLOTS * WIDTH + 1 and srv._window is None
    lat = srv.lm.spec.latent
    assert lat.row == 20 and lat.pool_row == 128
    assert [(c["latent"].shape, c["index"].shape) for c in srv._caches] \
        == [((srv.num_pages, PAGE, 128), (srv.num_pages, PAGE, 8))] * 4
    snap = srv.health_snapshot()
    assert snap["kv_pages_in_use"] == {"full": 0, "window": None}
    assert snap["dsa_positions_scored"] == {"mean": None, "window": 0}


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
    prompts = [rng.integers(1, VOCAB, n).tolist()
               for n in (90, 20, 1, 65, 33)]
    news = [30, 6, 38, 8, 7]
    for i, (p, n) in enumerate(zip(prompts, news)):
        inq.enqueue_prompt(f"r{i}", p, max_new_tokens=n)
    for _ in range(400):
        srv.serve_step()
    for i, (p, n) in enumerate(zip(prompts, news)):
        got = outq.query(f"r{i}")
        assert got["done"] and len(got["value"]) == n, got
        assert _reference_gap(cfg, weights, p, got["value"]) < 3e-6
    snap = srv.health_snapshot()
    assert snap["prefill_chunks_total"] == sum(
        len(srv.lm.chunk_plan(len(p) - 1)) for p in prompts)
    assert snap["kv_pages_in_use"] == {"full": 0, "window": None}
    assert snap["kv_pages_free"] == srv.num_pages - 1
    steps = snap["moe_experts_touched"]["window"]
    assert 0 < steps == snap["sparse_positions_read"]["window"] \
        == snap["dsa_positions_scored"]["window"] <= sum(news)
    # 4 experts a token of 16, all held, three expert layers
    assert snap["moe_assignments_total"] == 3 * 4 * sum(news)
    assert 1.0 <= snap["moe_experts_touched"]["mean"] <= 4.0 * slots
    assert snap["sparse_positions_read"]["mean"] <= TOPK
    assert snap["dsa_positions_scored"]["mean"] > TOPK
    assert snap["counters"]["errors"] == 0


def test_a_pool_sharded_over_pages_serves_the_same_tokens(model, tmp_path):
    """``kv_shard`` is placement alone: the latent and index pools' pages
    spread over two devices give the tokens of the one-device pool."""
    served = []
    for shard in (1, 2):
        srv, src = _server(model, tmp_path, slots=2, max_new_tokens=12,
                           kv_shard=shard, kv_pages=2 * WIDTH + 2)
        inq, outq = InputQueue(src), OutputQueue(src)
        rng = np.random.default_rng(3)
        for i, n in enumerate((50, 20, 70)):
            inq.enqueue_prompt(f"r{i}", rng.integers(1, VOCAB, n).tolist(),
                               max_new_tokens=10)
        for _ in range(150):
            srv.serve_step()
        served.append([outq.query(f"r{i}")["value"] for i in range(3)])
        assert all(len(v) == 10 for v in served[-1])
        assert srv.health_snapshot()["kv_shards"] == shard
    assert served[0] == served[1]
