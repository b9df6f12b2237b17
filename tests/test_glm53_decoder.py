"""The layered decoder with GLM-5.3-Flash's layers (gated delta-rule linear
attention with a decay a channel and a short convolution, latent attention
without rotary over a selection of pooled key blocks, four residual streams
mixed through Sinkhorn-normalised matrices, clamped gated-SiLU experts of
which the chip holds a share) against its plain reference, at a tiny size on
the CPU: chunked prefill then paged decode agree with the reference's full
forward; the chunked delta rule and its decode step agree with a scan over
single positions; a program that drops the delta term
or runs one Sinkhorn iteration does not; the pooled selection is the brute
force's; the clamp engages; the shares of a layer's experts add up.

The preset: a KDA layer with its dense feed-forward, then a sparse latent
layer and two KDA layers with experts; hidden 64, 4 KDA heads of 16, 4
latent heads of 16, ranks 32 and 16, an indexer of 2 heads of 8 over blocks
of 4 with top-16 (3 blocks and the query's own), 16 experts of width 32
with 4 a token and a shared one, 4 residual streams, pages of 8, chunks of
32.

Tolerances: the program runs float32 parameters here, so it and the
reference differ by the order of their float32 sums (the chunked form's
block products against the scan's, the step's sums against the einsums)
and logits agree to 2e-5 of logits of spread 0.03-0.1; each planted fault
moves them by more than a hundred times that."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from analytics_zoo_tpu.capture.decoder import (DecoderSpec,  # noqa: E402
                                               LayeredDecoder)
from analytics_zoo_tpu.ops import delta_attention as DA  # noqa: E402
from analytics_zoo_tpu.ops import latent_attention as LA  # noqa: E402
from analytics_zoo_tpu.ops import moe  # noqa: E402
from perfbench.references import glm53_lm as ref  # noqa: E402

PAGE, MAX_LEN, SLOTS, TOPK, CHUNK, KPOOL = 8, 128, 3, 16, 32, 4
WIDTH = MAX_LEN // PAGE
VOCAB, EXPERTS = 97, 16
#: float32 programs against the float32 reference: the order of sums
ATOL = 2e-5


def tiny_cfg(**more):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "glm_5_3_flash.json")) as f:
        cfg = json.load(f)
    cfg.update(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_head_dim=16, v_head_dim=16, index_n_heads=2,
        index_head_dim=8, index_topk=TOPK, n_routed_experts=EXPERTS,
        n_routed_experts_published=EXPERTS, held_experts=None,
        num_experts_per_tok=4, num_hidden_layers=4, first_k_dense_replace=1,
        layer_types=["linear_attention", "deepseek_sparse_attention",
                     "linear_attention", "linear_attention"],
        mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
        linear_attn_config=dict(cfg["linear_attn_config"], num_heads=4,
                                head_dim=16),
        n_positions=MAX_LEN, param_dtype="float32",
        router_bias_spread=0.02)
    cfg["serving"].update(max_new_tokens=24, kv_page_len=PAGE,
                          prefill_chunk=CHUNK, slots=SLOTS, kv_pages=None)
    cfg.update(more)
    return cfg


def tiny_spec(cfg):
    """The spec of ``cfg`` with tiles of 16 and 32 positions and blocks of
    16 in the chunked delta rule, so that every loop runs several times."""
    spec = DecoderSpec.from_config(cfg, MAX_LEN, page_len=PAGE)
    return dataclasses.replace(
        spec, latent=dataclasses.replace(
            spec.latent, step_tile=16, chunk_tile=16, attend_tile=32),
        delta=dataclasses.replace(spec.delta, block=16))


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


def _ref_logits(cfg, weights, tokens, **planted):
    """The reference's logits of ``tokens``, computed over the row padded
    to ``MAX_LEN`` (causal: the padding changes nothing before it), so that
    every case reuses one compiled forward."""
    row = np.zeros((1, MAX_LEN), np.int32)
    row[0, :len(tokens)] = tokens
    return np.asarray(ref.logits(cfg, weights, row, **planted))[0][
        :len(tokens)]


def _serve(model, tokens, fed, slot=1):
    """Prefill ``tokens[:fed]`` in chunks into ``slot``, then decode the rest
    one position a step beside two idle slots; the logits ``[steps, V]``
    and the steps' counts."""
    _, weights, lm, chunk, step = model
    caches = lm.init_paged_caches(1 + WIDTH * SLOTS, PAGE, slots=SLOTS)
    row = _row(5, WIDTH)
    for start, width in lm.chunk_plan(fed):
        n = max(0, min(width, fed - start))
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = tokens[start:start + n]
        caches = chunk(weights, padded, caches, jnp.asarray(row), slot,
                       start, n)
    table = np.zeros((SLOTS, WIDTH), np.int32)
    table[slot] = row
    lengths = np.zeros(SLOTS, np.int32)
    lengths[slot] = fed
    active = np.arange(SLOTS) == slot
    out, stats = [], []
    for t in tokens[fed:]:
        feed = np.zeros(SLOTS, np.int32)
        feed[slot] = t
        logits, caches, read = step(weights, feed, jnp.asarray(lengths),
                                    jnp.asarray(table), caches,
                                    jnp.asarray(active))
        out.append(np.asarray(logits)[slot])
        stats.append(np.asarray(read))
        lengths = lengths + active
    return np.stack(out), np.stack(stats)


# -- chunked prefill then paged decode against the full forward ---------------

@pytest.mark.parametrize("prompt,new", [
    (10, 5),      # one chunk of the smallest bucket; every block is read
    (33, 7),      # a chunk's end on a page's end, then past the selection
    (61, 12),     # two chunks, the last padded; a decode over the selection
    (100, 9),     # four chunks, the last of another bucket
])
def test_chunked_prefill_then_decode_agrees_with_the_reference(
        model, prompt, new):
    cfg, weights, _, _, _ = model
    tokens = np.random.default_rng(prompt).integers(
        1, VOCAB, prompt + new).astype(np.int32)
    want = _ref_logits(cfg, weights, tokens)
    fed = prompt - 1
    got, stats = _serve(model, tokens, fed)
    np.testing.assert_allclose(got, want[fed:], atol=ATOL)
    # the step's counts: positions read (the top-3 blocks of 4 and the
    # query's own, up to it) and the blocks wholly before the query scored
    at = fed + np.arange(new + 1)
    np.testing.assert_allclose(stats[:, 3], np.minimum(
        at + 1, (TOPK // KPOOL - 1) * KPOOL + at % KPOOL + 1))
    np.testing.assert_allclose(stats[:, 4], at // KPOOL)


@pytest.mark.parametrize("fault", ["no_delta_term", "one_sinkhorn_iteration"])
def test_a_planted_omission_fails_the_tolerance(model, fault):
    """The reference with the ``- b k k^T`` term dropped, or with one
    Sinkhorn normalisation in the place of 20, lies further from the
    program than the tolerance by a hundred times and more."""
    cfg, weights, _, _, _ = model
    tokens = np.random.default_rng(7).integers(1, VOCAB, 70).astype(np.int32)
    got, _ = _serve(model, tokens, 60)
    planted = dict(delta=False) if fault == "no_delta_term" else dict(iters=1)
    wrong = _ref_logits(cfg, weights, tokens, **planted)[60:]
    assert np.max(np.abs(got - wrong)) > 100 * ATOL


# -- the delta rule's two forms -----------------------------------------------

def _scan(q, k, v, g, beta, state):
    """The recurrence position by position, in float64."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    s = np.swapaxes(np.asarray(state, np.float64), -1, -2)   # [H, Dk, Dv]
    out = []
    for t in range(q.shape[0]):
        s = np.exp(g[t])[..., None] * s
        s = s - beta[t][:, None, None] * k[t][..., None] \
            * np.einsum("hk,hkv->hv", k[t], s)[:, None]
        s = s + beta[t][:, None, None] * k[t][..., None] * v[t][:, None]
        out.append(np.einsum("hkv,hk->hv", s, q[t]))
    return np.asarray(out), np.swapaxes(s, -1, -2)


def _delta_inputs(seed, t, heads=2, dim=16):
    rng = np.random.default_rng(seed)

    def l2(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = l2(rng.normal(size=(t, heads, dim))) / np.sqrt(dim)
    k = l2(rng.normal(size=(t, heads, dim)))
    v = rng.normal(size=(t, heads, dim))
    # decays from -5 (forgotten in a step) to -1e-4 (kept for thousands)
    g = -5.0 / (1.0 + np.exp(-rng.normal(-3.0, 3.0, (t, heads, dim))))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(t, heads))))
    state = rng.normal(size=(heads, dim, dim)) * 0.3
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, state)]


@pytest.mark.parametrize("t,n_valid,block", [
    (64, 64, 16), (64, 37, 16), (32, 1, 32), (32, 0, 32), (128, 100, 64)])
def test_chunk_and_step_agree_with_the_scan(t, n_valid, block):
    """``delta_attention_chunk`` with any ``n_valid`` (its outputs up to
    there and the state after it) and ``delta_attention_step`` repeated
    against the plain scan; within float32's sums (1e-5 of outputs of
    spread 1, states of spread 1)."""
    q, k, v, g, beta, state = _delta_inputs(t + n_valid, t)
    spec = DA.DeltaSpec(heads=2, head_dim=16, rank=16, block=block)
    want, after = _scan(q[:n_valid], k[:n_valid], v[:n_valid],
                        g[:n_valid], beta[:n_valid], state)
    out, got = DA.delta_attention_chunk(q, k, v, g, beta, state, n_valid,
                                        spec)
    np.testing.assert_allclose(np.asarray(out)[:n_valid],
                               want.reshape(n_valid, 2, 16), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), after, atol=1e-5)
    # the step, one slot active beside one that keeps its state
    states = jnp.stack([state, state * 2])
    active = jnp.asarray([True, False])
    step = jax.jit(DA.delta_attention_step)
    outs = []
    for i in range(n_valid):
        def two(a):
            return jnp.stack([a[i], a[i]])
        o, states = step(two(q), two(k), two(v), two(g), two(beta), states,
                         active)
        outs.append(np.asarray(o[0]))
    if n_valid:
        np.testing.assert_allclose(np.stack(outs), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(states[0]), after, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(states[1]),
                                  np.asarray(state * 2))


def test_the_window_carries_across_chunks():
    """The convolution over a prompt fed in pieces (any cut, padded) equals
    the convolution over it whole."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(40, 6)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    whole, _ = DA.conv_chunk(x, jnp.zeros((3, 6)), taps, 40)
    window, parts = jnp.zeros((3, 6)), []
    for start, n in ((0, 13), (13, 2), (15, 25)):
        piece = jnp.zeros((16 if n < 16 else 32, 6)).at[:n].set(
            x[start:start + n])
        y, window = DA.conv_chunk(piece, window, taps, n)
        parts.append(y[:n])
    np.testing.assert_allclose(np.concatenate(parts), whole, atol=1e-6)
    y, w = DA.conv_step(x[:2], jnp.stack([window, window]), taps,
                        jnp.asarray([True, False]))
    np.testing.assert_array_equal(np.asarray(w[1]), np.asarray(window))


# -- the pooled selection -----------------------------------------------------

def test_pooled_selection_is_the_brute_force(model):
    """A decode step's selection over pooled keys (the sums that the pool
    holds, written position by position and by a chunk) against brute
    force: the 3 blocks of largest mean-key score among those wholly
    before the query, ties to the lower block, and the query's own block
    up to the query, whatever its score."""
    cfg, _, lm, _, _ = model
    lat = lm.spec.latent
    rng = np.random.default_rng(11)
    keys = rng.normal(size=(MAX_LEN, 8)).astype(np.float32)
    keys[40:48] = keys[32:40]              # blocks that tie with others
    pool = LA.init_latent_pool(1 + WIDTH, PAGE, lat, jnp.float32)
    row = _row(1, WIDTH)
    at = np.arange(64)
    pages, offs = row[at // PAGE][None], (at % PAGE)[None]
    rows = jnp.zeros((1, 64, 16))
    # a chunk writes the first 64 with 7 of padding, then steps write on
    pool = LA.write(pool, jnp.asarray(pages), jnp.asarray(offs), rows,
                    jnp.asarray(keys[None, :64]),
                    jnp.asarray(at < 57))
    write = jax.jit(LA.write)
    for p in range(57, MAX_LEN - 1):
        pool = write(pool, jnp.asarray([[row[p // PAGE]]]),
                        jnp.asarray([[p % PAGE]]), jnp.zeros((1, 1, 16)),
                        jnp.asarray(keys[None, p:p + 1]))
    q = rng.normal(size=(3, 2, 8)).astype(np.float32)
    w = rng.uniform(0.1, 1, (3, 2)).astype(np.float32)
    lengths = np.asarray([5, 60, MAX_LEN - 2], np.int32)
    table = np.stack([row] * 3)
    scores = LA.index_step(lat, jnp.asarray(q), jnp.asarray(w), pool["index"],
                           jnp.asarray(table), jnp.asarray(lengths),
                           jnp.ones(3, bool), jnp.float32)
    got_at, got_real = LA.select_step(lat, scores, jnp.asarray(lengths))
    means = keys[:MAX_LEN - 1 - (MAX_LEN - 1) % 4].reshape(-1, 4, 8).mean(1)
    for s, t in enumerate(lengths):
        whole = t // KPOOL
        brute = np.einsum("hd,bd->hb", q[s], means[:whole])
        brute = (np.maximum(brute, 0) * w[s][:, None]).sum(0)
        order = sorted(range(whole), key=lambda b: (-brute[b], b))
        want = set()
        for b in order[:TOPK // KPOOL - 1] + [whole]:
            want |= {p for p in range(b * 4, b * 4 + 4) if p <= t}
        got = set(np.asarray(got_at[s])[np.asarray(got_real[s])].tolist())
        assert got == want, (t, sorted(got ^ want))


# -- the feed-forward ---------------------------------------------------------

def test_the_clamp_engages():
    """A gate over the limit counts as the limit and an up projection is
    held to +-limit: the clamped expert differs from the plain one where it
    engages, and equals it elsewhere."""
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(8, 16)) * 4, jnp.float32)
                    for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    gate, up = h @ w_gate, h @ w_up
    assert float(jnp.max(gate)) > 10 and float(jnp.max(jnp.abs(up))) > 10
    got = moe.shared(h, w_gate, w_up, w_down, limit=10.0)
    want = (jax.nn.silu(jnp.minimum(gate, 10.0))
            * jnp.clip(up, -10.0, 10.0)) @ w_down
    plain = moe.shared(h, w_gate, w_up, w_down)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert float(jnp.max(jnp.abs(got - plain))) > 1.0
    small = h * 0.01           # nothing over the limit: the clamp is inert
    np.testing.assert_allclose(moe.shared(small, w_gate, w_up, w_down, 10.0),
                               moe.shared(small, w_gate, w_up, w_down),
                               atol=1e-6)


def test_shares_of_the_experts_add_up_to_the_layer():
    """Eight chips each holding 2 of the 16 experts: their parts of a
    layer's feed-forward, with the shared expert counted once, add up to
    the uncut reference's layer; the program's part of one share is the
    reference's part of it."""
    cfg = tiny_cfg()
    weights = ref.init_weights(cfg, 9)
    p = weights["layers"][1]
    h = jnp.asarray(np.random.default_rng(1).normal(size=(12, 64)),
                    jnp.float32)
    whole = ref.feed_forward(cfg, "moe", p, h, "highest")
    shares = [list(range(2 * i, 2 * i + 2)) for i in range(8)]
    parts = [ref.feed_forward(cfg, "moe", p, h, "highest", held=s,
                              with_shared=False) for s in shares]
    shared = ref.gated(h, p["shared_gate"], p["shared_up"],
                       p["shared_down"], "highest", cfg["swiglu_limit"])
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5)
    held = shares[3]
    choice, gates = moe.route(h, p["router"], 4, "sigmoid", p["router_bias"],
                              cfg["routed_scaling_factor"])
    got, _ = moe.experts(h, choice, gates, p["w_gate"][jnp.asarray(held)],
                         p["w_up"][jnp.asarray(held)],
                         p["w_down"][jnp.asarray(held)], held, None,
                         jax.nn.silu, cfg["swiglu_limit"])
    np.testing.assert_allclose(got, parts[3], atol=1e-5)
