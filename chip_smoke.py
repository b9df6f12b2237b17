#!/usr/bin/env python3
"""chip_smoke.py — the main path, once, on the chip, through the public API.

    python3 chip_smoke.py            # one TPU chip: kernels, train, serve
    python3 chip_smoke.py --chips 4  # four chips: only the two cross-chip
                                     # comparisons (data-parallel BERT,
                                     # tensor-parallel TransformerLM)

Everything runs in this one process (a chip belongs to one process at a
time; the server is a thread). Models are at the full width of a
configuration the repo supports, weights and data come from ``SEED``:

- *kernels*: every pallas kernel of ``ops/attention.py`` and
  ``ops/embedding_kernels.py`` against the repo's own reference, with a
  check that the pallas branch was the one taken.
- *train*: BERT-base fine-tune (``capture.text.BERTClassifier``, sequence
  128, batch 32, dropout on): a few ``fit`` steps, one ``evaluate``, a
  checkpoint, and a resume that continues the loss history.
- *serve*: ``GenerativeServing`` over a GPT-2-small ``TransformerLM``,
  paged KV pool, file queue and client SDK; every request answered exactly
  once and token-identical to serial ``generate()``.

Each phase prints one JSON line. The last line of standard output is only
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device as
JAX reports it. Any phase that fails, and any device that is not a TPU (or
not as many chips as asked for), makes ``ok`` false and the exit code
non-zero. Whatever is printed about time is set-up information for the
device named on the same line, not a benchmark. The script has no size or
device option: the phase functions take their sizes as arguments so that
``tests/test_tpu_compile.py`` can drive them on the CPU at tiny sizes.
"""
import argparse
import json
import logging
import os
import shutil
import sys
import tempfile
import time

import numpy as np

SEED = 0

# -- the configurations, at the widths their sources publish ------------------

#: BERT-base (bench.py ``bench_bert``): 12 blocks, 768 hidden, 12 heads
BERT_BASE = dict(vocab=30522, hidden_size=768, n_block=12, n_head=12,
                 max_position_len=512, intermediate_size=3072)
TRAIN = dict(bert=BERT_BASE, seq=128, batch=32, steps=4, lr=5e-5)

#: GPT-2 small: 12 blocks, 768 hidden, 12 heads, 1024 positions
GPT2_SMALL = dict(vocab_size=50257, hidden=768, n_block=12, n_head=12,
                  max_len=1024)
#: prompt lengths on both sides of the 16|32 prefill bucket edge
#: (``capture/lm.py PREFILL_BUCKETS``; a prompt of n tokens prefills n - 1).
#: ``fit_steps`` steps teach the model a successor language over
#: ``alphabet`` tokens (see ``_successor_language``)
SERVE = dict(lm=GPT2_SMALL, alphabet=64, fit_steps=120, fit_seq=128,
             fit_batch=8, lr=3e-4, must_learn=True,
             prompt_lens=(9, 17, 18, 40, 16), max_new=16, slots=4)

KERNELS = dict(
    flash=[((4, 12, 2048, 64), "bfloat16"),    # fused single-pass backward
           ((2, 8, 4096, 128), "bfloat16"),
           ((1, 4, 8192, 128), "bfloat16")],   # K/V past VMEM: two-pass
    flash_bias=((2, 4, 1024, 64), "bfloat16"),
    short_bias=((32, 12, 128, 64), "bfloat16"),    # the BERT-base step
    short_causal=[((8, 12, 512, 64), "bfloat16"),
                  ((1, 12, 16, 64), "float32"),    # TransformerLM prefill
                  ((1, 12, 32, 64), "float32")],
    table=(2 ** 20, 128), ids=8192, bag=4, scatter_rows=4096,
    # GPT-2 small's serving pool: slots, heads, head dim, page length,
    # table width, pages; a third of the slots empty, the rest mid-stream
    paged=dict(slots=48, heads=12, dim=64, page_len=16, width=64,
               pages=3073))

DP = dict(bert=BERT_BASE, seq=128, batch=32, steps=3, lr=5e-5)
#: on |loss| between the data mesh and one device, bf16 compute. The first
#: steps of BERT-base from its initialiser are not smooth (0.75, 5.1, 1.25),
#: which magnifies rounding: 0.0175 was seen on four v5e chips, 0.001 on
#: four virtual CPU devices.
DP_TOLERANCE = 0.05
#: as SERVE: both layouts learn the successor language, so that their
#: greedy tokens have a margin; the loss histories are compared over the
#: first ``compare_steps`` steps, before rounding has had time to grow
TP = dict(lm=GPT2_SMALL, alphabet=64, fit_steps=120, seq=128, batch=8,
          lr=3e-4, compare_steps=10, must_learn=True, prompt_len=24,
          max_new=16)

_device = {}  # filled by main(); labels every phase line


def require(condition, message):
    """A failed check fails the run (an ``assert`` would vanish under
    ``python -O`` and leave ``ok`` true)."""
    if not condition:
        raise AssertionError(message)


def emit(phase, **fields):
    """One JSON line for one phase, labelled with the device it ran on."""
    print(json.dumps({"phase": phase, "device": _device, **fields}),
          flush=True)


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-6))


def _timed_call(fn, *args):
    """``(result, compile seconds, run seconds, kernel in program?)`` of a
    jitted ``fn`` — lowered, compiled and run as separate steps."""
    import jax
    lowered = jax.jit(fn).lower(*args)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, round(t1 - t0, 3), round(time.perf_counter() - t1, 3), \
        has_kernel


def _distinct_devices(tree):
    """Over the leaves of ``tree``: the smallest number of distinct devices
    a leaf's addressable shards lie on."""
    import jax
    return min(len({s.device for s in leaf.addressable_shards})
               for leaf in jax.tree_util.tree_leaves(tree))


def _prefill_has_kernel(lm, bucket):
    """Whether the LM's prefill program at ``bucket`` tokens holds a pallas
    kernel."""
    import jax
    return "tpu_custom_call" in jax.jit(lm.prefill_kv).lower(
        lm.params, np.zeros((1, bucket), np.int32)).as_text()


# -- phase: kernels -----------------------------------------------------------

def phase_kernels(cfg, expect_pallas=True):
    """Each pallas kernel through its public entry point against the
    repo's reference; on the TPU the program must hold the kernel."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import embedding_kernels as ek
    from analytics_zoo_tpu.ops.attention import (
        dot_product_attention, flash_attention, flash_attention_lse,
        fused_short_applicable, fused_short_attention)

    checks = []
    key = jax.random.PRNGKey(SEED)

    def short_attention(q, k, v, key_bias=None, causal=False, **dropout):
        """The fused short-sequence kernel the way its callers reach it
        (``keras/layers/attention.py``, ``capture/lm.py``): it has no
        reference of its own, they choose."""
        if fused_short_applicable(q, k):
            return fused_short_attention(q, k, v, key_bias=key_bias,
                                         causal=causal, **dropout)
        bias = None if key_bias is None else key_bias[:, None, None, :]
        return dot_product_attention(q, k, v, bias=bias, causal=causal,
                                     **dropout)

    def qkv(shape, dtype, scale=0.5):
        ks = jax.random.split(jax.random.fold_in(key, len(checks)), 3)
        return [(jax.random.normal(k, shape, jnp.float32) * scale
                 ).astype(dtype) for k in ks]

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    def fwd_and_grads(attn):
        """out, dq, dk, dv of ``attn(q, k, v)`` in one program."""
        def run(q, k, v):
            def loss(q, k, v):
                out = attn(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * 0.01), out
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (out,) + grads
        return run

    def compare(name, shape, dtype, got_fn, want_fn, args, tols):
        got, c_s, r_s, kernel = _timed_call(got_fn, *args)
        want, _, _, _ = _timed_call(want_fn, *args)
        got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
        require(len(got) == len(want) == len(tols),
                f"{name}: {len(got)} results, {len(tols)} tolerances")
        errs = [_rel_err(g, w) for g, w in zip(got, want)]
        checks.append({
            "kernel": name, "shape": list(shape), "dtype": str(dtype),
            "branch": "pallas" if kernel else "reference",
            "rel_err": [round(e, 6) for e in errs], "tol": list(tols),
            "compile_s": c_s, "run_s": r_s,
            "ok": (all(e <= t for e, t in zip(errs, tols))
                   and (kernel or not expect_pallas))})

    # streaming flash attention, causal, forward + all three gradients
    for shape, dtype in cfg["flash"]:
        compare("flash_attention fwd+bwd causal", shape, dtype,
                fwd_and_grads(lambda q, k, v: flash_attention(
                    q, k, v, causal=True)),
                fwd_and_grads(lambda q, k, v: dot_product_attention(
                    *f32(q, k, v), causal=True)),
                qkv(shape, dtype), (2e-2, 4e-2, 4e-2, 4e-2))

    # key-bias (padding mask) form: forward kernel, blockwise backward
    shape, dtype = cfg["flash_bias"]
    b, _, s, d = shape
    kb = jnp.where(jax.random.uniform(jax.random.fold_in(key, 101), (b, s))
                   > 0.2, 0.0, -1e9).astype(jnp.float32)
    kb = kb.at[:, 0].set(0.0)
    compare("flash_attention key-bias fwd (+ blockwise bwd)", shape, dtype,
            fwd_and_grads(lambda q, k, v: flash_attention(
                q, k, v, bias=kb[:, None, None, :])),
            fwd_and_grads(lambda q, k, v: dot_product_attention(
                *f32(q, k, v), bias=kb[:, None, None, :])),
            qkv(shape, dtype), (2e-2, 4e-2, 4e-2, 4e-2))

    # logsumexp form (ring hops): both outputs, lse cotangent included
    def lse_loss(attn):
        def run(q, k, v):
            def loss(q, k, v):
                out, lse = attn(q, k, v)
                return (jnp.sum(out.astype(jnp.float32)) * 0.01
                        + jnp.sum(lse) * 0.001), (out, lse)
            (_, outs), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return outs + grads
        return run

    def ref_lse(q, k, v):
        q, k, v = f32(q, k, v)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        return (dot_product_attention(q, k, v, causal=True),
                jax.scipy.special.logsumexp(
                    jnp.where(causal, scores, -1e30), axis=-1))

    compare("flash_attention_lse fwd+bwd causal", shape, dtype,
            lse_loss(lambda q, k, v: flash_attention_lse(q, k, v,
                                                         causal=True)),
            lse_loss(ref_lse), qkv(shape, dtype),
            (2e-2, 2e-2, 4e-2, 4e-2, 4e-2))

    # fused short-sequence attention: padding mask, both directions
    shape, dtype = cfg["short_bias"]
    b, _, s, d = shape
    kb = jnp.where(jax.random.uniform(jax.random.fold_in(key, 102), (b, s))
                   > 0.2, 0.0, -30.0).astype(jnp.float32)
    compare("fused_short_attention key-bias fwd+bwd", shape, dtype,
            fwd_and_grads(lambda q, k, v: short_attention(
                q, k, v, key_bias=kb)),
            fwd_and_grads(lambda q, k, v: dot_product_attention(
                *f32(q, k, v), bias=kb[:, None, None, :])),
            qkv(shape, dtype, 0.4), (2e-2, 4e-2, 4e-2, 4e-2))

    # ... and its in-kernel dropout, which no reference can reproduce bit
    # for bit: the same key gives the same result, the kept weights still
    # average to one, and <out, g> == <v, dv> shows that the backward
    # kernel re-drew the forward's mask (out is linear in v)
    q, k, v = qkv(shape, dtype, 0.4)
    ones = jnp.ones_like(v)
    g = jax.random.normal(jax.random.fold_in(key, 103), shape,
                          jnp.float32).astype(dtype)
    rng = jax.random.PRNGKey(SEED + 1)

    def dropped(q, k, v):
        return short_attention(q, k, v, key_bias=kb, dropout_rate=0.1,
                               dropout_rng=rng)

    def dropout_facts(q, k, v):
        out, vjp = jax.vjp(lambda v_: dropped(q, k, v_), v)
        (dv,) = vjp(g)
        return (out, dropped(q, k, v), dropped(q, k, ones),
                jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)),
                jnp.sum(v.astype(jnp.float32) * dv.astype(jnp.float32)))

    (out, again, row_sums, lhs, rhs), c_s, r_s, kernel = _timed_call(
        dropout_facts, q, k, v)
    facts = {"repeatable": bool(jnp.array_equal(out, again)),
             "mean_kept_weight": round(float(jnp.mean(
                 row_sums.astype(jnp.float32))), 4),
             "vjp_identity_rel": round(abs(float(lhs) - float(rhs))
                                       / max(abs(float(lhs)), 1e-6), 5)}
    checks.append({
        "kernel": "fused_short_attention dropout 0.1 fwd+bwd",
        "shape": list(shape), "dtype": str(dtype),
        "branch": "pallas" if kernel else "reference", **facts,
        "compile_s": c_s, "run_s": r_s,
        "ok": (facts["repeatable"]
               and abs(facts["mean_kept_weight"] - 1.0) < 0.05
               and facts["vjp_identity_rel"] < 2e-2
               and (kernel or not expect_pallas))})

    # causal form (training at short lengths, and TransformerLM prefill)
    for shape, dtype in cfg["short_causal"]:
        compare("fused_short_attention causal fwd+bwd", shape, dtype,
                fwd_and_grads(lambda q, k, v: short_attention(
                    q, k, v, causal=True)),
                fwd_and_grads(lambda q, k, v: dot_product_attention(
                    *f32(q, k, v), causal=True)),
                qkv(shape, dtype, 0.4), (2e-2, 4e-2, 4e-2, 4e-2))

    # embedding kernels: a table made on the device, ids with padding and
    # out-of-range entries
    vocab, dim = cfg["table"]
    n, bag = cfg["ids"], cfg["bag"]
    table = jax.random.normal(jax.random.fold_in(key, 200), (vocab, dim),
                              jnp.float32)
    ids = jax.random.randint(jax.random.fold_in(key, 201), (n,), -2,
                             vocab + 2)
    bags = jax.random.randint(jax.random.fold_in(key, 202), (n, bag), -1,
                              vocab)
    grads = jax.random.normal(jax.random.fold_in(key, 203), (n, dim),
                              jnp.float32)

    compare("gather_rows (fill)", (vocab, dim), "float32",
            ek.gather_rows,
            lambda t, i: jnp.take(t, i, axis=0, mode="fill", fill_value=0),
            (table, ids), (0.0,))

    def with_table_grad(lookup):
        def run(t, i):
            out, vjp = jax.vjp(lambda t_: lookup(t_, i), t)
            return out, vjp(jnp.ones_like(out) * 0.5)[0]
        return run

    compare("gather_rows_clip fwd+bwd", (vocab, dim), "float32",
            with_table_grad(ek.gather_rows_clip),
            with_table_grad(lambda t, i: jnp.take(t, i, axis=0)),
            (table, jnp.clip(ids, 0, vocab - 1)), (0.0, 1e-5))
    for combiner in ("sum", "mean"):
        compare(f"gather_pool {combiner} fwd+bwd", (vocab, dim), "float32",
                with_table_grad(lambda t, i: ek.gather_pool(t, i, combiner)),
                with_table_grad(lambda t, i: ek._gather_pool_ref(
                    t, i, combiner, True)),
                (table, bags), (1e-5, 1e-5))

    qtable, scale, _ = jax.jit(ek.quantize_table)(table)
    compare("gather_pool_int8 (dequant in kernel)", (vocab, dim), "int8",
            lambda t, s_, i: ek.gather_pool_int8(t, s_, i),
            lambda t, s_, i: ek.dequant_int8(
                jnp.take(t, jnp.maximum(i, 0), axis=0), s_, jnp.float32)
            * (i >= 0).astype(jnp.float32)[..., None],
            (qtable, scale, jnp.clip(ids, -2, vocab - 1)), (0.0,))

    rows = jax.random.randint(jax.random.fold_in(key, 204), (n,), 0,
                              cfg["scatter_rows"] + 2)
    compare("scatter_rows", (n, dim), "float32",
            lambda g_, r: ek.scatter_rows(g_, r, cfg["scatter_rows"]),
            lambda g_, r: jnp.zeros((cfg["scatter_rows"], dim),
                                    jnp.float32).at[r].add(g_, mode="drop"),
            (grads, rows), (1e-5,))

    # the paged decode step through its entry point: on the TPU the kernel
    # reads each slot's live pages in place, the reference is the XLA form
    # (write, gather every column, masked_context), and the pools that
    # come back are the same bit for bit
    from analytics_zoo_tpu.ops import decode
    from analytics_zoo_tpu.ops.attention import masked_context
    pg = cfg["paged"]
    slots, heads, dim = pg["slots"], pg["heads"], pg["dim"]
    page_len, width = pg["page_len"], pg["width"]
    max_len = page_len * width
    rs = np.random.RandomState(SEED)
    lengths = np.where(np.arange(slots) % 3 == 0, 0,
                       rs.randint(0, max_len, slots))
    lengths[-1] = max_len - 1
    table = np.zeros((slots, width), np.int32)
    free = rs.permutation(np.arange(1, pg["pages"]))
    for i in np.flatnonzero(lengths):
        need = lengths[i] // page_len + 1
        table[i, :need], free = free[:need], free[need:]
    pools = [jax.random.normal(jax.random.fold_in(key, 300 + i),
                               (pg["pages"], page_len, heads * dim),
                               jnp.float32) * 0.5 for i in range(2)]
    step_in = qkv((slots, heads, 1, dim), jnp.float32)

    def paged_step(q, k_new, v_new, k_pool, v_pool, table, lengths):
        ctx, cache = decode.paged_attention(
            q, k_new, v_new, {"k": k_pool, "v": v_pool}, table, lengths,
            max_len)
        return ctx, cache["k"], cache["v"]

    def paged_xla_form(q, k_new, v_new, k_pool, v_pool, table, lengths):
        at = decode._page_positions(table, lengths[:, None], page_len)
        cache = decode._paged_write(
            {"k": k_pool, "v": v_pool}, *at, k_new.transpose(0, 2, 1, 3),
            v_new.transpose(0, 2, 1, 3), inline_amax=False)
        k_buf, v_buf = decode.paged_gather(cache, table, heads)
        visible = jnp.arange(max_len)[None, None, :] <= lengths[:, None, None]
        with jax.default_matmul_precision("highest"):
            ctx = masked_context(q, k_buf, v_buf, visible[:, None],
                                 dim ** -0.5)
        return ctx, cache["k"], cache["v"]

    compare("paged_attention decode step (pages read in place)",
            (slots, heads, max_len, dim), "float32", paged_step,
            paged_xla_form,
            (*step_in, *pools, jnp.asarray(table),
             jnp.asarray(lengths, jnp.int32)), (2e-2, 0.0, 0.0))

    emit("kernels", checks=checks, peak_bytes_in_use=_peak_bytes())
    bad = [c["kernel"] for c in checks if not c["ok"]]
    require(not bad,
            f"kernel checks failed: {bad}")
    return checks


# -- phase: train -------------------------------------------------------------

def _bert_dataset(cfg, workdir):
    """Tokens and labels from SEED, stored as a TFRecord file the way a
    user's dataset would be, and read back through the repo's reader."""
    from analytics_zoo_tpu.feature.tfrecord import (
        TFRecordWriter, encode_example, open_tfrecord, read_examples)
    rs = np.random.RandomState(SEED)
    n, seq = cfg["batch"] * cfg["steps"], cfg["seq"]
    tokens = rs.randint(1, cfg["bert"]["vocab"], (n, seq))
    lengths = rs.randint(seq // 2, seq + 1, n)  # ragged: real padding masks
    tokens[np.arange(seq)[None, :] >= lengths[:, None]] = 0
    labels = (tokens[:, 1] % 2).astype(np.int64)
    path = os.path.join(workdir, "train.tfrecord")
    with TFRecordWriter(path) as w:
        for row, label in zip(tokens, labels):
            w.write(encode_example({"tokens": row.astype(np.int64),
                                    "label": np.asarray([label])}))
    reader = open_tfrecord(path)
    kind = type(reader).__name__.strip("_")
    reader.close()
    examples = list(read_examples(path))
    got_tokens = np.stack([np.asarray(e["tokens"]) for e in examples])
    got_labels = np.asarray([int(np.asarray(e["label"])[0])
                             for e in examples])
    require(np.array_equal(got_tokens, tokens)
            and np.array_equal(got_labels, labels),
            "the TFRecord file did not read back what was written")
    return got_tokens, got_labels.astype(np.float32), kind


def _train_step_has_kernel(clf, tokens, labels, batch):
    """Whether the estimator's compiled train step holds a pallas kernel."""
    import jax
    from analytics_zoo_tpu.capture.text import bert_input_pack
    from analytics_zoo_tpu.ops import dispatch
    from analytics_zoo_tpu.parallel.mesh import shard_batch
    est = clf.model.get_estimator()
    x, y = shard_batch(est.mesh, (bert_input_pack(tokens[:batch]),
                                  labels[:batch]))
    with dispatch.partitioned_over(est.mesh):
        text = est._train_step.lower(
            est.params, est.opt_state, est.model_state,
            jax.random.fold_in(est.root_rng, 0), x, y).as_text()
    return "tpu_custom_call" in text


def phase_train(cfg, workdir, expect_pallas=True):
    """BERT fine-tune through ``BERTClassifier``: fit, evaluate,
    checkpoint, and a fresh model that resumes from the checkpoint and
    continues the straight run's loss history."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.capture.text import BERTClassifier
    from analytics_zoo_tpu.keras.optimizers import AdamWeightDecay
    tokens, labels, reader = _bert_dataset(cfg, workdir)
    bert = dict(cfg["bert"], compute_dtype=jnp.bfloat16)
    batch, steps = cfg["batch"], cfg["steps"]
    ckpt = os.path.join(workdir, "ckpt")

    def classifier():  # the reference's BERT optimizer, fine-tune rate
        return BERTClassifier(2, bert_config=bert,
                              optimizer=AdamWeightDecay(cfg["lr"]))

    clf = classifier()
    # one snapshot, at the end of epoch 1 (a trigger is any callable on the
    # training state)
    clf.model.set_checkpoint(ckpt, lambda state: state.iteration == steps)
    t0 = time.perf_counter()
    straight = clf.fit(tokens, labels, batch_size=batch, epochs=2)
    t1 = time.perf_counter()
    scores = clf.evaluate(tokens, labels, batch_size=batch)
    t2 = time.perf_counter()

    # a fresh model resumes from the epoch-1 snapshot and must land where
    # the uninterrupted run landed
    resumed_clf = classifier()
    resumed_clf.model.load_weights(os.path.join(ckpt, f"snapshot-{steps}"))
    est = resumed_clf.model.get_estimator()
    require((est.epoch, est.global_step) == (2, steps),
            f"snapshot restored to epoch {est.epoch}, step "
            f"{est.global_step}")
    resumed = resumed_clf.fit(tokens, labels, batch_size=batch, epochs=2)
    t3 = time.perf_counter()
    clf.fit(tokens, labels, batch_size=batch, epochs=3)  # compiled: run only
    t4 = time.perf_counter()

    kernel = _train_step_has_kernel(clf, tokens, labels, batch)
    history = [round(float(v), 6) for v in straight["loss_history"]]
    losses = {"epoch_1": history[:steps], "epoch_2_straight": history[steps:],
              "epoch_2_resumed": [round(float(v), 6)
                                  for v in resumed["loss_history"]]}
    gap = float(np.max(np.abs(np.asarray(losses["epoch_2_straight"])
                              - np.asarray(losses["epoch_2_resumed"]))))
    scores = {k: float(v) for k, v in scores.items()}
    emit("train", model="BERT-base classifier", shapes={
        "batch": batch, "seq": cfg["seq"], "steps_per_epoch": steps,
        **{k: cfg["bert"][k] for k in ("hidden_size", "n_block", "n_head",
                                       "vocab")}},
         dropout={"hidden": 0.1, "attention": 0.1, "classifier": 0.1},
         seconds={"fit_2_epochs_with_compile": round(t1 - t0, 2),
                  "evaluate_with_compile": round(t2 - t1, 2),
                  "load_snapshot_and_epoch_2": round(t3 - t2, 2),
                  "fit_epoch_3_run_only": round(t4 - t3, 2),
                  "compile_estimate": round((t1 - t0) - 2 * (t4 - t3), 2)},
         losses=losses, resume_max_abs_diff=gap, evaluate=scores,
         tfrecord_reader=reader,
         attention_branch=("fused_short (pallas)" if kernel
                           else "dot_product (XLA)"),
         iterations={"straight": straight["iterations"],
                     "resumed": resumed["iterations"]},
         peak_bytes_in_use=_peak_bytes())
    for hist in losses.values():
        require(len(hist) == steps and np.isfinite(hist).all(),
                f"loss history not finite or not {steps} long: {losses}")
    require(np.isfinite(list(scores.values())).all(),
            f"evaluate not finite: {scores}")
    require(straight["iterations"] == resumed["iterations"] == 2 * steps,
            "the resumed run did not end on the straight run's step")
    require(gap <= 1e-4,
            f"resumed history left the straight run: {gap}")
    require(kernel or not expect_pallas,
            "train step holds no pallas kernel")
    return losses


# -- phase: serve -------------------------------------------------------------

def _successor_language(vocab, alphabet, rs):
    """``walk(start, n)``: n tokens of a language in which every token has
    one successor — a permutation of ``alphabet`` tokens drawn from the
    whole vocabulary. A few dozen steps teach it to the model, so greedy
    decoding has a margin: a model fresh from its initialiser is
    near-uniform (loss ~ ln vocab), and its arg-max ties flip on rounding
    that differs between two compiled programs."""
    letters = rs.choice(vocab, alphabet, replace=False)
    successor = rs.permutation(alphabet)

    def walk(start, n):
        out, cur = np.empty(n, np.int64), start
        for i in range(n):
            out[i], cur = cur, successor[cur]
        return letters[out]
    return walk


def phase_serve(cfg, workdir, expect_pallas=True):
    """GenerativeServing over a TransformerLM: requests through the file
    queue and the client SDK, several in flight, each answered exactly
    once and token-identical to serial ``generate()``."""
    from analytics_zoo_tpu.capture.lm import TransformerLM, prefill_bucket
    from analytics_zoo_tpu.keras.optimizers import Adam
    from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue

    rs = np.random.RandomState(SEED)
    walk = _successor_language(cfg["lm"]["vocab_size"], cfg["alphabet"], rs)
    rows = cfg["fit_steps"] * cfg["fit_batch"]
    lm = TransformerLM(seed=SEED, optimizer=Adam(cfg["lr"]), **cfg["lm"])
    t0 = time.perf_counter()
    fit = lm.fit(np.stack([walk(rs.randint(cfg["alphabet"]),
                                cfg["fit_seq"] + 1) for _ in range(rows)]),
                 batch_size=cfg["fit_batch"], epochs=1)
    t1 = time.perf_counter()
    fit_losses = [round(float(v), 4) for v in fit["loss_history"]]
    require(np.isfinite(fit_losses).all(), f"LM fit not finite: {fit_losses}")

    max_new, slots = cfg["max_new"], cfg["slots"]
    starts = rs.randint(cfg["alphabet"], size=len(cfg["prompt_lens"]))
    prompts = [walk(st, n).tolist()
               for st, n in zip(starts, cfg["prompt_lens"])]
    language = [walk(st, n + max_new)[n:].tolist()
                for st, n in zip(starts, cfg["prompt_lens"])]
    buckets = sorted({prefill_bucket(len(p) - 1, lm.max_len)
                      for p in prompts})
    serial = [lm.generate(np.asarray([p]), max_new_tokens=max_new
                          )[0].tolist() for p in prompts]
    t2 = time.perf_counter()

    page_len = 16
    per_stream = -(-max(max(buckets), max(cfg["prompt_lens"]) + max_new)
                   // page_len)
    kv_pages = slots * per_stream + 1
    src = "dir://" + os.path.join(workdir, "queue")
    srv = GenerativeServing(
        ServingConfig(data_src=src, slots=slots, max_new_tokens=max_new,
                      kv_pages=kv_pages, kv_page_len=page_len), lm)
    inq, outq = InputQueue(src), OutputQueue(src)
    uris = [f"req-{i}" for i in range(len(prompts))]
    for uri, prompt in zip(uris, prompts):
        inq.enqueue_prompt(uri, prompt)
    srv.start()
    served, most_in_flight = {}, 0
    deadline = time.monotonic() + 900
    try:
        while len(served) < len(uris):
            srv.check_health()
            require(time.monotonic() < deadline, "serving timed out")
            most_in_flight = max(most_in_flight,
                                 srv.health_snapshot()["slots_occupied"])
            for uri in uris:
                res = outq.query(uri) if uri not in served else None
                if res is not None and res.get("done", "error" in res):
                    served[uri] = res
            time.sleep(0.002)
        srv.drain(timeout_s=60)
        snap = srv.health_snapshot()
    finally:
        srv.stop()
    t3 = time.perf_counter()

    kernel = _prefill_has_kernel(lm, buckets[0])
    got = [served[uri].get("value") for uri in uris]
    learned = sum(g == want for g, want in zip(serial, language))
    emit("serve", model="TransformerLM (GPT-2 small)", shapes={
        "slots": slots, "kv_pages": kv_pages, "kv_page_len": page_len,
        "prompt_lens": list(cfg["prompt_lens"]),
        "prefill_buckets": buckets, "max_new_tokens": max_new,
        **{k: cfg["lm"][k] for k in ("hidden", "n_block", "n_head",
                                     "vocab_size", "max_len")}},
         seconds={"lm_fit_with_compile": round(t1 - t0, 2),
                  "serial_generate_with_compile": round(t2 - t1, 2),
                  "serve_with_compile": round(t3 - t2, 2)},
         lm_fit={"steps": len(fit_losses), "first_loss": fit_losses[0],
                 "last_loss": fit_losses[-1]},
         tokens_serial=serial, tokens_served=got,
         serial_streams_following_the_language=learned,
         most_in_flight=most_in_flight,
         terminals=snap["latency_ms"]["window"], counters=snap["counters"],
         pages_free_after=snap["kv_pages_free"],
         prefill_attention_branch=("fused_short (pallas)" if kernel
                                   else "flash/blockwise (XLA)"),
         peak_bytes_in_use=_peak_bytes())
    require(got == serial, "served tokens differ from serial generate()")
    require(all(len(t) == max_new for t in got),
            f"a stream is not {max_new} tokens long")
    require(learned == len(prompts) or not cfg["must_learn"],
            "the LM did not learn the successor language, so the token "
            "comparison has no margin")
    require(most_in_flight >= 2, "requests never overlapped")
    require(snap["latency_ms"]["window"] == len(uris),
            f"not one terminal per request: {snap['latency_ms']}")
    require(not any(snap["counters"][k]
                    for k in ("shed", "expired", "errors")),
            f"requests shed, expired or failed: {snap['counters']}")
    require(snap["in_flight"] == 0 and snap["slots_occupied"] == 0
            and snap["kv_pages_free"] == kv_pages - 1,
            "streams or KV pages still held after the drain")
    require(kernel or not expect_pallas, "prefill holds no pallas kernel")
    return got


# -- four chips: what exists only across chips --------------------------------

def phase_data_parallel(cfg, expect_pallas=True):
    """The same BERT ``Estimator`` steps on a data mesh over every device
    and on one device, from the same global batches. Dropout is off in the
    comparison (a kernel shard draws its own mask, so the two layouts see
    different noise); one more fit with dropout on shows that path runs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from analytics_zoo_tpu.capture.text import BERTClassifier, bert_input_pack
    from analytics_zoo_tpu.keras.optimizers import AdamWeightDecay
    from analytics_zoo_tpu.parallel.mesh import shard_batch

    devices = jax.devices()
    rs = np.random.RandomState(SEED)
    batch, steps, seq = cfg["batch"], cfg["steps"], cfg["seq"]
    tokens = rs.randint(1, cfg["bert"]["vocab"], (batch * steps, seq))
    tokens[:, seq - seq // 4:] = 0
    labels = (tokens[:, 1] % 2).astype(np.float32)
    quiet = dict(cfg["bert"], compute_dtype=jnp.bfloat16, hidden_p_drop=0.0,
                 attn_p_drop=0.0)

    def fit(mesh, bert, dropout):
        clf = BERTClassifier(2, bert_config=bert, dropout=dropout,
                             optimizer=AdamWeightDecay(cfg["lr"]))
        est = clf.model.get_estimator()
        est.mesh = mesh
        t0 = time.perf_counter()
        out = clf.fit(tokens, labels, batch_size=batch, epochs=1)
        return (clf, est, [float(v) for v in out["loss_history"]],
                round(time.perf_counter() - t0, 2))

    all_mesh = Mesh(np.asarray(devices), ("data",))
    clf_n, est_n, hist_n, s_n = fit(all_mesh, quiet, 0.0)
    _, est_1, hist_1, s_1 = fit(Mesh(np.asarray(devices[:1]), ("data",)),
                                quiet, 0.0)
    _, _, hist_drop, s_drop = fit(
        all_mesh, dict(cfg["bert"], compute_dtype=jnp.bfloat16), 0.1)

    x, y = shard_batch(est_n.mesh, (bert_input_pack(tokens[:batch]),
                                    labels[:batch]))
    placed = {"params": _distinct_devices(est_n.params),
              "opt_state": _distinct_devices(est_n.opt_state),
              "batch": _distinct_devices((x, y)),
              "batch_shard_rows": x[0].addressable_shards[0].data.shape[0],
              "one_device_params": _distinct_devices(est_1.params)}
    kernel = _train_step_has_kernel(clf_n, tokens, labels, batch)
    gap = float(np.max(np.abs(np.asarray(hist_n) - np.asarray(hist_1))))
    emit("data_parallel", model="BERT-base classifier", shapes={
        "devices": len(devices), "global_batch": batch, "seq": seq,
        "steps": steps}, seconds_with_compile={
        "all_devices": s_n, "one_device": s_1, "dropout_on": s_drop},
         loss_all_devices=hist_n, loss_one_device=hist_1,
         max_abs_diff=gap, tolerance=DP_TOLERANCE,
         loss_dropout_on=hist_drop,
         distinct_devices=placed,
         attention_branch=("fused_short per shard (pallas)" if kernel
                           else "dot_product (XLA)"),
         peak_bytes_in_use=_peak_bytes())
    require(np.isfinite(hist_n + hist_1 + hist_drop).all(),
            "a loss is not finite")
    require(gap <= DP_TOLERANCE,
            f"data-parallel loss left the one-device run: {gap}")
    n = len(devices)
    require(placed["params"] == placed["opt_state"] == placed["batch"] == n
            and placed["batch_shard_rows"] == batch // n
            and placed["one_device_params"] == 1,
            f"state or batch not laid out over {n} devices: {placed}")
    require(kernel or not expect_pallas,
            "data-parallel train step holds no pallas kernel")
    return hist_n


def phase_tensor_parallel(cfg, expect_pallas=True):
    """``TransformerLM(tensor_parallel=True)`` over every device against
    the replicated model: loss history and greedy tokens."""
    import jax
    from jax.sharding import Mesh
    from analytics_zoo_tpu.capture.lm import TransformerLM
    from analytics_zoo_tpu.keras.optimizers import Adam

    devices = jax.devices()
    rs = np.random.RandomState(SEED)
    walk = _successor_language(cfg["lm"]["vocab_size"], cfg["alphabet"], rs)
    tokens = np.stack([walk(rs.randint(cfg["alphabet"]), cfg["seq"] + 1)
                       for _ in range(cfg["fit_steps"] * cfg["batch"])])
    start = rs.randint(cfg["alphabet"])
    prompt = walk(start, cfg["prompt_len"])[None]
    language = walk(start, cfg["prompt_len"] + cfg["max_new"]
                    )[cfg["prompt_len"]:].tolist()

    def fit(**kw):
        lm = TransformerLM(seed=SEED, optimizer=Adam(cfg["lr"]),
                           **cfg["lm"], **kw)
        t0 = time.perf_counter()
        out = lm.fit(tokens, batch_size=cfg["batch"], epochs=1)
        t1 = time.perf_counter()
        toks = lm.generate(prompt, max_new_tokens=cfg["max_new"])[0].tolist()
        return (lm, [float(v) for v in out["loss_history"]], toks,
                round(t1 - t0, 2), round(time.perf_counter() - t1, 2))

    lm_tp, hist_tp, toks_tp, fit_tp, gen_tp = fit(
        mesh=Mesh(np.asarray(devices), ("model",)), tensor_parallel=True)
    _, hist_rep, toks_rep, fit_rep, gen_rep = fit()

    est = lm_tp._graph.estimator
    qkv = lm_tp.params["blocks"][0]["qkv"]["kernel"]
    placed = {"params": _distinct_devices(est.params),
              "opt_state": _distinct_devices(est.opt_state),
              "qkv_kernel_spec": [str(p) for p in qkv.sharding.spec],
              "qkv_kernel_shard": list(qkv.addressable_shards[0].data.shape),
              "qkv_kernel_whole": list(qkv.shape)}
    with_kernel = _prefill_has_kernel(lm_tp, 32)
    k = cfg["compare_steps"]
    rel = np.abs(np.asarray(hist_tp) - np.asarray(hist_rep)) \
        / np.abs(np.asarray(hist_rep))
    emit("tensor_parallel", model="TransformerLM (GPT-2 small)", shapes={
        "devices": len(devices), "batch": cfg["batch"], "seq": cfg["seq"],
        "steps": len(hist_tp), "prompt_len": cfg["prompt_len"],
        "max_new_tokens": cfg["max_new"]}, seconds_with_compile={
        "fit_tensor_parallel": fit_tp, "generate_tensor_parallel": gen_tp,
        "fit_replicated": fit_rep, "generate_replicated": gen_rep},
         loss_tensor_parallel=[round(v, 5) for v in hist_tp[:k]],
         loss_replicated=[round(v, 5) for v in hist_rep[:k]],
         last_loss={"tensor_parallel": round(hist_tp[-1], 4),
                    "replicated": round(hist_rep[-1], 4)},
         max_rel_diff_first_steps=float(rel[:k].max()), tolerance=1e-3,
         max_rel_diff_all_steps=float(rel.max()),
         tokens_tensor_parallel=toks_tp, tokens_replicated=toks_rep,
         tokens_of_the_language=language,
         distinct_devices=placed,
         prefill_attention_branch=("fused_short per shard (pallas)"
                                   if with_kernel else "flash/blockwise (XLA)"),
         peak_bytes_in_use=_peak_bytes())
    require(np.isfinite(hist_tp + hist_rep).all(), "a loss is not finite")
    require(rel[:k].max() <= 1e-3,
            f"tensor-parallel loss left the replicated run: {rel[:k].max()}")
    require(toks_tp == toks_rep, "greedy tokens differ")
    require(toks_rep == language or not cfg["must_learn"],
            "the LMs did not learn the successor language, so the token "
            "comparison has no margin")
    n = len(devices)
    require(placed["params"] == placed["opt_state"] == n,
            f"tensor-parallel state is not on {n} devices: {placed}")
    require(placed["qkv_kernel_shard"][1] * n
            == placed["qkv_kernel_whole"][1],
            f"the qkv kernel is not split {n} ways: {placed}")
    require(with_kernel or not expect_pallas,
            "tensor-parallel prefill holds no pallas kernel")
    return hist_tp


# -- the run ------------------------------------------------------------------

def _cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the two cross-chip comparisons")
    chips = ap.parse_args(argv).chips

    import jax
    dev = jax.devices()[0]
    _device.update(platform=dev.platform, kind=dev.device_kind,
                   count=len(jax.devices()))
    ok = False
    workdir = tempfile.mkdtemp(prefix="zoo_chip_smoke_")
    try:
        require(dev.platform == "tpu",
                f"chip_smoke.py needs a TPU; JAX found {dev.platform!r}")
        require(_device["count"] == chips,
                f"asked for {chips} chip(s), JAX sees {_device['count']}")

        logging.basicConfig(stream=sys.stderr, level=logging.INFO,
                            format="%(name)s %(levelname)s %(message)s")
        from analytics_zoo_tpu.common.config import global_config
        from analytics_zoo_tpu.common.context import (init_tpu_context,
                                                      wire_compilation_cache)
        from analytics_zoo_tpu.ops import dispatch
        # a compiler refusal must surface at once, not after five retries
        global_config().set("failure.retry_times", 0)
        # from here on the program counts JAX's compiles itself
        # (compile.cache_hits_total / compile.cache_misses_total)
        cache_dir = wire_compilation_cache()
        entries_before = _cache_entries(cache_dir)
        init_tpu_context()
        emit("setup", jax=jax.__version__, compile_cache_dir=cache_dir,
             cache_placed_by=("JAX_COMPILATION_CACHE_DIR"
                              if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                              else "in-tree default"),
             cache_entries_before=entries_before, retry_times=0)

        if chips == 1:
            phase_kernels(KERNELS)
            phase_train(TRAIN, workdir)
            phase_serve(SERVE, workdir)
        else:
            phase_data_parallel(DP)
            phase_tensor_parallel(TP)

        from analytics_zoo_tpu.common.metrics import metrics_snapshot
        counted = metrics_snapshot()
        emit("teardown", cache_entries_before=entries_before,
             cache_entries_after=_cache_entries(cache_dir),
             cache_hits=int(counted["compile.cache_hits_total"]["value"]),
             cache_misses=int(
                 counted["compile.cache_misses_total"]["value"]),
             reference_paths_taken_on_tpu=[
                 {"kernel": k, "rule": r}
                 for k, r in dispatch.fallbacks_seen()])
        ok = True
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        print(json.dumps({"ok": ok, "device": _device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
