"""Every pallas kernel of the main path, compiled ahead of time for a TPU
v5e that is described and not attached.

Interpret mode and CPU parity tests cannot see what the chip's compiler
refuses (a DMA slice off the HBM tiling, a scalar-prefetch operand padded
past scalar memory); these compiles can, at the widths ``chip_smoke.py``
runs, and cost no chip time. Nothing here RUNS: results are checked on the
chip by ``chip_smoke.py``. Code that asks ``jax.default_backend()`` still
sees the CPU in this process, so the ``_*_pallas`` / ``_*_call`` functions
are compiled directly. Skipped where the installation cannot describe the
topology.

The last test drives ``chip_smoke.py``'s phase functions on the CPU at tiny
sizes passed as arguments (the script itself has no size or device option).
"""
import importlib.util
import os
import pathlib

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from analytics_zoo_tpu.ops import attention as A  # noqa: E402
from analytics_zoo_tpu.ops import embedding_kernels as ek  # noqa: E402

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def v5e():
    """``spec(shape, dtype)`` for one described v5e chip, with JAX's
    persistent compilation cache off around the module: such compiles are
    written to it but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e!r}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    def spec(shape, dtype, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    spec.devices = topo.devices  # the 2x2 host, for the four-chip cases
    yield spec
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *specs):
    """Raises what the chip's compiler would raise; the kernel must be in
    the program that comes out."""
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# -- streaming flash attention ------------------------------------------------

@pytest.mark.parametrize("shape,dtype", [
    ((4, 12, 2048, 64), BF16),   # head dim 64, fused single-pass backward
    ((2, 8, 4096, 128), BF16),   # head dim 128, fused single-pass backward
    ((1, 4, 8192, 128), BF16),   # K/V past VMEM: two-pass backward
    ((8, 12, 128, 64), F32),     # TransformerLM.fit at GPT-2-small width
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else v.__name__)
def test_flash_fwd_bwd(v5e, shape, dtype):
    scale = shape[-1] ** -0.5
    blocks = (A.DEFAULT_Q_BLOCK, A.DEFAULT_KV_BLOCK)
    x = v5e(shape, dtype)
    lse = v5e((shape[0] * shape[1], shape[2]), F32)

    _compile(lambda q, k, v: A._flash_fwd_pallas(
        q, k, v, scale, True, *blocks, return_lse=True), x, x, x)
    _compile(lambda q, k, v, o, l, g: A._flash_bwd_pallas(
        q, k, v, o, l, g, scale, True, *blocks), x, x, x, x, lse, x)


def test_flash_fwd_key_bias(v5e):
    """The padding-mask form (forward kernel only; its backward is the
    stated blockwise rule)."""
    x = v5e((4, 12, 1024, 64), BF16)
    _compile(lambda q, k, v, b: A._flash_fwd_pallas(
        q, k, v, 0.125, False, A.DEFAULT_Q_BLOCK, A.DEFAULT_KV_BLOCK,
        key_bias=b), x, x, x, v5e((4, 1024), BF16))


# -- fused short-sequence attention -------------------------------------------

@pytest.mark.parametrize("shape,dtype,bias,rate,causal,bwd", [
    # BERT-base fine-tune: padding mask + in-kernel dropout, both directions
    ((32, 12, 128, 64), BF16, True, 0.1, False, True),
    ((8, 12, 512, 64), BF16, False, 0.0, True, True),
    # TransformerLM prefill (float32 params), one per end of the bucket
    # range of capture/lm.py PREFILL_BUCKETS
    ((1, 12, 16, 64), F32, False, 0.0, True, False),
    ((1, 12, 32, 64), F32, False, 0.0, True, False),
    ((1, 12, 128, 64), F32, False, 0.0, True, False),
    ((1, 12, 512, 64), F32, False, 0.0, True, False),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None)
def test_fused_short(v5e, shape, dtype, bias, rate, causal, bwd):
    x = v5e(shape, dtype)
    kb = v5e((shape[0], shape[2]), dtype)
    seed = v5e((), I32)
    scale = shape[-1] ** -0.5

    def call(q, k, v, b, s, do=None):
        return A._fused_short_call(q, k, v, b if bias else None, scale,
                                   rate, s, causal=causal, fwd=do is None,
                                   do=do)

    _compile(call, x, x, x, kb, seed)
    if bwd:
        _compile(call, x, x, x, kb, seed, x)


# -- four chips: kernels inside a partitioned program -------------------------

@pytest.mark.parametrize("axis", ["data", "model"])
def test_kernels_run_per_shard_on_four_chips(v5e, monkeypatch, axis):
    """JAX refuses a Mosaic kernel in a program partitioned automatically
    over several devices; under the owner's ``partitioned_over`` scope the
    public entry points wrap it per shard (batch over ``data``, heads over
    the tensor axis) and the four-chip program compiles with the kernel in
    it. The backend question is steered here, in the test."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(v5e.devices), (axis,))
    split = P(axis) if axis == "data" else P(None, axis)
    x = v5e((8, 12, 128, 64), BF16, NamedSharding(mesh, split))

    def step(q, k, v):
        def loss(q, k, v):
            short = A.fused_short_attention(q, k, v, causal=True)
            return jnp.sum((short + A.flash_attention(q, k, v, causal=True)
                            ).astype(F32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    with pytest.raises(NotImplementedError, match="shard_map"):
        # no scope: refused, loudly (a function of its own, since jit
        # would hand the scoped call below this one's trace)
        jax.jit(lambda q, k, v: step(q, k, v)).lower(x, x, x)
    with dispatch.partitioned_over(mesh):
        assert A.fused_short_applicable(x, x)
        text = jax.jit(step).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 4  # two kernels, both directions


# -- names on the device (docs/observability.md "Scope names on the device") ----

def _mosaic_calls(text):
    """``(instruction name, op_name)`` of each Mosaic call of a compiled
    program."""
    import re
    found = []
    for line in text.splitlines():
        if "custom-call(" in line and "tpu_custom_call" in line:
            name = re.match(r"\s*(?:ROOT )?%(\S+) = ", line).group(1)
            op_name = re.search(r'op_name="([^"]*)"', line)
            found.append((name, op_name.group(1) if op_name else ""))
    return found


def _attention_layer_step(dtype):
    """``value_and_grad`` through the attention layer's fused-short call,
    with an update under the ``optimizer`` scope, as the train step has it."""
    from analytics_zoo_tpu.keras.layers.attention import MultiHeadAttention
    layer = MultiHeadAttention(12, 768)
    params, _ = layer.build(jax.random.PRNGKey(0), (None, 128, 768))
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)

    def step(p, x, mask):
        loss, grads = jax.value_and_grad(lambda p: jnp.sum(
            layer.attend(p, x, x, mask).astype(F32)))(p)
        with jax.named_scope("optimizer"):
            return loss, jax.tree_util.tree_map(
                lambda a, g: a - 0.1 * g.astype(a.dtype), p, grads)
    return params, step


def test_scopes_name_the_short_kernel_forward_and_backward(v5e, monkeypatch):
    """One chip: XLA names a Mosaic call after the innermost scope, and JAX
    wraps ``jvp`` / ``transpose`` around it, so the benchmark's reader still
    tells the backward call from the forward one by its name; the
    projections carry ``attention``, the update ``optimizer``."""
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    params, step = _attention_layer_step(BF16)
    p = jax.tree_util.tree_map(lambda a: v5e(a.shape, a.dtype), params)
    text = jax.jit(step).lower(
        p, v5e((8, 128, 768), BF16), v5e((8, 128), F32)).compile().as_text()
    calls = dict(_mosaic_calls(text))
    assert len(calls) == 2, calls
    forward = [n for n in calls if n.startswith("jvp_attn_short")]
    backward = [n for n in calls if n.startswith("transpose_jvp_attn_short")]
    assert len(forward) == 1 and len(backward) == 1, calls
    assert calls[forward[0]].endswith("/jvp(attn_short)/pallas_call")
    assert calls[backward[0]].endswith(
        "/transpose(jvp(attn_short))/pallas_call")
    assert "/jvp(attention)/dot_general" in text
    assert "/transpose(jvp(attention))/dot_general" in text
    assert "/optimizer/" in text


def test_scopes_name_the_kernels_in_the_estimators_own_step(v5e, monkeypatch):
    """The step that ``Estimator`` builds for a BERT classifier, not one
    made here: a scope put around its ``value_and_grad``, or around the
    kernel's own by a model, would come between ``transpose`` and
    ``attn_short``, XLA would name the backward call like the forward one,
    and the benchmark's ``attn_short_roofline.train`` would read nothing."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from analytics_zoo_tpu.capture.text import (BERTClassifier,
                                                bert_input_pack)
    from analytics_zoo_tpu.keras.engine import init_model
    from analytics_zoo_tpu.keras.optimizers import AdamWeightDecay
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    clf = BERTClassifier(
        2, dropout=0.0, optimizer=AdamWeightDecay(5e-5),
        bert_config=dict(n_block=1, hidden_p_drop=0.0, attn_p_drop=0.0,
                         compute_dtype=BF16))
    est = clf.model.get_estimator()
    est.mesh = Mesh(np.asarray(v5e.devices[:1]), ("data",))
    whole = NamedSharding(est.mesh, P())

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: v5e(a.shape, a.dtype, whole), tree)

    x = bert_input_pack(np.ones((8, 128), np.int32))
    params, state = jax.eval_shape(
        lambda r: init_model(clf.model, r, x), jax.random.PRNGKey(0))
    text = est._build_train_step().lower(
        described(params), described(jax.eval_shape(est._init_opt_state,
                                                    params)),
        described(state), v5e((2,), jnp.uint32, whole),
        [v5e(a.shape, a.dtype, whole) for a in x],
        v5e((8,), F32, whole)).compile().as_text()
    calls = dict(_mosaic_calls(text))
    assert len(calls) == 2, calls
    assert sorted(n.split("attn_short")[0] for n in calls) == \
        ["jvp_", "transpose_jvp_"], calls
    for scope in ("jvp(embeddings)", "transpose(jvp(attention))",
                  "transpose(jvp(ffn))", "jvp(layer_norm)",
                  "jvp(classifier)", "jvp(loss)", "optimizer"):
        assert "/" + scope + "/" in text, scope


def test_scopes_name_the_short_kernel_per_shard_on_four_chips(
        v5e, monkeypatch):
    """Under the four-chip data mesh of
    ``test_kernels_run_per_shard_on_four_chips`` the scope sits inside the
    per-shard body, so both calls are ``attn_short`` (without it they are
    ``shard_map``); the direction is in the ``op_name``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(v5e.devices), ("data",))
    whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    params, step = _attention_layer_step(BF16)
    p = jax.tree_util.tree_map(lambda a: v5e(a.shape, a.dtype, whole), params)
    with dispatch.partitioned_over(mesh):
        text = jax.jit(step).lower(
            p, v5e((8, 128, 768), BF16, rows),
            v5e((8, 128), F32, rows)).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 2, calls
    assert all(name.startswith("attn_short") for name, _ in calls), calls
    assert all(op.endswith("/shard_map/attn_short/pallas_call")
               for _, op in calls), calls
    assert sorted("transpose(" in op for _, op in calls) == [False, True]


# -- embedding kernels --------------------------------------------------------

_TABLE = (2 ** 20, 128)


@pytest.mark.parametrize("kernel", ["gather", "gather_pool", "gather_int8",
                                    "scatter"])
def test_embedding_kernel(v5e, kernel):
    if kernel == "gather":
        assert ek._table_rule(v5e(_TABLE, F32), 8192) is None
        _compile(lambda t, i: ek._gather_call(t, i, clip=False),
                 v5e(_TABLE, F32), v5e((8192,), I32))
    elif kernel == "gather_pool":
        assert ek._table_rule(v5e(_TABLE, F32), 8192 * 4, bag=4) is None
        _compile(lambda t, i: ek._gather_pool_call(t, i, "mean"),
                 v5e(_TABLE, F32), v5e((8192, 4), I32))
    elif kernel == "gather_int8":
        assert ek._table_rule(v5e(_TABLE, I8), 8192) is None
        _compile(ek._gather_int8_call, v5e(_TABLE, I8), v5e((), F32),
                 v5e((8192,), I32))
    else:
        assert ek._scatter_rule(v5e((8192, 128), F32), 8192, 4096) is None
        _compile(lambda g, r: ek._scatter_call(g, r, 4096),
                 v5e((8192, 128), F32), v5e((8192,), I32))


@pytest.mark.parametrize("table,n_ids,bag,why", [
    ((1000, 64), 256, 1, "dim 64 is not 128"),          # NCF / Wide&Deep
    ((1000, 256), 256, 1, "dim 256 is not 128"),        # row spans two tiles
    ((1000, 128), 514, 1, "no divisor"),                # 2 x 257 rows
    ((1000, 128), 2 ** 18, 1, "scalar prefetch budget"),
])
def test_embedding_rule_matches_compiler(v5e, table, n_ids, bag, why):
    """Each shape ``_table_rule`` turns away is one the compiler refuses
    (or that overflows scalar memory), so the rule is not merely cautious."""
    t, ids = v5e(table, F32), v5e((n_ids,), I32)
    assert why in ek._table_rule(t, n_ids, bag)
    with pytest.raises(Exception):
        jax.jit(lambda t, i: ek._gather_call(t, i, clip=True)).lower(
            t, ids).compile()


# -- the serving programs update the KV page pools in place --------------------

_POOL = (3073, 16, 12 * 64)   # gpt2_small's cell: pages, positions, H*D


def _gpt2_small_server(tmp_path, int8):
    """``GenerativeServing`` over GPT-2 small with described parameters and
    a two-page pool: its jitted programs are lowered below for pools of the
    benchmark's size, which are never allocated."""
    from analytics_zoo_tpu.capture.lm import TransformerLM
    from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
    lm = TransformerLM(vocab_size=50257, hidden=768, n_block=12, n_head=12,
                       max_len=1024)
    lm._graph.estimator.params = jax.eval_shape(
        lm._init_params, jax.random.PRNGKey(0), None)
    # one chip, as in the benchmark's cell (this process has 8 virtual CPU
    # devices, and kernels trace by the mesh under the parameters)
    lm._graph.estimator.mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]), ("data",))
    return GenerativeServing(ServingConfig(
        data_src=f"dir://{tmp_path}/q", slots=48, kv_pages=2,
        kv_page_len=16, kv_int8=int8), lm)


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("program", ["decode_step", "prefill", "page_copy"])
def test_serving_programs_keep_the_page_pools_in_place(
        v5e, monkeypatch, tmp_path, program, int8):
    """The programs as the server declares them (pools donated), compiled
    for the chip at the benchmark's pool shape: every pool leaf is aliased
    to an output, and no ``copy`` of a whole pool is left in the program.
    ``[P, H, page_len, D]`` pools cost two such copies a pool in every one
    of these programs, with or without donation (PERF.md, PR 26).

    The decode step over the float32 pool also holds the kernel that reads
    the live pages in place, one Mosaic call a block, and none of the
    dense read's buffers (all 64 pages of all 48 slots gathered, selected
    and laid out again; PERF.md, PR 28); over the int8 pool it is the XLA
    form, and says so once."""
    import re
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(dispatch, "_seen", set())
    srv = _gpt2_small_server(tmp_path, int8)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: v5e(a.shape, a.dtype), tree)

    params, state = described(srv._params), described(srv._state)
    pools = described(jax.eval_shape(
        lambda: srv.lm.init_paged_caches(_POOL[0], _POOL[1], int8=int8)))
    assert pools[0]["k"].shape == _POOL
    table, row, scalar = v5e((48, 64), I32), v5e((64,), I32), v5e((), I32)
    if program == "decode_step":
        # the host's tokens, the step before's (with its pages-read count)
        lowered = srv._step_fn.lower(params, v5e((48,), I32),
                                     v5e((49,), I32),
                                     v5e((48, 2), jnp.uint32), state, table,
                                     pools)
    elif program == "prefill":
        lowered = srv._prefill_paged_fn.lower(
            params, v5e((1, 256), I32), pools, state, table, row, scalar,
            scalar)
    else:
        lowered = srv._copy_fn.lower(pools, scalar, scalar)
    text = lowered.compile().as_text()
    entry = text[text.index("\nENTRY "):]
    leaves = {int(n) for n in re.findall(
        r"%caches_\S+ = \S+ parameter\((\d+)\)", entry)}
    assert len(leaves) == len(jax.tree_util.tree_leaves(pools))
    aliased = {int(n) for n in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    assert leaves <= aliased, sorted(leaves - aliased)
    dims = ",".join(map(str, _POOL))
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= \w+\[%s\]\S* copy\(" % dims, line)]
    assert not copies, copies
    if program != "decode_step":
        return
    seen = [rule for kernel, rule in dispatch.fallbacks_seen()
            if kernel == "paged_decode"]
    dense = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"\[48,1024,12,64\]|\[48,64,16,768\]", line)]
    if int8:
        assert len(seen) == 1 and "int8 pool" in seen[0], seen
        assert dense
    else:
        assert seen == []
        assert text.count("tpu_custom_call") >= 12
        assert not dense, dense[:4]


_SALA = dict(slots=12, pages=4705, page_len=64, width=392)


@pytest.mark.parametrize("program", ["decode_step", "chunk_2048",
                                     "chunk_512"])
def test_sala_programs_keep_pools_and_states_in_place(v5e, tmp_path,
                                                      monkeypatch, program):
    """MiniCPM-SALA's decode step and prefill chunk as the server declares
    them, compiled for the chip at the benchmark's sizes (16 layers at the
    published widths, 4,705 pages of 64, 12 slots): every cache leaf (K/V
    pages, compressed keys, lightning states) is aliased to an output, and
    no copy of a whole pool or of the states is left in the program; the
    program and its temporaries fit the chip beside the weights. A chunk
    reads its sparse layers with the chunk kernel: one Mosaic call under
    ``sparse_attend`` a sparse layer (4), and none of the XLA tiles'
    float32 fusions ``[T, 2, 16, ...]``."""
    import json
    import pathlib
    import re
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(dispatch, "_seen", set())
    from analytics_zoo_tpu.capture.decoder import DecoderSpec, LayeredDecoder
    from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "perfbench" / "configs"
                      / "minicpm_sala.json").read_text())
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, cfg["n_positions"]))
    lm.set_params(jax.eval_shape(lm.init_params))
    slots = _SALA["slots"]
    srv = GenerativeServing(ServingConfig(
        data_src=f"dir://{tmp_path}/q", slots=slots, kv_pages=2,
        kv_page_len=_SALA["page_len"]), lm)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: v5e(a.shape, a.dtype), tree)

    params, state = described(srv._params), described(srv._state)
    pools = described(jax.eval_shape(lambda: lm.init_paged_caches(
        _SALA["pages"], _SALA["page_len"], slots=slots)))
    table = v5e((slots, _SALA["width"]), I32)
    row, scalar = v5e((_SALA["width"],), I32), v5e((), I32)
    if program == "decode_step":
        lowered = srv._step_fn.lower(params, v5e((slots,), I32),
                                     v5e((slots,), I32),
                                     v5e((slots, 2), jnp.uint32), state,
                                     table, pools)
    else:
        width = int(program.split("_")[1])
        lowered = srv._prefill_chunk_fn.lower(
            params, v5e((1, width), I32), pools, state, table, row, scalar,
            scalar, scalar, scalar, v5e((), jnp.bool_))
    compiled = lowered.compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    leaves = {int(n) for n in re.findall(
        r"%caches_\S+ = \S+ parameter\((\d+)\)", entry)}
    assert len(leaves) == len(jax.tree_util.tree_leaves(pools)) == 24
    aliased = {int(n) for n in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    assert leaves <= aliased, sorted(leaves - aliased)
    for dims in ("4705,64,256", "4705,4,256", "12,32,128,128"):
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= \w+\[%s\]\S* copy\(" % dims, line)]
        assert not copies, copies
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    assert held < 14.5e9, held   # of the chip's 16 GB
    assert dispatch.fallbacks_seen() == []
    attend = [name for name, op_name in _mosaic_calls(text)
              if "sparse_attend" in op_name]
    assert len(attend) == len(_mosaic_calls(text)), _mosaic_calls(text)
    if program == "decode_step":
        assert not attend, attend
    else:
        assert len(attend) == 4, attend
        tiles = [line.strip()[:160] for line in text.splitlines()
                 if "sparse_attend" in line and (
                     re.search(r"= f32\[%s,2,16\S* fusion\(" % width, line)
                     or re.search(r" while\(", line))]
        assert not tiles, tiles[:4]


_MIXED = dict(slots=32, pages=7937, window_pages=2113, page_len=64, width=248)


@pytest.mark.parametrize("program", ["decode_step", "chunk_2048",
                                     "chunk_512"])
def test_smallthinker_programs_keep_pools_and_expert_tables_in_place(
        v5e, tmp_path, monkeypatch, program):
    """SmallThinker's decode step and prefill chunk as the server declares
    them, compiled for the chip at the benchmark's sizes (8 layers at the
    published widths, all 64 experts, 7,937 full-layer and 2,113
    window-layer pages of 64, 32 slots): every pool leaf of both budgets is
    aliased to an output, no copy of a whole pool or of an expert table is
    left in the program, the grouped products are the compiler's own
    kernel, and the program with its temporaries fits the chip beside the
    weights. The decode step reads each stream's pages in place: one
    Mosaic call a layer (2 ``attn_full``, 6 ``attn_window``) and none of
    the XLA form's gathered tiles. A chunk attends with the chunk kernel:
    one Mosaic call a layer whose attention feeds a later layer, and none
    of the XLA form's float32 scores of a tile."""
    import json
    import pathlib
    import re
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(dispatch, "_seen", set())
    from analytics_zoo_tpu.capture.decoder import DecoderSpec, LayeredDecoder
    from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "perfbench" / "configs"
                      / "smallthinker_21b.json").read_text())
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, cfg["n_positions"]))
    lm.set_params(jax.eval_shape(lm.init_params))
    slots = _MIXED["slots"]
    assert lm.window_pages(slots) == _MIXED["window_pages"]
    srv = GenerativeServing(ServingConfig(
        data_src=f"dir://{tmp_path}/q", slots=2, kv_pages=2,
        kv_page_len=_MIXED["page_len"]), lm)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: v5e(a.shape, a.dtype), tree)

    params = described(srv._params)
    state = described(jax.eval_shape(
        lambda: {"length": jnp.zeros((slots,), I32),
                 "active": jnp.zeros((slots,), bool)}))
    pools = described(jax.eval_shape(lambda: lm.init_paged_caches(
        _MIXED["pages"], _MIXED["page_len"], slots=slots)))
    assert [p["k"].shape[0] for p in pools] == [7937, 2113, 2113, 2113] * 2
    table = v5e((slots, _MIXED["width"]), I32)
    row, scalar = v5e((_MIXED["width"],), I32), v5e((), I32)
    if program == "decode_step":
        lowered = srv._step_fn.lower(params, v5e((slots,), I32),
                                     v5e((slots,), I32),
                                     v5e((slots, 2), jnp.uint32), state,
                                     (table, table), pools)
    else:
        width = int(program.split("_")[1])
        lowered = srv._prefill_chunk_fn.lower(
            params, v5e((1, width), I32), pools, state, table, (row, row),
            scalar, scalar, scalar, scalar, v5e((), jnp.bool_))
    compiled = lowered.compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    leaves = {int(n) for n in re.findall(
        r"%caches_\S+ = \S+ parameter\((\d+)\)", entry)}
    assert len(leaves) == len(jax.tree_util.tree_leaves(pools)) == 16
    aliased = {int(n) for n in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    assert leaves <= aliased, sorted(leaves - aliased)
    for dims in ("7937,64,512", "2113,64,512", "64,2560,768", "64,768,2560"):
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= \w+\[%s\]\S* copy\(" % dims, line)]
        assert not copies, copies
    assert "ragged-dot" in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    assert held < 14.5e9, held   # of the chip's 16 GB
    assert dispatch.fallbacks_seen() == []
    calls = [name for name, _ in _mosaic_calls(text)
             if name.startswith("attn_")]
    full = [c for c in calls if c.startswith("attn_full")]
    window = [c for c in calls if c.startswith("attn_window")]
    assert len(full) + len(window) == len(calls), calls
    if program == "decode_step":
        assert (len(full), len(window)) == (2, 6), calls
        gathered = [line.strip()[:160] for line in text.splitlines()
                    if re.search(r"bf16\[32,1024,512\]", line)]
        assert not gathered, gathered[:4]
    else:
        # the last layer's attention feeds nothing in a chunk (no logits
        # come of it; its K and V are written before it) and the compiler
        # drops it, as in GLM-5's chunk: five window layers' kernels
        assert (len(full), len(window)) == (2, 5), calls
        scores = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"f32\[1,%s,4,7,512\]" % width, line)]
        assert not scores, scores[:4]


_LONGCTX = dict(slots=12, pages=6241, page_len=64, width=520)


@pytest.mark.parametrize("program", ["decode_step", "chunk_2048",
                                     "chunk_1024", "chunk_512"])
def test_glm5_programs_keep_pools_and_expert_tables_in_place(
        v5e, tmp_path, monkeypatch, program):
    """GLM-5's decode step and prefill chunk as the server declares them,
    compiled for the chip at the benchmark's sizes (1 dense + 4 expert
    layers at the published widths, 16 of 256 experts held, 6,241 pages of
    64 in a latent pool ``[P, 64, 640]`` (rows of 576 in whole tiles of 128
    lanes) and an index-key pool ``[P, 64, 128]`` a layer, 12 slots): every pool leaf is aliased to an output, no
    copy of a whole pool or of an expert table is left in the program, the
    grouped products are the compiler's own kernel, and the program with
    its temporaries fits the chip beside the weights. A chunk attends
    with the chunk kernel: one Mosaic call under ``mla_attend`` a layer
    whose attention feeds a later layer, which holds the loop over the key
    blocks, the expansion through ``kv_b`` and the running softmax, so the
    program has no ``while`` under that scope, none of the XLA loop's
    scores of a whole tile, and neither a tile's expanded keys and values
    ``[64, 2048, 448]`` nor a tile's result or the carry it is merged into
    ``[64, T, 256]``."""
    import json
    import pathlib
    import re
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(dispatch, "_seen", set())
    from analytics_zoo_tpu.capture.decoder import DecoderSpec, LayeredDecoder
    from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "perfbench" / "configs"
                      / "glm_5.json").read_text())
    lm = LayeredDecoder(DecoderSpec.from_config(cfg, cfg["n_positions"]))
    lm.set_params(jax.eval_shape(lm.init_params))
    slots = _LONGCTX["slots"]
    srv = GenerativeServing(ServingConfig(
        data_src=f"dir://{tmp_path}/q", slots=2, kv_pages=2,
        kv_page_len=_LONGCTX["page_len"]), lm)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: v5e(a.shape, a.dtype), tree)

    params = described(srv._params)
    state = described(jax.eval_shape(
        lambda: {"length": jnp.zeros((slots,), I32),
                 "active": jnp.zeros((slots,), bool)}))
    pools = described(jax.eval_shape(lambda: lm.init_paged_caches(
        _LONGCTX["pages"], _LONGCTX["page_len"], slots=slots)))
    assert [(p["latent"].shape, p["index"].shape) for p in pools] \
        == [((6241, 64, 640), (6241, 64, 128))] * 5
    table = v5e((slots, _LONGCTX["width"]), I32)
    row, scalar = v5e((_LONGCTX["width"],), I32), v5e((), I32)
    if program == "decode_step":
        lowered = srv._step_fn.lower(params, v5e((slots,), I32),
                                     v5e((slots,), I32),
                                     v5e((slots, 2), jnp.uint32), state,
                                     table, pools)
    else:
        width = int(program.split("_")[1])
        lowered = srv._prefill_chunk_fn.lower(
            params, v5e((1, width), I32), pools, state, table, row, scalar,
            scalar, scalar, scalar, v5e((), jnp.bool_))
    compiled = lowered.compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    leaves = {int(n) for n in re.findall(
        r"%caches_\S+ = \S+ parameter\((\d+)\)", entry)}
    assert len(leaves) == len(jax.tree_util.tree_leaves(pools)) == 10
    aliased = {int(n) for n in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    assert leaves <= aliased, sorted(leaves - aliased)
    for dims in ("6241,64,640", "6241,64,128", "16,6144,2048",
                 "16,2048,6144"):
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= \w+\[%s\]\S* copy\(" % dims, line)]
        assert not copies, copies
    assert "ragged-dot" in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    assert held < 14.5e9, held   # of the chip's 16 GB
    assert dispatch.fallbacks_seen() == []
    calls = [name for name, op_name in _mosaic_calls(text)
             if "ragged" not in name]
    if program == "decode_step":
        assert not calls, calls
    else:
        # the last layer's attention feeds nothing in a chunk (no logits
        # come of it) and the compiler drops it: four layers' kernels
        attend = [op_name for _, op_name in _mosaic_calls(text)
                  if "mla_attend" in op_name]
        assert len(calls) == len(attend) == 4, _mosaic_calls(text)
        loops = [line.strip()[:160] for line in text.splitlines()
                 if re.search(r" while\(", line) and "mla_attend" in line]
        assert not loops, loops[:4]
        whole = [line.strip()[:160] for line in text.splitlines()
                 if re.search(r"f32\[64,%s,2048\]" % width, line)
                 or re.search(r"(f32|bf16)\[64,(2048,448|448,2048|%s,256)\]"
                              % width, line)]
        assert not whole, whole[:4]


# the lowered text of SmallThinker's chunk kernel at its cell's shapes (2,048
# rows, a full layer's pool of 7,937 pages and a window layer's of 2,113),
# hashed on the tree before the kernel took a page mask (6ef3e02); see the
# test below
_SMALLTHINKER_CHUNK_KERNEL = {None: "b4e70b3d569e62e6",
                              4096: "373667d00769d217"}


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_smallthinker_chunk_kernel_lowers_to_the_text_it_was(v5e, window):
    """The grouped-query chunk kernel without a page mask, as SmallThinker's
    chunk programs call it, lowers to the text it lowered to before the
    mask came into the kernel (``ops/sparse_attention.py`` passes one):
    the Mosaic text is part of the compile cache's key and of what runs,
    so the unmasked kernel is the same program and compiles nothing
    anew."""
    import hashlib
    from analytics_zoo_tpu.common import context
    from analytics_zoo_tpu.ops import grouped_attention as GA
    context.wire_compilation_cache()
    pages = 7937 if window is None else 2113
    lowered = GA._attend_chunk_kernel.lower(
        v5e((2048, 4, 7, 128), F32), v5e((pages, 64, 512), BF16),
        v5e((pages, 64, 512), BF16), v5e((248,), I32), v5e((), I32),
        window, (512, 512))
    got = hashlib.sha256(
        lowered.as_text(debug_info=True).encode()).hexdigest()[:16]
    assert got == _SMALLTHINKER_CHUNK_KERNEL[window]


# the lowered text of the two older layered decoders' programs at a tiny size,
# hashed on PR 32's tree (abe2836); see the test below
_LOWERED = {
    "smallthinker.step": "e316ae3972f7144a",
    "smallthinker.chunk_8": "4b95ab126390b796",
    "smallthinker.chunk_32": "cc3bb262807dcd68",
    "sala.step": "97f003074592f864",
    "sala.chunk_8": "6fcd59c24b75e7b5",
    "sala.chunk_32": "ac903f110dd545a1",
    # GLM-5's, hashed at commit 180eaaa, before GLM-5.3-Flash's
    # layers came into the same files
    "glm5.step": "ca5690f0c1b1b239",
    "glm5.chunk_8": "23cac81076fbef67",
    "glm5.chunk_32": "a2b1056d8ccfeab0"}


@pytest.mark.parametrize("model", ["smallthinker", "sala", "glm5"])
def test_older_decoders_lower_to_the_programs_they_were(tmp_path, model):
    """MiniCPM-SALA's and SmallThinker's decode step and chunk programs, at a
    tiny size on the CPU, lower to the text they lowered to before GLM-5's
    layers came into ``capture/decoder.py`` and ``ops/moe.py``, and
    GLM-5's to theirs before GLM-5.3-Flash's layers came into those files
    and ``ops/latent_attention.py``: the
    text with its scope names is the compile cache's key
    (``common/context.py wire_compilation_cache`` keeps source lines out of
    it), so equal text means that neither model's cell compiles anything
    anew and both keep their scopes. A PR that changes these programs on
    purpose says so and pins the new hashes; one that moved an operation
    without meaning to finds out here and not in ``setup_s``."""
    import hashlib
    import json
    import pathlib
    from analytics_zoo_tpu.common import context
    from analytics_zoo_tpu.capture.decoder import DecoderSpec, LayeredDecoder
    from analytics_zoo_tpu.serving import GenerativeServing, ServingConfig
    context.wire_compilation_cache()
    root = pathlib.Path(__file__).resolve().parents[1]
    if model == "smallthinker":
        cfg = json.loads((root / "perfbench" / "configs"
                          / "smallthinker_21b.json").read_text())
        cfg.update(
            vocab_size=97, hidden_size=64, moe_ffn_hidden_size=32,
            head_dim=16, num_attention_heads=4, num_key_value_heads=2,
            moe_num_primary_experts=8, moe_num_active_primary_experts=2,
            sliding_window_size=32, num_hidden_layers=4,
            sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
            n_positions=128, param_dtype="float32")
        spec = DecoderSpec.from_config(cfg, 128, page_len=8)
    elif model == "glm5":
        cfg = json.loads((root / "perfbench" / "configs"
                          / "glm_5.json").read_text())
        cfg.update(
            vocab_size=97, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=12, qk_rope_head_dim=4, qk_head_dim=16,
            v_head_dim=16, index_n_heads=2, index_head_dim=8, index_topk=16,
            n_routed_experts=16, n_routed_experts_published=16,
            held_experts=None, num_experts_per_tok=4, num_hidden_layers=4,
            first_k_dense_replace=1, n_positions=128, param_dtype="float32")
        spec = DecoderSpec.from_config(cfg, 128, page_len=8)
    else:
        cfg = json.loads((root / "perfbench" / "configs"
                          / "minicpm_sala.json").read_text())
        cfg.update(
            vocab_size=97, hidden_size=64, intermediate_size=96, head_dim=16,
            num_attention_heads=4, num_key_value_heads=2, lightning_nh=4,
            lightning_head_dim=16, num_hidden_layers=4,
            mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                         "minicpm4"],
            n_positions=256, param_dtype="float32", dim_model_base=32,
            sparse_attention={"kernel_size": 4, "kernel_stride": 2,
                              "block_size": 8, "init_blocks": 1,
                              "window_size": 16, "topk": 2, "dense_len": 64})
        spec = DecoderSpec.from_config(cfg, 256)
    lm = LayeredDecoder(spec, prefill_chunk=32)
    lm.set_params(jax.eval_shape(lm.init_params))
    slots = 3
    srv = GenerativeServing(ServingConfig(
        data_src=f"dir://{tmp_path}/q", slots=slots, kv_pages=None,
        kv_page_len=lm.page_len), lm)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    params, state = described(srv._params), described(srv._state)
    pools = described(srv._caches)
    table, row = sds((slots, srv._table_w), I32), sds((srv._table_w,), I32)
    scalar = sds((), I32)
    if model == "smallthinker":
        table, row = (table, table), (row, row)

    def hashed(lowered):
        return hashlib.sha256(
            lowered.as_text(debug_info=True).encode()).hexdigest()[:16]
    got = {f"{model}.step": hashed(srv._step_fn.lower(
        params, sds((slots,), I32), sds((slots,), I32),
        sds((slots, 2), jnp.uint32), state, table, pools))}
    for width in (8, 32):
        got[f"{model}.chunk_{width}"] = hashed(srv._prefill_chunk_fn.lower(
            params, sds((1, width), I32), pools, state,
            table[0] if model == "smallthinker" else table, row, scalar,
            scalar, scalar, scalar, sds((), jnp.bool_)))
    assert got == {k: v for k, v in _LOWERED.items() if k in got}


# -- which branch ran: the reason strings, on the CPU -------------------------

def test_fallback_reasons_are_logged_once(monkeypatch, caplog):
    """Where a kernel gives way to its reference ON the TPU, one log line
    names the rule. The backend question is steered here, in the test;
    nothing is lowered, so tracing the kernels on the CPU is fine."""
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(dispatch, "_seen", set())
    q = jnp.ones((1, 2, 640, 64), BF16)
    table = jnp.ones((100, 64), F32)
    ids = jnp.zeros((4, 2), I32)
    with caplog.at_level("WARNING", logger="analytics_zoo_tpu.ops"):
        for _ in range(2):
            jax.make_jaxpr(jax.grad(lambda q: A.flash_attention(
                q, q, q, q_block=320).astype(F32).sum()))(q)
            jax.make_jaxpr(lambda t: ek.gather_pool(t, ids, "sum"))(table)
    seen = dict(dispatch.fallbacks_seen())
    assert "q tile 320 of q_len 640" in seen["flash_attention backward"]
    assert "blockwise_attention" in seen["flash_attention backward"]
    assert "table dim 64 is not 128" in seen["gather_pool"]
    assert len(caplog.records) == 2  # once each, not once per trace


def test_no_fallback_note_off_tpu(monkeypatch):
    """Off the TPU the lax path is the implementation, not a fallback."""
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "_seen", set())
    ek.gather_pool(jnp.ones((100, 64), F32), jnp.zeros((4, 2), I32), "sum")
    assert dispatch.fallbacks_seen() == []


# -- chip_smoke.py's phases at tiny sizes, on the CPU -------------------------

def _load_chip_smoke():
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_TINY_BERT = dict(vocab=64, hidden_size=32, n_block=1, n_head=8,
                  max_position_len=32, intermediate_size=64)
_TINY_LM = dict(vocab_size=64, hidden=32, n_block=1, n_head=8, max_len=64)


def test_chip_smoke_one_chip_phases_on_cpu(ctx, tmp_path):
    """kernels, train and serve at tiny sizes: the paths, arguments and
    control flow of the script, with the reference branches the CPU takes."""
    smoke = _load_chip_smoke()
    checks = smoke.phase_kernels(dict(
        flash=[((1, 2, 64, 16), "float32")],
        flash_bias=((1, 2, 32, 16), "float32"),
        short_bias=((2, 2, 16, 16), "float32"),
        short_causal=[((1, 2, 16, 16), "float32")],
        table=(64, 128), ids=16, bag=2, scatter_rows=8,
        paged=dict(slots=4, heads=2, dim=16, page_len=8, width=4,
                   pages=17)),
        expect_pallas=False)
    assert {c["branch"] for c in checks} == {"reference"}
    losses = smoke.phase_train(
        dict(bert=_TINY_BERT, seq=16, batch=8, steps=2, lr=1e-3),
        str(tmp_path),
        expect_pallas=False)
    assert losses["epoch_2_resumed"] == losses["epoch_2_straight"]
    served = smoke.phase_serve(
        dict(lm=_TINY_LM, alphabet=8, fit_steps=2, fit_seq=16, fit_batch=8,
             lr=1e-3, must_learn=False, prompt_lens=(17, 18), max_new=4,
             slots=2),
        str(tmp_path), expect_pallas=False)
    assert len(served) == 2


@pytest.mark.slow  # the four-chip rehearsal on virtual devices: ~25 s
def test_chip_smoke_cross_chip_phases_on_cpu(ctx):
    """``--chips 4``'s two comparisons over the suite's virtual devices:
    meshes, sharding rules and the placement checks."""
    smoke = _load_chip_smoke()
    n = len(jax.devices())
    smoke.phase_data_parallel(
        dict(bert=_TINY_BERT, seq=16, batch=2 * n, steps=2, lr=1e-3),
        expect_pallas=False)
    smoke.phase_tensor_parallel(
        dict(lm=dict(_TINY_LM, n_head=n), alphabet=8, fit_steps=2, seq=16,
             batch=8, lr=1e-3, compare_steps=2, must_learn=False,
             prompt_len=5, max_new=4), expect_pallas=False)
