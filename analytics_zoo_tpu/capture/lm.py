"""TransformerLM — decoder-only language model with cached generation.

Beyond-reference capability (the reference's only generator is the RNN
Seq2seq chatbot path): a pure-functional transformer decoder whose
TRAINING step runs causal flash attention (pallas on TPU) and whose
GENERATION runs the static-shape KV cache (``ops/decode.py``) with the
whole decode in one ``lax.scan`` dispatch. Training plugs into the
capture-style ``GraphModel.from_loss`` contract, so fit/evaluate ride the
same Estimator loop as every other captured model.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..keras.layers.attention import _layer_norm, _layer_norm_params
from ..ops import dispatch
from ..ops.attention import (flash_attention, fused_short_applicable,
                             fused_short_attention, masked_context)
from ..ops.decode import (beam_generate, cached_attention,
                          greedy_generate, init_kv_cache, init_paged_pool,
                          init_slot_cache, paged_attention, paged_insert,
                          paged_pages_read, paged_verify_attention,
                          sample_generate,
                          slot_attention, slot_insert, speculative_generate)

#: prefill length buckets: prompts are right-padded to the smallest bucket
#: that fits, so ONE compiled prefill program per bucket covers every
#: prompt length — both for ``generate()`` and for slot joins in the
#: continuous-batching scheduler (serving/server.py)
PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512)


def prefill_bucket(length: int, max_len: int) -> int:
    """Smallest prefill bucket >= ``length`` (capped at ``max_len``)."""
    for b in PREFILL_BUCKETS:
        if length <= b <= max_len:
            return b
    return max_len


class TransformerLM:
    """Decoder-only LM: tied-embedding logits, pre-LN blocks, causal
    attention. ``fit(tokens)`` trains next-token prediction;
    ``generate(prompt, max_new_tokens)`` decodes greedily off the KV
    cache."""

    def __init__(self, vocab_size: int, hidden: int = 256, n_block: int = 4,
                 n_head: int = 4, max_len: int = 512,
                 intermediate: Optional[int] = None, optimizer="adam",
                 mesh=None, tensor_parallel: bool = False,
                 pipeline_stages: Optional[int] = None,
                 pipeline_microbatches: Optional[int] = None,
                 seed: int = 0):
        if hidden % n_head:
            raise ValueError(f"hidden {hidden} not divisible by "
                             f"heads {n_head}")
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.n_block = n_block
        self.n_head = n_head
        self.max_len = max_len
        self.intermediate = intermediate or 4 * hidden
        self._head_dim = hidden // n_head
        from ..common.config import global_config
        cfg = global_config()
        if pipeline_stages is None:
            pipeline_stages = int(cfg.get("parallel.pipeline_stages"))
        if pipeline_microbatches is None:
            pipeline_microbatches = int(
                cfg.get("parallel.pipeline_microbatches"))
        self.mesh = mesh
        self.tensor_parallel = bool(tensor_parallel)
        self._pipe_stages = int(pipeline_stages)
        self._pipe_micro = int(pipeline_microbatches)
        self._pipe_loss_cache: Dict[int, Any] = {}
        if self._pipe_stages:
            from ..parallel.pipeline import PIPE_AXIS, note_pipeline_build
            if self.n_block % self._pipe_stages:
                raise ValueError(
                    f"n_block {self.n_block} not divisible by "
                    f"pipeline_stages {self._pipe_stages}")
            if self.mesh is None:
                from jax.sharding import Mesh
                devs = jax.devices()
                if len(devs) < self._pipe_stages:
                    raise ValueError(
                        f"pipeline_stages={self._pipe_stages} needs that "
                        f"many devices; have {len(devs)}")
                self.mesh = Mesh(np.asarray(devs[:self._pipe_stages]),
                                 (PIPE_AXIS,))
            # profiler gauge: the schedule's idle fraction is known at
            # build time (bytes-per-hop lands when fit sees the batch)
            note_pipeline_build(self._pipe_stages, self._pipe_micro)
        from .graph_model import GraphModel
        self._graph = GraphModel.from_loss(
            self._loss_pipelined if self._pipe_stages else self._loss,
            self._init_params, optimizer=optimizer,
            forward_fn=self._forward)
        # thread the seed into the Estimator's init rng
        self._graph.estimator.root_rng = jax.random.PRNGKey(seed)
        if self._pipe_stages:
            # params/opt state must live on the pipe mesh's devices
            # (replicated there; the shard_map in the loss stage-shards
            # the stacked blocks at dispatch)
            self._graph.estimator.mesh = self.mesh
        if self.tensor_parallel:
            from ..parallel.tensor import transformer_tp_rules
            axis = str(cfg.get("parallel.tensor_axis"))
            if self.mesh is None:
                from jax.sharding import Mesh
                self.mesh = Mesh(np.asarray(jax.devices()), (axis,))
            if axis not in self.mesh.axis_names:
                raise ValueError(
                    f"tensor_parallel needs a mesh with a '{axis}' axis; "
                    f"got {self.mesh.axis_names}")
            n = dict(zip(self.mesh.axis_names,
                         self.mesh.devices.shape))[axis]
            # qkv column sharding splits heads across the axis; fc1 splits
            # the FFN hidden dim — both must divide for equal shards
            if self.n_head % n or self.intermediate % n:
                raise ValueError(
                    f"n_head {self.n_head} and intermediate "
                    f"{self.intermediate} must both be divisible by the "
                    f"'{axis}' axis size {n}")
            est = self._graph.estimator
            est.mesh = self.mesh
            est.param_rules = (list(est.param_rules or [])
                               + transformer_tp_rules(axis))

    # -- parameters -----------------------------------------------------------

    def _init_params(self, rng, sample_x) -> Dict[str, Any]:
        del sample_x
        d, inter, v = self.hidden, self.intermediate, self.vocab_size
        keys = jax.random.split(rng, 2 + 4 * self.n_block)
        init = jax.nn.initializers.normal(0.02)

        def dense(key, fan_in, fan_out):
            return {"kernel": init(key, (fan_in, fan_out), jnp.float32),
                    "bias": jnp.zeros((fan_out,))}

        def ln():
            return _layer_norm_params(d)

        blocks = []
        for i in range(self.n_block):
            k = jax.random.split(keys[2 + i], 4)
            blocks.append({
                "ln1": ln(), "qkv": dense(k[0], d, 3 * d),
                "attn_out": dense(k[1], d, d),
                "ln2": ln(), "fc1": dense(k[2], d, inter),
                "fc2": dense(k[3], inter, d),
            })
        return {"embed": init(keys[0], (v, d), jnp.float32),
                "pos": init(keys[1], (self.max_len, d), jnp.float32),
                "blocks": blocks, "ln_f": ln()}

    # -- training-time forward (full sequence, flash attention) --------------

    def _split_heads(self, x):
        b, s, _ = x.shape
        return x.reshape(b, s, self.n_head, self._head_dim).transpose(
            0, 2, 1, 3)

    def _block(self, p, x, kv_fn):
        """One block. Scope names on the device (docs/observability.md):
        ``layer_norm``, ``attention`` (projections and layout), ``ffn``;
        what ``kv_fn`` runs names itself beside them (``attn_*`` kernels,
        ``kv_write`` / ``kv_gather`` / ``kv_attend`` of ``ops/decode.py``)."""
        with jax.named_scope("layer_norm"):
            h = _layer_norm(p["ln1"], x)
        with jax.named_scope("attention"):
            qkv = h @ p["qkv"]["kernel"] + p["qkv"]["bias"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q, k, v = (self._split_heads(q), self._split_heads(k),
                       self._split_heads(v))
        ctx = kv_fn(q, k, v)
        with jax.named_scope("attention"):
            b, _, s, _ = ctx.shape
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, self.hidden)
            x = x + ctx @ p["attn_out"]["kernel"] + p["attn_out"]["bias"]
        with jax.named_scope("layer_norm"):
            h = _layer_norm(p["ln2"], x)
        with jax.named_scope("ffn"):
            h = jax.nn.gelu(h @ p["fc1"]["kernel"] + p["fc1"]["bias"])
            return x + h @ p["fc2"]["kernel"] + p["fc2"]["bias"]

    def _head(self, params, x, last_only: bool):
        """Final norm and the tied output product: logits of every
        position, or of the last one only (decode)."""
        with jax.named_scope("layer_norm"):
            x = _layer_norm(params["ln_f"], x)
        with jax.named_scope("head"):
            return (x[:, -1] if last_only else x) @ params["embed"].T

    def _forward(self, params, tokens) -> jax.Array:
        tokens = tokens.astype(jnp.int32)
        s = tokens.shape[1]
        with jax.named_scope("embed"):
            x = params["embed"][tokens] + params["pos"][None, :s]
        for p in params["blocks"]:
            x = self._block(
                p, x, lambda q, k, v: flash_attention(q, k, v, causal=True))
        return self._head(params, x, last_only=False)  # tied [B, S, V]

    def _loss(self, params, x, y=None):
        tokens = x.astype(jnp.int32)
        logits = self._forward(params, tokens[:, :-1])
        targets = tokens[:, 1:]
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return jnp.mean(nll)

    # -- pipelined training (1F1B over the pipe mesh axis) --------------------

    def _pipe_stage_fn(self, local, x):
        """One pipeline stage: this device's ``n_block/P`` transformer
        blocks, applied in order. ``local`` is the device's slice of the
        ``[P, blocks_per_stage, ...]`` stage-stacked tree."""
        blocks = jax.tree_util.tree_map(lambda l: l[0], local)
        for i in range(self.n_block // self._pipe_stages):
            p = jax.tree_util.tree_map(lambda l: l[i], blocks)
            x = self._block(
                p, x, lambda q, k, v: flash_attention(q, k, v, causal=True))
        return x

    def _pipe_head_loss(self, head, out, targets):
        """Last-stage head: final LN + tied logits + next-token NLL for one
        microbatch — the same arithmetic as ``_loss`` after the trunk."""
        x = _layer_norm(head["ln_f"], out)
        logits = x @ head["embed"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll)

    def _pipe_loss_for(self, batch: int):
        """The compiled 1F1B loss for a given batch size: microbatch count
        is ``gcd(batch, pipeline_microbatches)`` so tail batches (smaller,
        separately compiled shapes anyway) still divide evenly."""
        import math
        from ..parallel.pipeline import make_pipeline_loss
        m = math.gcd(batch, self._pipe_micro) or 1
        fn = self._pipe_loss_cache.get(m)
        if fn is None:
            fn = make_pipeline_loss(self._pipe_stage_fn,
                                    self._pipe_head_loss, self.mesh,
                                    n_microbatches=m)
            self._pipe_loss_cache[m] = fn
        return fn

    def _loss_pipelined(self, params, x, y=None):
        """``_loss`` with the block trunk running the 1F1B pipeline schedule
        over ``mesh['pipe']``: embedding and the tied head stay outside the
        custom_vjp (so the embedding-gather gradient rides the returned
        ``dx``, summing with the head's tied-weight gradient), while the
        blocks are stage-stacked and sharded one group per device.
        Microbatch means average to the global mean at equal sizes, so
        parity vs ``_loss`` is float32 tolerance, not bitwise (documented
        in docs/parallelism.md)."""
        from ..parallel.pipeline import stack_stage_params
        tokens = x.astype(jnp.int32)
        inp, targets = tokens[:, :-1], tokens[:, 1:]
        s = inp.shape[1]
        xe = params["embed"][inp] + params["pos"][None, :s]
        bps = self.n_block // self._pipe_stages
        stacked = stack_stage_params(
            [stack_stage_params(params["blocks"][i * bps:(i + 1) * bps])
             for i in range(self._pipe_stages)])
        head = {"ln_f": params["ln_f"], "embed": params["embed"]}
        return self._pipe_loss_for(xe.shape[0])(stacked, head, xe, targets)

    # -- generative prefill + slot decode (continuous batching) ---------------

    def _prefill_attn(self, q, k, v):
        """Causal attention for the prefill forward: the fused short-seq
        kernel when the shape qualifies (TPU, bucketed length <= 512), the
        flash path otherwise — the same cutover the training step uses."""
        if fused_short_applicable(q, k):
            return fused_short_attention(q, k, v, causal=True)
        return flash_attention(q, k, v, causal=True)

    def prefill_kv(self, params, tokens):
        """Causal forward over a right-padded prompt block ``[B, Tb]``
        capturing every block's K/V projections ``[B, H, Tb, D]``.

        This is THE prefill path: ``generate()`` and the slot scheduler
        both call it with bucket-padded prompts, so a prompt prefilled
        serially and one joining a slot run the identical compiled program
        and land bit-identical K/V. Causality keeps real positions
        independent of the right-padding; the padded tail's K/V is written
        but never visible (decode masks by per-slot length and overwrites
        it token by token)."""
        tokens = tokens.astype(jnp.int32)
        s = tokens.shape[1]
        with jax.named_scope("embed"):
            x = params["embed"][tokens] + params["pos"][None, :s]
        kvs = []
        # the params live on the estimator's mesh, so whatever runs this —
        # generate() eagerly, the scheduler's jitted prefill — is a
        # program over that mesh (ops/dispatch.py)
        with dispatch.partitioned_over(self._graph.estimator.mesh):
            for p in params["blocks"]:
                holder = {}

                def kv_fn(q, k, v, holder=holder):
                    holder["kv"] = (k, v)
                    return self._prefill_attn(q, k, v)
                x = self._block(p, x, kv_fn)
                kvs.append(holder["kv"])
        return kvs

    def init_slot_caches(self, slots: int):
        """One slot-batched K/V cache per block (float32 — decode parity
        with the serial ``generate()`` caches). With :meth:`slot_step`, what
        a DRAFT model decodes off in speculative rounds (the scheduler's and
        :meth:`generate_speculative`'s) and the reference the paged step is
        held bit-identical to (tests/test_paged_kv.py); the scheduler's
        target model steps through :meth:`paged_slot_step`."""
        return [init_slot_cache(slots, self.n_head, self.max_len,
                                self._head_dim, jnp.float32)
                for _ in range(self.n_block)]

    def slot_step(self, params, tokens, lengths, caches):
        """One decode step over ALL slots: feed ``tokens`` [S] (one per
        slot), write each slot's K/V at its own ``lengths[s]`` position and
        attend against its visible prefix. Returns ``(next-token logits
        [S, V], updated caches)``. Pure and shape-static: slot occupancy
        and lengths are DATA, so the scheduler jits this once and never
        recompiles as streams join and leave."""
        tokens = jnp.asarray(tokens, jnp.int32)
        with jax.named_scope("embed"):
            x = (params["embed"][tokens][:, None]
                 + params["pos"][lengths][:, None])
        new_caches = []
        for p, cache in zip(params["blocks"], caches):
            holder = {}

            def kv_fn(q, k, v, cache=cache, holder=holder):
                ctx, holder["cache"] = slot_attention(q, k, v, cache,
                                                      lengths)
                return ctx
            x = self._block(p, x, kv_fn)
            new_caches.append(holder["cache"])
        return self._head(params, x, last_only=True), new_caches

    # -- paged decode + speculative verify ------------------------------------

    def init_paged_caches(self, num_pages: int, page_len: int,
                          int8: bool = False):
        """One paged KV pool per block, ``[num_pages, page_len, H*D]``
        (page 0 is the shared null page). A program that returns the pools
        should be given them (``jax.jit(..., donate_argnames=...)``): the
        chip's compiler then writes the new rows in place."""
        if self.max_len % page_len:
            raise ValueError(f"page_len {page_len} must divide "
                             f"max_len {self.max_len}")
        return [init_paged_pool(num_pages, self.n_head, page_len,
                                self._head_dim, jnp.float32, int8=int8)
                for _ in range(self.n_block)]

    def paged_slot_step(self, params, tokens, lengths, table, caches):
        """``slot_step`` against the paged pool: each slot's K/V lives in
        the pages its ``table`` row names instead of a private ``max_len``
        rectangle. Returns ``(next-token logits [S, V], updated caches,
        pages read)``: the last is what one block's attention read for all
        slots, counted here from ``lengths`` by the form that runs
        (``ops/decode.py paged_pages_read``).

        Off the TPU, and on it for int8 pools and programs over several
        devices, the XLA form runs and the step is bit-identical to
        ``slot_step`` (the gathered buffer differs from the contiguous one
        only at masked-to-exact-zero positions). On one TPU chip over an
        unquantised pool the kernel reads the live pages in place; the
        benchmark's ``correct`` holds it there (``served_logit_gap_max``)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        with jax.named_scope("embed"):
            x = (params["embed"][tokens][:, None]
                 + params["pos"][lengths][:, None])
        new_caches = []
        # the mesh under the parameters says whether the program spans
        # several devices, where no Mosaic kernel can go (ops/dispatch.py)
        with dispatch.partitioned_over(self._graph.estimator.mesh):
            read = paged_pages_read(caches[0], table, lengths, self.max_len)
            for p, cache in zip(params["blocks"], caches):
                holder = {}

                def kv_fn(q, k, v, cache=cache, holder=holder):
                    ctx, holder["cache"] = paged_attention(
                        q, k, v, cache, table, lengths, self.max_len)
                    return ctx
                x = self._block(p, x, kv_fn)
                new_caches.append(holder["cache"])
        return self._head(params, x, last_only=True), new_caches, read

    def verify_step(self, params, blocks, lengths, table, caches):
        """Speculative verify: feed ``blocks`` [S, T] (last committed token
        + T-1 drafts per slot) through the paged cache in ONE batched pass
        and return FULL logits [S, T, V] plus updated caches. Row ``j``
        attends causally at position ``lengths + j``; K/V is written at the
        same positions, so a later round's re-write over rejected drafts
        lands at identical offsets (no rollback copy needed)."""
        blocks = jnp.asarray(blocks, jnp.int32)
        t = blocks.shape[1]
        positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        with jax.named_scope("embed"):
            x = (params["embed"][blocks]
                 + params["pos"][jnp.minimum(positions, self.max_len - 1)])
        new_caches = []
        for p, cache in zip(params["blocks"], caches):
            holder = {}

            def kv_fn(q, k, v, cache=cache, holder=holder):
                ctx, holder["cache"] = paged_verify_attention(
                    q, k, v, cache, table, lengths)
                return ctx
            x = self._block(p, x, kv_fn)
            new_caches.append(holder["cache"])
        return self._head(params, x, last_only=False), new_caches

    def prefill_kv_suffix(self, params, tokens, prefix_kvs, prefix_len):
        """Causal forward over a right-padded SUFFIX block [B, Tb] whose
        positions start at static ``prefix_len``, attending over the
        already-materialised prefix K/V (``prefix_kvs``: per-block
        ``(k, v)`` [B, H, prefix_len, D]) plus the causal suffix. This is
        the shared-prefix join path: the common prompt's K/V comes from
        refcounted pages prefilled once, and only the divergent suffix
        burns a prefill forward."""
        tokens = tokens.astype(jnp.int32)
        s = tokens.shape[1]
        with jax.named_scope("embed"):
            x = (params["embed"][tokens]
                 + params["pos"][None, prefix_len:prefix_len + s])
        row_pos = jnp.arange(s, dtype=jnp.int32)
        kvs = []
        for p, (pk, pv) in zip(params["blocks"], prefix_kvs):
            holder = {}

            def kv_fn(q, k, v, pk=pk, pv=pv, holder=holder):
                holder["kv"] = (k, v)
                k_buf = jnp.concatenate([pk.astype(k.dtype), k], axis=2)
                v_buf = jnp.concatenate([pv.astype(v.dtype), v], axis=2)
                key_pos = jnp.arange(prefix_len + s, dtype=jnp.int32)
                visible = (key_pos[None, None, None, :]
                           <= prefix_len + row_pos[None, None, :, None])
                scale = 1.0 / (q.shape[-1] ** 0.5)
                return masked_context(q, k_buf, v_buf, visible, scale)
            x = self._block(p, x, kv_fn)
            kvs.append(holder["kv"])
        return kvs

    # -- public surface -------------------------------------------------------

    def fit(self, tokens, batch_size: int = 32, epochs: int = 1, **kw):
        """``tokens``: [N, S] int sequences; trains next-token NLL."""
        tokens = np.asarray(tokens, np.float32)
        if self._pipe_stages:
            # per-hop ppermute traffic is known once the batch shape is:
            # one [mb, S-1, hidden] float32 activation per tick per ring
            from ..parallel.pipeline import note_pipeline_build
            import math
            m = math.gcd(batch_size, self._pipe_micro) or 1
            micro_bytes = (batch_size // m) * (tokens.shape[1] - 1) \
                * self.hidden * 4
            note_pipeline_build(self._pipe_stages, m,
                                micro_bytes=micro_bytes)
        return self._graph.fit(tokens, batch_size=batch_size,
                               epochs=epochs, **kw)

    def logits(self, tokens, batch_size: int = 32):
        return self._graph.predict(np.asarray(tokens, np.float32),
                                   batch_size=batch_size)

    @property
    def params(self):
        params = self._graph.estimator.params
        if params is None:
            raise RuntimeError(
                "TransformerLM has no parameters yet: call fit() (or "
                "restore a checkpoint through the estimator) first")
        return params

    def generate(self, prompt, max_new_tokens: int,
                 eos_id: Optional[int] = None,
                 beam_size: int = 1,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 seed: Optional[int] = None) -> np.ndarray:
        """Continuation of ``prompt`` [B, S]: prefill the prompt minus its
        last token through the per-block KV caches, then decode
        ``max_new_tokens`` in one scan dispatch — greedy by default, beam
        search (best sequence returned) with ``beam_size > 1``, or sampled
        when ``temperature``/``top_k``/``top_p`` is given. Sampling draws
        fresh entropy per call; pass ``seed`` for reproducible draws."""
        sampling = (temperature is not None or top_k is not None
                    or top_p is not None)
        if sampling and beam_size > 1:
            raise ValueError("choose either beam_size > 1 or sampling "
                             "(temperature/top_k/top_p), not both")
        prompt = jnp.asarray(np.asarray(prompt), jnp.int32)
        b, s = prompt.shape
        if s + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_len={self.max_len}")
        params = self.params
        caches = [init_kv_cache(b, self.n_head, self.max_len,
                                self._head_dim, jnp.float32)
                  for _ in range(self.n_block)]

        def run(params, tokens, caches):
            """Feed ``tokens`` [B, T] through all blocks with caches;
            returns (next-token logits [B, V], caches)."""
            start = caches[0]["length"]
            with jax.named_scope("embed"):
                x = params["embed"][tokens] + jax.lax.dynamic_slice(
                    params["pos"], (start, 0),
                    (tokens.shape[1], self.hidden))[None]
            new_caches = []
            for p, cache in zip(params["blocks"], caches):
                holder = {}

                def kv_fn(q, k, v, cache=cache, holder=holder):
                    ctx, holder["cache"] = cached_attention(q, k, v, cache)
                    return ctx
                x = self._block(p, x, kv_fn)
                new_caches.append(holder["cache"])
            return self._head(params, x, last_only=True), new_caches

        if s > 1:
            # prefill everything except the last prompt token through the
            # SAME bucketed causal-forward path the continuous-batching
            # scheduler uses (fused short-seq kernel on TPU) — one compile
            # per length bucket instead of re-attending the whole prompt
            # through the incremental cache per request
            tb = prefill_bucket(s - 1, self.max_len)
            padded = jnp.zeros((b, tb), jnp.int32)
            padded = jax.lax.dynamic_update_slice(padded, prompt[:, :-1],
                                                  (0, 0))
            kvs = self.prefill_kv(params, padded)
            caches = [{"k": c["k"].at[:, :, :tb, :].set(
                           k.astype(c["k"].dtype)),
                       "v": c["v"].at[:, :, :tb, :].set(
                           v.astype(c["v"].dtype)),
                       "length": jnp.asarray(s - 1, jnp.int32)}
                      for c, (k, v) in zip(caches, kvs)]

        def step_fn(params, token, caches):
            return run(params, token[:, None], caches)

        if beam_size > 1:
            seqs, _ = beam_generate(step_fn, params, caches, prompt[:, -1],
                                    max_new_tokens, beam_size,
                                    eos_id=eos_id)
            return np.asarray(seqs[:, 0])  # best beam
        if sampling:
            if seed is None:  # fresh entropy: repeated calls differ
                seed = int(np.random.SeedSequence().entropy % (2 ** 31))
            return np.asarray(sample_generate(
                step_fn, params, caches, prompt[:, -1], max_new_tokens,
                jax.random.PRNGKey(seed),
                temperature=temperature if temperature is not None else 1.0,
                top_k=top_k, top_p=top_p, eos_id=eos_id))
        return np.asarray(greedy_generate(
            step_fn, params, caches, prompt[:, -1], max_new_tokens,
            eos_id=eos_id))

    def generate_speculative(self, prompt, draft_lm: "TransformerLM",
                             max_new_tokens: int, spec_k: int = 4,
                             eos_id: Optional[int] = None,
                             temperature: Optional[float] = None,
                             top_k: Optional[int] = None,
                             top_p: Optional[float] = None,
                             seed: Optional[int] = None,
                             page_len: int = 16) -> np.ndarray:
        """Speculative continuation of ``prompt`` [B, S] through the PAGED
        target cache: ``draft_lm`` proposes ``spec_k`` tokens per round off
        its contiguous slot cache, the target verifies the whole block in
        one batched ``verify_step``, and the standard accept rule keeps the
        longest agreeing run. Greedy output is token-identical to
        ``generate()``; sampled output follows the Leviathan accept/resample
        rule (exact target distribution). Both prompts are prefilled through
        the same bucketed path as ``generate()``."""
        sampling = (temperature is not None or top_k is not None
                    or top_p is not None)
        prompt = jnp.asarray(np.asarray(prompt), jnp.int32)
        b, s = prompt.shape
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if s + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_len={self.max_len}")
        if s + max_new_tokens + spec_k > draft_lm.max_len:
            raise ValueError(
                f"draft max_len={draft_lm.max_len} too short for prompt "
                f"({s}) + max_new_tokens ({max_new_tokens}) + spec_k "
                f"({spec_k}) transient draft positions")
        if self.max_len % page_len:
            raise ValueError(f"page_len {page_len} must divide "
                             f"max_len {self.max_len}")
        params, dparams = self.params, draft_lm.params
        pl = page_len
        # statically assigned private pages per row, wide enough for the
        # prompt, the budget, and the transient spec_k overshoot
        per_row = (s + max_new_tokens + spec_k + pl - 1) // pl
        width = (self.max_len + spec_k + pl - 1) // pl
        table_host = np.zeros((b, width), np.int32)
        for r in range(b):
            table_host[r, :per_row] = 1 + r * per_row + np.arange(per_row)
        table = jnp.asarray(table_host)
        caches = self.init_paged_caches(b * per_row + 1, pl)
        dcaches = draft_lm.init_slot_caches(b)
        lengths0 = jnp.full((b,), s - 1, jnp.int32)
        if s > 1:
            tb = prefill_bucket(s - 1, self.max_len)
            padded = jnp.zeros((b, tb), jnp.int32)
            padded = jax.lax.dynamic_update_slice(padded, prompt[:, :-1],
                                                  (0, 0))
            kvs = self.prefill_kv(params, padded)
            for r in range(b):
                caches = [paged_insert(c, table[r], k[r], v[r])
                          for c, (k, v) in zip(caches, kvs)]
            dtb = prefill_bucket(s - 1, draft_lm.max_len)
            dpadded = jnp.zeros((b, dtb), jnp.int32)
            dpadded = jax.lax.dynamic_update_slice(dpadded, prompt[:, :-1],
                                                   (0, 0))
            dkvs = draft_lm.prefill_kv(dparams, dpadded)
            for r in range(b):
                dcaches = [slot_insert(c, r, k[r], v[r])
                           for c, (k, v) in zip(dcaches, dkvs)]

        def draft_step_fn(dp, toks, ln, dc):
            return draft_lm.slot_step(dp, toks, ln, dc)

        def verify_fn(tp, block, ln, tc):
            return self.verify_step(tp, block, ln, table, tc)

        rng = None
        if sampling:
            if seed is None:
                seed = int(np.random.SeedSequence().entropy % (2 ** 31))
            rng = jax.random.PRNGKey(seed)
        out = speculative_generate(
            draft_step_fn, verify_fn, dparams, params, dcaches, caches,
            prompt[:, -1], lengths0, max_new_tokens, spec_k, eos_id=eos_id,
            rng=rng,
            temperature=temperature if temperature is not None else 1.0,
            top_k=top_k, top_p=top_p)
        return np.asarray(out)
