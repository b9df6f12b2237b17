"""Estimator — the distributed training loop, on device.

Re-designs the reference's ``Estimator.train/evaluate``
(``pipeline/estimator/Estimator.scala:118,163``) +
``InternalDistriOptimizer.train()`` (``Topology.scala:1085-1268``) as a single
jitted train step over a device mesh:

- the reference's per-iteration two-Spark-job dance (fetch param slices →
  forward/backward per core replica → put grad slices → slice owners apply the
  optimizer → workers fetch updated slices) collapses into ONE XLA program:
  ``value_and_grad`` → (XLA-inserted) psum over the ``data`` axis →
  optimizer update, with params donated so updates are in-place in HBM.
- per-core model replicas become per-chip shards of the batch axis; the
  global-batch contract (global batch = chips × per-chip batch,
  ``Topology.scala:1110-1119``) is kept: ``batch_size`` is always global.
- the driver-side retry-with-checkpoint elasticity loop
  (``Topology.scala:1180-1262``) is reproduced: on failure, reload the newest
  checkpoint within a retry budget (``failure.retry_times`` /
  ``failure.retry_interval_s`` config, ≙ ``bigdl.failure.retryTimes``).
- the reference's straggler mitigation (``dropPercentage`` — drop the
  slowest tasks' results per iteration, ``Topology.scala:1096-1099``) is
  DESIGNED AWAY: synchronous SPMD over ICI has no per-worker task results to
  drop — chips run one lock-step program, and a slow/failed chip surfaces as
  a step failure handled by the elastic retry above.
- TensorBoard scalars Loss/LearningRate/Throughput per iteration + validation
  scalars per metric (``Topology.scala:206-238``).
"""
from __future__ import annotations

import functools
import json
import logging
import os
import signal
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..common import faults, file_io
from ..common import metrics as zoo_metrics
from ..common import profiler as _profiler
from ..common.config import global_config
from ..common.context import get_context
from ..common.triggers import EveryEpoch, MaxEpoch, TrainingState, Trigger
from ..common.utils import time_it
from ..feature.featureset import FeatureSet, HostDataset
from ..feature.device_feed import (DeviceFeed, masked_eval_batches,
                                   shard_payload)
from ..keras import metrics as metrics_mod
from ..keras.optimizers import Optimizer
from ..ops import dispatch as _kernel_dispatch
from ..parallel import embedding as _embed_engine
from ..parallel.mesh import (param_sharding, replicated, shard_batch,
                             vocab_sharding_rule)
from ..utils.tensorboard import SummaryWriter


class CheckpointCorruptError(ValueError):
    """A snapshot failed checksum-manifest verification (torn write,
    bit-rot, tampering). The elastic restore path treats it as 'skip this
    snapshot and fall back to the next-older valid one'."""


class PreemptedError(RuntimeError):
    """Training stopped on a preemption notice (SIGTERM / the
    ``train.preempt`` fault site). A final snapshot and a resumable marker
    were written first when a checkpoint dir is configured; ``snapshot``
    carries its path (or ``None``)."""

    def __init__(self, message: str, snapshot: Optional[str] = None):
        super().__init__(message)
        self.snapshot = snapshot


#: train-loop + checkpoint telemetry (the shared registry every subsystem
#: reports into — see docs/observability.md for the full metric table)
_M_STEP = zoo_metrics.histogram(
    "train.step_seconds",
    "Train-step dispatch latency (device sync included only when the "
    "loop syncs the loss).")
_M_EXAMPLES = zoo_metrics.counter(
    "train.examples_total", "Examples consumed by the train loop.")
_M_CKPT_WRITE = zoo_metrics.histogram(
    "ckpt.write_seconds", "Snapshot serialize+publish latency.")
_M_CKPT_VERIFY = zoo_metrics.histogram(
    "ckpt.verify_seconds", "Checksum-manifest verification latency.")
_M_CKPT_RESTORE = zoo_metrics.histogram(
    "ckpt.restore_seconds", "Snapshot restore latency (verify included).")
_M_CKPT_FALLBACK = zoo_metrics.counter(
    "ckpt.fallback_total",
    "Restores that skipped a torn/corrupt newest snapshot and fell back "
    "to an older one.")

#: step-phase attribution for the train loop (host_input / dispatch /
#: execute / fetch / compile per step) — active only under profile.enabled
_P_TRAIN = _profiler.StepProfiler("train")


def _traced_for_own_mesh(method):
    """Estimator entry points trace their step programs for ``self.mesh``:
    say so, so that pallas kernels under a several-device mesh run per
    shard instead of being refused by the partitioner (ops/dispatch.py)."""
    @functools.wraps(method)
    def scoped(self, *args, **kwargs):
        with _kernel_dispatch.partitioned_over(self.mesh):
            return method(self, *args, **kwargs)
    return scoped


def _profiled_feed(feed, prof):
    """Wrap the device feed so each step window opens just before its
    blocking ``next()`` — host-input stalls land in THIS step's phases."""
    it = iter(feed)
    while True:
        prof.step_start()
        t0 = time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            return
        prof.add("host_input", time.perf_counter() - t0, start=t0)
        yield item


#: resumable-preemption marker filename, written next to the snapshots
PREEMPT_MARKER = "PREEMPTED.json"

#: per-snapshot checksum manifest filename (inside each snapshot dir)
_MANIFEST_NAME = "zoo_manifest.json"

#: per-rank seal stamp: ``zoo_rank-<i>.ok``, written by EVERY process of a
#: multi-process pod after the collective orbax save returns. Excluded
#: from the checksum manifest (ranks write them concurrently with rank
#: 0's manifest), but verification requires all of them: a rank killed
#: between save and seal leaves a snapshot no survivor may resume from.
_RANK_STAMP_FMT = "zoo_rank-{}.ok"


def _is_rank_stamp(name: str) -> bool:
    return (name.startswith("zoo_rank-") and name.endswith(".ok")
            and name[len("zoo_rank-"):-len(".ok")].isdigit())


def _dir_checksums(local_dir: str) -> Dict[str, List[int]]:
    """``{relpath: [size, crc32]}`` for every file under ``local_dir``
    except the manifest itself and the per-rank seal stamps. crc32 (not a
    cryptographic hash) on purpose: the threat model is torn writes and
    bit-rot, not an adversary, and restore-time verification must stay
    cheap next to the orbax read it guards."""
    entries: Dict[str, List[int]] = {}
    for root, _dirs, files in os.walk(local_dir):
        for name in sorted(files):
            if name == _MANIFEST_NAME or _is_rank_stamp(name):
                continue
            p = os.path.join(root, name)
            rel = os.path.relpath(p, local_dir).replace(os.sep, "/")
            crc, size = 0, 0
            with open(p, "rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    crc = zlib.crc32(chunk, crc)
                    size += len(chunk)
            entries[rel] = [size, crc]
    return entries


def _write_manifest(local_dir: str, ranks: Optional[int] = None) -> None:
    manifest: Dict[str, Any] = {"version": 1,
                                "files": _dir_checksums(local_dir)}
    if ranks:
        # seal which ranks must have stamped this snapshot: restore
        # refuses it until every one of zoo_rank-0..N-1.ok exists
        manifest["ranks"] = int(ranks)
    with open(os.path.join(local_dir, _MANIFEST_NAME), "w") as f:
        json.dump(manifest, f)


def _verify_manifest(local_dir: str, origin: str) -> bool:
    """Verify ``local_dir`` against its checksum manifest. Returns False
    for pre-manifest snapshots (nothing to verify — legacy tolerance);
    raises :class:`CheckpointCorruptError` on any size/checksum mismatch,
    missing file, unexpected extra file, or (for pod snapshots) a missing
    per-rank seal stamp."""
    mpath = os.path.join(local_dir, _MANIFEST_NAME)
    if not os.path.exists(mpath):
        return False
    t0 = time.perf_counter()
    with open(mpath) as f:
        manifest = json.load(f)
    want = {k: tuple(v) for k, v in manifest.get("files", {}).items()}
    have = {k: tuple(v) for k, v in _dir_checksums(local_dir).items()}
    _M_CKPT_VERIFY.observe(time.perf_counter() - t0)
    if want != have:
        missing = sorted(set(want) - set(have))
        extra = sorted(set(have) - set(want))
        corrupt = sorted(k for k in set(want) & set(have)
                         if want[k] != have[k])
        raise CheckpointCorruptError(
            f"checkpoint at {origin} failed checksum verification — torn "
            f"or corrupt snapshot (missing={missing[:4]}, "
            f"corrupt={corrupt[:4]}, unexpected={extra[:4]})")
    ranks = int(manifest.get("ranks") or 0)
    if ranks:
        unsealed = [i for i in range(ranks) if not os.path.exists(
            os.path.join(local_dir, _RANK_STAMP_FMT.format(i)))]
        if unsealed:
            raise CheckpointCorruptError(
                f"checkpoint at {origin} was written by a {ranks}-process "
                f"pod but ranks {unsealed[:8]} never sealed it (killed "
                f"between the collective save and the stamp) — refusing "
                f"the partial snapshot")
    return True


class _AsyncSnapshotWriter:
    """One-in-flight background checkpoint writer with an explicit fence.

    The TPU-first snapshot split: the device→host copy happens synchronously
    at trigger time (cheap — HBM→RAM), the serialize+write happens on this
    thread so the train loop never stalls on storage. ``wait()`` is the
    fence: called before the next snapshot is submitted, before any restore,
    and at train end; a failed background write surfaces there."""

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError("background checkpoint write failed") from err

    def submit(self, fn) -> None:
        self.wait()  # fence: at most one write in flight

        def run():
            try:
                fn()
            except BaseException as e:  # surfaced at the next fence
                self._error = e

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="zoo-ckpt-writer")
        self._thread.start()

    @property
    def in_flight(self) -> bool:
        return self._thread is not None and self._thread.is_alive()


def _flat_losses(vals):
    """Flatten a drain of per-dispatch losses: scalars (single-step) and
    [k] arrays (multi-step dispatch) both become per-step floats."""
    out: List[float] = []
    for leaf in vals:
        out.extend(float(v) for v in np.atleast_1d(np.asarray(leaf)))
    return out


def _drain_sum_pairs(pending):
    """Drain a pass worth of per-batch ``(sum, weight)`` device scalar
    pairs: ONE ``device_get`` for the whole list, then the same f64 host
    accumulation the synchronous loop performed per batch — bit-identical
    totals, one sync instead of 2·n."""
    host = jax.device_get(pending)
    total, weight = 0.0, 0.0
    for s, w in host:
        total += float(s)
        weight += float(w)
    return total, weight


def _drain_weighted_losses(pending):
    """Drain per-batch ``(loss_device_scalar, weight_int)`` pairs: ONE
    ``device_get`` over the loss scalars, then f64 ``loss * weight`` host
    accumulation (the record-weighted contract sync_eval defines)."""
    host = jax.device_get([loss for loss, _ in pending])
    total, weight = 0.0, 0
    for loss, (_, w) in zip(host, pending):
        total += float(loss) * w
        weight += w
    return total, weight


def _group_host_batches(it, first_epoch_remaining, per_epoch, k):
    """Stack up to ``k`` host batches into one step-stacked ``[g, B, ...]``
    group for the multi-step dispatch path. Groups never span an epoch
    boundary (the tail group is smaller), so epoch accounting and per-epoch
    reshuffles stay exact."""
    remaining = int(first_epoch_remaining)
    while True:
        if remaining <= 0:
            remaining = per_epoch
        g = min(k, remaining)
        batches = []
        for _ in range(g):
            try:
                batches.append(next(it))
            except StopIteration:
                # finite duck-typed iterator exhausted mid-group (the train
                # iterator contract is endless, but the g=1 path tolerates
                # finite ones — so must this): flush what we have
                break
        if not batches:
            return
        yield jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
        if len(batches) < g:
            return
        remaining -= g

def _prepare_dataset(dataset, local_batch: int) -> None:
    """Duck-typed warm-up hook: lazy/mp data planes fork their worker
    pools, map shared-memory slabs and create replay caches here — one-time
    setup that must not land inside the overlapped dispatch loop."""
    prepare = getattr(dataset, "prepare", None)
    if prepare is not None:
        prepare(local_batch)


logger = logging.getLogger("analytics_zoo_tpu")


class Estimator:
    def __init__(self, model, loss_fn: Callable, optimizer: Optimizer,
                 metrics: Optional[Sequence] = None,
                 mesh=None, param_sharding_rules: Optional[Sequence] = None,
                 direct_loss_fn: Optional[Callable] = None,
                 direct_eval_loss_fn: Optional[Callable] = None,
                 direct_eval_per_example_fn: Optional[Callable] = None,
                 compute_dtype=None,
                 seed: int = 42):
        """``direct_loss_fn(params, model_state, rng, x, y) -> (loss,
        new_state)`` bypasses the model.call→loss_fn(y, y_pred) convention —
        the capture-style API hook (≙ TFOptimizer.from_loss, where the user
        hands over the whole loss graph instead of a model).
        ``direct_eval_loss_fn`` is the eval-mode variant (no dropout etc.);
        defaults to ``direct_loss_fn``.

        ``direct_eval_per_example_fn(params, model_state, rng, x, y) ->
        [batch] per-record losses`` makes padded-tail evaluation EXACT:
        pad rows are masked out of the sum before the global weighting, so
        multi-process eval equals the single-process result bit-for-bit in
        expectation (without it, the batch-mean form leaves an
        O(pad/batch) bias on tail batches, documented below).

        ``compute_dtype`` (e.g. ``jnp.bfloat16``) enables mixed precision:
        float inputs are cast to it before the forward pass (layers follow
        activation dtype, so matmuls hit the MXU in bf16) while params, the
        optimizer state, and the loss stay float32 — the standard TPU
        mixed-precision policy."""
        self.model = model
        self.loss_fn = loss_fn
        self.direct_loss_fn = direct_loss_fn
        self.direct_eval_loss_fn = direct_eval_loss_fn or direct_loss_fn
        self.direct_eval_per_example_fn = direct_eval_per_example_fn
        self.optimizer = optimizer
        self.metrics = [metrics_mod.get(m) for m in (metrics or [])]
        self.compute_dtype = compute_dtype
        self.ctx = get_context()
        self.mesh = mesh if mesh is not None else self.ctx.mesh
        self.param_rules = param_sharding_rules
        # vocab-sharded embedding layers built outside a mesh context must
        # shard against THIS estimator's mesh (parallel/embedding.py)
        _embed_engine.set_default_mesh(self.mesh)
        rng_impl = global_config().get("rng.impl") or None
        if rng_impl:
            # "rbg"/"unsafe_rbg" use the TPU's hardware RNG for bit
            # generation — dropout-heavy training (BERT: ~600M draws/step)
            # pays double-digit ms/step for threefry's ALU chain; rbg is
            # deterministic per seed but its streams differ from threefry's
            self.root_rng = jax.random.key(seed, impl=rng_impl)
        else:
            self.root_rng = jax.random.PRNGKey(seed)

        self.params = None
        self.opt_state = None
        self.model_state: Any = {}
        self.global_step = 0
        self.epoch = 1

        self._train_step = None
        self._multi_step = None
        #: whether the current step fn has been dispatched once, i.e. has
        #: traced and compiled: failures before that are never retried
        self._step_proven = False
        self._eval_step = None
        self._predict_step = None
        self._direct_eval_step = None
        self._direct_pe_step = None
        self._clip: Optional[Tuple[str, Any]] = None
        self._tb: Optional[Tuple[str, str]] = None
        self._ckpt_dir: Optional[str] = None
        self._ckpt_trigger: Optional[Trigger] = None
        self._ckpt_writer = _AsyncSnapshotWriter()
        self._train_writer: Optional[SummaryWriter] = None
        self._val_writer: Optional[SummaryWriter] = None
        self._preempt_requested = False
        #: per-traced-step (exchange, grad) byte totals of the sharded
        #: embedding path; None until the first dispatch of a fresh step fn
        self._embed_step_bytes: Optional[Tuple[int, int]] = None
        #: high-water mark of MoE drop counts already drained into the
        #: parallel.moe_dropped_tokens_total counter (the __moe_dropped__
        #: state contract accumulates a RUNNING total on device)
        self._moe_drops_seen = 0

    def _drain_moe_drops(self) -> None:
        """Publish MoE capacity-drop counts at the per-epoch sync point.

        MoE layers accumulate a running dropped-token count in model state
        under the ``MOE_DROP_KEY`` contract (keras/engine.py); this drains
        the delta since the last epoch into the
        ``parallel.moe_dropped_tokens_total`` counter. Runs next to the
        loss drain — already a sanctioned host sync — so capacity-factor
        dropping is never silent yet never adds a per-step sync."""
        from ..keras.engine import MOE_DROP_KEY
        from ..parallel.moe import drain_drop_counter
        flat = jax.tree_util.tree_flatten_with_path(self.model_state)[0]
        total = 0
        for path, leaf in flat:
            if path and str(getattr(path[-1], "key", "")) == MOE_DROP_KEY:
                total += int(jax.device_get(leaf))
        if total:
            self._moe_drops_seen = drain_drop_counter(
                total, self._moe_drops_seen)

    # -- configuration (reference KerasNet setters, Topology.scala:111-127) ---

    def set_gradient_clipping(self, clip: Tuple[str, Any]) -> None:
        self._clip = clip
        self._train_step = None  # rebuild
        self._multi_step = None

    def set_tensorboard(self, log_dir: str, app_name: str) -> None:
        self._tb = (log_dir, app_name)

    def set_checkpoint(self, path: str, trigger: Optional[Trigger] = None) -> None:
        self._ckpt_dir = path
        self._ckpt_trigger = trigger or EveryEpoch()

    # -- initialization -------------------------------------------------------

    def _model_layers(self) -> List:
        m = self.model
        if hasattr(m, "flattened_layers"):
            return m.flattened_layers()
        return list(getattr(m, "layers", None) or [m])

    def _sharded_table_specs(self) -> Dict[Tuple[str, str], Any]:
        """``{(layer_name, param_key): ShardSpec}`` over every vocab-sharded
        embedding table in the model. Deterministic PRE-BUILD (layers compute
        their spec on demand), so checkpoint restore can rebuild the split
        optimizer-state structure before the first trace."""
        out: Dict[Tuple[str, str], Any] = {}
        for layer in self._model_layers():
            tables = getattr(layer, "sharded_tables", None)
            if tables is None:
                continue
            for key, spec in tables().items():
                out[(layer.name, key)] = spec
        return out

    def _embed_plan(self) -> Dict[Tuple[str, str], Any]:
        """Tables the SPARSE row-subset optimizer path owns this build:
        vocab-sharded tables x an optimizer whose math has a sparse
        equivalent. Empty plan == exactly the historical dense behavior."""
        if (self.optimizer is None
                or getattr(self.optimizer, "sparse_rows", None) is None
                or self.direct_loss_fn is not None
                or not global_config().get("embed.sparse_updates")):
            return {}
        return self._sharded_table_specs()

    def _maybe_add_vocab_rules(self) -> None:
        """Idempotently append the GSPMD vocab-sharding rule for the
        model's sharded tables to ``param_rules`` (params, frozen-table
        model state and row-wise optimizer state all ride the same rule)."""
        _embed_engine.set_default_mesh(self.mesh)
        tables = {k: spec.axis
                  for k, spec in self._sharded_table_specs().items()}
        if not tables or getattr(self, "_vocab_rule_tables", None) == tables:
            return
        rule = vocab_sharding_rule(tables)
        rule._is_vocab_rule = True
        base = [r for r in (self.param_rules or [])
                if not getattr(r, "_is_vocab_rule", False)]
        self.param_rules = base + [rule]
        self._vocab_rule_tables = tables

    def _opt_rules(self) -> Optional[List]:
        """Sharding rules for the optimizer state tree (row-wise embed
        state shards with its table; everything else stays replicated)."""
        tables = {k: spec.axis
                  for k, spec in self._sharded_table_specs().items()}
        return [vocab_sharding_rule(tables)] if tables else None

    def _init_opt_state(self, params):
        """Optimizer-state init honoring the sparse-embedding plan: plan
        tables get row-wise state under ``opt["embed"]`` (read/written only
        for touched rows each step) and are STRIPPED from the dense optax
        state; an empty plan returns the plain optax init unchanged."""
        plan = self._embed_plan()
        plan = {k: v for k, v in plan.items()
                if k[0] in params and k[1] in params[k[0]]}
        if not plan:
            return self.optimizer.init(params)
        kind, _hyper = self.optimizer.sparse_rows
        stripped = {ln: {k: v for k, v in sub.items()
                         if (ln, k) not in plan}
                    for ln, sub in params.items()}
        stripped = {ln: sub for ln, sub in stripped.items() if sub}
        embed: Dict[str, Dict[str, Any]] = {}
        for ln, key in sorted(plan):
            embed.setdefault(ln, {})[key] = _embed_engine.init_row_state(
                kind, params[ln][key])
        return {"dense": self.optimizer.init(stripped), "embed": embed}

    def _ensure_initialized(self, sample_x) -> None:
        # "state resolved" distinguishes a genuinely-stateless model (state
        # legitimately {}) from state that simply hasn't been built yet — an
        # empty dict alone can't express that, and skipping the build for a
        # BatchNorm model means KeyError at call time
        state_resolved = (getattr(self, "_state_resolved", False)
                          or bool(self.model_state))
        if self.params is not None and state_resolved and (
                self.opt_state is not None or self.optimizer is None):
            return
        self._maybe_add_vocab_rules()
        from ..keras.engine import init_model
        self.root_rng, init_rng = jax.random.split(self.root_rng)
        if self.params is None:
            params, state = init_model(self.model, init_rng, sample_x)
            sharding = param_sharding(self.mesh, params, self.param_rules)
            self.params = jax.device_put(params, sharding)
            if not self.model_state:
                self.model_state = jax.device_put(
                    state, param_sharding(self.mesh, state, self.param_rules))
            self._state_resolved = True
        elif not state_resolved:
            # params were imported (set_params); build only fresh model state
            # — under jit XLA dead-code-eliminates the (discarded) param init
            state = jax.jit(
                lambda r: init_model(self.model, r, sample_x)[1])(init_rng)
            if jax.tree_util.tree_leaves(state):
                self.model_state = jax.device_put(
                    state, param_sharding(self.mesh, state, self.param_rules))
            else:
                self.model_state = {}
            self._state_resolved = True
        if self.opt_state is None and self.optimizer is not None:
            opt = self._init_opt_state(self.params)
            self.opt_state = jax.device_put(
                opt, param_sharding(self.mesh, opt, self._opt_rules()))

    def _clip_transform(self):
        if self._clip is None:
            return None
        kind, val = self._clip
        if kind == "l2":
            return optax.clip_by_global_norm(val)
        lo, hi = val
        if abs(lo) != abs(hi):
            # optax.clip is symmetric; emulate asymmetric constant clip
            return optax.stateless(
                lambda g, p: jax.tree_util.tree_map(
                    lambda t: jnp.clip(t, lo, hi), g))
        return optax.clip(hi)

    # -- compiled steps -------------------------------------------------------

    def _cast_inputs(self, x):
        """Mixed precision: float inputs -> compute_dtype (ints untouched)."""
        if self.compute_dtype is None:
            return x
        dtype = self.compute_dtype
        return jax.tree_util.tree_map(
            lambda t: t.astype(dtype)
            if jnp.issubdtype(jnp.asarray(t).dtype, jnp.floating) else t, x)

    def _build_train_step(self):
        model, loss_fn, optimizer = self.model, self.loss_fn, self.optimizer
        direct = self.direct_loss_fn
        clip = self._clip_transform()
        cast = self._cast_inputs
        plan = self._embed_plan()
        sparse = getattr(optimizer, "sparse_rows", None) if plan else None

        # transfer learning: frozen layers get stop_gradient (XLA then
        # dead-code-eliminates their backward pass) and zeroed updates (so
        # weight-decay terms can't drift them either)
        frozen = frozenset(getattr(model, "frozen_layers", ()) or ())

        from ..keras.engine import AUX_LOSS_KEY

        def fold_aux(loss, new_state):
            # the AUX_LOSS_KEY state contract: layers (MoE router balance,
            # activation regularizers...) publish scalar penalties in their
            # state; they join the objective here — on BOTH the model.call
            # and the direct-loss (capture) paths
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    new_state)[0]:
                if path and str(getattr(path[-1], "key", "")) == AUX_LOSS_KEY:
                    loss = loss + leaf
            return loss

        def train_step(params, opt_state, model_state, rng, x, y):
            def compute_loss(p):
                if frozen:
                    p = {k: jax.lax.stop_gradient(v) if k in frozen else v
                         for k, v in p.items()}
                if direct is not None:
                    loss, new_state = direct(p, model_state, rng, x, y)
                    return fold_aux(loss, new_state), new_state
                y_pred, new_state = model.call(p, model_state, cast(x),
                                               training=True, rng=rng)
                # loss in float32 regardless of activation dtype
                with jax.named_scope("loss"):
                    y_pred = jax.tree_util.tree_map(
                        lambda t: t.astype(jnp.float32), y_pred)
                    return fold_aux(loss_fn(y, y_pred), new_state), new_state

            # scope names on the device (docs/observability.md): the model
            # names its own parts and JAX wraps them in jvp(...) /
            # transpose(jvp(...)), which tells forward from backward. No
            # scope goes around this call: it would come between
            # ``transpose`` and the kernels' own scope, and XLA would name
            # the backward attention kernel like the forward one. The
            # gradient's reduction over the data axis is XLA's, not a call
            # of this program: it shows as all-reduce operations.
            (loss, new_state), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(params)
            # sharded embedding layers stash their forward exchange blob in
            # the state tree; it must come OUT of the carried state (scan
            # carry structure) whether or not the sparse update consumes it
            rows_map, new_state = _embed_engine.pop_stashed_rows(new_state)
            if clip is not None:
                with jax.named_scope("optimizer"):
                    grads, _ = clip.update(grads, clip.init(params), params)
            if not plan:
                with jax.named_scope("optimizer"):
                    updates, opt_state = optimizer.update(grads, opt_state,
                                                          params)
                    if frozen:
                        updates = {
                            k: jax.tree_util.tree_map(jnp.zeros_like, u)
                            if k in frozen else u
                            for k, u in updates.items()}
                    params = optax.apply_updates(params, updates)
                return params, opt_state, new_state, loss

            # sparse path: dense optax over the non-plan leaves, row-subset
            # updates over the sharded tables (untouched rows' optimizer
            # state is neither read nor written)
            kind, hyper = sparse
            dense_params = {ln: {k: v for k, v in sub.items()
                                 if (ln, k) not in plan}
                            for ln, sub in params.items()}
            dense_params = {ln: sub for ln, sub in dense_params.items() if sub}
            dense_grads = {ln: {k: g for k, g in sub.items()
                                if (ln, k) not in plan}
                           for ln, sub in grads.items()}
            dense_grads = {ln: sub for ln, sub in dense_grads.items() if sub}
            with jax.named_scope("optimizer"):
                updates, dense_opt = optimizer.update(
                    dense_grads, opt_state["dense"], dense_params)
                if frozen:
                    updates = {k: jax.tree_util.tree_map(jnp.zeros_like, u)
                               if k in frozen else u
                               for k, u in updates.items()}
                new_dense = optax.apply_updates(dense_params, updates)
            out_params = {ln: dict(sub) for ln, sub in params.items()}
            for ln, sub in new_dense.items():
                for k, v in sub.items():
                    out_params[ln][k] = v
            embed_opt = {ln: dict(sub)
                         for ln, sub in opt_state["embed"].items()}
            for ln, key in sorted(plan):
                spec = plan[(ln, key)]
                table, g = params[ln][key], grads[ln][key]
                rstate = opt_state["embed"][ln][key]
                blob = rows_map.get(ln, {}).get(key)
                if ln in frozen:
                    new_table, new_rstate = table, rstate
                elif blob is not None:
                    with jax.named_scope("optimizer"):
                        new_table, new_rstate = \
                            _embed_engine.apply_row_update(
                                kind, hyper, spec, table, g, blob, rstate)
                else:
                    # lookup fell back to the dense gather this step (id
                    # count not divisible over the shards): same optimizer
                    # arithmetic applied to the whole (sharded) table
                    with jax.named_scope("optimizer"):
                        new_table, new_rstate = \
                            _embed_engine.apply_dense_update(
                                kind, hyper, table, g, rstate)
                out_params[ln][key] = new_table
                embed_opt[ln][key] = new_rstate
            return (out_params, {"dense": dense_opt, "embed": embed_opt},
                    new_state, loss)

        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    def _build_multi_step(self):
        """K train steps in ONE dispatch: ``lax.scan`` over a step-stacked
        batch ``[k, B, ...]``. Amortizes per-dispatch host/RPC latency — the
        TPU-first answer to the reference's twice-per-step Spark job launch
        (SURVEY §5: "the loop lives on-device, the host only feeds data");
        essential on remote-attached chips, a win everywhere. Losses come
        back per step; triggers quantize to the group boundary."""
        step = self._train_step  # jitted; inlines under the outer jit

        def multi(params, opt_state, mstate, root_rng, step0, xs, ys):
            def body(carry, inp):
                p, o, m, i = carry
                x, y = inp
                rng = jax.random.fold_in(root_rng, i)
                p, o, m, loss = step(p, o, m, rng, x, y)
                return (p, o, m, i + 1), loss

            (p, o, m, _), losses = jax.lax.scan(
                body, (params, opt_state, mstate,
                       jnp.asarray(step0, jnp.int32)), (xs, ys))
            return p, o, m, losses

        return jax.jit(multi, donate_argnums=(0, 1, 2))

    def _build_eval_step(self):
        model, metrics = self.model, self.metrics

        cast = self._cast_inputs

        def eval_step(params, model_state, metric_states, x, y, mask):
            y_pred, _ = model.call(params, model_state, cast(x), training=False)
            y_pred = jax.tree_util.tree_map(
                lambda t: t.astype(jnp.float32), y_pred)
            return [m.update(s, y, y_pred, mask)
                    for m, s in zip(metrics, metric_states)]

        return jax.jit(eval_step, donate_argnums=(2,))

    def _wire_step_cost(self, group, x, y):
        """One-time per compiled step fn: install the XLA cost model
        (FLOPs + HBM bytes per dispatch) behind the train loop's MFU and
        roofline gauges. ``lower()`` retraces abstractly — no execution,
        no recompile — and any failure just leaves the gauges unset."""
        try:
            if group > 1:
                lowered = self._multi_step.lower(
                    self.params, self.opt_state, self.model_state,
                    self.root_rng, np.int32(self.global_step), x, y)
            else:
                step_rng = jax.random.fold_in(self.root_rng,
                                              self.global_step)
                lowered = self._train_step.lower(
                    self.params, self.opt_state, self.model_state,
                    step_rng, x, y)
            _P_TRAIN.set_cost(_profiler.cost_flops(lowered),
                              _profiler.cost_bytes(lowered))
        except Exception:
            pass

    def _build_predict_step(self):
        model = self.model

        cast = self._cast_inputs

        def predict_step(params, model_state, x):
            y_pred, _ = model.call(params, model_state, cast(x), training=False)
            return jax.tree_util.tree_map(
                lambda t: t.astype(jnp.float32), y_pred)

        if self.ctx.process_count > 1:
            # multi-host: every host must be able to fetch the predictions
            # (np.asarray on a batch-sharded output would span
            # non-addressable devices) — replicate outputs; XLA inserts the
            # all-gather over the batch axis
            from jax.sharding import NamedSharding, PartitionSpec
            return jax.jit(predict_step, out_shardings=NamedSharding(
                self.mesh, PartitionSpec()))
        return jax.jit(predict_step)

    # -- train (the InternalDistriOptimizer.train equivalent) -----------------

    @_traced_for_own_mesh
    def train(self, train_set: FeatureSet, batch_size: int,
              epochs: Optional[int] = None,
              end_trigger: Optional[Trigger] = None,
              validation_set: Optional[FeatureSet] = None,
              validation_trigger: Optional[Trigger] = None,
              checkpoint_trigger: Optional[Trigger] = None,
              steps_per_dispatch: int = 1) -> Dict[str, Any]:
        """Train with preemption protection: a SIGTERM during this call
        (the TPU preemption notice — seconds of warning) stops at the next
        step boundary, fences the async checkpoint writer, writes a final
        snapshot plus a ``PREEMPTED.json`` resumable marker, and raises
        :class:`PreemptedError`. A leftover marker from a previous
        preempted run is consumed (removed) here — resuming is
        ``load_checkpoint(latest)`` + ``train()`` as usual. See
        :meth:`_train_impl` for the loop semantics."""
        self._preempt_requested = False
        restore_handler = self._install_preemption_handler()
        try:
            if self._ckpt_dir:
                marker = file_io.join(self._ckpt_dir, PREEMPT_MARKER)
                if file_io.exists(marker):
                    file_io.remove(marker)
            return self._train_impl(
                train_set, batch_size, epochs=epochs,
                end_trigger=end_trigger, validation_set=validation_set,
                validation_trigger=validation_trigger,
                checkpoint_trigger=checkpoint_trigger,
                steps_per_dispatch=steps_per_dispatch)
        finally:
            restore_handler()

    @_traced_for_own_mesh
    def train_online(self, train_set: FeatureSet, batch_size: int,
                     max_steps: Optional[int] = None,
                     end_trigger: Optional[Trigger] = None,
                     snapshot_interval_s: Optional[float] = None,
                     validation_set: Optional[FeatureSet] = None,
                     validation_trigger: Optional[Trigger] = None,
                     steps_per_dispatch: int = 1) -> Dict[str, Any]:
        """Continual training off a stream: unbounded by default (runs
        until SIGTERM preemption or ``max_steps``/``end_trigger``), with
        snapshots paced by wall time (``snapshot_interval_s``, default
        config ``online.snapshot_interval_s``) instead of epoch
        boundaries — an unbounded stream has none worth waiting for.

        This is :meth:`train` with online-shaped triggers; everything
        else — resumable ``data_state`` capture, async checksummed
        snapshots, elastic retry, preemption protection — is the same
        loop.  Pair with a :class:`~analytics_zoo_tpu.online.stream.
        QueueFeatureSet` (``FeatureSet.from_queue``) for exact resume:
        its journal cursor rides in every snapshot's data_state.  Sparse
        embedding updates (``sparse_rows``) make the per-step cost scale
        with rows *touched* by the stream, not table size — see
        docs/online.md."""
        from ..common.triggers import MaxIteration, Never, TimeInterval
        if snapshot_interval_s is None:
            snapshot_interval_s = float(
                global_config().get("online.snapshot_interval_s"))
        if end_trigger is None:
            end_trigger = (MaxIteration(int(max_steps))
                           if max_steps is not None else Never())
        checkpoint_trigger = (TimeInterval(snapshot_interval_s)
                              if self._ckpt_dir else None)
        return self.train(
            train_set, batch_size, end_trigger=end_trigger,
            validation_set=validation_set,
            validation_trigger=validation_trigger,
            checkpoint_trigger=checkpoint_trigger,
            steps_per_dispatch=steps_per_dispatch)

    def _install_preemption_handler(self):
        """Install the SIGTERM→preempt-flag handler for the duration of a
        train() call; returns the undo callable. Signals only land on the
        main thread — a train() driven from a worker thread (pod tests,
        notebooks) keeps whatever handler the host process installed."""
        if threading.current_thread() is not threading.main_thread():
            return lambda: None
        try:
            prev = signal.signal(signal.SIGTERM, self._on_sigterm)
        except ValueError:  # embedded interpreters without signal support
            return lambda: None
        return lambda: signal.signal(signal.SIGTERM, prev)

    def _on_sigterm(self, signum, frame) -> None:
        logger.warning(
            "SIGTERM: preemption requested — will write a final snapshot "
            "and a resumable marker at the next step boundary")
        self._preempt_requested = True

    @staticmethod
    def preemption_marker(ckpt_dir: str) -> Optional[Dict[str, Any]]:
        """Read a checkpoint dir's resumable-preemption marker (``None``
        when the last run was not preempted)."""
        path = file_io.join(ckpt_dir, PREEMPT_MARKER)
        if not file_io.exists(path):
            return None
        with file_io.fopen(path) as f:
            return json.load(f)

    def _finalize_preemption(self, history: List[float],
                             pending: List[Any]) -> None:
        """The preempt flag is set and the step loop has stopped: drain
        what the device still owes, fence the writer, publish a final
        snapshot + marker, and surface :class:`PreemptedError`."""
        try:
            history.extend(_flat_losses(jax.device_get(pending)))
        except Exception:
            logger.exception(
                "async step failure surfaced while draining losses during "
                "preemption; the final snapshot still reflects the last "
                "good params")
        pending.clear()
        snap = None
        if self._ckpt_dir:
            try:
                self._ckpt_writer.wait()
            except RuntimeError:
                logger.exception(
                    "background checkpoint write had failed; the "
                    "preemption snapshot below replaces it")
            snap = file_io.join(self._ckpt_dir,
                                f"snapshot-{self.global_step}")
            self._write_snapshot(snap, self._snapshot_tree())
            with file_io.fopen(file_io.join(self._ckpt_dir, PREEMPT_MARKER),
                               "w") as f:
                json.dump({"global_step": self.global_step,
                           "epoch": self.epoch,
                           "snapshot": f"snapshot-{self.global_step}",
                           "resumable": True}, f)
        if self._train_writer is not None:
            self._train_writer.flush()
            self._val_writer.flush()
        raise PreemptedError(
            f"training preempted (SIGTERM) at step {self.global_step}"
            + (f"; resume from {snap}" if snap
               else "; no checkpoint dir configured — progress lost"),
            snapshot=snap)

    def _train_impl(self, train_set: FeatureSet, batch_size: int,
                    epochs: Optional[int] = None,
                    end_trigger: Optional[Trigger] = None,
                    validation_set: Optional[FeatureSet] = None,
                    validation_trigger: Optional[Trigger] = None,
                    checkpoint_trigger: Optional[Trigger] = None,
                    steps_per_dispatch: int = 1) -> Dict[str, Any]:
        """``steps_per_dispatch > 1`` runs K train steps per device dispatch
        (host stacks K batches, the device scans over them): trigger checks,
        per-step TB scalars and loss syncs then happen every K steps —
        interval triggers (``SeveralIteration``) fire whenever a boundary is
        crossed inside the K-step group (quantized to the group boundary,
        never skipped) — and ``MaxIteration`` end triggers may overshoot by
        up to K-1 steps. Groups never span an epoch boundary."""
        cfg = global_config()
        if end_trigger is None:
            end_trigger = MaxEpoch(epochs if epochs is not None else 1)
        validation_trigger = validation_trigger or EveryEpoch()
        checkpoint_trigger = checkpoint_trigger or self._ckpt_trigger or EveryEpoch()
        local_batch = self.ctx.local_batch(batch_size)
        # the batch axis is sharded over the mesh's data axis only; this host
        # contributes its per-host share of that axis
        from ..parallel.mesh import DATA_AXIS
        dp_size = (self.mesh.shape[DATA_AXIS]
                   if DATA_AXIS in self.mesh.axis_names else 1)
        local_dp = max(1, dp_size // self.ctx.process_count)
        if local_batch % local_dp:
            good = self.ctx.process_count * local_dp * max(1, local_batch // local_dp)
            raise ValueError(
                f"per-host batch {local_batch} must be divisible by this "
                f"host's {local_dp} data-axis devices; use batch_size={good}")

        _prepare_dataset(train_set, local_batch)
        sample = next(train_set.train_iterator(local_batch))
        self._ensure_initialized(sample[0])
        # freeze()/unfreeze() may have changed since the step was compiled —
        # the frozen set is baked into the jitted program, so compare rather
        # than rely on the model holding a reference back to this estimator
        frozen_now = frozenset(getattr(self.model, "frozen_layers", ()) or ())
        if self._train_step is None or frozen_now != getattr(
                self, "_frozen_at_build", frozenset()):
            self._frozen_at_build = frozen_now
            self._train_step = self._build_train_step()
            self._multi_step = None  # closes over _train_step
            self._step_proven = False
            # first dispatch of a fresh step fn is compile-dominated: the
            # profiler books it as phase=compile, not dispatch
            self._prof_fresh_dispatch = True
            self._prof_cost_done = False
            # the sharded-embedding engine counts its exchange bytes at
            # trace time; a fresh step fn re-traces, so re-attribute
            self._embed_step_bytes = None
            _embed_engine.reset_trace_bytes()
        if self._tb and self._train_writer is None:
            log_dir, app = self._tb
            self._train_writer = SummaryWriter(os.path.join(log_dir, app, "train"))
            self._val_writer = SummaryWriter(os.path.join(log_dir, app, "validation"))

        batches_per_epoch = train_set.num_batches(local_batch)
        slice_bounds = train_set.slice_boundaries(local_batch)
        state = TrainingState(epoch=self.epoch, iteration=self.global_step,
                              num_slices=train_set.num_slices)

        retry_budget = int(cfg.get("failure.retry_times"))
        retry_window = float(cfg.get("failure.retry_interval_s"))
        retries_left = retry_budget
        last_failure = float("-inf")  # monotonic domain: no epoch-0 anchor
        history: List[float] = []
        pending: List[Any] = []  # device loss scalars, drained per epoch
        # only sync loss to host per-step when something consumes it; otherwise
        # jax's async dispatch pipelines the whole epoch without host stalls
        # (duck-typed callables without requires_loss are treated as consumers)
        need_loss = (self._tb is not None
                     or getattr(end_trigger, "requires_loss", True)
                     or getattr(validation_trigger, "requires_loss", True)
                     or getattr(checkpoint_trigger, "requires_loss", True))

        # the data pipeline is part of the checkpoint: expose enough state for
        # _snapshot_tree to record "which permutation, how far in"
        self._active_train_set = train_set
        self._batches_per_epoch = batches_per_epoch
        self._local_batch = local_batch

        while not end_trigger(state):
            skip = 0
            resumable = hasattr(train_set, "data_state")
            if getattr(self, "_restore_data", None) is not None and resumable:
                rng_json, skip, saved_batch = self._restore_data
                self._restore_data = None
                train_set.set_data_state(rng_json)
                if skip and saved_batch and saved_batch != local_batch:
                    raise ValueError(
                        f"resuming a mid-epoch snapshot taken with per-host "
                        f"batch {saved_batch} using batch {local_batch} would "
                        f"replay the wrong records; resume with the original "
                        f"batch size (or from an epoch-boundary snapshot)")
                skip = min(skip, batches_per_epoch)
            self._epoch_data_state = (train_set.data_state() if resumable
                                      else None)
            group = max(1, int(steps_per_dispatch))
            host_it = train_set.train_iterator(local_batch, skip_batches=skip)
            if group > 1:
                if self._multi_step is None:
                    self._multi_step = self._build_multi_step()
                    self._step_proven = False
                    self._prof_fresh_dispatch = True
                    self._prof_cost_done = False
                    self._embed_step_bytes = None
                    _embed_engine.reset_trace_bytes()
                host_it = _group_host_batches(
                    host_it, batches_per_epoch - skip, batches_per_epoch,
                    group)
                feed = DeviceFeed(
                    host_it, self.mesh,
                    shard_fn=lambda m, b: shard_batch(m, b, batch_axis=1))
            else:
                feed = DeviceFeed(host_it, self.mesh)
            epoch_iter = skip
            self._epoch_offset = epoch_iter
            building = False
            prof = _profiler.enabled()
            step_source = (_profiled_feed(feed, _P_TRAIN) if prof
                           else iter(feed))
            try:
                for x, y in step_source:
                    # chaos site: a firing injection models a chip
                    # failure at step dispatch — caught by the elastic
                    # retry below exactly like a real one
                    faults.inject("train.step")
                    step_start = time.perf_counter()
                    # the first dispatch of a fresh step fn traces and
                    # compiles it: a failure there is a fault of the
                    # program, which no checkpoint cures
                    building = not self._step_proven
                    if group > 1:
                        g = jax.tree_util.tree_leaves(x)[0].shape[0]
                        with time_it("train_step"):
                            (self.params, self.opt_state, self.model_state,
                             losses) = self._multi_step(
                                self.params, self.opt_state,
                                self.model_state, self.root_rng,
                                np.int32(self.global_step), x, y)
                        loss = losses[-1]
                    else:
                        g = 1
                        step_rng = jax.random.fold_in(self.root_rng,
                                                      self.global_step)
                        with time_it("train_step"):
                            (self.params, self.opt_state, self.model_state,
                             loss) = self._train_step(
                                self.params, self.opt_state, self.model_state,
                                step_rng, x, y)
                        losses = loss
                    building = False
                    self._step_proven = True
                    if prof:
                        now = time.perf_counter()
                        _P_TRAIN.add(
                            "compile" if self._prof_fresh_dispatch
                            else "dispatch", now - step_start,
                            start=step_start)
                        self._prof_fresh_dispatch = False
                        if not self._prof_cost_done:
                            self._prof_cost_done = True
                            self._wire_step_cost(group, x, y)
                        # explicit fence: device compute becomes its own
                        # phase instead of hiding inside the loss sync —
                        # profiling trades the async pipeline for this
                        t_x = time.perf_counter()
                        # zoolint: disable=jit-host-sync — deliberate profiling fence (prof mode trades the async pipeline for phase attribution)
                        jax.block_until_ready(losses)
                        _P_TRAIN.add("execute", time.perf_counter() - t_x,
                                     start=t_x)
                    self.global_step += g
                    epoch_iter += g
                    self._epoch_offset = epoch_iter
                    state.iteration = self.global_step
                    state.dispatch_width = g
                    pending.append(losses)

                    if need_loss:
                        with _P_TRAIN.phase("fetch"):
                            # device sync point
                            # zoolint: disable=jit-host-sync — gated: runs only when a trigger/writer consumes the loss
                            loss_val = float(loss)
                        state.loss = loss_val
                        if self._train_writer is not None:
                            lr = self.optimizer.learning_rate
                            lr_val = (float(lr(self.global_step)) if callable(lr)  # zoolint: disable=jit-host-sync — host-side LR schedule, evaluated behind the gated loss sync
                                      else float(lr))
                            self._train_writer.add_scalar("Loss", loss_val,
                                                          self.global_step)
                            self._train_writer.add_scalar("LearningRate", lr_val,
                                                          self.global_step)
                            # per-iteration Throughput (reference
                            # Topology.scala:218-224): timed over dispatch +
                            # the loss sync just above, which bounds this
                            # step's device work — validation/checkpoint time
                            # between steps is deliberately NOT counted
                            step_time = time.perf_counter() - step_start
                            if step_time > 0:
                                global_batch = (local_batch * g
                                                * self.ctx.process_count)
                                self._train_writer.add_scalar(
                                    "Throughput", global_batch / step_time,
                                    self.global_step)

                    # telemetry: one histogram sample per dispatch (the
                    # sync above is inside the window when it ran, so the
                    # recorded time bounds this step's device work) + the
                    # examples throughput counter
                    _M_STEP.observe(time.perf_counter() - step_start)
                    _M_EXAMPLES.inc(local_batch * g)
                    if self._embed_step_bytes is None:
                        # the first dispatch traced the step: the engine's
                        # accumulator now holds ONE step's exchange bytes
                        self._embed_step_bytes = \
                            _embed_engine.take_trace_bytes()
                    ex_b, gr_b = self._embed_step_bytes
                    if ex_b or gr_b:
                        _embed_engine.note_exchange_bytes(ex_b * g, gr_b * g)
                    if prof:
                        _P_TRAIN.step_end()

                    state.epoch_finished = epoch_iter >= batches_per_epoch
                    # boundaries CROSSED by this dispatch (g > 1 can jump
                    # over several sub-epoch slice marks at once)
                    crossed = sum(1 for b in slice_bounds
                                  if epoch_iter - g < b <= epoch_iter)
                    if state.epoch_finished and crossed == 0:
                        crossed = 1
                    state.slice_index += crossed
                    if state.epoch_finished:
                        # drain device losses inside the try: this is the sync
                        # point where async step failures surface so the
                        # checkpoint-retry path below can catch them, and it
                        # bounds the number of live device scalars
                        # zoolint: disable=jit-host-sync — per-EPOCH drain, not per-step: the sanctioned pattern
                        history.extend(_flat_losses(jax.device_get(pending)))
                        pending.clear()
                        self._drain_moe_drops()
                        state.epoch += 1
                        self.epoch = state.epoch

                    if validation_set is not None and validation_trigger(state):
                        results = self.evaluate(validation_set, batch_size)
                        state.score = next(iter(results.values()), None)
                        if self._val_writer is not None:
                            for k, v in results.items():
                                self._val_writer.add_scalar(k, v, self.global_step)
                    if self._ckpt_dir and checkpoint_trigger(state):
                        self._save_snapshot()
                    if faults.inject("train.preempt"):
                        self._preempt_requested = True
                    if (self._preempt_requested or state.epoch_finished
                            or end_trigger(state)):
                        break
                if not state.epoch_finished and not end_trigger(state):
                    # featureset exhausted mid-epoch (shouldn't happen: endless)
                    state.epoch_finished = True
                    state.epoch += 1
            except Exception:
                if building:
                    logger.error(
                        "train step failed to trace or compile; not a "
                        "device failure, so not retried from a checkpoint")
                    raise
                # elasticity: retry from newest checkpoint (Topology.scala:1180-1262)
                now = time.monotonic()
                if now - last_failure > retry_window:
                    retries_left = retry_budget  # sparse failures reset budget
                last_failure = now
                retries_left -= 1
                pending.clear()  # discard losses from the failed dispatch
                try:
                    # drain a failed BACKGROUND write separately: it must not
                    # consume the retry or mask the step failure being
                    # retried (snapshot writes are atomic-publish, so the
                    # newest intact snapshot is still loadable)
                    self._ckpt_writer.wait()
                except RuntimeError:
                    logger.exception(
                        "background checkpoint write had failed; retrying "
                        "from the newest intact snapshot anyway")
                if retries_left < 0 or not self._snapshot_candidates():
                    # budget exhausted (or nothing to restore from):
                    # surface the error — but restore the newest VALID
                    # snapshot first, so the estimator's params are a
                    # known-good state the caller can still save/serve
                    if self._restore_latest_valid() is not None:
                        logger.error(
                            "retry budget exhausted after %d attempts; "
                            "params restored to the newest valid snapshot "
                            "(step %d) before surfacing the failure",
                            retry_budget + 1, self.global_step)
                    raise
                logger.exception(
                    "training step failed; resuming from checkpoint "
                    "(%d retries left)", retries_left)
                # a torn/corrupt NEWEST snapshot must not kill the retry:
                # fall back past checksum-invalid snapshots to the newest
                # valid one
                if self._restore_latest_valid() is None:
                    logger.error(
                        "no restorable snapshot survived validation; "
                        "surfacing the original step failure")
                    raise
                state.epoch = self.epoch
                state.iteration = self.global_step
                continue
            finally:
                # epochs usually end by `break` with the feed still mid-epoch;
                # stop its producer thread and release prefetched device batches
                feed.close()
            if self._preempt_requested:
                self._finalize_preemption(history, pending)
            state.epoch_finished = False

        if pending:
            # trailing drain (end_trigger fired mid-epoch): an async failure
            # here means params are in an undefined state — restore the newest
            # checkpoint so the estimator stays usable, then surface the error
            try:
                history.extend(_flat_losses(jax.device_get(pending)))
            except Exception:
                if self._ckpt_dir and self._snapshot_candidates():
                    logger.exception(
                        "trailing training step failed; restoring newest "
                        "valid checkpoint before surfacing the error")
                    self._restore_latest_valid()
                raise
            finally:
                pending.clear()
        if self._train_writer is not None:
            self._train_writer.flush()
            self._val_writer.flush()
        # train() must not return with a checkpoint still writing (and a
        # failed background write must surface to the caller)
        self._ckpt_writer.wait()
        return {"loss_history": history, "iterations": self.global_step}

    # -- evaluate (Estimator.evaluate / InternalDistriOptimizer eval) ---------

    @_traced_for_own_mesh
    def evaluate(self, val_set: FeatureSet, batch_size: int) -> Dict[str, float]:
        """Pipelined evaluation: host gather/shard for batch N+1 runs on the
        DeviceFeed producer thread while the device computes batch N, and
        metric accumulation stays ON DEVICE (the eval step folds each batch
        into the metric-state carry) — the whole pass syncs to host exactly
        once, in :func:`metrics.compute_all`. ``eval.async = False`` falls
        back to the synchronous per-batch loop (``sync_eval``)."""
        if self.direct_loss_fn is not None and not self.metrics:
            return self._evaluate_direct(val_set, batch_size)
        if not self.metrics:
            self.metrics = [metrics_mod.Loss(self.loss_fn)]
        local_batch = min(self.ctx.local_batch(batch_size), val_set.size)
        ndev = self.mesh.devices.size
        local_batch = max(ndev, (local_batch // ndev) * ndev)
        if not global_config().get("eval.async"):
            from . import sync_eval
            return sync_eval.evaluate_sync(self, val_set, batch_size,
                                           local_batch)
        # ONE iterator pass: streaming sets restart their generator per
        # eval_iterator call, so peeking with a second iterator would decode
        # the first batch twice on every evaluation — the first batch is
        # consumed here for initialization and chained back into the feed
        import itertools
        _prepare_dataset(val_set, local_batch)
        it = val_set.eval_iterator(local_batch, pad_remainder=True)
        try:
            first = next(it)
        except StopIteration:
            raise ValueError("validation set produced no batches") from None
        self._ensure_initialized(first[0])
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        metric_states = [
            jax.device_put(m.init_state(), replicated(self.mesh))
            for m in self.metrics]
        host_it = masked_eval_batches(itertools.chain([first], it),
                                      local_batch)
        prof = _profiler.enabled()
        with DeviceFeed(host_it, self.mesh, shard_fn=shard_payload,
                        profile_loop="eval" if prof else None) as feed:
            for (bx, by, bm), _ in feed:
                t_d = time.perf_counter() if prof else 0.0
                metric_states = self._eval_step(self.params, self.model_state,
                                                metric_states, bx, by, bm)
                if prof:
                    _profiler.record_phase(
                        "eval", "dispatch", time.perf_counter() - t_d,
                        start=t_d)
        if prof:
            # the single host sync of the pass: everything blocked here
            # is the fetch phase
            t_f = time.perf_counter()
            out = metrics_mod.compute_all(self.metrics, metric_states)
            _profiler.record_phase("eval", "fetch",
                                   time.perf_counter() - t_f, start=t_f)
            return out
        return metrics_mod.compute_all(self.metrics, metric_states)

    def _evaluate_direct_exact(self, val_set: FeatureSet, batch_size: int
                               ) -> Dict[str, float]:
        """Per-example masked eval — ZERO tail bias on any process
        topology: pad rows (and whole valid=0 re-fed batches on short
        hosts) contribute nothing, the result is
        sum(valid per-record losses) / global valid count, identical to a
        single-process pass over the concatenated shards. One compile
        shape total (the mask is data)."""
        import math

        pe = self.direct_eval_per_example_fn
        multiproc = self.ctx.process_count > 1
        if not multiproc and val_set.size == 0:
            raise ValueError("validation set is empty (0 records)")
        ndev = self.mesh.devices.size
        local_batch = self.ctx.local_batch(batch_size)
        if not multiproc:
            local_batch = min(local_batch, val_set.size)
        local_batch = max(ndev, (local_batch // ndev) * ndev)
        _prepare_dataset(val_set, local_batch)
        n_local = math.ceil(val_set.size / local_batch)
        if multiproc:
            from jax.experimental import multihost_utils as mhu
            counts = np.asarray(mhu.process_allgather(
                np.asarray([n_local], np.int64)))
            if counts.min() == 0:
                raise ValueError(
                    "a host has an empty validation shard; every process "
                    "needs at least one batch for the collective eval")
            n_steps = int(counts.max())
        else:
            n_steps = n_local
        sample = next(val_set.eval_iterator(local_batch, pad_remainder=True))
        self._ensure_initialized(sample[0])
        if self._direct_pe_step is None:
            def step(p, s, rng, x, y, mask):
                losses = pe(p, s, rng, x, y)
                return (jnp.sum(losses.astype(jnp.float32) * mask),
                        jnp.sum(mask))

            self._direct_pe_step = jax.jit(step)
        if not global_config().get("eval.async"):
            from . import sync_eval
            return sync_eval.evaluate_direct_exact_sync(
                self, val_set, local_batch, n_steps)
        eval_rng = jax.random.PRNGKey(0)

        def host_batches():
            it = val_set.eval_iterator(local_batch, pad_remainder=True)
            last = None
            for _ in range(n_steps):
                try:
                    x, y, valid = next(it)
                    last = (x, y)
                except StopIteration:  # short host re-feeds mask all-zero
                    (x, y), valid = last, 0
                mask = (np.arange(local_batch) < valid).astype(np.float32)
                yield x, y, mask

        # per-batch (loss-sum, valid-count) scalars stay on device; the
        # dispatch loop never blocks — ONE device_get drains the pass
        pending: List[Any] = []
        prof = _profiler.enabled()
        with DeviceFeed(host_batches(), self.mesh,
                        profile_loop="eval" if prof else None) as feed:
            for bx, by, bm in feed:
                t_d = time.perf_counter() if prof else 0.0
                pending.append(self._direct_pe_step(
                    self.params, self.model_state, eval_rng, bx, by, bm))
                if prof:
                    _profiler.record_phase(
                        "eval", "dispatch", time.perf_counter() - t_d,
                        start=t_d)
        if prof:
            t_f = time.perf_counter()
            total, weight = _drain_sum_pairs(pending)
            _profiler.record_phase("eval", "fetch",
                                   time.perf_counter() - t_f, start=t_f)
        else:
            total, weight = _drain_sum_pairs(pending)
        if weight == 0:
            raise ValueError(
                f"validation set is empty ({val_set.size} records)")
        return {"loss": total / weight}

    def _evaluate_direct(self, val_set: FeatureSet, batch_size: int
                         ) -> Dict[str, float]:
        """Record-weighted average of the captured loss (direct-loss capture
        mode: the loss fn sees the raw batch, so padding cannot be masked).
        Single process: full batches run sharded and the tail runs UNPADDED
        through the same jitted step (one extra compile at the tail shape) —
        exact. Multi-process: every host runs the same number of
        identically-shaped padded steps (batch count agreed by allgather),
        tail batches weighted by their global valid count — every record
        counts; see the inline note for the tail-pad approximation."""
        if self.direct_eval_per_example_fn is not None:
            return self._evaluate_direct_exact(val_set, batch_size)
        multiproc = self.ctx.process_count > 1
        ndev = self.mesh.devices.size
        local_batch = self.ctx.local_batch(batch_size)
        if not multiproc:
            # single process may clamp to the data; multi-process must NOT —
            # local_batch derives from batch_size alone there, so every host
            # compiles the same global shape regardless of its shard size
            local_batch = min(local_batch, val_set.size)
        local_batch = max(ndev, (local_batch // ndev) * ndev)
        _prepare_dataset(val_set, local_batch)
        if multiproc:
            # all-hosts-agree padded-tail eval: every host runs the SAME
            # number of identically-shaped sharded steps (the black-box
            # direct loss is a global-batch program — per-host early exit
            # or shape changes would diverge SPMD). The full per-step
            # valid-count schedule is known upfront on every host, so ONE
            # allgather (before any data is touched — an empty shard fails
            # collectively, not with a bare StopIteration leaving peers
            # hung) exchanges both the batch counts and the weights. Short
            # hosts re-feed their last batch with valid=0. Tail batches are
            # weighted by their GLOBAL valid count — the pad rows (repeats
            # of the last row) leave an O(pad/batch) bias on that one
            # batch's mean, but every record is counted (previously tails
            # were silently dropped).
            import math

            from jax.experimental import multihost_utils as mhu
            n_local = math.ceil(val_set.size / local_batch)
            cap = int(np.asarray(mhu.process_allgather(
                np.asarray([n_local], np.int64))).max())
            sched = np.zeros(cap + 1, np.int64)
            sched[0] = n_local
            for t in range(n_local):
                sched[t + 1] = min(val_set.size - t * local_batch,
                                   local_batch)
            all_sched = np.asarray(mhu.process_allgather(sched)
                                   ).reshape(self.ctx.process_count, cap + 1)
            if all_sched[:, 0].min() == 0:
                raise ValueError(
                    "a host has an empty validation shard; every process "
                    "needs at least one batch for the collective eval")
            n_global = cap
            v_globals = all_sched[:, 1:].sum(axis=0)  # per-step weights
            sample = next(val_set.eval_iterator(local_batch,
                                                pad_remainder=True))
            self._ensure_initialized(sample[0])
            if self._direct_eval_step is None:
                direct = self.direct_eval_loss_fn
                self._direct_eval_step = jax.jit(
                    lambda p, s, rng, x, y: direct(p, s, rng, x, y)[0])
            if not global_config().get("eval.async"):
                from . import sync_eval
                return sync_eval.evaluate_direct_multiproc_sync(
                    self, val_set, local_batch, n_global, v_globals)
            eval_rng = jax.random.PRNGKey(0)

            def host_batches():
                it = val_set.eval_iterator(local_batch, pad_remainder=True)
                last = None
                for t in range(n_global):
                    try:
                        x, y, _ = next(it)
                        last = (x, y)
                    except StopIteration:
                        x, y = last
                    yield (x, y), int(v_globals[t])

            pending: List[Any] = []
            with DeviceFeed(host_batches(), self.mesh,
                            shard_fn=shard_payload) as feed:
                for (xs, ys), w in feed:
                    pending.append((self._direct_eval_step(
                        self.params, self.model_state, eval_rng, xs, ys), w))
            total, weight = _drain_weighted_losses(pending)
            return {"loss": total / weight}
        sample = next(val_set.eval_iterator(local_batch, pad_remainder=True))
        self._ensure_initialized(sample[0])
        if self._direct_eval_step is None:
            direct = self.direct_eval_loss_fn
            self._direct_eval_step = jax.jit(
                lambda p, s, rng, x, y: direct(p, s, rng, x, y)[0])
        if not global_config().get("eval.async"):
            from . import sync_eval
            return sync_eval.evaluate_direct_single_sync(
                self, val_set, local_batch)
        eval_rng = jax.random.PRNGKey(0)

        def shard_full(mesh, item):
            # single-process: full batches shard over the data axis; the
            # tail evaluates exactly via a replicated-batch compile at its
            # true size (host arrays pass straight into the jitted step)
            (x, y), valid = item
            if valid == local_batch:
                return shard_batch(mesh, (x, y)), valid
            return (x, y), valid

        def host_batches():
            for x, y, valid in val_set.eval_iterator(local_batch,
                                                     pad_remainder=False):
                yield (x, y), valid

        pending: List[Any] = []
        with DeviceFeed(host_batches(), self.mesh,
                        shard_fn=shard_full) as feed:
            for (x, y), valid in feed:
                pending.append((self._direct_eval_step(
                    self.params, self.model_state, eval_rng, x, y), valid))
        total, weight = _drain_weighted_losses(pending)
        if weight == 0:
            raise ValueError(
                f"validation set is empty ({val_set.size} records)")
        return {"loss": total / weight}

    # -- predict (TFNet/Predictable equivalent) -------------------------------

    @_traced_for_own_mesh
    def predict(self, x, batch_size: int = 32):
        """Pipelined prediction: batches stream through the DeviceFeed and a
        bounded window of ``eval.predict_window`` dispatches stays in
        flight — results are fetched (trimmed to their valid rows) BEHIND
        the dispatch frontier, so the host→device upload of batch N+K, the
        device compute of N+1..N+K-1, and the device→host download of batch
        N all overlap. ``eval.async = False`` falls back to the synchronous
        fetch-per-batch loop."""
        if not isinstance(x, HostDataset):
            x = FeatureSet.from_ndarrays(x, None, shuffle=False, shard=False)
        local_batch = min(self.ctx.local_batch(batch_size), x.size)
        ndev = self.mesh.devices.size
        local_batch = max(ndev, (local_batch // ndev) * ndev)
        _prepare_dataset(x, local_batch)
        sample = next(x.eval_iterator(local_batch, pad_remainder=True))
        self._ensure_initialized(sample[0])
        if self._predict_step is None:
            self._predict_step = self._build_predict_step()
        cfg = global_config()
        if not cfg.get("eval.async"):
            from . import sync_eval
            return sync_eval.predict_sync(self, x, local_batch)
        window = max(1, int(cfg.get("eval.predict_window")))

        def host_batches():
            for bx, _, valid in x.eval_iterator(local_batch,
                                                pad_remainder=True):
                yield bx, valid

        def fetch(y, valid):
            # device→host download of a batch K dispatches behind the
            # frontier — the one place predict touches host memory
            return jax.tree_util.tree_map(
                lambda t: np.asarray(t)[:valid], y)

        from collections import deque
        outs = []
        inflight: "deque" = deque()
        with DeviceFeed(host_batches(), self.mesh,
                        shard_fn=shard_payload) as feed:
            for bx, valid in feed:
                inflight.append(
                    (self._predict_step(self.params, self.model_state, bx),
                     valid))
                if len(inflight) > window:
                    outs.append(fetch(*inflight.popleft()))
        while inflight:
            outs.append(fetch(*inflight.popleft()))
        if isinstance(outs[0], (list, tuple)):
            return type(outs[0])(
                np.concatenate([o[i] for o in outs]) for i in range(len(outs[0])))
        return np.concatenate(outs)

    # -- params / checkpoint --------------------------------------------------

    def get_params(self):
        return jax.tree_util.tree_map(np.asarray, self.params)

    def set_params(self, params) -> None:
        self._maybe_add_vocab_rules()
        sharding = param_sharding(self.mesh, params, self.param_rules)
        self.params = jax.device_put(params, sharding)

    def set_model_state(self, state) -> None:
        """Install non-trainable model state (e.g. imported BN statistics).
        An explicit empty tree marks the model as deliberately stateless."""
        self.model_state = jax.device_put(
            state, param_sharding(self.mesh, state, self.param_rules))
        self._state_resolved = True

    def _snapshot_tree(self):
        if self.opt_state is None and self.params is not None:
            # saving a compiled-but-never-stepped model: materialize the
            # optimizer state so the checkpoint restores against the same
            # structure a trained snapshot has
            self.opt_state = self._init_opt_state(self.params)
        tree = {
            "params": jax.tree_util.tree_map(np.asarray, self.params),
            "opt_state": jax.tree_util.tree_map(np.asarray, self.opt_state),
            "model_state": jax.tree_util.tree_map(np.asarray, self.model_state),
            # the step rng is fold_in(root_rng, global_step): a resumed
            # run draws the dropout masks of the uninterrupted one only if
            # it also resumes the root key
            "meta": {"global_step": self.global_step, "epoch": self.epoch,
                     "root_rng": np.asarray(
                         jax.random.key_data(self.root_rng))},
        }
        ts = getattr(self, "_active_train_set", None)
        if ts is not None and hasattr(ts, "data_state"):
            # data-pipeline state: an epoch-end snapshot records the post-epoch
            # RNG (next epoch starts fresh); a mid-epoch one records the
            # epoch-START rng + batches consumed so resume replays the same
            # permutation from the same position. JSON→uint8 so orbax treats
            # it as a plain array leaf.
            if self._epoch_offset >= self._batches_per_epoch:
                rng_json, offset = ts.data_state(), 0
            else:
                rng_json, offset = self._epoch_data_state, self._epoch_offset
            tree["meta"]["data_rng"] = np.frombuffer(
                rng_json.encode(), dtype=np.uint8).copy()
            tree["meta"]["data_offset"] = offset
            tree["meta"]["data_batch"] = self._local_batch
        return tree

    def _save_snapshot(self) -> None:
        """Async snapshot: device→host copy NOW (the only part the train
        loop waits for), serialize+write on the background writer thread.
        ``submit`` fences the previous write first, so at most one snapshot
        is in flight and ordering is preserved. Crash safety: on the
        single-process local path, writes go to a ``.writing`` staging dir
        published by atomic rename, so a crash between copy and write
        leaves the previous snapshot intact; multi-process saves rely on
        orbax's own collective commit protocol, and remote URIs upload via
        a staging dir WITHOUT an atomic publish (object stores can't
        rename atomically) — a crash can tear a remote snapshot, which the
        per-snapshot checksum manifest catches at restore, falling back to
        the next-older valid snapshot. Retention pruning
        (``checkpoint.keep``) runs after each publish on the writer
        thread."""
        path = file_io.join(self._ckpt_dir, f"snapshot-{self.global_step}")
        tree = self._snapshot_tree()  # device fetch, synchronous

        def write_then_prune():
            self._write_snapshot(path, tree)
            self._prune_snapshots()

        self._ckpt_writer.submit(write_then_prune)

    def _snapshot_candidates(self) -> List[Tuple[int, str]]:
        """``(step, path)`` for every published snapshot, ascending by
        step. Only names of the exact ``snapshot-<int>`` form qualify:
        ``.writing`` staging dirs are excluded by a real suffix check (a
        substring test would also hide a valid snapshot whose path merely
        CONTAINS '.writing'), and entries whose step suffix is not an
        integer — foreign dirs, editor droppings — are skipped instead of
        crashing the restore path."""
        if not self._ckpt_dir or not file_io.isdir(self._ckpt_dir):
            return []
        out: List[Tuple[int, str]] = []
        for d in file_io.listdir(self._ckpt_dir):
            if not d.startswith("snapshot-") or d.endswith(".writing"):
                continue
            try:
                step = int(d[len("snapshot-"):])
            except ValueError:
                continue
            out.append((step, file_io.join(self._ckpt_dir, d)))
        out.sort()
        return out

    def _latest_snapshot(self) -> Optional[str]:
        cands = self._snapshot_candidates()
        return cands[-1][1] if cands else None

    def _restore_latest_valid(self) -> Optional[str]:
        """Restore the newest snapshot that passes checksum-manifest and
        structure validation, transparently falling back past torn or
        corrupt newer ones. Returns the restored path, or ``None`` when no
        snapshot survives."""
        for _step, path in reversed(self._snapshot_candidates()):
            try:
                self.load_checkpoint(path)
                return path
            except Exception:
                _M_CKPT_FALLBACK.inc()
                logger.exception(
                    "snapshot %s failed to restore; falling back to the "
                    "next older snapshot", path)
        return None

    def _prune_snapshots(self) -> None:
        """Retention: keep the newest ``checkpoint.keep`` snapshots (the
        fallback candidates torn-newest recovery needs) and delete the
        rest — bounded disk growth without giving up elasticity. Runs on
        the writer thread after each successful publish; multi-process
        pods prune on process 0 only (the dir is shared)."""
        keep = int(global_config().get("checkpoint.keep") or 0)
        if keep <= 0 or (self.ctx.process_count > 1
                         and jax.process_index() != 0):
            return
        cands = self._snapshot_candidates()
        for _step, path in cands[:-keep]:
            try:
                file_io.rmtree(path)
                logger.info("pruned old snapshot %s (checkpoint.keep=%d)",
                            path, keep)
            except Exception:
                logger.exception("failed to prune old snapshot %s", path)

    def save_checkpoint(self, path: str) -> None:
        """Write a snapshot (synchronous public API; the train loop's
        triggered snapshots go through the async writer instead). EVERY
        process must call this: orbax's save is a collective (it barriers
        across ``jax.process_count()`` processes and elects process 0 as
        the writer) — gating it to rank 0 deadlocks the pod at the barrier.
        Remote URIs (``gs://...``) are written via a local staging dir (the
        reference's HDFS-aware save, ``common/Utils.scala:97``)."""
        self._ckpt_writer.wait()  # order behind any in-flight async write
        self._write_snapshot(path, self._snapshot_tree())

    def _write_snapshot(self, path: str, tree) -> None:
        with time_it("ckpt.write"):
            self._write_snapshot_impl(path, tree)

    def _write_snapshot_impl(self, path: str, tree) -> None:
        import orbax.checkpoint as ocp

        # chaos site: a firing injection models the writer dying before
        # any publish — the previous snapshot must stay the newest intact
        faults.inject("ckpt.write")
        write_t0 = time.perf_counter()
        import shutil
        ckptr = ocp.PyTreeCheckpointer()
        if file_io.is_remote(path):
            import tempfile
            tmp = tempfile.mkdtemp(prefix="zoo_snap_")
            try:
                ckptr.save(os.path.join(tmp, "ckpt"), tree, force=True)
                # manifest computed over the local staging tree BEFORE the
                # upload: on object stores (no atomic rename) it is the
                # only way restore can tell a torn upload from a whole one
                _write_manifest(tmp)
                if file_io.isdir(path):
                    # re-writing this step (elastic replay / preemption
                    # colliding with a triggered write): orbax file names
                    # are content-addressed per write, so uploading over
                    # the old objects would leave STALE extras that fail
                    # manifest verification — clear the target first
                    file_io.rmtree(path)
                file_io.put_tree(tmp, path)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
        else:
            final = os.path.abspath(file_io.local_path(path))
            if self.ctx.process_count > 1:
                # orbax's save is a collective: every process participates
                # and orbax coordinates the write + its own commit
                # atomicity; a per-process stage+rename would race ranks
                ckptr.save(final, tree, force=True)
                # the save is globally complete once it returns (orbax
                # barriers) — each rank now seals its participation; a
                # rank killed in this window leaves a snapshot that
                # FAILS verification, so elastic resume falls back to
                # the previous fully-sealed one instead of trusting it
                rank = jax.process_index()
                stamp = os.path.join(final, _RANK_STAMP_FMT.format(rank))
                with open(stamp, "w") as f:
                    json.dump({"rank": rank,
                               "global_step": self.global_step}, f)
                if rank == 0:  # one writer for the manifest
                    _write_manifest(final, ranks=self.ctx.process_count)
                _M_CKPT_WRITE.observe(time.perf_counter() - write_t0)
                return
            staging = final + ".writing"
            if os.path.exists(staging):  # leftover from a killed writer
                shutil.rmtree(staging)
            ckptr.save(staging, tree, force=True)
            _write_manifest(staging)  # sealed into the same atomic publish
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(staging, final)  # atomic publish
        _M_CKPT_WRITE.observe(time.perf_counter() - write_t0)
        # chaos site: tear the snapshot AFTER publish — the checksum
        # manifest must catch it at restore and fall back one older
        if faults.inject("ckpt.corrupt"):
            faults.tear_snapshot(path)

    def load_checkpoint(self, path: str) -> None:
        """Restore a snapshot. Restores are data-only (orbax reads arrays,
        never pickled code — the CheckedObjectInputStream concern from the
        reference, ``common/CheckedObjectInputStream.scala:1``, is designed
        away), but the STRUCTURE is still validated before any state is
        touched so a truncated/foreign checkpoint can't half-install."""
        # fence: an in-flight async write may be producing the newest
        # snapshot (or the very one being restored)
        self._ckpt_writer.wait()
        restore_t0 = time.perf_counter()
        verify = bool(global_config().get("checkpoint.verify"))
        if file_io.is_remote(path):
            with file_io.localized(path, "r") as tmp:
                if verify:
                    _verify_manifest(tmp, path)
                self._load_checkpoint_local(os.path.join(tmp, "ckpt"))
        else:
            local = os.path.abspath(file_io.local_path(path))
            if verify:
                _verify_manifest(local, path)
            self._load_checkpoint_local(local)
        _M_CKPT_RESTORE.observe(time.perf_counter() - restore_t0)

    def _load_checkpoint_local(self, path: str) -> None:
        import orbax.checkpoint as ocp
        self._maybe_add_vocab_rules()
        ckptr = ocp.PyTreeCheckpointer()
        tree = ckptr.restore(path)
        missing = {"params", "opt_state", "model_state", "meta"} - set(tree)
        if missing:
            raise ValueError(
                f"checkpoint at {path} is not an estimator snapshot "
                f"(missing {sorted(missing)})")
        if self.params is not None:
            live = jax.tree_util.tree_structure(
                jax.tree_util.tree_map(lambda x: 0, self.params))
            loaded = jax.tree_util.tree_structure(
                jax.tree_util.tree_map(lambda x: 0, tree["params"]))
            if live != loaded:
                raise ValueError(
                    f"checkpoint param structure does not match the live "
                    f"model: {loaded} vs {live}")
        # orbax returns optax NamedTuple states as plain containers; re-restore
        # with a live template so the optimizer state keeps its structure.
        live_opt = (self.opt_state if self.opt_state is not None
                    else self._init_opt_state(tree["params"]))
        tree = ckptr.restore(path, item={
            "params": tree["params"],
            "opt_state": live_opt,
            "model_state": tree["model_state"],
            "meta": tree["meta"],
        })
        sharding = param_sharding(self.mesh, tree["params"], self.param_rules)
        self.params = jax.device_put(tree["params"], sharding)
        self.model_state = jax.device_put(
            tree["model_state"],
            param_sharding(self.mesh, tree["model_state"], self.param_rules))
        self.opt_state = jax.device_put(
            tree["opt_state"],
            param_sharding(self.mesh, tree["opt_state"], self._opt_rules()))
        self.global_step = int(tree["meta"]["global_step"])
        self.epoch = int(tree["meta"]["epoch"])
        # a restored model_state (even a legitimately empty one) is final —
        # without this a stateless model burns an rng split rebuilding it,
        # diverging the resumed dropout stream from an uninterrupted run
        self._state_resolved = True
        if "root_rng" in tree["meta"]:
            data = jnp.asarray(tree["meta"]["root_rng"], jnp.uint32)
            self.root_rng = (
                jax.random.wrap_key_data(
                    data, impl=jax.random.key_impl(self.root_rng))
                if jnp.issubdtype(self.root_rng.dtype, jax.dtypes.prng_key)
                else data)
        if "data_rng" in tree["meta"]:
            rng_json = bytes(np.asarray(tree["meta"]["data_rng"])).decode()
            self._restore_data = (rng_json,
                                  int(tree["meta"]["data_offset"]),
                                  int(tree["meta"].get("data_batch", 0)))
