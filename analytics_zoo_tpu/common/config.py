"""Layered configuration registry.

The reference scatters configuration across six mechanisms (packaged
``spark-analytics-zoo.conf`` defaults, SparkConf keys, MKL env vars, Java system
properties, per-service YAML, build-info properties — see
``pyzoo/zoo/common/nncontext.py:148-200`` and ``zoo/.../common/NNContext.scala:35-78``
in the reference). This module centralizes the same capability into a single
layered registry: registered defaults < config file < environment variables <
programmatic overrides.
"""
from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional
from . import file_io

_ENV_PREFIX = "ZOO_TPU_"


@dataclass
class _Flag:
    name: str
    default: Any
    parser: Callable[[str], Any]
    help: str = ""


def _parse_bool(s: str) -> bool:
    return str(s).strip().lower() in ("1", "true", "yes", "on")


class Config:
    """A single process-wide layered flag registry.

    Precedence (lowest to highest):
      1. registered defaults (``register``)
      2. values loaded from a JSON config file (``load_file``)
      3. environment variables ``ZOO_TPU_<UPPER_NAME>``
      4. programmatic ``set`` overrides
    """

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._flags: Dict[str, _Flag] = {}
        self._file_values: Dict[str, Any] = {}
        self._overrides: Dict[str, Any] = {}

    def register(self, name: str, default: Any, help: str = "",
                 parser: Optional[Callable[[str], Any]] = None) -> None:
        with self._lock:
            if parser is None:
                if isinstance(default, bool):
                    parser = _parse_bool
                elif isinstance(default, int):
                    parser = int
                elif isinstance(default, float):
                    parser = float
                else:
                    parser = str
            self._flags[name] = _Flag(name, default, parser, help)

    def load_file(self, path: str) -> None:
        with file_io.fopen(path) as f:
            values = json.load(f)
        with self._lock:
            self._file_values.update(values)

    def set(self, name: str, value: Any) -> None:
        with self._lock:
            self._overrides[name] = value

    def unset(self, name: str) -> None:
        with self._lock:
            self._overrides.pop(name, None)

    def get(self, name: str, default: Any = None) -> Any:
        with self._lock:
            flag = self._flags.get(name)
            if name in self._overrides:
                return self._overrides[name]
            env_key = _ENV_PREFIX + name.upper().replace(".", "_").replace("-", "_")
            if env_key in os.environ:
                raw = os.environ[env_key]
                return flag.parser(raw) if flag else raw
            if name in self._file_values:
                return self._file_values[name]
            if flag is not None:
                return flag.default
            return default

    def as_dict(self) -> Dict[str, Any]:
        with self._lock:
            out = {name: self.get(name) for name in self._flags}
            for name in self._file_values:
                out.setdefault(name, self.get(name))
            for name in self._overrides:
                out[name] = self._overrides[name]
            return out


_global_config = Config()


def global_config() -> Config:
    return _global_config


# Core defaults (mirrors the knobs the reference exposes via SparkConf / sysprops).
_global_config.register("failure.retry_times", 5,
                        "Max training retries from checkpoint within a retry window "
                        "(reference: bigdl.failure.retryTimes).")
_global_config.register("failure.retry_interval_s", 120.0,
                        "Window seconds for retry budget reset "
                        "(reference: bigdl.failure.retryTimeInterval).")
_global_config.register("failure.io_retries", 3,
                        "Retries for transient remote file_io failures "
                        "(exponential backoff; local paths never retry).")
_global_config.register("failure.io_backoff_s", 0.05,
                        "Base backoff seconds for remote IO retries "
                        "(doubles per attempt).")
_global_config.register("checkpoint.keep", 5,
                        "Snapshots retained per checkpoint dir (older ones "
                        "pruned after each successful write; >= 2 keeps a "
                        "fallback candidate for torn-newest recovery; "
                        "0 = unlimited).")
_global_config.register("checkpoint.verify", True,
                        "Verify the per-snapshot checksum manifest on "
                        "restore; a mismatch raises CheckpointCorruptError "
                        "and elastic restores fall back to the next-older "
                        "valid snapshot.")
_global_config.register("faults.plan", "",
                        "Fault-injection schedule: 'site:N' fires on the "
                        "N-th call, 'site:0.1' with probability 0.1, "
                        "'@B' suffix sets the budget (default 1); "
                        "comma-separated. '' = injection disabled.")
_global_config.register("faults.seed", 0,
                        "Seed for probabilistic fault-injection draws "
                        "(per-site streams are derived deterministically).")
_global_config.register("data.task_retries", 0,
                        "Times a failed transform-worker task is retried "
                        "before TransformWorkerError surfaces (transient "
                        "per-task faults: flaky decode/remote reads).")
_global_config.register("data.worker_respawns", 2,
                        "Respawn budget for transform workers that die "
                        "mid-task (SIGKILL/OOM): the pool forks a "
                        "replacement and resubmits the lost task; once "
                        "exhausted the consumer gets TransformWorkerError "
                        "promptly instead of hanging.")
_global_config.register("data.prefetch", 2, "Device-feed prefetch depth.")
_global_config.register("data.num_workers", 0,
                        "Default worker count for FeatureSet transforms "
                        "(0 = serial loop; >1 enables the parallel tiers).")
_global_config.register("data.transform_mode", "auto",
                        "Per-record transform engine: auto|mp|thread|loop. "
                        "'auto' picks forked shared-memory workers (mp) "
                        "when num_workers > 1 — the only tier that beats "
                        "the GIL for pure-Python transforms — falling back "
                        "to a thread pool where fork is unavailable.")
_global_config.register("data.shm_slots", 4,
                        "Shared-memory batch slabs per transform worker "
                        "pool — the mp data plane's pipeline depth. A "
                        "yielded zero-copy batch view stays valid until "
                        "shm_slots-1 further batches are drawn; keep this "
                        "above data.prefetch + 2.")
_global_config.register("data.cache_dir", "",
                        "Directory for one-shot lazy-transform memmap "
                        "replay caches ('' = a fresh temp dir per set).")
_global_config.register("data.staging_slots", 0,
                        "Train-iterator staging ring depth for zero-alloc "
                        "batch gathers (np.take(..., out=...) into reused "
                        "buffers). 0 = fresh arrays per batch (safe "
                        "default: a yielded batch is overwritten after "
                        "staging_slots further batches, which breaks "
                        "consumers that buffer batches or alias host "
                        "memory into device arrays).")
_global_config.register("eval.async", True,
                        "Pipeline evaluate()/predict() through the "
                        "DeviceFeed with on-device accumulation (one host "
                        "sync per pass). False falls back to the "
                        "synchronous per-batch loops (parity reference / "
                        "A-B benchmarking).")
_global_config.register("eval.predict_window", 2,
                        "Max in-flight predict dispatches before results "
                        "are fetched behind the dispatch frontier.")
_global_config.register("metrics.enabled", True,
                        "Record into the process-global metrics registry "
                        "(common/metrics.py). False turns every counter/"
                        "gauge/histogram record into a sub-microsecond "
                        "no-op (the bench obs_overhead A/B baseline); "
                        "serving health counters go dark too.")
_global_config.register("mesh.data_axis", "data", "Default data-parallel mesh axis name.")
_global_config.register("mesh.model_axis", "model", "Default model-parallel mesh axis name.")
_global_config.register("rng.impl", "",
                        "JAX PRNG implementation for estimator rng streams "
                        "('' = default threefry; 'rbg'/'unsafe_rbg' use the "
                        "TPU hardware RNG — faster bit generation, streams "
                        "differ from threefry's).")
_global_config.register("profile.enabled", False,
                        "Step-phase attribution profiler (common/profiler."
                        "py): decompose train/eval/serving steps into "
                        "host_input/dispatch/execute/fetch/compile phases "
                        "with MFU and roofline gauges. Off = sub-microsecond "
                        "no-ops; on, the train loop fences each step "
                        "(block_until_ready) to separate execute from "
                        "dispatch, trading pipelining for attribution.")
_global_config.register("profile.capture_dir", "",
                        "Output directory for jax.profiler capture windows "
                        "('' disables all captures, armed or not).")
_global_config.register("profile.capture_steps", 0,
                        "Arm one jax.profiler capture for this many profiled "
                        "steps at the first step boundary (0 = not armed).")
_global_config.register("profile.capture_on_breach", False,
                        "Arm a time-bounded jax.profiler capture on the "
                        "first serving SLO breach (shed or expired) of the "
                        "process.")
_global_config.register("profile.capture_seconds", 2.0,
                        "Wall-seconds bound for breach-triggered capture "
                        "windows.")
_global_config.register("profile.peak_flops", 0.0,
                        "Override the device's peak bf16 FLOP/s for the MFU "
                        "gauge (0 = auto-detect from the device kind; "
                        "detection knows TPU v4/v5e/v5p/v6e).")
_global_config.register("data.validate_ids", "count",
                        "Embedding-id validation policy ('count' | 'raise' "
                        "| 'clamp'). 'clamp' keeps the historical silent "
                        "jnp.take clip; 'count' clamps but counts offenders "
                        "into embed.oob_ids_total; 'raise' raises on "
                        "out-of-range ids when the lookup runs eagerly "
                        "(test suites) and degrades to 'count' under jit.")
_global_config.register("embed.sparse_updates", True,
                        "Apply sparse row-subset optimizer updates to "
                        "sharded embedding tables (parallel/embedding.py): "
                        "only the rows touched this step are read/written, "
                        "and their optimizer state lives outside the dense "
                        "optax tree. False funnels embedding grads through "
                        "the dense optimizer like any other parameter.")
_global_config.register("data.handoff", "slab",
                        "XShard → FeatureSet lowering path: 'slab' has "
                        "ETL workers write partition rows straight into "
                        "one shared feature/label segment the FeatureSet "
                        "wraps zero-copy; 'gather' is the eager "
                        "concat-into-from_dataframe baseline (parity "
                        "reference / A-B benchmarking).")
_global_config.register("xshard.num_workers", 0,
                        "ETL worker fleet size for the XShard engine "
                        "(0 = the transform pool's default: min(4, "
                        "cpu_count)).")
_global_config.register("xshard.partitions", 0,
                        "Default partition count for XShard.from_pandas "
                        "(0 = one partition per ETL worker).")
_global_config.register("xshard.slab_mb", 64.0,
                        "Per-partition shared-memory slab budget (MB) for "
                        "XShard blocks; a partition output exceeding it "
                        "spills to a per-partition memmap file instead "
                        "(xshard.spill_bytes_total counts the bytes).")
_global_config.register("xshard.spill_dir", "",
                        "Directory for XShard spill files ('' = a fresh "
                        "temp dir per engine, removed at engine close).")
_global_config.register("embed.cold_lr", 0.01,
                        "SGD learning rate for host-DRAM cold-tier embedding "
                        "rows (applied eagerly on the host inside the "
                        "backward callback; independent of the device "
                        "optimizer).")
_global_config.register("fleet.stale_after_s", 5.0,
                        "Health-file age beyond which the fleet router "
                        "treats an instance as dead: its spool is "
                        "reclaimed and its in-flight streams fail over "
                        "from their last streamed prefix.")
_global_config.register("fleet.health_refresh_s", 0.25,
                        "Router cadence for re-reading per-instance "
                        "health files (placement gauges refresh at most "
                        "this often).")
_global_config.register("fleet.scale_headroom", 1.25,
                        "Multiplier on observed demand when computing the "
                        "fleet.desired_instances scale signal (>1 keeps "
                        "spare capacity for failover).")
_global_config.register("fleet.scale_interval_s", 0.25,
                        "Fleet supervisor actuation cadence: how often "
                        "the desired-instance signal is compared against "
                        "the live fleet and a spawn/drain is issued "
                        "(rate-limits scale thrash).")
_global_config.register("cluster.heartbeat_s", 0.5,
                        "Worker lease heartbeat cadence: every pod worker "
                        "bumps its lease seq this often so the elastic "
                        "supervisor can tell a live rank from a dead or "
                        "hung one.")
_global_config.register("cluster.lease_expiry_s", 0.0,
                        "Monotonic lease age (seconds since the supervisor "
                        "last SAW a worker's lease seq change) beyond "
                        "which the rank is declared dead and the elastic "
                        "restart path fires. 0 = 6 x cluster.heartbeat_s.")
_global_config.register("cluster.respawns", 3,
                        "Elastic restart budget: how many pod-generation "
                        "respawns the supervisor performs before giving "
                        "up and surfacing the failure (the reference's "
                        "failure.retryTimes, at cluster scope).")
_global_config.register("cluster.restart_backoff_s", 0.5,
                        "Base backoff between a detected worker death and "
                        "the respawned generation (grows linearly with "
                        "consecutive restarts so a crash-looping pod "
                        "does not spin).")
_global_config.register("ingest.buffer_records", 4096,
                        "Bounded-buffer capacity of the streaming ingest "
                        "tier (journaled-but-unconsumed plus claimed-but-"
                        "unreleased records); at capacity the ingest "
                        "thread stops claiming, so backpressure surfaces "
                        "as queue depth.")
_global_config.register("ingest.watermark_s", 0.0,
                        "Event-time watermark: a claimed record is "
                        "released to the journal once its timestamp is "
                        "at least this old (0 releases immediately); a "
                        "full buffer force-releases regardless.")
_global_config.register("ingest.poll_interval_s", 0.02,
                        "Sleep between ingest polls when the queue is "
                        "quiet, and between journal-growth checks on the "
                        "consumer side.")
_global_config.register("online.snapshot_interval_s", 30.0,
                        "Default wall-time snapshot cadence for "
                        "Estimator.train_online (unbounded streams "
                        "checkpoint by time, not epoch boundaries).")
_global_config.register("online.rollout_verify_timeout_s", 5.0,
                        "How long the promotion coordinator polls an "
                        "instance's health_snapshot for the new "
                        "model_version before declaring the rollout "
                        "failed and rolling back.")
_global_config.register("kernels.fused_embedding", True,
                        "Route embedding lookups through the fused "
                        "gather/pool/scatter kernels in "
                        "ops/embedding_kernels.py (pallas on TPU, "
                        "bit-identical lax elsewhere). Off = the "
                        "historical unfused layer ops, kept as the "
                        "bit-parity reference.")
_global_config.register("parallel.tensor_axis", "model",
                        "Mesh axis tensor-parallel (Megatron column/row) "
                        "rules shard over; transformer_tp_rules() reads "
                        "this when no axis is passed explicitly.")
_global_config.register("parallel.pipeline_stages", 0,
                        "Default pipeline-parallel stage count for "
                        "TransformerLM training (0 = pipelining off; "
                        "stages must divide n_block and equal the "
                        "'pipe' mesh axis size).")
_global_config.register("parallel.pipeline_microbatches", 4,
                        "Microbatches per global batch in the 1F1B "
                        "pipeline schedule; bubble fraction is "
                        "2(P-1)/(M+2(P-1)) so larger M amortizes the "
                        "pipeline fill/drain bubbles.")
_global_config.register("parallel.moe_capacity_factor", 1.25,
                        "Default MoE expert capacity factor (GShard "
                        "k*tokens*C/experts convention) when MoE(...) "
                        "is built without an explicit value; overflow "
                        "tokens ride the residual path and are counted "
                        "in parallel.moe_dropped_tokens_total.")
_global_config.register("parallel.moe_exchange", "auto",
                        "MoE expert dispatch: 'dense' = one-hot einsum "
                        "dispatch with GSPMD-inserted collectives; "
                        "'alltoall' = explicit fixed-size all-to-all "
                        "exchange (route -> local expert compute -> "
                        "reverse, the PR 7 embedding-exchange shape); "
                        "'auto' = alltoall when a mesh with an 'expert' "
                        "axis is active and shapes divide, dense "
                        "otherwise.")
_global_config.register("serving.brownout_high", 0.75,
                        "Pressure (max of queue-fill, slot-occupancy and "
                        "KV-page-scarcity ratios) above which the brownout "
                        "controller steps DOWN one degradation rung on the "
                        "next health tick (docs/serving.md"
                        "#overload-survival).")
_global_config.register("serving.brownout_low", 0.35,
                        "Pressure below which the brownout controller "
                        "steps back UP one rung after "
                        "serving.brownout_hold_ticks consecutive calm "
                        "health ticks.")
_global_config.register("serving.brownout_hold_ticks", 3,
                        "Consecutive calm health ticks required before the "
                        "brownout controller recovers one rung — "
                        "hysteresis so the fleet does not flap between "
                        "rungs at the threshold.")
_global_config.register("serving.brownout_token_frac", 0.25,
                        "Fraction of the configured max_new_tokens that "
                        "the deepest brownout rung caps generative "
                        "budgets to (rung 3; rung 2 caps at twice this).")
_global_config.register("client.retry_budget_ratio", 0.1,
                        "Retry-budget token-bucket earn rate: each first "
                        "attempt deposits this many tokens, each "
                        "retry/hedge spends one — retry amplification is "
                        "bounded at 1 + ratio by construction.")
_global_config.register("client.retry_attempts", 2,
                        "Max budgeted retries per logical request in "
                        "ResilientClient.call (only on terminal errors "
                        "with retriable: true).")
_global_config.register("client.retry_backoff_s", 0.05,
                        "Full-jitter retry backoff base: attempt N sleeps "
                        "uniform(0, base * 2^N) seconds before "
                        "re-enqueueing.")
_global_config.register("client.hedge_delay_ms", 200.0,
                        "Hedge trigger floor for ResilientClient."
                        "query_any: a second copy races the first after "
                        "this long (or the client's observed p99 once "
                        "enough history exists) without a terminal.")
_global_config.register("fleet.breaker_failures", 3,
                        "Consecutive settled error terminals from one "
                        "instance that trip its circuit breaker open "
                        "(docs/fleet.md#overload-survival).")
_global_config.register("fleet.breaker_latency_ratio", 4.0,
                        "An instance whose EWMA service time exceeds this "
                        "multiple of the fleet median for "
                        "fleet.breaker_failures consecutive health "
                        "refreshes trips its breaker (sick-but-not-dead "
                        "detection ahead of health-file staleness).")
_global_config.register("fleet.breaker_cooldown_s", 1.0,
                        "Seconds an open breaker holds before moving to "
                        "half-open and admitting one probe placement.")
_global_config.register("ops.enabled", False,
                        "Master switch for the ops plane (structured "
                        "event log, metric history sampler, SLO alert "
                        "engine). Off by default: a disabled plane costs "
                        "one boolean check per would-be event and "
                        "nothing per step (docs/observability.md"
                        "#ops-plane).")
_global_config.register("ops.dir", "",
                        "Shared event-spool directory for the structured "
                        "event log. Point every process of a fleet "
                        "(supervisor, servers, forked workers) at the "
                        "same path so the incident CLI reads one story; "
                        "empty = a private temp spool per creating "
                        "process.")
_global_config.register("ops.ring_events", 2048,
                        "Capacity of the per-process in-memory event ring "
                        "(EventLog.tail) — bounds memory regardless of "
                        "run length; the JSONL spool on disk is the "
                        "unbounded record.")
_global_config.register("ops.sample_interval_s", 0.25,
                        "Cadence of the metric history sampler thread "
                        "snapshotting the shm registry into per-series "
                        "rings.")
_global_config.register("ops.history_depth", 512,
                        "Samples retained per (metric, label) series in "
                        "the history rings — memory is bounded by "
                        "series x depth (at the default cadence, ~2 "
                        "minutes of history).")
_global_config.register("ops.eval_interval_s", 0.5,
                        "Cadence of the SLO alert engine's evaluation "
                        "pass over the metric history.")
_global_config.register("ops.incident_dir", "",
                        "Directory incident bundles are sealed into; "
                        "empty = an 'incidents/' subdirectory of the "
                        "event spool.")
_global_config.register("ops.incident_window_s", 60.0,
                        "Trailing window of events and metric history "
                        "frozen into each incident bundle.")
