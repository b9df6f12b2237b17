"""Serving engine (reference ``serving/ClusterServing.scala:45``): the loop
is claim micro-batch → decode base64 images → preprocess to the model shape
→ batched ``InferenceModel.doPredict`` → top-N postprocess → result
write-back, with throughput summaries (``:312-331``). One process per host;
the TPU executes the batched forward, threads only move bytes.

Request-lifecycle SLO layer (the Tail-at-Scale/Clipper machinery the
reference leaves to the operator): the invariant is that **every claimed
request receives exactly one terminal result — a value or an explicit
error — no matter what fails**. Deadlines are checked at claim, after
decode, and before dispatch (expired work answers ``deadline exceeded``
instead of burning device time); overload sheds the oldest requests with
explicit shed errors instead of silent trims; SIGTERM drains (finish
in-flight, flush, terminal ``health.json``) instead of dropping; and
``reload_model`` hot-swaps the model off the serve path with a canary
predict and rollback. ``health_snapshot()`` is the deep-health surface
(queue depth, claim age, in-flight, p50/p99, shed/expired/error counters)
supervisors consume as a dict or as the periodically-written
``config.health_path`` file."""
from __future__ import annotations

import itertools
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from ..common import faults, file_io
from ..common import metrics as _metrics
from ..common import profiler as _profiler
from ..common import utils as _utils
from ..common.config import global_config
from ..common.utils import time_it, wall_clock
from ..inference.inference_model import InferenceModel
from ..ops import alerts as ops_alerts
from ..ops import events as ops_events
from ..ops import incident as ops_incident
from ..utils import trace as _trace
from .config import ServingConfig
from .queues import QueueBackend, decode_image, make_queue

logger = logging.getLogger("analytics_zoo_tpu.serving")

#: canonical terminal error texts (clients match on these)
SHED_ERROR = "shed: queue overloaded"
PAGE_SHED_ERROR = "shed: kv page pool exhausted"
DEADLINE_ERROR = "deadline exceeded"
SHUTDOWN_ERROR = "serving shut down before this request completed"
# Chunked prefill: decode steps of the resident streams after each chunk of a
# joining prompt. On the chip a chunk of 2,048 positions takes as long as four
# to five steps (147 ms against 33 ms an iteration), so with 5 a joining
# prompt gets at most half of the loop and a resident stream's tokens keep
# coming at half their pace instead of a sixth; with 1 the token rate
# swings fivefold with every join (docs/serving.md).
DECODE_STEPS_PER_CHUNK = 5

#: SLO telemetry in the shared registry (common/metrics.py). Every family
#: is labeled by server instance so two servers in one process (tests, the
#: multi-server spool) keep separate series; ``health_snapshot()`` is a
#: per-instance view of these.
_M_COUNTERS = {
    "shed": _metrics.counter(
        "serving.shed_total", "Requests shed by admission control.",
        labels=("server",)),
    "expired": _metrics.counter(
        "serving.expired_total", "Requests answered with deadline errors.",
        labels=("server",)),
    "errors": _metrics.counter(
        "serving.error_total",
        "Requests answered with non-deadline error results.",
        labels=("server",)),
    "claim_faults": _metrics.counter(
        "serving.claim_fault_total", "Transient claim-stage failures.",
        labels=("server",)),
    "reloads": _metrics.counter(
        "serving.reload_total", "Successful hot model reloads.",
        labels=("server",)),
    "reload_failures": _metrics.counter(
        "serving.reload_failure_total",
        "Model reloads that failed and rolled back.", labels=("server",)),
}
_M_RECORDS = _metrics.counter(
    "serving.records_total", "Records answered with prediction values.",
    labels=("server",))
_M_LATENCY = _metrics.histogram(
    "serving.request_latency_seconds",
    "Enqueue-to-terminal-result latency (client-stamped enqueue_t).",
    labels=("server",))
_M_QUEUE_DEPTH = _metrics.gauge(
    "serving.queue_depth", "Pending requests in the claim queue.",
    labels=("server",))
_M_IN_FLIGHT = _metrics.gauge(
    "serving.in_flight", "Claimed requests without a terminal result yet.",
    labels=("server",))
_M_CLAIM_AGE = _metrics.gauge(
    "serving.claim_age_seconds", "Seconds since the last successful claim.",
    labels=("server",))
#: generative (continuous-batching) serving telemetry
_M_TTFT = _metrics.histogram(
    "serving.ttft_seconds",
    "Enqueue-to-first-token latency of generative streams.",
    labels=("server",))
_M_QUEUE_WAIT = _metrics.histogram(
    "serving.queue_wait_seconds",
    "Enqueue-to-claim wait of generative requests (client-stamped "
    "enqueue_t): the part of time to first token spent in the queue.",
    labels=("server",))
_M_PREFILL_WAIT = _metrics.histogram(
    "serving.prefill_wait_seconds",
    "Claim-to-first-chunk wait of a prompt that is prefilled in chunks: "
    "its turn comes behind the chunks of the prompts claimed before it "
    "and the decode steps between them.", labels=("server",))
_M_TOKENS = _metrics.counter(
    "serving.tokens_total",
    "Tokens decoded across all generative streams.", labels=("server",))
_M_SLOTS = _metrics.gauge(
    "serving.slots_occupied",
    "Decode slots currently holding an active stream.", labels=("server",))
#: paged KV engine + speculative decoding telemetry
_M_PAGES_FREE = _metrics.gauge(
    "serving.kv_pages_free",
    "Allocatable pages remaining in the paged KV pool (0 = joins shed).",
    labels=("server",))
_M_PAGE_EVICT = _metrics.counter(
    "serving.kv_page_evictions_total",
    "KV pages returned to the pool by stream retirement.",
    labels=("server",))
_M_POOL_REBUILDS = _metrics.counter(
    "serving.kv_pool_rebuilds_total",
    "Times the KV caches were made anew after a failed program that had "
    "been given them (every resident stream errored, prefixes prefilled "
    "again).",
    labels=("server",))
_M_SPEC_ACCEPT = _metrics.gauge(
    "serving.spec_accept_ratio",
    "Mean fraction of draft tokens accepted in the last verify round.",
    labels=("server",))
#: chunked prefill and the recurrent state (models with linear-attention
#: layers, capture/decoder.py), which read nought for every other model,
#: and the result publisher
_M_GEN_COUNTERS = {
    "prefill_chunks": _metrics.counter(
        "serving.prefill_chunks_total",
        "Chunks of joining prompts dispatched (chunked prefill).",
        labels=("server",)),
    "prompt_tokens": _metrics.counter(
        "serving.prompt_tokens_total",
        "Prompt positions prefilled chunk by chunk.", labels=("server",)),
    "steps_between_chunks": _metrics.counter(
        "serving.steps_between_chunks_total",
        "Decode steps of the resident streams that ran while a joining "
        "prompt was between two of its chunks or waited for its first.",
        labels=("server",)),
    # how often the result publisher lands a stream's newest record in the
    # place of one a token
    "partials_superseded": _metrics.counter(
        "serving.partials_superseded_total",
        "Partial results replaced by a newer record of the same stream "
        "before the publisher had written them.", labels=("server",)),
    # the serve loop keeps one decode step in flight (docs/serving.md)
    "decode_steps": _metrics.counter(
        "serving.decode_steps_total",
        "Decode steps dispatched (a speculative round counts as one).",
        labels=("server",)),
    "steps_ahead": _metrics.counter(
        "serving.steps_ahead_total",
        "Decode steps dispatched while the step before them was not yet "
        "folded: the device ran them beside the host's iteration.",
        labels=("server",)),
    "overrun_slot_steps": _metrics.counter(
        "serving.overrun_slot_steps_total",
        "Slot-steps whose token was thrown away because the stream had "
        "ended on eos_id in the step before, which was still in flight "
        "when they were dispatched.", labels=("server",)),
}
#: the result publisher (_ResultPublisher): how far it is behind the loop
_M_PUBLISH_BACKLOG = _metrics.gauge(
    "serving.publish_backlog",
    "Result records handed to the publisher and not yet taken up for "
    "writing.", labels=("server",))
_M_STATE_SLOTS = _metrics.gauge(
    "serving.state_slots_in_use",
    "Slots whose recurrent state is live: resident streams and prompts "
    "being prefilled.", labels=("server",))
_M_SPARSE_READ = _metrics.histogram(
    "serving.sparse_positions_read",
    "Positions that a sparse-attention layer's gather read for one stream "
    "in one decode step, as the step program counted them: one "
    "observation a step.", labels=("server",))
_M_DSA_SCORED = _metrics.histogram(
    "serving.dsa_positions_scored",
    "Positions that a latent layer's indexer scored for one live stream in "
    "one decode step (the stream's context), the mean over the active "
    "slots and the layers, as the step program counted them: one "
    "observation a step.", labels=("server",))
_M_DSA_BLOCKS = _metrics.histogram(
    "serving.dsa_blocks_scored",
    "Pooled key blocks that a latent layer's indexer scored for one live "
    "stream in one decode step (the blocks wholly before its query), the "
    "mean over the active slots and the layers, as the step program "
    "counted them: one observation a step.", labels=("server",))
_M_PAGES_READ = _metrics.histogram(
    "serving.paged_pages_read",
    "KV pages that the decode step's attention read for all slots in one "
    "block, as the step program counted them from the lengths it was "
    "given: the live pages where the kernel reads them in place, the "
    "table's rectangle under the XLA form; one observation a step.",
    labels=("server",))
#: a model whose layers keep two page budgets (window layers beside full
#: ones) and routes tokens to experts (capture/decoder.py, ops/moe.py)
_M_PAGES_IN_USE = _metrics.gauge(
    "serving.kv_pages_in_use",
    "KV pages that streams or registered prefixes hold, by the kind of "
    "layer whose budget they come from: full (every model's one page "
    "table) or window (pages that lie behind a stream's window go back).",
    labels=("server", "kind"))
_M_WINDOW_RELEASED = _metrics.counter(
    "serving.window_pages_released_total",
    "Window-layer pages given back because they lay wholly behind their "
    "stream's window (not those freed by the stream's end).",
    labels=("server",))
_M_MOE_TOUCHED = _metrics.histogram(
    "serving.moe_experts_touched",
    "Distinct experts that a layer's active tokens were routed to in one "
    "decode step, the mean over the layers, as the step program counted "
    "them: one observation a step.", labels=("server",))
_M_MOE_LOAD = _metrics.histogram(
    "serving.moe_expert_load",
    "Assignments that the busiest expert of a layer got in one decode "
    "step over the mean of all experts, the mean over the layers: one "
    "observation a step.", labels=("server",))
_M_MOE_ASSIGNMENTS = _metrics.counter(
    "serving.moe_assignments_total",
    "Token-to-expert assignments of the decode steps, all layers, active "
    "slots only.", labels=("server",))
_M_BROWNOUT = _metrics.gauge(
    "serving.brownout_level",
    "Current brownout degradation rung: 0=normal, 1=coarse streaming/wide "
    "batch window, 2=half token budget, 3=quarter token budget "
    "(docs/serving.md 'Overload survival').", labels=("server",))

_instance_ids = itertools.count()

#: ops-plane event types (docs/observability.md "Ops plane") — one event
#: per state transition, replayed by the incident correlator
_E_BROWNOUT = ops_events.event_type(
    "serving.brownout_rung",
    "Brownout ladder rung change (level_from/level_to, pressure).")
_E_SHED = ops_events.event_type(
    "serving.shed",
    "Admission control shed the oldest requests (count, allowed depth).")
_E_RELOAD = ops_events.event_type(
    "serving.reload",
    "Hot model reload landed (ok=true, version) or rolled back "
    "(ok=false).")
_E_LIFECYCLE = ops_events.event_type(
    "serving.lifecycle",
    "Server reached a terminal lifecycle state "
    "(state=drained|stopped|crashed).")


class _Brownout:
    """Hysteretic brownout ladder (docs/serving.md "Overload survival").

    A feedback loop over the server's own pressure signal — queue fill
    against the shed-allowed depth, and KV-page scarcity for paged
    generative servers. ``tick(pressure)`` steps DOWN one rung whenever
    pressure exceeds ``serving.brownout_high`` and back UP one rung only
    after ``serving.brownout_hold_ticks`` consecutive ticks below
    ``serving.brownout_low`` — asymmetric on purpose: degrade fast,
    recover cautiously, never oscillate across a noisy boundary.

    The rungs trade answer *quality* for answer *existence*:

    - **L1** coarsens stream partials (4x ``stream_interval``) and widens
      the one-shot micro-batch window (2x ``batch_wait_ms``) — fewer
      queue writes and fuller batches at a small latency cost.
    - **L2** additionally caps new streams' ``max_new_tokens`` at
      2 x ``serving.brownout_token_frac`` of the configured budget and
      widens the batch window to 4x.
    - **L3** tightens the cap to ``serving.brownout_token_frac``.

    Speculative depth and int8 paged KV are BUILD-TIME levers (the step
    program and pool dtype are compiled/allocated at ``__init__``): an
    operator browning out a fleet applies them via config + rolling
    ``reload_model``, not live (see the docs table)."""

    MAX_LEVEL = 3
    #: batch-window multiplier per rung (one-shot micro-batching)
    _WINDOW = (1, 2, 4, 4)
    #: stream-partial stride multiplier per rung (generative)
    _STRIDE = (1, 4, 4, 4)

    def __init__(self, label: str = ""):
        cfg = global_config()
        self.high = float(cfg.get("serving.brownout_high"))
        self.low = float(cfg.get("serving.brownout_low"))
        self.hold_ticks = int(cfg.get("serving.brownout_hold_ticks"))
        self.token_frac = float(cfg.get("serving.brownout_token_frac"))
        self.label = label
        self.level = 0
        self._calm = 0

    def tick(self, pressure: float) -> int:
        prev = self.level
        if pressure > self.high:
            self._calm = 0
            if self.level < self.MAX_LEVEL:
                self.level += 1
        elif pressure < self.low:
            self._calm += 1
            if self._calm >= self.hold_ticks and self.level > 0:
                self.level -= 1
                self._calm = 0
        else:
            self._calm = 0
        if self.level != prev:
            _E_BROWNOUT.emit(label=self.label, level_from=prev,
                             level_to=self.level,
                             pressure=round(float(pressure), 4))
        return self.level

    def token_cap(self, budget: int) -> int:
        """Effective per-stream token budget at the current rung."""
        if self.level < 2:
            return budget
        frac = self.token_frac * (2.0 if self.level == 2 else 1.0)
        return max(1, min(budget, int(round(budget * frac))))

    def batch_window_ms(self, base_ms: float) -> float:
        return base_ms * self._WINDOW[self.level]

    def stream_stride(self, base: int) -> int:
        return base * self._STRIDE[self.level] if base > 0 else base


def _model_version_of(path: Optional[str]) -> str:
    """Version label for a servable path: its basename (snapshot export
    dirs are named by version), or ``inline-0`` for models handed over
    as live objects with no path to name them by."""
    base = os.path.basename(str(path or "").rstrip("/"))
    return base or "inline-0"


class ModelReloadError(RuntimeError):
    """``reload_model`` failed; the PREVIOUS model is still serving."""


def top_n(probs: np.ndarray, n: int) -> List[Dict[str, float]]:
    """Per-record topN (class, prob) filter (reference
    ``PostProcessing.scala``)."""
    idx = np.argsort(-probs)[:n]
    return [{"class": int(i), "prob": float(probs[i])} for i in idx]


class ClusterServing:
    #: min seconds between shed passes — a shed scans the backlog, and
    #: re-scanning every 5ms claim poll would double the spool listings
    #: (expensive on remote spools) for no added protection
    SHED_INTERVAL_S = 0.05

    def __init__(self, config: ServingConfig,
                 model: Optional[InferenceModel] = None,
                 queue: Optional[QueueBackend] = None):
        self.config = config
        self.queue = queue if queue is not None else make_queue(config.data_src)
        self.model = model if model is not None else self._load_model()
        # which snapshot is live: stamped here and on every successful
        # reload_model — the promotion canary verifies it via health
        self.model_version = _model_version_of(
            config.model_path if (model is None or config.model_path)
            else None)
        self._inline_versions = itertools.count(1)
        # compile warmth before traffic: the first claimed micro-batch must
        # hit an already-compiled program, not eat a multi-second XLA
        # compile while clients poll (InferenceModel.compile_counts proves
        # it — tests assert no NEW compile on the first request)
        self.prewarmed = self._prewarm_model()
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._pool = None
        self.records_served = 0
        self.device_seconds = 0.0  # dispatch→fetch time across batches
        # -- SLO bookkeeping --------------------------------------------------
        # counters/latency/gauges live in the process-global metrics
        # registry, one label per server instance (health_snapshot() and
        # the .counters property are views of it)
        self.metrics_label = f"srv{next(_instance_ids)}"
        self._m = {key: fam.labels(server=self.metrics_label)
                   for key, fam in _M_COUNTERS.items()}
        self._m_records = _M_RECORDS.labels(server=self.metrics_label)
        self._m_latency = _M_LATENCY.labels(server=self.metrics_label)
        self._m_depth = _M_QUEUE_DEPTH.labels(server=self.metrics_label)
        self._m_in_flight = _M_IN_FLIGHT.labels(server=self.metrics_label)
        self._m_claim_age = _M_CLAIM_AGE.labels(server=self.metrics_label)
        self._m_brownout = _M_BROWNOUT.labels(server=self.metrics_label)
        self._brownout = _Brownout(self.metrics_label)
        self._counter_lock = threading.Lock()
        self._in_flight = 0  # claimed, no terminal result yet
        #: uri -> (enqueue_t, trace_id) — latency base + flow-chain id
        self._meta: Dict[str, Tuple[float, Optional[int]]] = {}
        self._ewma_record_s = 0.0  # smoothed device seconds per record
        self._last_claim_m: Optional[float] = None  # monotonic
        self._last_health_m = -1e18
        self._last_shed_m = -1e18
        self._claim_fail_streak = 0
        self._loop_running = False
        self._terminal_state: Optional[str] = None
        self._reload_lock = threading.Lock()
        self._writer = None
        if config.log_dir:
            from ..utils.tensorboard import SummaryWriter
            self._writer = SummaryWriter(
                os.path.join(config.log_dir, "serving"))

    def _load_model(self, cfg: Optional[ServingConfig] = None
                    ) -> InferenceModel:
        cfg = cfg if cfg is not None else self.config
        im = InferenceModel(concurrent_num=cfg.concurrent_num)
        if cfg.model_type == "zoo":
            im.load_zoo(cfg.model_path)
        elif cfg.model_type == "savedmodel":
            im.load_savedmodel(cfg.model_path)
        elif cfg.model_type == "torch":
            im.load_torch(cfg.model_path)
        elif cfg.model_type == "onnx":
            im.load_onnx(cfg.model_path)
        elif cfg.model_type == "caffe":
            h, w, c = cfg.image_shape
            im.load_caffe(cfg.model_path, cfg.model_weight_path or None,
                          input_shape=(c, h, w))
        else:
            raise ValueError(f"unknown model_type {cfg.model_type}")
        if cfg.quantize:
            im.quantize(cfg.quantize)
        return im

    def _example_batch(self) -> np.ndarray:
        """A zeros batch shaped like ``_prepare``'s output: image records
        decode to ``image_shape`` arrays (uint8 or float32 per
        ``input_dtype``), tensor records are always float32."""
        cfg = self.config
        dtype = np.uint8 if cfg.input_dtype == "uint8" else np.float32
        return np.zeros((cfg.batch_size,) + tuple(cfg.image_shape), dtype)

    def _prewarm_model(self, model: Optional[InferenceModel] = None) -> bool:
        """AOT-compile the configured ``batch_size`` bucket at startup.
        A model whose forward rejects a zeros batch just logs and compiles
        lazily."""
        model = model if model is not None else self.model
        if not getattr(model, "prewarm", None):
            return False
        try:
            model.prewarm(self._example_batch(),
                          buckets=(self.config.batch_size,))
            return True
        except Exception:
            logger.exception(
                "startup prewarm failed; the first request at each shape "
                "bucket will pay the compile instead")
            return False

    # -- record prep ----------------------------------------------------------

    def _prepare(self, record: Dict[str, Any]) -> np.ndarray:
        # chaos site: a faulty decode must become THIS record's error
        # result (the _decode future handler), never kill the claim loop
        faults.inject("serving.decode")
        cfg = self.config
        if "image" in record:  # base64-encoded image bytes
            img = decode_image(record["image"])
            h, w = cfg.image_shape[0], cfg.image_shape[1]
            if img.shape[:2] != (h, w):
                import cv2
                img = cv2.resize(img, (w, h))
            # uint8 wire applies to IMAGES only (pixels are uint8 by nature)
            dtype = np.uint8 if cfg.input_dtype == "uint8" else np.float32
            return np.asarray(img, dtype)
        if "tensor" in record:  # raw numeric payload: always float32 — a
            # uint8 cast would silently truncate/wrap client floats
            return np.asarray(record["tensor"], np.float32)
        raise ValueError(f"record has neither image nor tensor: "
                         f"{sorted(record)}")

    def _decode_pool(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=self.config.decode_threads,
                thread_name_prefix="zoo-serving-decode")
        return self._pool

    def _shutdown_pool(self) -> None:
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    # -- SLO bookkeeping ------------------------------------------------------

    @property
    def counters(self) -> Dict[str, int]:
        """Instance view of the registry-backed SLO counters (same keys the
        old hand-rolled dict had, so supervisors/tests read it unchanged)."""
        return {key: int(c.value()) for key, c in self._m.items()}

    def _count(self, key: str, n: int = 1) -> None:
        self._m[key].inc(n)
        if key in ("shed", "expired"):
            # first SLO breach can arm a jax.profiler capture window
            # (profile.capture_on_breach) — cheap no-op otherwise
            _profiler.on_slo_breach(key)

    def _flow_uris(self, uris: List[str], stage: str) -> None:
        """Stamp one flow-chain point per uri (no-op unless a trace
        session is active — the lookup cost stays off the hot path)."""
        if not _trace.tracing():
            return
        with self._counter_lock:
            ids = [self._meta.get(u, (0.0, None))[1] for u in uris]
        for flow_id in ids:
            _trace.flow_point(flow_id, stage, "t")

    def _expiry(self, rec: Dict[str, Any]) -> Optional[float]:
        """Absolute wall-clock expiry for a record, or None when it has no
        deadline. Wall clock is deliberate: ``enqueue_t`` is stamped by the
        CLIENT process and the wall is the only clock two processes share;
        every purely-local interval in this file uses ``time.monotonic()``."""
        deadline_ms = rec.get("deadline_ms") or self.config.default_deadline_ms
        if not deadline_ms:
            return None
        t0 = rec.get("enqueue_t")
        base = float(t0) if t0 is not None else wall_clock()
        return base + float(deadline_ms) / 1000.0

    def _post_terminal(self, uri: str, value: Dict[str, Any]) -> None:
        """Every claimed request funnels its ONE terminal result (value or
        error) through here — latency and in-flight accounting included.
        Error terminals are stamped ``retriable``: shed errors are (the
        overload may clear), deadline/validation/shutdown are not — a
        retry would burn the fleet's retry budget on a certain failure."""
        if "error" in value and "retriable" not in value:
            value = dict(value)
            value["retriable"] = value["error"] in (SHED_ERROR,
                                                    PAGE_SHED_ERROR)
        try:
            self.queue.put_result(uri, value)
        except Exception:
            logger.exception("posting result for %s failed", uri)
        with self._counter_lock:
            self._in_flight = max(0, self._in_flight - 1)
            in_flight = self._in_flight
            meta = self._meta.pop(uri, None)
        self._m_in_flight.set(in_flight)
        if meta is not None:
            t0, flow_id = meta
            self._m_latency.observe(max(wall_clock() - t0, 0.0))
            # flow terminus: the request's lifecycle chain ends here
            _trace.flow_point(flow_id, "serving.result", "f")

    def _error_batch(self, uris: List[str], message: str,
                     counter: str = "errors") -> None:
        for uri in uris:
            self._post_terminal(uri, {"error": message})
        if uris:
            self._count(counter, len(uris))

    # -- pipeline stages ------------------------------------------------------

    def _shed(self) -> None:
        """Erroring admission control (replaces the silent trim): every
        dropped request gets an explicit shed error result. Two knobs:
        ``max_pending`` caps absolute depth; ``shed_wait_ms`` caps the
        ESTIMATED WAIT of the queue tail (depth x smoothed per-record
        service time) so a slow model sheds earlier than a fast one."""
        now = time.monotonic()
        if now - self._last_shed_m < self.SHED_INTERVAL_S:
            return
        self._last_shed_m = now
        cfg = self.config
        allowed = cfg.max_pending
        if cfg.shed_wait_ms:
            with self._counter_lock:
                per_record_s = self._ewma_record_s
            if per_record_s > 0:
                allowed = min(allowed, max(
                    cfg.batch_size,
                    int(cfg.shed_wait_ms / 1000.0 / per_record_s)))
        try:
            dropped = self.queue.shed(allowed, reason=SHED_ERROR)
        except OSError as e:
            logger.warning("shed pass failed (transient): %r", e)
            return
        # brownout feedback rides the shed cadence: queue fill against the
        # shed-allowed depth is the pressure signal (docs/serving.md)
        try:
            pending = self.queue.pending_count()
        except Exception:
            pending = None
        fill = (pending / float(max(allowed, 1))
                if pending is not None else 0.0)
        self._m_brownout.set(self._brownout.tick(fill))
        if dropped:
            self._count("shed", len(dropped))
            _E_SHED.emit(label=self.metrics_label, count=len(dropped),
                         allowed=allowed)
            logger.warning(
                "overload: shed %d oldest requests with error results "
                "(allowed depth %d)", len(dropped), allowed)

    def _claim(self) -> List[Tuple[str, Dict[str, Any]]]:
        """Claim up to one micro-batch: shed first, then fill the batch
        within the ``batch_wait_ms`` window on the MONOTONIC clock (a
        wall-clock step must not warp the batch window). A transient
        claim failure (flaky backend, injected ``serving.claim`` fault) is
        absorbed and retried; ``claim_retries`` consecutive failures
        surface the backend as dead."""
        cfg = self.config
        self._shed()
        # brownout L1+: widen the micro-batch window — fuller batches
        # amortize dispatch overhead exactly when the queue is deepest
        wait_ms = self._brownout.batch_window_ms(cfg.batch_wait_ms)
        deadline = time.monotonic() + wait_ms / 1000.0
        batch: List[Tuple[str, Dict[str, Any]]] = []
        while len(batch) < cfg.batch_size and time.monotonic() < deadline:
            try:
                # chaos site: a flaky queue backend must be retried, not
                # kill the serve loop
                faults.inject("serving.claim")
                got = self.queue.claim_batch(cfg.batch_size - len(batch))
                self._claim_fail_streak = 0
            except OSError as e:
                self._count("claim_faults")
                self._claim_fail_streak += 1
                if self._claim_fail_streak > cfg.claim_retries:
                    raise  # dead backend, not a flaky one: surface it
                logger.warning("transient claim failure (%d/%d): %r",
                               self._claim_fail_streak, cfg.claim_retries, e)
                # full-jitter backoff on the fail streak: N servers that
                # all saw the same queue hiccup must not re-claim in
                # lockstep (the retry-discipline lint polices this shape)
                time.sleep(np.random.uniform(
                    0.0, 0.002 * (2 ** min(self._claim_fail_streak, 6))))
                continue
            if got:
                self._last_claim_m = time.monotonic()
                batch.extend(got)
            elif not batch:
                break  # nothing pending at all
            else:
                time.sleep(0.001)
        if batch:
            now = wall_clock()
            with self._counter_lock:
                self._in_flight += len(batch)
                in_flight = self._in_flight
                for uri, rec in batch:
                    self._meta[uri] = (float(rec.get("enqueue_t") or now),
                                       rec.get("trace_id"))
            self._m_in_flight.set(in_flight)
            if _trace.tracing():
                for uri, rec in batch:
                    _trace.flow_point(rec.get("trace_id"),
                                      "serving.claim", "t")
        return batch

    def _filter_expired(self, batch: List[Tuple[str, Dict[str, Any]]]
                        ) -> List[Tuple[str, Dict[str, Any]]]:
        """Deadline check at claim: already-expired records answer the
        deadline error immediately — no decode, no device time."""
        if not batch:
            return batch
        now = wall_clock()
        live, expired = [], []
        for uri, rec in batch:
            exp = self._expiry(rec)
            (expired if exp is not None and now >= exp
             else live).append((uri, rec))
        if expired:
            self._error_batch([u for u, _ in expired], DEADLINE_ERROR,
                              counter="expired")
        return live

    def _decode(self, batch: List):
        """Decode a claimed batch on the thread pool (cv2 releases the GIL);
        undecodable records become error results immediately, and records
        whose deadline expired DURING decode answer the deadline error
        instead of riding to the device."""
        uris, arrays, expiries = [], [], []
        errors, expired = [], []
        tracing = _trace.tracing()
        t_dec = time.perf_counter()
        with time_it("serving.decode_batch"):
            futures = [(uri, rec,
                        self._decode_pool().submit(self._prepare, rec))
                       for uri, rec in batch]
            for uri, rec, fut in futures:
                try:
                    arr = fut.result()
                except Exception as e:  # undecodable record → error result
                    errors.append((uri, str(e)))
                    continue
                if tracing:
                    _trace.flow_point(rec.get("trace_id"),
                                      "serving.decode", "t")
                exp = self._expiry(rec)
                if exp is not None and wall_clock() >= exp:
                    expired.append(uri)
                    continue
                uris.append(uri)
                arrays.append(arr)
                expiries.append(exp)
        _profiler.record_phase("serving", "host_input",
                               time.perf_counter() - t_dec, start=t_dec)
        for uri, msg in errors:
            self._post_terminal(uri, {"error": msg})
        if errors:
            self._count("errors", len(errors))
        self._error_batch(expired, DEADLINE_ERROR, counter="expired")
        return uris, arrays, expiries

    def _expire_before_dispatch(self, uris: List[str], x: np.ndarray,
                                expiries: List[Optional[float]]):
        """Last deadline check, right before device dispatch — queueing
        inside the pipeline must not launder expired work onto the chip."""
        now = wall_clock()
        keep = [i for i, e in enumerate(expiries) if e is None or now < e]
        if len(keep) == len(uris):
            return uris, x
        kept = set(keep)
        self._error_batch([u for i, u in enumerate(uris) if i not in kept],
                          DEADLINE_ERROR, counter="expired")
        if not keep:
            return [], x[:0]
        return [uris[i] for i in keep], x[keep]

    def _dispatch(self, x: np.ndarray):
        """Async device dispatch for one decoded batch. Single choke point
        for the ``serving.predict`` chaos site: callers catch any failure
        and post per-uri error results so one bad batch cannot take the
        loop (or its batch's clients) down with it."""
        faults.inject("serving.predict")
        t_d = time.perf_counter()
        with time_it("serving.dispatch_batch"):
            handle = self.model.predict_async(x)
        _profiler.record_phase("serving", "dispatch",
                               time.perf_counter() - t_d, start=t_d)
        return handle

    def _writeback(self, uris: List[str], probs: np.ndarray,
                   device_elapsed: float) -> None:
        # chaos site: a failed writeback must error its batch and keep the
        # server draining (the writeback thread's per-batch catch)
        faults.inject("serving.writeback")
        cfg = self.config
        with time_it("serving.writeback_batch"):
            for uri, p in zip(uris, probs):
                p = np.asarray(p).reshape(-1)
                if cfg.filter_top_n:
                    self._post_terminal(uri,
                                        {"topN": top_n(p, cfg.filter_top_n)})
                else:
                    self._post_terminal(uri, {"value": p.tolist()})
        self._m_records.inc(len(uris))
        self.records_served += len(uris)
        self.device_seconds += device_elapsed
        if uris:
            per = device_elapsed / len(uris)
            with self._counter_lock:
                self._ewma_record_s = (
                    per if self._ewma_record_s == 0.0
                    else 0.8 * self._ewma_record_s + 0.2 * per)
        if self._writer is not None:
            self._writer.add_scalar("Serving Throughput",
                                    len(uris) / max(device_elapsed, 1e-9),
                                    self.records_served)
            self._writer.add_scalar("Total Records Number",
                                    self.records_served, self.records_served)

    def _force_sentinel(self, q) -> None:
        """Land a ``None`` sentinel on a possibly-full queue. Any real
        in-flight item displaced to make room was already CLAIMED from the
        spool — its requests get error results rather than vanishing (the
        client would otherwise poll to its timeout)."""
        import queue as pyqueue
        while True:
            try:
                q.put(None, timeout=0.2)
                return
            except pyqueue.Full:
                try:
                    item = q.get_nowait()
                except pyqueue.Empty:
                    continue
                if item is None:
                    continue
                self._error_batch(list(item[0]), SHUTDOWN_ERROR)

    # -- deep health ----------------------------------------------------------

    def health_snapshot(self) -> Dict[str, Any]:
        """Structured deep-health snapshot: lifecycle state, queue depth,
        last-claim age, in-flight count, p50/p99 terminal latency, and the
        shed/expired/error counters. Supervisors consume the same dict as
        the periodically-written ``config.health_path`` file; tests consume
        it directly. (``check_health()`` remains the narrow liveness probe
        that re-raises a crashed background loop.)

        This is a per-instance VIEW of the shared metrics registry
        (``common.metrics.metrics_snapshot()``): the counters and the
        latency histogram live there, scrapable as Prometheus text via the
        ``metrics.prom`` file written next to ``health.json``. On an empty
        latency window ``p50``/``p99`` are ``null`` — never a fake
        ``0.0`` (see docs/observability.md)."""
        with self._counter_lock:
            in_flight = self._in_flight
        counters = self.counters

        def _pct(p: float) -> Optional[float]:
            v = self._m_latency.percentile(p)
            return None if v is None else round(v * 1e3, 3)

        err = getattr(self, "_background_error", None)
        if self._terminal_state is not None:
            state = self._terminal_state
        elif err is not None:
            state = "crashed"
        elif self._draining.is_set():
            state = "draining"
        elif self._loop_running or (self._thread is not None
                                    and self._thread.is_alive()):
            state = "running"
        else:
            state = "idle"
        try:
            pending = self.queue.pending_count()
        except Exception:
            pending = None
        now_m = time.monotonic()
        claim_age = (round(now_m - self._last_claim_m, 3)
                     if self._last_claim_m is not None else None)
        # refresh the point-in-time gauges on the same cadence the
        # snapshot is taken (scrapers read them from metrics.prom)
        if pending is not None:
            self._m_depth.set(pending)
        self._m_in_flight.set(in_flight)
        if claim_age is not None:
            self._m_claim_age.set(claim_age)
        with self._counter_lock:
            ewma = self._ewma_record_s
        return {
            "state": state,
            "time": wall_clock(),
            "queue_pending": pending,
            "in_flight": in_flight,
            "records_served": self.records_served,
            "device_seconds": round(self.device_seconds, 4),
            "service_time_s_ewma": (round(ewma, 6) if ewma > 0 else None),
            "brownout_level": self._brownout.level,
            "last_claim_age_s": claim_age,
            "latency_ms": {"p50": _pct(0.50), "p99": _pct(0.99),
                           "window": self._m_latency.count()},
            "counters": counters,
            "prewarmed": self.prewarmed,
            "model_version": self.model_version,
            "alerts": sorted(ops_alerts.active_alerts()),
            "incident": ops_incident.last_incident(),
            "error": repr(err) if err is not None else None,
        }

    def _write_health(self) -> None:
        path = self.config.health_path
        if not path:
            return
        # health cadence doubles as the profiler's slow tick: refresh the
        # HBM/RSS/build-info gauges so they land in THIS metrics.prom, and
        # close any elapsed time-bounded capture window (a quiet queue sees
        # no step boundaries)
        try:
            _profiler.sample_memory()
            _profiler.maybe_stop_capture()
        except Exception:
            logger.debug("profiler health tick failed", exc_info=True)
        tmp = path + ".tmp"
        try:
            with file_io.fopen(tmp, "w") as f:
                f.write(json.dumps(self.health_snapshot()))
            file_io.replace(tmp, path)  # atomic: readers never see a tear
        except OSError:
            logger.warning("health write to %s failed", path)
        # Prometheus exposition rides the same cadence: metrics.prom next
        # to health.json, for a node-exporter textfile collector / sidecar
        sep = "/" if "/" in path or "://" in path else os.sep
        prom = path.rsplit(sep, 1)[0] + sep + "metrics.prom" \
            if sep in path else "metrics.prom"
        tmp = prom + ".tmp"
        try:
            with file_io.fopen(tmp, "w") as f:
                f.write(_metrics.expose_text())
            file_io.replace(tmp, prom)
        except OSError:
            logger.warning("metrics write to %s failed", prom)

    def _maybe_write_health(self) -> None:
        if not self.config.health_path:
            return
        now = time.monotonic()
        if now - self._last_health_m >= self.config.health_interval_s:
            self._last_health_m = now
            self._write_health()

    # -- hot model reload -----------------------------------------------------

    def reload_model(self, model_path: Optional[str] = None, *,
                     model: Optional[InferenceModel] = None,
                     model_type: Optional[str] = None,
                     version: Optional[str] = None) -> InferenceModel:
        """Hot-swap the serving model with canary + rollback. The candidate
        loads and prewarms OFF the serve path (the old model keeps serving
        the whole time), canary-predicts one synthetic batch, and only then
        swaps in — a single attribute store, atomic under the GIL, so no
        request is ever dropped or misrouted: in-flight batches hold a
        reference to whichever model dispatched them. ANY failure (load,
        prewarm, canary, injected ``serving.reload`` chaos) leaves the old
        model serving and raises :class:`ModelReloadError`."""
        with self._reload_lock:
            old = self.model
            cfg = self.config
            try:
                # chaos site: a reload that dies anywhere must roll back
                faults.inject("serving.reload")
                if model is None:
                    if model_path is None:
                        raise ValueError(
                            "reload_model needs model_path= or model=")
                    import dataclasses
                    model = self._load_model(dataclasses.replace(
                        cfg, model_path=model_path,
                        model_type=model_type or cfg.model_type))
                # prewarm + canary off the serve path: the swap only
                # happens once the candidate has proven it can answer
                self._prewarm_model(model)
                example = self._example_batch()
                canary = model.predict(example)
                import jax
                leaves = jax.tree_util.tree_leaves(canary)
                if not leaves:
                    raise ValueError("canary predict returned no outputs")
                for leaf in leaves:
                    a = np.asarray(leaf)
                    if a.shape[0] != cfg.batch_size:
                        raise ValueError(
                            f"canary predict returned leading dim "
                            f"{a.shape[0]} for a batch of {cfg.batch_size}")
                    if np.issubdtype(a.dtype, np.floating) \
                            and not np.isfinite(a).all():
                        raise ValueError(
                            "canary predict produced non-finite values")
                self.model = model  # atomic swap: next dispatch uses it
                if model_path is not None:
                    cfg.model_path = model_path
                    if model_type:
                        cfg.model_type = model_type
                # stamp only on success: a failed reload leaves both the
                # old model AND its version label live
                if version is not None:
                    self.model_version = version
                elif model_path is not None:
                    self.model_version = _model_version_of(model_path)
                else:
                    self.model_version = \
                        f"inline-{next(self._inline_versions)}"
                self._count("reloads")
                _E_RELOAD.emit(label=self.metrics_label, ok=True,
                               version=self.model_version)
                logger.info("model reloaded%s",
                            f" from {model_path}" if model_path else "")
                return model
            except Exception as e:
                self.model = old  # rollback (no-op unless a partial swap)
                self._count("reload_failures")
                _E_RELOAD.emit(label=self.metrics_label, ok=False,
                               version=self.model_version)
                logger.exception(
                    "model reload failed; previous model still serving")
                raise ModelReloadError(
                    f"model reload failed ({e!r}); previous model still "
                    f"serving") from e

    # -- the serve loop -------------------------------------------------------

    def serve_once(self) -> int:
        """One synchronous micro-batch (claim → decode → predict →
        writeback); returns the number of records claimed — every one of
        them receives a terminal result (value, deadline error, decode
        error, or predict error) before this returns. ``run()`` pipelines
        these stages — this method is the single-step form for tests and
        manual driving."""
        batch = self._claim()
        self._maybe_write_health()
        if not batch:
            return 0
        claimed = len(batch)
        uris, arrays, expiries = self._decode(self._filter_expired(batch))
        if arrays:
            x = np.stack(arrays)
            uris, x = self._expire_before_dispatch(uris, x, expiries)
            if uris:
                start = time.perf_counter()
                try:
                    self._flow_uris(uris, "serving.dispatch")
                    fetch = self._dispatch(x)
                    probs = np.asarray(fetch())
                    self._writeback(uris, probs,
                                    time.perf_counter() - start)
                except Exception as e:
                    logger.exception("predict/writeback failed for %d "
                                     "records", len(uris))
                    self._error_batch(uris, repr(e))
        return claimed

    def run(self, poll_interval_s: float = 0.005) -> None:
        """Pipelined serve loop: a claim+decode thread feeds the dispatch
        stage, and a writeback thread drains device results — batch N+1
        decodes on host threads while batch N runs on the device and batch
        N-1's results upload (the reference runs decode serially inside the
        structured-streaming micro-batch, ``ClusterServing.scala:160-259``;
        overlapping the stages is what keeps a fast chip fed)."""
        import queue as pyqueue

        logger.info("serving started (src=%s batch=%d)",
                    self.config.data_src, self.config.batch_size)
        ops_alerts.ensure_default()  # no-op unless ops.enabled
        self._terminal_state = None
        self._loop_running = True
        # a fresh loop gets an immediate admission pass: a backlog that
        # piled up while the server was down must shed BEFORE it is
        # claimed, not ride through because the previous loop's shed
        # stamp is still inside the interval gate
        self._last_shed_m = -1e18
        decoded_q: "pyqueue.Queue" = pyqueue.Queue(maxsize=2)
        fetch_q: "pyqueue.Queue" = pyqueue.Queue(maxsize=2)
        errors: List[BaseException] = []
        dead = threading.Event()  # any stage died — unblock everyone

        def _put(q: "pyqueue.Queue", item) -> bool:
            """Bounded put that can never wedge the pipeline: gives up when
            the loop is stopping or a peer stage has died. Monotonic-clock
            stall accounting — wall steps must not mask a wedged stage."""
            start = time.monotonic()
            while not dead.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except pyqueue.Full:
                    if time.monotonic() - start > 30:
                        logger.warning(
                            "pipeline stage blocked handing off a batch "
                            "for %.0fs", time.monotonic() - start)
                        start = time.monotonic()
                    continue
            return False

        def decoder() -> None:
            try:
                while not self._stop.is_set() and not dead.is_set():
                    if self._draining.is_set():
                        return  # drain: stop CLAIMING; sentinel flushes
                    self._maybe_write_health()
                    batch = self._filter_expired(self._claim())
                    if not batch:
                        time.sleep(poll_interval_s)
                        continue
                    uris, arrays, expiries = self._decode(batch)
                    if arrays and not _put(decoded_q,
                                           (uris, np.stack(arrays),
                                            expiries)):
                        self._error_batch(uris, SHUTDOWN_ERROR)
                        return
            except BaseException as e:  # pragma: no cover - surfaced below
                errors.append(e)
                dead.set()
            finally:
                self._force_sentinel(decoded_q)

        def writeback() -> None:
            while True:
                item = fetch_q.get()
                if item is None:
                    return
                uris, fetch = item
                try:
                    t0 = time.perf_counter()
                    probs = fetch()  # blocks on the device fetch only
                    elapsed = time.perf_counter() - t0
                    # device execute + transfer both resolve inside fetch()
                    # on the async path; attribute the blocked time there
                    _profiler.record_phase("serving", "fetch", elapsed,
                                           start=t0)
                    self._writeback(uris, np.asarray(probs), elapsed)
                except BaseException as e:
                    # one failed batch must not wedge the server: record
                    # error results and keep draining
                    logger.exception("writeback failed for %d records",
                                     len(uris))
                    self._error_batch(list(uris), repr(e))

        threads = [threading.Thread(target=decoder, daemon=True,
                                    name="zoo-serving-claim"),
                   threading.Thread(target=writeback, daemon=True,
                                    name="zoo-serving-writeback")]
        for t in threads:
            t.start()
        try:
            while True:
                item = decoded_q.get()
                if item is None:
                    break
                uris, x, expiries = item
                uris, x = self._expire_before_dispatch(uris, x, expiries)
                if not uris:
                    continue
                # async dispatch: the device computes while the NEXT batch
                # decodes and the PREVIOUS batch's fetch+writeback runs
                try:
                    self._flow_uris(uris, "serving.dispatch")
                    fetch = self._dispatch(x)
                except Exception as e:
                    logger.exception("dispatch failed for %d records",
                                     len(uris))
                    self._error_batch(uris, repr(e))
                    continue
                if not _put(fetch_q, (uris, fetch)):
                    self._error_batch(uris, SHUTDOWN_ERROR)
                    break
        finally:
            drained = (self._draining.is_set() and not dead.is_set()
                       and not errors)
            self._stop.set()
            dead.set()
            self._force_sentinel(fetch_q)
            for t in threads:
                t.join(timeout=10)
            self._shutdown_pool()
            self._loop_running = False
            self._terminal_state = ("crashed" if errors
                                    else "drained" if drained else "stopped")
            _E_LIFECYCLE.emit(label=self.metrics_label,
                              state=self._terminal_state)
            self._write_health()
        if errors:
            raise errors[0]
        if self._writer is not None:
            self._writer.flush()

    def start(self) -> "ClusterServing":
        """Run the loop in a background thread (the spark-submit long-running
        job role). A crash in the loop is captured and re-raised from
        :meth:`stop` / :meth:`check_health` — a dead queue backend must not
        kill the server silently."""
        ops_alerts.ensure_default()  # no-op unless ops.enabled
        self._stop.clear()
        self._draining.clear()
        self._terminal_state = None
        self._background_error: Optional[BaseException] = None

        def _run() -> None:
            try:
                self.run()
            except BaseException as e:
                logger.exception("serving loop died")
                self._background_error = e

        self._thread = threading.Thread(target=_run, daemon=True)
        self._thread.start()
        return self

    def check_health(self) -> None:
        """Raise the background loop's failure, if any (liveness probe for
        supervisors driving :meth:`start`; :meth:`health_snapshot` is the
        rich readiness/depth surface)."""
        err = getattr(self, "_background_error", None)
        if err is not None:
            raise RuntimeError("serving loop died in the background") from err

    def drain(self, timeout_s: float = 30.0) -> None:
        """Graceful shutdown, distinct from the hard :meth:`stop`: stop
        CLAIMING new requests, finish every in-flight batch, flush all
        results, then write the terminal ``health.json`` state. A drained
        server has answered everything it ever claimed — zero shutdown
        errors. Called on a foreground :meth:`run` (e.g. from the SIGTERM
        handler) it just flags the loop, which unwinds and finalizes
        itself."""
        self._draining.set()
        if self._loop_running and self._thread is None:
            return  # foreground run(): the loop finalizes itself
        t = self._thread
        if t is not None:
            t.join(timeout=timeout_s)
            if t.is_alive():
                raise RuntimeError(
                    f"drain did not complete within {timeout_s}s "
                    f"({self._in_flight} requests still in flight)")
            self._thread = None
        self._shutdown_pool()
        if self._terminal_state is None:
            self._terminal_state = "drained"
            _E_LIFECYCLE.emit(label=self.metrics_label, state="drained")
        self._write_health()
        self.check_health()

    def stop(self) -> None:
        """Hard stop: the loop exits as fast as it can; displaced in-flight
        work is answered with explicit shutdown errors (never silently
        dropped). Use :meth:`drain` for deploys."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                # a wedged backend (claim blocked on a dead connection) is as
                # dead as a crashed one — don't report a clean shutdown
                self._thread = None
                raise RuntimeError(
                    "serving loop did not shut down within 10s (queue "
                    "backend wedged?); thread leaked")
            self._thread = None
        self._shutdown_pool()
        if self._terminal_state is None:
            self._terminal_state = "stopped"
            _E_LIFECYCLE.emit(label=self.metrics_label, state="stopped")
        self._write_health()
        self.check_health()


class _ResultPublisher:
    """Lands a ``GenerativeServing``'s result records, off the serve loop.

    The loop hands a record over and goes on to the next decode step; one
    daemon thread calls ``queue.put_result``, so a decode iteration does
    not wait for one write a stream a token. What a client can read is
    what the loop itself used to write, under three rules:

    - **the newest record a stream**: at most one partial a uri is
      pending. A partial still pending when the next one of its stream
      falls due is replaced (it carries the whole stream so far and the
      write overwrites one idempotent record), so a publisher that keeps
      up writes one record a due partial and one that does not writes the
      tokens folded since that stream's last write;
    - **terminals are never merged, dropped or reordered**: a terminal
      takes the place of its uri's pending partial, is written before any
      pending partial of another stream and in the order it was handed
      over, and its accounting (``GenerativeServing._settle``) runs when
      it has landed;
    - **back-pressure**: pending partials are bounded by the slots; once
      more than ``slots`` terminals are pending (a wedged backend) the
      hand-over blocks, as the write itself used to.

    The thread starts with the first record and :meth:`close` joins it
    when everything handed over has landed; an exception that kills it
    reaches the server's ``_background_error`` and every later hand-over.
    All state is under one condition: the loop (or, with no loop running,
    the thread that steps, stops or hands off) is the one producer."""

    def __init__(self, server: "GenerativeServing"):
        self._srv = server
        self._cv = threading.Condition()
        # (uri, value, folded, first_claim), oldest first
        self._terminals: Deque[Tuple[str, Dict[str, Any], float,
                                     Optional[float]]] = deque()
        # uri -> (tokens, n, seed, folded, first_claim), in the order each
        # uri first fell due: a replaced partial keeps its place in line
        self._partials: Dict[str, Tuple[List[int], int, Optional[int],
                                        float, Optional[float]]] = {}
        self._thread: Optional[threading.Thread] = None
        self._closing = False
        self._dead: Optional[BaseException] = None

    def _note_backlog(self) -> None:
        self._srv._m_backlog.set(len(self._terminals) + len(self._partials))

    def _handed_over(self) -> None:
        """With the condition held, after a record went in: the gauge,
        and a thread to take it."""
        self._note_backlog()
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name=f"{self._srv.metrics_label}-publisher")
            self._thread.start()
        self._cv.notify_all()

    def _require_alive(self) -> None:
        if self._dead is not None:
            raise RuntimeError("the result publisher died") from self._dead

    def partials(self, due: List[Tuple[str, List[int], int, Optional[int],
                                       Optional[float]]],
                 folded: float) -> None:
        """One step's due partials, ``(uri, tokens, n, seed, first_claim)``
        each: the stream's token list with the length that counts (the
        list only grows until its slot is cleared, so the copy and the
        serialisation are the publisher's), ``folded`` on ``perf_counter``
        when the newest of them was folded, and the stream's claim time
        where this record is the first to carry a token of it."""
        superseded = 0
        with self._cv:
            self._require_alive()
            for uri, tokens, n, seed, first in due:
                old = self._partials.get(uri)
                if old is not None:
                    superseded += 1
                    if first is None:  # its first token is still unwritten
                        first = old[4]
                self._partials[uri] = (tokens, n, seed, folded, first)
            self._handed_over()
        if superseded:
            self._srv._count("partials_superseded", superseded)

    def terminal(self, uri: str, value: Dict[str, Any], folded: float,
                 first: Optional[float] = None) -> None:
        with self._cv:
            self._require_alive()
            old = self._partials.pop(uri, None)
            if old is not None and first is None:
                first = old[4]
            self._terminals.append((uri, value, folded, first))
            self._handed_over()
            while (len(self._terminals) > self._srv.slots
                   and self._dead is None):
                self._cv.wait()
        if old is not None:
            self._srv._count("partials_superseded")

    def drop_partials(self) -> None:
        """Forget every pending partial (:meth:`GenerativeServing.handoff`:
        the streams go to another instance, whose records a late write of
        this one must not overwrite). Terminals stay."""
        with self._cv:
            self._partials.clear()
            self._note_backlog()

    def close(self) -> None:
        """Returns when every record handed over has landed (pending
        partials too) and the thread has ended; the next record starts a
        new one."""
        with self._cv:
            thread = self._thread
            if thread is None:
                return
            self._closing = True
            self._cv.notify_all()
        thread.join()

    def _run(self) -> None:
        try:
            while True:
                with self._cv:
                    while not (self._terminals or self._partials):
                        if self._closing:
                            self._thread, self._closing = None, False
                            return
                        self._cv.wait()
                    if self._terminals:
                        uri, value, folded, first = self._terminals.popleft()
                        record = None
                    else:
                        uri = next(iter(self._partials))
                        record = self._partials.pop(uri)
                    self._note_backlog()
                    self._cv.notify_all()  # a hand-over that was blocked
                if record is not None:
                    tokens, n, seed, folded, first = record
                    value = {"stream": tokens[:n], "done": False}
                    if seed is not None:
                        value["seed"] = seed
                self._land(uri, value, record is None, folded, first)
        except Exception as e:
            logger.exception("the result publisher died")
            self._srv._background_error = e
            with self._cv:
                self._dead, self._thread = e, None
                self._cv.notify_all()

    def _land(self, uri: str, value: Dict[str, Any], terminal: bool,
              folded: float, first: Optional[float]) -> None:
        srv = self._srv
        request = srv._request_of(uri)
        try:
            with time_it("serve.put_result", request=request):
                srv.queue.put_result(uri, value)
        except Exception:
            logger.exception("posting result for %s failed" if terminal
                             else "partial result for %s failed", uri)
        else:
            if _utils.span_hooks:
                _utils.offer_span("serve.publish_lag", folded,
                                  time.perf_counter() - folded,
                                  request=request, life=True)
        if first is not None and _utils.span_hooks:
            _utils.offer_span("serve.first_token", first,
                              time.perf_counter() - first,
                              request=request, life=True)
        if terminal:
            srv._settle(uri)


class _WindowPages:
    """The window layers' page budget: a table row a slot and a free list
    of its own, beside the page table that the full layers (and every other
    model) use. A stream holds the pages its window can lie on and no more:
    pages are handed out as its positions are written (:meth:`cover`) and
    go back once they lie wholly behind the window of its next query
    (:meth:`behind`). The budget is derived so that it cannot run out
    (``LayeredDecoder.window_pages``); serve-loop thread only."""

    def __init__(self, slots: int, width: int, num_pages: int,
                 page_len: int, window: int):
        self.rows = np.zeros((slots, width), np.int32)
        self.free = list(range(num_pages - 1, 0, -1))  # page 0: null page
        self.num_pages, self.page_len, self.window = (num_pages, page_len,
                                                      window)

    def in_use(self) -> int:
        return self.num_pages - 1 - len(self.free)

    def cover(self, slot: int, first: int, end: int) -> None:
        """A page for every position in ``[first, end)`` that has none
        (positions past the table's width land on the null page)."""
        row = self.rows[slot]
        for j in range(first // self.page_len,
                       min(-(-end // self.page_len), len(row))):
            if not row[j]:
                if not self.free:
                    raise RuntimeError(
                        "the window layers' page budget ran out: it is "
                        "derived so that it cannot (a bug)")
                row[j] = self.free.pop()

    def _give_back(self, slot: int, where) -> int:
        row = self.rows[slot]
        pages = row[where]
        pages = pages[pages != 0]
        self.free.extend(int(p) for p in pages)
        row[where] = 0
        return len(pages)

    def behind(self, slot: int, query: int) -> int:
        """Give back the pages whose last position the query at ``query``
        no longer sees; returns how many."""
        last = (query - self.window - self.page_len + 1) // self.page_len
        if last < 0 or not self.rows[slot, :last + 1].any():
            return 0
        return self._give_back(slot, slice(0, last + 1))

    def beyond(self, slot: int, position: int) -> None:
        """Give back the pages past the one that holds ``position``: what a
        last chunk's padding was written to."""
        self._give_back(slot, slice(position // self.page_len + 1, None))

    def release(self, slot: int) -> None:
        self._give_back(slot, slice(None))

    def table(self, active: np.ndarray) -> np.ndarray:
        """The rows of the resident streams (a prompt still joining reads
        its own row in its chunks; a step must not write through it)."""
        return np.where(active[:, None], self.rows, 0)


class _Stream:
    """What the serve loop keeps of one request while it decodes. A stream
    is resident in ``slot`` until its last step has been dispatched or it
    ends another way; a step in flight holds the streams it stepped, so a
    token finds its stream whoever has the slot by then. Serve-loop thread
    only."""

    __slots__ = ("uri", "slot", "tokens", "budget", "dispatched", "expires",
                 "enqueue_t", "first_t", "claim_pc", "streamed", "keys",
                 "prompt", "seed", "deadline_ms", "ended")

    def __init__(self, uri: str, slot: int, prompt: List[int],
                 prefix: List[int], budget: int):
        self.uri, self.slot, self.prompt = uri, slot, prompt
        self.tokens = list(prefix)
        self.budget = budget
        # positions handed to the device: what the NEXT step does (its
        # sampling key, its window, whether there is one) goes by this
        # count, not by the tokens folded, which lag it by the step in
        # flight
        self.dispatched = len(prefix)
        self.streamed = len(prefix)
        self.expires: Optional[float] = None
        self.enqueue_t = 0.0
        self.first_t: Optional[float] = None
        self.claim_pc = 0.0
        self.keys: Optional[np.ndarray] = None
        self.seed: Optional[int] = None
        self.deadline_ms: Optional[float] = None
        # why it takes no more tokens: "budget" or "eos" (its value is
        # posted), "gone" (an error, or another instance's now)
        self.ended: Optional[str] = None


class GenerativeServing:
    """Token-level continuous batching for ``TransformerLM`` generation.

    ``ClusterServing`` is one-request-one-predict: a full decode occupies
    the device while other requests queue, so utilization collapses under
    load. This scheduler keeps ``config.slots`` streams RESIDENT in one
    slot-batched KV cache (``ops/decode.py``) and advances all of them
    with ONE fused device step per token; requests join free slots and
    finished/expired streams are evicted EVERY step, not between requests.
    All device shapes are static — slot indices, lengths and occupancy are
    data — so the step program compiles once and prefill compiles once per
    length bucket (``capture/lm.py PREFILL_BUCKETS``).

    The PR 4 SLO invariant carries over per token: every claimed request
    gets exactly one terminal result (``{"value": tokens}`` or an error),
    deadlines are checked every step (an expired stream is evicted
    mid-flight with a deadline error), overload sheds by the estimated
    queue wait at the CURRENT smoothed tokens/s, and ``drain()`` stops
    admitting but finishes in-flight streams. Partial results
    (``{"stream": [...], "done": false}``) are idempotent overwrites of
    the same result record — they are progress, not terminals — and
    ``OutputQueue.stream()`` turns them into a client-side generator.

    One KV engine: a global page pool + per-slot page tables
    (``ops/decode.py`` paged ops) — HBM is paid per ALLOCATED page, not
    per slot, so concurrency scales with actual stream lengths.
    ``config.kv_pages`` is the pool's size, a deployment's memory budget;
    left ``None`` it is worked out so that every slot can reach
    ``max_len`` and no join sheds for want of pages. Joins allocate pages
    (shedding with ``PAGE_SHED_ERROR`` on exhaustion — the
    ``serving.page_alloc`` fault site), retirement refcounts them back.
    ``register_prefix()`` shares a common prompt's pages across streams
    with copy-on-write tails; ``config.kv_int8`` stores the pool in int8
    with delayed scaling; ``config.spec_k`` + a ``draft_lm`` makes the
    step a speculative draft/verify round (greedy-only, token-identical
    to serial greedy).

    One step ahead: inside :meth:`run` the loop keeps ONE decode step in
    flight. Step N+1 is dispatched from the device's own tokens of step N
    (the host names a token only for a slot joined since) before step N's
    are fetched and folded, so the device's step and the host's iteration
    overlap. The host counts the positions it has dispatched
    (``_Stream.dispatched``): a stream whose budget a step reaches leaves
    its slot at that dispatch; one that ends on ``eos_id`` is seen a step
    late and its one overrun token is thrown away. ``serve_step()`` by
    hand and speculative rounds fold at once (docs/serving.md "One step
    ahead"; tests/test_step_ahead.py).

    Decode parity: served streams are BIT-IDENTICAL to serial
    ``TransformerLM.generate()`` runs on the CPU — both share the bucketed
    prefill (``prefill_kv``), the ``make_logit_filter`` sampling chain,
    and the XLA form of the paged read mirrors ``cached_attention``'s
    arithmetic. tests/test_generative_serving.py holds that line on the
    derived pool and on a small one, tests/test_paged_serving.py holds
    the pool's own mechanisms (prefixes, int8, speculation, sharding,
    exhaustion), tests/test_paged_kv.py holds the paged ops to the slot
    rectangles of ``ops/decode.py``."""

    SHED_INTERVAL_S = 0.05

    def __init__(self, config: ServingConfig, lm,
                 queue: Optional[QueueBackend] = None, draft_lm=None):
        import jax
        import jax.numpy as jnp

        from ..ops.decode import (make_logit_filter,
                                  page_copy, page_table_clear,
                                  page_table_set, paged_insert,
                                  paged_prefix_kv, slot_evict, slot_insert,
                                  slot_join,
                                  spec_accept_greedy)

        self.config = config
        self.lm = lm
        self.model_version = _model_version_of(config.model_path)
        self.queue = (queue if queue is not None
                      else make_queue(config.data_src))
        if config.slots < 1:
            raise ValueError(f"slots must be >= 1, got {config.slots}")
        self.slots = int(config.slots)
        self._sampling = (config.temperature is not None
                          or config.top_k is not None
                          or config.top_p is not None)
        filter_logits = None
        if self._sampling:
            filter_logits = make_logit_filter(
                config.temperature if config.temperature is not None
                else 1.0, config.top_k, config.top_p)
        self._spec = draft_lm is not None and config.spec_k > 0
        if self._spec and self._sampling:
            raise ValueError("speculative decoding in the scheduler is "
                             "greedy-only (per-request sampled accept is a "
                             "follow-up); unset temperature/top_k/top_p")
        self._spec_k = int(config.spec_k) if self._spec else 0
        # a layered decoder (capture/decoder.py) is prefilled in chunks; it
        # may keep a state a slot beside the pages (recurrent layers) or a
        # second page budget (window layers). What would need a snapshot of
        # the state, a page that a window layer has given back, or a pool
        # its reads cannot dequantise is refused here, each for its reason,
        # rather than run wrongly
        self._chunked = bool(getattr(lm, "chunked", False))
        self._recurrent = bool(getattr(lm, "recurrent", False))
        self._window_len = int(getattr(lm, "window_len", 0) or 0)
        if self._recurrent:
            why = "a model with recurrent (linear-attention) layers"
            if draft_lm is not None or config.spec_k:
                raise ValueError(
                    f"speculative decoding is refused for {why}: rejected "
                    f"drafts would have to be rolled back out of the state")
            if int(getattr(config, "kv_shard", 1) or 1) > 1:
                raise ValueError(
                    f"kv_shard is refused for {why}: the state a slot is "
                    f"not sharded with the pages")
        if self._window_len:
            why = "a model with window-attention layers"
            if draft_lm is not None or config.spec_k:
                raise ValueError(
                    f"speculative decoding is refused for {why}: a window "
                    f"layer gives back the pages behind the drafted "
                    f"positions' window, and a rejected draft's roll-back "
                    f"would read them")
            if int(getattr(config, "kv_shard", 1) or 1) > 1:
                raise ValueError(
                    f"kv_shard is refused for {why}: the window layers' "
                    f"page budget is not sharded with the full layers'")
        if self._chunked:
            why = "a model that is prefilled in chunks"
            if config.kv_int8:
                raise ValueError(
                    f"kv_int8 is refused for {why}: its layers' paged "
                    f"reads have no dequantising gather")
            if draft_lm is not None or config.spec_k:
                raise ValueError(
                    f"speculative decoding is refused for {why}: its "
                    f"decoder has no verify step over several positions")
            if self._sampling:
                raise ValueError(f"sampling is not wired for {why} yet: "
                                 f"unset temperature/top_k/top_p")
        # -- device state: the page pools + ONE shared occupancy ----------
        self._params = lm.params
        pl = int(config.kv_page_len)
        if self._chunked:
            # the model names its page (a sparse layer's selection block)
            if pl != lm.page_len:
                raise ValueError(
                    f"kv_page_len must be the model's {lm.page_is}, "
                    f"{lm.page_len}; got {pl}")
        elif pl < 1 or (pl & (pl - 1)) or pl > 16:
            raise ValueError(f"kv_page_len must be a power of two "
                             f"<= 16 (divides every prefill bucket), "
                             f"got {pl}")
        if lm.max_len % pl:
            raise ValueError(f"kv_page_len {pl} must divide the LM's "
                             f"max_len {lm.max_len}")
        self.page_len = pl
        # table rows carry slack columns for the transient spec_k
        # overshoot past max_len (those writes land on real pages the
        # stream owns only within its allocation; beyond it, the null
        # page absorbs them)
        self._table_w = (lm.max_len + self._spec_k + pl - 1) // pl
        # no budget named: every slot can fill its table row, so no join
        # sheds for want of pages (+ 1: page 0 is the null page)
        self.num_pages = (int(config.kv_pages) if config.kv_pages is not None
                          else self.slots * self._table_w + 1)
        if self.num_pages < 2:
            raise ValueError(f"kv_pages must be >= 2 (page 0 is the "
                             f"null page), got {self.num_pages}")
        self._kv_shard = int(getattr(config, "kv_shard", 1) or 1)
        self._prefixes: List[Dict[str, Any]] = []
        if self._spec:
            self.draft_lm = draft_lm
            self._dparams = draft_lm.params
            if draft_lm.max_len < lm.max_len + self._spec_k:
                raise ValueError(
                    f"draft max_len={draft_lm.max_len} must cover "
                    f"max_len={lm.max_len} + spec_k={self._spec_k} "
                    f"transient draft positions")
        self._fresh_device_state()

        @jax.named_scope("select")
        def _select(logits, keys):
            if filter_logits is None:
                return jnp.argmax(logits, axis=-1)
            filt = filter_logits(logits.astype(jnp.float32))
            return jax.vmap(lambda kk, row: jax.random.categorical(
                kk, row, axis=-1))(keys, filt)

        def _step_paged(params, tokens, prev, keys, state, table, caches):
            # a stream's input is the device's own last output, ``prev``,
            # as the step before left it: no token crosses to the host and
            # back on the way to the next step. The host names a token
            # (>= 0) only for a slot joined since that step was dispatched
            with jax.named_scope("merge"):
                tokens = jnp.where(tokens >= 0, tokens,
                                   prev[:tokens.shape[0]])
            if self._chunked:
                # a recurrent layer's state moves for active slots only: a
                # prompt between two chunks keeps what its last chunk left.
                # ``table`` is (full, window) where there are window layers
                logits, caches, read = lm.paged_state_step(
                    params, tokens, state["length"], table, caches,
                    state["active"])
            else:
                logits, caches, read = lm.paged_slot_step(
                    params, tokens, state["length"], table, caches)
            nxt = _select(logits, keys)
            if self._chunked:  # the host fetches both
                nxt = (nxt, read)
            else:
                # the step's own count of the pages it read rides behind
                # the tokens: one array, the step's one fetch
                nxt = jnp.concatenate([nxt.astype(jnp.int32), read[None]])
            # lengths advance ONCE, after every block attended with the
            # pre-increment value (write-then-attend, as serial decode)
            state = {"length": (state["length"]
                                + state["active"].astype(jnp.int32)),
                     "active": state["active"]}
            return nxt, state, caches

        spec_k = self._spec_k

        def _step_spec(params, dparams, tokens, state, table, caches,
                       dcaches):
            """One speculative round: spec_k chained draft steps, one
            batched verify through the paged cache, longest-agreeing-run
            accept. Lengths advance by each slot's ACCEPTED count."""
            lengths = state["length"]
            active = state["active"]

            def draft_body(carry, _):
                tok, ln, dc = carry
                dlogits, dc = draft_lm.slot_step(dparams, tok, ln, dc)
                nd = jnp.argmax(dlogits, axis=-1).astype(tok.dtype)
                return (nd, ln + active.astype(jnp.int32), dc), nd

            (_, _, dcaches), drafts = jax.lax.scan(
                draft_body, (tokens, lengths, dcaches), None, length=spec_k)
            drafts = jnp.swapaxes(drafts, 0, 1)          # [S, k]
            block = jnp.concatenate([tokens[:, None], drafts], axis=1)
            tlogits, caches = lm.verify_step(params, block, lengths, table,
                                             caches)
            emitted, n = spec_accept_greedy(drafts, tlogits)
            n = n * active.astype(n.dtype)
            state = {"length": lengths + n, "active": active}
            return emitted, n, state, caches, dcaches

        def _prefill_paged(params, padded, caches, state, table, row, slot,
                           length):
            kvs = lm.prefill_kv(params, padded)
            caches = [paged_insert(c, row, k[0], v[0])
                      for c, (k, v) in zip(caches, kvs)]
            return (caches, slot_join(state, slot, length),
                    page_table_set(table, slot, row))

        def _prefill_spec(params, dparams, padded, dpadded, caches, dcaches,
                          state, table, row, slot, length):
            kvs = lm.prefill_kv(params, padded)
            caches = [paged_insert(c, row, k[0], v[0])
                      for c, (k, v) in zip(caches, kvs)]
            dkvs = draft_lm.prefill_kv(dparams, dpadded)
            dcaches = [slot_insert(c, slot, k[0], v[0])
                       for c, (k, v) in zip(dcaches, dkvs)]
            return (caches, dcaches, slot_join(state, slot, length),
                    page_table_set(table, slot, row))

        def _prefill_suffix(params, padded, caches, state, table, row, prow,
                            slot, length, plen):
            # gather the shared prefix K/V (refcounted pages, prefilled
            # once) and run only the divergent suffix forward
            pref = [paged_prefix_kv(c, prow, lm.n_head, plen) for c in caches]
            kvs = lm.prefill_kv_suffix(params, padded, pref, plen)
            caches = [paged_insert(c, row, k[0], v[0], start=plen)
                      for c, (k, v) in zip(caches, kvs)]
            return (caches, slot_join(state, slot, length),
                    page_table_set(table, slot, row))

        def _prefill_chunk(params, padded, caches, state, table, row, slot,
                           start, n_valid, length, last):
            """One chunk of a joining prompt (chunked prefill): states and
            pages carried on from the chunk before; the ``last`` one
            joins the slot and installs its table row. ``row`` is (full,
            window) where there are window layers: the window rows are the
            host's (:class:`_WindowPages`)."""
            caches = lm.prefill_chunk(params, padded, caches, row, slot,
                                      start, n_valid)
            if isinstance(row, tuple):
                row = row[0]
            joined = slot_join(state, slot, length)
            state = {k: jnp.where(last, joined[k], state[k]) for k in state}
            table = jnp.where(last, page_table_set(table, slot, row), table)
            return caches, state, table

        def _prefill_prefix(params, padded, caches, row):
            kvs = lm.prefill_kv(params, padded)
            return [paged_insert(c, row, k[0], v[0])
                    for c, (k, v) in zip(caches, kvs)]

        def _copy_pages(caches, src, dst):
            return [page_copy(c, src, dst) for c in caches]

        # every program that takes the page pools and returns them is
        # GIVEN them (donated): with the pool layout of ops/decode.py the
        # chip's compiler then writes the token rows in place, and no
        # program copies a pool. The handles passed in are dead once the
        # call returns, so each caller below rebinds the results at once.
        pools = ("caches",)
        if self._spec:
            both = ("caches", "dcaches")
            self._step_fn = jax.jit(_step_spec, donate_argnames=both)
            self._prefill_spec_fn = jax.jit(_prefill_spec,
                                            donate_argnames=both)
        else:
            self._step_fn = jax.jit(_step_paged, donate_argnames=pools)
        self._prefill_paged_fn = jax.jit(_prefill_paged,
                                         donate_argnames=pools)
        self._prefill_suffix_fn = jax.jit(_prefill_suffix,
                                          static_argnames=("plen",),
                                          donate_argnames=pools)
        self._prefill_prefix_fn = jax.jit(_prefill_prefix,
                                          donate_argnames=pools)
        self._copy_fn = jax.jit(_copy_pages, donate_argnames=pools)
        self._table_set_fn = jax.jit(page_table_set)
        self._table_clear_fn = jax.jit(page_table_clear)
        if self._chunked:  # one compile per chunk bucket
            self._prefill_chunk_fn = jax.jit(_prefill_chunk,
                                             donate_argnames=pools)
        self._join_fn = jax.jit(slot_join)    # T==1 prompts: no prefill
        # the first step's ``prev``: no token yet, but placed as a program
        # places its results (made from the lengths, which a join has
        # returned by then), so that the second step meets the program
        # the first one compiled
        n_prev = self.slots + (0 if self._chunked else 1)
        self._no_tokens_fn = jax.jit(
            lambda length: jnp.zeros((n_prev,), jnp.int32) * length[0])
        self._evict_fn = jax.jit(slot_evict)
        self._split = lambda seed, n: np.asarray(
            jax.random.split(jax.random.PRNGKey(seed), n))
        # -- host-side per-slot bookkeeping (scheduler-thread private) ----
        s = self.slots
        self._streams: List[Optional[_Stream]] = [None] * s
        self._claim_pc = [0.0] * s  # perf_counter at the slot's claim
        # a slot's next input where the host has it: the last token of a
        # prompt joined since the last dispatch (a speculative stream's
        # last accepted token, always); -1 where the device's own last
        # output is the input
        self._next_tokens = np.full(s, 0 if self._spec else -1, np.int32)
        self._active_host = np.zeros(s, bool)
        # the decode step dispatched and not yet folded, with the streams
        # it stepped, and the streams that have left their slot (their
        # last step dispatched) with a token still to come
        self._in_flight_step: Optional[Dict[str, Any]] = None
        self._leaving: List[_Stream] = []
        self._last_landed = 0.0  # perf_counter when a step's tokens came
        # chunked prefill: prompts claimed and not yet resident, oldest
        # first, each holding its slot
        self._prefilling: List[Dict[str, Any]] = []
        self._steps_since_chunk = DECODE_STEPS_PER_CHUNK
        self._reserved = np.zeros(s, bool)
        # -- SLO bookkeeping (same registry families as ClusterServing) ---
        self.metrics_label = f"srv{next(_instance_ids)}"
        self._m = {key: fam.labels(server=self.metrics_label)
                   for key, fam in {**_M_COUNTERS,
                                    **_M_GEN_COUNTERS}.items()}
        self._m_state_slots = _M_STATE_SLOTS.labels(
            server=self.metrics_label)
        self._m_sparse_read = _M_SPARSE_READ.labels(
            server=self.metrics_label)
        self._m_pages_read = _M_PAGES_READ.labels(server=self.metrics_label)
        self._m_pages_in_use = {
            kind: _M_PAGES_IN_USE.labels(server=self.metrics_label,
                                         kind=kind)
            for kind in ("full", "window")}
        self._m_window_released = _M_WINDOW_RELEASED.labels(
            server=self.metrics_label)
        self._m_dsa_scored = _M_DSA_SCORED.labels(server=self.metrics_label)
        # a model without pooled index keys takes no slots of the
        # registry for it
        self._m_dsa_blocks = (
            _M_DSA_BLOCKS.labels(server=self.metrics_label)
            if "dsa_blocks_scored" in getattr(lm, "step_stats", ())
            else None)
        self._m_moe_touched = _M_MOE_TOUCHED.labels(
            server=self.metrics_label)
        self._m_moe_load = _M_MOE_LOAD.labels(server=self.metrics_label)
        self._m_moe_assignments = _M_MOE_ASSIGNMENTS.labels(
            server=self.metrics_label)
        # what a layered decoder's step returns beside the tokens, by the
        # names it gives (``LayeredDecoder.step_stats``)
        self._step_observers = {
            "sparse_positions_read": self._m_sparse_read.observe,
            "dsa_positions_scored": self._m_dsa_scored.observe,
            "dsa_blocks_scored": getattr(self._m_dsa_blocks, "observe",
                                         None),
            "moe_experts_touched": self._m_moe_touched.observe,
            "moe_expert_load": self._m_moe_load.observe,
            "moe_assignments": self._m_moe_assignments.inc}
        self._m_records = _M_RECORDS.labels(server=self.metrics_label)
        self._m_latency = _M_LATENCY.labels(server=self.metrics_label)
        self._m_depth = _M_QUEUE_DEPTH.labels(server=self.metrics_label)
        self._m_in_flight = _M_IN_FLIGHT.labels(server=self.metrics_label)
        self._m_claim_age = _M_CLAIM_AGE.labels(server=self.metrics_label)
        self._m_ttft = _M_TTFT.labels(server=self.metrics_label)
        self._m_queue_wait = _M_QUEUE_WAIT.labels(server=self.metrics_label)
        self._m_prefill_wait = _M_PREFILL_WAIT.labels(
            server=self.metrics_label)
        self._m_tokens = _M_TOKENS.labels(server=self.metrics_label)
        self._m_slots = _M_SLOTS.labels(server=self.metrics_label)
        self._m_pages_free = _M_PAGES_FREE.labels(server=self.metrics_label)
        self._m_page_evict = _M_PAGE_EVICT.labels(server=self.metrics_label)
        self._m_pool_rebuilds = _M_POOL_REBUILDS.labels(
            server=self.metrics_label)
        self._m_spec_accept = _M_SPEC_ACCEPT.labels(
            server=self.metrics_label)
        self._m_brownout = _M_BROWNOUT.labels(server=self.metrics_label)
        self._m_backlog = _M_PUBLISH_BACKLOG.labels(
            server=self.metrics_label)
        self._brownout = _Brownout(self.metrics_label)
        self._note_pages()
        self._counter_lock = threading.Lock()
        self._in_flight = 0
        self._meta: Dict[str, Tuple[float, Optional[int]]] = {}
        self._ewma_token_s = 0.0  # smoothed wall seconds per decoded token
        self._last_claim_m: Optional[float] = None
        self._last_health_m = -1e18
        self._last_shed_m = -1e18
        self._claim_fail_streak = 0
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._handoff_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._loop_running = False
        self._terminal_state: Optional[str] = None
        # every result record goes through it; nothing else of this class
        # calls queue.put_result
        self._publisher = _ResultPublisher(self)

    # -- terminal accounting (ClusterServing's exactly-one-terminal rule) --

    @property
    def counters(self) -> Dict[str, int]:
        return {key: int(c.value()) for key, c in self._m.items()}

    def _count(self, key: str, n: int = 1) -> None:
        self._m[key].inc(n)
        if key in ("shed", "expired"):
            _profiler.on_slo_breach(key)

    def _expiry(self, rec: Dict[str, Any]) -> Optional[float]:
        deadline_ms = (rec.get("deadline_ms")
                       or self.config.default_deadline_ms)
        if not deadline_ms:
            return None
        t0 = rec.get("enqueue_t")
        base = float(t0) if t0 is not None else wall_clock()
        return base + float(deadline_ms) / 1000.0

    def _post_terminal(self, uri: str, value: Dict[str, Any],
                       folded: Optional[float] = None,
                       first: Optional[float] = None) -> None:
        """Every claimed request funnels its ONE terminal result (value or
        error) through here — partial ``stream`` records do NOT. Error
        terminals carry ``retriable`` (shed yes; deadline/validation/
        shutdown no) for the client's retry-budget discipline. The
        publisher writes it and then calls :meth:`_settle`; ``folded`` and
        ``first`` are the publisher's (a terminal that carries a step's
        token, the stream's first)."""
        if "error" in value and "retriable" not in value:
            value = dict(value)
            value["retriable"] = value["error"] in (SHED_ERROR,
                                                    PAGE_SHED_ERROR)
        self._publisher.terminal(
            uri, value, time.perf_counter() if folded is None else folded,
            first)

    def _settle(self, uri: str) -> None:
        """The accounting of a terminal that has landed (or whose write
        failed and was logged): on the publisher's thread."""
        with self._counter_lock:
            self._in_flight = max(0, self._in_flight - 1)
            in_flight = self._in_flight
            meta = self._meta.pop(uri, None)
        self._m_in_flight.set(in_flight)
        if meta is not None:
            t0, flow_id = meta
            self._m_latency.observe(max(wall_clock() - t0, 0.0))
            _trace.flow_point(flow_id, "serving.result", "f")

    def _request_of(self, uri: str):
        """What the spans of one claimed request share as their
        ``request``: the ``trace_id`` its client stamped, else its uri."""
        meta = self._meta.get(uri)
        return uri if meta is None or meta[1] is None else meta[1]

    def _end(self, stream: _Stream, value: Dict[str, Any], why: str,
             counter: Optional[str] = None, folded: Optional[float] = None,
             first: Optional[float] = None) -> Optional[int]:
        """A stream's ONE terminal, and its slot's host bookkeeping freed
        where it still has one; returns that slot (the DEVICE evict is the
        caller's one vectorized ``_evict_slots``). ``why`` is what
        ``_Stream.ended`` says from here on."""
        self._post_terminal(stream.uri, value, folded, first)
        if counter is not None:
            self._count(counter)
        elif "value" in value:
            self._m_records.inc()
        stream.ended = why
        slot = stream.slot
        if slot is None:
            self._leaving.remove(stream)
        else:
            self._vacate(slot)
        return slot

    def _vacate(self, slot: int) -> None:
        """The slot's stream lets go of it and of its pages."""
        self._streams[slot].slot = None
        self._streams[slot] = None
        self._active_host[slot] = False
        self._release_pages(slot)

    def _abandon(self, slot: int) -> None:
        """Release a slot WITHOUT posting a terminal — the stream's one
        terminal will be posted by whichever instance adopts its re-routed
        continuation. Only :meth:`handoff` may do this: every other exit
        path funnels through :meth:`_end`."""
        stream = self._streams[slot]
        with self._counter_lock:
            self._in_flight = max(0, self._in_flight - 1)
            in_flight = self._in_flight
            self._meta.pop(stream.uri, None)
        self._m_in_flight.set(in_flight)
        stream.ended = "gone"
        self._vacate(slot)

    def _fresh_device_state(self) -> None:
        """KV caches, slot occupancy, page table and page allocator as a
        server starts with them: at construction, and again after a failed
        program that had been given the caches (:meth:`_rebuild_pools`)."""
        import jax.numpy as jnp

        from ..ops.decode import init_slot_state, shard_paged_pool
        more = {"slots": self.slots} if self._chunked else {}
        self._caches = self.lm.init_paged_caches(
            self.num_pages, self.page_len, int8=self.config.kv_int8, **more)
        if self._kv_shard > 1:
            # page axis spread over kv_shard devices; decode gathers
            # each stream's pages to the compute device, so tokens
            # stay bit-identical to the single-device pool
            self._caches = shard_paged_pool(self._caches, self._kv_shard)
        self._table = jnp.zeros((self.slots, self._table_w), jnp.int32)
        # host-side allocator: free-page stack, refcounts, and the
        # pages each slot holds (shared prefix pages appear in many)
        self._free_pages = self._initial_free_pages(self.num_pages,
                                                    self._kv_shard)
        self._page_refs = np.zeros(self.num_pages, np.int64)
        self._slot_pages: List[List[int]] = [[] for _ in range(self.slots)]
        # window layers draw on a budget of their own, derived from the
        # slots so that it cannot run out; kv_pages is the full layers'
        self._window = (_WindowPages(
            self.slots, self._table_w, self.lm.window_pages(self.slots),
            self.page_len, self._window_len) if self._window_len else None)
        # the occupancy (length, active) is the slot state of ops/decode.py
        self._state = init_slot_state(self.slots)
        # what the last decode step returned for the host to fetch: its
        # tokens are the next step's inputs, read where they lie
        self._prev_tokens = None
        if self._spec:
            # the draft model still decodes off slot rectangles
            self._dcaches = self.draft_lm.init_slot_caches(self.slots)

    def _rebuild_pools(self) -> None:
        """What a failed program costs once it was given the caches: the
        donated handles are dead (or hold a failed computation's results),
        so nothing of the old device state is read again. The caller has
        errored every resident stream; here the caches, the table and the
        allocator start over and every registered prefix is prefilled
        again into fresh pages. A second failure in here is not caught:
        the loop dies and the health snapshot says ``crashed``."""
        self._caches = None  # the old pools go before the new are made
        prefixes, self._prefixes = self._prefixes, []
        self._fresh_device_state()
        for pfx in prefixes:
            self.register_prefix(pfx["tokens"])
        self._m_pool_rebuilds.inc()
        self._note_pages()
        logger.warning("kv caches rebuilt after a failed dispatch "
                       "(%d prefixes prefilled again)", len(prefixes))

    @staticmethod
    def _initial_free_pages(num_pages: int, kv_shard: int):
        """Allocatable pages ``1..num_pages-1`` as a pop()-able stack.
        Sharded pools interleave the stack round-robin across page shards
        so consecutive allocations land on different devices — without it
        a cold pool would fill shard 0 solid before touching shard 1,
        hot-spotting its HBM and its gather traffic."""
        if kv_shard <= 1:
            return list(range(num_pages - 1, 0, -1))
        per = num_pages // kv_shard  # pages per shard (validated to divide)
        order = sorted(range(1, num_pages),
                       key=lambda p: (p % per, p // per))
        return order[::-1]  # .pop() walks shards round-robin

    def _pages_free_per_shard(self):
        """Free-page count per pool shard (shard of page p: ``p // per``).
        The fleet router sizes sharded capacity by the MIN shard: an
        allocation needs a free page on whichever shard the round-robin
        stack surfaces, and a full shard stalls placement even when other
        shards have room."""
        per = self.num_pages // self._kv_shard
        counts = [0] * self._kv_shard
        for p in self._free_pages:
            counts[p // per] += 1
        return counts

    def _release_pages(self, slot: int) -> None:
        """Decrement every page the slot holds; refcount-0 pages return to
        the free stack (shared prefix pages outlive the stream via the
        registry's own reference). The slot's window pages go back too."""
        pages, self._slot_pages[slot] = self._slot_pages[slot], []
        freed = 0
        for p in pages:
            self._page_refs[p] -= 1
            if self._page_refs[p] == 0:
                self._free_pages.append(p)
                freed += 1
        if freed:
            self._m_page_evict.inc(freed)
        if self._window is not None:
            self._window.release(slot)
        self._note_pages()

    def _note_pages(self) -> None:
        """The allocators' gauges, after any change to a free list."""
        free = len(self._free_pages)
        self._m_pages_free.set(free)
        self._m_pages_in_use["full"].set(self.num_pages - 1 - free)
        if self._window is not None:
            self._m_pages_in_use["window"].set(self._window.in_use())

    # -- device hot path (policed by scripts/check_hot_path_syncs.py) ------

    def _dispatch_step(self, tokens, keys):
        """Dispatch one fused step and rebind the device state to its
        results before anything else runs: the caches passed in were given
        to the program. Returns what the host fetches, its copy to the
        host already under way."""
        t0 = time.perf_counter()
        if self._spec:
            (emitted, n_acc, self._state, self._caches,
             self._dcaches) = self._step_fn(
                self._params, self._dparams, tokens, self._state,
                self._table, self._caches, self._dcaches)
            out = (emitted, n_acc)
        else:
            table = self._table
            if self._window is not None:
                table = (table, self._window.table(self._active_host))
            prev = self._prev_tokens
            if prev is None:  # the first step of this device state
                prev = self._no_tokens_fn(self._state["length"])
            out, self._state, self._caches = self._step_fn(
                self._params, tokens, prev, keys, self._state, table,
                self._caches)
            if self._chunked:  # (tokens, the step's counts)
                self._prev_tokens = out[0]
                out[1].copy_to_host_async()
            else:
                self._prev_tokens = out
            self._prev_tokens.copy_to_host_async()
        _profiler.record_phase("serving", "dispatch",
                               time.perf_counter() - t0, start=t0)
        return out

    def _insert_request_paged(self, padded, row, slot, length):
        self._caches, self._state, self._table = self._prefill_paged_fn(
            self._params, padded, self._caches, self._state, self._table,
            row, slot, length)

    def _insert_request_spec(self, padded, dpadded, row, slot, length):
        (self._caches, self._dcaches, self._state,
         self._table) = self._prefill_spec_fn(
            self._params, self._dparams, padded, dpadded, self._caches,
            self._dcaches, self._state, self._table, row, slot, length)

    def _insert_suffix_paged(self, padded, row, prow, slot, length, plen):
        self._caches, self._state, self._table = self._prefill_suffix_fn(
            self._params, padded, self._caches, self._state, self._table,
            row, prow, slot, length, plen=plen)

    def _copy_page_device(self, src, dst):
        # copy-on-write: a private copy of a shared prefix tail page
        self._caches = self._copy_fn(self._caches, np.int32(src),
                                     np.int32(dst))

    def _evict_slots(self, mask):
        self._state = self._evict_fn(self._state, mask)
        self._table = self._table_clear_fn(self._table, mask)

    def _fetch_tokens(self, nxt) -> np.ndarray:
        # the one host sync per step, deliberately OUTSIDE the policed
        # dispatch body: everything queued ahead of it stays async
        t0 = time.perf_counter()
        out = np.asarray(nxt)
        _profiler.record_phase("serving", "fetch",
                               time.perf_counter() - t0, start=t0)
        return out

    # -- admission -----------------------------------------------------------

    def _shed(self) -> None:
        """Admission control at TOKEN granularity: a queued request waits
        for a free slot, and slots free up at ``slots / (budget x smoothed
        per-token seconds)`` streams per second — shed the backlog down to
        what answers within ``shed_wait_ms`` at the CURRENT decode rate."""
        now = time.monotonic()
        if now - self._last_shed_m < self.SHED_INTERVAL_S:
            return
        self._last_shed_m = now
        cfg = self.config
        allowed = cfg.max_pending
        # the brownout token cap shortens the estimated stream time, so a
        # browned-out server ADMITS deeper queues instead of shedding them
        eff_budget = self._brownout.token_cap(cfg.max_new_tokens)
        if cfg.shed_wait_ms and self._ewma_token_s > 0:
            stream_s = eff_budget * self._ewma_token_s
            allowed = min(allowed, max(
                self.slots,
                int(cfg.shed_wait_ms / 1000.0 / stream_s * self.slots)))
        try:
            dropped = self.queue.shed(allowed, reason=SHED_ERROR)
        except OSError as e:
            logger.warning("shed pass failed (transient): %r", e)
            return
        # brownout feedback: pressure is the max of queue fill (against
        # the shed-allowed depth) and KV-page scarcity (docs/serving.md)
        try:
            pending = self.queue.pending_count()
        except Exception:
            pending = None
        fill = (pending / float(max(allowed, 1))
                if pending is not None else 0.0)
        scarcity = 1.0 - (len(self._free_pages)
                          / float(max(self.num_pages - 1, 1)))
        self._m_brownout.set(self._brownout.tick(max(fill, scarcity)))
        if dropped:
            self._count("shed", len(dropped))
            _E_SHED.emit(label=self.metrics_label, count=len(dropped),
                         allowed=allowed)
            logger.warning(
                "overload: shed %d oldest streams with error results "
                "(allowed depth %d)", len(dropped), allowed)

    # -- paged join: page allocation + shared-prefix attach ----------------

    def _match_prefix(self, prompt) -> Optional[Dict[str, Any]]:
        """Longest registered prefix that ``prompt`` strictly extends (the
        last prompt token is never prefilled, so the prompt must be longer
        than the prefix)."""
        best = None
        for pfx in self._prefixes:
            n = pfx["len"]
            if (len(prompt) > n and list(prompt[:n]) == pfx["tokens"]
                    and (best is None or n > best["len"])):
                best = pfx
        return best

    def register_prefix(self, tokens) -> int:
        """Prefill a shared prompt prefix ONCE into refcounted pool pages.
        Every later join whose prompt extends it references those pages
        (full pages shared in place; a partially-filled tail page gets a
        private copy-on-write duplicate, since the stream appends into it)
        and prefills only its divergent suffix. The registry holds a
        permanent reference, so the pages survive every stream's
        retirement. Admin-plane call — register before ``start()`` or
        between steps, not concurrently with the loop."""
        if self._spec:
            raise RuntimeError("shared prefixes are not wired into the "
                               "speculative scheduler yet (the draft "
                               "cache is contiguous)")
        if self._recurrent:
            raise RuntimeError(
                "register_prefix is refused for a model with recurrent "
                "(linear-attention) layers: a shared prefix would need a "
                "snapshot of every layer's state at its end, which is not "
                "kept")
        if self._window_len:
            raise RuntimeError(
                "register_prefix is refused for a model with "
                "window-attention layers: a window layer gives back the "
                "pages that lie behind a stream's window, so a prefix's "
                "pages cannot be held for the streams that share it")
        if self._chunked:
            raise RuntimeError(
                "register_prefix is refused for a model that is prefilled "
                "in chunks: its chunk program has no form that starts "
                "from another stream's pages")
        from ..capture.lm import prefill_bucket
        toks = [int(x) for x in tokens]
        n = len(toks)
        if n < 1 or n >= self.lm.max_len:
            raise ValueError(f"prefix length {n} out of range for "
                             f"max_len={self.lm.max_len}")
        npages = -(-n // self.page_len)
        if len(self._free_pages) < npages:
            raise RuntimeError(
                f"kv page pool exhausted: prefix needs {npages} pages, "
                f"{len(self._free_pages)} free")
        pages = [self._free_pages.pop() for _ in range(npages)]
        for p in pages:
            self._page_refs[p] = 1  # the registry's permanent hold
        row = np.zeros(self._table_w, np.int32)
        row[:npages] = pages
        tb = prefill_bucket(n, self.lm.max_len)
        padded = np.zeros((1, tb), np.int32)
        padded[0, :n] = toks
        self._caches = self._prefill_prefix_fn(self._params, padded,
                                               self._caches, row)
        self._prefixes.append({"tokens": toks, "len": n, "pages": pages})
        self._note_pages()
        return len(self._prefixes) - 1

    def _take_pages(self, uri: str, needed: int) -> Optional[List[int]]:
        """``needed`` pages off the free stack for the request ``uri``, or
        ``None`` after shedding it: pool exhaustion (or the armed
        ``serving.page_alloc`` fault) answers the request with the page
        shed error, and every resident stream keeps decoding."""
        # chaos site: pool exhaustion at join → shed-or-evict, not a crash
        if (faults.inject("serving.page_alloc")
                or len(self._free_pages) < needed):
            self._post_terminal(uri, {"error": PAGE_SHED_ERROR})
            self._count("shed")
            logger.warning(
                "kv page pool exhausted: shed %s (need %d pages, %d free)",
                uri, needed, len(self._free_pages))
            return None
        return [self._free_pages.pop() for _ in range(needed)]

    def _join_paged(self, slot: int, uri: str, prompt, t: int,
                    budget: int) -> bool:
        """Allocate pages for a validated request and prefill it into
        ``slot``. Pool exhaustion (or the armed ``serving.page_alloc``
        fault) SHEDS the request — its one terminal result is the page
        shed error — and every resident stream keeps decoding. A prefill
        that FAILS takes the donated pools with it: see
        :meth:`_rebuild_pools`."""
        from ..capture.lm import prefill_bucket
        pl = self.page_len
        pfx = self._match_prefix(prompt) if not self._spec else None
        plen = pfx["len"] if pfx else 0
        full = plen // pl       # whole shared pages
        rem = plen % pl         # prefix tokens on the shared tail page
        fed = t - 1             # positions prefilled before decode starts
        tb = (prefill_bucket(fed - plen, self.lm.max_len)
              if fed > plen else 0)
        self._join_bucket = tb
        # highest position the stream may WRITE within its allocation:
        # bucket padding past the suffix, the decode budget, and the
        # transient spec_k overshoot all need real (owned) pages
        high = max(plen + tb, t + budget + self._spec_k)
        # bucket padding past the table width is never visible and never
        # decoded over — the null page absorbs it; no page needed
        fresh_needed = min(-(-high // pl), self._table_w) - full
        fresh = self._take_pages(uri, fresh_needed)
        if fresh is None:
            return False
        shared = [int(p) for p in pfx["pages"][:full]] if pfx else []
        row = np.zeros(self._table_w, np.int32)
        row[:full] = shared
        row[full:full + fresh_needed] = fresh
        for p in shared:
            self._page_refs[p] += 1
        for p in fresh:
            self._page_refs[p] = 1
        self._slot_pages[slot] = shared + fresh
        self._note_pages()
        try:
            if pfx and rem:
                # CoW: the stream appends into logical page ``full``, which
                # still holds shared prefix tail tokens — give it a private
                # copy (fresh[0] occupies that table position)
                self._copy_page_device(pfx["pages"][full], fresh[0])
            if fed > plen:
                padded = np.zeros((1, tb), np.int32)
                padded[0, :fed - plen] = prompt[plen:fed]
                if pfx:
                    prow = np.asarray(pfx["pages"], np.int32)
                    self._insert_suffix_paged(padded, row, prow,
                                              np.int32(slot), np.int32(fed),
                                              plen)
                elif self._spec:
                    dtb = prefill_bucket(fed, self.draft_lm.max_len)
                    dpadded = np.zeros((1, dtb), np.int32)
                    dpadded[0, :fed] = prompt[:fed]
                    self._insert_request_spec(padded, dpadded, row,
                                              np.int32(slot), np.int32(fed))
                else:
                    self._insert_request_paged(padded, row, np.int32(slot),
                                               np.int32(fed))
            else:
                # nothing to prefill (one-token prompt, or the prompt is
                # prefix + one token): join + install the table row
                self._state = self._join_fn(self._state, np.int32(slot),
                                            np.int32(fed))
                self._table = self._table_set_fn(self._table, np.int32(slot),
                                                 row)
        except Exception as e:
            # a program that was given the pools failed: this request and
            # every resident stream get their one terminal, the pools start
            # over, and the requests claimed with this one join after it
            logger.exception("prefill of %s failed", uri)
            self._post_terminal(uri, {"error": repr(e)})
            self._count("errors")
            self._fail_active(repr(e), rebuild=True)
            return False
        return True

    def _join(self, slot: int, uri: str, rec: Dict[str, Any],
              now: float) -> bool:
        """Validate a claimed request and prefill it into ``slot``. Returns
        False (slot stays free) when the request terminates immediately
        (bad prompt, over-budget, already expired).

        A request carrying a ``prefix`` (tokens already decoded elsewhere
        — a re-routed stream after its server died or drained) is ADOPTED:
        ``prompt + prefix`` is re-prefilled through the same bucketed path
        and decoding resumes at position ``len(prefix)``; with an explicit
        ``seed`` the key schedule is rebuilt over the FULL original budget
        so step ``i`` uses the same key an uninterrupted stream would —
        the continuation is token-identical (docs/fleet.md)."""
        cfg = self.config
        self._join_bucket = 0  # the prefill program's width, once known
        prompt = rec.get("prompt")
        if not prompt:
            self._post_terminal(uri, {"error": "empty prompt"})
            self._count("errors")
            return False
        budget = int(rec.get("max_new_tokens") or cfg.max_new_tokens)
        # brownout L2/L3: new streams join with a capped budget — shorter
        # answers for everyone beat no answers for the queue tail. An
        # adopted prefix that already exceeds the cap settles immediately
        # (the prefix >= budget branch below).
        budget = self._brownout.token_cap(budget)
        prompt = [int(x) for x in prompt]
        prefix = [int(x) for x in (rec.get("prefix") or [])]
        t = len(prompt)
        if budget < 1 or t + budget > self.lm.max_len:
            self._post_terminal(uri, {
                "error": f"prompt ({t}) + max_new_tokens ({budget}) "
                         f"out of range for max_len={self.lm.max_len}"})
            self._count("errors")
            return False
        exp = self._expiry(rec)
        if exp is not None and now >= exp:
            self._post_terminal(uri, {"error": DEADLINE_ERROR})
            self._count("expired")
            return False
        if prefix and len(prefix) >= budget:
            # the dead server decoded the whole budget but never posted
            # the terminal — settle it here, nothing left to decode
            self._post_terminal(uri, {"value": prefix[:budget],
                                      "done": True})
            self._m_records.inc()
            return False
        full = prompt + prefix
        t_full = len(full)
        # the profiler's phase for what follows keeps the name it has in
        # ClusterServing, host_input; here it is the prefill's dispatch
        t0 = time.perf_counter()
        if self._chunked:
            # pages now, chunks over the coming iterations; the stream is
            # resident once its last chunk has run (_advance_prefill)
            began = self._begin_prefill(slot, uri, rec, prompt, prefix,
                                        budget, exp, now)
            _profiler.record_phase("serving", "host_input",
                                   time.perf_counter() - t0, start=t0)
            return began
        joined = self._join_paged(slot, uri, full, t_full,
                                  budget - len(prefix))
        _profiler.record_phase("serving", "host_input",
                               time.perf_counter() - t0, start=t0)
        if joined:
            self._activate(slot, uri, rec, prompt, prefix, budget, exp, now)
        return joined

    def _activate(self, slot: int, uri: str, rec: Dict[str, Any], prompt,
                  prefix, budget: int, exp: Optional[float],
                  now: float) -> None:
        """The host's bookkeeping of a stream that is now resident in
        ``slot``: its prompt is in the caches and the next step decodes
        its first token."""
        stream = _Stream(uri, slot, prompt, prefix, budget)
        stream.expires = exp
        stream.enqueue_t = float(rec.get("enqueue_t") or now)
        # TTFT was already observed on the original server for an adopted
        # stream — don't observe it twice
        stream.first_t = now if prefix else None
        stream.claim_pc = self._claim_pc[slot]
        stream.deadline_ms = rec.get("deadline_ms")
        if self._sampling:
            seed = rec.get("seed")
            if seed is None:  # fresh entropy: repeated requests differ
                seed = int(np.random.SeedSequence().entropy % (2 ** 31))
            # the FULL per-request key schedule, precomputed once: step i
            # uses key [i] — identical to serial sample_generate's
            # split(PRNGKey(seed), budget) schedule. The step index is
            # the stream's ``dispatched``, which starts at an adopted
            # prefix's length, so the schedule resumes exactly where the
            # dead server left off.
            stream.seed = int(seed)
            stream.keys = self._split(int(seed), budget)
        self._streams[slot] = stream
        self._next_tokens[slot] = int((prefix or prompt)[-1])
        self._active_host[slot] = True

    # -- chunked prefill (layered decoders) ----------------------------------

    def _begin_prefill(self, slot: int, uri: str, rec: Dict[str, Any],
                       prompt, prefix, budget: int, exp: Optional[float],
                       now: float) -> bool:
        """Give a validated request its pages and queue its prompt for
        prefill in chunks; the slot is held from now on. Pool exhaustion
        sheds the request, as in :meth:`_join_paged`."""
        full = prompt + prefix
        fed = len(full) - 1
        plan = self.lm.chunk_plan(fed)
        high = max(plan[-1][0] + plan[-1][1],
                   len(full) + budget - len(prefix))
        needed = min(-(-high // self.page_len), self._table_w)
        pages = self._take_pages(uri, needed)
        if pages is None:
            return False
        row = np.zeros(self._table_w, np.int32)
        row[:needed] = pages
        for p in pages:
            self._page_refs[p] = 1
        self._slot_pages[slot] = pages
        self._note_pages()
        self._reserved[slot] = True
        self._prefilling.append({
            "slot": slot, "uri": uri, "rec": rec, "prompt": prompt,
            "prefix": prefix, "budget": budget, "exp": exp, "now": now,
            "full": full, "fed": fed, "plan": plan, "row": row, "next": 0})
        return True

    def _drop_prefill(self, job: Dict[str, Any], value: Dict[str, Any],
                      counter: Optional[str]) -> None:
        """End a joining prompt before it is resident: its one terminal,
        its pages back, its slot free. What its chunks wrote stays where
        it is: the next join of the slot overwrites the states."""
        self._post_terminal(job["uri"], value)
        if counter is not None:
            self._count(counter)
        self._release_pages(job["slot"])
        self._reserved[job["slot"]] = False

    def _advance_prefill(self) -> bool:
        """Dispatch the next chunk of the oldest joining prompt; its last
        chunk makes the stream resident. Returns whether a chunk ran."""
        job = self._prefilling[0]
        if job["exp"] is not None and wall_clock() >= job["exp"]:
            self._prefilling.pop(0)
            self._drop_prefill(job, {"error": DEADLINE_ERROR}, "expired")
            return False
        slot, index, count = job["slot"], job["next"], len(job["plan"])
        start, width = job["plan"][index]
        n = max(0, min(width, job["fed"] - start))
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = job["full"][start:start + n]
        last = index == count - 1
        row = job["row"]
        if self._window is not None:
            # the chunk's own pages now; what it passes goes back below
            self._window.cover(slot, start, start + width)
            row = (row, self._window.rows[slot].copy())
        t0 = time.perf_counter()
        try:
            self._caches, self._state, self._table = self._prefill_chunk_fn(
                self._params, padded, self._caches, self._state,
                self._table, row, np.int32(slot), np.int32(start),
                np.int32(n), np.int32(job["fed"]), np.bool_(last))
        except Exception as e:
            # the chunk had been given the caches: as after a failed
            # prefill, everything resident or joining gets its terminal
            logger.exception("prefill chunk %d/%d of %s failed", index + 1,
                             count, job["uri"])
            self._fail_active(repr(e), rebuild=True)
            return False
        if index == 0:
            # the prompt's wait for its turn: behind the chunks of the
            # prompts claimed before it and the decode steps between them
            claimed = self._claim_pc[slot]
            self._m_prefill_wait.observe(t0 - claimed)
        if _utils.span_hooks:
            request = self._request_of(job["uri"])
            if index == 0:
                _utils.offer_span("serve.prefill_wait", claimed,
                                  t0 - claimed, request=request, life=True)
            _utils.offer_span(
                "serve.prefill_chunk", t0, time.perf_counter() - t0,
                request=request,
                args=(("start", start), ("rows", n), ("width", width),
                      ("index", index), ("count", count)))
        self._count("prefill_chunks")
        self._count("prompt_tokens", n)
        job["next"] += 1
        if self._window is not None:
            # the next query is the next chunk's first, or the first decode
            # step's; every later program runs after this chunk has read
            if last:
                self._window.beyond(slot, job["fed"])
            self._m_window_released.inc(self._window.behind(
                slot, job["fed"] if last else start + width))
            self._note_pages()
        if last:
            self._prefilling.pop(0)
            self._reserved[slot] = False
            self._activate(slot, job["uri"], job["rec"], job["prompt"],
                           job["prefix"], job["budget"], job["exp"],
                           job["now"])
        return True

    def _window_advance(self) -> None:
        """Before a decode step: each resident stream gives back the window
        pages that its query no longer sees and gets the page that the
        step writes, if it starts one."""
        released, held = 0, self._window.in_use()
        for i in np.flatnonzero(self._active_host):
            stream = self._streams[i]
            t = len(stream.prompt) - 1 + stream.dispatched
            released += self._window.behind(i, t)
            self._window.cover(i, t, t + 1)
        if released:
            self._m_window_released.inc(released)
        if released or self._window.in_use() != held:
            self._note_pages()

    def _admit(self) -> None:
        free = [i for i in range(self.slots)
                if not self._active_host[i] and not self._reserved[i]]
        if not free:
            return
        with time_it("serve.admit"):
            self._admit_into(free)

    def _admit_into(self, free: List[int]) -> None:
        """Shed, claim up to ``len(free)`` requests and join each."""
        self._shed()
        try:
            with time_it("serve.claim"):
                got = self.queue.claim_batch(len(free))
            self._claim_fail_streak = 0
        except OSError as e:
            self._count("claim_faults")
            self._claim_fail_streak += 1
            if self._claim_fail_streak > self.config.claim_retries:
                raise  # dead backend, not a flaky one: surface it
            logger.warning("transient claim failure (%d/%d): %r",
                           self._claim_fail_streak,
                           self.config.claim_retries, e)
            return
        if not got:
            return
        self._last_claim_m = time.monotonic()
        now = wall_clock()
        claim_pc = time.perf_counter()
        with self._counter_lock:
            self._in_flight += len(got)
            in_flight = self._in_flight
            for uri, rec in got:
                self._meta[uri] = (float(rec.get("enqueue_t") or now),
                                   rec.get("trace_id"))
        self._m_in_flight.set(in_flight)
        for uri, rec in got:
            # the request's wait in the queue, moved onto perf_counter by
            # the one pair of clock reads above
            waited = max(now - float(rec.get("enqueue_t") or now), 0.0)
            self._m_queue_wait.observe(waited)
            if _utils.span_hooks:
                _utils.offer_span("serve.queue_wait", claim_pc - waited,
                                  waited, request=self._request_of(uri),
                                  life=True)
        for uri, rec in got:
            slot = free.pop(0)
            self._claim_pc[slot] = claim_pc
            with time_it("serve.join",
                         request=self._request_of(uri)) as span:
                joined = self._join(slot, uri, rec, now)
                span.note("bucket", self._join_bucket)
            if not joined:
                free.insert(0, slot)

    # -- the step loop -------------------------------------------------------

    def _expire_slots(self) -> None:
        """Per-token deadline check: an expired stream is evicted
        MID-FLIGHT — its one terminal result is the deadline error (the
        partials it already streamed are not terminals; a token of it
        still in flight is dropped at its fold)."""
        now = wall_clock()
        mask = np.zeros(self.slots, bool)
        for i, stream in enumerate(self._streams):
            if (stream is not None and stream.expires is not None
                    and now >= stream.expires):
                mask[i] = True
                self._end(stream, {"error": DEADLINE_ERROR}, "gone",
                          counter="expired")
        if mask.any():
            self._evict_slots(mask)

    def _fail_active(self, message: str, rebuild: bool = False) -> None:
        """Error every resident stream (its one terminal). ``rebuild``: the
        failure came at or after a dispatch that was given the caches, so
        the device state starts over instead of being evicted from, and
        the step in flight goes with it: a stream that had left its slot
        with a token still to come gets the error too."""
        mask = np.zeros(self.slots, bool)
        for i, stream in enumerate(self._streams):
            if stream is not None:
                mask[i] = True
                self._end(stream, {"error": message}, "gone",
                          counter="errors")
        jobs, self._prefilling = self._prefilling, []
        for job in jobs:  # joining prompts lose what their chunks wrote
            self._drop_prefill(job, {"error": message}, "errors")
        if rebuild:
            for stream in list(self._leaving):
                self._end(stream, {"error": message}, "gone",
                          counter="errors")
            self._in_flight_step = None
            self._rebuild_pools()
        elif mask.any():
            self._evict_slots(mask)

    def _count_dispatched(self, stepped) -> None:
        """After a dispatch: one more position a stream it stepped. A
        stream whose budget that reaches is not stepped again: it leaves
        its slot now, inactive on the device before the next dispatch and
        free for the next request, and its last token finds it through the
        step in flight."""
        ending = []
        for slot, stream in stepped:
            stream.dispatched += 1
            if stream.dispatched >= stream.budget:
                ending.append((slot, stream))
        if not ending:
            return
        with time_it("serve.evict"):
            mask = np.zeros(self.slots, bool)
            for slot, stream in ending:
                mask[slot] = True
                self._vacate(slot)
                self._leaving.append(stream)
            self._evict_slots(mask)

    def _post_tokens(self, nxt: np.ndarray, stepped) -> np.ndarray:
        """Fold one step's tokens, one a stream it stepped. A stream that
        has ended since the dispatch (expired, failed, on ``eos_id``) does
        not get its token; nothing after an eos reaches a record. Returns
        :meth:`_fold`'s mask."""
        overrun = sum(stream.ended == "eos" for _, stream in stepped)
        finished = self._fold(
            (stream, (int(nxt[slot]),)) for slot, stream in stepped
            if stream.ended is None)
        if overrun:
            self._count("overrun_slot_steps", overrun)
        return finished

    def _post_tokens_spec(self, emitted: np.ndarray,
                          n_acc: np.ndarray) -> np.ndarray:
        """Fold one speculative round's ACCEPTED tokens — the rules of
        :meth:`_fold`, but up to ``spec_k + 1`` tokens land per stream per
        round. The budget clamp and eos truncation are host-side; a stream
        they cut short ends in the same pass, so the device's over-advanced
        length never feeds another step. That is why a round is folded
        before the next is dispatched: its lengths wait on the host."""
        eos = self.config.eos_id

        def accepted():
            for i, stream in enumerate(self._streams):
                if stream is None:
                    continue
                take = min(int(n_acc[i]),
                           stream.budget - len(stream.tokens))
                toks = [int(x) for x in emitted[i, :take]]
                if eos is not None and eos in toks:
                    toks = toks[:toks.index(eos) + 1]
                if toks:
                    self._next_tokens[i] = toks[-1]
                    yield stream, toks
        return self._fold(accepted())

    def _fold(self, taken) -> np.ndarray:
        """Fold a step's new tokens, ``(stream, tokens)`` each, into the
        streams: TTFT on the first token, a partial result due every
        ``stream_interval`` tokens, terminal value on eos / budget
        exhaustion. The records go to the publisher; no write is waited
        for here. A stream's claim time rides with the first record that
        carries a token of it, so that ``serve.first_token`` ends when a
        client could see one. Returns the mask of the slots whose streams
        ended here while they still held one: the device's eviction of
        them is the caller's."""
        now, folded = wall_clock(), time.perf_counter()
        cfg = self.config
        # brownout L1+: coarser partials — every queue write the streamers
        # skip is backend bandwidth returned to terminals
        stride = self._brownout.stream_stride(cfg.stream_interval)
        finished = np.zeros(self.slots, bool)
        due, n_tok = [], 0
        for stream, toks in taken:
            stream.tokens.extend(toks)
            n_tok += len(toks)
            claimed = None
            if stream.first_t is None:
                stream.first_t = now
                self._m_ttft.observe(max(now - stream.enqueue_t, 0.0))
                if _utils.span_hooks:
                    claimed = stream.claim_pc
            have = len(stream.tokens)
            eos = cfg.eos_id is not None and toks[-1] == cfg.eos_id
            if eos or have >= stream.budget:
                # the list is the record's from here
                slot = self._end(stream,
                                 {"value": stream.tokens, "done": True},
                                 "eos" if eos else "budget", folded=folded,
                                 first=claimed)
                if slot is not None:
                    finished[slot] = True
            elif stride > 0 and have - stream.streamed >= stride:
                # the list and the length that counts: the copy and the
                # serialisation are the publisher's
                due.append((stream.uri, stream.tokens, have, stream.seed,
                            claimed))
                stream.streamed = have
            elif claimed is not None:  # no record carries this token
                _utils.offer_span("serve.first_token", claimed,
                                  time.perf_counter() - claimed,
                                  request=self._request_of(stream.uri),
                                  life=True)
        if due:
            self._publisher.partials(due, folded)
        if n_tok:
            self._m_tokens.inc(n_tok)
        return finished

    def serve_step(self) -> int:
        """One scheduler step: evict expired streams, admit new requests
        into free slots (shed + bucketed prefill), dispatch ONE fused
        decode step over every occupied slot, stream/terminate per token.
        Returns the number of streams stepped (or, where nothing was left
        to step, of those whose last tokens it folded) — the single-step
        form tests and the bench drive directly; :meth:`run` loops it.

        Inside :meth:`run` the loop keeps one step in flight: the step is
        dispatched from the device's own last tokens, and only then are
        the tokens of the step before it fetched and folded, so the device
        computes beside the host's iteration. Stepped by hand, with no loop
        running, the step's own tokens are folded and its records have
        landed when this returns, so the caller reads the streams and the
        result store as the step left them."""
        try:
            # the parent of everything the iteration emits; an iteration
            # that stepped nothing leaves none, and what it emitted stands
            # at the top
            with time_it("serve.step", tentative=True) as span:
                stepped = self._serve_step()
                if not stepped:
                    span.drop()
            return stepped
        finally:
            if not self._loop_running:
                self._publisher.close()

    def _serve_step(self) -> int:
        self._maybe_write_health()
        with time_it("serve.expire"):
            self._expire_slots()
        if not self._draining.is_set():
            self._admit()
        # chunked prefill: one chunk of the oldest joining prompt, then
        # DECODE_STEPS_PER_CHUNK iterations of the resident streams' decode
        # step below before the next chunk (none where nothing is resident)
        chunked = (bool(self._prefilling)
                   and (self._steps_since_chunk >= DECODE_STEPS_PER_CHUNK
                        or not self._active_host.any())
                   and self._advance_prefill())
        if chunked:
            self._steps_since_chunk = 0
        n_active = int(np.sum(self._active_host))
        self._m_slots.set(n_active)
        if self._recurrent:
            self._m_state_slots.set(n_active + len(self._prefilling))
        if n_active == 0:
            # nothing to dispatch: what is in flight is folded before the
            # loop may sleep; a prompt still joining keeps it awake
            return (self._fold_in_flight()
                    or int(chunked or bool(self._prefilling)))
        with time_it("serve.prepare"):
            if self._window is not None:
                self._window_advance()
            stepped = [(i, stream) for i, stream in enumerate(self._streams)
                       if stream is not None]
            # fresh arrays a step: the dispatch may read them after it
            # returns
            tokens = self._next_tokens.copy()
            keys = np.zeros((self.slots, 2), np.uint32)
            if self._sampling:
                for i, stream in stepped:
                    keys[i] = stream.keys[stream.dispatched]
            step = {"streams": stepped, "t_step": time.perf_counter(),
                    "ahead_from": None}
        given = False
        try:
            # chaos site, raised BEFORE the dispatch: the step errors every
            # active stream (their one terminal) and the caches stay whole
            faults.inject("serving.decode_step")
            given = True
            step["out"] = self._dispatch_step(tokens, keys)
        except Exception as e:
            logger.exception("decode step failed for %d streams", n_active)
            self._fail_active(repr(e), rebuild=given)
            return 0
        self._count("decode_steps")
        if self._chunked:
            self._steps_since_chunk += 1
            if self._prefilling:
                self._count("steps_between_chunks")
        if self._spec:
            # the round's accepted counts are clamped by the host: its
            # lengths wait on the fold, so it is folded at once
            return n_active if self._land_step(step) else 0
        self._next_tokens[:] = -1
        self._count_dispatched(stepped)
        before, self._in_flight_step = self._in_flight_step, step
        if before is not None:
            # this step went to the device ahead of the fold of the one
            # before it; the host work from here to its own fetch runs
            # beside it
            self._count("steps_ahead")
            step["ahead_from"] = time.perf_counter()
            if not self._land_step(before):
                return 0
        if not self._loop_running and not self._fold_in_flight():
            return 0  # by hand: the step's own tokens, before it returns
        return n_active

    def _fold_in_flight(self) -> int:
        """Fetch and fold the step in flight, if there is one: before the
        loop sleeps, drains, stops or hands its streams off. Returns how
        many streams' tokens were folded."""
        step, self._in_flight_step = self._in_flight_step, None
        if step is None or not self._land_step(step):
            return 0
        return len(step["streams"])

    def _land_step(self, step: Dict[str, Any]) -> bool:
        """The one blocking call an iteration: fetch a dispatched step's
        tokens, then fold them. A fetch that fails has lost the caches the
        step was given: every stream errors and the pools start over."""
        if step["ahead_from"] is not None and _utils.span_hooks:
            # a stretch of the step's life: it lies over two iterations
            _utils.offer_span("serve.step_ahead", step["ahead_from"],
                              time.perf_counter() - step["ahead_from"],
                              life=True)
        n_active = len(step["streams"])
        try:
            if self._spec:
                emitted, n_acc = step["out"]
                em_host = self._fetch_tokens(emitted)
                n_host = self._fetch_tokens(n_acc)
            elif self._chunked:
                out, read = step["out"]
                nxt_host = self._fetch_tokens(out)
            else:
                nxt_host = self._fetch_tokens(step["out"])
                nxt_host, read = nxt_host[:-1], nxt_host[-1]
        except Exception as e:
            logger.exception("decode step failed for %d streams", n_active)
            self._fail_active(repr(e), rebuild=True)
            return False
        # the cadence of the steps: from this one's dispatch, or from the
        # landing of the one before it where that came later
        landed = time.perf_counter()
        took = landed - max(step["t_step"], self._last_landed)
        self._last_landed = landed
        if self._spec:
            n_emitted = int(np.sum(n_host[self._active_host]))
            per = took / max(n_emitted, 1)
            self._m_spec_accept.set(float(np.mean(np.maximum(
                n_host[self._active_host] - 1, 0))) / self._spec_k)
        else:
            per = took / n_active
        self._ewma_token_s = (per if self._ewma_token_s == 0.0
                              else 0.8 * self._ewma_token_s + 0.2 * per)
        if self._chunked:
            # the step's own counts, computed with the tokens just fetched
            for name, value in zip(self.lm.step_stats,
                                   np.atleast_1d(np.asarray(read))):
                self._step_observers[name](float(value))
        elif not self._spec:
            self._m_pages_read.observe(float(read))
        with time_it("serve.post"):
            if self._spec:
                finished = self._post_tokens_spec(em_host, n_host)
            else:
                finished = self._post_tokens(nxt_host, step["streams"])
        if finished.any():  # on eos_id, or a budget the host clamped
            with time_it("serve.evict"):
                self._evict_slots(finished)
        return True

    # -- lifecycle (mirrors ClusterServing) ----------------------------------

    def run(self, poll_interval_s: float = 0.005) -> None:
        logger.info("generative serving started (src=%s slots=%d)",
                    self.config.data_src, self.slots)
        ops_alerts.ensure_default()  # no-op unless ops.enabled
        self._terminal_state = None
        self._loop_running = True
        self._last_shed_m = -1e18
        # the loop's lane in every span record, whoever's thread this is
        thread = threading.current_thread()
        was, thread.name = thread.name, f"{self.metrics_label}-loop"
        try:
            while (not self._stop.is_set()
                   and not self._handoff_evt.is_set()):
                stepped = self.serve_step()
                if self._draining.is_set() and stepped == 0:
                    return  # drained: every in-flight stream finished
                if stepped == 0:
                    # no stream to step: the device waits for a request,
                    # not for the host
                    with time_it("serve.idle"):
                        time.sleep(poll_interval_s)
        finally:
            # the loop counts as running until its last record has landed:
            # drain()'s terminal health, stop()'s return and handoff()'s
            # re-enqueue all come after every write of this instance
            try:
                # the step in flight first: a stopped stream's error, a
                # drained stream's value and a handed-off prefix all come
                # after the last token the device was asked for
                self._fold_in_flight()
                if self._stop.is_set():
                    self._fail_active(SHUTDOWN_ERROR)
                if self._handoff_evt.is_set():
                    self._publisher.drop_partials()
                self._publisher.close()
            finally:
                self._loop_running = False
            try:
                self._maybe_write_health()
            finally:
                thread.name = was

    def start(self) -> "GenerativeServing":
        ops_alerts.ensure_default()  # no-op unless ops.enabled
        self._stop.clear()
        self._draining.clear()
        self._handoff_evt.clear()
        self._terminal_state = None
        self._background_error: Optional[BaseException] = None
        # a new start takes a new publisher: one that died stays dead
        self._publisher = _ResultPublisher(self)

        def _run() -> None:
            try:
                self.run()
            except BaseException as e:
                logger.exception("generative serving loop died")
                self._background_error = e

        self._thread = threading.Thread(target=_run, daemon=True)
        self._thread.start()
        return self

    def check_health(self) -> None:
        err = getattr(self, "_background_error", None)
        if err is not None:
            raise RuntimeError(
                "generative serving loop died in the background") from err

    def drain(self, timeout_s: float = 30.0) -> None:
        """Stop ADMITTING, finish every in-flight stream (each runs out
        its budget / eos / deadline), then write terminal health."""
        self._draining.set()
        if self._loop_running and self._thread is None:
            return  # foreground run(): the loop finalizes itself
        t = self._thread
        if t is not None:
            t.join(timeout=timeout_s)
            if t.is_alive():
                raise RuntimeError(
                    f"drain did not complete within {timeout_s}s "
                    f"({int(np.sum(self._active_host))} streams active)")
            self._thread = None
        if self._terminal_state is None:
            self._terminal_state = "drained"
            _E_LIFECYCLE.emit(label=self.metrics_label, state="drained")
        self._write_health()
        self.check_health()

    def handoff(self, to_queue, timeout_s: float = 30.0) -> int:
        """Drain WITHOUT finishing locally: pause the loop and re-enqueue
        every in-flight stream onto ``to_queue`` carrying its accumulated
        token ``prefix`` (+ sampling ``seed``), so another instance adopts
        it mid-stream and continues token-identically — the fast half of
        the failover protocol (docs/fleet.md). No terminal is posted here;
        the adopting server posts the stream's ONE terminal. A stream
        whose re-enqueue fails is errored instead (never silently lost).
        Returns the number of streams handed off."""
        self._draining.set()
        self._handoff_evt.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout_s)
            if t.is_alive():
                raise RuntimeError(
                    f"handoff: serve loop did not pause within {timeout_s}s")
            self._thread = None
        elif self._loop_running:
            # foreground run(): wait for the loop to notice the event
            deadline = time.monotonic() + timeout_s
            while self._loop_running:
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"handoff: serve loop did not pause within "
                        f"{timeout_s}s")
                time.sleep(0.002)
        # the prefix is whole (the step in flight folded) and no partial of
        # a stream that is about to be another instance's may land after
        # its re-enqueue: forget them, wait for the write in flight (the
        # loop did all three as it paused; with no loop, here)
        self._fold_in_flight()
        self._publisher.drop_partials()
        self._publisher.close()
        moved = 0
        mask = np.zeros(self.slots, bool)
        for i, stream in enumerate(self._streams):
            if stream is None:
                continue
            # the original prompt, seed and deadline ride along with the
            # accumulated prefix (docs/fleet.md)
            rec: Dict[str, Any] = {
                "prompt": list(stream.prompt),
                "prefix": list(stream.tokens),
                "max_new_tokens": stream.budget,
                "enqueue_t": stream.enqueue_t,
            }
            if stream.deadline_ms is not None:
                rec["deadline_ms"] = stream.deadline_ms
            if stream.seed is not None:
                rec["seed"] = stream.seed
            mask[i] = True
            try:
                to_queue.enqueue(stream.uri, rec)
            except Exception:
                logger.exception("handoff enqueue for %s failed", stream.uri)
                self._end(stream, {"error": SHUTDOWN_ERROR}, "gone",
                          counter="errors")
                continue
            self._abandon(i)
            moved += 1
        jobs, self._prefilling = self._prefilling, []
        for job in jobs:  # a prompt between two chunks goes back whole
            try:
                to_queue.enqueue(job["uri"], job["rec"])
            except Exception:
                logger.exception("handoff enqueue for %s failed", job["uri"])
                self._drop_prefill(job, {"error": SHUTDOWN_ERROR}, "errors")
                continue
            with self._counter_lock:
                self._in_flight = max(0, self._in_flight - 1)
                self._meta.pop(job["uri"], None)
            self._release_pages(job["slot"])
            self._reserved[job["slot"]] = False
            moved += 1
        if mask.any():
            self._evict_slots(mask)
        self._publisher.close()  # the terminals of failed re-enqueues
        if self._terminal_state is None:
            self._terminal_state = "drained"
            _E_LIFECYCLE.emit(label=self.metrics_label, state="drained")
        self._write_health()
        self.check_health()
        return moved

    def stop(self) -> None:
        """Hard stop: active streams are answered with explicit shutdown
        errors (never silently dropped). Use :meth:`drain` for deploys."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                self._thread = None
                raise RuntimeError(
                    "generative serving loop did not shut down within 10s "
                    "(queue backend wedged?); thread leaked")
            self._thread = None
        else:
            try:
                self._fold_in_flight()
                self._fail_active(SHUTDOWN_ERROR)
            finally:
                self._publisher.close()
        if self._terminal_state is None:
            self._terminal_state = "stopped"
            _E_LIFECYCLE.emit(label=self.metrics_label, state="stopped")
        self._write_health()
        self.check_health()

    # -- deep health ---------------------------------------------------------

    def health_snapshot(self) -> Dict[str, Any]:
        """Generative twin of ``ClusterServing.health_snapshot``: lifecycle
        state, queue depth, slots occupied, tokens decoded, TTFT/latency
        percentiles and the SLO counters — a per-instance view of the
        shared metrics registry."""
        with self._counter_lock:
            in_flight = self._in_flight

        def _pct(fam, p: float) -> Optional[float]:
            v = fam.percentile(p)
            return None if v is None else round(v * 1e3, 3)

        def _mean_of(hist, digits: int = 1) -> Dict[str, Any]:
            # a histogram of counts: its values lie above the shared
            # buckets, so the exact sum over the count, not a percentile
            n = hist.count()
            return {"mean": round(hist.sum() / n, digits) if n else None,
                    "window": n}

        err = getattr(self, "_background_error", None)
        if self._terminal_state is not None:
            state = self._terminal_state
        elif err is not None:
            state = "crashed"
        elif self._draining.is_set():
            state = "draining"
        elif self._loop_running or (self._thread is not None
                                    and self._thread.is_alive()):
            state = "running"
        else:
            state = "idle"
        try:
            pending = self.queue.pending_count()
        except Exception:
            pending = None
        if pending is not None:
            self._m_depth.set(pending)
        self._m_in_flight.set(in_flight)
        now_m = time.monotonic()
        claim_age = (round(now_m - self._last_claim_m, 3)
                     if self._last_claim_m is not None else None)
        if claim_age is not None:
            self._m_claim_age.set(claim_age)
        return {
            "state": state,
            "time": wall_clock(),
            "queue_pending": pending,
            "in_flight": in_flight,
            "slots": self.slots,
            "slots_occupied": int(np.sum(self._active_host)),
            "tokens_total": int(self._m_tokens.value()),
            "tokens_per_sec_ewma": (round(1.0 / self._ewma_token_s, 1)
                                    if self._ewma_token_s > 0 else None),
            "kv_pages_free": len(self._free_pages),
            "kv_shards": self._kv_shard,
            "kv_pool_rebuilds": int(self._m_pool_rebuilds.value()),
            "kv_pages_free_min_shard": (
                min(self._pages_free_per_shard())
                if self._kv_shard > 1 else None),
            "spec_accept_ratio": (
                round(float(self._m_spec_accept.value()), 4)
                if self._spec else None),
            "brownout_level": self._brownout.level,
            "prefills_pending": len(self._prefilling),
            "prefill_chunks_total": int(self._m["prefill_chunks"].value()),
            "prompt_tokens_total": int(self._m["prompt_tokens"].value()),
            "steps_between_chunks_total": int(
                self._m["steps_between_chunks"].value()),
            "decode_steps_total": int(self._m["decode_steps"].value()),
            "steps_ahead_total": int(self._m["steps_ahead"].value()),
            "overrun_slot_steps_total": int(
                self._m["overrun_slot_steps"].value()),
            "state_slots_in_use": (
                int(np.sum(self._active_host)) + len(self._prefilling)
                if self._recurrent else None),
            "sparse_positions_read": _mean_of(self._m_sparse_read),
            "dsa_positions_scored": _mean_of(self._m_dsa_scored),
            "dsa_blocks_scored": (_mean_of(self._m_dsa_blocks)
                                  if self._m_dsa_blocks is not None
                                  else None),
            "kv_pages_in_use": {
                "full": self.num_pages - 1 - len(self._free_pages),
                "window": (self._window.in_use()
                           if self._window is not None else None)},
            "window_pages_released_total": int(
                self._m_window_released.value()),
            # three digits: a reader takes the mean of a stretch of the
            # run from two snapshots' means times their counts
            "moe_experts_touched": _mean_of(self._m_moe_touched, 3),
            "moe_expert_load": _mean_of(self._m_moe_load, 3),
            "moe_assignments_total": int(self._m_moe_assignments.value()),
            "paged_pages_read": _mean_of(self._m_pages_read),
            "last_claim_age_s": claim_age,
            "ttft_ms": {"p50": _pct(self._m_ttft, 0.50),
                        "p99": _pct(self._m_ttft, 0.99),
                        "window": self._m_ttft.count()},
            "queue_wait_ms": {"p50": _pct(self._m_queue_wait, 0.50),
                              "p99": _pct(self._m_queue_wait, 0.99),
                              "window": self._m_queue_wait.count()},
            "prefill_wait_ms": {"p50": _pct(self._m_prefill_wait, 0.50),
                                "p99": _pct(self._m_prefill_wait, 0.99),
                                "window": self._m_prefill_wait.count()},
            "latency_ms": {"p50": _pct(self._m_latency, 0.50),
                           "p99": _pct(self._m_latency, 0.99),
                           "window": self._m_latency.count()},
            "counters": self.counters,
            "model_version": self.model_version,
            "alerts": sorted(ops_alerts.active_alerts()),
            "incident": ops_incident.last_incident(),
            "error": repr(err) if err is not None else None,
        }

    def _write_health(self) -> None:
        path = self.config.health_path
        if not path:
            return
        tmp = path + ".tmp"
        try:
            with file_io.fopen(tmp, "w") as f:
                f.write(json.dumps(self.health_snapshot()))
            file_io.replace(tmp, path)
        except OSError:
            logger.warning("health write to %s failed", path)

    def _maybe_write_health(self) -> None:
        if not self.config.health_path:
            return
        now = time.monotonic()
        if now - self._last_health_m >= self.config.health_interval_s:
            self._last_health_m = now
            with time_it("serve.health"):
                self._write_health()


def main() -> None:
    """CLI entry (the ``cluster-serving-start`` role, packaged as
    ``zoo-serving``): read a YAML config, write a pidfile, serve. SIGTERM
    drains (deploy-friendly: finish in-flight, flush, terminal health);
    SIGINT stops hard."""
    import signal
    import sys

    cfg_path = sys.argv[1] if len(sys.argv) > 1 else "config.yaml"
    cfg = ServingConfig.from_yaml(cfg_path)
    # construct (model load, queue init) BEFORE writing the pidfile so a
    # startup failure can't leave a stale pidfile for a supervisor to kill
    # an unrelated reused pid with
    serving = ClusterServing(cfg)
    signal.signal(signal.SIGTERM, lambda *_: serving.drain())
    signal.signal(signal.SIGINT, lambda *_: serving.stop())
    pidfile = os.environ.get("ZOO_SERVING_PIDFILE", "/tmp/zoo_serving.pid")
    try:
        with open(pidfile, "w") as f:
            f.write(str(os.getpid()))
        serving.run()
    finally:
        try:
            with open(pidfile) as f:
                if f.read().strip() == str(os.getpid()):
                    os.remove(pidfile)
        except OSError:
            pass
