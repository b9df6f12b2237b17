"""InferenceModel — pooled multi-backend serving model.

Parity with the reference (``pipeline/inference/InferenceModel.scala:30``):
``concurrentNum`` model copies in a ``LinkedBlockingQueue``, borrowed per
predict call; loaders for multiple formats; int8 quantized variants. TPU
re-design:

- a jitted forward is already thread-safe and the TPU serializes compute, so
  "copies" become a semaphore of ``concurrent_num`` dispatch slots — same
  backpressure contract, no duplicated weights in HBM.
- bucketed-shape AOT compile cache (≙ OpenVINO model-optimizer IR cache,
  ``OpenVinoInferenceSupportive.scala:64``): batch is padded to the next
  bucket so arbitrary request sizes reuse a handful of compiled programs
  (serving under XLA recompilation, SURVEY §7 hard part (f)).
- backends: native zoo models / checkpoints, raw JAX fns, flax modules,
  TF SavedModel (via ``jax2tf.call_tf``), TorchScript (host-side torch CPU,
  ≙ TorchNet), with bf16/int8 weight quantization.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..common import metrics as _metrics
from ..common import profiler as _profiler
from ..common.context import wire_compilation_cache
from .quantize import dequantize_params, quantize_params

_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)

#: process-wide XLA compile telemetry (per-model per-bucket detail stays in
#: ``InferenceModel.compile_counts`` / ``compile_seconds``)
_M_COMPILE = _metrics.counter(
    "infer.compile_total", "XLA executables compiled by InferenceModel.")
_M_COMPILE_S = _metrics.counter(
    "infer.compile_seconds_total",
    "Seconds spent in InferenceModel XLA compiles.")


class _TextArtifact:
    """A raw-StableHLO AOT artifact (TF-imported models, export_compiled's
    ``stablehlo_text`` format): compiled straight through PJRT on first
    call — serving needs neither TF nor the exporting process.

    ``output_keys``: for dict-output signatures, the names matching the
    program's flat result order (tf.nest flattens dicts by sorted key), so
    the artifact path returns the SAME dict shape as the live call_tf
    path."""

    def __init__(self, text: str, n_outputs: int, output_keys=None):
        self._text = text
        self._n = n_outputs
        self._keys = list(output_keys) if output_keys else None
        self._exe = None
        self._lock = threading.Lock()

    def _compile(self):
        # Raw-StableHLO execution has no public jax surface yet; this leans
        # on jax internals and is feature-checked so a jax upgrade fails with
        # a clear message instead of an AttributeError mid-serving.
        try:
            from jax._src.interpreters import mlir as jmlir
            from jax._src.lib import _jax, xla_client as xc
            from jax._src.lib.mlir import ir as mlir_ir
        except ImportError as e:  # pragma: no cover - version drift guard
            raise RuntimeError(
                "this jax version moved the internal StableHLO-compile "
                "surface the AOT text-artifact loader relies on; pin jax to "
                "a tested release or re-export the model with jax.export"
            ) from e
        client = jax.devices()[0].client
        if not (hasattr(client, "compile_and_load")
                and hasattr(_jax, "DeviceList")
                and hasattr(xc, "CompileOptions")):  # pragma: no cover
            raise RuntimeError(
                "jax internals moved (compile_and_load/DeviceList/"
                "CompileOptions); this jax version is incompatible with the "
                "raw-StableHLO loader — pin jax or re-export with jax.export")
        with jmlir.make_ir_context():
            module = mlir_ir.Module.parse(self._text)
            return client.compile_and_load(
                module, _jax.DeviceList(tuple(jax.devices()[:1])),
                xc.CompileOptions(), [])

    def call(self, *args):
        with self._lock:
            if self._exe is None:
                self._exe = self._compile()
        bufs = [jax.device_put(np.asarray(a)) for a in args]
        res = self._exe.execute_sharded(bufs)
        outs = [a[0] for a in res.disassemble_into_single_device_arrays()]
        if self._keys is not None:
            return dict(zip(self._keys, outs))
        return outs[0] if self._n == 1 else tuple(outs)


def _bucket(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    return ((n + 1023) // 1024) * 1024


class InferenceModel:
    def __init__(self, concurrent_num: int = 1):
        if concurrent_num < 1:
            raise ValueError("concurrent_num must be >= 1")
        self.concurrent_num = concurrent_num
        self._slots = threading.Semaphore(concurrent_num)
        self._forward: Optional[Callable] = None  # forward(params, x)
        self._params: Any = None
        self._jit: Optional[Callable] = None  # jit caches per shape itself
        self._host_predict: Optional[Callable] = None  # non-XLA backends
        # compile-warmth layer: AOT-compiled executables keyed by exact
        # input signature, with per-bucket compile counters so "did the
        # first request compile?" is an assertion, not a latency guess
        self._compiled: Dict[Tuple, Any] = {}
        self._compile_lock = threading.Lock()
        self.compile_counts: Dict[int, int] = {}
        self.compile_seconds: Dict[int, float] = {}
        wire_compilation_cache()

    def _set_forward(self, forward: Callable) -> None:
        """Install the forward fn and its jit wrapper eagerly — one wrapper
        per model, so concurrent cold predicts share XLA's compile cache
        instead of racing to build separate wrappers."""
        self._forward = forward
        self._jit = jax.jit(forward)
        self._reset_compile_cache()
        # loader-specific side channels die with the forward they belong
        # to — a reused InferenceModel must not export a PREVIOUS model
        self._savedmodel_ir = None
        self._keras_model = None
        self._keras_state = None

    def _reset_compile_cache(self) -> None:
        """A new forward (or new params tree) invalidates every compiled
        executable AND the warmth accounting."""
        with self._compile_lock:
            self._compiled = {}
            self.compile_counts = {}
            self.compile_seconds = {}

    def _ensure_compiled(self, xs: List[np.ndarray], is_multi: bool,
                         bucket: int):
        """Fetch (or AOT-compile) the executable for this exact padded
        input signature. ``jit.lower().compile()`` bypasses jit's implicit
        per-call cache, so the memo here is the ONLY cache — which is what
        makes the per-bucket counters truthful."""
        key = (is_multi, tuple((a.shape, a.dtype.str) for a in xs))
        exe = self._compiled.get(key)
        if exe is not None:
            return exe
        with self._compile_lock:
            exe = self._compiled.get(key)
            if exe is None:
                t0 = time.perf_counter()
                exe = self._jit.lower(
                    self._params, list(xs) if is_multi else xs[0]).compile()
                self._compiled[key] = exe
                elapsed = time.perf_counter() - t0
                self.compile_counts[bucket] = \
                    self.compile_counts.get(bucket, 0) + 1
                self.compile_seconds[bucket] = \
                    self.compile_seconds.get(bucket, 0.0) + elapsed
                _M_COMPILE.inc()
                _M_COMPILE_S.inc(elapsed)
                _profiler.record_phase("serving", "compile", elapsed,
                                       start=t0)
        return exe

    def prewarm(self, example,
                buckets: Optional[Sequence[int]] = None) -> "InferenceModel":
        """Compile the expected shape buckets BEFORE traffic arrives.

        ``example``: one input batch (any batch size) fixing dtypes and
        feature shapes — the same convention as :meth:`export_compiled`.
        ``buckets``: request batch sizes to warm (each resolves through the
        same bucket selection ``predict`` uses); defaults to the bucket the
        example's own batch size pads to. A production server calls this at
        load time so no client eats the multi-second first-hit XLA compile
        mid-traffic-ramp; once the persistent compilation cache holds the
        programs the warmup itself is usually a disk read. Host-side backends (TorchScript) have
        nothing to warm. Compiles are recorded in ``compile_counts`` /
        ``compile_seconds`` per bucket."""
        if self._host_predict is not None:
            return self
        aot = getattr(self, "_aot", None)
        if self._forward is None and aot is None:
            raise RuntimeError("load a model first")
        is_multi = isinstance(example, (list, tuple))
        xs = [np.asarray(a) for a in (example if is_multi else [example])]
        n = xs[0].shape[0]
        sizes = [n] if buckets is None else [int(b) for b in buckets]
        resolved = set()
        for size in sizes:
            if aot is not None:
                b = next((bb for bb in sorted(aot) if max(size, 1) <= bb),
                         None)
                if b is None:  # larger than every exported bucket: predict
                    continue   # would chunk to the biggest, already covered
            else:
                b = _bucket(size)
            resolved.add(b)
        for b in sorted(resolved):
            shaped = [np.repeat(a[:1], b, axis=0) if n
                      else np.zeros((b,) + a.shape[1:], a.dtype) for a in xs]
            if aot is not None:
                art = aot[b]
                if isinstance(art, _TextArtifact):
                    t0 = time.perf_counter()
                    with art._lock:
                        if art._exe is None:
                            art._exe = art._compile()
                            elapsed = time.perf_counter() - t0
                            self.compile_counts[b] = \
                                self.compile_counts.get(b, 0) + 1
                            self.compile_seconds[b] = \
                                self.compile_seconds.get(b, 0.0) + elapsed
                            _M_COMPILE.inc()
                            _M_COMPILE_S.inc(elapsed)
                            _profiler.record_phase("serving", "compile",
                                                   elapsed, start=t0)
                # serialized jax.export artifacts load pre-compiled
            else:
                self._ensure_compiled(shaped, is_multi, b)
        return self

    @staticmethod
    def _device(tree):
        """Explicit placement: one batched device_put instead of letting
        jit transfer each host numpy leaf implicitly."""
        put = jax.device_put(tree)
        jax.block_until_ready(put)
        return put

    # -- loaders (doLoad* family) ---------------------------------------------

    def load_zoo(self, path: str) -> "InferenceModel":
        """Load a saved ``ZooModel`` directory (≙ doLoadBigDL)."""
        from ..models.common import ZooModel
        zm = ZooModel.load_model(path)
        est = zm.model.get_estimator()
        model = zm.model

        def forward(params, x):
            y, _ = model.call(params, est.model_state, x, training=False)
            return y

        self._set_forward(forward)
        self._params = self._device(est.params)
        self._keras_model = model  # calibrated int8 needs the layer graph
        self._keras_state = est.model_state
        return self

    def load_keras(self, model, params=None, model_state=None
                   ) -> "InferenceModel":
        """Wrap an in-memory Keras-style model (compiled or raw)."""
        if params is None:
            est = model.get_estimator()
            params, model_state = est.params, est.model_state
        model_state = model_state or {}

        def forward(p, x):
            y, _ = model.call(p, model_state, x, training=False)
            return y

        self._set_forward(forward)
        self._params = self._device(params)
        self._keras_model = model  # calibration needs the layer graph
        self._keras_state = model_state
        return self

    def load_jax(self, forward_fn: Callable, params: Any) -> "InferenceModel":
        """Raw ``forward(params, x)`` + params pytree (≙ doLoadTF frozen)."""
        self._set_forward(forward_fn)
        self._params = self._device(params)
        return self

    def load_flax(self, module, variables: Any) -> "InferenceModel":
        def forward(vars_, x):
            return module.apply(vars_, x)
        self._set_forward(forward)
        self._params = self._device(variables)
        return self

    def load_savedmodel(self, path: str, signature: str = "serving_default"
                        ) -> "InferenceModel":
        """TF SavedModel import (≙ doLoadTF SavedModel,
        ``TFNetForInference.scala``). The signature is wrapped with
        ``jax2tf.call_tf`` and predict()'s jit EMBEDS the lowered TF
        computation into the XLA program — TF runs at trace time (once per
        shape bucket), not per request. For serving with no TF dependency
        at all, round-trip to a serialized artifact:
        ``load_savedmodel(p).export_compiled(dir, example)`` then serve via
        ``load_compiled(dir)`` (pure StableHLO; tested TF-free in
        ``tests/test_capture_inference.py``)."""
        import tensorflow as tf  # gated import
        from jax.experimental import jax2tf
        loaded = tf.saved_model.load(path)
        fn = loaded.signatures[signature]
        keys = list(fn.structured_input_signature[1].keys())

        def positional_fn(*args):  # signatures take kwargs; call_tf positional
            return fn(**dict(zip(keys, args)))

        def forward(params, x):
            del params
            xs = x if isinstance(x, (list, tuple)) else [x]
            out = jax2tf.call_tf(positional_fn)(*xs)
            if isinstance(out, dict) and len(out) == 1:
                return next(iter(out.values()))
            return out

        def stablehlo_ir(shaped):
            """Lower the signature at concrete shapes via TF's own XLA
            bridge — raw StableHLO text, no call_tf effect, serializable
            (export_compiled's TF-free artifact path)."""
            jfn = tf.function(positional_fn, jit_compile=True)
            specs = [tf.TensorSpec(np.asarray(a).shape,
                                   tf.as_dtype(np.asarray(a).dtype))
                     for a in shaped]
            return str(jfn.experimental_get_compiler_ir(*specs)(
                stage="stablehlo"))

        self._set_forward(forward)
        self._params = {}
        self._keep_alive = loaded
        self._savedmodel_ir = stablehlo_ir
        return self

    def load_onnx(self, path: str) -> "InferenceModel":
        """ONNX file → native model pool entry (≙ the OpenVINO-IR load role;
        imports through the dependency-free ONNX loader)."""
        from ..net import load_onnx as _load
        return self.load_keras(*_load(path))

    def load_caffe(self, prototxt_path: str,
                   caffemodel_path: Optional[str] = None,
                   input_shape: Optional[Sequence[int]] = None
                   ) -> "InferenceModel":
        """Caffe prototxt+caffemodel → native model pool entry
        (≙ doLoadCaffe). ``input_shape``: (C, H, W), for deploy prototxts
        that declare no input shape."""
        from ..net import load_caffe as _load
        return self.load_keras(*_load(prototxt_path, caffemodel_path,
                                      input_shape=input_shape))

    def load_torch(self, path: str) -> "InferenceModel":
        """TorchScript model on host CPU (≙ doLoadPyTorch / TorchNet JNI).
        Runs outside XLA; the pool semaphore is the real concurrency guard."""
        import torch  # gated import
        module = torch.jit.load(path)
        module.eval()

        def host_predict(x):
            import torch as _t
            with _t.no_grad():
                xs = x if isinstance(x, (list, tuple)) else [x]
                out = module(*[_t.from_numpy(np.asarray(a, np.float32))
                               for a in xs])
                return out.numpy()

        self._host_predict = host_predict
        return self

    # -- quantization (int8/VNNI path equivalent) -----------------------------

    def quantize(self, dtype: str = "bf16", calibration_data=None,
                 percentile: float = 99.9) -> "InferenceModel":
        """``bf16`` casts weights; ``int8`` without calibration is
        weight-only (dequantized on the fly). ``int8`` WITH
        ``calibration_data`` (an iterable of input batches, e.g. a
        FeatureSet iterator) runs activation observers over the batches and
        installs the static-quantization path: Dense/Conv kernels carry
        per-tensor activation scales and execute on the int8 grid
        (the reference's calibrated OpenVINO int8,
        ``OpenVinoInferenceSupportive.scala:64``)."""
        if self._params is None:
            raise RuntimeError("load a model first")
        base = self._forward
        if dtype == "int8" and calibration_data is not None:
            model = getattr(self, "_keras_model", None)
            if model is None:
                raise ValueError(
                    "calibrated int8 needs a keras-graph model "
                    "(load_keras/load_zoo); weight-only int8 works for "
                    "opaque forwards — call quantize('int8') without "
                    "calibration_data")
            from .quantize import observe_activation_scales
            host_params = jax.tree_util.tree_map(np.asarray, self._params)
            act_scales = observe_activation_scales(
                model, host_params, self._keras_state, calibration_data,
                percentile=percentile)
            qparams = quantize_params(self._params, "int8",
                                      act_scales=act_scales)
            self._act_scales = act_scales
            # layers consume their quantized kernels natively — the base
            # forward runs unchanged on the mixed tree; the param AVALs
            # changed, so every compiled executable is stale
            self._params = self._device(qparams)
            self._reset_compile_cache()
            return self
        qparams = quantize_params(self._params, dtype)

        if dtype == "int8":
            def forward(qp, x):
                return base(dequantize_params(qp), x)
        else:
            def forward(qp, x):
                import jax.numpy as jnp
                y = base(qp, x)
                return jax.tree_util.tree_map(
                    lambda t: t.astype(jnp.float32), y)
        self._set_forward(forward)
        self._params = self._device(qparams)
        return self

    # -- AOT artifact export/import (OpenVINO model-optimizer IR role) --------

    def export_compiled(self, path: str, example,
                        batch_sizes: Sequence[int] = (1, 8, 32, 128),
                        platforms: Sequence[str] = ("cpu", "tpu")
                        ) -> "InferenceModel":
        """Ahead-of-time compile the loaded forward at fixed batch buckets
        and serialize the artifacts to ``path`` (≙ OpenVINO model-optimizer
        IR emission, ``OpenVinoInferenceSupportive.scala:64-123``). Params
        are frozen into the artifact as constants — the exported file IS the
        model, no separate weights. ``example``: one input batch (any batch
        size) fixing dtypes/feature shapes. Artifacts lower for every
        platform in ``platforms`` so an export made on a CPU host serves on
        TPU."""
        import json

        import jax.export as jex

        from ..common import file_io

        if self._forward is None:
            raise RuntimeError("load a model first")
        file_io.makedirs(path, exist_ok=True)
        multi = isinstance(example, (list, tuple))
        xs = [np.asarray(a) for a in (example if multi else [example])]
        if getattr(self, "_savedmodel_ir", None) is not None:
            # TF-imported model: the artifact is the TF-side StableHLO
            # lowering itself (raw text per bucket) — serving it never
            # touches TF (jax.export can't serialize call_tf's effect)
            y = self._forward(self._params, xs if multi else xs[0])
            n_out = (len(jax.tree_util.tree_leaves(y))
                     if isinstance(y, (dict, list, tuple)) else 1)
            # dict outputs keep their names: XLA's flat result order is
            # tf.nest's flatten order (sorted keys)
            out_keys = sorted(y.keys()) if isinstance(y, dict) else None
            for b in sorted(batch_sizes):
                shaped = [np.repeat(a[:1], b, axis=0) for a in xs]
                text = self._savedmodel_ir(shaped)
                with file_io.fopen(
                        file_io.join(path, f"batch-{b}.stablehlo.txt"),
                        "w") as f:
                    f.write(text)
            with file_io.fopen(file_io.join(path, "aot_meta.json"),
                               "w") as f:
                f.write(json.dumps({"batch_sizes": sorted(batch_sizes),
                                    "multi": multi,
                                    "format": "stablehlo_text",
                                    "n_outputs": n_out,
                                    "output_keys": out_keys,
                                    "platforms": list(platforms)}))
            return self
        params = self._params
        fwd = self._forward
        # mirror predict()'s calling convention exactly: a list input stays
        # a list even with one element
        if multi:
            frozen = jax.jit(lambda *args: fwd(params, list(args)))
        else:
            frozen = jax.jit(lambda x: fwd(params, x))
        for b in sorted(batch_sizes):
            shaped = [np.repeat(a[:1], b, axis=0) for a in xs]
            exp = jex.export(frozen, platforms=tuple(platforms))(*shaped)
            with file_io.fopen(file_io.join(path, f"batch-{b}.stablehlo"),
                               "wb") as f:
                f.write(exp.serialize())
        with file_io.fopen(file_io.join(path, "aot_meta.json"), "w") as f:
            f.write(json.dumps({"batch_sizes": sorted(batch_sizes),
                                "multi": multi,
                                "platforms": list(platforms)}))
        return self

    def load_compiled(self, path: str) -> "InferenceModel":
        """Load an :meth:`export_compiled` artifact directory; ``predict``
        then runs the pre-compiled programs (pad to the bucket, trim) with
        zero JIT compiles at serve time."""
        import json

        import jax.export as jex

        from ..common import file_io

        with file_io.fopen(file_io.join(path, "aot_meta.json")) as f:
            meta = json.loads(f.read())
        arts = {}
        if meta.get("format") == "stablehlo_text":
            for b in meta["batch_sizes"]:
                with file_io.fopen(
                        file_io.join(path, f"batch-{b}.stablehlo.txt")) as f:
                    arts[b] = _TextArtifact(f.read(),
                                            int(meta.get("n_outputs", 1)),
                                            meta.get("output_keys"))
        else:
            for b in meta["batch_sizes"]:
                with file_io.fopen(
                        file_io.join(path, f"batch-{b}.stablehlo"),
                        "rb") as f:
                    arts[b] = jex.deserialize(f.read())
        self._aot = arts
        self._aot_multi = bool(meta["multi"])
        return self

    # -- predict (doPredict) --------------------------------------------------

    def predict(self, x, batch_size: Optional[int] = None, *,
                _fetch: bool = True):
        """Borrow a pool slot, pad to the shape bucket, run, trim.
        ``batch_size`` splits oversized inputs into chunks (each bucketed).
        With a :meth:`load_compiled` artifact, the pre-compiled program for
        the bucket runs instead of the JIT path — same pad/chunk/trim
        contract."""
        if self._host_predict is not None:
            with self._slots:
                res = self._host_predict(x)
                return res if _fetch else (lambda: res)
        aot = getattr(self, "_aot", None)
        if self._forward is None and aot is None:
            raise RuntimeError("no model loaded")
        is_multi = isinstance(x, (list, tuple))
        if aot is not None and is_multi != self._aot_multi:
            want = "a list of inputs" if self._aot_multi else "one array"
            raise ValueError(
                f"this AOT artifact was exported for {want}; got "
                f"{'a list' if is_multi else 'one array'}")
        xs = [np.asarray(a) for a in (x if is_multi else [x])]
        n = xs[0].shape[0]

        # effective chunk limit: caller's batch_size, and for AOT also the
        # largest exported bucket
        limit = batch_size
        if aot is not None:
            biggest = max(aot)
            limit = biggest if limit is None else min(limit, biggest)
        if limit is not None and n > limit:
            # chunks inherit _fetch: an async caller gets every chunk
            # DISPATCHED now and a thunk that fetches/concats later, so the
            # pipeline overlap survives bucketed chunking
            chunk_thunks = [self.predict(
                [a[i:i + limit] for a in xs] if is_multi
                else xs[0][i:i + limit], batch_size=limit, _fetch=False)
                for i in range(0, n, limit)]

            def gather():
                chunks = [t() for t in chunk_thunks]
                if isinstance(chunks[0], (list, tuple)):
                    return type(chunks[0])(
                        np.concatenate([c[i] for c in chunks])
                        for i in range(len(chunks[0])))
                if isinstance(chunks[0], dict):
                    return {k: np.concatenate([c[k] for c in chunks])
                            for k in chunks[0]}
                return np.concatenate(chunks)

            return gather() if _fetch else gather

        if aot is not None:
            # smallest exported bucket that fits; empty batches still run
            # the bucket-1 program and trim to zero rows
            bucket = next(b for b in sorted(aot) if max(n, 1) <= b)
        else:
            bucket = _bucket(n)
        if bucket != n:
            pad_row = (lambda a: a[-1:] if n else
                       np.zeros((1,) + a.shape[1:], a.dtype))
            xs = [np.concatenate(
                [a, np.repeat(pad_row(a), bucket - n, axis=0)]) for a in xs]
        if aot is None:
            # resolve (or compile) the executable BEFORE taking a pool
            # slot: a cold bucket must not hold a dispatch slot hostage
            # for the length of an XLA compile
            exe = self._ensure_compiled(xs, is_multi, bucket)
        args = jax.device_put(xs)  # explicit transfer (see _device)
        with self._slots:
            if aot is not None:
                y = aot[bucket].call(*args)
            else:
                y = exe(self._params, args if is_multi else args[0])
        def fetch():
            trim = lambda t: np.asarray(t)[:n]
            if isinstance(y, dict):
                return {k: trim(v) for k, v in y.items()}
            if isinstance(y, (list, tuple)):
                return type(y)(trim(t) for t in y)
            return trim(y)

        return fetch() if _fetch else fetch

    def predict_async(self, x, batch_size: Optional[int] = None):
        """Dispatch a predict WITHOUT blocking on the device→host fetch.
        Returns a zero-argument callable producing :meth:`predict`'s result;
        the device computes while the caller overlaps other work (the
        serving pipeline decodes batch N+1 during batch N's flight)."""
        return self.predict(x, batch_size, _fetch=False)

    def predict_many(self, batches: Sequence) -> List:
        """Concurrent batch predicts through the pool (thread fan-out)."""
        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(max_workers=self.concurrent_num) as ex:
            return list(ex.map(self.predict, batches))
