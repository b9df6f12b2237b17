"""Serving config (reference ``scripts/cluster-serving/config.yaml`` schema
parsed by ``ClusterServingHelper.scala``: model path, data src, image shape,
topN filter, batch size, memory cap)."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence


@dataclass
class ServingConfig:
    model_path: str = ""
    model_type: str = "zoo"  # zoo | savedmodel | torch | onnx | caffe
    model_weight_path: str = ""  # caffe: path to the .caffemodel
    data_src: str = "dir:///tmp/zoo_serving"
    image_shape: Sequence[int] = (224, 224, 3)
    input_dtype: str = "float32"  # "uint8" halves x4 the host->device bytes
    #   (pair with a model that normalizes on device, e.g.
    #   resnet(preprocess="imagenet_uint8"))
    filter_top_n: Optional[int] = None
    batch_size: int = 4
    batch_wait_ms: int = 20  # micro-batch window
    max_pending: int = 10000  # erroring load-shed depth threshold
    concurrent_num: int = 1
    decode_threads: int = 4  # host threads decoding while the device runs
    quantize: Optional[str] = None  # bf16 | int8
    log_dir: Optional[str] = None  # TensorBoard serving summaries
    # -- SLO layer ------------------------------------------------------------
    default_deadline_ms: Optional[int] = None  # server-side deadline for
    #   records that carry none (clients stamp per-request deadline_ms)
    shed_wait_ms: Optional[int] = None  # estimated-wait admission: shed the
    #   queue down to what the smoothed service rate can answer within this
    #   wait (None = depth-only shedding via max_pending)
    claim_retries: int = 20  # consecutive transient claim failures the loop
    #   absorbs before surfacing the backend as dead
    health_path: Optional[str] = None  # periodic + terminal health.json
    health_interval_s: float = 1.0  # min seconds between health writes
    # -- generative serving (continuous batching) -----------------------------
    slots: int = 8  # resident decode slots (device batch of the step loop)
    max_new_tokens: int = 64  # per-stream budget when the request omits one
    eos_id: Optional[int] = None  # stop token; None = run out the budget
    stream_interval: int = 1  # post a partial result every N tokens
    temperature: Optional[float] = None  # sampling knobs: any set => the
    top_k: Optional[int] = None          # scheduler samples through the
    top_p: Optional[float] = None        # shared make_logit_filter; all
    #   None => greedy argmax decoding
    # -- the KV page pool -------------------------------------------------------
    kv_pages: Optional[int] = None  # pool size in pages, a deployment's
    #   memory budget; None = every slot can reach max_len:
    #   slots * ceil((max_len + spec_k) / kv_page_len) + 1. Page 0 is the
    #   null page, so kv_pages - 1 pages are allocatable.
    kv_page_len: int = 16  # tokens per page; must divide the LM's max_len
    #   and be a power of two <= 16 (so it divides every prefill bucket);
    #   a model with sparse-attention layers asks for its selection block
    #   (capture/decoder.py: 64 for MiniCPM-SALA)
    kv_int8: bool = False  # int8 KV pool (delayed-scaling quantization)
    kv_shard: int = 1  # devices the pool's PAGE axis shards over (a model
    #   whose KV exceeds one device's HBM spreads pages across the mesh;
    #   decode gathers each stream's pages to the compute device, so
    #   sharded output is token-identical to kv_shard=1). Must divide
    #   kv_pages and be <= the local device count.
    spec_k: int = 0  # speculative decoding: draft tokens per verify round;
    #   0 = disabled. Requires a draft_lm, greedy-only.

    @staticmethod
    def from_yaml(path: str) -> "ServingConfig":
        import yaml
        with open(path) as f:
            raw = yaml.safe_load(f) or {}
        model = raw.get("model", {}) or {}
        data = raw.get("data", {}) or {}
        params = raw.get("params", {}) or {}
        cfg = ServingConfig()
        cfg.model_path = model.get("path", cfg.model_path)
        cfg.model_type = model.get("type", cfg.model_type)
        cfg.model_weight_path = model.get("weight_path",
                                          cfg.model_weight_path)
        cfg.data_src = data.get("src") or cfg.data_src
        cfg.input_dtype = data.get("input_dtype", cfg.input_dtype)
        if cfg.input_dtype not in ("float32", "uint8"):
            raise ValueError(f"input_dtype must be float32 or uint8, got "
                             f"{cfg.input_dtype!r}")
        if data.get("image_shape"):
            shape = data["image_shape"]
            if isinstance(shape, str):
                shape = [int(s) for s in shape.split(",")]
            cfg.image_shape = tuple(shape)
        if data.get("filter"):  # "topN(5)" like the reference
            s = str(data["filter"])
            if s.lower().startswith("topn"):
                cfg.filter_top_n = int(s[s.index("(") + 1:s.index(")")])
        cfg.batch_size = int(params.get("batch_size", cfg.batch_size))
        cfg.batch_wait_ms = int(params.get("batch_wait_ms", cfg.batch_wait_ms))
        cfg.max_pending = int(params.get("max_pending", cfg.max_pending))
        cfg.concurrent_num = int(params.get("concurrent_num",
                                            cfg.concurrent_num))
        cfg.decode_threads = int(params.get("decode_threads",
                                            cfg.decode_threads))
        cfg.quantize = params.get("quantize", cfg.quantize)
        if params.get("deadline_ms") is not None:
            cfg.default_deadline_ms = int(params["deadline_ms"])
        if params.get("shed_wait_ms") is not None:
            cfg.shed_wait_ms = int(params["shed_wait_ms"])
        cfg.claim_retries = int(params.get("claim_retries",
                                           cfg.claim_retries))
        cfg.slots = int(params.get("slots", cfg.slots))
        cfg.max_new_tokens = int(params.get("max_new_tokens",
                                            cfg.max_new_tokens))
        if params.get("eos_id") is not None:
            cfg.eos_id = int(params["eos_id"])
        cfg.stream_interval = int(params.get("stream_interval",
                                             cfg.stream_interval))
        if params.get("temperature") is not None:
            cfg.temperature = float(params["temperature"])
        if params.get("top_k") is not None:
            cfg.top_k = int(params["top_k"])
        if params.get("top_p") is not None:
            cfg.top_p = float(params["top_p"])
        if params.get("kv_pages") is not None:
            cfg.kv_pages = int(params["kv_pages"])
        cfg.kv_page_len = int(params.get("kv_page_len", cfg.kv_page_len))
        cfg.kv_int8 = bool(params.get("kv_int8", cfg.kv_int8))
        cfg.kv_shard = int(params.get("kv_shard", cfg.kv_shard))
        cfg.spec_k = int(params.get("spec_k", cfg.spec_k))
        cfg.log_dir = raw.get("log_dir", cfg.log_dir)
        cfg.health_path = raw.get("health_path", cfg.health_path)
        if raw.get("health_interval_s") is not None:
            cfg.health_interval_s = float(raw["health_interval_s"])
        return cfg
