"""8→64-chip scaling projection from the COMPILED 8-way programs.

Method (the honest substitute for pod hardware this environment lacks):

1. jit the real data-parallel train step over an 8-device mesh (CPU
   simulation — the HLO collectives are identical to the TPU lowering for
   the same shardings) and read every ``all-reduce`` instruction's tensor
   bytes out of the optimized module: that is the per-step collective
   payload B.
2. Per-chip compute time T_c is an INPUT (the constants in ``main``):
   builders' single-chip differenced step times from before PR 1, which
   no driver record holds. The nearest the driver recorded (ROADMAP
   "Recent") is r03 bert 1188.6 samples/s, i.e. 107.7 ms at b128. Replace
   them with ledger numbers once the benchmark records some.
3. α-β ring model on v5e ICI: a bidirectional ring all-reduce of B bytes
   over n chips moves 2·B·(n−1)/n per chip; with the 2D torus both axes
   carry traffic, so the effective per-chip ICI bandwidth is
   W = links_used · per-link bandwidth. Published v5e figures used:
   45 GB/s unidirectional per link, 2 links usable per all-reduce
   direction (2D torus axes), α = 1 µs per hop.
4. Efficiency bounds: XLA overlaps the grad all-reduce with backward
   compute where dependencies allow —
     no-overlap (pessimistic):  eff = T_c / (T_c + T_ar(n))
     full-overlap (optimistic): eff = T_c / max(T_c, T_ar(n))
   Real systems land between; DP grad reduction overlaps well in
   practice (the reduce of layer i's grads runs during layer i−1's
   backward), so the truth sits near the optimistic bound.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     JAX_PLATFORMS=cpu python scripts/scaling_projection.py
"""
import re
import sys

sys.path.insert(0, ".")

import jax

jax.config.update("jax_platforms", "cpu")  # a projection, never a chip run

import numpy as np

DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
            "f64": 8, "s64": 8}

# v5e ICI assumptions (public "How to Scale Your Model" figures)
W_LINK = 4.5e10          # bytes/s unidirectional per ICI link
LINKS_PER_AR = 2         # 2D torus: both axes carry ring traffic
ALPHA = 1e-6             # per-hop latency seconds
W_EFF = W_LINK * LINKS_PER_AR


def collective_bytes(compiled) -> int:
    """Sum payload bytes over every all-reduce/reduce-scatter/all-gather
    in the optimized HLO."""
    txt = compiled.as_text()
    total = 0
    ops = ("all-reduce(", "all-reduce-start(", "reduce-scatter(",
           "all-gather(")
    for line in txt.splitlines():
        if " = " not in line:
            continue
        seg = line.split(" = ", 1)[1]
        hit = next((op for op in ops if op in seg), None)
        if hit is None:
            continue
        shape_part = seg.split(hit)[0]  # tuple or single shape before opcode
        for m in re.finditer(r"(\w+)\[([0-9,]*)\]", shape_part):
            dt, dims = m.groups()
            if dt not in DT_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * DT_BYTES[dt]
    return total


def ar_time(bytes_, n):
    """Bidirectional ring all-reduce over n chips."""
    return 2.0 * bytes_ * (n - 1) / n / W_EFF + ALPHA * (n - 1)


def project(name, bytes_, step_s, chips=(8, 16, 32, 64)):
    print(f"\n## {name}: collective payload {bytes_/1e6:.1f} MB/step, "
          f"per-chip step {step_s*1e3:.1f} ms")
    print("| chips | all-reduce ms | eff (no overlap) | eff (overlapped) |")
    print("|---|---|---|---|")
    rows = []
    for n in chips:
        t = ar_time(bytes_, n)
        e_no = step_s / (step_s + t)
        e_ov = step_s / max(step_s, t)
        rows.append((n, t, e_no, e_ov))
        print(f"| {n} | {t*1e3:.2f} | {e_no*100:.1f}% | {e_ov*100:.1f}% |")
    return rows


def build_resnet_step():
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.common.context import init_tpu_context
    from analytics_zoo_tpu.estimator import Estimator
    from analytics_zoo_tpu.keras import objectives, optimizers
    from analytics_zoo_tpu.models.image.imageclassification import resnet
    from analytics_zoo_tpu.parallel.mesh import shard_batch

    init_tpu_context()
    model = resnet(50, num_classes=2, input_shape=(224, 224, 3))
    est = Estimator(model=model,
                    loss_fn=objectives.get("sparse_categorical_crossentropy"),
                    optimizer=optimizers.SGD(0.1, momentum=0.9),
                    compute_dtype=jnp.bfloat16)
    rs = np.random.RandomState(0)
    x = rs.rand(8, 224, 224, 3).astype(np.float32)  # batch size is
    y = rs.randint(0, 2, 8).astype(np.float32)      # irrelevant to grads
    bx, by = shard_batch(est.mesh, (x, y))
    est._ensure_initialized(bx)
    step = est._build_train_step()
    return step.lower(est.params, est.opt_state, est.model_state,
                      __import__("jax").random.PRNGKey(0), bx, by).compile()


def build_bert_step():
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.capture.text import BERTClassifier, bert_input_pack
    from analytics_zoo_tpu.parallel.mesh import shard_batch

    cfg = dict(vocab=30522, hidden_size=768, n_block=12, n_head=12,
               max_position_len=512, intermediate_size=3072,
               compute_dtype=jnp.bfloat16)
    clf = BERTClassifier(2, bert_config=cfg)
    est = clf.model.get_estimator()
    rs = np.random.RandomState(0)
    x = bert_input_pack(rs.randint(1, 30000, (8, 128)))
    y = rs.randint(0, 2, 8).astype(np.float32)
    bx, by = shard_batch(est.mesh, (x, y))
    est._ensure_initialized(bx)
    step = est._build_train_step()
    return step.lower(est.params, est.opt_state, est.model_state,
                      jax.random.PRNGKey(0), bx, by).compile()


def main():
    import jax
    assert jax.device_count() >= 8, "run with 8 simulated devices"
    print("devices:", jax.device_count(), jax.devices()[0].platform)

    resnet_c = build_resnet_step()
    b = collective_bytes(resnet_c)
    # single-chip step, builders' figure (not in any driver record):
    # 95.4 ms @ b256
    project("ResNet-50 b256/chip DP", b, 0.0954)

    bert_c = build_bert_step()
    b2 = collective_bytes(bert_c)
    # builders' figure: 105.4 ms @ b128 s128 (driver's r03: 107.7 ms)
    project("BERT-base b128/chip DP", b2, 0.1054)


if __name__ == "__main__":
    main()
