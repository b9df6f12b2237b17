"""Tuning harness: measure flash-attention fwd+bwd step time on the real
chip across block sizes (run manually; results inform DEFAULT_*_BLOCK)."""
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from analytics_zoo_tpu.ops.attention import flash_attention  # noqa: E402

B, H, S, D = 4, 8, 4096, 64
STEPS = 20


def timed_once(fn, *args):
    t0 = time.perf_counter()
    float(fn(*args))
    return time.perf_counter() - t0


def measure(q_block, kv_block, causal=True):
    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype(np.float32),
                           jnp.bfloat16) for _ in range(3))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       q_block=q_block, kv_block=kv_block
                                       ).astype(jnp.float32))

    grad_fn = jax.grad(loss, argnums=(0, 1, 2))

    def chained(q, k, v, eps, n):
        def body(carry, _):
            cq, ck, cv = carry
            dq, dk, dv = grad_fn(cq, ck, cv)
            return (cq + eps * dq, ck + eps * dk, cv + eps * dv), ()
        (q, k, v), _ = jax.lax.scan(body, (q, k, v), None, length=n)
        return jnp.sum(q.astype(jnp.float32))

    eps = jnp.bfloat16(0.0)
    # difference two scan lengths: t(2N) - t(N) = N steps of pure device
    # time, with the per-dispatch host latency cancelled
    c1 = jax.jit(lambda q, k, v, e: chained(q, k, v, e, STEPS)
                 ).lower(q, k, v, eps).compile()
    c2 = jax.jit(lambda q, k, v, e: chained(q, k, v, e, 2 * STEPS)
                 ).lower(q, k, v, eps).compile()
    float(c1(q, k, v, eps)); float(c2(q, k, v, eps))  # warm
    t1 = min(timed_once(c1, q, k, v, eps) for _ in range(3))
    t2 = min(timed_once(c2, q, k, v, eps) for _ in range(3))
    elapsed = max(t2 - t1, 1e-9)
    flops = 9 * B * H * S * S * D  # 9 causal-halved matmuls/step (bench.py)
    mfu = flops * STEPS / elapsed / 197e12
    per_step_ms = elapsed / STEPS * 1e3
    print(f"bq={q_block:5d} bk={kv_block:5d} step={per_step_ms:7.3f} ms "
          f"mfu={mfu:.3f}", flush=True)
    return mfu


if __name__ == "__main__":
    combos = [(512, 512), (256, 512), (512, 1024), (1024, 512),
              (1024, 1024), (256, 1024), (2048, 512), (512, 2048),
              (128, 1024), (1024, 128)]
    if len(sys.argv) > 1:
        combos = [tuple(map(int, a.split("x"))) for a in sys.argv[1:]]
    for bq, bk in combos:
        try:
            measure(bq, bk)
        except Exception as e:
            print(f"bq={bq} bk={bk} FAILED: {repr(e)[:200]}", flush=True)
