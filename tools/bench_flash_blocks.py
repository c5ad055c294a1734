"""Micro-bench flash attention fwd+bwd block sizes at a given shape.

Run: python tools/bench_flash_blocks.py [B H T D]
Prints ms per fwd+bwd for each (block_q, block_k) combo — the tuning
data behind the per-shape block choices in flash_attention.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from deepspeed_tpu.ops.attention.flash_attention import flash_attention

    B = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    H = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    T = int(sys.argv[3]) if len(sys.argv) > 3 else 1024
    D = int(sys.argv[4]) if len(sys.argv) > 4 else 64
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, T, D)) * 0.1, jnp.bfloat16) for _ in range(3))

    flops = 4 * B * H * T * T * D / 2 * 3.5  # causal fwd (x1) + FA2 bwd (~x2.5)
    results = []
    for bq in (1024, 512, 256, 128):
        for bk in (1024, 512, 256, 128):
            if bq > T or bk > T:
                continue

            def f(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk) ** 2
                )

            # chain iterations through a data dependency, end with a true
            # host fetch, and DIFFERENCE two chain lengths — the fixed
            # cost of one dispatch cancels instead of swamping sub-ms
            # kernels
            def chain(length):
                def many(q, k, v):
                    def body(c, _):
                        dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(c, k, v)
                        return c + 1e-6 * dq.astype(c.dtype), (jnp.sum(dk) + jnp.sum(dv)).astype(jnp.float32)

                    c, s = jax.lax.scan(body, q, None, length=length)
                    return jnp.sum(c).astype(jnp.float32) + jnp.sum(s)

                return jax.jit(many)

            try:
                m_short, m_long = chain(20), chain(120)
                float(m_short(q, k, v))
                float(m_long(q, k, v))  # compile + warm both
                t0 = time.time()
                float(m_short(q, k, v))
                t_short = time.time() - t0
                t0 = time.time()
                float(m_long(q, k, v))
                t_long = time.time() - t0
                dt = (t_long - t_short) / 100
            except Exception as e:
                print(f"bq={bq:5d} bk={bk:5d}  FAILED {str(e)[:80]}")
                continue
            tf = flops / dt / 1e12
            results.append((dt, bq, bk))
            print(f"bq={bq:5d} bk={bk:5d}  {dt*1e3:7.2f} ms  ~{tf:5.1f} TFLOP/s")
    results.sort()
    print("best:", results[0] if results else None)


if __name__ == "__main__":
    main()
