"""Splash block-sparse attention vs dense flash: speed curve over
sequence length (the reference claims up to 6.3x at long sequences,
docs/_posts/2020-09-09-sparse-attention.md:32).

Run on the TPU chip: python tools/bench_sparse.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.attention.flash_attention import flash_attention
from deepspeed_tpu.ops.attention.sparse import BigBirdSparsityConfig, block_sparse_attention


def timed_chain(fn, q, k, v, iters=48):
    """Dependency-chained timing: q is perturbed by a reduction of the
    output, so the chain runs as one program and ``iters`` amortizes its
    one dispatch over the calls."""

    @jax.jit
    def chain(q, k, v):
        def body(i, carry):
            q, s = carry
            o = fn(q, k, v)
            s2 = jnp.mean(o.astype(jnp.float32))
            return q + (s2 * 1e-12).astype(q.dtype), s + s2

        q, s = jax.lax.fori_loop(0, iters, body, (q, jnp.zeros((), jnp.float32)))
        return s

    out = chain(q, k, v)
    _ = float(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _ = float(chain(q, k, v))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def grad_of(fn):
    """Full training backward: differentiate ALL of q/k/v and fold every
    grad into the result, or XLA dead-code-eliminates the dk/dv kernel
    of whichever backend splits its backward into separate programs and
    the comparison is asymmetric."""

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    def g(q, k, v):
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return dq + (jnp.sum(dk) + jnp.sum(dv)).astype(dq.dtype)

    return g


def main():
    H, hd, block = 12, 64, 128
    B = 1
    mode = sys.argv[1] if len(sys.argv) > 1 else "both"
    r = np.random.default_rng(0)
    print(f"{'seq':>6s} {'pass':>8s} {'dense flash':>12s} {'splash':>12s} {'speedup':>8s} {'density':>8s}")
    for T in (4096, 8192, 16384):
        sc = BigBirdSparsityConfig(
            num_heads=H, block=block, num_random_blocks=1,
            num_sliding_window_blocks=3, num_global_blocks=1, attention="unidirectional",
        )
        layout = sc.make_layout(T)
        density = float(layout.sum()) / layout[0].size / H
        q = jnp.asarray(r.standard_normal((B, H, T, hd)) * 0.3, jnp.bfloat16)
        k = jnp.asarray(r.standard_normal((B, H, T, hd)) * 0.3, jnp.bfloat16)
        v = jnp.asarray(r.standard_normal((B, H, T, hd)) * 0.3, jnp.bfloat16)

        dense = lambda q, k, v: flash_attention(q, k, v, causal=True)
        splash = lambda q, k, v: block_sparse_attention(
            q, k, v, layout, block, causal=True, backend="splash"
        )
        passes = []
        if mode in ("fwd", "both"):
            passes.append(("fwd", dense, splash))
        if mode in ("bwd", "both"):
            # training path: fwd + dedicated Pallas backward
            passes.append(("fwd+bwd", grad_of(dense), grad_of(splash)))
        for name, fd, fs in passes:
            t_dense = timed_chain(fd, q, k, v)
            t_splash = timed_chain(fs, q, k, v)
            print(
                f"{T:6d} {name:>8s} {t_dense*1e3:10.2f}ms {t_splash*1e3:10.2f}ms "
                f"{t_dense/t_splash:7.2f}x {density*100:7.1f}%",
                flush=True,
            )


if __name__ == "__main__":
    main()
