"""Measure dense-psum vs sparse (rows+ids allgather) embedding-gradient
exchange at GPT-2 shapes — the in-graph analog of the reference's CSR
embedding gradients (``runtime/csr_tensor.py`` + ``engine.py:1559``
``csr_allreduce``), which this framework deliberately does NOT run
in-graph (VERDICT r3 #10 asks for the decision to be measured and
written down; the conclusion lives in docs/design-notes.md).

Two exchange formulations for the wte gradient under data parallelism:

  dense:  every rank psums the full (V, D) scatter-added gradient —
          what the engine's compiled step does today (the embedding
          grad rides the same psum/reduce-scatter as every other grad).
  sparse: every rank all-gathers its (B·T, D) token-grad rows + ids and
          scatter-adds the gathered rows into the dense (V, D) buffer
          locally — wire ∝ tokens instead of vocab (the reference's CSR
          motivation), compute adds a (dp·B·T)-row scatter.

Run on the 8-device CPU mesh for HLO wire bytes; on TPU it times the
local scatter-add the sparse form adds.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.utils.device import on_tpu_backend
    from deepspeed_tpu.utils.hlo import collective_bytes

    V, D = 50257, 768  # GPT-2 small vocab/emb
    BT = 4 * 1024      # per-rank tokens (micro_bs 4 × seq 1024)
    n = jax.device_count()
    on_tpu = on_tpu_backend()
    mesh = make_mesh(MeshConfig(data=n))
    rows_sh = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    rng = np.random.default_rng(0)
    ids = jax.device_put(rng.integers(0, V, (n * BT,), dtype=np.int32), rows_sh)
    rows = jax.device_put(rng.standard_normal((n * BT, D)).astype(np.float32), rows_sh)

    def dense_exchange(ids, rows):
        # per-rank scatter-add to dense, then psum (what grad-psum does)
        g = jnp.zeros((V, D), jnp.float32).at[ids].add(rows)
        return jax.lax.with_sharding_constraint(g, rep)

    def sparse_exchange(ids, rows):
        # allgather rows+ids (already sharded → constraint to replicated
        # inserts the gather), then ONE local scatter-add
        ids_full = jax.lax.with_sharding_constraint(ids, rep)
        rows_full = jax.lax.with_sharding_constraint(rows, rep)
        return jnp.zeros((V, D), jnp.float32).at[ids_full].add(rows_full)

    d_txt = jax.jit(dense_exchange).lower(ids, rows).compile().as_text()
    s_txt = jax.jit(sparse_exchange).lower(ids, rows).compile().as_text()
    d_bytes, s_bytes = collective_bytes(d_txt), collective_bytes(s_txt)
    print(f"devices={n}  V·D dense grad = {V*D*4/1e6:.1f} MB, per-rank rows = {BT*D*4/1e6:.1f} MB")
    print(f"dense-psum wire bytes:  {d_bytes/1e6:10.1f} MB")
    print(f"sparse-gather wire:     {s_bytes/1e6:10.1f} MB   ({d_bytes/max(s_bytes,1):.1f}x less)")

    if on_tpu:
        # the sparse form's added local cost: scatter-add of n·BT rows
        f = jax.jit(lambda i, r: jnp.zeros((V, D), jnp.float32).at[i].add(r))
        i1 = jnp.asarray(np.asarray(ids))
        r1 = jnp.asarray(np.asarray(rows))
        _ = np.asarray(f(i1, r1)[0, 0])
        t0 = time.time()
        for _ in range(10):
            o = f(i1, r1)
        _ = np.asarray(o[0, 0])
        print(f"TPU scatter-add of {n*BT} rows into ({V},{D}): {(time.time()-t0)/10*1000:.2f} ms")


if __name__ == "__main__":
    main()
