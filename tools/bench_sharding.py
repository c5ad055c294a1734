"""Weight-update-sharding sweep: replicated vs cross-replica ZeRO-1.

Runs standalone:

    python tools/bench_sharding.py --dryrun      # 8 virtual CPU devices
    python tools/bench_sharding.py --steps 16    # real devices

Sweeps the optimizer-update phase (docs/sharding.md) on a GPT-2 config
(124M on TPU, tiny on the CPU dryrun) across three placements:

* ``replicated`` — classic GSPMD ZeRO-0-style update: every replica
  recomputes the full update over replicated optimizer state;
* ``cross-replica`` — arXiv:2004.13336 weight-update sharding, the
  default at ``zero_optimization.stage >= 1``: state + update sharded
  along ``data``, one params-sized all-gather of updated values;
* ``cross-replica x fsdp`` — the composed ``data x fsdp`` grid
  (``add_update_axis`` fsdp-major placement), when devices allow.

Each record carries the MEASURED update-phase costs next to the
analytic model so regressions in either are visible:

* ``update_flops_per_replica`` / ``update_bytes_per_replica`` —
  compiled cost analysis of the engine's ``_apply_update`` phase alone
  (the same probe tests/test_sharding.py pins the ~dp x ratio with);
* ``opt_state_bytes_per_replica`` — addressable-shard bytes of the
  live optimizer state (vs ``opt_state_bytes_total``);
* ``update_allgather_bytes_hlo`` — all-gather wire bytes parsed from
  the compiled train executable (sharded pays one params-sized gather,
  replicated pays none);
* ``model`` — :func:`deepspeed_tpu.sharding.weight_update_model`;
* ``steps_per_s``, the loss trajectory (parity vs replicated), and
  ``compiles`` (must be 1: the sharded update is one executable).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# --dryrun must win before jax initializes (same recipe as tests/conftest.py)
if "--dryrun" in sys.argv:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def log(msg):
    print(f"[bench_sharding] {msg}", file=sys.stderr, flush=True)


def emit(rec):
    print(json.dumps(rec), flush=True)


def _opt_state_bytes(engine):
    import jax

    leaves = [
        l for l in jax.tree.leaves(engine.state["opt_state"]) if hasattr(l, "addressable_shards")
    ]
    per_dev = sum(l.addressable_shards[0].data.nbytes for l in leaves)
    total = sum(l.nbytes for l in leaves)
    return per_dev, total


def _update_phase_cost(engine):
    """Compiled cost analysis of the update phase ALONE — grads in,
    updated state out — so the numbers isolate exactly what
    cross-replica sharding claims to cut."""
    import jax
    import jax.numpy as jnp

    grads = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), engine.state["params"])
    compiled = jax.jit(lambda s, g: engine._apply_update(s, g)).lower(engine.state, grads).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def _train_allgather_bytes(engine):
    from deepspeed_tpu.utils.hlo import collective_bytes_by_op

    keys = [k for k in engine._compiled if isinstance(k, tuple) and k[0] == "train_batch"]
    if not keys:
        return 0
    return collective_bytes_by_op(engine._compiled[keys[0]].as_text()).get("all-gather", 0)


def sweep(steps: int, on_tpu: bool):
    import dataclasses

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.sharding import weight_update_model

    n_dev = jax.device_count()
    cfg = (
        dataclasses.replace(gpt2.GPT2_SMALL, remat=False, scan_unroll=gpt2.GPT2_SMALL.n_layer)
        if on_tpu
        else dataclasses.replace(gpt2.GPT2_TINY, n_layer=4, n_embd=64, n_head=4, vocab_size=256)
    )
    micro_bs, seq = (8, 1024) if on_tpu else (1, 32)
    model_fn, init_fn, _ = gpt2.make_model(cfg)
    init = init_fn()

    def batches(n, global_bs):
        r = np.random.default_rng(1)  # same data per placement
        for _ in range(n):
            yield {"input_ids": r.integers(0, cfg.vocab_size, (global_bs, seq), dtype=np.int32)}

    runs = [
        ("replicated", {"data": n_dev}, 1, False),
        ("cross-replica", {"data": n_dev}, 1, True),
    ]
    if n_dev >= 4 and n_dev % 2 == 0:
        runs.append(("cross-replica-fsdp", {"data": 2, "fsdp": n_dev // 2}, 2, True))

    base = None  # the replicated baseline record
    for name, mesh, stage, cross in runs:
        config = {
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": 1,
            "bf16": {"enabled": True},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": stage, "cross_replica_weight_update": cross},
            "mesh": mesh,
            "steps_per_print": 100000,
        }
        try:
            init_copy = jax.tree.map(np.copy, init)
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model_fn, model_parameters=init_copy, config=config
            )
            global_bs = micro_bs * engine.mesh_info.dp_world_size
            losses = [float(engine.train_batch(b)) for b in batches(2, global_bs)]  # warm
            t0 = time.time()
            losses += [float(engine.train_batch(b)) for b in batches(steps, global_bs)]
            dt = (time.time() - t0) / steps
        except Exception as e:  # noqa: BLE001 — one failed placement must not kill the sweep
            log(f"[{name}] FAILED: {str(e)[:300]}")
            emit({"metric": f"weight_update_{name}", "skipped": True, "reason": str(e)[:300]})
            continue

        dp = engine.mesh_info.dp_world_size
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(engine.state["params"]))
        flops, bytes_ = _update_phase_cost(engine)
        per_dev, total = _opt_state_bytes(engine)
        rec = {
            "metric": f"weight_update_{name}",
            "value": round(1.0 / dt, 3),
            "unit": "steps/s",
            "dp": dp,
            "n_params": n_params,
            "update_flops_per_replica": int(flops),
            "update_bytes_per_replica": int(bytes_),
            "opt_state_bytes_per_replica": int(per_dev),
            "opt_state_bytes_total": int(total),
            "update_allgather_bytes_hlo": int(_train_allgather_bytes(engine)),
            "model": weight_update_model(n_params, dp, sharded=cross),
            "final_loss": round(losses[-1], 5),
            "losses": [round(l, 5) for l in losses],
            "compiles": engine.compilation_count,
            "micro_bs": micro_bs,
            "seq": seq,
        }
        if name == "replicated":
            base = rec
        elif base is not None and base["dp"] == dp:
            rec["update_flops_reduction_vs_replicated"] = round(
                base["update_flops_per_replica"] / max(rec["update_flops_per_replica"], 1), 2
            )
            rec["opt_state_bytes_reduction_vs_replicated"] = round(
                base["opt_state_bytes_per_replica"] / max(rec["opt_state_bytes_per_replica"], 1), 2
            )
            pairs = list(zip(rec["losses"], base["losses"]))
            rec["loss_rel_dev_vs_replicated"] = round(
                float(np.mean([abs(a - b) / (abs(b) + 1e-9) for a, b in pairs])), 4
            )
        log(
            f"[{name}] steps/s={rec['value']} update_flops/replica={int(flops):,} "
            f"opt_bytes/replica={per_dev:,} (total {total:,}) compiles={rec['compiles']}"
        )
        emit(rec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true", help="8 virtual CPU devices (handled pre-import)")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()

    import jax

    from deepspeed_tpu.utils.device import on_tpu_backend

    on_tpu = on_tpu_backend()
    steps = args.steps if args.steps is not None else (12 if on_tpu else 4)
    log(f"backend={jax.default_backend()} devices={jax.device_count()} steps={steps}")
    sweep(steps, on_tpu)


if __name__ == "__main__":
    main()
