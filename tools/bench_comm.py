"""Comm-strategy sweep: dense vs int8 vs 1-bit gradient exchange.

Runs standalone:

    python tools/bench_comm.py --dryrun          # 8 virtual CPU devices
    python tools/bench_comm.py --steps 16        # real devices

Two model families (the ISSUE-6 acceptance pair): a GPT-2 config (124M
on TPU, tiny-8L on the CPU dryrun) swept across comm.strategy
dense/int8/onebit, and a BERT s512 config (BERT-Large on TPU, tiny on
CPU) swept dense/int8 plus the **1-bit LAMB** frozen-exchange phase
(optimizer-level momentum compression — the large-batch rung of
arXiv:2104.06069).

Each record carries, per strategy:

* ``steps_per_s`` and the final-loss trajectory (parity vs dense);
* ``grad_exchange_bytes_hlo`` — collective bytes parsed from the
  compiled train executable (utils/hlo.py).  NB dense's per-micro
  reduction sits inside the accumulation scan, so its static text
  *undercounts* runtime bytes by ``gas``x; ``grad_exchange_bytes_step``
  applies that correction (and is what the >= 4x acceptance ratio is
  computed from);
* ``comm_bytes_model`` — the analytic model (comm/strategy.py);
* ``compiles`` — must be 1 per strategy (zero recompiles across steps).
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
    print(f"[bench_comm] {msg}", file=sys.stderr, flush=True)


def emit(rec):
    print(json.dumps(rec), flush=True)


def _tb_collective_bytes(engine):
    """Collective bytes of the ACTIVE train executable — the frozen one
    when a 1-bit optimizer has entered its compressed phase."""
    from deepspeed_tpu.utils.hlo import collective_bytes

    keys = [k for k in engine._compiled if isinstance(k, tuple) and k[0] == "train_batch"]
    frozen = [k for k in keys if k[1]]
    key = frozen[0] if frozen else keys[0]
    return collective_bytes(engine._compiled[key].as_text())


def _run_engine(model_fn, params, config, batches, steps, label, warm_steps=2):
    import deepspeed_tpu

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=params, config=config
    )
    # warm past any phase boundary (1-bit freeze_step recompiles once)
    losses = [float(engine.train_batch(b)) for b in batches(warm_steps)]
    t0 = time.time()
    losses += [float(engine.train_batch(b)) for b in batches(steps)]
    dt = (time.time() - t0) / steps
    log(f"[{label}] step={dt*1e3:.1f}ms loss={losses[-1]:.4f} compiles={engine.compilation_count}")
    return engine, losses, dt


def sweep_family(family: str, steps: int, on_tpu: bool):
    import jax

    import deepspeed_tpu  # noqa: F401

    n_dev = jax.device_count()
    rng = np.random.default_rng(0)

    if family == "gpt2":
        import dataclasses

        from deepspeed_tpu.models import gpt2

        cfg = (
            dataclasses.replace(gpt2.GPT2_SMALL, remat=False, scan_unroll=gpt2.GPT2_SMALL.n_layer)
            if on_tpu
            else dataclasses.replace(gpt2.GPT2_TINY, n_layer=4, n_embd=64, n_head=4, vocab_size=256)
        )
        micro_bs, seq = (4, 1024) if on_tpu else (1, 32)
        model_fn, init_fn, _ = gpt2.make_model(cfg)
        init = init_fn()

        def make_batches(global_bs):
            def batches(n):
                r = np.random.default_rng(1)  # same data per strategy
                for _ in range(n):
                    yield {"input_ids": r.integers(0, cfg.vocab_size, (global_bs, seq), dtype=np.int32)}

            return batches

        opt_sweep = []
    else:  # bert-s512
        import dataclasses

        from deepspeed_tpu.models import bert

        base = bert.BERT_LARGE if on_tpu else bert.BERT_TINY
        seq = min(512, base.max_position_embeddings)
        cfg = dataclasses.replace(base, remat=False, scan_unroll=base.num_hidden_layers)
        micro_bs = 16 if on_tpu else 2
        model_fn, init_fn, _ = bert.make_model(cfg)
        init = init_fn()

        def make_batches(global_bs):
            def batches(n):
                r = np.random.default_rng(1)
                for _ in range(n):
                    ids = r.integers(0, cfg.vocab_size, (global_bs, seq), dtype=np.int32)
                    yield {
                        "input_ids": ids,
                        "masked_lm_labels": np.where(
                            r.random((global_bs, seq)) < 0.15, ids, -100
                        ).astype(np.int32),
                        "next_sentence_label": r.integers(0, 2, (global_bs,), dtype=np.int32),
                    }

            return batches

        # the 1-bit LAMB rung: optimizer-level momentum compression
        # (frozen phase) rather than a comm.strategy grad exchange.
        # freeze_step=3: the variance estimate needs a few warmup steps
        # or the frozen denom is garbage (freeze_step=1 diverges)
        opt_sweep = [("onebit-lamb", {"type": "OneBitLamb", "params": {"lr": 1e-3, "freeze_step": 3}})]

    # gas=4: large-batch accumulation is where one-exchange-per-step
    # wins — dense reduces per micro batch, the compressed strategies
    # exchange once at the boundary
    gas = 4
    dense_bytes_step = None
    dense_losses = None
    runs = [("dense", None), ("int8", None), ("onebit", None)] + [
        (name, opt) for name, opt in opt_sweep
    ]
    for strat, opt_cfg in runs:
        config = {
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": gas,
            "bf16": {"enabled": True},
            "optimizer": opt_cfg or {"type": "Adam", "params": {"lr": 1e-4 if family == "gpt2" else 1e-3}},
            "steps_per_print": 100000,
        }
        if opt_cfg is None:
            config["comm"] = {"strategy": strat, "threshold_bytes": 0}
        label = f"{family}-{strat}"
        try:
            import jax as _jax

            init_copy = _jax.tree.map(np.copy, init)
            warm = 2 if opt_cfg is None else int(opt_cfg["params"].get("freeze_step", 0)) + 2
            engine, losses, dt = _run_engine(
                model_fn, init_copy, config,
                make_batches(micro_bs * gas * n_dev), steps, label, warm_steps=warm,
            )
        except Exception as e:  # noqa: BLE001 — one failed rung must not kill the sweep
            log(f"[{label}] FAILED: {str(e)[:300]}")
            emit({"metric": f"comm_strategy_{family}_{strat}", "skipped": True, "reason": str(e)[:300]})
            continue
        hlo_bytes = _tb_collective_bytes(engine)
        summ = engine.comm_summary()
        # dense's grad reduction runs per micro batch inside the scan —
        # static HLO text shows it once; correct to runtime bytes.  The
        # explicit strategies and the 1-bit frozen phase exchange ONCE
        # per step (their rows accumulate locally), no correction.
        once_per_step = engine._comm_explicit or engine._onebit_frozen
        bytes_step = hlo_bytes * (1 if once_per_step else gas)
        rec = {
            "metric": f"comm_strategy_{family}_{strat}",
            "value": round(1.0 / dt, 3),
            "unit": "steps/s",
            "comm_strategy": summ["strategy"] if opt_cfg is None else strat,
            "grad_exchange_bytes_hlo": int(hlo_bytes),
            "grad_exchange_bytes_step": int(bytes_step),
            "comm_bytes_model": summ["grad_exchange_bytes"],
            "final_loss": round(losses[-1], 5),
            "losses": [round(l, 5) for l in losses],
            "compiles": engine.compilation_count,
            "gas": gas,
            "micro_bs": micro_bs,
            "seq": seq,
        }
        if strat == "dense":
            dense_bytes_step = bytes_step
            dense_losses = losses
        else:
            if dense_bytes_step:
                rec["bytes_reduction_vs_dense"] = round(dense_bytes_step / max(bytes_step, 1), 2)
            if dense_losses:
                pairs = [(a, b) for a, b in zip(losses, dense_losses)]
                rec["loss_rel_dev_vs_dense"] = round(
                    float(np.mean([abs(a - b) / (abs(b) + 1e-9) for a, b in pairs])), 4
                )
        emit(rec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true", help="8 virtual CPU devices (handled pre-import)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--families", default="gpt2,bert")
    args = ap.parse_args()

    import jax

    from deepspeed_tpu.utils.device import on_tpu_backend

    on_tpu = on_tpu_backend()
    steps = args.steps if args.steps is not None else (12 if on_tpu else 6)
    log(f"backend={jax.default_backend()} devices={jax.device_count()} steps={steps}")
    for family in args.families.split(","):
        sweep_family(family.strip(), steps, on_tpu)


if __name__ == "__main__":
    main()
