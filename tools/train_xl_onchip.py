"""Train GPT-2 XL (1.5B — the BASELINE.json north-star model) on ONE
chip via the ZeRO-Infinity streaming executor: HBM holds one layer
group + boundary activations; fp32 masters + Adam moments live on the
host (reference capability row: 13B on one 32GB device,
docs/_pages/features.md:116, partitioned_param_swapper.py:36).

This run is the CAPABILITY proof for the north-star model: every step
uploads each layer group from the host, so the host<->device link
bounds the step; throughput at this scale wants fsdp>=2 (see bench.py's
note).  Prints per-step loss/time + a JSON record.

Run: python tools/train_xl_onchip.py [steps] [seq] [micro_bs] [buffer_count]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 2
    seq = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    mb = int(sys.argv[3]) if len(sys.argv) > 3 else 4
    lpg = int(sys.argv[4]) if len(sys.argv) > 4 else 8

    cfg = gpt2.GPT2_XL
    model_fn, init_fn, _ = gpt2.make_model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "cpu", "buffer_count": lpg},
            "offload_optimizer": {"device": "cpu"},
        },
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "steps_per_print": 10_000,
    }
    t0 = time.time()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=init_fn(), config=config
    )
    print(f"init {time.time()-t0:.0f}s  engine={type(engine).__name__}", flush=True)

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (mb, seq), dtype=np.int32)}
    losses, times = [], []
    for s in range(steps):
        t0 = time.time()
        loss = float(engine.train_batch(batch))
        dt = time.time() - t0
        losses.append(loss)
        times.append(dt)
        print(f"step {s}: loss={loss:.4f}  {dt:.0f}s", flush=True)

    # one SERIALIZED step with per-phase sync: attributes wall time to
    # host-link upload vs chip compute vs grad drain vs host Adam (the
    # r4 steady-state decomposition — overlaps removed, so the phase sum
    # exceeds a normal pipelined step's wall time)
    timing = {}
    t0 = time.time()
    loss = float(engine.train_batch(batch, timing=timing))
    timing["total_serialized_s"] = time.time() - t0
    losses.append(loss)
    print("profiled step: " + "  ".join(f"{k}={v:.1f}s" for k, v in timing.items()), flush=True)

    rec = {
        "metric": "gpt2_xl_1p5b_single_chip_streaming_train",
        "params_m": round(cfg.num_params() / 1e6, 1),
        "losses": [round(l, 4) for l in losses],
        "step_seconds": [round(t, 1) for t in times],
        "step_breakdown_serialized": {k: round(v, 1) for k, v in timing.items()},
        "seq": seq,
        "micro_bs": mb,
        "engine": type(engine).__name__,
        "note": "steady-state streaming record on one chip: HBM holds "
        "one layer group; the serialized-step breakdown attributes wall time "
        "to host-link upload / chip compute / grad drain / host Adam "
        "(pipelined steps overlap these, so their wall < breakdown sum)",
    }
    print("RESULT " + json.dumps(rec), flush=True)
    # capability records live in their own file — bench.py clears
    # BENCH_EXTRA.json at the start of every run
    import bench

    bench.append_capability_record(rec)


if __name__ == "__main__":
    main()
