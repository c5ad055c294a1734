"""Profile one compiled train step on the real chip: per-source /
per-HLO-category / top-op device-time attribution (the tool behind the
MFU work — it exposed the fp32-dot flash kernels, the scan bookkeeping,
and the per-line TFLOP/s of every matmul).

The cost walk itself lives in ``deepspeed_tpu.telemetry.attribution``
(shared with profile_bert_step.py / profile_decode.py); this script is
the GPT-2 harness around it, plus the compile-time roofline table from
the executable's own HLO.

Run: python tools/profile_train_step.py [preset] [micro_bs] [gas] [seq]
"""
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.runtime.engine import _PlacedBatch
    from deepspeed_tpu.telemetry.attribution import (
        format_trace_tables,
        profile_and_report,
    )

    preset = sys.argv[1] if len(sys.argv) > 1 else "gpt2"
    mb = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    gas = int(sys.argv[3]) if len(sys.argv) > 3 else 4
    seq = int(sys.argv[4]) if len(sys.argv) > 4 else 1024
    steps = 3

    if preset.startswith("sweep:"):
        # profile one of the 774M sweep configurations by name
        from tools.sweep_774m import CONFIGS

        c = CONFIGS[preset.split(":", 1)[1]]
        cfg = dataclasses.replace(gpt2.GPT2_LARGE, **c["model"])
        mb, gas = c["mb"], c["gas"]
        opt_extra = c.get("opt") or {}
    else:
        cfg = dataclasses.replace(gpt2.PRESETS[preset], remat=False)
        opt_extra = {}
    seq = min(seq, cfg.n_positions)
    model_fn, init_fn, tp_fn = gpt2.make_model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3 if preset.startswith("sweep:") else 0},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4, **opt_extra}},
        "steps_per_print": 10_000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=init_fn(), config=config, tp_spec_fn=tp_fn
    )
    rng = np.random.default_rng(0)
    placed = _PlacedBatch(
        engine._stack_and_place(
            {"input_ids": rng.integers(0, cfg.vocab_size, (mb * gas, seq), dtype=np.int32)}
        )
    )
    loss = engine.train_batch(placed)
    float(loss)  # true sync

    def one_step():
        nonlocal loss
        loss = engine.train_batch(placed)

    tables = profile_and_report(one_step, steps=steps, sync=lambda: float(loss))
    print(format_trace_tables(tables, unit="step"))

    # compile-time roofline view from the executable's own HLO — the
    # same table the telemetry plane publishes as attribution/* gauges
    attr = engine.train_step_attribution()
    if attr is not None:
        print()
        print(attr.format_table())


if __name__ == "__main__":
    main()
