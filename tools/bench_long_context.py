"""Long-context TRAINING throughput: sparse (BigBird splash) vs dense
flash attention inside the full engine train step at 8k/16k sequence —
the reference's long-sequence story is block-sparse attention
("10x longer sequences, up to 6.3x faster",
docs/_posts/2020-09-09-sparse-attention.md:27-33); this measures the
TPU-native analog end-to-end (not just the attention kernel): GPT-2
small-width (768) model, selective remat keeping the attention kernels'
residuals (attn_o/attn_lse — both the flash and splash paths emit
them), chunked cross-entropy, in-kernel dropout available.

Run: python tools/bench_long_context.py [seq] [n_layer]
Appends a capability record on TPU.
"""
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_mode(mode: str, seq: int, n_layer: int, steps: int):
    import jax

    import bench
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.device import on_tpu_backend

    on_tpu = on_tpu_backend()
    cfg = dataclasses.replace(
        gpt2.GPT2_SMALL if on_tpu else gpt2.GPT2_TINY,
        n_positions=seq,
        n_layer=n_layer,
        attention_mode=mode,
        remat=True,
        xent_chunk_size=512,
        remat_save_names=("qkv", "attn_o", "attn_lse"),
    )
    model_fn, init_fn, tp_fn = gpt2.make_model(cfg)
    # The fed global batch is (dp, seq).  Pin the config to it (mesh
    # default data=-1 makes dp == device count) so the engine's batch
    # triad check holds by construction and the per-chip tokens/s
    # normalization below (seq/dt — the dp-sized batch cancels the dp
    # chips) can't silently drift if either side changes.
    dp_devices = jax.device_count()
    config = {
        "train_batch_size": dp_devices,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "steps_per_print": 10_000,
    }
    params = gpt2.init_params_device(cfg) if on_tpu else init_fn()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=params, config=config, tp_spec_fn=tp_fn
    )
    rng = np.random.default_rng(0)

    # global batch = dp world (1 on the single TPU chip; the 8-CPU dev
    # mesh shards one sample per device — tokens/s stays per-chip)
    dp = engine.mesh_info.dp_world_size
    assert dp == dp_devices, (
        f"mesh dp world ({dp}) != device count ({dp_devices}); the config "
        "batch above was pinned to the wrong dp"
    )
    def batches(n):
        for _ in range(n):
            yield {"input_ids": rng.integers(0, cfg.vocab_size, (dp, seq), dtype=np.int32)}

    dt, _phases = bench._timed_steps(engine, batches, steps, f"long-{mode}-{seq}")
    tok_s = seq / dt  # per-chip: the dp-sized global batch cancels the dp chips
    print(f"[long-context {mode}] seq={seq} L={n_layer}: step={dt*1e3:.1f}ms tokens/s={tok_s:,.0f}", flush=True)
    return dt, tok_s


def make_record(seq: int, n_layer: int, dt_f: float, tok_f: float, dt_s: float, tok_s: float) -> dict:
    """The capability/bench record for one sparse-vs-dense pair — single
    source of the metric name and field layout (bench.py's longctx-train
    rung and this tool's main() both emit it)."""
    speedup = dt_f / dt_s
    return {
        "metric": f"long_context_seq{seq}_sparse_train_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/s (full train step, 1 chip)",
        "dense_flash_tokens_per_sec": round(tok_f, 1),
        "sparse_over_dense": round(speedup, 2),
        "n_layer": n_layer,
        "note": "end-to-end TRAINING step (fwd+bwd+Adam) with BigBird splash "
        "attention vs dense flash; selective remat keeps both kernels' "
        "attn_o/attn_lse residuals (reference long-seq claim: up to 6.3x, "
        "sparse-attention blog :32; NB r5.1 made the DENSE baseline itself "
        "2.19x faster at 16k via splash-dense routing)",
    }


def main():
    import jax

    seq = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    n_layer = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    from deepspeed_tpu.utils.device import on_tpu_backend

    on_tpu = on_tpu_backend()
    if not on_tpu:
        # big enough for the default BigBird layout's sliding window
        seq, n_layer = min(seq, 512), 2
    steps = 4 if on_tpu else 2

    dt_f, tok_f = run_mode("flash", seq, n_layer, steps)
    dt_s, tok_s = run_mode("sparse", seq, n_layer, steps)
    print(f"sparse speedup over dense flash at seq {seq}: {dt_f / dt_s:.2f}x", flush=True)

    rec = make_record(seq, n_layer, dt_f, tok_f, dt_s, tok_s)
    print("RESULT " + json.dumps(rec), flush=True)
    from deepspeed_tpu.telemetry.regression import tool_history_emit

    tool_history_emit(rec, rung="longctx-train",
                      base_dir=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if on_tpu:
        import bench

        bench.append_capability_record(rec)


if __name__ == "__main__":
    main()
