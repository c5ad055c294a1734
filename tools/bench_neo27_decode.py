"""GPT-Neo-2.7B-class KV-cache decode on one chip — the BASELINE.json
workload ladder's last rung ("GPT-Neo-2.7B inference with kernel
injection").  HF GPT-Neo weights flow through
``inference/injection.HFGPTNEOLayerPolicy`` (HF-parity test in
tests/test_inference.py); this probe measures serving throughput at the
2.7B scale with an on-chip random init (bf16 weights ≈ 5.3GB HBM) and
appends the record to BENCH_CAPABILITY.json.

The measurement itself is ``bench.bench_inference`` — identical
methodology (windowed marginal decode rate + noise guard) to the XL
decode rungs, applied to the Neo preset.

Run: python tools/bench_neo27_decode.py [quantize_bits: 0|8]
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    import bench
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.device import on_tpu_backend

    bits = int(sys.argv[1]) if len(sys.argv) > 1 else 0
    label = {0: "bf16", 8: "int8"}.get(bits)
    if label is None:
        raise SystemExit("quantize_bits must be 0 (bf16) or 8 (true-int8 serving)")
    on_tpu = on_tpu_backend()
    name = "gpt-neo-2.7b" if on_tpu else "tiny"  # dev runs shrink the model

    rec = bench.bench_inference(name, bits, label)
    rec.update(
        params_m=round(gpt2.PRESETS[name].num_params() / 1e6, 1),
        note="BASELINE ladder final rung: 2.7B-class serving on one v5e; "
        "HF GPT-Neo weights map through HFGPTNEOLayerPolicy (parity test "
        "in tests/test_inference.py); random on-chip init",
    )
    print("RESULT " + json.dumps(rec), flush=True)
    if on_tpu:
        bench.append_capability_record(rec)


if __name__ == "__main__":
    main()
