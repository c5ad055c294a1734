"""Arithmetic the metrics share: percentiles, the peaks row, MFU."""
from __future__ import annotations

import math
from typing import Dict, Sequence

# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
# Copied from ``deepspeed_tpu/profiling/flops_profiler.py::DEVICE_PEAKS``
# so that no program PR can move a peak.  A device without a row is an
# error, not a default.
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e" system architecture',
    },
}


def peak(device_kind: str) -> Dict[str, object]:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peak for device kind {device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0–100), linear interpolation between
    order statistics (numpy's default method)."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def gpt2_param_count(dims: Dict[str, int]) -> int:
    """Parameters of a GPT-2 with a tied head (embeddings counted once)."""
    d, l = dims["n_embd"], dims["n_layer"]
    per_block = 12 * d * d + 13 * d  # qkv, proj, fc, fc_proj + biases + two LayerNorms
    return dims["vocab_size"] * d + dims["n_positions"] * d + l * per_block + 2 * d


def train_flops_per_token(dims: Dict[str, int], seq: int) -> float:
    """Operations the forward and backward passes need per trained
    token, recomputation not counted: ``6 N`` for the matmuls against
    the weights plus ``12 L D seq`` for attention's two products."""
    return 6.0 * gpt2_param_count(dims) + 12.0 * dims["n_layer"] * dims["n_embd"] * seq
