"""Arithmetic the metrics share: percentiles, the peaks row, MFU, and
the spread of a set of runs as a check reads it.

    python3 benchmark/stats.py <file of "RUN set=<s> seed=<n>" lines and result lines>

prints, for every set and end-to-end metric in the file, the median,
the quartiles and the spreads (``benchmark/sets.sh`` ends with it).
"""
from __future__ import annotations

import json
import math
import statistics
import sys
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


def spread(values: Sequence[float], leave_out_farthest: bool = False) -> float:
    """A set of runs' spread: the distance between its first and third
    quartile (``statistics.quantiles``: numpy's lie closer together) as
    a share of its median.  ``leave_out_farthest`` leaves
    out the run farthest from the median where that narrows the spread,
    as a check does before it holds the spread against a bound: one
    far-off run in a set then does no harm, two still show."""
    def iqr_share(xs: Sequence[float]) -> float:
        q1, _, q3 = statistics.quantiles(xs, n=4)
        return (q3 - q1) / abs(statistics.median(xs))

    xs = list(values)
    whole = iqr_share(xs)
    if not leave_out_farthest or len(xs) < 3:
        return whole
    med = statistics.median(xs)
    xs.remove(max(xs, key=lambda x: abs(x - med)))
    return min(whole, iqr_share(xs))


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


def summarize_sets(lines: Sequence[str]) -> Dict[str, Dict[str, Dict[str, float]]]:
    """``{set: {metric: {n, median, q1, q3, spread, spread_less_farthest}}}`` from
    the output of ``benchmark/sets.sh``: a ``RUN set=<s> seed=<n>`` line
    names the set of the result line that follows it."""
    runs: Dict[str, Dict[str, list]] = {}
    current = "?"
    for line in lines:
        line = line.strip()
        if line.startswith("RUN "):
            current = dict(kv.split("=", 1) for kv in line.split()[1:]).get("set", "?")
        elif line.startswith("{"):
            for name, m in json.loads(line).get("metrics", {}).items():
                runs.setdefault(current, {}).setdefault(name, []).append(float(m["value"]))
    out: Dict[str, Dict[str, Dict[str, float]]] = {}
    for s, metrics in runs.items():
        for name, xs in metrics.items():
            if len(xs) < 2:
                continue
            q1, med, q3 = statistics.quantiles(xs, n=4)
            out.setdefault(s, {})[name] = {"n": len(xs), "median": med, "q1": q1, "q3": q3, "spread": spread(xs),
                                           "spread_less_farthest": spread(xs, leave_out_farthest=True)}
    return out


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        for s, metrics in summarize_sets(f.readlines()).items():
            for name, r in metrics.items():
                print(f"SPREAD set={s} {name}: n={r['n']} median={r['median']!r} q1={r['q1']!r} q3={r['q3']!r} "
                      f"spread={100 * r['spread']:.3f}% less_farthest={100 * r['spread_less_farthest']:.3f}%")
