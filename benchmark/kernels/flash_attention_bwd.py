"""``flash_attention_bwd`` (the fused single pass): causal attention
backward on one device's ``(rows x heads, seq, head_dim)`` tensors.

Must do: five products over the lower triangle (recompute S, dP, dV,
dQ, dK) — ``5 * BH * T^2 * d`` operations, 2.5 times the forward.  Must
move: q, k, v, o, do in and dq, dk, dv out once in bf16, plus the
float32 log-sum-exp row.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    bh, t, d = shapes["rows_per_device"] * m["n_head"], shapes["seq"], m["n_embd"] // m["n_head"]
    return {"flops": calls * 5.0 * bh * t * t * d, "bytes": calls * (8 * bh * t * d * 2 + bh * t * 4)}
