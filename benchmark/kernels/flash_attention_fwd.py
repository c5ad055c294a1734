"""``flash_attention_fwd``: causal attention forward on one device's
``(rows x heads, seq, head_dim)`` bf16 q, k, v.

Must do: QK^T and PV over the lower triangle only — ``2 * BH * T^2 * d``
operations (half of the dense ``4 * BH * T^2 * d``; the masked half is
not work the algorithm needs).  Must move: q, k, v in and o out once in
bf16, plus the float32 log-sum-exp row the backward reads.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    bh, t, d = shapes["rows_per_device"] * m["n_head"], shapes["seq"], m["n_embd"] // m["n_head"]
    return {"flops": calls * 2.0 * bh * t * t * d, "bytes": calls * (4 * bh * t * d * 2 + bh * t * 4)}
