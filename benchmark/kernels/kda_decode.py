"""``kda_decode``: one decode step's KDA recurrence of one linear-attention
layer — every decoding row's recurrent state decayed, updated by the
delta rule and read, in place.

Must move, per call: for each row that decoded this step, the layer's
whole state **in and out once** — ``heads x dk x dv`` float32 each way,
4.19 MB at 64 heads of 128 x 128 — plus the row's ``q, k, g`` (``dk``
each), ``v`` and the output ``o`` (``dv`` each) and ``beta`` per head,
float32.  Not the other layers' states, not the slots that are empty or
still prefilling (the kernel's grid walks the decoding rows only).
Operations: 7 a state element (the decay; the product with ``k`` and its
sum; the outer product and its sum; the product with ``q`` and its sum):
under a FLOP a byte, far below the bandwidth bound.

The harness counts, for the decode steps inside the traced window, the
rows that decoded (``shapes``); a decode step calls the kernel once per
KDA layer.  The trace's own call count is used for the total, so a step
cut by the window's edge is not counted twice.
"""


def work(shapes, calls, out_elems):
    lin = shapes["model"]["linear_attn_config"]
    heads, dk = lin["num_heads"], lin["head_dim"]
    dv = dk
    steps = max(1, shapes["decode_steps_traced"])
    rows_per_call = shapes["decode_rows_traced"] / steps
    state_bytes = 2 * heads * dk * dv * 4
    vector_bytes = heads * (3 * dk + 2 * dv + 1) * 4
    flops = 7.0 * heads * dk * dv
    return {"flops": calls * rows_per_call * flops, "bytes": calls * rows_per_call * (state_bytes + vector_bytes)}
