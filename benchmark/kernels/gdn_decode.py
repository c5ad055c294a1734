"""``gdn_decode``: one decode step's gated-delta-rule recurrence of one
linear-attention layer — every decoding row's recurrent state decayed by
its head's scalar, updated by the delta rule and read, in place.

Must move, per call: for each row that decoded this step, the layer's
whole state **in and out once** — ``value heads x dk x dv`` float32 each
way, 4.19 MB at 64 heads of 128 x 128 — plus the row's ``q, k`` (``key
heads x dk`` each: a query / key head is shared by ``value heads / key
heads`` states and need be read once), ``v`` and the output ``o``
(``value heads x dv`` each), ``g`` and ``beta`` (one number a value head
each), float32.  Not the other layers' states, not the slots that are
empty or still prefilling.  Operations: 7 a state element (the decay; the
product with ``k`` and its sum; the outer product and its sum; the
product with ``q`` and its sum).  The same work whatever the kernel's
body moves: a body that carries the decay a channel or a shared query /
key twice reads lower, honestly.

The harness counts, for the decode steps inside the traced window, the
rows that decoded (``shapes``); a decode step calls the kernel once per
delta-rule layer.  The trace's own call count is used for the total, so
a step cut by the window's edge is not counted twice.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    steps = max(1, shapes["decode_steps_traced"])
    rows_per_call = shapes["decode_rows_traced"] / steps
    state_bytes = 2 * hv * dk * dv * 4
    vector_bytes = (2 * hk * dk + 2 * hv * dv + 2 * hv) * 4
    flops = 7.0 * hv * dk * dv
    return {"flops": calls * rows_per_call * flops, "bytes": calls * rows_per_call * (state_bytes + vector_bytes)}
