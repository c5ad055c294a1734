"""``swa_decode_paged`` with **a sink and values narrower than keys**: one
decode step's attention of one window layer whose KV geometry is its own
— every live row's ``swa_num_attention_heads`` query heads against the
last ``sliding_window`` positions of the row's ring of K pages
(``swa_num_key_value_heads`` heads, ``swa_head_dim`` wide) and V pages
(``swa_v_head_dim`` wide), the query's own position among them, one
learned sink logit a head in the softmax.

Must move, per call: **the window's positions of the live rows**,
``min(fill, window)`` positions of ``kv_heads x (k_dim + v_dim)`` bf16 a
row — K and V each as wide as it is stored, once a KV head, not once a
query head — plus each live row's q in (``heads x k_dim``) and output out
(``heads x v_dim``); the sinks are 256 bytes a call and not counted.
Operations: ``2 heads (k_dim + v_dim)`` per position a live row attends
(a score product over ``k_dim``, a value product over ``v_dim``).  **The
window's positions, not the pages an implementation happens to read**: a
ring of two pages read for a window of one page's length is the
implementation's to pay for, so the share cannot pass ~50 % here and
cannot pass 100 % anywhere.  ``heads / kv_heads`` = 8 FLOP a cached byte
is under the v5e's ridge: the bytes bound.

The kernel in the trace is ``swa_decode_paged`` (the body of
``flash_decode_paged`` under its window form, with the sink operand); the
counts come from the harness: ``decode_window_positions_traced`` is
``min(fill, window)`` summed over the traced decode steps' rows.
``kernels/swa_decode_paged.py`` counts one width for K and V and the
model's one KV head count: it would miscount this family.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    heads, kv_heads = m["swa_num_attention_heads"], m["swa_num_key_value_heads"]
    dk, dv = m["swa_head_dim"], m["swa_v_head_dim"]
    steps = max(1, shapes["decode_steps_traced"])
    positions = shapes["decode_window_positions_traced"] / steps
    rows_per_call = shapes["decode_rows_traced"] / steps
    kv_bytes = positions * kv_heads * (dk + dv) * 2
    qo_bytes = rows_per_call * heads * (dk + dv) * 2
    flops = 2.0 * heads * (dk + dv) * positions
    return {"flops": calls * flops, "bytes": calls * (kv_bytes + qo_bytes)}
