"""``swa_decode_paged``: one decode step's attention of one **window**
layer — every live row's ``window_heads`` query heads against the last
``window`` positions of the row's own ring of K/V pages (``kv_heads``
heads), the query's own position among them.

Must move, per call: **the window's positions of the live rows**,
``min(fill, window)`` positions of ``kv_heads x head_dim`` bf16 a row,
once for K and once for V — once a KV head, not once a query head — plus
each live row's q in and output out (``window_heads x head_dim``).
Operations: ``4 window_heads head_dim`` per position a live row attends.
**The window's positions, not the pages an implementation happens to
read**: a span that reaches before the window or past the query, a ring
page that holds an earlier lap, are the implementation's to pay for, so
the share reads the same work whatever implements it and cannot pass
100 %.  ``window_heads / kv_heads`` = 9 FLOP a cached byte is under the
v5e's ridge: the bytes bound.

The kernel in the trace is ``swa_decode_paged`` (the body of
``flash_decode_paged`` under its window form); the counts come from the
harness: ``decode_window_positions_traced`` is ``min(fill, window)``
summed over the traced decode steps' rows.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    heads, kv_heads, d = shapes["window_heads"], m["num_key_value_heads"], m["head_dim"]
    steps = max(1, shapes["decode_steps_traced"])
    positions = shapes["decode_window_positions_traced"] / steps
    rows_per_call = shapes["decode_rows_traced"] / steps
    kv_bytes = positions * kv_heads * d * 2 * 2
    qo_bytes = rows_per_call * heads * d * 2 * 2
    flops = 4.0 * heads * d * positions
    return {"flops": calls * flops, "bytes": calls * (kv_bytes + qo_bytes)}
