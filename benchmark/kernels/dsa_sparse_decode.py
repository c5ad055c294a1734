"""``dsa_sparse_decode``: one decode step's attention of one layer over
**the positions each row's indexer selected**.

Must move, per call: for each row that decoded this step, the K and V of
its ``min(fill, topk)`` selected positions **once a KV head** —
``min(fill, 2048) x 4 heads x 128 x 2 B``, once for K and once for V —
plus the row's q in and output out (32 heads x 128, bf16).  Not the row's
whole context: what the selection exists to avoid.  Operations: ``4 x 32
x 128 x min(fill, 2048)`` a live row, far under the bandwidth bound at one
query.

Whatever implements the attention is held to this.  The kernel walks
every filled page of the row under the selection mask (2,048 positions
scattered over a long row touch nearly every page, and a row gather by
XLA reached 105-142 GB/s on the chip where the page walk streams at ~690:
PERF.md section 6, PR 45), so at a fill of 17 k it reads ~8 x what is
counted here and its share reads low, honestly.

The harness counts, for the decode steps inside the traced window, the
rows that decoded and the positions they selected (``shapes``); a decode
step calls the kernel once per layer.  The trace's own call count is used
for the total, so a step cut by the window's edge is not counted twice.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    heads, kv_heads, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    steps = max(1, shapes["decode_steps_traced"])
    selected_per_call = shapes["decode_selected_traced"] / steps
    rows_per_call = shapes["decode_rows_traced"] / steps
    kv_bytes = selected_per_call * kv_heads * d * 2 * 2
    qo_bytes = rows_per_call * heads * d * 2 * 2
    flops = 4.0 * heads * d * selected_per_call
    return {"flops": calls * flops, "bytes": calls * (kv_bytes + qo_bytes)}
