"""``flash_decode_paged``: one decode step's attention of one layer,
every slot's single query against its own pages of the pool.

Must move, per call: the **filled pages of the live rows of one
layer's K and V** — a row that decoded this step reads
``ceil(fill / page_len)`` pages of ``heads x page_len x head_dim`` bf16,
once for K and once for V — plus each live row's q in and output out.
Not the pool (``num_pages`` pages), not all 48 layers, not the slots
that are empty or still prefilling.  Operations: ``4 * heads * fill *
head_dim`` per live row, far under the bandwidth bound at one query.

The harness counts, for the decode steps inside the traced window, the
rows that decoded and the pages they filled (``shapes``); a decode step
calls the kernel once per layer.  The trace's own call count is used
for the total, so a step cut by the window's edge is not counted twice.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    heads, d = m["n_head"], m["n_embd"] // m["n_head"]
    steps = max(1, shapes["decode_steps_traced"])
    pages_per_call = shapes["decode_pages_traced"] / steps
    rows_per_call = shapes["decode_rows_traced"] / steps
    kv_bytes = pages_per_call * heads * shapes["page_len"] * d * 2 * 2
    qo_bytes = rows_per_call * heads * d * 2 * 2
    flops = 4.0 * heads * d * pages_per_call * shapes["page_len"]
    return {"flops": calls * flops, "bytes": calls * (kv_bytes + qo_bytes)}
