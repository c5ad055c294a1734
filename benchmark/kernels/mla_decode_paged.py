"""``mla_decode_paged``: one decode step's absorbed latent attention of
one layer — every live slot's 128 absorbed queries against the slot's
own pages of the latent pool.

Must move, per call: the **filled pages of the live rows of one layer**,
``ceil(fill / page_len)`` pages of ``page_len x width`` bf16 a row,
**once** — keys and values are the one cached row (``width`` =
``kv_lora_rank + qk_rope_head_dim`` = 576, values its first
``kv_lora_rank`` = 512 numbers) — plus each live row's queries in
(``heads x width``) and output out (``heads x kv_lora_rank``).  Not the
pool, not the other layers, not the slots that are empty or prefilling.
Operations: ``2 heads (width + kv_lora_rank)`` per live row and cached
position (the score product over 576, the value product over 512),
counted over the filled pages like the bytes: 241 FLOP a cached byte at
the published sizes, the v5e's ridge, so either bound may be the larger.

The harness counts, for the decode steps inside the traced window, the
rows that decoded and the pages they filled (``shapes``); a decode step
calls the kernel once per layer.  The trace's own call count is used for
the total, so a step cut by the window's edge is not counted twice.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    heads, c = m["num_attention_heads"], m["kv_lora_rank"]
    width = c + m["qk_rope_head_dim"]
    steps = max(1, shapes["decode_steps_traced"])
    pages_per_call = shapes["decode_pages_traced"] / steps
    rows_per_call = shapes["decode_rows_traced"] / steps
    positions = pages_per_call * shapes["page_len"]
    cache_bytes = positions * width * 2
    qo_bytes = rows_per_call * heads * (width + c) * 2
    flops = 2.0 * heads * (width + c) * positions
    return {"flops": calls * flops, "bytes": calls * (cache_bytes + qo_bytes)}
