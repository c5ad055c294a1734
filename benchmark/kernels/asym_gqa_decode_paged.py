"""``flash_decode_paged`` under grouped queries with **values narrower
than keys**: one decode step's attention of one full-attention layer,
every slot's ``num_attention_heads`` query heads against the slot's own
pages of ``num_key_value_heads`` K heads ``head_dim`` wide and V heads
``v_head_dim`` wide.

Must move, per call: the **filled pages of the live rows**, ``ceil(fill /
page_len)`` pages of ``kv_heads x page_len x (k_dim + v_dim)`` bf16 a row
— K and V each as wide as it is stored, once a KV head, not once a query
head — plus each live row's q in (``heads x k_dim``) and output out
(``heads x v_dim``).  Operations: ``2 heads (k_dim + v_dim)`` per cached
position, counted over the filled pages like the bytes: ``heads /
kv_heads`` = 16 FLOP a cached byte, under the v5e's ridge, so the bytes
bound.

The kernel in the trace is ``flash_decode_paged``; the counts come from
the harness as in ``kernels/gqa_decode_paged.py``, which counts ``2 x
head_dim`` a position and would read 6,144 B for this family's 5,120.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    heads, kv_heads, dk, dv = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"], m["v_head_dim"]
    steps = max(1, shapes["decode_steps_traced"])
    pages_per_call = shapes["decode_pages_traced"] / steps
    rows_per_call = shapes["decode_rows_traced"] / steps
    positions = pages_per_call * shapes["page_len"]
    kv_bytes = positions * kv_heads * (dk + dv) * 2
    qo_bytes = rows_per_call * heads * (dk + dv) * 2
    flops = 2.0 * heads * (dk + dv) * positions
    return {"flops": calls * flops, "bytes": calls * (kv_bytes + qo_bytes)}
