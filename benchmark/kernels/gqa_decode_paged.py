"""``flash_decode_paged`` under **grouped queries**: one decode step's
attention of one softmax-attention layer, every slot's ``heads`` query
heads against the slot's own pages of ``kv_heads`` K/V heads.

Must move, per call: the **filled pages of the live rows of the layer's K
and V**, ``ceil(fill / page_len)`` pages of ``kv_heads x page_len x
head_dim`` bf16 a row, once for K and once for V — **once a KV head, not
once a query head**: the ``heads / kv_heads`` query heads of a group share
the page — plus each live row's q in and output out (``heads x
head_dim``).  Operations: ``4 heads fill head_dim`` per live row (every
query head meets every cached position), counted over the filled pages
like the bytes: ``heads / kv_heads`` = 8 FLOP a cached byte, under the
v5e's ridge, so the bytes bound.

The kernel in the trace is ``flash_decode_paged`` (the one GPT-2's cells
read with ``kernels/flash_decode_paged.py``, which counts GPT-2's
multi-head shapes); the counts come from the harness as there.
"""


def work(shapes, calls, out_elems):
    m = shapes["model"]
    heads, kv_heads, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    steps = max(1, shapes["decode_steps_traced"])
    pages_per_call = shapes["decode_pages_traced"] / steps
    rows_per_call = shapes["decode_rows_traced"] / steps
    positions = pages_per_call * shapes["page_len"]
    kv_bytes = positions * kv_heads * d * 2 * 2
    qo_bytes = rows_per_call * heads * d * 2 * 2
    flops = 4.0 * heads * d * positions
    return {"flops": calls * flops, "bytes": calls * (kv_bytes + qo_bytes)}
