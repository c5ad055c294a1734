"""``dsa_index_scores_paged``: one decode step's indexer scores of one
layer — every decoding row's 16 indexer query heads against its own cached
indexer keys.

Must move, per call: for each row that decoded this step, its ``fill``
cached indexer keys once — ``fill x 64 x 2 B`` (one key head, bf16) —
plus the row's query heads and head weights in (16 x 64 + 16, float32)
and its strip of scores out (``fill x 4 B``).  Not the pages past the
row's position, not the rows that are empty or still prefilling (the
kernel's grid walks the decoding rows' filled pages only).  Operations:
``2 x 16 x 64 x fill`` a live row (the products; ReLU and the weighted sum
over heads are under a tenth of that), float32 at highest precision: far
under the bandwidth bound.

The harness counts, for the decode steps inside the traced window, the
rows that decoded and the positions they could attend (``shapes``); a
decode step calls the kernel once per layer.
"""


def work(shapes, calls, out_elems):
    sa = shapes["model"]["sa_config"]
    heads, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    steps = max(1, shapes["decode_steps_traced"])
    positions_per_call = shapes["decode_positions_traced"] / steps
    rows_per_call = shapes["decode_rows_traced"] / steps
    key_bytes = positions_per_call * d * 2
    qw_bytes = rows_per_call * (heads * d + heads) * 4
    out_bytes = positions_per_call * 4
    flops = 2.0 * heads * d * positions_per_call
    return {"flops": calls * flops, "bytes": calls * (key_bytes + qw_bytes + out_bytes)}
