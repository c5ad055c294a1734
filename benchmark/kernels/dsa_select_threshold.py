"""``dsa_select_threshold``: the exact top-2,048 of a block of queries'
indexer scores — the k-th largest value found bit by bit, then the last
position a tie may take — once a layer in a decode step (16 rows) and in a
prefill chunk (2,048 rows).

Must move, per call: each **real** query's scores over the positions it
could attend, once — ``attendable x 4 B`` (float32 scores as ordered
``int32``) — and two numbers a row out.  Not the rows of slots that do not
decode or a chunk's padded tail, not the positions past a query's own,
not one pass a count: the 32 + ``log2 n`` counts run over rows held in
VMEM.  Operations: two a score a count (a compare and an add), vector
work far under what the matmul peak prices; the bound is the bytes.

The engine's host counts, over the window, the decode steps and rows, the
chunks and their real queries, and the positions each could attend
(``counters.engine_stats``: ``dsa_*``); the kernel computes ``slots`` rows
a decode step and ``prefill_chunk`` rows a chunk over the context's first
multiple of 4,096, so the needed bytes a *computed* row are the window's
attendable positions over the rows computed, and the trace's own output
sizes (128 numbers a row a call) give the rows computed while it ran.
"""


def work(shapes, calls, out_elems):
    s = shapes["select"]
    rows_computed = s["decode_steps"] * s["slots"] + s["chunks"] * s["prefill_chunk"]
    needed_per_row = (s["decode_positions_attendable"] + s["chunk_positions_attendable"]) / max(1, rows_computed)
    rows_traced = out_elems / 128.0
    counts = 32 + 16
    return {"flops": 2.0 * counts * rows_traced * needed_per_row, "bytes": rows_traced * (needed_per_row * 4 + 8)}
