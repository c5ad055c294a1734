"""``fused_adam``: one pass over a leaf's float32 parameter, gradient
and two moments.

Must move: p, g, m, v in and p, m, v out, 4 bytes each — 28 bytes an
element; about a dozen operations an element, far under the bandwidth
bound.  The elements are read off the trace (each call's first output
is the leaf's new parameters), so leaves the engine keeps on the XLA
path (ragged ones such as the 50,257-row embedding) are not counted.
The traced run logs one event's full text: at PR 23 all four operands
were f32 (``f32[720,256]`` p, g, m, v).  A bf16 gradient would make the
count 26, so check that text again if the share nears 100 %.
"""


def work(shapes, calls, out_elems):
    return {"flops": 12.0 * out_elems, "bytes": 28.0 * out_elems}
