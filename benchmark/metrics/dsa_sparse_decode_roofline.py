"""Roofline share of whatever attends over the selection in a decode step — the Mosaic kernel
``dsa_sparse_decode`` — against ``benchmark/kernels/dsa_sparse_decode.py``, whose work counts the selected
positions' K and V once a KV head.  None where the trace holds no such kernel."""
from benchmark.roofline import share_pct


def read(record):
    return share_pct(record, "dsa_sparse_decode")
