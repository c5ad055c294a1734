"""Roofline share of ``gdn_decode``: trace time under the kernel's name against
``benchmark/kernels/gdn_decode.py``.  None where the trace holds no such kernel."""
from benchmark.roofline import share_pct


def read(record):
    return share_pct(record, "gdn_decode")
