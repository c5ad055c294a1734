"""Roofline share of ``mla_decode_paged``: trace time under the kernel's name against
``benchmark/kernels/mla_decode_paged.py``.  None where the trace holds no such kernel."""
from benchmark.roofline import share_pct


def read(record):
    return share_pct(record, "mla_decode_paged")
