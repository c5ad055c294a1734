"""Roofline share of ``dsa_select_threshold`` (both serve programs' calls): trace time under the kernel's name against
``benchmark/kernels/dsa_select_threshold.py``.  None where the trace holds no such kernel."""
from benchmark.roofline import share_pct


def read(record):
    return share_pct(record, "dsa_select_threshold")
