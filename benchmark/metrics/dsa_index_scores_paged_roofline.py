"""Roofline share of ``dsa_index_scores_paged``: trace time under the kernel's name against
``benchmark/kernels/dsa_index_scores_paged.py``.  None where the trace holds no such kernel."""
from benchmark.roofline import share_pct


def read(record):
    return share_pct(record, "dsa_index_scores_paged")
