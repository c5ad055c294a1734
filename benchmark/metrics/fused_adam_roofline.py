"""Roofline share of ``fused_adam``: trace time under the kernel's name against
``benchmark/kernels/fused_adam.py``."""
from benchmark.roofline import share_pct


def read(record):
    return share_pct(record, "fused_adam")
