"""Roofline share of ``flash_attention_bwd``: trace time under the kernel's name against
``benchmark/kernels/flash_attention_bwd.py``."""
from benchmark.roofline import share_pct


def read(record):
    return share_pct(record, "flash_attention_bwd")
