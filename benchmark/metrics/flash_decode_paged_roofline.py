"""Roofline share of ``flash_decode_paged``: trace time under the kernel's name against
``benchmark/kernels/flash_decode_paged.py``."""
from benchmark.roofline import share_pct


def read(record):
    return share_pct(record, "flash_decode_paged")
