"""Roofline share of ``swa_decode_paged`` (the window layers' decode attention): trace time under the
kernel's name against ``benchmark/kernels/swa_decode_paged.py`` (the window's positions of the live rows,
once a KV head).  None where the trace holds no such kernel or the run kept no window positions."""
from benchmark import roofline


def read(record):
    if "decode_window_positions_traced" not in record.get("shapes", {}):
        return None
    return roofline.share_pct(record, "swa_decode_paged")
