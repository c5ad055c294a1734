"""A kernel's share of its roofline, from the trace and the kernel's
own operations-and-bytes function (``kernels/<pallas_name>.py``).

``work(shapes, calls, out_elems)`` returns the floating-point
operations and the bytes that *all* ``calls`` of the kernel in the
traced window must do and move — what the algorithm needs, not what the
implementation happens to touch.  The least time the chip could take is
the larger of operations over peak FLOP/s and bytes over peak bytes/s;
the share is that over the kernel's measured device time.  It cannot
pass 100 %: a reading above means the work is counted too high or the
time leaves part of it out, and nothing here clips it.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from .stats import peak


def share_pct(record: Dict[str, Any], kernel: str) -> Optional[float]:
    tr = record.get("trace")
    k = (tr or {}).get("kernels", {}).get(kernel)
    if not k or not k["calls"] or k["seconds"] <= 0:
        return None
    work = record["manifest"].module("kernels", kernel).work(record["shapes"], k["calls"], k["out_elems"])
    pk = peak(record["device"]["kind"])
    least_s = max(work["flops"] / pk["bf16_flops"], work["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least_s / k["seconds"]
