"""Roofline share of ``swa_decode_paged`` where the window layers carry a sink and their own KV geometry (keys wider than
values): trace time under the kernel's name against ``benchmark/kernels/swa_sink_decode_paged.py`` (the window's positions
of the live rows, K and V each as wide as stored, once a KV head).  None where the trace holds no such kernel, the run kept
no window positions, or the configuration names no window geometry of its own."""
from benchmark.stats import peak


def read(record):
    k = ((record.get("trace") or {}).get("kernels") or {}).get("swa_decode_paged")
    shapes = record.get("shapes", {})
    if not k or not k["calls"] or k["seconds"] <= 0 or "decode_window_positions_traced" not in shapes \
            or "swa_v_head_dim" not in shapes.get("model", {}):
        return None
    work = record["manifest"].module("kernels", "swa_sink_decode_paged").work(shapes, k["calls"], k["out_elems"])
    pk = peak(record["device"]["kind"])
    return 100.0 * max(work["flops"] / pk["bf16_flops"], work["bytes"] / pk["hbm_bytes_per_s"]) / k["seconds"]
