"""Roofline share of ``flash_decode_paged`` under grouped queries with values narrower than keys: trace time under the
kernel's name against ``benchmark/kernels/asym_gqa_decode_paged.py`` (each filled page once a KV head, K and V each as wide
as stored).  None where the trace holds no such kernel or the configuration names no value width of its own."""
from benchmark.stats import peak


def read(record):
    k = ((record.get("trace") or {}).get("kernels") or {}).get("flash_decode_paged")
    model = record.get("shapes", {}).get("model", {})
    if not k or not k["calls"] or k["seconds"] <= 0 or "v_head_dim" not in model or "num_key_value_heads" not in model:
        return None
    work = record["manifest"].module("kernels", "asym_gqa_decode_paged").work(record["shapes"], k["calls"], k["out_elems"])
    pk = peak(record["device"]["kind"])
    return 100.0 * max(work["flops"] / pk["bf16_flops"], work["bytes"] / pk["hbm_bytes_per_s"]) / k["seconds"]
