"""HLO text analysis helpers — collective byte accounting.

The reference tracks its comm volume implicitly (bucket sizes,
allgather_bucket_size knobs, stage2.py:1489 allgather tail); under XLA
the compiled HLO is the ground truth, so the framework ships a parser
that attributes wire bytes to each collective op.  Used by ds_shard's
collective audit (analysis/shard/), the 1-bit wire-byte regression tests
(tests/test_onebit.py), and the ZeRO collective-byte regression test.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

# op -> ring-traffic weight: an all-reduce moves ~2x its payload
# (reduce-scatter + all-gather phases); the others ~1x.
COLLECTIVE_WEIGHTS = {
    "all-reduce": 2,
    "all-gather": 1,
    "all-to-all": 1,
    "collective-permute": 1,
    "reduce-scatter": 1,
}

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
}

SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%(?P<name>[^\s=]+)\s+=\s+(?P<type>\([^)]*\)|\S+)\s+"
    r"(?P<opcode>[\w-]+)\((?P<rest>.*)$"
)


def shape_bytes(type_str: str, dtype_filter: Optional[str] = None) -> int:
    """Total bytes of an HLO type string (a tuple's members summed), of one dtype tag if given."""
    nbytes = 0
    for dt, dims in SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES or (dtype_filter and dt != dtype_filter):
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * DTYPE_BYTES[dt]
    return nbytes


def collective_bytes_by_op(hlo_text: str, dtype_filter: Optional[str] = None) -> Dict[str, int]:
    """Estimated wire bytes per collective op kind in an HLO dump.

    Byte counts are the op RESULT shapes times the ring weight — a
    first-order ring-traffic model, good for regression ratios and
    roofline demand estimates (not a cycle-accurate simulator).
    ``dtype_filter`` restricts to one dtype tag (e.g. "f32").
    """
    totals: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        parts = line.split(" = ", 1)
        if len(parts) != 2:
            continue
        rhs = parts[1]
        cut, weight, kind = -1, 1, None
        for c, w in COLLECTIVE_WEIGHTS.items():
            for op in (f" {c}(", f" {c}-start("):
                i = rhs.find(op)
                if i >= 0 and (cut < 0 or i < cut):
                    cut, weight, kind = i, w, c
        if cut < 0:
            continue
        totals[kind] = totals.get(kind, 0) + shape_bytes(rhs[:cut], dtype_filter) * weight
    return totals


def collective_bytes(hlo_text: str, dtype_filter: Optional[str] = None) -> int:
    """Total estimated wire bytes of all collectives in an HLO dump."""
    return sum(collective_bytes_by_op(hlo_text, dtype_filter).values())
