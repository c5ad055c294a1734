"""Readings several per-layer metrics share.  A metric still has a
reader file of its own (``metrics/<name>.py``); where a train and a
serve metric are the same quantity, both files call one function here."""
from __future__ import annotations

from typing import Any, Dict, Optional

from .stats import percentile


def step_ms_p50(record: Dict[str, Any]) -> Optional[float]:
    """Median wall of the harness span round one engine step."""
    walls = record["window"]["step_walls_s"]
    return percentile(walls, 50) * 1e3 if walls else None


def device_idle_pct(record: Dict[str, Any]) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device (mean over the chips used)."""
    tr = record.get("trace")
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None


def hbm_peak_gb(record: Dict[str, Any]) -> Optional[float]:
    """Peak device memory in use on the fullest chip, as PJRT saw it
    (``memory_stats()["peak_bytes_in_use"]``)."""
    peak = record["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
