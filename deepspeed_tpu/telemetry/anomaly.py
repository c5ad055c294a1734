"""Live anomaly watch: a step-wall spike against the trailing window
(``telemetry/manager.py``) and cross-rank stragglers on the heartbeat's
snapshots (``telemetry/aggregate.py``).  Pure functions."""

import statistics
from typing import Any, Dict, List, Optional, Tuple


def check_step_spike(
    wall_ms: float,
    window_mean_ms: Optional[float],
    window_count: int,
    spike_factor: float = 2.5,
    min_window: int = 8,
) -> Optional[Dict[str, Any]]:
    """Window-relative step-wall spike test (pure; the manager feeds the
    gauge ring's mean from BEFORE the current sample so a spike can't
    mask itself).  Returns the structured event or None."""
    if window_mean_ms is None or window_count < min_window or window_mean_ms <= 0:
        return None
    if wall_ms <= spike_factor * window_mean_ms:
        return None
    return {
        "event": "step_wall_spike",
        "wall_ms": round(float(wall_ms), 3),
        "window_mean_ms": round(float(window_mean_ms), 3),
        "factor": round(float(wall_ms) / float(window_mean_ms), 2),
        "threshold_factor": spike_factor,
    }


def find_stragglers(
    latest: Dict[int, Dict[str, float]],
    alive: List[int],
    key_substr: str = "step_wall_ms",
    factor: float = 1.5,
) -> List[Dict[str, Any]]:
    """Cross-rank straggler test on the heartbeat-piggybacked snapshots:
    for every step-wall metric present on >= 2 live ranks, flag ranks
    whose wall exceeds ``factor`` x the cluster median."""
    by_metric: Dict[str, List[Tuple[int, float]]] = {}
    for r in alive:
        for name, v in (latest.get(r) or {}).items():
            if key_substr in name:
                by_metric.setdefault(name, []).append((r, float(v)))
    out: List[Dict[str, Any]] = []
    for name, pairs in sorted(by_metric.items()):
        if len(pairs) < 2:
            continue
        med = statistics.median(v for _, v in pairs)
        if med <= 0:
            continue
        for r, v in pairs:
            if v > factor * med:
                out.append({
                    "event": "straggler", "rank": r, "metric": name,
                    "value": round(v, 3), "cluster_median": round(med, 3),
                    "factor": round(v / med, 2), "threshold_factor": factor,
                })
    return out
