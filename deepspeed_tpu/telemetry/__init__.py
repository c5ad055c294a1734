"""Unified telemetry plane (docs/telemetry.md).

One process-wide :class:`~.registry.MetricsRegistry` + one
:class:`~.spans.TraceBuffer`, armed by :func:`configure` (the train
engine calls it from the validated ``telemetry`` config block; tools
call it directly).  Sources publish through a per-engine
:class:`~.manager.TelemetryManager` or, for rare out-of-engine events
(retries, rescues, comm decisions), straight into :func:`get_registry`.

Exporters (JSONL / Prometheus textfile / TensorBoard sink) run on a
background cadence — never on the hot path; the Chrome-trace buffer
exports ``trace.json`` for Perfetto; cross-rank aggregation piggybacks
on the supervision heartbeat (:mod:`.aggregate`).
"""
from __future__ import annotations

import atexit
import os
import time
from typing import Any, Dict, Optional

from deepspeed_tpu.telemetry.aggregate import (
    CrossRankAggregator,
    decode_metrics,
    encode_metrics,
)
from deepspeed_tpu.telemetry.exporters import (
    ExportLoop,
    JsonlExporter,
    PrometheusTextfileExporter,
    TensorBoardSink,
)
from deepspeed_tpu.telemetry.manager import TelemetryManager
from deepspeed_tpu.telemetry.anomaly import check_step_spike, find_stragglers
from deepspeed_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from deepspeed_tpu.telemetry.spans import (
    PID_CHECKPOINT,
    PID_ENGINE,
    PID_REQUESTS,
    TraceBuffer,
    validate_chrome_trace,
)

# process singletons: disabled at import; configure() arms them
_REGISTRY = MetricsRegistry(enabled=False)
_TRACER = TraceBuffer(enabled=False)
_EXPORT_LOOP: Optional[ExportLoop] = None
_CONFIG = None
_TRACE_PATH: Optional[str] = None
_ATEXIT_DONE = False


def get_registry() -> MetricsRegistry:
    return _REGISTRY


def get_tracer() -> TraceBuffer:
    return _TRACER


def default_output_path(cfg=None) -> str:
    p = getattr(cfg or _CONFIG, "output_path", "") or ""
    return p or "telemetry"


def configure(config=None, rank: int = 0, label: str = "train",
              monitor=None) -> TelemetryManager:
    """Arm the process-wide plane from a validated
    :class:`~deepspeed_tpu.config.config.TelemetryConfig` (or None for
    defaults) and return the caller's :class:`TelemetryManager`.

    Idempotent-by-design for multi-engine processes: a second call
    reconfigures the shared registry/tracer in place (cached metric
    handles stay live) and replaces the export loop if the sink set
    changed."""
    global _EXPORT_LOOP, _CONFIG, _TRACE_PATH, _ATEXIT_DONE
    from deepspeed_tpu.config.config import TelemetryConfig

    if config is None:
        config = TelemetryConfig()
    elif isinstance(config, dict):
        config = TelemetryConfig.from_dict(config)
    _CONFIG = config

    _REGISTRY.configure(enabled=config.enabled, ring=config.ring, rank=rank)
    _TRACER.configure(
        enabled=config.enabled and config.trace,
        max_events=config.trace_buffer_events,
    )
    out_dir = default_output_path(config)
    _TRACE_PATH = config.trace_path or os.path.join(out_dir, "trace.json")

    # (re)build the export loop for the configured sink set
    if _EXPORT_LOOP is not None:
        _EXPORT_LOOP.stop()
        _EXPORT_LOOP = None
    if config.enabled and config.exporters:
        exporters = []
        for name in config.exporters:
            if name == "jsonl":
                exporters.append(
                    JsonlExporter(os.path.join(out_dir, f"metrics_rank{rank}.jsonl"))
                )
            elif name == "prometheus":
                exporters.append(
                    PrometheusTextfileExporter(
                        os.path.join(out_dir, f"metrics_rank{rank}.prom")
                    )
                )
            elif name == "tensorboard":
                exporters.append(TensorBoardSink(monitor))
        _EXPORT_LOOP = ExportLoop(
            _REGISTRY, exporters, interval_seconds=config.export_interval_seconds
        ).start()
    if not _ATEXIT_DONE:
        _ATEXIT_DONE = True
        atexit.register(shutdown)
    return TelemetryManager(label, _REGISTRY, _TRACER, monitor=monitor, config=config)


def manager_for(label: str, monitor=None) -> TelemetryManager:
    """A manager bound to the current process plane WITHOUT
    reconfiguring it (serving/inference engines attach to whatever the
    process armed; a no-config process gets no-op publishes)."""
    return TelemetryManager(label, _REGISTRY, _TRACER, monitor=monitor, config=_CONFIG)


def flush() -> None:
    """Force an immediate export (a reader of the files comes right after)."""
    if _EXPORT_LOOP is not None:
        _EXPORT_LOOP.flush()


def export_trace(path: Optional[str] = None,
                 metadata: Optional[Dict[str, Any]] = None) -> Optional[str]:
    """Write the span buffer as Chrome-trace JSON; returns the path or
    None when tracing never armed."""
    if not _TRACER.enabled and not _TRACER.events():
        return None
    return _TRACER.export(path or _TRACE_PATH or "trace.json", metadata=metadata)


def shutdown() -> None:
    """Atexit: final metric export + trace flush (a crash-adjacent exit
    must not drop the evidence)."""
    global _EXPORT_LOOP
    if _EXPORT_LOOP is not None:
        _EXPORT_LOOP.stop()
        _EXPORT_LOOP = None
    if _TRACER.enabled and _TRACER.events():
        try:
            export_trace()
        except OSError:  # pragma: no cover - exit path best-effort
            pass


def status() -> Dict[str, Any]:
    """ds_report rows: enabled sinks, cadence, registry size, last
    export age, trace state."""
    loop = _EXPORT_LOOP
    return {
        "enabled": _REGISTRY.enabled,
        "rank": _REGISTRY.rank,
        "registry_size": _REGISTRY.size(),
        "ring": _REGISTRY.ring,
        "sinks": [getattr(e, "name", "?") for e in (loop.exporters if loop else [])],
        "export_interval_seconds": loop.interval if loop else None,
        "exports": loop.exports if loop else 0,
        "last_export_age_seconds": loop.last_export_age() if loop else None,
        "trace_enabled": _TRACER.enabled,
        "trace_events": len(_TRACER.events()),
        "trace_path": _TRACE_PATH,
    }


def reset_for_tests() -> None:
    """Tear the plane back to import state (tests only)."""
    global _EXPORT_LOOP, _CONFIG, _TRACE_PATH
    if _EXPORT_LOOP is not None:
        _EXPORT_LOOP.stop()
        _EXPORT_LOOP = None
    _REGISTRY.reset()
    _REGISTRY.configure(enabled=False)
    _TRACER.clear()
    _TRACER.enabled = False
    _CONFIG = None
    _TRACE_PATH = None


__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "TraceBuffer", "validate_chrome_trace",
    "PID_ENGINE", "PID_REQUESTS", "PID_CHECKPOINT",
    "JsonlExporter", "PrometheusTextfileExporter", "TensorBoardSink", "ExportLoop",
    "CrossRankAggregator", "encode_metrics", "decode_metrics",
    "TelemetryManager",
    "check_step_spike", "find_stragglers",
    "configure", "manager_for", "get_registry", "get_tracer",
    "flush", "export_trace", "shutdown", "status", "reset_for_tests",
]
