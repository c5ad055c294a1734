"""Rule: raw metric emission belongs in the telemetry plane.

Every metric is an aggregation/export/cross-rank decision
(docs/telemetry.md): a direct ``.add_scalar(...)`` /
``.write_events(...)`` call — or a hand-built ``SummaryWriter`` —
outside ``deepspeed_tpu/telemetry/`` bypasses the registry, so the
value never reaches the JSONL/Prometheus exporters or the cross-rank
aggregate stream, and its cadence/flush
behaviour is ad hoc.  Publish through the engine's
:class:`~deepspeed_tpu.telemetry.TelemetryManager` (or
``telemetry.get_registry()`` for out-of-engine events); the
TensorBoard monitor is a *sink* the manager forwards to.

Exempt: the telemetry package itself (where sinks legitimately call
the writer) and ``utils/monitor.py`` (the sink's own implementation).
Tier C: the value still lands somewhere; it just falls out of the
unified plane.
"""
from __future__ import annotations

import ast
import os

from deepspeed_tpu.analysis.core import Severity, make_finding, register

_EMIT_METHODS = {"add_scalar", "add_scalars", "write_events"}
_EXEMPT = ("deepspeed_tpu/telemetry/", "deepspeed_tpu/utils/monitor.py")


@register(
    "raw-metric-emit",
    Severity.C,
    "direct add_scalar/write_events call or hand-built SummaryWriter "
    "outside deepspeed_tpu/telemetry/ — publish through the metrics "
    "registry so exporters and cross-rank aggregation "
    "see the value",
)
def check_raw_metric(rule, ctx):
    path = os.path.normpath(ctx.path).replace(os.sep, "/")
    if any(marker in path for marker in _EXEMPT):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _EMIT_METHODS:
            yield make_finding(
                rule, ctx, node,
                f"direct '.{f.attr}()' metric emit outside the telemetry plane — "
                "route through TelemetryManager / telemetry.get_registry() so the "
                "registry, exporters, and cross-rank aggregation see it",
            )
        elif (
            isinstance(f, ast.Name) and f.id == "SummaryWriter"
        ) or (
            isinstance(f, ast.Attribute) and f.attr == "SummaryWriter"
        ):
            yield make_finding(
                rule, ctx, node,
                "hand-built SummaryWriter outside the telemetry plane — the "
                "TensorBoard monitor is a telemetry sink; attach it via the "
                "manager instead of writing events directly",
            )
