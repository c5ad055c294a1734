"""Rule: raw ``pl.pallas_call`` sites belong in the kernel seam.

Every Pallas kernel is a block-size decision (the autotuner's domain,
``ops/kernels/autotune.py``), an arming and interpret-or-compile
decision (``utils/device.py``: one probe, no quiet fallback), a stable
``name=`` the optimized HLO and the device trace find it by, and a
``benchmark/kernels/<name>.py`` that prices its work for the roofline
share (docs/kernels.md).  A bare ``pl.pallas_call`` outside
``deepspeed_tpu/ops/kernels/`` and ``deepspeed_tpu/ops/attention/``
gets none of that: hardcoded tiles, its own platform probe, and cost
invisible to the roofline table.  New kernels go in ``ops/kernels/``
(or the attention package, whose flash/splash kernels predate the
seam).
"""
from __future__ import annotations

import ast
import os

from deepspeed_tpu.analysis.core import Severity, make_finding, register

# the two sanctioned kernel homes (attention/ predates the seam and
# already carries autotune defaults + stable names)
_EXEMPT = ("deepspeed_tpu/ops/kernels/", "deepspeed_tpu/ops/attention/")


def _is_pallas_call(node: ast.Call):
    """Match ``pl.pallas_call(...)`` / ``pallas.pallas_call(...)`` /
    bare ``pallas_call(...)`` (however the module was imported)."""
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "pallas_call":
        return True
    if isinstance(f, ast.Name) and f.id == "pallas_call":
        return True
    return False


@register(
    "raw-pallas-call-outside-kernels",
    Severity.B,
    "direct pl.pallas_call site outside deepspeed_tpu/ops/kernels/ and "
    "ops/attention/ — new kernels go through the kernel seam (autotuned "
    "blocks, arming rule, a stable name= and a benchmark/kernels/<name>.py "
    "per docs/kernels.md)",
)
def check_raw_pallas_call(rule, ctx):
    path = os.path.normpath(ctx.path).replace(os.sep, "/")
    if any(marker in path for marker in _EXEMPT):
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and _is_pallas_call(node):
            yield make_finding(
                rule, ctx, node,
                "raw 'pallas_call' outside the kernel seam — this kernel gets "
                "no autotuned blocks, no arming rule and no stable name the "
                "benchmark's trace reducer keys on; put it in ops/kernels/ "
                "(see docs/kernels.md)",
            )
