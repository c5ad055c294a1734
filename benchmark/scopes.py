"""Device time by **named scope**: what ``jax.named_scope`` leaves on the
operations of a compiled program.

The profiler's device events carry the HLO instruction (``%fusion.12 =
…``) and not the scope it was traced under; the scope is in the
*compiled program's* text, in each instruction's ``metadata={op_name=
"jit(serve_decode)/jit(main)/cca.mix/dot_general" …}`` (a fusion carries
its root's).  So a runner that wants scopes read keeps, for each of its
programs, :func:`ops_by_scope` of ``compiled.as_text()`` on its record
beside its trace (:func:`keep`: ``{program: {instruction: [scopes]}}``),
and the reader joins that with the trace by instruction name inside the
program's executions.  Two steps as in ``trace.py``: :func:`load_xplane`
gives a plain dict

    {"ops": {plane: [[instruction, start_ns, dur_ns], ...]},
     "modules": {plane: [[program, start_ns, dur_ns], ...]}}

to which :func:`of_run` adds the kept ``"scoped_ops"``, and
:func:`scope_share_pct` does the arithmetic on it, so that it can be
tested on a small recorded dict.
"""
from __future__ import annotations

import bisect
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence

from . import programs
from . import trace as trace_mod

OPS_LINE = "XLA Ops"
SCOPED_OPS_FILE = "scoped_ops.json"
_SCOPED = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"', re.M)


def ops_by_scope(hlo_text: str, scopes: Sequence[str]) -> Dict[str, List[str]]:
    """``{instruction name: [scopes it was traced under]}`` for every
    instruction of an optimized HLO module's text whose ``op_name`` has
    one of ``scopes`` as a path component; the others are left out."""
    out: Dict[str, List[str]] = {}
    for name, op in _SCOPED.findall(hlo_text):
        parts = op.split("/")
        found = [s for s in scopes if s in parts]
        if found:
            out[name] = found
    return out


def instruction(event_name: str) -> str:
    """``%fusion.12.remat = f32[…] fusion(…)`` → ``fusion.12.remat``: the name the module's text gives it."""
    m = trace_mod._OP_HEAD.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0].lstrip("%")


def load_xplane(path: str) -> Dict[str, Any]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List[List[Any]]] = {}
    modules: Dict[str, List[List[Any]]] = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops[plane.name] = [[instruction(e.name), int(e.start_ns), int(e.duration_ns)] for e in line.events]
            elif line.name == programs.MODULES_LINE:
                modules[plane.name] = [[programs.program_name(e.name), int(e.start_ns), int(e.duration_ns)] for e in line.events]
    return {"ops": ops, "modules": modules}


def keep(trace_dir: str, scoped_ops: Dict[str, Dict[str, List[str]]]) -> None:
    """A traced run's ``{program: ops_by_scope(its text)}``, written beside its trace."""
    os.makedirs(trace_dir, exist_ok=True)
    with open(os.path.join(trace_dir, SCOPED_OPS_FILE), "w") as f:
        json.dump(scoped_ops, f)


def of_run(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The scoped trace of the run that made ``record`` (kept on the
    record), or None when the run was not traced on a chip or its runner
    kept no scoped operations beside the trace."""
    if "scopes" not in record:
        record["scopes"] = None
        if record.get("trace") is not None:
            trace_dir = os.path.join(record["manifest"].root, ".bench_scratch", "trace", record["cell"]["name"])
            try:
                with open(os.path.join(trace_dir, SCOPED_OPS_FILE)) as f:
                    scoped_ops = json.load(f)
                record["scopes"] = {**load_xplane(trace_mod.find_xplane(trace_dir)), "scoped_ops": scoped_ops}
            except FileNotFoundError:
                pass
    return record["scopes"]


def scope_share_pct(raw: Dict[str, Any], scope: str, program: str) -> Optional[float]:
    """Self time of the operations under ``scope`` that ran inside an
    execution of ``program``, over the summed device time of those
    executions, in percent.  None where the trace holds no such program
    or no operation under the scope (a program built before the scope
    existed)."""
    tagged = {name for name, found in (raw.get("scoped_ops") or {}).get(program, {}).items() if scope in found}
    inside = total = 0
    for plane, events in raw["ops"].items():
        runs = sorted((s, s + d) for name, s, d in raw["modules"].get(plane, []) if name == program)
        starts = [a for a, _ in runs]
        total += sum(b - a for a, b in runs)
        for name, start, _, self_ns in trace_mod._self_times(events):
            i = bisect.bisect_right(starts, start) - 1  # the execution that had begun last (they do not overlap)
            if name in tagged and i >= 0 and start < runs[i][1]:
                inside += self_ns
    return 100.0 * inside / total if total and inside else None
