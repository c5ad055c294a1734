"""The compiled programs and the engines' own spans in a traced run.

``benchmark/trace.py`` keeps the ``XLA Ops`` line and the harness's
``bench.*`` spans.  This reads the same ``.xplane.pb`` a second time for
what lies one level up: each execution of a whole compiled program (the
``XLA Modules`` line of a ``/device:TPU:n`` plane, events named
``jit_serve_prefill(<fingerprint>)``) and the spans the engines write
themselves (``ds.serve.*``, ``ds.train.*``; a step's span carries its
number as the argument ``step``).  Two steps as in ``trace.py``:
:func:`load_xplane` gives a plain dict

    {"modules": {plane: [[name, start_ns, dur_ns], ...]},
     "spans": [[name, start_ns, dur_ns, step or None], ...]}

and the functions below do the arithmetic on it, so that it can be
tested on a small recorded dict.  A program belongs to the host span
that holds its midpoint: the two clocks agree to a millisecond or two,
every serving program is tens of milliseconds long and ends inside its
step (the step reads its result back), and a train step is closed by
the harness's ``block_until_ready``.

Every function returns None where the span or the program it looks for
is not in the trace (a program built before the engines named their
programs runs both serving steps as ``jit_fn``), never a guess.
"""
from __future__ import annotations

import bisect
import os
from typing import Any, Dict, List, Optional

from . import trace as trace_mod
from .stats import percentile

MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("ds.", trace_mod.SPAN_PREFIX)


def load_xplane(path: str) -> Dict[str, Any]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    modules: Dict[str, List[List[Any]]] = {}
    spans: List[List[Any]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [[program_name(e.name), int(e.start_ns), int(e.duration_ns)]
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        step = dict(e.stats).get("step") if e.name.startswith("ds.") else None
                        spans.append([e.name, int(e.start_ns), int(e.duration_ns),
                                      None if step is None else int(step)])
    return {"modules": modules, "spans": sorted(spans, key=lambda s: s[1])}


def program_name(event_name: str) -> str:
    """``jit_serve_decode(16828633983051800625)`` → ``jit_serve_decode``."""
    return event_name.split("(", 1)[0]


def of_run(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The loaded trace of the run that made ``record`` (kept on the
    record: eleven readers share one load), or None when the run was not
    traced on a chip."""
    if "programs" not in record:
        record["programs"] = None
        if record.get("trace") is not None:
            # where harness.run_cell has Context write a run's trace
            trace_dir = os.path.join(record["manifest"].root, ".bench_scratch", "trace", record["cell"]["name"])
            try:
                record["programs"] = load_xplane(trace_mod.find_xplane(trace_dir))
            except FileNotFoundError:
                pass
    return record["programs"]


def _spans(raw: Dict[str, Any], name: str) -> List[List[Any]]:
    return [s for s in raw["spans"] if s[0] == name]


def _by_span(events: List[List[Any]], spans: List[List[Any]]) -> List[List[List[Any]]]:
    """``events`` (of one device plane) grouped by the span, of ``spans``
    in start order, that holds each one's midpoint; an event outside
    every span is left out."""
    starts = [s[1] for s in spans]
    out: List[List[List[Any]]] = [[] for _ in spans]
    for e in events:
        mid = e[1] + e[2] // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < spans[i][1] + spans[i][2]:
            out[i].append(e)
    return out


def _p50_ms(values_ns: List[int]) -> Optional[float]:
    return percentile(values_ns, 50) / 1e6 if values_ns else None


def device_ms_p50(raw: Optional[Dict[str, Any]], program: str) -> Optional[float]:
    """Median device time of one execution of ``program``, over every
    execution in the trace on every chip."""
    if raw is None:
        return None
    return _p50_ms([e[2] for events in raw["modules"].values() for e in events if e[0] == program])


def programs_per_step(raw: Optional[Dict[str, Any]], step_span: str = trace_mod.SPAN_PREFIX + "step") -> Optional[float]:
    """Program executions inside a ``step_span``, per span and per chip.
    Read off the harness's own span, so a program that writes no span of
    its own is counted too."""
    if raw is None:
        return None
    spans = _spans(raw, step_span)
    planes = [events for events in raw["modules"].values() if events]
    if not spans or not planes:
        return None
    return sum(len(g) for events in planes for g in _by_span(events, spans)) / (len(spans) * len(planes))


def step_gap_ms_p50(raw: Optional[Dict[str, Any]], step_span: str) -> Optional[float]:
    """Median, over the steps of the trace, of the time one step leaves
    the device idle: from the end of the last program of the step before
    (``step`` one less) to the end of the step's own last program, less
    the time its programs ran — the wait for the step's first program
    plus the waits between its programs.  With the programs' device
    times it adds up to the step."""
    if raw is None:
        return None
    spans = [s for s in _spans(raw, step_span) if s[3] is not None]
    gaps: List[int] = []
    for events in raw["modules"].values():
        groups = _by_span(events, spans)
        for (before, progs_before), (span, progs) in zip(zip(spans, groups), zip(spans[1:], groups[1:])):
            if span[3] == before[3] + 1 and progs_before and progs:
                period = max(e[1] + e[2] for e in progs) - max(e[1] + e[2] for e in progs_before)
                gaps.append(period - sum(e[2] for e in progs))
    return _p50_ms(gaps)


def between_ms_p50(raw: Optional[Dict[str, Any]], program: str) -> Optional[float]:
    """Median device idle time between two consecutive executions of
    ``program`` on one chip: from the end of one to the start of the
    next, less whatever other program ran in between."""
    if raw is None:
        return None
    gaps: List[int] = []
    for events in raw["modules"].values():
        events = sorted(events, key=lambda e: e[1])
        mine = [(i, e) for i, e in enumerate(events) if e[0] == program]
        for (i, a), (j, b) in zip(mine, mine[1:]):
            gaps.append(b[1] - (a[1] + a[2]) - sum(e[2] for e in events[i + 1: j]))
    return _p50_ms(gaps)


def timeline_ms(record: Dict[str, Any], key: str) -> Optional[float]:
    """A key of the serving engine's own ``StepTimeline.summary()`` over
    the whole window (the runner resets it where the window opens), or
    None from an engine that does not report it."""
    return (record["counters"].get("timeline") or {}).get(key)
