"""What a serve run's device idle time was: every idle interval of the
``XLA Modules`` line cut at the edges of the engine's own leaf spans.

Pure functions on the dict ``benchmark/programs.py::load_xplane`` gives
(``programs.of_run(record)``: no reader of the ``.xplane.pb`` here), so
that the arithmetic can be tested on a small hand-made dict.

A serving step (``ds.serve.step``) is serial on one host thread, and
every instant of it lies under exactly one leaf span: ``sweep``,
``sched``, a program's ``stage`` / ``dispatch`` / ``wait`` / ``note``,
``commit`` (docs/telemetry.md).  ``dispatch`` + ``wait`` hold three
things — the launch, the program on the device, the read-back — and the
device's own line says where the program lay in them:

* **the join**: the k-th ``ds.serve.{prefill,decode}.dispatch`` span of
  a step launched the k-th ``jit_serve_*`` execution whose midpoint the
  step holds (``programs.py``'s rule); its ``*.note`` span begins where
  ``device_get`` returned.  A step whose spans and programs do not pair
  off by count and by name is left out, never guessed at;
* **the clocks**: launch + device time + read-back of one execution is
  free of the offset between the host's and the device's clock; the
  split is not.  No execution starts before its ``dispatch`` span does
  nor ends after its read returned: the least slack of either kind over
  all executions bounds the offset from either side.  The split is read
  at the middle of the bounds and the half-width is returned beside it
  (:func:`clock`): a split finer than the half-width is not known;
* **the partition**: the idle intervals, moved onto the host's clock,
  are cut at the leaves' edges.  From a ``dispatch`` span's start to the
  program's midpoint idle time is ``launch``, from there to the ``note``
  span's start ``readback`` (the device is busy between, so the midpoint
  is as good a cut as any); inside a step under no leaf
  ``unattributed``; outside every step ``between_steps``: the caller's
  loop.  The labels sum to the idle time exactly.

Every function returns None where the trace lacks what it reads (a run
that was not traced; a program that writes no ``ds.serve.*.note`` or
``ds.serve.commit`` span: its hand-back and commit would be read as the
caller's time), never a guess.
"""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Tuple

from . import trace as trace_mod
from .stats import percentile

STEP = "ds.serve.step"
PROGRAM = {"jit_serve_prefill": "prefill", "jit_serve_decode": "decode"}
# leaf span -> label of the partition (dispatch and wait are cut by the device's line instead)
LEAVES = {"ds.serve.sweep": "sweep", "ds.serve.sched": "sched", "ds.serve.commit": "commit",
          "ds.serve.prefill.stage": "stage", "ds.serve.decode.stage": "stage",
          "ds.serve.prefill.note": "note", "ds.serve.decode.note": "note"}
LABELS = ("readback", "note", "commit", "between_steps", "sweep", "sched", "stage", "launch", "unattributed")

Interval = Tuple[int, int]


def _steps(raw: Dict[str, Any]) -> List[List[Any]]:
    return [s for s in raw["spans"] if s[0] == STEP]  # raw["spans"] is in start order


def _by_step(steps: List[List[Any]], starts: List[int], spans: List[List[Any]]) -> List[List[List[Any]]]:
    """``spans`` grouped by the step whose span holds each one's start
    and end; one that no step holds whole is left out."""
    out: List[List[List[Any]]] = [[] for _ in steps]
    for s in spans:
        i = bisect.bisect_right(starts, s[1]) - 1
        if i >= 0 and s[1] + s[2] <= steps[i][1] + steps[i][2]:
            out[i].append(s)
    return out


def executions(raw: Optional[Dict[str, Any]]) -> Optional[List[Dict[str, Any]]]:
    """The join: one row a traced program execution that pairs off with
    its step's spans — ``program``, ``step``, the host's ``dispatch_ns``
    (the ``dispatch`` span's start), ``wait_end_ns``, ``note_ns`` (the
    ``note`` span's start: the read returned) and the device's
    ``start_ns`` / ``end_ns``, each on its own clock.  None where the
    trace has no step that pairs off."""
    if raw is None:
        return None
    steps = _steps(raw)
    starts = [s[1] for s in steps]
    of = {kind: _by_step(steps, starts, [s for s in raw["spans"] if s[0].endswith("." + kind) and s[0].startswith("ds.serve.")])
          for kind in ("dispatch", "wait", "note")}
    rows: List[Dict[str, Any]] = []
    for events in raw["modules"].values():
        mine = sorted((e for e in events if e[0] in PROGRAM), key=lambda e: e[1])
        # a program belongs to the step that holds its midpoint on the host's clock as it stands:
        # the clocks agree to a millisecond or two, a serving program is tens of them long
        progs: List[List[List[Any]]] = [[] for _ in steps]
        for e in mine:
            i = bisect.bisect_right(starts, e[1] + e[2] // 2) - 1
            if i >= 0 and e[1] + e[2] // 2 < steps[i][1] + steps[i][2]:
                progs[i].append(e)
        for i, step in enumerate(steps):
            d, w, n, p = of["dispatch"][i], of["wait"][i], of["note"][i], progs[i]
            if not p or not (len(d) == len(w) == len(n) == len(p)):
                continue
            if any(ds[0] != f"ds.serve.{PROGRAM[e[0]]}.dispatch" or ns[0] != f"ds.serve.{PROGRAM[e[0]]}.note"
                   for ds, ns, e in zip(d, n, p)):
                continue
            rows += [{"program": e[0], "step": step[3], "dispatch_ns": ds[1], "wait_end_ns": ws[1] + ws[2], "note_ns": ns[1],
                      "start_ns": e[1], "end_ns": e[1] + e[2]} for ds, ws, ns, e in zip(d, w, n, p)]
    return rows or None


def clock(rows: List[Dict[str, Any]]) -> Tuple[int, float]:
    """``(offset_ns, halfwidth_ns)``: what to take off a device time to
    stand on the host's clock, read at the middle of its bounds — no
    execution starts before its ``dispatch`` span (offset ≤ the least
    ``start − dispatch``) nor ends after its read returned (offset ≥ the
    largest ``end − note``) — and half the distance between the bounds:
    how far the split of launch from read-back may be off.  A negative
    half-width says the two clocks drifted apart inside the trace by more
    than its shortest launch + read-back."""
    hi = min(r["start_ns"] - r["dispatch_ns"] for r in rows)
    lo = max(r["end_ns"] - r["note_ns"] for r in rows)
    return (lo + hi) // 2, (hi - lo) / 2.0


def _split(raw: Optional[Dict[str, Any]]) -> Optional[Tuple[List[Dict[str, Any]], int, float]]:
    rows = executions(raw)
    if rows is None:
        return None
    offset, half = clock(rows)
    return rows, offset, half


def launch_ms_p50(raw: Optional[Dict[str, Any]]) -> Optional[float]:
    """Median, over the traced executions, from the start of the
    ``dispatch`` span to the program's first instruction on the device."""
    got = _split(raw)
    if got is None:
        return None
    rows, offset, _ = got
    return percentile([r["start_ns"] - offset - r["dispatch_ns"] for r in rows], 50) / 1e6


def readback_ms_p50(raw: Optional[Dict[str, Any]]) -> Optional[float]:
    """Median, over the traced executions, from the program's end on the
    device to ``device_get`` returning (the ``note`` span's start)."""
    got = _split(raw)
    if got is None:
        return None
    rows, offset, _ = got
    return percentile([r["note_ns"] - (r["end_ns"] - offset) for r in rows], 50) / 1e6


def _overlap_ns(a: List[Interval], b: List[Interval]) -> int:
    """Length of the intersection of two lists of disjoint intervals, each in start order."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def partition(raw: Optional[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """The device's idle seconds in the traced window (from the first
    harness span's start to the last one's end, as ``trace.reduce``
    takes it; the steps' extent where the harness wrote none) by label,
    a mean over the chips::

        {"window_s", "idle_s", "seconds": {label: s for label in LABELS},
         "executions": joined, "programs": serving programs on the device's line,
         "clock_offset_ms", "clock_halfwidth_ms",
         "join_error_ms_max": the longest sliver between a wait span's end and its note span's start —
                              by how much launch + device time + read-back misses dispatch + wait}

    None where the trace has no step that pairs off, or lacks the
    ``commit`` or ``note`` spans."""
    got = _split(raw)
    if got is None or not any(s[0] == "ds.serve.commit" for s in raw["spans"]):
        return None
    rows, offset, half = got
    steps = _steps(raw)
    starts = [s[1] for s in steps]
    bench = [s for s in raw["spans"] if s[0].startswith(trace_mod.SPAN_PREFIX)] or steps
    w0, w1 = min(s[1] for s in bench), max(s[1] + s[2] for s in bench)
    by_label: Dict[str, List[Interval]] = {label: [] for label in LABELS}
    for held in _by_step(steps, starts, [s for s in raw["spans"] if s[0] in LEAVES]):
        for s in held:
            by_label[LEAVES[s[0]]].append((s[1], s[1] + s[2]))
    for r in rows:
        mid = (r["start_ns"] + r["end_ns"]) // 2 - offset
        by_label["launch"].append((r["dispatch_ns"], mid))
        by_label["readback"].append((mid, r["note_ns"]))
    in_steps = [(s[1], s[1] + s[2]) for s in steps]
    planes = [events for events in raw["modules"].values() if events]
    total = {label: 0 for label in LABELS}
    idle_ns = 0
    for events in planes:
        busy = trace_mod._union([(max(e[1] - offset, w0), min(e[1] + e[2] - offset, w1)) for e in events
                                 if e[1] - offset < w1 and e[1] + e[2] - offset > w0])
        edges = [w0] + [t for ab in busy for t in ab] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        idle_ns += sum(b - a for a, b in idle)
        under = {label: _overlap_ns(idle, sorted(spans)) for label, spans in by_label.items()}
        inside = _overlap_ns(idle, in_steps)
        # under no leaf: what is left of the idle time inside the steps; outside every step: the caller's
        under["unattributed"] = inside - sum(under.values())
        under["between_steps"] = sum(b - a for a, b in idle) - inside
        for label in LABELS:
            total[label] += under[label]
    n = len(planes)
    return {
        "window_s": (w1 - w0) / 1e9, "idle_s": idle_ns / 1e9 / n,
        "seconds": {label: total[label] / 1e9 / n for label in LABELS},
        "executions": len(rows), "programs": sum(1 for events in planes for e in events if e[0] in PROGRAM),
        "clock_offset_ms": offset / 1e6, "clock_halfwidth_ms": half / 1e6,
        "join_error_ms_max": max(abs(r["note_ns"] - r["wait_end_ns"]) for r in rows) / 1e6,
    }


def share_pct(raw: Optional[Dict[str, Any]], label: str) -> Optional[float]:
    """``label``'s share of the device's idle time in the traced window, per cent."""
    table = partition(raw)
    if table is None or table["idle_s"] <= 0:
        return None
    return 100.0 * table["seconds"][label] / table["idle_s"]
