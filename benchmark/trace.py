"""From a profiler trace to numbers.  The benchmark's own reduction, so
every PR computes the same number the same way.

Two steps, so that the arithmetic can be tested on a small recorded
trace without the profiler:

* :func:`load_xplane` reads an ``.xplane.pb`` with
  ``jax.profiler.ProfileData`` into a plain dict
  ``{"devices": {plane: [[name, start_ns, dur_ns], ...]}, "spans": [[name, start_ns, dur_ns], ...]}``
  — the ``XLA Ops`` line of each ``/device:TPU:n`` plane, and the
  harness's own ``bench.*`` annotations from the host planes;
* :func:`reduce` turns that dict into device busy and idle time over
  the traced window, self time per operation (grouped by the names the
  program gives), time in collectives, and the idle gaps labelled by
  the harness span open on the host at the time.

On a v5e trace the op and host clocks agree to a millisecond or two
(seen: a device op 1.2 ms ahead of the host span that launched it), so
a gap is labelled only when it is longer than ``MIN_GAP_NS``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
MIN_GAP_NS = 2_000_000
_COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
                         r"collective-broadcast|ragged-all-to-all)")
_OP_HEAD = re.compile(r"^%?([^\s=]+)\s*=\s*(\(?[a-z0-9]+\[[^\]]*\])?")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> Dict[str, Any]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[List[Any]]] = {}
    spans: List[List[Any]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def op_name(event_name: str) -> str:
    """``%flash_attention_fwd.1 = bf16[80,1024,64]{…} custom-call(…)`` →
    ``flash_attention_fwd``: the instruction's name without the
    compiler's ``.n`` suffixes."""
    m = _OP_HEAD.match(event_name)
    name = m.group(1) if m else event_name.split(" ")[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def op_group(event_name: str) -> str:
    """Grouping key of the breakdown: op name and output shape."""
    m = _OP_HEAD.match(event_name)
    shape = (m.group(2) or "").lstrip("(") if m else ""
    return f"{op_name(event_name)} {shape}".strip()


def first_output_elems(event_name: str) -> int:
    """Elements of the first array the instruction produces."""
    m = re.search(r"=\s*\(?[a-z0-9]+\[([\d,]*)\]", event_name)
    if not m or not m.group(1):
        return 0
    n = 1
    for d in m.group(1).split(","):
        n *= int(d)
    return n


def _self_times(events: List[List[Any]]) -> List[Tuple[str, int, int, int]]:
    """(name, start, dur, self) per event: an op that contains others
    (a ``while``, a ``conditional``) keeps only the time its children do
    not cover, so nothing is counted twice."""
    out: List[List[Any]] = []
    stack: List[int] = []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][1] + out[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= dur
        out.append([name, start, dur, dur])
        stack.append(len(out) - 1)
    return [(n, s, d, max(0, sf)) for n, s, d, sf in out]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _span_at(spans: List[List[Any]], t: int) -> str:
    """Innermost harness span open at host time ``t``."""
    best: Optional[List[Any]] = None
    for s in spans:
        if s[1] <= t < s[1] + s[2] and (best is None or s[2] < best[2]):
            best = s
    return best[0] if best else "outside-spans"


def reduce(raw: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """Busy/idle, per-op self time, collectives and labelled gaps over
    the traced window: from the first harness span's start to the last
    one's end.  None when no operation ran on a device."""
    spans = raw["spans"]
    devices = {k: v for k, v in raw["devices"].items() if v}
    if not spans or not devices:
        return None
    w0 = min(s[1] for s in spans)
    w1 = max(s[1] + s[2] for s in spans)
    busy_ns, coll_ns = [], []
    ops: Dict[str, int] = {}
    kernels: Dict[str, Dict[str, float]] = {}
    gaps: Dict[str, int] = {}
    for events in devices.values():
        clipped = [(n, max(s, w0), min(s + d, w1) - max(s, w0)) for n, s, d in events if s < w1 and s + d > w0]
        merged = _union([(s, s + d) for _, s, d in clipped if d > 0])
        busy_ns.append(sum(b - a for a, b in merged))
        coll = 0
        for name, _, _, self_ns in _self_times([list(e) for e in clipped]):
            base = op_name(name)
            ops[op_group(name)] = ops.get(op_group(name), 0) + self_ns
            # "example": one event's full text (operand types included), for checking a work function by eye
            k = kernels.setdefault(base, {"seconds": 0.0, "calls": 0, "out_elems": 0, "example": name[:600]})
            k["seconds"] += self_ns / 1e9
            k["calls"] += 1
            k["out_elems"] += first_output_elems(name)
            if _COLLECTIVE.match(base):
                coll += self_ns
        coll_ns.append(coll)
        edges = [w0] + [t for ab in merged for t in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= MIN_GAP_NS:
                label = _span_at(spans, (a + b) // 2)
                gaps[label] = gaps.get(label, 0) + (b - a)
    n = len(devices)
    window_s = (w1 - w0) / 1e9
    rank = lambda d: [[k, v / 1e9 / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "window_s": window_s,
        "busy_s": sum(busy_ns) / 1e9 / n,
        "collective_s": sum(coll_ns) / 1e9 / n,
        "chips": n,
        # per op name, summed over chips: seconds of self time, number of
        # events, and elements of the events' first outputs
        "kernels": kernels,
        "device_ops": rank(ops),
        "idle_gaps": rank(gaps),
        "span_counts": {name: sum(1 for s in spans if s[0] == name) for name in {s[0] for s in spans}},
    }
