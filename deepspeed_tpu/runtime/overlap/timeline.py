"""Step-phase timeline: honest per-step wall-time attribution.

Every training step's wall time is split into named phases:

* ``data_wait``  — host blocked waiting for an input batch (loader pull,
  stacking, ``device_put`` transfer, prefetch-queue wait);
* ``compute``    — dispatch of the compiled step until its outputs are
  ready, recorded ONLY when the engine fences it with
  ``jax.block_until_ready`` (``overlap.timeline.fence``, defaulting to
  the ``wall_clock_breakdown`` opt-in) — XLA dispatch is asynchronous,
  so an unfenced delta only measures Python overhead (the ds_lint
  ``unfenced-timing`` rule) and a per-step fence costs the round trip
  ThroughputTimer deliberately avoids off report steps;
* ``ckpt_stall`` — time training was stalled on checkpoint I/O (the
  full save for synchronous saves; snapshot+submit for async saves);
* ``compile``    — building a new executable (trace+lower+compile);
* ``other``      — whatever remains of the step wall (host bookkeeping,
  logging, monitor flushes).

Notes accumulate into a *pending* record; :meth:`end_step` closes it
against the wall clock since the previous step boundary, so host work
that happens between steps (e.g. a checkpoint save between two
``train_batch`` calls) is attributed to the step that paid for it.

The timeline itself is pure host bookkeeping (two ``perf_counter``
reads and a dict update per note): enabled without the fence it does
not change the hot path and still attributes every host-measurable
phase; the per-step device fence is the engine's (opt-in) choice.

Every :meth:`StepTimeline.phase` block is also a
``jax.profiler.TraceAnnotation`` named ``ds.<prefix>.<name>``, so the
same phases stand in the profiler's trace, on the clock of the device
planes, whenever a ``jax.profiler`` trace is running (and cost one
inactive annotation otherwise).  A dotted name (``prefill.stage``) is a
*sub-phase*: annotated under its full name, recorded under its last
component and, lying inside another phase, left out of the sum from
which ``other`` and ``wall`` derive (docs/telemetry.md).
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager
from typing import Deque, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

PHASES = ("data_wait", "compute", "ckpt_stall", "compile", "other")


class StepTimeline:
    """Rolling per-step phase attribution over the last ``window`` steps.

    ``phases`` customizes the attributed phase names (the serving engine
    uses ``prefill/decode/sched``); ``other`` is always present as the
    unattributed remainder.  ``sub_phases`` names what is timed *inside*
    a phase (serving: ``stage/dispatch/wait`` inside prefill and decode)
    and is therefore reported but never added to the step's wall;
    ``blocked_on`` names the one in which the host is blocked on the
    device, so that :meth:`summary` can report the rest of each step
    (``host_ms_p50/p95``: time in which a serial engine has given the
    device nothing).
    ``prefix`` (``train`` or ``serve``) names the engine in the
    profiler's trace: ``ds.<prefix>.<phase>``.  :meth:`set_gauge`
    records per-step levels (e.g. queue depth) that are averaged — not
    ms-scaled — in :meth:`summary`; :meth:`count` keeps running totals
    of events since the last :meth:`reset_window`."""

    def __init__(self, enabled: bool = True, window: int = 512, phases=None,
                 sub_phases=(), blocked_on: Optional[str] = None, prefix: str = "train"):
        self.enabled = bool(enabled)
        self.window = max(1, int(window))
        self.phases = tuple(phases) if phases is not None else PHASES
        if "other" not in self.phases:
            self.phases = self.phases + ("other",)
        self.sub_phases = tuple(sub_phases)
        self.blocked_on = blocked_on
        self.prefix = str(prefix)
        self.records: Deque[Dict[str, float]] = deque(maxlen=self.window)
        self.total_steps = 0
        self._pending: Dict[str, float] = {}
        self._pending_gauges: Dict[str, float] = {}
        self._gauge_names: set = set()
        self.counts: Dict[str, int] = {}
        self._last_boundary: Optional[float] = None
        # comm metadata (docs/comm.md): the active gradient-exchange
        # strategy and its modeled bytes/step — static per engine, set
        # once by the comm layer, carried into every summary/record
        self.comm_strategy: Optional[str] = None
        self.comm_bytes: Optional[int] = None
        # telemetry plane attachment (docs/telemetry.md): None-checked
        # on the hot path; when attached, phases become Chrome-trace
        # spans and closed step records publish into the registry
        self._telemetry = None
        self._t_prefix = "train"
        self._trace_pid = 0

    def attach_telemetry(self, manager, prefix: str = "train", trace_pid: int = 0) -> None:
        """Route this timeline into a
        :class:`~deepspeed_tpu.telemetry.TelemetryManager`: every
        ``phase()`` block also lands as a span (when tracing is armed)
        and every ``end_step`` publishes the closed record as
        histograms/gauges.  Detach with ``manager=None``."""
        self._telemetry = manager
        self._t_prefix = prefix
        self._trace_pid = int(trace_pid)

    def set_comm(self, strategy: str, bytes_per_step: int) -> None:
        """Record the engine's active comm strategy + per-step
        grad-exchange bytes model (not gated on ``enabled`` — metadata,
        not a timed phase)."""
        self.comm_strategy = str(strategy)
        self.comm_bytes = int(bytes_per_step)

    # -- recording --------------------------------------------------------
    def note(self, phase: str, seconds: float) -> None:
        """Accumulate ``seconds`` of ``phase`` into the pending step."""
        if not self.enabled:
            return
        self._pending[phase] = self._pending.get(phase, 0.0) + float(seconds)

    def annotation(self, name: str, **args) -> TraceAnnotation:
        """``ds.<prefix>.<name>`` in the profiler's trace, with ``args``
        (the step number on a step's span) as the event's arguments.
        Written only while a ``jax.profiler`` trace is running; not
        gated on ``enabled``, and nothing is recorded here."""
        return TraceAnnotation(f"ds.{self.prefix}.{name}", **args)

    @contextmanager
    def phase(self, name: str):
        """Time a host block and note it under ``name`` — a sub-phase
        ``outer.inner`` under ``inner`` — and annotate it in the
        profiler's trace (and, a phase only, as a Chrome-trace span when
        the attached telemetry plane has tracing armed).  Yields the
        annotation: ``set_metadata(**args)`` on it adds arguments that
        are known only once the block has run."""
        with self.annotation(name) as span:
            if not self.enabled:
                yield span
                return
            key = name.rpartition(".")[2]
            tm = self._telemetry
            tracer = tm.tracer if key == name and tm is not None and tm.tracer.enabled else None
            t0m = tracer.now() if tracer is not None else 0.0
            t0 = time.perf_counter()
            try:
                yield span
            finally:
                dt = time.perf_counter() - t0
                self.note(key, dt)
                if tracer is not None:
                    tracer.add_span(
                        f"{self._t_prefix}/{name}", self._t_prefix, t0m, t0m + dt,
                        pid=self._trace_pid,
                    )

    def set_gauge(self, name: str, value: float) -> None:
        """Record a per-step level (queue depth, live slots, ...): kept
        as-is in the step record and reported as a window mean, not a
        millisecond phase."""
        if not self.enabled:
            return
        self._pending_gauges[name] = float(value)
        self._gauge_names.add(name)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the event counter ``name`` (serving:
        ``stage_puts``, ``programs``): a running total, not a per-step
        level — :meth:`summary` reports it as it stands, and
        :meth:`reset_window` starts it afresh."""
        if not self.enabled:
            return
        self.counts[name] = self.counts.get(name, 0) + int(n)

    def end_step(self, count: int = 1) -> None:
        """Close the pending record against the wall clock.  ``count > 1``
        spreads the window evenly over ``count`` steps (one compiled
        multi-step run, e.g. ``train_batches``)."""
        if not self.enabled:
            return
        now = time.perf_counter()
        # sub-phases lie inside a phase: counted again they would zero
        # ``other`` and inflate ``wall``
        noted = sum(v for p, v in self._pending.items() if p not in self.sub_phases)
        if self._last_boundary is None:
            # first boundary: no previous anchor, the wall is whatever
            # was explicitly noted (avoids charging engine build time
            # to step 1's "other")
            wall = noted
        else:
            wall = now - self._last_boundary
        self._last_boundary = now
        other = max(0.0, wall - noted)
        count = max(1, int(count))
        rec = {p: self._pending.get(p, 0.0) / count
               for p in self.phases + self.sub_phases if p != "other"}
        rec["other"] = (self._pending.get("other", 0.0) + other) / count
        rec["wall"] = max(wall, noted) / count
        rec.update(self._pending_gauges)
        for _ in range(count):
            self.records.append(dict(rec))
        self.total_steps += count
        if self._telemetry is not None:
            # registry publish of the closed record (host dict ops; the
            # manager also derives the live MFU gauge from the wall)
            self._telemetry.publish_step(
                self._t_prefix, rec, count=count, gauge_names=self._gauge_names
            )
        self._pending = {}
        self._pending_gauges = {}

    def reset_window(self) -> None:
        """Drop recorded steps and zero the event counters (keep the
        wall anchor); the next ``summary()`` covers only what was
        recorded after this call."""
        self.records.clear()
        self.counts = dict.fromkeys(self.counts, 0)

    # -- reporting --------------------------------------------------------
    def summary(self, last_n: Optional[int] = None) -> Dict[str, float]:
        """Mean per-step milliseconds per phase over the last ``last_n``
        recorded steps (default: the whole window), plus ``steps_per_s``
        derived from the mean step wall; and, so that a slow stretch of
        the window does not vanish in a mean, ``<phase>_ms_p50`` and
        ``<phase>_ms_p95`` for every phase, sub-phase, ``other`` and
        ``wall`` (and ``host``: wall minus the ``blocked_on`` phase)."""
        recs: List[Dict[str, float]] = list(self.records)
        if last_n is not None:
            recs = recs[-int(last_n):]
        timed = self.phases + self.sub_phases
        out = {f"{p}_ms": 0.0 for p in timed}
        out["wall_ms"] = 0.0
        out["steps"] = len(recs)
        out["steps_per_s"] = 0.0
        for g in sorted(self._gauge_names):
            out[g] = 0.0
        out.update(self.counts)
        if self.comm_strategy is not None:
            out["comm_strategy"] = self.comm_strategy
            out["comm_bytes_per_step"] = self.comm_bytes
        if not recs:
            return out
        n = len(recs)
        per_step = {p: [r.get(p, 0.0) for r in recs] for p in timed + ("wall",)}
        for p in timed:
            out[f"{p}_ms"] = round(sum(per_step[p]) * 1000.0 / n, 3)
        if self.blocked_on is not None:
            per_step["host"] = [r.get("wall", 0.0) - r.get(self.blocked_on, 0.0) for r in recs]
        for p, vals in per_step.items():
            for q, v in zip((50, 95), np.percentile(vals, (50, 95))):
                out[f"{p}_ms_p{q}"] = round(float(v) * 1000.0, 3)
        for g in sorted(self._gauge_names):
            out[g] = round(sum(r.get(g, 0.0) for r in recs) / n, 3)
        wall = sum(r.get("wall", 0.0) for r in recs) / n
        out["wall_ms"] = round(wall * 1000.0, 3)
        out["steps_per_s"] = round(1.0 / wall, 3) if wall > 0 else 0.0
        return out

    def format_summary(self, last_n: Optional[int] = None) -> str:
        """One log line: phase means and their share of the step wall."""
        s = self.summary(last_n)
        if not s["steps"]:
            return "step timeline: no steps recorded"
        wall = max(s["wall_ms"], 1e-9)
        parts = [
            f"{p}: {s[f'{p}_ms']:.1f}ms ({100.0 * s[f'{p}_ms'] / wall:.0f}%)"
            for p in self.phases
            if s[f"{p}_ms"] > 0.0 or p in ("data_wait", "compute")
        ]
        parts += [f"{g}: {s[g]:.1f}" for g in sorted(self._gauge_names)]
        comm = ""
        if s.get("comm_strategy"):
            comm = (
                f" | comm: {s['comm_strategy']}"
                f" ({s.get('comm_bytes_per_step', 0) / 1e6:.1f} MB/step grad exchange)"
            )
        return (
            f"step timeline over {s['steps']} step(s): wall {s['wall_ms']:.1f}ms "
            f"({s['steps_per_s']:.2f} steps/s) | " + " | ".join(parts) + comm
        )
