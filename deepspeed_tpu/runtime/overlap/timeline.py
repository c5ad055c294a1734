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

:meth:`StepTimeline.summary` covers **every step since**
:meth:`StepTimeline.reset_window` — up to ``window`` of them, one row of
floats each in a ring; what fell out of the ring is counted
(``steps_dropped``) — and counts the window's *stalls*: steps whose
wall is more than ``STALL_FACTOR`` times the window's median, or,
where steps differ in what they wait for (``stall_among``), the median
of the steps alike.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

PHASES = ("data_wait", "compute", "ckpt_stall", "compile", "other")
# a step is a stall when its wall exceeds this many medians of its window:
# the longest ordinary serving step (one with a prefill chunk) is 1.8 x its
# window's median, the stalls seen on the chip's host 4-6 x and up
STALL_FACTOR = 3.0
STALLS_KEPT = 16  # stalls() names at most this many, the longest


class StepTimeline:
    """Per-step phase attribution over every step since the last
    :meth:`reset_window`, up to ``window`` of them.

    ``phases`` customizes the attributed phase names (the serving engine
    uses ``sched/sweep/prefill/decode/commit``); ``other`` is always
    present as the unattributed remainder.  ``sub_phases`` names what is
    timed *inside* a phase (serving: ``stage/dispatch/wait/note`` inside
    prefill and decode) and is therefore reported but never added to the
    step's wall; ``blocked_on`` names the one in which the host is
    blocked on the device, so that :meth:`summary` can report the rest
    of each step (``host_ms_p50/p95``: time in which a serial engine has
    given the device nothing).
    ``stall_among`` names a gauge by which steps are alike (serving:
    ``reads``, the programs a step read back): a step is a stall against
    the median wall of the steps that set it to the same value, not the
    whole window's — a step that waits for three programs is no stall
    for being three times one that waits for one.
    ``prefix`` (``train`` or ``serve``) names the engine in the
    profiler's trace: ``ds.<prefix>.<phase>``.  :meth:`set_gauge`
    records per-step levels (e.g. queue depth) that are averaged — not
    ms-scaled — in :meth:`summary`; :meth:`count` keeps running totals
    of events since the last :meth:`reset_window`.

    A step is one row of floats (its phases, ``other``, ``wall``, when
    it began, its gauges) in a ring of ``window`` rows: 8 bytes a number,
    so a window of tens of thousands of steps is a few MB, touched as it
    fills.  Steps past ``window`` overwrite the oldest and are counted
    (``steps_dropped``)."""

    def __init__(self, enabled: bool = True, window: int = 512, phases=None,
                 sub_phases=(), blocked_on: Optional[str] = None, prefix: str = "train",
                 stall_among: Optional[str] = None):
        self.enabled = bool(enabled)
        self.window = max(1, int(window))
        self.phases = tuple(phases) if phases is not None else PHASES
        if "other" not in self.phases:
            self.phases = self.phases + ("other",)
        self.sub_phases = tuple(sub_phases)
        self.blocked_on = blocked_on
        self.stall_among = stall_among
        self.prefix = str(prefix)
        # the ring: step n since reset_window() is row n % window.  "at"
        # (column 0) is the step's start, seconds since reset_window();
        # gauges take a column each, after the timed ones, as they first
        # appear.  Made at the first end_step
        self._cols: List[str] = ["at"] + [p for p in self.phases + self.sub_phases if p != "other"] + ["other", "wall"]
        self._timed = len(self._cols)
        self._rows: Optional[np.ndarray] = None
        self._n = 0
        self._t_reset = time.perf_counter()
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
        self._t_phases: Optional[frozenset] = None

    def attach_telemetry(self, manager, prefix: str = "train", trace_pid: int = 0, phases=None) -> None:
        """Route this timeline into a
        :class:`~deepspeed_tpu.telemetry.TelemetryManager`: every
        ``phase()`` block also lands as a span (when tracing is armed)
        and every ``end_step`` publishes the closed record as
        histograms/gauges.  ``phases`` keeps the plane to the named
        phases and sub-phases (with ``other``, ``wall`` and the gauges):
        what a timeline times beside them stands on the profiler's clock
        and in :meth:`summary` only, and is the plane's ``other``.
        Detach with ``manager=None``."""
        self._telemetry = manager
        self._t_prefix = prefix
        self._trace_pid = int(trace_pid)
        self._t_phases = None if phases is None else frozenset(phases) | {"other", "wall"}

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
    def phase(self, name: str, **args):
        """Time a host block and note it under ``name`` — a sub-phase
        ``outer.inner`` under ``inner`` — and annotate it in the
        profiler's trace with ``args`` (and, a phase only, as a
        Chrome-trace span when the attached telemetry plane has tracing
        armed).  Yields the annotation: ``set_metadata(**args)`` on it
        adds arguments that are known only once the block has run."""
        with self.annotation(name, **args) as span:
            if not self.enabled:
                yield span
                return
            key = name.rpartition(".")[2]
            tm = self._telemetry
            tracer = None
            if key == name and tm is not None and tm.tracer.enabled and (self._t_phases is None or key in self._t_phases):
                tracer = tm.tracer
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
        # a step in progress at reset_window() began where the window does
        began = max(0.0, now - rec["wall"] * count - self._t_reset)
        for k in range(count):
            self._append(rec, began + k * rec["wall"])
        self.total_steps += count
        tm = self._telemetry
        if tm is not None:
            # registry publish of the closed record (host dict ops; the
            # manager also derives the live MFU gauge from the wall)
            keep = self._t_phases
            if keep is not None:
                # a phase the plane is not told of is its `other`: what it has still adds up to the wall
                hidden = sum(v for k, v in rec.items() if k in self.phases and k not in keep)
                rec = {k: v for k, v in rec.items() if k in keep or k in self._gauge_names}
                rec["other"] += hidden
            tm.publish_step(self._t_prefix, rec, count=count, gauge_names=self._gauge_names)
        self._pending = {}
        self._pending_gauges = {}

    def _append(self, rec: Dict[str, float], at: float) -> None:
        """One closed step into the ring."""
        if self._rows is None or len(self._cols) < len(self._gauge_names) + self._timed:
            new = [g for g in sorted(self._gauge_names) if g not in self._cols]
            self._cols += new
            if self._rows is None:
                self._rows = np.zeros((self.window, len(self._cols)))
            else:  # a gauge first set after steps were recorded: it read nothing in them
                self._rows = np.concatenate([self._rows, np.zeros((self.window, len(new)))], axis=1)
        get = rec.get
        row = [get(c, 0.0) for c in self._cols]
        row[0] = at
        self._rows[self._n % self.window] = row
        self._n += 1

    def reset_window(self) -> None:
        """Drop recorded steps and zero the event counters (keep the
        wall anchor); the next ``summary()`` covers only what was
        recorded after this call."""
        self._n = 0
        self._t_reset = time.perf_counter()
        self.counts = dict.fromkeys(self.counts, 0)

    # -- reporting --------------------------------------------------------
    def _held(self, last_n: Optional[int] = None) -> Dict[str, np.ndarray]:
        """The steps held, oldest first, a column a name (the last
        ``last_n`` of them)."""
        if self._rows is None:
            rows = np.zeros((0, len(self._cols)))
        elif self._n <= self.window:
            rows = self._rows[: self._n]
        else:
            i = self._n % self.window
            rows = np.concatenate([self._rows[i:], self._rows[:i]])
        if last_n is not None:
            rows = rows[-int(last_n):] if int(last_n) > 0 else rows[:0]
        return {c: rows[:, i] for i, c in enumerate(self._cols)}

    @property
    def records(self) -> List[Dict[str, float]]:
        """The steps held, oldest first, a dict a step (phases,
        sub-phases, ``other``, ``wall`` in seconds; gauges as set)."""
        held = self._held()
        names = [c for c in self._cols if c != "at"]
        return [{c: float(held[c][k]) for c in names} for k in range(len(held["wall"]))]

    def _stalled(self, held: Dict[str, np.ndarray]):
        """(each step's median wall — the window's, or that of the steps
        alike in ``stall_among`` — and the mask of the steps that are
        stalls)."""
        wall = held["wall"]
        p50 = np.zeros(len(wall))
        alike = held.get(self.stall_among, np.zeros(len(wall)))
        for value in np.unique(alike):
            among = alike == value
            p50[among] = np.percentile(wall[among], 50)
        return p50, (wall > STALL_FACTOR * p50) & (p50 > 0)

    def summary(self, last_n: Optional[int] = None) -> Dict[str, float]:
        """Mean per-step milliseconds per phase over every step since
        :meth:`reset_window` (the last ``window`` of them, ``steps_dropped``
        saying how many fell out; or the last ``last_n``), plus
        ``steps_per_s`` derived from the mean step wall; and, so that a
        slow stretch of the window does not vanish in a mean,
        ``<phase>_ms_p50`` and ``<phase>_ms_p95`` for every phase,
        sub-phase, ``other`` and ``wall`` (and ``host``: wall minus the
        ``blocked_on`` phase), ``wall_ms_max`` (and the ``blocked_on``
        phase's), and the stalls: ``stall_steps`` whose wall is over
        ``STALL_FACTOR`` medians (of the steps alike in ``stall_among``,
        where that is set), ``stall_ms`` their summed excess over
        the median, ``stall_first_at_s`` seconds after
        :meth:`reset_window` (-1: none).  Scalars only; :meth:`stalls`
        names the steps."""
        held = self._held(last_n)
        n = len(held["wall"])
        timed = self.phases + self.sub_phases
        out = {f"{p}_ms": 0.0 for p in timed}
        out["wall_ms"] = 0.0
        out["steps"] = n
        out["steps_dropped"] = max(0, self._n - self.window)
        out["steps_per_s"] = 0.0
        out.update(stall_steps=0, stall_ms=0.0, stall_first_at_s=-1.0)
        for g in sorted(self._gauge_names):
            out[g] = 0.0
        out.update(self.counts)
        if self.comm_strategy is not None:
            out["comm_strategy"] = self.comm_strategy
            out["comm_bytes_per_step"] = self.comm_bytes
        if not n:
            return out
        for p in timed:
            out[f"{p}_ms"] = round(float(held[p].sum()) * 1000.0 / n, 3)
        per_step = {p: held[p] for p in timed + ("wall",)}
        if self.blocked_on is not None:
            per_step["host"] = held["wall"] - held[self.blocked_on]
        for p, vals in per_step.items():
            for q, v in zip((50, 95), np.percentile(vals, (50, 95))):
                out[f"{p}_ms_p{q}"] = round(float(v) * 1000.0, 3)
        for p in ("wall", self.blocked_on):
            if p is not None:
                out[f"{p}_ms_max"] = round(float(held[p].max()) * 1000.0, 3)
        p50, over = self._stalled(held)
        if over.any():
            out["stall_steps"] = int(over.sum())
            out["stall_ms"] = round(float((held["wall"] - p50)[over].sum()) * 1000.0, 3)
            out["stall_first_at_s"] = round(float(held["at"][over][0]), 3)
        for g in sorted(self._gauge_names):
            out[g] = round(float(held[g].sum()) / n, 3) if g in held else 0.0
        wall = float(held["wall"].sum()) / n
        out["wall_ms"] = round(wall * 1000.0, 3)
        out["steps_per_s"] = round(1.0 / wall, 3) if wall > 0 else 0.0
        return out

    def stalls(self) -> List[Dict[str, float]]:
        """The window's stalls by name, the ``STALLS_KEPT`` longest in
        step order: ``step`` (the ordinal since the timeline began: the
        serving engine's step number), ``at_s`` since
        :meth:`reset_window`, ``wall_ms`` and every phase's and
        sub-phase's ``<name>_ms`` of that step."""
        held = self._held()
        wall = held["wall"]
        _, over = self._stalled(held)
        idx = np.flatnonzero(over)
        idx = np.sort(idx[np.argsort(-wall[idx], kind="stable")[:STALLS_KEPT]])
        first = self.total_steps - len(wall) + 1
        return [{"step": first + int(k), "at_s": round(float(held["at"][k]), 3),
                 "wall_ms": round(float(wall[k]) * 1e3, 3),
                 **{f"{p}_ms": round(float(held[p][k]) * 1e3, 3) for p in self.phases + self.sub_phases}}
                for k in idx]

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
