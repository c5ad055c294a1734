"""Runs one cell once: the part of the benchmark no cell owns.

``run_cell`` loads the manifest, finds the cell's configuration, traffic
mix and runner by name, hands the runner a :class:`Context`, reduces the
trace of a traced run, asks each per-layer metric's reader for its
number, and builds the one result line of the contract.  The runner
(``runners/<runner>.py``, ``run(ctx) -> record``) builds the system
under test through its public entry points, warms it, checks its
outputs and measures.
"""
from __future__ import annotations

import contextlib
import os
import sys
import time
from typing import Any, Dict, List, Optional

from . import trace as trace_mod
from .manifest import Manifest

TRACE_SECONDS = 5.0  # a traced run profiles the last seconds of its window
# JAX's monitoring events for a program built by the compiler and for one loaded from the persistent cache
_PROGRAM_EVENTS = ("/jax/core/compile/backend_compile_duration", "/jax/compilation_cache/cache_retrieval_time_sec")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Context:
    """What a runner is given."""

    def __init__(self, manifest: Manifest, cell: Dict[str, Any], seed: int, seconds: float,
                 trace: bool, devices: List[Any], t_start: float, scratch: str):
        self.manifest = manifest
        self.cell = cell
        self.config = manifest.config(cell["config"])
        self.traffic = manifest.traffic(cell["traffic"])
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.devices = devices
        self.t_start = t_start  # process start on time.perf_counter()'s clock
        self.scratch = scratch
        self.spans: List[List[Any]] = []  # [name, t0, t1] on time.perf_counter()'s clock
        self._tracing = False
        self.trace_dir = os.path.join(scratch, "trace", cell["name"])
        self.trace_t0: Optional[float] = None
        # every XLA program this process compiled or loaded from the
        # persistent cache: [seconds on perf_counter's clock, event]
        self.program_events: List[List[Any]] = []
        self.window: Optional[List[float]] = None

    def say(self, msg: str) -> None:
        print(f"[bench +{time.perf_counter() - self.t_start:7.2f}s] {msg}", file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        """A harness span round a call into a layer: kept for the span
        metrics, and written into the profiler's trace (``bench.<name>``)
        so that idle gaps can be labelled."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.spans.append([name, t0, time.perf_counter()])

    # -- the window ------------------------------------------------------------
    def window_opens(self) -> float:
        """The runner calls this where set-up ends.  From here to
        :meth:`window_closes` nothing may compile: JAX logs the name of
        any program it compiles meanwhile, and the harness counts every
        program compiled or loaded from the cache."""
        import jax

        jax.config.update("jax_log_compiles", True)
        self.window = [time.perf_counter(), float("inf")]
        return self.window[0]

    def window_closes(self) -> None:
        import jax

        jax.config.update("jax_log_compiles", False)
        self.window[1] = time.perf_counter()
        self.stop_trace()

    def programs_built_in_window(self) -> int:
        return sum(1 for t, _ in self.program_events if self.window and self.window[0] <= t <= self.window[1])

    # -- profiler ------------------------------------------------------------
    def maybe_start_trace(self, now: float, t_close: float) -> None:
        """Called by the runner at step boundaries inside the window."""
        if not self.trace or self._tracing or now < t_close - min(TRACE_SECONDS, self.seconds):
            return
        import jax
        import shutil

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True
        self.trace_t0 = time.perf_counter()

    def stop_trace(self) -> None:
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False


def _device_block(devices: List[Any]) -> Dict[str, Any]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": int(max(peaks))}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
             manifest_path: Optional[str] = None, require_tpu: bool = True,
             scratch: Optional[str] = None) -> Dict[str, Any]:
    """Run one cell; returns ``{"result": <the contract's line>, "record": <everything measured>}``.

    ``require_tpu=False`` is for the CPU tests: the run then reports the
    program's *counts* only — every metric that is a time, a rate or a
    share of the device is left out, so no CPU number can appear under a
    device metric's name.
    """
    manifest = Manifest(manifest_path) if manifest_path else Manifest()
    cell = manifest.cell(workload)
    import jax

    found = jax.devices()
    if require_tpu and (found[0].platform != "tpu" or len(found) < cell["chips"]):
        raise NoAccelerator(f"cell {workload!r} needs {cell['chips']} TPU chip(s); JAX found "
                            f"{len(found)} x {found[0].platform} ({found[0].device_kind})")
    devices = list(found[: cell["chips"]])
    on_tpu = devices[0].platform == "tpu"
    scratch = scratch or os.path.join(manifest.root, ".bench_scratch")
    os.makedirs(scratch, exist_ok=True)
    ctx = Context(manifest, cell, seed, seconds, trace, devices, t_start, scratch)
    runner = manifest.module("runners", ctx.config["runner"])
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: ctx.program_events.append([time.perf_counter(), event])
        if event in _PROGRAM_EVENTS else None)
    try:
        record = runner.run(ctx)
    finally:
        ctx.stop_trace()
    tl = record["counters"].get("timeline") or {}
    if "wall_ms_p50" in tl:  # beside a run that reads far off: was every call into PJRT longer, or a few steps stalled?
        ctx.say("engine's step timeline over the window, p50 / mean ms: " + ", ".join(
            f"{p} {tl[p + '_ms_p50']} / {tl[p + '_ms']}" for p in ("wall", "stage", "dispatch", "wait") if p + "_ms_p50" in tl))
    # the engines' own counters see their step executables; this sees every program, however small
    record["counters"]["compiles_in_window"] += ctx.programs_built_in_window()
    ctx.say(f"programs built in the window: {record['counters']['compiles_in_window']} "
            f"(of {len(ctx.program_events)} compiled or loaded by this process)")
    record["spans"] = ctx.spans
    record["device"] = _device_block(devices)
    record["config"], record["traffic"], record["cell"], record["manifest"] = ctx.config, ctx.traffic, cell, manifest

    reduced = None
    if trace and on_tpu:
        reduced = trace_mod.reduce(trace_mod.load_xplane(trace_mod.find_xplane(ctx.trace_dir)))
        if reduced is None:
            raise RuntimeError("the traced window holds no device operation or no harness span")
    record["trace"] = reduced
    if reduced is not None:
        for entry in manifest.per_layer(workload):
            k = reduced["kernels"].get(entry["name"][: -len("_roofline")]) if entry["name"].endswith("_roofline") else None
            if k:
                ctx.say(f"{entry['name']}: {k['calls']} calls, {k['seconds']:.6f}s, e.g. {k['example']}")

    wanted = manifest.per_layer(workload) if trace else manifest.end_to_end(workload)
    metrics: Dict[str, Dict[str, Any]] = {}
    for entry in wanted:
        if not on_tpu and entry["source"] != "program_counter":
            continue
        if trace:
            value = manifest.module("metrics", entry["name"]).read(record)
        else:
            value = record["end_to_end"].get(entry["name"])
        if value is not None:
            metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}

    result: Dict[str, Any] = {
        "correct": bool(record["checks"]) and all(c["ok"] for c in record["checks"]),
        "attempted": int(record["attempted"]), "failed": int(record["failed"]),
        "metrics": metrics, "device": dict(record["device"]),
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    # every number compared beside its limit: the last key of the line, and the last lines on standard error
    result["checks"] = {c["name"]: {"value": c["value"] if c["value"] == c["value"] else None, "limit": c["limit"],
                                    "op": c["op"], "ok": c["ok"]} for c in record["checks"]}
    for c in record["checks"]:
        print(f"check {c['name']}: {c['value']!r} (limit {c['op']} {c['limit']!r}) -> "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr, flush=True)
    return {"result": result, "record": record}


def check(name: str, value: float, op: str, limit: float) -> Dict[str, Any]:
    """One compared number beside its limit (``op`` is ``<=`` or ``>=``)."""
    if op not in ("<=", ">="):
        raise ValueError(op)
    ok = (value <= limit) if op == "<=" else (value >= limit)
    return {"name": name, "value": float(value), "op": op, "limit": float(limit), "ok": bool(ok and value == value)}


def memory_analysis(executable) -> str:
    """One line of the compiler's own memory analysis of a compiled
    program, for a traced run's log.  Not a metric: on the v5e its
    arguments + temporaries came to more than the chip holds for programs
    that run (19.9 GB for the paged prefill step, 17.4 GB for the GPT-2
    Large train step, of 16.9 GB; PERF.md section 7), so the peak the
    benchmark reports is PJRT's."""
    m = executable.memory_analysis()
    if m is None:
        return "no memory analysis"
    return (f"arguments {m.argument_size_in_bytes / 1e9:.2f} GB, temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, "
            f"outputs {m.output_size_in_bytes / 1e9:.2f} GB of which aliased {m.alias_size_in_bytes / 1e9:.2f} GB")
