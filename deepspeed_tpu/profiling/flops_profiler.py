"""FLOPs profiler.

Reference: ``profiling/flops_profiler/profiler.py`` (``FlopsProfiler``
:11, standalone ``get_model_profile`` :888) — monkey-patches
``torch.nn.functional`` and hangs module hooks to count MACs/params/
latency per module.

TPU-native re-design (SURVEY §5.1): XLA already knows the cost of the
compiled program — ``jitted.lower().compile().cost_analysis()`` returns
exact flops/bytes for the *fused* computation, which is more truthful
than functional-patch counting (it sees rematerialization, fused
epilogues, and the backward pass).  The profiler therefore:

* profiles any jittable ``fn(*args)`` via AOT lowering (no execution
  needed for the static numbers);

  CAVEAT: XLA cost analysis counts a ``lax.scan`` body ONCE, not per
  trip — models that scan over layers (models/gpt2.py) or engines that
  scan over micro-batches under-report flops by that factor.  For MFU
  use an analytic count (flops/token ≈ 6N + attention, as benchmark/stats.py does),
  or unroll the scan for profiling;
* measures wall clock around real calls for achieved FLOPS / MFU against
  a configurable peak;
* integrates with the engine: ``profile_step`` triggers a one-shot
  report of the compiled train step (config block ``flops_profiler``,
  reference ``profiling/config.py:49``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import log_dist, logger


@dataclasses.dataclass(frozen=True)
class DevicePeak:
    """One chip's published peaks: the MFU and roofline denominators."""

    bf16_tflops: float
    hbm_gbps: float
    source: str


# The one peaks table, keyed by ``jax.devices()[0].device_kind``.  A row
# is added with its source; a device that has none has no MFU and no
# roofline (:func:`device_peak` raises) rather than an invented one.
DEVICE_PEAKS: Dict[str, DevicePeak] = {
    "TPU v5 lite": DevicePeak(
        bf16_tflops=197.0, hbm_gbps=819.0,
        source='Google Cloud documentation, "TPU v5e" system architecture',
    ),
}


def device_peak(device_kind: Optional[str] = None) -> DevicePeak:
    """Peaks of ``device_kind`` (default: this process's first device)."""
    kind = device_kind or jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no published peak for device kind {kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add a DEVICE_PEAKS row with its source"
        )
    return DEVICE_PEAKS[kind]


def _num_params(tree: Any) -> int:
    return sum(int(np.prod(np.shape(p))) for p in jax.tree.leaves(tree))


def cost_bytes(cost: Optional[Dict[str, float]]) -> float:
    """HBM bytes from a ``cost_analysis()`` dict."""
    return float((cost or {}).get("bytes accessed", 0.0))


def peak_flops(device_kind: Optional[str] = None, n_devices: int = 1) -> float:
    """bf16 peak FLOP/s for the MFU denominator.  Defaults to ONE
    chip's peak: XLA ``cost_analysis()`` reports the *partitioned*
    (per-device) module, so per-device flops over per-chip peak is the
    correct MFU (verified against the analytic 6N+attention count on
    the 8-device dryrun, within 10%; tests/test_telemetry.py pins it)."""
    return device_peak(device_kind).bf16_tflops * 1e12 * max(1, int(n_devices))


def peak_hbm_bytes_per_s(device_kind: Optional[str] = None) -> float:
    """Peak HBM bytes/s for ONE chip — the roofline bandwidth ceiling
    (per-device, matching :func:`peak_flops`)."""
    return device_peak(device_kind).hbm_gbps * 1e9


def derive_step_stats(
    cost: Optional[Dict[str, float]],
    wall_s: float,
    device_kind: Optional[str] = None,
) -> Dict[str, float]:
    """The one MFU/HBM derivation (shared by the profiler and the engine's
    telemetry gauges): compiled-cost FLOPs and bytes
    over a measured step wall against the PER-CHIP peak.

    ``cost`` is the executable's ``cost_analysis()`` dict — the
    **per-device** flops/bytes of the GSPMD-partitioned module, which is
    why the denominator is one chip's peak.  NB the module-level scan
    caveat applies: a ``lax.scan`` body is counted ONCE — profile with
    the scan unrolled for truthful absolute numbers."""
    cost = cost or {}
    flops = float(cost.get("flops", 0.0))
    hbm = cost_bytes(cost)
    peak = peak_flops(device_kind)
    achieved = flops / wall_s if wall_s and wall_s > 0 else float("nan")
    return {
        "flops_per_step": flops,
        "hbm_bytes_per_step": hbm,
        "achieved_flops": achieved,
        "mfu": achieved / peak,
        "hbm_gbps": hbm / wall_s / 1e9 if wall_s and wall_s > 0 else float("nan"),
    }


def _fmt(n: float, unit: str = "") -> str:
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= scale:
            return f"{n / scale:.2f} {suffix}{unit}"
    return f"{n:.2f} {unit}"


def analyze_fn(fn: Callable, *args, static_argnums=()) -> Dict[str, float]:
    """AOT cost analysis of ``fn(*args)``: flops, HBM bytes accessed,
    peak-memory estimate — from XLA, post-fusion."""
    # out_shardings=None: AOT cost analysis only — nothing executes, so
    # no layout is imposed on real arrays
    lowered = jax.jit(fn, static_argnums=static_argnums, out_shardings=None).lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": cost_bytes(cost),
        "peak_memory_bytes": float(getattr(mem, "temp_size_in_bytes", 0) or 0)
        + float(getattr(mem, "argument_size_in_bytes", 0) or 0),
    }
    return out


def get_model_profile(
    model_fn: Callable,
    args: Tuple = (),
    kwargs: Optional[dict] = None,
    print_profile: bool = True,
    detailed: bool = True,
    warm_up: int = 1,
    as_string: bool = False,
    params: Any = None,
) -> Tuple[Any, Any, Any]:
    """Reference ``get_model_profile`` (:888): returns
    ``(flops, macs, params)`` for one forward call.  MACs are flops/2
    (XLA counts multiply and add separately)."""
    kwargs = kwargs or {}
    cost = analyze_fn(lambda *a: model_fn(*a, **kwargs), *args)
    flops = cost["flops"]
    macs = flops / 2.0
    n_params = _num_params(params) if params is not None else _num_params(args[0]) if args else 0
    if print_profile:
        logger.info(
            f"model profile: flops={_fmt(flops, 'FLOPs')} macs={_fmt(macs, 'MACs')} "
            f"params={_fmt(n_params)} bytes={_fmt(cost['bytes_accessed'], 'B')}"
        )
    if as_string:
        return _fmt(flops, "FLOPs"), _fmt(macs, "MACs"), _fmt(n_params)
    return flops, macs, n_params


class FlopsProfiler:
    """Engine-attached profiler (reference ``FlopsProfiler`` :11).

    Engine calls ``maybe_profile(step)`` each train_batch; at
    ``profile_step`` it runs cost analysis on the already-compiled step,
    times the next execution, and prints flops / throughput / MFU.
    """

    def __init__(self, config, engine=None):
        self.cfg = config
        self.engine = engine
        self._static: Optional[Dict[str, float]] = None
        self._t0: Optional[float] = None
        self.results: Dict[str, float] = {}

    @property
    def enabled(self) -> bool:
        return bool(getattr(self.cfg, "enabled", False))

    def start_step(self, step: int) -> None:
        if self.enabled and step == self.cfg.profile_step:
            self._t0 = time.perf_counter()

    def end_step(self, step: int, cost: Optional[Dict[str, float]] = None, sync_token=None) -> None:
        """Consume the compiled step's XLA cost analysis (captured by
        the engine at AOT-compile time — no recompile happens here):
        FLOPs *and* HBM bytes over the fenced latency, via the shared
        :func:`derive_step_stats` derivation.  Results land in
        ``self.results`` and, when the telemetry plane is armed, as
        ``profile/*`` registry gauges."""
        if not (self.enabled and step == self.cfg.profile_step):
            return
        if sync_token is not None:
            jax.block_until_ready(sync_token)
        elapsed = time.perf_counter() - self._t0 if self._t0 else float("nan")
        stats = derive_step_stats(cost, elapsed)
        self.results = {"step": step, "latency_s": elapsed, **stats}
        params = _num_params(self.engine.state["params"]) if self.engine is not None else 0
        self.results["params"] = params
        from deepspeed_tpu.telemetry import get_registry

        reg = get_registry()
        if reg.enabled:
            for key in ("flops_per_step", "hbm_bytes_per_step", "mfu", "hbm_gbps"):
                v = stats[key]
                if np.isfinite(v):
                    reg.gauge(f"profile/{key}").set(v)
        log_dist(
            f"flops profiler @ step {step}: params={_fmt(params)} "
            f"flops/step={_fmt(stats['flops_per_step'], 'FLOPs')} "
            f"hbm={_fmt(stats['hbm_bytes_per_step'], 'B')} "
            f"({stats['hbm_gbps']:.1f} GB/s) latency={elapsed * 1e3:.1f}ms "
            f"achieved={_fmt(stats['achieved_flops'], 'FLOPS')} "
            f"MFU={100 * stats['mfu']:.1f}%"
        )


def _live_bytes_by_device() -> Dict[int, int]:
    """Per-device live-buffer accounting from ``jax.live_arrays()`` —
    the real number on backends whose PJRT client exposes no
    ``memory_stats`` (XLA:CPU): sum of addressable shard bytes per
    device over every live Array."""
    out: Dict[int, int] = {}
    for a in jax.live_arrays():
        try:
            for s in a.addressable_shards:
                out[s.device.id] = out.get(s.device.id, 0) + int(s.data.nbytes)
        except Exception:  # noqa: BLE001 — deleted/donated arrays mid-walk
            continue
    return out


def see_memory_usage(message: str = "", force: bool = True) -> Dict[str, float]:
    """Reference ``see_memory_usage`` (runtime/utils.py:588): device +
    host memory snapshot.  Devices report PJRT ``memory_stats`` where
    the backend has them (TPU) and fall back to live-``jax.Array``
    shard accounting (CPU and any stats-less PJRT client) — real
    numbers on every platform, never silent zeros.  Host side prefers
    psutil and falls back to ``resource.getrusage`` peak RSS."""
    out: Dict[str, float] = {}
    live: Optional[Dict[int, int]] = None
    for d in jax.local_devices():
        stats = getattr(d, "memory_stats", lambda: None)()
        if stats:
            out[f"{d.id}/bytes_in_use"] = stats.get("bytes_in_use", 0)
            out[f"{d.id}/peak_bytes_in_use"] = stats.get("peak_bytes_in_use", 0)
        else:
            if live is None:
                live = _live_bytes_by_device()
            out[f"{d.id}/bytes_in_use"] = live.get(d.id, 0)
    try:
        import psutil

        vm = psutil.virtual_memory()
        out["host/used_gb"] = vm.used / 1e9
        out["host/percent"] = vm.percent
    except ImportError:
        try:
            import resource

            # ru_maxrss is KB on Linux — peak, not current, but honest
            out["host/peak_rss_gb"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        except Exception:  # pragma: no cover - non-posix
            pass
    if message or out:
        dev_in_use = sum(v for k, v in out.items() if k.endswith("/bytes_in_use"))
        host = (
            f"host={out['host/used_gb']:.1f}GB" if "host/used_gb" in out
            else f"host_peak_rss={out.get('host/peak_rss_gb', 0):.1f}GB"
            if "host/peak_rss_gb" in out else ""
        )
        logger.info(f"memory usage {message}: device={_fmt(dev_in_use, 'B')} " + host)
    return out
