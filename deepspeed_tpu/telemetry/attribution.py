"""Per-op cost attribution: which kernel family owns the step's cost.

PR 9 ended at whole-step gauges (``mfu``, ``hbm_bytes_per_step``); the
kernel arc needs to know *which* fusion is the bottleneck before any
Pallas kernel lands.  This module walks a compiled executable's
optimized HLO (``executable.as_text()``) instruction by instruction,
computes per-instruction FLOPs/HBM bytes analytically, and buckets them
into named kernel families:

* ``attention``        — flash/sparse/ring attention math (``ops/attention``)
* ``matmul``           — parameter matmuls (qkv/proj/ffn/lm-head dots + grads)
* ``optimizer-update`` — Adam/LAMB master-weight update (``ops/adam|lamb``)
* ``comm-collective``  — all-reduce/-gather/reduce-scatter/… + comm-layer math
* ``kv-dequant``       — (de)quantization traffic (``ops/quantizer``, runtime
  quantize) — the int8-KV decode round-trip the roadmap targets
* ``layernorm/other``  — layernorm, loss/xent, dropout, and the residual

The bucket table is **calibrated against the module's own
``cost_analysis()``**: the analytically-unattributed remainder lands in
``layernorm/other`` (recorded as ``unattributed_*``), so the table's
totals always match XLA's whole-module numbers — tests pin the sum to
within 1% and the ``matmul`` bucket to the analytic ``6N`` count.

Per bucket the roofline view reports arithmetic intensity (FLOPs/byte),
a compute- vs memory-bound verdict against the platform's machine
balance, the roofline-implied minimum time share, and %-of-peak — the
evidence format EQuARX (arXiv:2506.17615) and cross-replica sharding
(arXiv:2004.13336) used to prove their wins.

Publishing surfaces: registry gauges (``attribution/<bucket>/*``),
Perfetto counter tracks, ``ds_report`` rows and bench records.  The
roofline needs the device's published peaks
(``flops_profiler.DEVICE_PEAKS``); a device that has none gets no table.

This file also owns the ONE ``jax.profiler`` trace cost-walk shared by
``tools/profile_train_step.py`` / ``profile_bert_step.py`` /
``profile_decode.py`` (previously three ad-hoc copies).
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

BUCKETS = (
    "attention",
    "matmul",
    "optimizer-update",
    "comm-collective",
    "kv-dequant",
    "layernorm/other",
)
OTHER = "layernorm/other"

# opcodes whose cost is ~one flop per output element (cheap transcendentals
# deliberately counted as 1 — the residual calibration absorbs the model error)
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "negate", "maximum", "minimum",
    "abs", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "sqrt", "rsqrt", "cbrt", "power", "tanh", "logistic", "sine", "cosine",
    "atan2", "remainder", "compare", "select", "clamp", "sign", "floor",
    "ceil", "round-nearest-afz", "round-nearest-even", "and", "or", "xor",
    "not", "shift-left", "shift-right-logical", "shift-right-arithmetic",
}
# every other opcode (broadcast/copy/transpose/slice/gather/...) is data
# movement: zero flops by fall-through, but its bytes are still counted
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%(?P<name>[^\s=]+)\s+=\s+(?P<type>\([^)]*\)|\S+)\s+"
    r"(?P<opcode>[\w-]+)\((?P<rest>.*)$"
)
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\([^)]*\)\s*->")
_META_RE = re.compile(
    r'metadata=\{[^}]*?op_name="(?P<op>[^"]*)"'
    r'(?:[^}]*?source_file="(?P<src>[^"]*)")?'
)
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _shape_elems_bytes(type_str: str) -> Tuple[int, int]:
    """(total elements, total bytes) of an HLO type string; tuple types
    sum their members."""
    elems = 0
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES[dt]
    if elems == 0 and type_str.split("{")[0] in _DTYPE_BYTES:
        # scalar like "f32[]" is matched above; bare "f32" (rare) here
        elems, nbytes = 1, _DTYPE_BYTES[type_str.split("{")[0]]
    return elems, nbytes


def _dot_flops(out_type: str, rest: str) -> float:
    """2 · |out| · Π(contracted dims), from the dot's result type, its
    lhs operand shape and ``lhs_contracting_dims``."""
    out_elems, _ = _shape_elems_bytes(out_type)
    m = _CONTRACT_RE.search(rest)
    first_operand = _SHAPE_RE.search(rest)
    if m is None or first_operand is None:
        return 2.0 * out_elems  # degenerate; residual calibration absorbs it
    dims_txt = first_operand.group(2)
    lhs_dims = [int(d) for d in dims_txt.split(",")] if dims_txt else []
    contract = 1
    for idx in (int(i) for i in m.group(1).split(",") if i):
        if 0 <= idx < len(lhs_dims):
            contract *= lhs_dims[idx]
    return 2.0 * out_elems * contract


def classify(opcode: str, op_name: str, source_file: str) -> str:
    """Bucket one HLO instruction.  Priority: collective opcode > comm
    source > quantize source > attention > optimizer > matmul > other."""
    if opcode.startswith(_COLLECTIVES):
        return "comm-collective"
    src = source_file or ""
    op = op_name or ""
    if "/comm/" in src:
        return "comm-collective"
    # the Pallas kernel suite (ops/kernels, docs/kernels.md) wins over
    # the dequant match: with the fused flash-decode kernel armed, the
    # int8 scale math happens IN-KERNEL and is attention work — the
    # kv-dequant bucket exists to expose the un-fused round-trip
    if "ops/kernels/flash_decode" in src or "flash_decode" in op:
        return "attention"
    if "ops/kernels/fused_update" in src or "fused_update" in op:
        return "optimizer-update"
    if "quantiz" in src or "dequant" in op or "quantize" in op:
        return "kv-dequant"
    if "ops/attention" in src or "flash_attention" in op or "attention" in op:
        return "attention"
    if "ops/adam" in src or "ops/lamb" in src or "/optimizer" in src:
        return "optimizer-update"
    if opcode == "dot" or (
        opcode == "custom-call" and ("matmul" in op or "dot" in op)
    ):
        return "matmul"
    return OTHER


@dataclass
class BucketCost:
    flops: float = 0.0
    bytes: float = 0.0
    ops: int = 0


@dataclass
class Attribution:
    """Per-bucket cost table for ONE compiled executable, calibrated to
    its module-level ``cost_analysis()``."""

    label: str
    buckets: Dict[str, BucketCost]
    module_flops: float
    module_bytes: float
    unattributed_flops: float  # residual folded into layernorm/other
    unattributed_bytes: float
    backend: Optional[str] = None  # device_kind the roofline is priced for
    meta: Dict[str, Any] = field(default_factory=dict)

    # -- derived views ------------------------------------------------------
    def total_flops(self) -> float:
        return sum(b.flops for b in self.buckets.values())

    def total_bytes(self) -> float:
        return sum(b.bytes for b in self.buckets.values())

    def roofline(self, backend: Optional[str] = None,
                 wall_s: Optional[float] = None) -> List[Dict[str, Any]]:
        """Per-bucket roofline rows: arithmetic intensity, bound verdict
        vs the platform machine balance, the roofline-implied minimum
        time (the floor at peak hardware) and its share of the module
        floor.  With a measured ``wall_s``, each row also carries
        ``pct_peak`` — the bucket's binding-resource utilization under
        the time-share estimate ``t_bucket ≈ share × wall`` (an honest
        static estimate; the per-op *measured* %-of-peak comes from the
        jax.profiler trace walk on real hardware)."""
        from deepspeed_tpu.profiling.flops_profiler import (
            peak_flops,
            peak_hbm_bytes_per_s,
        )

        backend = backend or self.backend
        pk_f = peak_flops(backend)
        pk_b = peak_hbm_bytes_per_s(backend)
        balance = pk_f / pk_b  # flops/byte at the roofline ridge
        rows = []
        times = {
            name: max(b.flops / pk_f, b.bytes / pk_b)
            for name, b in self.buckets.items()
        }
        t_total = sum(times.values()) or 1.0
        for name in BUCKETS:
            b = self.buckets.get(name)
            if b is None or (b.flops == 0 and b.bytes == 0):
                continue
            ai = b.flops / b.bytes if b.bytes else float("inf")
            bound = "compute" if ai >= balance else "memory"
            t = times[name]
            row = {
                "bucket": name,
                "flops": b.flops,
                "bytes": b.bytes,
                "ops": b.ops,
                "ai": round(ai, 3),
                "bound": bound,
                "min_time_ms": round(t * 1e3, 6),
                "min_time_share_pct": round(100.0 * t / t_total, 2),
            }
            if wall_s and wall_s > 0:
                est_t = (t / t_total) * wall_s
                peak_rate = pk_f if bound == "compute" else pk_b
                used = b.flops if bound == "compute" else b.bytes
                row["pct_peak"] = round(100.0 * used / (est_t * peak_rate), 2)
            rows.append(row)
        rows.sort(key=lambda r: -r["min_time_share_pct"])
        return rows

    def verdict(self, bucket: str, backend: Optional[str] = None) -> Optional[str]:
        for row in self.roofline(backend):
            if row["bucket"] == bucket:
                return row["bound"]
        return None

    def top_buckets(self, n: int = 3, backend: Optional[str] = None) -> List[Tuple[str, float]]:
        return [(r["bucket"], r["min_time_share_pct"]) for r in self.roofline(backend)[:n]]

    # -- serialization ------------------------------------------------------
    def to_record(self, backend: Optional[str] = None) -> Dict[str, Any]:
        return {
            "label": self.label,
            "backend": backend or self.backend,
            "module_flops": self.module_flops,
            "module_bytes": self.module_bytes,
            "unattributed_flops": self.unattributed_flops,
            "unattributed_bytes": self.unattributed_bytes,
            "roofline": self.roofline(backend),
            **self.meta,
        }

    def format_table(self, backend: Optional[str] = None) -> str:
        lines = [
            f"attribution [{self.label}] module: "
            f"{self.module_flops / 1e9:.3f} GFLOPs, "
            f"{self.module_bytes / 1e6:.1f} MB accessed",
            f"{'bucket':18s} {'GFLOPs':>10s} {'MB':>9s} {'AI':>8s} "
            f"{'bound':>8s} {'floor-ms':>9s} {'t-share%':>8s}",
        ]
        for r in self.roofline(backend):
            lines.append(
                f"{r['bucket']:18s} {r['flops'] / 1e9:10.4f} {r['bytes'] / 1e6:9.2f} "
                f"{r['ai']:8.2f} {r['bound']:>8s} {r['min_time_ms']:9.4f} "
                f"{r['min_time_share_pct']:8.2f}"
            )
        return "\n".join(lines)

    # -- publishing ---------------------------------------------------------
    def publish(self, manager) -> None:
        """Registry gauges + Perfetto counter tracks through a
        :class:`~deepspeed_tpu.telemetry.TelemetryManager` (one-shot at
        compile time — nothing here runs on the hot path)."""
        rows = self.roofline()
        if manager.registry.enabled:
            present = set()
            for r in rows:
                present.add(r["bucket"])
                g = lambda name: manager.gauge(name, bucket=r["bucket"])  # noqa: E731
                g("attribution/flops").set(r["flops"])
                g("attribution/bytes").set(r["bytes"])
                g("attribution/time_share_pct").set(r["min_time_share_pct"])
            # a recompile that drops a bucket must not leave its old
            # gauges reporting forever (same rule as the straggler
            # gauges): zero EXISTING handles for buckets absent from the
            # new table (never create handles just to zero them)
            for m in manager.registry.metrics():
                if (
                    m.kind == "gauge"
                    and m.name.startswith("attribution/")
                    and m.labels.get("engine") == manager.label
                    and m.labels.get("bucket") not in present
                    and m.value
                ):
                    m.set(0.0)
        tracer = getattr(manager, "tracer", None)
        if tracer is not None and tracer.enabled and rows:
            # ONE "C" sample carrying the whole series — Perfetto stacks
            # the args keys into per-bucket tracks on one timestamp
            tracer.add_counter(
                f"attribution/{self.label}/time_share_pct",
                {r["bucket"]: r["min_time_share_pct"] for r in rows},
            )


# ---------------------------------------------------------------------------
# the HLO walk
# ---------------------------------------------------------------------------

def attribute_hlo_text(
    hlo_text: str,
    module_cost: Optional[Dict[str, float]] = None,
    label: str = "module",
    backend: Optional[str] = None,
) -> Attribution:
    """Walk optimized HLO text into a calibrated bucket table.

    FLOPs are computed analytically per instruction (dots:
    ``2·|out|·Πcontracted``; elementwise: one per output element; reduce:
    one per input element) and bytes per *top-level* instruction
    (operands + result — fusion bodies are internal traffic and free).
    The module-level ``cost_analysis()`` numbers are authoritative: the
    unattributed remainder is folded into ``layernorm/other`` so bucket
    totals sum to the module cost exactly; an analytic *over*-count is
    scaled back proportionally (both recorded)."""
    buckets: Dict[str, BucketCost] = {b: BucketCost() for b in BUCKETS}

    # pass 1: find fusion-body computations (their instructions carry
    # flops attribution but NOT byte traffic)
    fused = set(_CALLS_RE.findall(hlo_text))

    current: Optional[str] = None
    for line in hlo_text.splitlines():
        if not line:
            continue
        if not line.startswith(" "):
            m = _COMP_RE.match(line)
            if m is not None:
                current = m.group("name")
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        opcode = m.group("opcode")
        if opcode in ("parameter", "constant", "tuple", "get-tuple-element"):
            continue
        out_type = m.group("type")
        rest = m.group("rest")
        meta = _META_RE.search(rest)
        op_name = meta.group("op") if meta else ""
        source = (meta.group("src") or "") if meta else ""
        bucket = classify(opcode, op_name, source)
        bc = buckets[bucket]
        bc.ops += 1

        out_elems, out_bytes = _shape_elems_bytes(out_type)
        # flops — attributed wherever the instruction lives
        if opcode == "dot":
            bc.flops += _dot_flops(out_type, rest)
        elif opcode in _ELEMENTWISE:
            bc.flops += out_elems
        elif opcode in ("reduce", "reduce-window"):
            operand = _SHAPE_RE.search(rest)
            if operand is not None:
                n = 1
                for d in (operand.group(2).split(",") if operand.group(2) else []):
                    n *= int(d)
                bc.flops += n
        elif opcode == "convolution":
            bc.flops += 2.0 * out_elems  # lower bound; residual calibrates

        # bytes — only top-level (non-fusion-body) instructions touch
        # HBM; bitcasts are layout bookkeeping, not traffic
        if current in fused or opcode == "bitcast":
            continue
        operand_bytes = 0
        # strip trailing metadata/attrs before scanning operand types so
        # attribute payloads (e.g. replica_groups) don't count as shapes
        arg_section = rest.split("), ")[0] if "), " in rest else rest
        arg_section = arg_section.split(", metadata=")[0]
        for dt, dims in _SHAPE_RE.findall(arg_section):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in (dims.split(",") if dims else []):
                n *= int(d)
            operand_bytes += n * _DTYPE_BYTES[dt]
        bc.bytes += out_bytes + operand_bytes

    module_cost = module_cost or {}
    module_flops = float(module_cost.get("flops", 0.0) or 0.0)
    from deepspeed_tpu.profiling.flops_profiler import cost_bytes

    module_bytes = float(cost_bytes(module_cost))

    unattr_flops = _calibrate(buckets, "flops", module_flops)
    unattr_bytes = _calibrate(buckets, "bytes", module_bytes)
    return Attribution(
        label=label,
        buckets=buckets,
        module_flops=module_flops or sum(b.flops for b in buckets.values()),
        module_bytes=module_bytes or sum(b.bytes for b in buckets.values()),
        unattributed_flops=unattr_flops,
        unattributed_bytes=unattr_bytes,
        backend=backend,
    )


def _calibrate(buckets: Dict[str, BucketCost], attr: str, module_total: float) -> float:
    """Fold the unattributed remainder into ``layernorm/other`` (or
    scale an overcount back) so ``sum(buckets) == module_total``.
    Returns the signed residual that was applied."""
    if module_total <= 0:
        return 0.0
    attributed = sum(getattr(b, attr) for b in buckets.values())
    residual = module_total - attributed
    other = buckets[OTHER]
    if residual >= 0:
        setattr(other, attr, getattr(other, attr) + residual)
        return residual
    # overcount: shrink `other` first, then scale every bucket
    take = min(getattr(other, attr), -residual)
    setattr(other, attr, getattr(other, attr) - take)
    remaining = sum(getattr(b, attr) for b in buckets.values())
    if remaining > 0 and remaining > module_total:
        scale = module_total / remaining
        for b in buckets.values():
            setattr(b, attr, getattr(b, attr) * scale)
    return residual


def _module_cost(compiled) -> Dict[str, float]:
    try:
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        import numpy as np

        return {k: float(v) for k, v in cost.items() if np.isscalar(v)}
    except Exception:  # noqa: BLE001 — attribution is best-effort evidence
        return {}


def attribute_executable(
    compiled,
    label: str = "module",
    backend: Optional[str] = None,
    module_cost: Optional[Dict[str, float]] = None,
    max_hlo_mb: float = 256.0,
) -> Optional[Attribution]:
    """Attribute one compiled executable (``jit(...).lower().compile()``
    result, or the engine's cached train-step executable).  Returns None
    when the HLO text is unavailable or over the size cap (a fully
    unrolled XL module can reach hundreds of MB of text; the cap keeps
    compile-time hooks bounded)."""
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001 — some backends ship no text
        return None
    if not text or len(text) > max_hlo_mb * 1e6:
        return None
    if backend is None:
        import jax

        backend = jax.devices()[0].device_kind
    return attribute_hlo_text(
        text, module_cost=module_cost or _module_cost(compiled),
        label=label, backend=backend,
    )


def attribute_jit(fn, *args, label: str = "fn", static_argnums=(),
                  backend: Optional[str] = None) -> Optional[Attribution]:
    """AOT lower+compile ``fn(*args)`` and attribute it (tools/tests;
    no execution happens)."""
    import jax

    # AOT analysis only (never executed): layout is irrelevant, the walk
    # reads whatever GSPMD produced
    compiled = jax.jit(fn, static_argnums=static_argnums).lower(*args).compile()  # ds-lint: disable=bare-jit
    return attribute_executable(compiled, label=label, backend=backend)


# ---------------------------------------------------------------------------
# analytic pins (the 6N check bench.py and the tests share)
# ---------------------------------------------------------------------------

def analytic_matmul_flops(n_params: int, tokens: int, n_devices: int = 1) -> float:
    """The ``6N`` analytic training count for the parameter matmuls
    (fwd 2N + bwd 4N per token), per device — what the ``matmul`` bucket
    of a full train step should show (attention-score math lives in the
    ``attention`` bucket and is excluded here, unlike bench.py's
    whole-step ``6N + 12·L·D·s`` MFU count)."""
    return 6.0 * float(n_params) * float(tokens) / max(1, int(n_devices))


# ---------------------------------------------------------------------------
# the shared jax.profiler trace cost-walk (tools/profile_*.py)
# ---------------------------------------------------------------------------

_SKIP_CATEGORIES = ("while", "conditional", "call")


def load_profiler_trace(trace_dir: str) -> List[Dict[str, Any]]:
    """Newest ``*.trace.json.gz`` under a ``jax.profiler.trace`` output
    dir → the device-op events (complete spans with an
    ``hlo_category``), control-flow wrappers dropped."""
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz"))
    )
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    with gzip.open(paths[-1]) as fh:
        data = json.load(fh)
    out = []
    for e in data.get("traceEvents", ()):
        if e.get("ph") != "X" or not e.get("args"):
            continue
        cat = e["args"].get("hlo_category")
        if not cat or cat in _SKIP_CATEGORIES:
            continue
        out.append(e)
    return out


def trace_tables(events: Iterable[Dict[str, Any]], denom: float = 1.0) -> Dict[str, Any]:
    """The per-source / per-HLO-category / top-op device-time tables the
    three profile tools all print.  ``denom`` divides durations (steps
    for a train profile, tokens for decode); TFLOP/s uses the trace's
    own ``model_flops``."""
    src_t: collections.Counter = collections.Counter()
    src_f: collections.Counter = collections.Counter()
    cat_t: collections.Counter = collections.Counter()
    cat_f: collections.Counter = collections.Counter()
    op_t: collections.Counter = collections.Counter()
    total = 0.0
    for e in events:
        dur = e.get("dur", 0)
        flops = int(e["args"].get("model_flops", 0) or 0)
        src = e["args"].get("source", "?")
        cat = e["args"]["hlo_category"]
        src_t[src] += dur
        src_f[src] += flops
        cat_t[cat] += dur
        cat_f[cat] += flops
        op_t[e.get("name", "?")[:70]] += dur
        total += dur

    def rows(t: collections.Counter, f: Optional[collections.Counter], n: int):
        out = []
        for key, dur in t.most_common(n):
            row = {"name": key, "ms": dur / 1e3 / denom}
            if f is not None:
                row["tflops"] = f[key] / (dur * 1e-6) / 1e12 if dur else 0.0
            out.append(row)
        return out

    return {
        "total_ms": total / 1e3 / denom,
        "by_source": rows(src_t, src_f, 20),
        "by_category": rows(cat_t, cat_f, 12),
        "top_ops": rows(op_t, None, 15),
    }


def format_trace_tables(tables: Dict[str, Any], unit: str = "step") -> str:
    lines = [f"total device time: {tables['total_ms']:.2f} ms/{unit}"]
    lines.append(f"\n{'source':68s} {'ms/' + unit:>9s} {'TFLOP/s':>8s}")
    for r in tables["by_source"]:
        lines.append(f"{r['name'][-68:]:68s} {r['ms']:9.2f} {r['tflops']:8.1f}")
    lines.append(f"\n{'hlo category':30s} {'ms/' + unit:>9s} {'TFLOP/s':>8s}")
    for r in tables["by_category"]:
        lines.append(f"{r['name']:30s} {r['ms']:9.2f} {r['tflops']:8.1f}")
    lines.append(f"\n{'top ops':70s} {'ms/' + unit:>9s}")
    for r in tables["top_ops"]:
        lines.append(f"{r['name']:70s} {r['ms']:9.2f}")
    return "\n".join(lines)


def profile_and_report(engine_step, trace_dir: Optional[str] = None,
                       steps: int = 3, unit: str = "step",
                       denom: Optional[float] = None,
                       sync=None) -> Dict[str, Any]:
    """Run ``engine_step()`` ``steps`` times under ``jax.profiler.trace``
    and return the cost tables (the whole body the three profile tools
    used to duplicate).  ``sync`` (e.g. ``lambda: float(loss)``) runs
    once INSIDE the trace window so async dispatch is fully captured;
    ``denom`` overrides the per-unit divisor (tokens for decode)."""
    import tempfile

    import jax

    trace_dir = trace_dir or tempfile.mkdtemp(prefix="ds_attr_trace_")
    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            engine_step()
        if sync is not None:
            sync()
    tables = trace_tables(load_profiler_trace(trace_dir),
                          denom=denom if denom is not None else steps)
    tables["trace_dir"] = trace_dir
    return tables
