"""ds_shard Pass 2 — compiled-collective audit (post-compile).

Walks an AOT-compiled executable's optimized HLO (utils/hlo.py's
instruction regex) and classifies every collective as
*budgeted* or *unbudgeted* against the PR 6/PR 8 comm model:

* each instruction's replica groups are mapped back to mesh axes (both
  explicit ``{{0,1},{2,3}}`` and iota ``[G,N]<=[dims]`` group formats)
  and to the DCN seam via the granule split
  (:func:`deepspeed_tpu.sharding.mesh._granules` — the same contiguous
  blocks ``DS_DCN_SLICES`` simulates);
* payloads below the control floor (loss scalars, overflow flags,
  grad-norm psums) are budgeted as control plane;
* remaining traffic is charged to a per-opcode ledger funded by the
  site's byte-model rows (``step_comm_bytes``: all-gather /
  reduce-scatter / all-reduce / grad-exchange) with the documented
  tolerance ``actual <= budget * (1 + rel) + abs``; ring-weighted
  bytes use :data:`deepspeed_tpu.utils.hlo.COLLECTIVE_WEIGHTS`
  (all-reduce counts 2x its payload) so actuals and model speak the
  same unit;
* instructions that do not fit the ledger are tier A
  ``unbudgeted-collective`` findings naming the inferred
  producer/consumer specs;
* any DCN-crossing collective is additionally held to the PR 8 policy
  floor: uncompressed (>= 2-byte element) payloads at/above
  ``dcn_floor`` are **always** tier A ``unbudgeted-dcn-collective``,
  budgeted or not — the policy table requires a compressed strategy on
  that link.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deepspeed_tpu.analysis.core import Finding
from deepspeed_tpu.analysis.shard.rules import (
    SiteContext,
    make_shard_finding,
)
from deepspeed_tpu.utils.hlo import COLLECTIVE_WEIGHTS, DTYPE_BYTES, INSTR_RE, SHAPE_RE, shape_bytes

_COLLECTIVES = (*COLLECTIVE_WEIGHTS, "collective-broadcast")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')

# budget-matching tolerance: actual <= budget * (1 + REL) + ABS.
# REL covers GSPMD's extra partial-sum reductions riding the same link
# (measured 1.18x on the dryrun train step); ABS absorbs per-step
# scalar chatter that never graduates past a few control payloads.
DEFAULT_TOLERANCE_REL = 0.30
DEFAULT_TOLERANCE_ABS = 64 * 1024
# payloads at/below this are control plane (loss means, grad norms,
# overflow flags) — always budgeted, never worth a policy row
DEFAULT_CONTROL_FLOOR = 4 * 1024
# DCN policy floor: uncompressed payloads at/above this on a
# DCN-crossing group are tier A regardless of ledger room (PR 8's
# dcn_threshold_bytes default)
DEFAULT_DCN_FLOOR = 1 * 1024 * 1024

# ring-weighted byte accounting, same convention as
# utils/hlo.collective_bytes_by_op: all-reduce moves ~2x its payload
_OP_WEIGHT = {"all-reduce": 2.0}

_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|\[[^\]]*\]<=\[[^\]]*\](?:T\([\d,]*\))?)")
_SRC_LINE_RE = re.compile(r'source_line=(\d+)')
_SRC_FILE_RE = re.compile(r'source_file="([^"]*)"')
_DIM_RE = re.compile(r"dimensions=\{(\d+)\}")


@dataclass
class CollectiveInstr:
    """One parsed collective instruction."""

    name: str
    opcode: str
    payload_bytes: int
    dtype_bytes: int
    groups: List[List[int]] = field(default_factory=list)
    op_name: str = ""
    source_file: Optional[str] = None
    source_line: int = 1
    operand_shapes: List[Tuple[int, ...]] = field(default_factory=list)
    result_shape: Tuple[int, ...] = ()
    raw: str = ""

    @property
    def weighted_bytes(self) -> float:
        return self.payload_bytes * _OP_WEIGHT.get(self.opcode, 1.0)


def _parse_groups(raw: str) -> List[List[int]]:
    """Both replica-group encodings XLA emits: explicit
    ``{{0,1},{2,3}}`` lists and iota ``[G,N]<=[d0,d1,...]T(perm)``."""
    raw = raw.strip()
    if raw.startswith("{{"):
        return [
            [int(x) for x in grp.split(",") if x.strip()]
            for grp in re.findall(r"\{([\d,\s]*)\}", raw[1:-1])
        ]
    m = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", raw)
    if not m:
        return []
    out_dims = [int(x) for x in m.group(1).split(",")]
    src_dims = [int(x) for x in m.group(2).split(",")]
    import numpy as np

    ids = np.arange(int(np.prod(src_dims))).reshape(src_dims)
    if m.group(3):
        ids = ids.transpose([int(x) for x in m.group(3).split(",")])
    ids = ids.reshape(out_dims)
    if ids.ndim == 1:
        ids = ids.reshape(1, -1)
    return [list(map(int, row)) for row in ids]


def _result_shapes(type_str: str) -> List[Tuple[int, ...]]:
    shapes = []
    for _dt, dims in SHAPE_RE.findall(type_str):
        shapes.append(tuple(int(d) for d in dims.split(",") if d))
    return shapes


def parse_collectives(hlo_text: str) -> List[CollectiveInstr]:
    out: List[CollectiveInstr] = []
    for line in hlo_text.splitlines():
        m = INSTR_RE.match(line)
        if not m or m.group("opcode") not in _COLLECTIVES:
            continue
        if "-start" in m.group("opcode") or "-done" in m.group("opcode"):
            continue
        nbytes = shape_bytes(m.group("type"))
        dtype_bytes = 4
        dt = re.match(r"\(?\s*(\w+)\[", m.group("type"))
        if dt:
            dtype_bytes = DTYPE_BYTES.get(dt.group(1), 4)
        gm = _GROUPS_RE.search(line)
        meta = _OP_NAME_RE.search(line)
        fm = _SRC_FILE_RE.search(line)
        lm = _SRC_LINE_RE.search(line)
        rest = m.group("rest")
        operand_shapes = _result_shapes(rest.split("metadata=")[0])
        shapes = _result_shapes(m.group("type"))
        out.append(CollectiveInstr(
            name=m.group("name"),
            opcode=m.group("opcode"),
            payload_bytes=nbytes,
            dtype_bytes=dtype_bytes,
            groups=_parse_groups(gm.group(1)) if gm else [],
            op_name=meta.group(1) if meta else "",
            source_file=fm.group(1) if fm else None,
            source_line=int(lm.group(1)) if lm else 1,
            operand_shapes=operand_shapes,
            result_shape=shapes[0] if shapes else (),
            raw=line.strip(),
        ))
    return out


# ---------------------------------------------------------------------------
# group -> mesh axes / DCN seam
# ---------------------------------------------------------------------------

def group_axes(mesh, groups: Sequence[Sequence[int]]) -> Tuple[str, ...]:
    """Which mesh axes a collective's groups span: partition ids map to
    mesh coordinates row-major over ``mesh.devices`` (GSPMD numbers
    partitions in mesh device order); an axis is spanned when its
    coordinate varies within any group."""
    import numpy as np

    if mesh is None or not groups:
        return ()
    shape = mesh.devices.shape
    spanned = set()
    n = int(np.prod(shape))
    for grp in groups:
        coords = [np.unravel_index(p, shape) for p in grp if p < n]
        if len(coords) < 2:
            continue
        for d, axis in enumerate(mesh.axis_names):
            if len({c[d] for c in coords}) > 1:
                spanned.add(axis)
    return tuple(a for a in mesh.axis_names if a in spanned)


def crosses_dcn(mesh, groups: Sequence[Sequence[int]]) -> bool:
    """True when any replica group spans more than one DCN granule
    (the contiguous device blocks ``_granules`` defines — real slices
    on multi-slice topologies, simulated ones under DS_DCN_SLICES)."""
    from deepspeed_tpu.sharding.mesh import _granules

    if mesh is None or not groups:
        return False
    flat = list(mesh.devices.flat)
    granules = _granules(flat)
    if granules is None or len(granules) <= 1:
        return False
    granule_of = {}
    for gi, devs in enumerate(granules):
        for d in devs:
            granule_of[id(d)] = gi
    for grp in groups:
        gids = {granule_of.get(id(flat[p])) for p in grp if p < len(flat)}
        if len(gids - {None}) > 1:
            return True
    return False


def _describe_specs(instr: CollectiveInstr, axes: Tuple[str, ...]) -> str:
    """Name the producer/consumer layouts a reshard mediates, inferred
    from the per-device operand/result shapes: the dim that grows by
    the group size is the gathered one (producer sharded over ``axes``
    there, consumer replicated); shrink is the scatter direction."""
    grp = len(instr.groups[0]) if instr.groups else 0
    ax = "/".join(axes) or "?"
    opnd = instr.operand_shapes[0] if instr.operand_shapes else ()
    res = instr.result_shape
    if instr.opcode == "all-gather" and opnd and res and len(opnd) == len(res):
        for d, (a, b) in enumerate(zip(opnd, res)):
            if a != b and a and b % a == 0:
                return (f"producer=P(dim{d}:{ax!r}) {opnd} -> "
                        f"consumer=replicated {res}")
    if instr.opcode == "reduce-scatter" and opnd and res and len(opnd) == len(res):
        for d, (a, b) in enumerate(zip(opnd, res)):
            if a != b and b and a % b == 0:
                return (f"producer=replicated(partial) {opnd} -> "
                        f"consumer=P(dim{d}:{ax!r}) {res}")
    if instr.opcode == "all-reduce":
        return (f"producer=partial-sum over {ax!r} {opnd or res} -> "
                f"consumer=replicated {res}")
    if instr.opcode == "all-to-all":
        return f"producer/consumer resharded across {ax!r} (groups of {grp})"
    return f"producer/consumer specs differ across {ax!r} (groups of {grp})"


# which byte-model rows fund which opcode's ledger
_LEDGER_ROWS = {
    "all-gather": ("all-gather", "weight-update-all-gather"),
    "reduce-scatter": ("reduce-scatter",),
    "all-reduce": ("all-reduce", "grad-exchange"),
    "all-to-all": ("all-to-all", "grad-exchange"),
    "collective-broadcast": ("all-gather",),
}
# decision-record sites that arm an opcode without a byte row (bytes
# are data-dependent at the site, e.g. the pipe micro-batch handoff)
_DECISION_OPCODES = {
    "collective-permute": ("pipe-p2p", "kv-handoff"),
}


def audit_hlo(ctx: SiteContext,
              tolerance_rel: float = DEFAULT_TOLERANCE_REL,
              tolerance_abs: int = DEFAULT_TOLERANCE_ABS,
              control_floor: int = DEFAULT_CONTROL_FLOOR,
              dcn_floor: Optional[int] = None) -> List[Finding]:
    """Classify every collective in the site's optimized HLO."""
    text = ctx.hlo_text()
    if not text:
        return []
    if dcn_floor is None:
        dcn_floor = int(ctx.budget.get("dcn-threshold-bytes", 0) or DEFAULT_DCN_FLOOR)
    instrs = parse_collectives(text)
    findings: List[Finding] = []
    opath, oline = ctx.origin

    def anchor(instr: CollectiveInstr) -> Tuple[str, int]:
        if instr.source_file:
            return instr.source_file, instr.source_line
        return opath, oline

    # fund the per-opcode ledgers from the byte model (ring-weighted
    # units on both sides)
    ledger: Dict[str, float] = {}
    for opcode, rows in _LEDGER_ROWS.items():
        ledger[opcode] = float(sum(int(ctx.budget.get(r, 0) or 0) for r in rows))
    strategy = str(ctx.budget.get("strategy", "dense"))

    # DCN policy first: an uncompressed dense payload at/above the
    # floor on a DCN-crossing group is tier A no matter the ledger
    dcn_flagged = set()
    for instr in instrs:
        if not crosses_dcn(ctx.mesh, instr.groups):
            continue
        if instr.payload_bytes >= dcn_floor and instr.dtype_bytes >= 2:
            axes = group_axes(ctx.mesh, instr.groups)
            p, ln = anchor(instr)
            findings.append(make_shard_finding(
                "unbudgeted-dcn-collective", p, ln,
                f"[{ctx.site}] {instr.opcode} {instr.name!r} moves "
                f"{instr.payload_bytes / 2**20:.2f} MiB of "
                f"{instr.dtype_bytes}-byte elements across the DCN seam "
                f"(axes {axes or ('?',)}, strategy={strategy}) — the "
                f"policy floor ({dcn_floor} B) requires a compressed "
                f"strategy on this link; {_describe_specs(instr, axes)}"))
            dcn_flagged.add(instr.name)

    # control plane + ledger for the rest, largest payloads first so a
    # blowup is what overflows the cap, not the legitimate tail behind it
    charged = [i for i in instrs if i.name not in dcn_flagged]
    charged.sort(key=lambda i: -i.weighted_bytes)
    spent: Dict[str, float] = {}
    over: Dict[str, List[CollectiveInstr]] = {}
    for instr in charged:
        if instr.payload_bytes <= control_floor:
            continue  # control plane: budgeted by definition
        if instr.opcode in _DECISION_OPCODES:
            sites = _DECISION_OPCODES[instr.opcode]
            if any(s in ctx.decisions for s in sites):
                continue  # a decision record priced this path
            over.setdefault(instr.opcode, []).append(instr)
            continue
        cap = ledger.get(instr.opcode, 0.0) * (1.0 + tolerance_rel) + tolerance_abs
        used = spent.get(instr.opcode, 0.0)
        if used + instr.weighted_bytes <= cap:
            spent[instr.opcode] = used + instr.weighted_bytes
            continue
        over.setdefault(instr.opcode, []).append(instr)

    for opcode, bad in over.items():
        for instr in bad:
            axes = group_axes(ctx.mesh, instr.groups)
            budget = sum(int(ctx.budget.get(r, 0) or 0)
                         for r in _LEDGER_ROWS.get(opcode, ()))
            p, ln = anchor(instr)
            findings.append(make_shard_finding(
                "unbudgeted-collective", p, ln,
                f"[{ctx.site}] {opcode} {instr.name!r} moves "
                f"{instr.weighted_bytes / 2**20:.2f} MiB (ring-weighted) "
                f"over axes {axes or ('?',)} but the byte model budgets "
                f"{budget} B for {opcode} here (strategy={strategy}) — "
                f"GSPMD inserted a reshard nobody priced; "
                f"{_describe_specs(instr, axes)}"))
    return findings
