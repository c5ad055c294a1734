"""ds_shard Pass 1 — partition-spec dataflow (pre-compile).

Four checks, all over abstract shapes (eval_shape trees / jaxprs —
nothing executes):

* rule-table hygiene: dead and shadowed regex rows per model family,
  decided against the family's *model corpus* (every param tree the
  family's builders can produce, eval-shaped);
* leaf resolution: every param/state/KV leaf of a compile site must
  resolve through PartitionRules into a spec the site's mesh can
  realize (tier A otherwise), and the live sharding must agree with
  the resolved base spec (tier A on conflict);
* donation layout: each donated input leaf must match the declared
  output sharding at the same tree position (tier A — XLA demotes the
  alias to a copy silently);
* replicated blowup: jaxpr walk flagging unconstrained intermediates
  above ``hbm_fraction`` of per-device HBM, attributed to the op's
  source line.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deepspeed_tpu.analysis.core import Finding
from deepspeed_tpu.analysis.shard.rules import (
    LeafSpec,
    SiteContext,
    make_shard_finding,
    mesh_axis_sizes,
    spec_dim_axes,
)

# Default HBM capacity the replicated-blowup threshold is a fraction
# of.  v4/v5 chips carry 16-32 GiB; override with DS_SHARD_HBM_BYTES.
DEFAULT_HBM_BYTES = 16 * 1024 ** 3
DEFAULT_HBM_FRACTION = 0.05


# ---------------------------------------------------------------------------
# rule-table hygiene: dead / shadowed rows
# ---------------------------------------------------------------------------

def _rules_source_location(pattern: str) -> Tuple[str, int]:
    """Best-effort source attribution for a family-table row: the line
    in sharding/rules.py whose text contains the regex literal (the
    tables are built from literals in that file)."""
    from deepspeed_tpu.sharding import rules as rules_mod

    path = rules_mod.__file__
    needle = pattern.replace("\\", "\\\\")
    try:
        with open(path) as f:
            for i, line in enumerate(f, start=1):
                if pattern in line or needle in line:
                    return path, i
    except OSError:
        pass
    return path, 1


def audit_rule_table(family: str, rules, corpus: Dict[str, Sequence[str]]) -> List[Finding]:
    """Dead/shadowed detection for one family table.

    ``corpus`` maps a corpus label (e.g. ``gpt2-tiny``) to the leaf
    paths of one model tree the family supports.  A row is *dead* when
    no corpus path matches its regex at all, *shadowed* when paths
    match it but an earlier row wins first-match on every one of them.
    Exact-duplicate patterns are shadowed even with an empty corpus.
    """
    findings: List[Finding] = []
    table = getattr(rules, "rules", ())
    if not table:
        return findings
    all_paths = sorted({p for paths in corpus.values() for p in paths})
    seen_patterns: Dict[str, int] = {}
    for i, (rx, _spec) in enumerate(table):
        first_hits = []
        any_hits = []
        for p in all_paths:
            if rx.search(p) is None:
                continue
            any_hits.append(p)
            winner = next(j for j, (rj, _s) in enumerate(table) if rj.search(p) is not None)
            if winner == i:
                first_hits.append(p)
        path, line = _rules_source_location(rx.pattern)
        dup_of = seen_patterns.get(rx.pattern)
        if dup_of is not None:
            findings.append(make_shard_finding(
                "shadowed-rule-row", path, line,
                f"family {family!r} row {i} ({rx.pattern!r}) duplicates "
                f"row {dup_of}; first-match-wins makes it unreachable"))
        elif all_paths and not any_hits:
            findings.append(make_shard_finding(
                "dead-rule-row", path, line,
                f"family {family!r} row {i} ({rx.pattern!r}) matches no "
                f"leaf in corpus {sorted(corpus)} — remove it or extend "
                f"the corpus"))
        elif any_hits and not first_hits:
            winners = sorted({
                next(j for j, (rj, _s) in enumerate(table) if rj.search(p) is not None)
                for p in any_hits
            })
            findings.append(make_shard_finding(
                "shadowed-rule-row", path, line,
                f"family {family!r} row {i} ({rx.pattern!r}) never wins "
                f"first-match: row(s) {winners} shadow it on "
                f"{len(any_hits)} matching leaf/leaves (e.g. {any_hits[0]!r})"))
        seen_patterns.setdefault(rx.pattern, i)
    return findings


def _leaf_paths(tree: Any) -> List[str]:
    import jax

    from deepspeed_tpu.sharding.rules import _path_str

    paths: List[str] = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        paths.append(_path_str(kp))
    return paths


def family_corpora() -> Dict[str, Dict[str, List[str]]]:
    """{family: {corpus label: leaf paths}} — one eval-shaped model
    tree per supported layout variant, so row liveness is decided
    against real trees, not guesses.  gpt2 hosts both the dense and
    the MoE block layout; neo shares gpt2's dense schema (GPT-Neo has
    no MoE variant); moe is the MoE layout alone; bert is bert."""
    import dataclasses

    import jax

    from deepspeed_tpu.models import bert, deepseek_v2, gpt2

    tiny = dataclasses.replace(gpt2.GPT2_TINY)
    tiny_moe = dataclasses.replace(gpt2.GPT2_TINY, n_experts=4)
    bert_tiny = bert.BERT_TINY

    def shaped(init_fn, *args):
        return _leaf_paths(jax.eval_shape(init_fn, *args))

    gpt2_dense = shaped(lambda: gpt2.init_params(tiny))
    gpt2_moe = shaped(lambda: gpt2.init_params(tiny_moe))
    bert_tree = shaped(lambda: bert.init_params(bert_tiny))
    return {
        "gpt2": {"gpt2-tiny": gpt2_dense, "gpt2-tiny-moe": gpt2_moe},
        "neo": {"gpt-neo (gpt2 dense schema)": gpt2_dense},
        "moe": {"gpt2-tiny-moe": gpt2_moe},
        "bert": {"bert-tiny": bert_tree},
        "deepseek_v2": {"deepseek-v2-tiny": _leaf_paths(jax.tree.map(
            lambda shape: 0, deepseek_v2.param_shapes(deepseek_v2.DEEPSEEK_V2_TINY),
            is_leaf=lambda s: isinstance(s, tuple)))},
    }


def audit_builtin_tables() -> List[Finding]:
    """Dead/shadowed audit over every registered family table."""
    from deepspeed_tpu.sharding.rules import _FAMILIES, rules_for_family

    corpora = family_corpora()
    findings: List[Finding] = []
    for family in sorted(_FAMILIES):
        findings.extend(audit_rule_table(
            family, rules_for_family(family), corpora.get(family, {})))
    return findings


# ---------------------------------------------------------------------------
# leaf resolution + conflicts
# ---------------------------------------------------------------------------

def _resolve(rules, leaf: LeafSpec):
    """(spec, error) — rule resolution with failures captured."""
    try:
        spec = rules.spec(leaf.path, leaf.shape) if rules is not None else None
    except Exception as e:  # noqa: BLE001 — a raising table IS the finding
        return None, f"resolution raised {type(e).__name__}: {e}"
    return spec, None


def audit_leaves(ctx: SiteContext) -> List[Finding]:
    findings: List[Finding] = []
    sizes = mesh_axis_sizes(ctx.mesh)
    opath, oline = ctx.origin
    for leaf in ctx.leaves:
        spec, err = _resolve(ctx.rules, leaf)
        if err is not None:
            findings.append(make_shard_finding(
                "unresolved-partition-spec", opath, oline,
                f"[{ctx.site}] {leaf.path}: {err}"))
            continue
        dims = tuple(spec) if spec is not None else ()
        if len(dims) > len(leaf.shape):
            findings.append(make_shard_finding(
                "unresolved-partition-spec", opath, oline,
                f"[{ctx.site}] {leaf.path}: spec {spec} has {len(dims)} "
                f"dims but the leaf has rank {len(leaf.shape)} "
                f"(shape {leaf.shape})"))
            continue
        bad = False
        for d, entry in enumerate(dims):
            for axis in spec_dim_axes(entry):
                size = sizes.get(axis)
                if size is None and sizes:
                    findings.append(make_shard_finding(
                        "unresolved-partition-spec", opath, oline,
                        f"[{ctx.site}] {leaf.path}: spec {spec} names "
                        f"axis {axis!r} but the mesh has "
                        f"{sorted(sizes)}"))
                    bad = True
                elif size and leaf.shape[d] % size != 0:
                    findings.append(make_shard_finding(
                        "unresolved-partition-spec", opath, oline,
                        f"[{ctx.site}] {leaf.path}: dim {d} "
                        f"(size {leaf.shape[d]}) is not divisible by "
                        f"axis {axis!r} (size {size})"))
                    bad = True
        if bad or leaf.actual is None:
            continue
        # conflict: a dim the table shards over a >1 axis must carry
        # that axis in the live sharding (composition may ADD axes —
        # ZeRO stacks fsdp on top — but must not drop the base one).
        actual_dims = tuple(leaf.actual)
        for d, entry in enumerate(dims):
            for axis in spec_dim_axes(entry):
                if sizes.get(axis, 1) <= 1:
                    continue
                live = spec_dim_axes(actual_dims[d]) if d < len(actual_dims) else ()
                if axis not in live:
                    findings.append(make_shard_finding(
                        "conflicting-partition-spec", opath, oline,
                        f"[{ctx.site}] {leaf.path}: table shards dim "
                        f"{d} over {axis!r} (spec {spec}) but the live "
                        f"sharding is {leaf.actual} — rule engine and "
                        f"executable disagree"))
    return findings


# ---------------------------------------------------------------------------
# donation layout
# ---------------------------------------------------------------------------

def audit_donations(ctx: SiteContext) -> List[Finding]:
    findings: List[Finding] = []
    opath, oline = ctx.origin
    for pair in ctx.donations:
        donor = tuple(pair.donor) if pair.donor is not None else ()
        target = tuple(pair.target) if pair.target is not None else ()
        if donor != target:
            findings.append(make_shard_finding(
                "donation-layout-mismatch", opath, oline,
                f"[{ctx.site}] {pair.path}: donated input is laid out "
                f"P{donor} but the output at the same position is "
                f"P{target} — XLA drops the alias and copies"))
    return findings


# ---------------------------------------------------------------------------
# replicated blowup (jaxpr walk)
# ---------------------------------------------------------------------------

_CONSTRAINT_PRIMS = ("sharding_constraint", "with_sharding_constraint")


def _eqn_source_line(eqn) -> Tuple[Optional[str], int]:
    try:
        from jax._src import source_info_util as siu

        frame = siu.user_frame(eqn.source_info.traceback)  # the installed JAX's signature: a traceback, not the SourceInfo
        if frame is not None:
            return frame.file_name, int(frame.start_line)
    except (ImportError, AttributeError, TypeError):
        pass
    return None, 1


def audit_jaxpr(ctx: SiteContext, hbm_bytes: Optional[int] = None,
                hbm_fraction: float = DEFAULT_HBM_FRACTION) -> List[Finding]:
    """Flag intermediates whose unsharded materialization exceeds
    ``hbm_fraction`` of per-device HBM and that no sharding constraint
    pins down.  Pre-compile heuristic — GSPMD may still shard the
    value — so tier B: above the threshold the layout bet must be
    explicit, not implicit."""
    if ctx.jaxpr_thunk is None:
        return []
    if hbm_bytes is None:
        hbm_bytes = int(os.environ.get("DS_SHARD_HBM_BYTES", DEFAULT_HBM_BYTES))
    threshold = int(hbm_bytes * hbm_fraction)
    try:
        jaxpr = ctx.jaxpr_thunk()
    except Exception:  # noqa: BLE001 — a site that can't trace is skipped, not fatal
        return []
    findings: List[Finding] = []
    opath, oline = ctx.origin
    constrained = set()

    # first pass marks every constrained var (constraints may appear
    # AFTER the producing eqn in program order), second pass flags
    def mark(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in _CONSTRAINT_PRIMS:
                for v in eqn.outvars:
                    constrained.add(id(v))
                for v in eqn.invars:
                    constrained.add(id(v))
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    mark(sub.jaxpr)

    def walk(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in _CONSTRAINT_PRIMS:
                continue
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                nbytes = int(getattr(aval, "size", 0)) * getattr(
                    getattr(aval, "dtype", None), "itemsize", 4)
                if nbytes > threshold and id(v) not in constrained:
                    fpath, fline = _eqn_source_line(eqn)
                    findings.append(make_shard_finding(
                        "replicated-blowup", fpath or opath,
                        fline if fpath else oline,
                        f"[{ctx.site}] {name} materializes "
                        f"{aval.shape} ({nbytes / 2**20:.1f} MiB) with "
                        f"no sharding constraint — above "
                        f"{hbm_fraction:.0%} of {hbm_bytes / 2**30:.0f} "
                        f"GiB HBM, pin its layout explicitly"))

    top = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    mark(top)
    walk(top)
    return findings


def audit_site_specs(ctx: SiteContext, hbm_bytes: Optional[int] = None,
                     hbm_fraction: float = DEFAULT_HBM_FRACTION) -> List[Finding]:
    """All Pass 1 checks for one compile site."""
    out = audit_leaves(ctx)
    out += audit_donations(ctx)
    out += audit_jaxpr(ctx, hbm_bytes=hbm_bytes, hbm_fraction=hbm_fraction)
    return out
