"""ds_shard collector: how engines feed Pass 1/Pass 2 contexts from
their existing AOT-compile sites.

Disarmed (the default) every ``note_*`` call is a None-check and
return — the ds_san pattern, nothing on the hot path.  The ds_shard
runner arms a collector, builds the dryrun engines (compiling exactly
what production compiles), then audits every collected
:class:`~deepspeed_tpu.analysis.shard.rules.SiteContext`.

Heavy work is deferred: notes store abstract shapes (ShapeDtypeStructs)
and thunks; AOT lowering of plain-jit sites happens only when the audit
actually reads the site's HLO.
"""
from __future__ import annotations

import sys
from typing import Any, Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.analysis.shard.rules import (
    DonationPair,
    LeafSpec,
    SiteContext,
)

_COLLECTOR: Optional["ShardCollector"] = None


class ShardCollector:
    """Accumulates one SiteContext per engine compile site."""

    def __init__(self) -> None:
        self.sites: Dict[str, SiteContext] = {}
        self.notes: List[str] = []

    def add(self, ctx: SiteContext) -> None:
        self.sites[ctx.site] = ctx

    def skip(self, site: str, reason: str) -> None:
        self.notes.append(f"{site}: {reason}")


def armed() -> bool:
    return _COLLECTOR is not None


def arm() -> ShardCollector:
    global _COLLECTOR
    _COLLECTOR = ShardCollector()
    return _COLLECTOR


def disarm() -> None:
    global _COLLECTOR
    _COLLECTOR = None


def current() -> Optional[ShardCollector]:
    return _COLLECTOR


def _origin(depth: int = 2) -> Tuple[str, int]:
    """(file, line) of the engine-side note call — the anchor findings
    without HLO source metadata attach to (and the line a
    ``# ds-shard: disable=...`` pragma suppresses them on)."""
    try:
        fr = sys._getframe(depth)
        return fr.f_code.co_filename, fr.f_lineno
    except ValueError:
        return "<unknown>", 1


def _abstract(tree: Any) -> Any:
    import jax
    import numpy as np

    def conv(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(tuple(np.shape(x)), x.dtype)
        return x

    return jax.tree_util.tree_map(conv, tree)


def _live_leaves(tree: Any, kind: str, prefix: str = "") -> List[LeafSpec]:
    """LeafSpecs from a live (placed) tree: shapes plus the
    PartitionSpec each array actually carries."""
    import jax
    import numpy as np

    from deepspeed_tpu.sharding.rules import _path_str

    out: List[LeafSpec] = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        shape = tuple(np.shape(leaf))
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        path = (prefix + "/" if prefix else "") + _path_str(kp)
        out.append(LeafSpec(path=path, shape=shape,
                            dtype=getattr(leaf, "dtype", None),
                            actual=spec, kind=kind))
    return out


def _donations_from(tree: Any, donor_sh: Any, target_sh: Any) -> List[DonationPair]:
    import jax

    from deepspeed_tpu.sharding.rules import _path_str

    donor_leaves = jax.tree_util.tree_flatten_with_path(donor_sh)[0]
    target_leaves = jax.tree_util.tree_leaves(target_sh)
    out: List[DonationPair] = []
    if len(donor_leaves) != len(target_leaves):
        return out
    for (kp, d), t in zip(donor_leaves, target_leaves):
        out.append(DonationPair(
            path=_path_str(kp),
            donor=getattr(d, "spec", d),
            target=getattr(t, "spec", t)))
    return out


def _jit_hlo_thunk(jit_fn: Any, args: Tuple[Any, ...],
                   collector: ShardCollector, site: str) -> Callable[[], Optional[str]]:
    """Deferred AOT lower+compile of a plain-jit site against the
    abstract shapes of its first real invocation (the
    ServingEngine.compiled_step pattern).  Compile failures are recorded
    as skips, not findings — pipe SPMD doesn't compile on every
    backend (tests/capabilities.py)."""
    abstract = _abstract(args)

    def thunk() -> Optional[str]:
        try:
            return jit_fn.lower(*abstract).compile().as_text()
        except Exception as e:  # noqa: BLE001 — backend capability, not a finding
            collector.skip(site, f"AOT compile unavailable: {type(e).__name__}: {e}")
            return None

    return thunk


def train_budget(engine) -> Tuple[Dict[str, int], Dict[str, Tuple[str, str]]]:
    """(byte-model budget, CommLayer decision table) for a train engine —
    the comparison baseline Pass 2 certifies compiled collectives against."""
    try:
        summary = engine.comm_summary()
    except Exception:  # noqa: BLE001 — a partially-built engine still audits specs
        return {}, {}
    budget = dict(summary.get("model") or {})
    comm_cfg = getattr(getattr(engine, "comm", None), "cfg", None)
    dcn_floor = getattr(comm_cfg, "dcn_threshold_bytes", None)
    if dcn_floor:
        budget["dcn-threshold-bytes"] = int(dcn_floor)
    return budget, dict(summary.get("table") or {})


# ---------------------------------------------------------------------------
# engine-side notes (one line at each compile site)
# ---------------------------------------------------------------------------

def note_train(engine, site: str, executable, fn=None, args=None,
               out_state_shardings=None) -> None:
    """Train engine AOT sites (train_batch / train_batches): the
    executable exists, so Pass 2 reads its HLO directly; Pass 1 gets
    the live param leaves, the state donation map (donated state vs the
    declared out_shardings), and a jaxpr thunk."""
    if _COLLECTOR is None:
        return
    budget, decisions = train_budget(engine)
    donor_sh = getattr(engine, "_state_shardings", None)
    target_sh = out_state_shardings if out_state_shardings is not None else donor_sh
    jaxpr_thunk = None
    if fn is not None and args is not None:
        abstract = _abstract(args)

        def jaxpr_thunk() -> Any:  # noqa: F811 — the closure IS the thunk
            import jax

            return jax.make_jaxpr(fn)(*abstract)

    _COLLECTOR.add(SiteContext(
        site=site,
        mesh=engine.mesh,
        topology=getattr(engine, "topology", None),
        rules=getattr(engine, "partition_rules", None),
        origin=_origin(),
        leaves=_live_leaves(engine.state.get("params", {}), "param", prefix=""),
        donations=_donations_from(donor_sh, donor_sh, target_sh) if donor_sh else [],
        budget=budget,
        decisions=decisions,
        jaxpr_thunk=jaxpr_thunk,
        hlo_thunk=lambda: executable.as_text(),
    ))


def note_jit(engine, site: str, jit_fn, args, *, mesh=None, rules=None,
             leaves=None, budget=None, decisions=None, origin=None) -> None:
    """Plain-jit compile sites (pipe train, offload drain, inference
    generate): Pass 2 AOT-lowers lazily against the call's abstract
    shapes; Pass 1 audits whatever live leaves the caller names."""
    if _COLLECTOR is None:
        return
    mesh = mesh if mesh is not None else getattr(engine, "mesh", None)
    topology = getattr(engine, "topology", None)
    if topology is None and mesh is not None:
        from deepspeed_tpu.sharding.mesh import derive_topology

        topology = derive_topology(mesh)
    _COLLECTOR.add(SiteContext(
        site=site,
        mesh=mesh,
        topology=topology,
        rules=rules if rules is not None else getattr(
            engine, "partition_rules", getattr(engine, "_rules", None)),
        origin=origin if origin is not None else _origin(),
        leaves=leaves or [],
        budget=dict(budget or {}),
        decisions=dict(decisions or {}),
        hlo_thunk=_jit_hlo_thunk(jit_fn, args, _COLLECTOR, site),
    ))


def note_serving(srv, site: str, jit_fn, args) -> None:
    """Serving prefill/decode: params + the KV pool are the leaf set
    (the pool is the tree ROADMAP item 1 will shard — every leaf must
    already resolve)."""
    if _COLLECTOR is None:
        return
    engine = srv.engine
    leaves = _live_leaves(engine.params, "param")
    pool = getattr(srv, "pool", None)
    if pool is not None:
        leaves += _live_leaves(getattr(pool, "k", {}), "kv", prefix="kv_pool/k")
        leaves += _live_leaves(getattr(pool, "v", {}), "kv", prefix="kv_pool/v")
    note_jit(engine, site, jit_fn, args, leaves=leaves, origin=_origin())


def live_param_leaves(tree: Any, kind: str = "param") -> List[LeafSpec]:
    """Public helper for engine hook sites."""
    return _live_leaves(tree, kind)
