"""ds_shard: partition-spec dataflow analysis + compiled-collective
audit — the fourth analysis surface next to ds_lint (AST hygiene),
ds_san (runtime numerics), and ds_race (lock discipline).

Two cooperating passes share ds_lint's Finding/severity/baseline/
suppression machinery (docs/ds_shard.md):

* **Pass 1 — spec dataflow (pre-compile, ``speccheck``):** abstract
  interpretation over the PR 8 rule engine and each engine's
  eval-shaped step trees.  Every param/state/KV leaf must resolve
  through :class:`~deepspeed_tpu.sharding.rules.PartitionRules`
  (tier A on unresolved or conflicting specs), dead/shadowed regex
  rows in the family tables are flagged, donation targets must
  layout-match their donors, and replicated intermediates above a
  configurable HBM fraction are reported with the offending op's
  source line.

* **Pass 2 — collective audit (post-compile, ``hloaudit``):** walk
  each AOT-compiled executable's optimized HLO (utils/hlo.py's
  regexes) and classify every all-gather / all-reduce / reduce-scatter
  / all-to-all / collective-permute as *budgeted* (a CommLayer
  decision record or the PR 8 byte model covers it within tolerance)
  or *unbudgeted* (tier A: GSPMD inserted a reshard nobody priced —
  the finding names the mismatched producer/consumer specs), with ICI
  vs DCN rows split via
  :class:`~deepspeed_tpu.sharding.mesh.MeshTopology` so an
  uncompressed DCN-crossing collective is always tier A.

Engines feed Pass 2 through the ``hooks`` collector at their existing
AOT-compile sites; ``bin/ds_shard`` / ``python -m
deepspeed_tpu.analysis shard`` run the self-audit over the 8-device
dryrun configs.  The baseline lives next to ds_lint's as
``.ds_shard_baseline.json``.
"""
from deepspeed_tpu.analysis.shard.rules import all_shard_rules
from deepspeed_tpu.analysis.shard.runner import (
    SHARD_BASELINE_NAME,
    SHARD_STATUS_NAME,
    shard_run,
)

__all__ = [
    "all_shard_rules",
    "shard_run",
    "SHARD_BASELINE_NAME",
    "SHARD_STATUS_NAME",
]
