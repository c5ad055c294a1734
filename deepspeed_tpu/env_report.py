"""Environment / ops report — the ``ds_report`` analog (reference
``env_report.py``).  Prints which ops lower to Pallas vs plain XLA vs
native C++, the device inventory, and asserts **zero CUDA ops** (the
north-star requirement): any op whose lowering would require CUDA is a
FAIL row.
"""
from __future__ import annotations

import os
import sys


GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
OKAY = f"{GREEN}[OKAY]{END}"
FAIL = f"{RED}[FAIL]{END}"
WARNING = f"{YELLOW}[WARNING]{END}"


def op_report(verbose: bool = True) -> bool:
    from deepspeed_tpu.ops.registry import all_ops

    max_dots = 50
    print("-" * 64)
    print("deepspeed_tpu op lowering report")
    print("-" * 64)
    print("op name" + "." * (max_dots - len("op name")) + "lowering / status")
    print("-" * 64)
    ok = True
    cuda_ops = 0
    for name, spec in sorted(all_ops().items()):
        compatible = spec.is_compatible()
        ok = ok and compatible
        if spec.lowering == "cuda":
            cuda_ops += 1
        status = OKAY if compatible else FAIL
        print(f"{name}{'.' * (max_dots - len(name))}[{spec.lowering}] {status}")
    print("-" * 64)
    if cuda_ops:
        print(f"CUDA ops detected: {cuda_ops} {FAIL}")
        ok = False
    else:
        print(f"CUDA ops detected: 0 {OKAY}")
    return ok


def _compilation_cache_status() -> str:
    """Whether XLA's persistent compilation cache is on, and where.
    Checked the same way jax resolves it: config flag first, then the
    environment variable."""
    import jax

    cache_dir = None
    try:
        cache_dir = jax.config.jax_compilation_cache_dir
    except AttributeError:
        pass
    cache_dir = cache_dir or os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        return "disabled"
    min_size = getattr(jax.config, "jax_persistent_cache_min_entry_size_bytes", None)
    detail = f", min entry size {min_size}B" if min_size else ""
    return f"enabled ({cache_dir}{detail})"


def debug_report() -> None:
    import jax

    print()
    print("DeepSpeed-TPU general environment info:")
    from deepspeed_tpu.version import __version__

    devices = jax.devices()
    rows = [
        ("deepspeed_tpu version", __version__),
        ("jax version", jax.__version__),
        ("default backend", jax.default_backend()),
        ("detected platform", devices[0].platform if devices else "none"),
        ("device count", jax.device_count()),
        ("local device count", jax.local_device_count()),
        ("process count", jax.process_count()),
        ("devices", ", ".join(str(d) for d in devices[:8]) + (" ..." if jax.device_count() > 8 else "")),
        ("compilation cache", _compilation_cache_status()),
    ]
    try:
        import jaxlib

        rows.insert(2, ("jaxlib version", jaxlib.__version__))
    except Exception:
        pass
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def resilience_report(config=None) -> None:
    """Resilience configuration summary rows (docs/resilience.md).
    ``config`` may be a DeepSpeedConfig, a ResilienceConfig, or None
    (prints the defaults a config-less run gets)."""
    from deepspeed_tpu.config.config import ResilienceConfig

    r = getattr(config, "resilience", config)
    if r is None:
        r = ResilienceConfig()
    ck, wd, rt, dv, sv = r.checkpoint, r.watchdog, r.retry, r.divergence, r.supervision
    print()
    print("resilience configuration:")
    rows = [
        (
            "atomic checkpoints",
            f"enabled (verify_on_load={'on' if ck.verify_on_load else 'off'}, checksum={ck.checksum})"
            if ck.atomic
            else f"{YELLOW}DISABLED{END} (non-atomic legacy writes)",
        ),
        (
            "retention policy",
            "keep all tags"
            if ck.keep_last_n <= 0
            else f"keep_last_n={ck.keep_last_n}"
            + (f", keep_every={ck.keep_every} steps" if ck.keep_every > 0 else ""),
        ),
        (
            "preemption watchdog",
            f"enabled (grace {wd.grace_seconds:g}s, exit code {wd.exit_code})"
            if wd.enabled
            else "disabled",
        ),
        (
            "retry policy",
            f"{rt.max_attempts} attempt(s), backoff {rt.backoff_seconds:g}s "
            f"(cap {rt.backoff_max_seconds:g}s"
            + (f", deadline {rt.timeout_seconds:g}s)" if rt.timeout_seconds else ")"),
        ),
        (
            "divergence guard",
            f"{dv.action} after {dv.threshold} skipped steps" if dv.enabled else "disabled",
        ),
        (
            "supervision",
            f"enabled ({sv.channel} channel, beat {sv.beat_interval_seconds:g}s)"
            if sv.enabled
            else "disabled (one dead rank hangs the collectives forever)",
        ),
        (
            "supervision deadlines",
            f"death after {sv.beat_timeout_seconds:g}s stale beat, hung sync after "
            f"{sv.sync_timeout_seconds:g}s; exit {sv.exit_code} = peer-failed-and-saved",
        ),
        (
            "elastic restarts",
            (lambda n: f"{n} (launcher --restarts, resumes from newest verified tag)"
             if n else "0 (launch with --restarts N to relaunch on exit 43/44)")(
                int(os.environ.get("DS_RESTARTS", "0") or 0)
            ),
        ),
    ]
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def overlap_report(config=None) -> None:
    """Overlap configuration summary rows (docs/performance.md).
    ``config`` may be a DeepSpeedConfig, an OverlapConfig, or None
    (prints the defaults a config-less run gets)."""
    from deepspeed_tpu.config.config import OverlapConfig

    o = getattr(config, "overlap", config)
    if o is None or not hasattr(o, "prefetch"):
        o = OverlapConfig()
    pf, ac, tl = o.prefetch, o.async_checkpoint, o.timeline
    print()
    print("overlap configuration:")
    rows = [
        (
            "input prefetch",
            f"enabled (depth {pf.depth}, pipelined load+place)"
            if pf.enabled
            else f"{YELLOW}DISABLED{END} (train step waits on host transfer)",
        ),
        (
            "async checkpointing",
            f"enabled (drain timeout {ac.drain_timeout_seconds:g}s)"
            if ac.enabled
            else "disabled (saves stall training for the full write)",
        ),
        (
            "step timeline",
            f"enabled (window {tl.window} steps: data_wait/compute/ckpt_stall/other)"
            if tl.enabled
            else "disabled",
        ),
    ]
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def sanitizer_report(config=None) -> None:
    """ds_san availability/overhead rows (docs/ds_san.md).  ``config``
    may be a DeepSpeedConfig, a SanitizerConfig, or None (defaults +
    the DS_SAN env switch a config-less run would see)."""
    import os
    import timeit

    from deepspeed_tpu.config.config import SanitizerConfig

    s = getattr(config, "sanitizer", config)
    if s is None or not hasattr(s, "checkers"):
        s = SanitizerConfig.from_env() if os.environ.get("DS_SAN") == "1" else SanitizerConfig()
    import jax

    has_guard = hasattr(jax, "transfer_guard")
    try:
        from jax.experimental import checkify  # noqa: F401

        has_checkify = True
    except ImportError:
        has_checkify = False
    # the only hot-path cost when armed: one signature per compiled call
    from deepspeed_tpu.analysis.sanitizer.recompile import signature

    tree = {"params": {f"l{i}": {"w": __import__("numpy").zeros((4, 4))} for i in range(32)}}
    sig_us = timeit.timeit(lambda: signature(tree), number=200) / 200 * 1e6
    print()
    print("sanitizer (ds_san) configuration:")
    rows = [
        (
            "ds_san",
            f"{GREEN}ENABLED{END} ({', '.join(s.checkers)})"
            if s.enabled
            else "disabled (opt in: DS_SAN=1 or the `sanitizer` config block)",
        ),
        ("compile budget", f"{s.compile_budget} compiles per call site"),
        ("sharding drift sweep", f"every {s.drift_interval} steps + after checkpoint load"),
        (
            "transfer guard support",
            f"jax.transfer_guard available {OKAY}" if has_guard else f"missing {FAIL}",
        ),
        (
            "nonfinite probe support",
            f"checkify available {OKAY}" if has_checkify else f"missing {WARNING}",
        ),
        ("armed overhead", f"~{sig_us:.0f}us signature per compile check (32-leaf state)"),
    ]
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def comm_report(config=None) -> None:
    """Comm-layer strategy table (docs/comm.md).  ``config`` may be a
    DeepSpeedConfig, a CommConfig, or None (defaults).  Prints the
    config knobs plus the policy table — which strategy a few
    representative fp32 tensor sizes get over an 8-rank dp grid — and
    the per-exchange wire bytes/param of each strategy."""
    from deepspeed_tpu.comm.strategy import (
        select_strategy,
        strategy_wire_bytes_per_param,
    )
    from deepspeed_tpu.config.config import CommConfig

    c = getattr(config, "comm", config)
    if c is None or not hasattr(c, "threshold_bytes"):
        c = CommConfig()
    print()
    print("comm layer configuration:")
    rows = [
        ("strategy (config)", c.strategy),
        ("threshold_bytes", f"{c.threshold_bytes} (below: always dense)"),
        ("quantize_bits", c.quantize_bits),
        ("error_feedback", "on" if c.error_feedback else "off"),
        ("stochastic_rounding", "on" if c.stochastic_rounding else "off"),
    ]
    import numpy as np

    for label, nbytes in (
        ("16 KB fp32 @ dp=8", 16 << 10),
        ("4 MB fp32 @ dp=8", 4 << 20),
        ("500 MB fp32 @ dp=8", 500 << 20),
    ):
        d = select_strategy(c, nbytes, np.float32, 8)
        rows.append((label, f"{d.strategy} ({d.reason})"))
    for s in ("dense", "int8", "onebit"):
        rows.append(
            (f"wire B/param ({s})", f"{strategy_wire_bytes_per_param(s):g}")
        )
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def sharding_report(config=None) -> None:
    """Partition-rule engine + mesh topology rows (docs/sharding.md):
    the family rule catalog, the derived mesh shape and its ICI×DCN
    factoring over the available devices, and the cross-replica
    weight-update sharding status with its ~dp× byte/FLOP model."""
    from deepspeed_tpu.config.config import MeshConfig, ZeroConfig
    from deepspeed_tpu.sharding.mesh import MESH_AXES, resolve_mesh_shape, _granules, split_dcn_ici
    from deepspeed_tpu.sharding.rules import family_catalog
    from deepspeed_tpu.sharding.update import weight_update_model

    mc = getattr(config, "mesh", None) or MeshConfig()
    zc = getattr(config, "zero_config", None) or ZeroConfig()
    print()
    print("sharding / partition-rule engine:")
    rows = [
        (
            "partition-rule families",
            ", ".join(f"{k} ({v} rules)" for k, v in family_catalog().items()),
        ),
    ]
    try:
        import jax

        devices = jax.devices()
        sizes = resolve_mesh_shape(mc, len(devices))
        rows.append(
            ("mesh shape", " × ".join(f"{ax}={sizes[ax]}" for ax in MESH_AXES if sizes[ax] > 1) or "1 device")
        )
        granules = _granules(devices)
        if granules is not None and len(granules) > 1:
            split = split_dcn_ici(sizes, len(granules))
            if split is not None:
                dcn, ici = split
                rows.append(
                    (
                        "topology",
                        f"{len(granules)} slices: dcn="
                        + "×".join(str(dcn[ax]) for ax in MESH_AXES)
                        + " ici=" + "×".join(str(ici[ax]) for ax in MESH_AXES),
                    )
                )
            else:
                rows.append(("topology", f"{len(granules)} granules (unfactorable — flat order)"))
        else:
            rows.append(("topology", "single slice (all-ICI)"))
        dp = sizes.get("data", 1) * sizes.get("fsdp", 1)
    except Exception as e:  # no devices / bad mesh config: still report
        rows.append(("mesh shape", f"unavailable ({e})"))
        dp = 1
    cross = zc.stage >= 1 and getattr(zc, "cross_replica_weight_update", True)
    rows.append(
        (
            "weight-update sharding",
            (
                f"cross-replica (default ZeRO-1): ~{dp}x less update FLOPs + "
                f"opt-state bytes/replica, one params all-gather/step"
                if cross and dp > 1
                else ("off (zero_optimization.cross_replica_weight_update=false)"
                      if zc.stage >= 1 else "n/a (zero stage 0)")
            ),
        )
    )
    if dp > 1:
        m = weight_update_model(125_000_000, dp)
        rows.append(
            (
                "byte model @125M params",
                f"{m['opt_state_bytes_per_replica'] / 1e6:.0f} MB opt state/replica "
                f"(vs {weight_update_model(125_000_000, dp, sharded=False)['opt_state_bytes_per_replica'] / 1e6:.0f} replicated), "
                f"{m['update_allgather_bytes'] / 1e6:.0f} MB all-gather/step",
            )
        )
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def serving_report(config=None) -> None:
    """Serving-layer summary rows (docs/serving.md).  ``config`` may be
    a DeepSpeedConfig, a ServingConfig, or None (defaults).  Prints the
    slot-pool sizing knobs, the KV dtype, the scheduler policy knobs and
    the per-slot cache-byte formula (model dims are engine-time
    knowledge, so the formula is shown with the knobs filled in)."""
    from deepspeed_tpu.config.config import ServingConfig

    s = getattr(config, "serving", config)
    if s is None or not hasattr(s, "num_slots"):
        s = ServingConfig()
    print()
    print("serving configuration:")
    max_len = s.max_len if s.max_len else "derived (engine capacity // chunk * chunk)"
    rows = [
        ("slot pool", f"{s.num_slots} slots x {max_len} positions"),
        (
            "kv cache dtype",
            "int8 (codes + f32 scales, ~2x less HBM/slot)"
            if s.kv_cache_dtype == "int8"
            else "model (engine dtype; int8 if the engine's kv cache is)",
        ),
        (
            "pool bytes/slot",
            "2 x layers x heads x max_len x head_dim x itemsize"
            + (" x ~0.53 (int8+scales)" if s.kv_cache_dtype == "int8" else ""),
        ),
        (
            "chunked prefill",
            f"{s.prefill_chunk} tokens/chunk, "
            f"{s.prefill_chunks_per_step} chunk(s) interleaved per decode step",
        ),
        (
            "admission",
            f"max_queue={s.max_queue} (submit() rejects past it), "
            + (
                f"queue-wait deadline {s.deadline_seconds:g}s"
                if s.deadline_seconds
                else "no queue-wait deadline"
            ),
        ),
        ("default generation budget", f"{s.max_new_tokens} tokens/request"),
        # resilience rows (docs/serving.md §Resilience)
        (
            "overload shedding",
            f"estimated-TTFT test vs slo_ttft_ms={s.slo_ttft_ms:g} "
            "(priority 0 bypasses; sheds carry retry_after)"
            if s.slo_ttft_ms
            else "off (slo_ttft_ms=0; hard max_queue bound only)",
        ),
        (
            "degradation ladder",
            f"engage >= {s.degrade_queue_watermark:g}x max_queue for "
            f"{s.degrade_engage_steps} ticks, disengage after "
            f"{s.degrade_disengage_steps}; rungs: clamp max_new_tokens"
            + (f"->{s.degrade_max_new_tokens}" if s.degrade_max_new_tokens else "(off)")
            + " | 1 prefill chunk/step | shed low priority",
        ),
        (
            "graceful drain",
            f"SIGTERM -> stop admission, drain <= {s.drain_deadline_seconds:g}s, "
            "journal commit, exit 43",
        ),
        (
            "request journal",
            f"{s.journal_dir} ({s.journal_segment_records} records/segment, "
            f"compact past {s.journal_keep_segments} segments)"
            if s.journal_dir
            else "off (journal_dir unset; a crash loses queued+in-flight work)",
        ),
    ]
    # paged KV rows (docs/serving.md §Paged KV & prefix caching)
    kv = getattr(s, "kvcache", None)
    if kv is not None:
        if not kv.enabled:
            rows.append((
                "paged kv cache",
                "off (serving.kvcache.enabled=false; slot-contiguous pool)",
            ))
        else:
            rows += [
                (
                    "paged kv cache",
                    f"on: {kv.page_len}-token pages, "
                    + (f"{kv.num_pages} pages"
                       if kv.num_pages
                       else "pages derived (garbage + 2x slot capacity)")
                    + "; shared prefixes dedup via radix index + COW tails",
                ),
                (
                    "pinned prefixes",
                    f"{len(kv.pinned_prefixes)} pinned "
                    f"({sum(len(p) for p in kv.pinned_prefixes)} tokens, never evicted)"
                    if kv.pinned_prefixes
                    else "none (prefixes learned from traffic, LRU-evicted)",
                ),
                (
                    "session kv reuse",
                    (f"warm park, ttl {kv.session_ttl_seconds:g}s"
                     if kv.session_ttl_seconds else "warm park, no ttl")
                    + (f"; cold spill -> {kv.spill_dir} (manifest-gated, "
                       "recover() re-pins)"
                       if kv.spill_dir else "; no spill dir (cold sessions drop)"),
                ),
            ]
            # KV tiering rows (docs/serving.md §KV tiering)
            t = getattr(kv, "tiers", None)
            if t is not None and t.enabled:
                rows.append((
                    "kv tiering",
                    f"on: T1 host <= {t.host_pages} pages"
                    + (f", T2 disk -> {t.disk_dir}" if t.disk_dir
                       else ", no T2 (host-only)")
                    + f"; demote past {t.demote_watermark:g} pool watermark"
                    + (f", tail residency {t.residency_window} tokens"
                       if t.residency_window else "")
                    + f", prefetch {t.prefetch_ahead} hint(s)/step",
                ))
                rows += _kv_tier_rows()
            elif t is not None:
                rows.append((
                    "kv tiering",
                    "off (serving.kvcache.tiers.enabled=false; "
                    "parked sessions stay in HBM until spill/drop)",
                ))
    # fleet front-door rows (docs/serving.md §Fleet)
    f = getattr(s, "fleet", None)
    if f is not None:
        rows += [
            (
                "fleet router",
                f"{f.replicas} replica(s), least-estimated-TTFT placement, "
                f"{f.route_retries} failover retr"
                + ("y" if f.route_retries == 1 else "ies")
                + " per submit",
            ),
            (
                "fleet breaker",
                f"trip at {f.breaker_failures} consecutive failures, "
                f"backoff {f.breaker_backoff_seconds:g}s.."
                f"{f.breaker_backoff_max_seconds:g}s, "
                f"{f.breaker_halfopen_probes} half-open probe(s)",
            ),
            (
                "fleet hedging",
                f"duplicate after {f.hedge_factor:g}x p99 TTFT "
                f"(armed past {f.hedge_min_observations} samples; "
                "first token wins, loser cancelled)"
                if f.hedge
                else "off (hedge=false; per-request opt-in via submit)",
            ),
            (
                "fleet restart",
                f"supervised, <= {f.max_restarts} restart(s)/replica, "
                f"{f.restart_backoff_seconds:g}s backoff"
                + (f", budget decays 1/{f.restart_budget_reset_seconds:g}s "
                   "clean service"
                   if f.restart_budget_reset_seconds else "")
                + "; journal replay re-binds in-flight ids (lossless)",
            ),
        ]
        # elastic fleet rows (docs/serving.md §Elastic fleet)
        e = getattr(f, "elastic", None)
        if e is not None and e.enabled:
            rows += [
                (
                    "fleet autoscaler",
                    f"{e.min_replicas}..{e.max_replicas} replicas; up at "
                    f"queue>{e.scale_up_queue_depth} or "
                    f"ttft>{e.scale_up_ttft_seconds:g}s "
                    f"x{e.engage_ticks} ticks (cooldown "
                    f"{e.scale_up_cooldown_seconds:g}s), down at "
                    f"queue<={e.scale_down_queue_depth} "
                    f"x{e.disengage_ticks} ticks (cooldown "
                    f"{e.scale_down_cooldown_seconds:g}s)",
                ),
                (
                    "fleet warm pool",
                    f"{e.warm_pool_size} pre-built replica(s) "
                    "(compiled off the routing thread)"
                    if e.warm_pool_size
                    else "off (scale-up builds inline)",
                ),
                (
                    "fleet migration",
                    f"live KV session migration on drain (spill wire "
                    f"format, manifest-gated); <= {e.migration_retries} "
                    f"retr{'y' if e.migration_retries == 1 else 'ies'}, "
                    f"{e.migration_deadline_seconds:g}s drain deadline "
                    "(in-flight past it aborts the scale-down)",
                ),
            ]
        elif e is not None:
            rows.append((
                "fleet autoscaler",
                "off (serving.fleet.elastic.enabled=false; fixed replica "
                "count)",
            ))
    # front-door rows (docs/serving.md §Front-door)
    fd = getattr(s, "frontdoor", None)
    if fd is not None:
        rows.append((
            "http front-door",
            f"on: {fd.host}:{fd.port or 'ephemeral'}, chunked streaming "
            f"(poll {fd.stream_poll_seconds:g}s), 429/503 + Retry-After, "
            "SIGTERM drain -> stream-out -> exit 43"
            if fd.enabled
            else "off (serving.frontdoor.enabled=false; rpc/in-process "
            "submit only)",
        ))
    tn = getattr(s, "tenants", None)
    if tn is not None:
        if not tn.enabled:
            rows.append((
                "tenants",
                "off (serving.tenants.enabled=false; single-tenant "
                "admission)",
            ))
        else:
            bucket = (
                f"{tn.refill_tokens_per_second:g} tok/s burst "
                f"{tn.burst_tokens:g}"
                if tn.refill_tokens_per_second or tn.burst_tokens
                else "unlimited (accounting/WFQ only)"
            )
            rows += [
                (
                    "tenants",
                    f"on: default bucket {bucket}, weight {tn.weight:g}, "
                    f"slo {tn.slo_class}; {len(tn.overrides)} override(s) "
                    f"({', '.join(sorted(tn.overrides)) or 'none'})",
                ),
                (
                    "tenant kv quotas",
                    (f"kv_pages_max={tn.kv_pages_max}"
                     if tn.kv_pages_max else "pages uncapped")
                    + ", "
                    + (f"pinned_prefixes_max={tn.pinned_prefixes_max}"
                       if tn.pinned_prefixes_max else "pins uncapped")
                    + " (over-quota allocs defer, over-quota pins degrade)",
                ),
            ]
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def autoscaler_report(autoscaler) -> None:
    """LIVE autoscaler rows (ds_report with a running fleet, bench
    tools): current phase, warm pool, last scale events, migrations."""
    s = autoscaler.stats()
    wp = s["warm_pool"]
    rows = [
        ("autoscaler replicas",
         f"{s['replicas']} (bounds {s['min_replicas']}..{s['max_replicas']})"),
        ("autoscaler phase",
         s["phase"] + (f" (victim {s['victim']})" if s["victim"] else "")
         + f"; hot {s['hot_ticks']} cold {s['cold_ticks']} of "
         f"{s['ticks']} ticks"),
        ("warm pool",
         f"{wp['ready']}/{wp['size']} ready ({wp['built']} built, "
         f"{wp['build_failures']} failed)"),
        ("scale events",
         f"{s['scale_ups']} up / {s['scale_downs']} down "
         f"({s['scale_downs_aborted']} aborted)"),
        ("scale reactions",
         "up "
         + (f"{s['last_scale_up_reaction_s']:.3f}s"
            if s["last_scale_up_reaction_s"] is not None else "n/a")
         + ", down "
         + (f"{s['last_scale_down_reaction_s']:.3f}s"
            if s["last_scale_down_reaction_s"] is not None else "n/a")),
        ("migrations",
         f"{s['migrations_completed']} completed / "
         f"{s['migrations_failed']} failed "
         f"({s['sessions_migrated']} session(s) moved)"),
    ]
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def telemetry_report(config=None) -> None:
    """Telemetry-plane rows (docs/telemetry.md): enabled sinks and
    cadence from the config, plus the LIVE process plane (registry
    size, last export age, trace state) when one is armed."""
    from deepspeed_tpu import telemetry as tel
    from deepspeed_tpu.config.config import TelemetryConfig

    t = getattr(config, "telemetry", config)
    if t is None or not hasattr(t, "exporters"):
        t = TelemetryConfig()
    live = tel.status()
    print()
    print("telemetry configuration:")
    age = live["last_export_age_seconds"]
    rows = [
        (
            "metrics registry",
            f"enabled (ring {t.ring} samples/metric)"
            if t.enabled
            else "disabled (zero-overhead: no publishes anywhere)",
        ),
        (
            "exporters",
            ", ".join(t.exporters) + f" every {t.export_interval_seconds:g}s"
            if t.exporters
            else "none configured (jsonl | prometheus | tensorboard)",
        ),
        (
            "trace (Perfetto)",
            f"enabled ({t.trace_buffer_events} event ring -> "
            f"{t.trace_path or '<output_path>/trace.json'})"
            if t.trace
            else "disabled",
        ),
        (
            "cross-rank aggregation",
            "piggybacks on supervision beats (min/mean/max + dead-rank flags)"
            if t.aggregate and t.enabled
            else "off",
        ),
        (
            "live registry",
            f"{live['registry_size']} metric(s), rank {live['rank']}"
            if live["enabled"]
            else "not armed in this process",
        ),
        (
            "last export",
            # exports==0 means "never", full stop — a loop that has not
            # flushed yet must not print a bogus epoch-sized age
            "never"
            if live["sinks"] and (age is None or not live["exports"])
            else (f"{age:.1f}s ago ({live['exports']} total)" if age is not None
                  else "n/a (no sinks armed)"),
        ),
        (
            "profiler capture",
            f"dir {t.profiler_dir}, {t.profiler_capture_ms}ms window"
            + (f", on TTFT > {t.slo_ttft_breach_ms:g}ms" if t.slo_ttft_breach_ms else " (on-demand)")
            if t.profiler_dir
            else "off (set telemetry.profiler_dir)",
        ),
        (
            "anomaly watch",
            f"step-wall spike > {t.spike_factor:g}x window mean "
            f"(>= {t.spike_min_window} samples); straggler > "
            f"{t.straggler_factor:g}x cluster median"
            if t.enabled else "off (telemetry disabled)",
        ),
    ]
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def _kv_tier_rows() -> list:
    """LIVE tier-state rows from the ``kvcache/tier/*`` gauges an armed
    engine publishes each step (per-tier page counts/bytes, hit rates,
    in-flight migrations, last swap-hide ratio).  Empty before the
    first step — the config row above already says tiering is on."""
    from deepspeed_tpu import telemetry as tel

    g = {}
    for m in tel.get_registry().metrics():
        if m.name.startswith("kvcache/tier/") and m.kind == "gauge" \
                and m.value is not None:
            g[m.name[len("kvcache/tier/"):]] = m.value
    if not g:
        return []
    hits = g.get("hits_t1", 0) + g.get("hits_t2", 0)
    probes = hits + g.get("tier_misses", 0)
    return [
        (
            "kv tier residency",
            f"T1 {g.get('host_entries', 0):.0f} entr(ies) / "
            f"{g.get('host_pages', 0):.0f} page(s) / "
            f"{g.get('host_bytes', 0) / 2**20:.1f} MB, "
            f"T2 {g.get('disk_entries', 0):.0f} entr(ies) / "
            f"{g.get('disk_pages', 0):.0f} page(s)",
        ),
        (
            "kv tier traffic",
            f"demote {g.get('demote_t0_t1', 0):.0f}v {g.get('demote_t1_t2', 0):.0f}d, "
            f"promote {g.get('promote_t1_t0', 0) + g.get('promote_t2_t0', 0):.0f}^ "
            f"({g.get('promote_t2_t1', 0):.0f} prefetched), "
            f"hit rate {hits / probes:.0%} over {probes:.0f} probe(s), "
            f"{g.get('inflight', 0):.0f} migration(s) in flight"
            if probes else
            f"demote {g.get('demote_t0_t1', 0):.0f}v {g.get('demote_t1_t2', 0):.0f}d, "
            f"no promotion probes yet, "
            f"{g.get('inflight', 0):.0f} migration(s) in flight",
        ),
        (
            "kv swap hiding",
            f"{g.get('swap_hidden_ratio', 1.0):.0%} of "
            f"{g.get('swap_seconds_total', 0.0):.2f}s swap IO hidden "
            "beneath serving steps",
        ),
    ]


def kernels_report(config=None) -> None:
    """Pallas kernel-suite rows (docs/kernels.md): which kernels are
    armed for this process/backend and the block autotuner cache state
    (mode / path / entry count / LRU hits)."""
    from deepspeed_tpu.ops import kernels as k

    c = getattr(config, "kernels", None)
    if c is not None:
        k.configure_from_config(c)
    rep = k.kernels_report()
    at = rep["autotune"]
    print()
    print("pallas kernel suite:")
    rows = [
        ("suite armed", f"{'yes' if rep['suite_armed'] else 'no'} (DS_KERNELS={rep['env']})"),
        ("flash_decode kernel", "armed" if rep["flash_decode"] else "off"),
        ("fused_update kernel", "armed" if rep["fused_update"] else "off"),
        ("autotune mode", at["mode"]),
        ("autotune cache", at["path"] + ("" if at["cache_ok"] else " [CORRUPT -> defaults]")),
        ("autotune entries", f"{at['entries']} on disk, {at['lru']} in LRU"),
        ("autotune hits/misses", f"{at['hits']}/{at['misses']} ({at['tunes']} tuned this process)"),
    ]
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def analysis_report() -> None:
    """Static-analysis suite rows: per-tool rule counts, checked-in
    baseline sizes, and a live ds_race self-run (cheap — AST-only, no
    jax import) so drift from the baseline shows up in the report
    (docs/ds_lint.md / docs/ds_san.md / docs/ds_race.md)."""
    import json
    import time

    from deepspeed_tpu.analysis.baseline import BASELINE_NAME
    from deepspeed_tpu.analysis.core import Severity, all_rules
    from deepspeed_tpu.analysis.race import (
        RACE_BASELINE_NAME, all_race_rules, race_paths,
    )
    from deepspeed_tpu.analysis.race.stress import all_scenarios
    from deepspeed_tpu.analysis.sanitizer.cli import SAN_BASELINE_NAME
    from deepspeed_tpu.analysis.shard.rules import all_shard_rules
    from deepspeed_tpu.analysis.shard.runner import (
        SHARD_BASELINE_NAME, read_run_status,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def baseline_size(name: str) -> str:
        path = os.path.join(root, name)
        if not os.path.exists(path):
            return "no baseline"
        try:
            with open(path) as f:
                return f"{len(json.load(f)['findings'])} grandfathered"
        except (OSError, ValueError, KeyError) as e:
            return f"baseline unreadable ({e})"

    def tiers(rules) -> str:
        counts = {t: sum(1 for r in rules.values() if r.tier == t)
                  for t in (Severity.A, Severity.B, Severity.C)}
        return "/".join(f"{counts[t]}{t.name}" for t in (Severity.A, Severity.B, Severity.C))

    lint_rules, race_rules = all_rules(), all_race_rules()
    print()
    print("analysis suite:")
    rows = [
        ("ds_lint", f"{len(lint_rules)} rule(s) ({tiers(lint_rules)}), "
                    f"{baseline_size(BASELINE_NAME)}"),
        ("ds_san", f"runtime checkers (see sanitizer section), "
                   f"{baseline_size(SAN_BASELINE_NAME)}"),
        ("ds_race", f"{len(race_rules)} rule(s) ({tiers(race_rules)}) + "
                    f"{len(all_scenarios())} stress scenario(s), "
                    f"{baseline_size(RACE_BASELINE_NAME)}"),
    ]
    shard_rules = all_shard_rules()
    rows.append(("ds_shard", f"{len(shard_rules)} rule(s) ({tiers(shard_rules)}), "
                             f"{baseline_size(SHARD_BASELINE_NAME)}"))
    t0 = time.monotonic()
    try:
        res = race_paths([os.path.join(root, "deepspeed_tpu")])
        new = len(res.findings) + len(res.parse_errors)
        status = (f"{GREEN}GREEN{END}" if new == 0
                  else f"{RED}RED{END} ({new} unbaselined finding(s))")
        rows.append(("ds_race self-run",
                     f"{status} over {res.files} file(s) in "
                     f"{time.monotonic() - t0:.1f}s"))
    except Exception as e:  # noqa: BLE001 — a report must not crash the report
        rows.append(("ds_race self-run", f"{RED}failed{END}: {e!r}"))
    # ds_shard compiles every engine, far too heavy for a report — show
    # the persisted verdict of the last real run instead
    status = read_run_status(root)
    if status is None:
        rows.append(("ds_shard self-run",
                     "no run recorded (bin/ds_shard to refresh)"))
    else:
        verdict = status.get("verdict", "?")
        color = GREEN if verdict == "GREEN" else RED
        rows.append((
            "ds_shard self-run",
            f"{color}{verdict}{END} over {len(status.get('sites', []))} "
            f"site(s), {status.get('new_tier_a', '?')} new tier-A, "
            f"{len(status.get('skips', []))} skip(s) at "
            f"{status.get('timestamp', '?')}",
        ))
    for name, value in rows:
        print(f"{name} " + "." * (30 - len(name)) + f" {value}")


def cli_main() -> int:
    ok = op_report()
    debug_report()
    resilience_report()
    overlap_report()
    sanitizer_report()
    comm_report()
    sharding_report()
    serving_report()
    telemetry_report()
    kernels_report()
    analysis_report()
    return 0 if ok else 1


def main():
    sys.exit(cli_main())


if __name__ == "__main__":
    main()
