"""Request-level serving SLO bench: seeded Poisson arrivals against the
continuous-batching engine (docs/serving.md).

Drives the `serving` bench rung (bench.py) and runs standalone:

    python tools/bench_serving.py --dryrun        # tiny model, CPU
    python tools/bench_serving.py                 # gpt2-xl on the chip

Per (kv dtype, offered load) it emits ONE record in the bench schema:

* ``value`` — end-to-end generated tokens/s over the run's makespan;
* ``ttft_p50_ms / ttft_p99_ms`` — time-to-first-token from the request's
  *scheduled* arrival (queue wait + chunked prefill included);
* ``tpot_p50_ms / tpot_p99_ms`` — per-output-token decode latency
  ((finish - first token) / (generated - 1));
* ``prefill_ms / decode_ms / sched_ms / queue_depth`` — the serving
  timeline's per-step phase attribution and mean queue depth.

Arrivals are a seeded Poisson process (exponential inter-arrivals);
offered loads are fractions of the measured closed-loop service rate, so
0.5x is comfortably under capacity and 2.0x is a sustained overload that
exercises queueing (and, with ``--max-queue``, rejection).  All timing
is host wall-clock around ``step()`` — nothing wall-clock-dependent is
traced (the compiled steps see only token/position values).

NB p99 over a few dozen requests is a tail *estimate*; the record
carries ``completed`` so readers can judge the sample size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# --dryrun must win before jax initializes (same recipe as tests/conftest.py)
if "--dryrun" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def log(msg):
    print(f"[bench_serving] {msg}", file=sys.stderr, flush=True)


def emit(rec, rung="serving"):
    print(json.dumps(rec), flush=True)
    from deepspeed_tpu.telemetry.regression import tool_history_emit

    # standalone runs feed the persistent bench history too (no-op when
    # the bench.py driver parent is the history writer)
    tool_history_emit(rec, rung=rung,
                      base_dir=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_workload(n, prompt_lo, prompt_hi, max_new, seed, vocab):
    """Seeded request set: ragged prompts + per-request generation
    budgets (arrival times are drawn per load in run_load)."""
    rng = np.random.default_rng(seed)
    return [
        {
            "prompt": rng.integers(1, vocab, int(rng.integers(prompt_lo, prompt_hi + 1)),
                                   dtype=np.int32),
            "max_new": int(max_new),
        }
        for _ in range(n)
    ]


def warm(srv, workload):
    """Compile both serving executables before the measured window (a
    fresh ServingEngine's first chunk/decode otherwise charges the jit
    trace to the first request's latency).  Priority 0: the warm-up
    must admit even when --overload arms the shedder."""
    w = workload[0]
    srv.submit(w["prompt"], max_new_tokens=min(2, w["max_new"]), priority=0)
    srv.drain(max_steps=10_000)
    srv.timeline.reset_window()
    return srv


def run_closed_loop(make_serving, workload):
    """Everything submitted at t=0 → drain: the capacity measurement the
    offered loads are scaled from."""
    from deepspeed_tpu.serving import ServingQueueFull

    srv = warm(make_serving(), workload)
    t0 = time.monotonic()
    for w in workload:
        while True:
            try:
                srv.submit(w["prompt"], max_new_tokens=w["max_new"])
                break
            except ServingQueueFull:  # bounded queue: drain a step, retry
                srv.step()
    res = srv.drain(max_steps=100_000)
    dt = time.monotonic() - t0
    toks = sum(len(r.generated) for r in res.values())
    return toks / max(dt, 1e-9), len(res) / max(dt, 1e-9), dt


def run_load(make_serving, workload, offered_rps, seed):
    """Open-loop seeded Poisson run at ``offered_rps`` requests/s."""
    from deepspeed_tpu.serving import ServingQueueFull

    srv = warm(make_serving(), workload)
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, size=len(workload)))
    t0 = time.monotonic()
    pending = list(zip(arrivals, workload))
    ids = {}  # request_id -> scheduled arrival offset
    finished = {}
    shed_retry = []  # retry_after hints carried by shed/queue-full rejections
    while pending or srv.scheduler.has_work():
        now = time.monotonic() - t0
        while pending and pending[0][0] <= now:
            arr, w = pending.pop(0)
            try:
                rid = srv.submit(w["prompt"], max_new_tokens=w["max_new"])
                ids[rid] = arr
            except ServingQueueFull as e:
                # shed load under overload; scheduler counts the rejection
                if e.retry_after is not None:
                    shed_retry.append(e.retry_after)
        if srv.scheduler.has_work():
            srv.step()
        elif pending:
            # idle until the next arrival (host sleep, nothing traced)
            time.sleep(min(0.005, max(0.0, pending[0][0] - now)))
        finished.update(srv.pop_results())
    makespan = time.monotonic() - t0
    ttft, ttft_submit, tpot, toks = [], [], [], 0
    for rid, arr in ids.items():
        r = finished.get(rid)
        if r is None or r.first_token_time is None:
            continue
        toks += len(r.generated)
        ttft.append((r.first_token_time - t0 - arr) * 1e3)
        # submit-anchored TTFT: the same timestamps the telemetry
        # plane's per-request spans carry, so a trace.json reconstructs
        # these two percentiles exactly (docs/telemetry.md; the
        # arrival-anchored ttft_* above additionally charges the
        # bench's submission-poll delay)
        ttft_submit.append((r.first_token_time - r.submit_time) * 1e3)
        if len(r.generated) > 1 and r.finish_time is not None:
            tpot.append(
                (r.finish_time - r.first_token_time) * 1e3 / (len(r.generated) - 1)
            )
    pct = lambda a, q: round(float(np.percentile(a, q)), 2) if a else None
    stats = srv.stats()
    tel = srv.telemetry_summary()
    return {
        "tokens_per_s": round(toks / max(makespan, 1e-9), 1),
        "ttft_p50_ms": pct(ttft, 50),
        "ttft_p99_ms": pct(ttft, 99),
        "ttft_submit_p50_ms": pct(ttft_submit, 50),
        "ttft_submit_p99_ms": pct(ttft_submit, 99),
        "tpot_p50_ms": pct(tpot, 50),
        "tpot_p99_ms": pct(tpot, 99),
        "completed": len(ttft),
        "rejected": stats["rejected"],
        "expired": stats["expired"],
        # overload-resilience fields (docs/serving.md §Resilience):
        # shed_rate over OFFERED requests; the ttft_* percentiles above
        # are admitted-only, which is exactly the shedder's SLO claim
        "shed": stats["shed"],
        "shed_rate": round(stats["rejected"] / max(len(workload), 1), 3),
        "retry_after_p50_s": pct(shed_retry, 50),
        "degrade_engagements": stats["degrade_engagements"],
        "degrade_level_final": stats["degrade_level"],
        "offered_rps": round(offered_rps, 3),
        "prefill_ms": stats["prefill_ms"],
        "decode_ms": stats["decode_ms"],
        "sched_ms": stats["sched_ms"],
        "queue_depth": stats["queue_depth"],
        "decode_compiles": stats["decode_compiles"],
        "mfu": tel["mfu"],
        "hbm_bytes_per_step": tel["hbm_bytes_per_step"],
        "telemetry": tel["telemetry"],
        **({"ds_san": True} if srv._sanitizer is not None else {}),
    }


def run_fleet_load(router, reps, workload, offered_rps, seed, kill_at_frac=None):
    """Open-loop seeded Poisson run through the FleetRouter; with
    ``kill_at_frac`` the busiest replica is killed once that fraction of
    the arrival schedule has elapsed (the failover measurement)."""
    from deepspeed_tpu.serving.fleet import FleetOverloaded

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, size=len(workload)))
    kill_at = (
        float(arrivals[max(int(len(arrivals) * kill_at_frac) - 1, 0)])
        if kill_at_frac is not None else None
    )
    t0 = time.monotonic()
    pending = list(zip(arrivals, workload))
    handles = {}  # handle_id -> scheduled arrival offset
    finished = {}
    rejected = 0
    while pending or router.has_work():
        now = time.monotonic() - t0
        if kill_at is not None and now >= kill_at:
            victim = max((r for r in reps if r.alive()),
                         key=lambda r: r.queue_depth())
            victim.kill("bench chaos: kill mid-run")
            kill_at = None
        while pending and pending[0][0] <= now:
            arr, w = pending.pop(0)
            try:
                hid = router.submit(w["prompt"], max_new_tokens=w["max_new"])
                handles[hid] = arr
            except FleetOverloaded:
                rejected += 1
        if router.has_work():
            router.step()
        elif pending:
            time.sleep(min(0.005, max(0.0, pending[0][0] - now)))
        finished.update(router.pop_results())
    makespan = time.monotonic() - t0
    # quiesce: a background restart may still be rebuilding after the
    # last result lands — step it to completion so the record carries
    # the restart count and the process doesn't exit mid-compile
    sup = getattr(router, "_supervisor", None)
    if sup is not None:
        while sup.pending():
            router.step()
        router.step()  # absorb a completion that landed after the last poll
    finished.update(router.pop_results())
    ttft, toks = [], 0
    for hid, arr in handles.items():
        r = finished.get(hid)
        if r is None or r.first_token_time is None:
            continue
        toks += len(r.generated)
        # submit-anchored admitted TTFT — a refired/replayed request's
        # clock restarts with its re-admission, which is exactly the
        # replica-local latency the failover SLO is about
        ttft.append((r.first_token_time - r.submit_time) * 1e3)
    pct = lambda a, q: round(float(np.percentile(a, q)), 2) if a else None
    st = router.stats()
    return {
        "tokens_per_s": round(toks / max(makespan, 1e-9), 1),
        "ttft_submit_p50_ms": pct(ttft, 50),
        "ttft_submit_p99_ms": pct(ttft, 99),
        "completed": len(ttft),
        "offered": len(workload),
        "availability": round(len(ttft) / max(len(workload), 1), 3),
        "rejected": rejected,
        "deaths": st["deaths"],
        "restarts": st["restarts"],
        "failovers": st["failovers"],
        "refired": st["refired"],
        "offered_rps": round(offered_rps, 3),
    }


def run_fleet_bench(engine, args, slots, chunk, max_len, max_new, workload, model):
    """The ``fleet`` bench rung: a 3-replica FleetRouter under seeded
    Poisson load, measured twice with the SAME arrival schedule —
    steady-state, then with one replica killed mid-run and supervised
    back to life.  The PR 11 perf sentinel gates the emitted record;
    its headline ratio is failover-p99 TTFT over steady-state p99 (the
    fleet proof bound: <= 2x)."""
    import tempfile

    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.fleet import (
        FleetRouter,
        LocalReplica,
        ReplicaSupervisor,
    )

    n_replicas = 3
    # 4x the serving workload: p99 over a dozen samples is just the max
    # sample, which makes the failover ratio a coin flip on whichever
    # request happened to straddle the kill
    workload = workload * 4

    with tempfile.TemporaryDirectory(prefix="bench_fleet_") as root:
        def build_fleet(tag):
            def factory(name):
                d = os.path.join(root, tag, name, "journal")
                return lambda: ServingEngine(
                    engine, num_slots=slots, prefill_chunk=chunk,
                    max_len=max_len, max_queue=args.max_queue,
                    max_new_tokens=max_new, journal_dir=d,
                )
            # the warm hook compiles both executables per engine build —
            # INCLUDING supervised restarts, so the rebuilt replica's jit
            # trace never lands on the replayed requests' TTFT
            reps = [
                LocalReplica(f"r{i}", factory(f"r{i}"),
                             warm=lambda e: warm(e, workload))
                for i in range(n_replicas)
            ]
            # background=True: the supervised restart (rebuild + warm +
            # replay) runs on a thread while the survivors keep serving —
            # a synchronous restart would block the routing loop for the
            # whole rebuild and charge it to every in-flight TTFT
            router = FleetRouter(
                reps,
                supervisor=ReplicaSupervisor(max_restarts=n_replicas,
                                             background=True),
                seed=args.seed,
            )
            return router, reps

        # capacity anchor: one replica's closed-loop service rate
        def make_one():
            return ServingEngine(engine, num_slots=slots, prefill_chunk=chunk,
                                 max_len=max_len, max_queue=args.max_queue,
                                 max_new_tokens=max_new)

        _, req_s, _ = run_closed_loop(make_one, workload)
        # 1.5x one replica's capacity (50% fleet utilization), but the
        # arrival schedule must SPAN the kill + supervised restart —
        # a rate that drains the whole workload in a fraction of a
        # second turns the failover run into a burst test where queue
        # depth, not failover, sets the tail
        offered = max(min(req_s * 1.5, len(workload) / 5.0), 1e-3)
        log(f"[fleet] single-replica capacity {req_s:.2f} req/s; "
            f"offering {offered:.2f} req/s to {n_replicas} replicas "
            f"over ~{len(workload) / offered:.1f}s")

        router, reps = build_fleet("steady")
        steady = run_fleet_load(router, reps, workload, offered, args.seed)
        log(f"[fleet] steady-state: {steady['tokens_per_s']} tok/s, "
            f"admitted p99 {steady['ttft_submit_p99_ms']} ms")

        router, reps = build_fleet("chaos")
        chaos = run_fleet_load(router, reps, workload, offered, args.seed,
                               kill_at_frac=0.4)
        if chaos["deaths"] < 1:
            log("[fleet] WARNING: the kill never fired (run too short?)")

    ratio = None
    if steady["ttft_submit_p99_ms"] and chaos["ttft_submit_p99_ms"]:
        ratio = round(
            chaos["ttft_submit_p99_ms"] / steady["ttft_submit_p99_ms"], 3
        )
    rec = {
        "metric": f"serving_fleet_{model.replace('-', '_')}_3rep_kill1",
        "value": chaos.pop("tokens_per_s"),
        "unit": "tokens/s",
        "replicas": n_replicas,
        "num_slots": slots,
        "prefill_chunk": chunk,
        "max_len": max_len,
        "requests": len(workload),
        "failover_over_steady_p99": ratio,
        "steady_tokens_per_s": steady["tokens_per_s"],
        "steady_ttft_submit_p99_ms": steady["ttft_submit_p99_ms"],
        **chaos,
    }
    emit(rec, rung="fleet")
    log(f"[fleet] kill-1-of-3: {rec['value']} tok/s "
        f"(steady {rec['steady_tokens_per_s']}), admitted p99 "
        f"{rec['ttft_submit_p99_ms']} ms = {ratio}x steady, "
        f"availability {rec['availability']:.1%}, deaths {rec['deaths']}, "
        f"restarts {rec['restarts']}")


def run_elastic_load(router, auto, workload, offered_rps, seed,
                     scale_down_at_frac=None):
    """Open-loop seeded Poisson run through an AUTOSCALED fleet: the
    :class:`FleetAutoscaler` ticks on the routing loop (its contract);
    with ``scale_down_at_frac`` a forced scale-down (drain + live KV
    migration) is requested once that fraction of the arrival schedule
    has elapsed.  ``auto=None`` runs the same loop without elasticity
    (the steady-state baseline)."""
    from deepspeed_tpu.serving.fleet import FleetOverloaded

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, size=len(workload)))
    down_at = (
        float(arrivals[max(int(len(arrivals) * scale_down_at_frac) - 1, 0)])
        if scale_down_at_frac is not None else None
    )
    t0 = time.monotonic()
    pending = list(zip(arrivals, workload))
    handles = {}  # handle_id -> scheduled arrival offset
    finished = {}
    rejected = 0
    peak_replicas = len(router._order)
    scale_down_requested = False
    while (pending or router.has_work()
           or (auto is not None and auto.stats()["phase"] != "idle")):
        now = time.monotonic() - t0
        if down_at is not None and now >= down_at:
            scale_down_requested = auto.request_scale_down()
            down_at = None
        while pending and pending[0][0] <= now:
            arr, w = pending.pop(0)
            try:
                hid = router.submit(w["prompt"], max_new_tokens=w["max_new"])
                handles[hid] = arr
            except FleetOverloaded:
                rejected += 1  # shed: the fleet is saturated end to end
        if auto is not None:
            auto.tick()
            peak_replicas = max(peak_replicas, len(router._order))
        if router.has_work():
            router.step()
        elif pending:
            time.sleep(min(0.005, max(0.0, pending[0][0] - now)))
        finished.update(router.pop_results())
    makespan = time.monotonic() - t0
    finished.update(router.pop_results())
    ttft, toks = [], 0
    for hid, arr in handles.items():
        r = finished.get(hid)
        if r is None or r.first_token_time is None:
            continue
        toks += len(r.generated)
        # submit-anchored admitted-only TTFT: the autoscaler's SLO claim
        # is about what the fleet ADMITTED while shedding the rest
        ttft.append((r.first_token_time - r.submit_time) * 1e3)
    pct = lambda a, q: round(float(np.percentile(a, q)), 2) if a else None
    return {
        "tokens_per_s": round(toks / max(makespan, 1e-9), 1),
        "ttft_submit_p50_ms": pct(ttft, 50),
        "ttft_submit_p99_ms": pct(ttft, 99),
        "completed": len(ttft),
        "offered": len(workload),
        "admitted": len(handles),
        "shed_rate": round(rejected / max(len(workload), 1), 3),
        "offered_rps": round(offered_rps, 3),
        "peak_replicas": peak_replicas,
        "scale_down_requested": scale_down_requested,
        "makespan_s": round(makespan, 2),
    }


def run_elastic_bench(engine, args, slots, chunk, max_len, max_new,
                      workload, model):
    """The ``elastic`` bench rung (docs/serving.md §Elastic fleet): an
    autoscaled fleet under ~10x one replica's offered load.  One paged
    replica + a FleetAutoscaler (warm pool pre-compiles off the routing
    thread) absorb a seeded Poisson surge; mid-surge a FORCED scale-down
    drains a victim and live-migrates its KV.  The record carries
    aggregate tokens/s, admitted-p99 TTFT (and its ratio over a
    single-replica steady state), shed rate, and the scale-up /
    scale-down reaction times."""
    import tempfile

    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.fleet import (
        FleetAutoscaler,
        FleetRouter,
        LocalReplica,
    )

    base = workload

    with tempfile.TemporaryDirectory(prefix="bench_elastic_") as root:
        def mk_factory(tag):
            def factory(name):
                d = os.path.join(root, tag, name, "journal")

                def build():
                    return ServingEngine(
                        engine, num_slots=slots, prefill_chunk=chunk,
                        max_len=max_len, max_queue=args.max_queue,
                        max_new_tokens=max_new, journal_dir=d,
                        slo_ttft_ms=args.slo_ttft_ms,
                        kvcache={"enabled": True, "page_len": chunk},
                    )
                return LocalReplica(name, build,
                                    warm=lambda e: warm(e, base))
            return factory

        # capacity anchor: one replica's closed-loop service rate sets
        # the offered-load scale (the shedder never engages closed-loop)
        def make_one():
            return ServingEngine(
                engine, num_slots=slots, prefill_chunk=chunk,
                max_len=max_len, max_queue=args.max_queue,
                max_new_tokens=max_new,
                kvcache={"enabled": True, "page_len": chunk},
            )

        _, req_s, _ = run_closed_loop(make_one, base)

        # steady-state baseline: ONE replica comfortably under capacity
        # — the denominator of the elastic p99 ratio
        steady_factory = mk_factory("steady")
        router = FleetRouter([steady_factory("r0")], seed=args.seed)
        steady = run_elastic_load(router, None, base * 2,
                                  max(req_s * 0.6, 1e-3), args.seed)
        log(f"[elastic] single-replica capacity {req_s:.2f} req/s; steady "
            f"admitted p99 {steady['ttft_submit_p99_ms']} ms")

        # the surge: ~10x one replica's capacity, sized so the arrival
        # window spans scale-up + mid-surge forced scale-down
        offered = max(req_s * 10.0, 1e-3)
        n_need = max(int(offered * 6.0) + 1, len(base))
        surge = (base * (n_need // len(base) + 1))[:n_need]
        elastic_factory = mk_factory("elastic")
        router = FleetRouter([elastic_factory("r0")], seed=args.seed)
        auto = FleetAutoscaler(
            router, elastic_factory,
            config={
                "enabled": True, "min_replicas": 1, "max_replicas": 3,
                "scale_up_queue_depth": max(slots, 4),
                "scale_up_ttft_seconds": args.slo_ttft_ms / 1e3,
                "scale_down_queue_depth": 1,
                "engage_ticks": 3,
                "disengage_ticks": 10 ** 6,  # scale-down is forced mid-run
                "scale_up_cooldown_seconds": 1.0,
                "scale_down_cooldown_seconds": 0.0,
                "warm_pool_size": 1,
                "migration_deadline_seconds": 120.0,
                "migration_retries": 2,
            },
            handoff_root=root,
        )
        try:
            log(f"[elastic] offering {offered:.2f} req/s "
                f"(~{offered / max(req_s, 1e-9):.1f}x capacity, "
                f"{len(surge)} requests over ~{len(surge) / offered:.1f}s)")
            elastic = run_elastic_load(router, auto, surge, offered,
                                       args.seed, scale_down_at_frac=0.55)
            st = auto.stats()
        finally:
            auto.stop()

    ratio = None
    if steady["ttft_submit_p99_ms"] and elastic["ttft_submit_p99_ms"]:
        ratio = round(
            elastic["ttft_submit_p99_ms"] / steady["ttft_submit_p99_ms"], 3
        )
    rec = {
        "metric": f"serving_elastic_{model.replace('-', '_')}_10x_autoscale",
        "value": elastic.pop("tokens_per_s"),
        "unit": "tokens/s",
        "offered_x_capacity": round(offered / max(req_s, 1e-9), 2),
        "num_slots": slots,
        "prefill_chunk": chunk,
        "max_len": max_len,
        "slo_ttft_ms": args.slo_ttft_ms,
        "elastic_over_steady_p99": ratio,
        "steady_tokens_per_s": steady["tokens_per_s"],
        "steady_ttft_submit_p99_ms": steady["ttft_submit_p99_ms"],
        "scale_ups": st["scale_ups"],
        "scale_downs": st["scale_downs"],
        "scale_downs_aborted": st["scale_downs_aborted"],
        "scale_up_reaction_s": (
            round(st["last_scale_up_reaction_s"], 3)
            if st["last_scale_up_reaction_s"] is not None else None),
        "scale_down_reaction_s": (
            round(st["last_scale_down_reaction_s"], 3)
            if st["last_scale_down_reaction_s"] is not None else None),
        "migrations_completed": st["migrations_completed"],
        "migrations_failed": st["migrations_failed"],
        "sessions_migrated": st["sessions_migrated"],
        "warm_pool_built": st["warm_pool"]["built"],
        **elastic,
    }
    emit(rec, rung="elastic")
    log(f"[elastic] {rec['offered_x_capacity']}x offered: {rec['value']} "
        f"tok/s aggregate, admitted p99 {rec['ttft_submit_p99_ms']} ms "
        f"= {ratio}x steady, shed {rec['shed_rate']:.1%}, "
        f"scale-up x{rec['scale_ups']} ({rec['scale_up_reaction_s']}s), "
        f"scale-down x{rec['scale_downs']} "
        f"({rec['scale_down_reaction_s']}s), "
        f"{rec['sessions_migrated']} session(s) migrated")


def run_tenant_load(make_serving, schedule):
    """Open-loop run over a pre-merged ``[(arrival_s, item), ...]``
    schedule where every item carries a ``tenant``; returns per-tenant
    admitted TTFT percentiles plus throttle counts (a throttled submit
    raises ``TenantThrottled`` — a ``ServingQueueFull`` subclass — and
    counts as that tenant's rejection, exactly the front-door's 429)."""
    from deepspeed_tpu.serving import ServingQueueFull

    srv = warm(make_serving(), [w for _, w in schedule])
    t0 = time.monotonic()
    pending = list(schedule)
    ids = {}  # rid -> (tenant, arrival offset)
    finished = {}
    rejected = {}  # tenant -> throttled/queue-full submit count
    while pending or srv.scheduler.has_work():
        now = time.monotonic() - t0
        while pending and pending[0][0] <= now:
            arr, w = pending.pop(0)
            try:
                rid = srv.submit(w["prompt"], max_new_tokens=w["max_new"],
                                 tenant=w["tenant"])
                ids[rid] = (w["tenant"], arr)
            except ServingQueueFull:
                rejected[w["tenant"]] = rejected.get(w["tenant"], 0) + 1
        if srv.scheduler.has_work():
            srv.step()
        elif pending:
            time.sleep(min(0.005, max(0.0, pending[0][0] - now)))
        finished.update(srv.pop_results())
    makespan = time.monotonic() - t0
    per, per_steps, toks = {}, {}, 0
    for rid, (tn, arr) in ids.items():
        r = finished.get(rid)
        if r is None or r.first_token_time is None:
            continue
        toks += len(r.generated)
        per.setdefault(tn, []).append(
            (r.first_token_time - r.submit_time) * 1e3)
        # submit-to-first-token in SCHEDULER STEPS: the virtual-time
        # view of the same wait (queue + chunked prefill), immune to
        # the host descheduling that makes wall-clock ms ungateable
        # on shared runners — a stalled host stops the step clock too
        per_steps.setdefault(tn, []).append(
            r.first_token_step - r.submit_step)
    pct = lambda a, q: round(float(np.percentile(a, q)), 2) if a else None
    return {
        "tokens_per_s": round(toks / max(makespan, 1e-9), 1),
        "tenants": {
            tn: {
                "completed": len(per.get(tn, [])),
                "rejected": rejected.get(tn, 0),
                "ttft_submit_p50_ms": pct(per.get(tn, []), 50),
                "ttft_submit_p99_ms": pct(per.get(tn, []), 99),
                "ttft_steps_p50": pct(per_steps.get(tn, []), 50),
                "ttft_steps_p99": pct(per_steps.get(tn, []), 99),
            }
            for tn in sorted(set(per) | set(rejected))
        },
    }


def run_tenant_bench(engine, args, slots, chunk, max_len, max_new, model):
    """The ``tenants`` bench rung (docs/serving.md §Front-door): the
    multi-tenant isolation proof.  A QUIET tenant runs the same seeded
    Poisson stream twice — once alone, once next to a NOISY tenant
    offered 10x its token-bucket quota.  The bucket + weighted-fair
    queue must absorb the noisy tenant (throttled at admission, fair-
    queued behind quiet's requests when admitted), so the quiet
    tenant's admitted median TTFT in the mixed run — measured in
    scheduler steps, the engine's virtual clock — IS the gated metric:
    if isolation breaks, the quiet tenant queues for more steps, the
    number inflates past the noise band and the perf sentinel goes
    red."""
    from deepspeed_tpu.serving import ServingEngine

    log("=== mixed-tenant isolation bench ===")
    rng = np.random.default_rng(args.seed)
    n_req = args.requests or 16
    lo, hi = 4, min(48, max_len // 2)
    base = build_workload(n_req, lo, hi, max_new, args.seed,
                          engine.model_config.vocab_size)
    # the bucket charges prompt + max_new at admission — quota math is
    # in TOKENS/s, so size it off the mean request cost
    cost = float(np.mean([len(w["prompt"]) + w["max_new"] for w in base]))

    # raw capacity (no tenants armed) sizes both offered rates
    def make_plain():
        return ServingEngine(engine, num_slots=slots, prefill_chunk=chunk,
                             max_len=max_len, max_queue=args.max_queue)

    toks_s, req_s, _ = run_closed_loop(make_plain, base)
    quiet_rps = max(req_s * 0.4, 1e-3)
    noisy_quota_rps = max(req_s * 0.3, 1e-3)  # the bucket's sustained rate
    noisy_offered_rps = noisy_quota_rps * 10.0  # 10x its quota
    log(f"[tenants] capacity {req_s:.2f} req/s; quiet offered "
        f"{quiet_rps:.2f} req/s, noisy offered {noisy_offered_rps:.2f} "
        f"req/s against a {noisy_quota_rps:.2f} req/s quota")

    tenants_cfg = {
        "enabled": True,
        "overrides": {
            # quiet: unlimited bucket, gold SLO (maps to priority 0)
            "quiet": {"slo_class": "gold"},
            # noisy: bucket sized to ~30% of capacity in token terms
            "noisy": {
                "refill_tokens_per_second": noisy_quota_rps * cost,
                "burst_tokens": max(2.0 * cost, 1.0),
                "slo_class": "bronze",
            },
        },
    }

    def make_tenanted():
        return ServingEngine(engine, num_slots=slots, prefill_chunk=chunk,
                             max_len=max_len, max_queue=args.max_queue,
                             tenants=tenants_cfg)

    # the quiet stream: IDENTICAL arrivals in both phases (same seed)
    quiet_items = [dict(w, tenant="quiet") for w in base]
    quiet_arr = np.cumsum(
        np.random.default_rng(args.seed + 1).exponential(
            1.0 / quiet_rps, size=len(quiet_items)))
    sched_quiet = sorted(zip(quiet_arr.tolist(), quiet_items))

    # latency noise on a shared host is one-sided (descheduling only
    # ADDS time), so each phase runs ``repeats`` times and the gated
    # number is the BEST step-count p50 — a real isolation regression
    # is workload behaviour and inflates every repeat, a jitter
    # outlier only one
    repeats = 5

    def best(runs_):
        return min(runs_, key=lambda r: (
            r["tenants"]["quiet"]["ttft_steps_p50"]
            if r["tenants"]["quiet"]["ttft_steps_p50"] is not None
            else float("inf")))

    solo_runs = [run_tenant_load(make_tenanted, sched_quiet)
                 for _ in range(repeats)]
    solo = best(solo_runs)
    q_solo = solo["tenants"]["quiet"]
    log(f"[tenants] quiet solo: admitted p50 "
        f"{q_solo['ttft_steps_p50']} steps / "
        f"{q_solo['ttft_submit_p50_ms']} ms best-of-{repeats} (p99 "
        f"{q_solo['ttft_submit_p99_ms']} ms, "
        f"{q_solo['completed']}/{len(quiet_items)} completed)")

    # the noisy stream spans the quiet window at 10x quota
    window_s = float(quiet_arr[-1])
    n_noisy = max(int(noisy_offered_rps * window_s) + 1, 4)
    noisy_base = (base * (n_noisy // len(base) + 1))[:n_noisy]
    noisy_items = [dict(w, tenant="noisy") for w in noisy_base]
    noisy_arr = np.cumsum(rng.exponential(
        1.0 / noisy_offered_rps, size=len(noisy_items)))
    merged = sorted(
        list(zip(quiet_arr.tolist(), quiet_items))
        + list(zip(noisy_arr.tolist(), noisy_items)),
        key=lambda p: p[0])

    mixed_runs = [run_tenant_load(make_tenanted, merged)
                  for _ in range(repeats)]
    mixed = best(mixed_runs)
    q_mix = mixed["tenants"]["quiet"]
    n_mix = mixed["tenants"].get(
        "noisy", {"completed": 0, "rejected": 0,
                  "ttft_submit_p99_ms": None})
    ratio = None
    if q_solo["ttft_steps_p50"] and q_mix["ttft_steps_p50"]:
        ratio = round(
            q_mix["ttft_steps_p50"] / q_solo["ttft_steps_p50"], 3)
    throttle_rate = round(
        n_mix["rejected"] / max(len(noisy_items), 1), 3)
    rec = {
        # "ttft"/"p50" tokens -> lower-is-better for the perf
        # sentinel; a DS_BENCH_INJECT 'tenants:3.0' triples it -> RED
        # (CI check).  Gated on the quiet tenant's MEDIAN submit-to-
        # first-token measured in SCHEDULER STEPS (virtual time), best
        # of ``repeats`` identical mixed phases: a starved tenant
        # queues for more steps in every repeat, while wall-clock ms
        # at single-digit magnitudes is dominated by shared-runner
        # descheduling (the ms percentiles ride along as context)
        "metric": f"serving_tenants_{model.replace('-', '_')}"
                  "_quiet_ttft_p50_steps_under_10x_noisy",
        "value": q_mix["ttft_steps_p50"],
        "unit": "steps",
        "repeats": repeats,
        "quiet_steps_p50_runs": [
            r["tenants"]["quiet"]["ttft_steps_p50"]
            for r in mixed_runs],
        "quiet_steps_p99": q_mix["ttft_steps_p99"],
        "quiet_solo_steps_p50": q_solo["ttft_steps_p50"],
        "quiet_p50_ms": q_mix["ttft_submit_p50_ms"],
        "quiet_p99_ms": q_mix["ttft_submit_p99_ms"],
        "quiet_solo_p50_ms": q_solo["ttft_submit_p50_ms"],
        "quiet_solo_p99_ms": q_solo["ttft_submit_p99_ms"],
        "quiet_mixed_over_solo_p50_steps": ratio,
        "quiet_completed": q_mix["completed"],
        "quiet_offered": len(quiet_items),
        "quiet_rejected": q_mix["rejected"],
        "noisy_offered": len(noisy_items),
        "noisy_completed": n_mix["completed"],
        "noisy_throttled": n_mix["rejected"],
        "noisy_throttle_rate": throttle_rate,
        "noisy_p99_ms": n_mix["ttft_submit_p99_ms"],
        "noisy_offered_x_quota": 10.0,
        "capacity_req_s": round(req_s, 2),
        "tokens_per_s": mixed["tokens_per_s"],
        "num_slots": slots,
        "prefill_chunk": chunk,
        "max_len": max_len,
    }
    emit(rec, rung="tenants")
    log(f"[tenants] mixed: quiet admitted p50 {rec['value']} steps "
        f"best-of-{repeats} {rec['quiet_steps_p50_runs']} "
        f"= {ratio}x solo ({rec['quiet_p50_ms']} ms, p99 "
        f"{rec['quiet_p99_ms']} ms); "
        f"noisy throttled {throttle_rate:.1%} "
        f"({n_mix['rejected']}/{len(noisy_items)}), quiet rejected "
        f"{q_mix['rejected']}")


def run_kvcache_bench(engine, args, slots, chunk, max_len, max_new, model):
    """The ``kvcache`` bench rung (docs/serving.md §Paged KV & prefix
    caching): an 80%-shared system-prompt batch plus 3-turn chat
    sessions, run twice with the SAME schedule — paged KV on vs off.
    The record proves the three acceptance claims at once: greedy
    outputs bit-identical, prefill FLOPs (chunk dispatches) reduced
    >= 2x, and TTFT p50/p99 measurably lower with the cache on."""
    from deepspeed_tpu.serving import ServingEngine

    rng = np.random.default_rng(args.seed)
    vocab = engine.model_config.vocab_size
    sys_len = max_len // 2  # the shared system prompt
    sys_prompt = rng.integers(1, vocab, sys_len, dtype=np.int32)
    n_req = args.requests or 12
    budget = min(max_new, 6)
    tail = lambda lo, hi: rng.integers(
        1, vocab, int(rng.integers(lo, hi + 1)), dtype=np.int32)
    # 80% of the batch shares the system prompt; the rest is cold
    batch = [
        np.concatenate([sys_prompt, tail(chunk // 4, chunk)])
        if i % 5 != 4 else tail(sys_len // 2, sys_len)
        for i in range(n_req)
    ]
    n_sess, n_turns = 3, 3
    sess_tails = [[tail(chunk // 4, chunk // 2) for _ in range(n_turns)]
                  for _ in range(n_sess)]

    def run(kvcache_on):
        kw = {"kvcache": {"enabled": True, "page_len": chunk}} if kvcache_on else {}
        srv = ServingEngine(engine, num_slots=slots, prefill_chunk=chunk,
                            max_len=max_len, max_queue=args.max_queue,
                            max_new_tokens=budget, **kw)
        warm(srv, [{"prompt": batch[0][: chunk // 2], "max_new": 2}])
        outputs, ttfts, chunks = [], [], 0
        t0 = time.monotonic()

        def go(prompts, **skw):
            nonlocal chunks
            rids = [srv.submit(p, max_new_tokens=budget, **dict(skw, **e))
                    for p, e in prompts]
            chunks += sum(-(-len(p) // chunk) for p, _ in prompts)
            res = srv.drain(max_steps=100_000)
            for rid in rids:
                r = res[rid]
                outputs.append(np.asarray(r.tokens()))
                ttfts.append((r.first_token_time - r.submit_time) * 1e3)
            return [np.asarray(res[rid].tokens()) for rid in rids]

        # seed the shared prefix (prefix warming: one full prefill both
        # runs pay; every later shared prompt can then hit)
        go([(sys_prompt, {})])
        # phase A: the shared-prefix batch, all offered at once
        go([(p, {}) for p in batch])
        # phase B: 3-turn sessions (turn n+1 extends turn n's output)
        hist = [np.concatenate([sys_prompt, sess_tails[s][0]])
                for s in range(n_sess)]
        for turn in range(n_turns):
            outs = go([(hist[s], {"session_id": f"sess-{s}"})
                       for s in range(n_sess)])
            if turn + 1 < n_turns:
                hist = [np.concatenate([outs[s], sess_tails[s][turn + 1]])
                        for s in range(n_sess)]
        makespan = time.monotonic() - t0
        toks = sum(len(o) for o in outputs)
        kv = srv.stats().get("kvcache") if kvcache_on else None
        return outputs, ttfts, chunks, makespan, toks, kv

    out_off, ttft_off, chunks_off, span_off, toks_off, _ = run(False)
    out_on, ttft_on, chunks_on_sched, span_on, toks_on, kv = run(True)
    bit_identical = len(out_on) == len(out_off) and all(
        np.array_equal(a, b) for a, b in zip(out_on, out_off)
    )
    # prefix hits are chunk-aligned, so saved chunks are exact
    chunks_on = chunks_on_sched - kv["tokens_saved"] // chunk
    reduction = round(chunks_off / max(chunks_on, 1), 3)
    pct = lambda a, q: round(float(np.percentile(a, q)), 2) if a else None
    rec = {
        "metric": f"serving_kvcache_{model.replace('-', '_')}_prefix_session",
        "value": reduction,
        "unit": "x_prefill_flops",
        "bit_identical": bit_identical,
        "hit_rate": kv["hit_rate"],
        "tokens_saved": kv["tokens_saved"],
        "prefill_chunks_off": chunks_off,
        "prefill_chunks_on": chunks_on,
        "ttft_p50_ms_on": pct(ttft_on, 50),
        "ttft_p99_ms_on": pct(ttft_on, 99),
        "ttft_p50_ms_off": pct(ttft_off, 50),
        "ttft_p99_ms_off": pct(ttft_off, 99),
        "tokens_per_s_on": round(toks_on / max(span_on, 1e-9), 1),
        "tokens_per_s_off": round(toks_off / max(span_off, 1e-9), 1),
        "cow_copies": kv["cow_copies"],
        "session_rebinds": kv["session_rebinds"],
        "evictions": kv["evictions"],
        "page_len": kv["page_len"],
        "requests": len(out_on),
        "num_slots": slots,
        "prefill_chunk": chunk,
        "max_len": max_len,
    }
    emit(rec, rung="kvcache")
    log(f"[kvcache] prefill FLOPs {reduction}x lower "
        f"({chunks_off} -> {chunks_on} chunks), hit rate "
        f"{kv['hit_rate']:.0%}, ttft p50 {rec['ttft_p50_ms_on']} ms vs "
        f"{rec['ttft_p50_ms_off']} ms off, bit_identical={bit_identical}")


def run_kvtiers_bench(engine, args, slots, chunk, max_len, max_new, model):
    """The ``kvtiers`` rung (docs/serving.md §KV tiering): a long-context
    session fleet whose parked working set is ~4x the device page pool,
    run three ways with the SAME prompt schedule —

    * all-HBM reference (paged KV, pool sized to hold everything);
    * tiering armed but T0-resident (same big pool + tiers: measures the
      tier manager's overhead when nothing needs to move);
    * tiering armed at ~4x oversubscription (tiny T0, host + disk tiers
      absorb the rest; every turn revisits sessions demoted since).

    Gates: greedy outputs bit-identical to the all-HBM run, zero
    ServingQueueFull at 4x, T0-resident tokens/s within 10% of all-HBM
    (recorded as ``tok_ratio_resident``); ``swap_hidden_ratio`` records
    what fraction of device<->host/disk migration time hid beneath
    serving steps (soft gate >= 0.8)."""
    import shutil
    import tempfile

    from deepspeed_tpu.serving import ServingEngine

    rng = np.random.default_rng(args.seed)
    vocab = engine.model_config.vocab_size
    page_len = chunk
    n_sess, n_turns = 8, 3
    tail_len = max(4, page_len // 2)
    budget = max(2, min(max_new, page_len // 4))
    pages_for = lambda toks: -(-max(toks, 1) // page_len)
    # the working set is what the sessions park by the end; size T0 to a
    # quarter of it (but never below one max request's upfront claim)
    parked_toks = n_turns * (tail_len + budget) - 1
    ws_pages = n_sess * pages_for(parked_toks)
    per_req = pages_for(n_turns * (tail_len + budget)) + 1  # +1 COW page
    t0_usable = max(-(-ws_pages // 4), per_req + 1)
    # the pool refuses a T0 smaller than one slot's ceiling, so cap this
    # rung's max_len to what the longest turn actually needs
    rung_max_len = min(max_len, page_len * (per_req + 1))
    tails = [[rng.integers(1, vocab, tail_len, dtype=np.int32)
              for _ in range(n_turns)] for _ in range(n_sess)]

    def run(num_pages, tiers_kw):
        kv = {"enabled": True, "page_len": page_len}
        if num_pages:
            kv["num_pages"] = num_pages
        if tiers_kw:
            kv["tiers"] = {"enabled": True, **tiers_kw}
        srv = ServingEngine(engine, num_slots=slots, prefill_chunk=chunk,
                            max_len=rung_max_len, max_queue=args.max_queue,
                            max_new_tokens=budget, kvcache=kv)
        warm(srv, [{"prompt": tails[0][0][: page_len // 2], "max_new": 2}])
        outputs = []
        hist = [np.array([], np.int32) for _ in range(n_sess)]
        t0 = time.monotonic()
        for turn in range(n_turns):
            prompts = [np.concatenate([hist[s], tails[s][turn]]).astype(np.int32)
                       for s in range(n_sess)]
            rids = [srv.submit(prompts[s], max_new_tokens=budget,
                               temperature=0.0, session_id=f"tier-sess-{s}")
                    for s in range(n_sess)]
            res = srv.drain(max_steps=100_000)
            for s, rid in enumerate(rids):
                gen = np.asarray(res[rid].generated, np.int32)
                outputs.append(gen)
                hist[s] = np.concatenate([prompts[s], gen]).astype(np.int32)
        makespan = time.monotonic() - t0
        toks = sum(len(o) for o in outputs)
        st = srv.stats()
        rejected = int(st.get("rejected", 0))
        tiers = st.get("kvcache", {}).get("tiers")
        if getattr(srv, "_tiers", None) is not None:
            srv._tiers.close()  # stop the migration worker between runs
        return outputs, toks / max(makespan, 1e-9), rejected, tiers

    t2_dir = tempfile.mkdtemp(prefix="ds_kvtiers_")
    # the all-HBM pool holds the parked working set AND every active
    # slot's upfront claim comfortably below the default demote
    # watermark — no reclaim or demotion pressure, the true T0 baseline
    hbm_pages = int((ws_pages + slots * per_req) / 0.7) + 2
    try:
        out_ref, tps_ref, rej_ref, _ = run(hbm_pages, None)
        out_res, tps_res, rej_res, tiers_res = run(hbm_pages, {
            "host_pages": t0_usable, "disk_dir": os.path.join(t2_dir, "res"),
        })
        out_4x, tps_4x, rej_4x, tiers_4x = run(t0_usable + 1, {
            "host_pages": t0_usable,
            "disk_dir": os.path.join(t2_dir, "cold"),
            "residency_window": page_len,
            "demote_watermark": 0.5,
            "demote_batch": 8,
            "prefetch_ahead": slots,
        })
    finally:
        shutil.rmtree(t2_dir, ignore_errors=True)

    bit_identical = (
        len(out_4x) == len(out_ref) == len(out_res)
        and all(np.array_equal(a, b) for a, b in zip(out_4x, out_ref))
        and all(np.array_equal(a, b) for a, b in zip(out_res, out_ref))
    )
    ratio_res = round(tps_res / max(tps_ref, 1e-9), 3)
    swaps = (tiers_4x["demote_t0_t1"] + tiers_4x["promote_t1_t0"]
             + tiers_4x["promote_t2_t0"])
    rec = {
        "metric": f"serving_kvtiers_{model.replace('-', '_')}_4x",
        # the headline is the KV capacity multiple served at zero
        # rejects with bit-identical outputs — deterministic by
        # construction, so the perf sentinel can gate it with a tight
        # band (raw tok/s rides along below; too noisy on CPU runners)
        "value": round(ws_pages / t0_usable, 2),
        "unit": "x_hbm_kv_capacity",
        "bit_identical": bit_identical,
        "working_set_pages": ws_pages,
        "t0_pages": t0_usable,
        "oversubscription_x": round(ws_pages / t0_usable, 2),
        "tokens_per_s_4x": round(tps_4x, 1),
        "tokens_per_s_ref": round(tps_ref, 1),
        "tokens_per_s_resident": round(tps_res, 1),
        "tok_ratio_resident": ratio_res,
        "queue_full_4x": rej_4x,
        "swaps": swaps,
        "swap_hidden_ratio": tiers_4x["swap_hidden_ratio"],
        "demote_t0_t1": tiers_4x["demote_t0_t1"],
        "demote_t1_t2": tiers_4x["demote_t1_t2"],
        "promote_t1_t0": tiers_4x["promote_t1_t0"],
        "promote_t2_t1": tiers_4x["promote_t2_t1"],
        "promote_t2_t0": tiers_4x["promote_t2_t0"],
        "hits_t1": tiers_4x["hits_t1"],
        "hits_t2": tiers_4x["hits_t2"],
        "sessions": n_sess,
        "turns": n_turns,
        "num_slots": slots,
        "page_len": page_len,
        "max_len": rung_max_len,
    }
    emit(rec, rung="kvtiers")
    log(f"[kvtiers] {rec['oversubscription_x']}x working set: "
        f"{rec['tokens_per_s_4x']} tok/s (ref {rec['tokens_per_s_ref']}, resident "
        f"ratio {ratio_res}), {swaps} swaps, hidden "
        f"{rec['swap_hidden_ratio']:.0%}, bit_identical={bit_identical}, "
        f"queue_full={rej_4x}")
    if not bit_identical:
        raise SystemExit("[kvtiers] FAIL: tiered outputs diverge from all-HBM")
    if rej_4x or rej_res or rej_ref:
        raise SystemExit(f"[kvtiers] FAIL: ServingQueueFull raised "
                         f"(ref={rej_ref} resident={rej_res} 4x={rej_4x})")
    if swaps == 0:
        raise SystemExit("[kvtiers] FAIL: 4x run never exercised the tiers")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true", help="tiny model on CPU")
    ap.add_argument("--model", default=None)
    ap.add_argument("--loads", default="0.5,1.0,2.0",
                    help="offered loads as fractions of measured capacity")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--kv", default="both", choices=("both", "model", "int8"))
    ap.add_argument("--num-slots", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--fleet", action="store_true",
                    help="fleet-failover mode (docs/serving.md §Fleet): a "
                         "3-replica FleetRouter under seeded Poisson load, "
                         "one replica killed mid-run and supervised back — "
                         "records availability + failover-p99-over-steady")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic-fleet mode (docs/serving.md §Elastic "
                         "fleet): an autoscaled fleet under ~10x one "
                         "replica's offered load with a forced mid-surge "
                         "scale-down + live KV migration — records "
                         "aggregate tokens/s, admitted-p99 TTFT, shed "
                         "rate, and scale reaction times")
    ap.add_argument("--kvcache", action="store_true",
                    help="paged-KV mode (docs/serving.md §Paged KV & prefix "
                         "caching): an 80%%-shared system-prompt batch plus "
                         "3-turn sessions, run with the cache on vs off — "
                         "records prefill-FLOPs reduction, hit rate, and "
                         "TTFT p50/p99 both ways at bit-identical outputs")
    ap.add_argument("--kvtiers", action="store_true",
                    help="KV-tiering mode (docs/serving.md §KV tiering): "
                         "a session fleet whose parked KV working set is "
                         "~4x the device page pool, vs an all-HBM "
                         "reference — records tokens/s at 4x, the "
                         "T0-resident overhead ratio, and the swap-hide "
                         "ratio at bit-identical outputs")
    ap.add_argument("--tenants", action="store_true",
                    help="mixed-tenant isolation mode (docs/serving.md "
                         "§Front-door): a quiet tenant's seeded stream "
                         "run solo vs next to a noisy tenant offered "
                         "10x its token-bucket quota — records the "
                         "quiet tenant's admitted p99 TTFT both ways "
                         "plus the noisy throttle rate")
    ap.add_argument("--overload", action="store_true",
                    help="overload-resilience mode: arm the estimated-TTFT "
                         "shedder (--slo-ttft-ms) and run 2x/4x offered load, "
                         "recording shed-rate + admitted-p99 TTFT")
    ap.add_argument("--slo-ttft-ms", type=float, default=500.0,
                    help="serving.slo_ttft_ms for --overload engines")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Chrome-trace/Perfetto trace.json of the "
                         "run's spans (per-request lifecycles + step phases)")
    args = ap.parse_args()

    import jax

    import deepspeed_tpu
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.config.config import TelemetryConfig
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.utils.device import on_tpu_backend

    # arm the process plane before any engine is built; tracing only
    # when requested (the span buffer is a ring, but why pay for it)
    telemetry.configure(
        TelemetryConfig(trace=bool(args.trace), trace_path=args.trace or ""),
        label="bench_serving",
    )

    on_tpu = on_tpu_backend()
    if args.dryrun or not on_tpu:
        model, slots, chunk, max_len = "tiny", 4, 16, 128
        n_req, max_new, lo, hi = 12, 8, 4, 48
        quantize_bits = 0
    else:
        model, slots, chunk, max_len = (args.model or "gpt2-xl"), 8, 128, 512
        n_req, max_new, lo, hi = 32, 64, 32, 384
        quantize_bits = 8  # int8 weights: the serving-optimized decode path
    n_req = args.requests or n_req
    max_new = args.max_new or max_new
    slots = args.num_slots or slots
    chunk = args.prefill_chunk or chunk
    if args.overload and args.loads == "0.5,1.0,2.0":
        args.loads = "2.0,4.0"  # the shed regime, unless --loads overrides
    loads = [float(x) for x in args.loads.split(",") if x]

    t0 = time.monotonic()
    engine = deepspeed_tpu.init_inference(
        model=model, quantize_bits=quantize_bits, max_out_tokens=max_len,
        init_on_device=on_tpu and not args.dryrun,
    )
    log(f"engine ready in {time.monotonic()-t0:.1f}s (model={model})")
    workload = build_workload(
        n_req, lo, hi, max_new, args.seed, engine.model_config.vocab_size
    )

    if args.fleet:
        run_fleet_bench(engine, args, slots, chunk, max_len, max_new,
                        workload, model)
        if args.trace:
            path = telemetry.export_trace(args.trace)
            log(f"trace exported -> {path}")
        return

    if args.elastic:
        run_elastic_bench(engine, args, slots, chunk, max_len, max_new,
                          workload, model)
        if args.trace:
            path = telemetry.export_trace(args.trace)
            log(f"trace exported -> {path}")
        return

    if args.tenants:
        run_tenant_bench(engine, args, slots, chunk, max_len, max_new, model)
        if args.trace:
            path = telemetry.export_trace(args.trace)
            log(f"trace exported -> {path}")
        return

    if args.kvcache:
        run_kvcache_bench(engine, args, slots, chunk, max_len, max_new, model)
        if args.trace:
            path = telemetry.export_trace(args.trace)
            log(f"trace exported -> {path}")
        return

    if args.kvtiers:
        run_kvtiers_bench(engine, args, slots, chunk, max_len, max_new, model)
        if args.trace:
            path = telemetry.export_trace(args.trace)
            log(f"trace exported -> {path}")
        return

    kvs = ("model", "int8") if args.kv == "both" else (args.kv,)
    for kv in kvs:
        # dryrun engines are f32 but keep the "bf16" tag so the rung's
        # metric names stay stable across dev and TPU runs
        tag = "int8" if kv == "int8" else "bf16"

        def make_serving():
            kw = {}
            if args.overload:
                # arm the admission controller; the capacity measurement
                # below stays unshedded (closed-loop never queues deep)
                kw["slo_ttft_ms"] = args.slo_ttft_ms
            return ServingEngine(
                engine, num_slots=slots, prefill_chunk=chunk, max_len=max_len,
                kv_cache_dtype=kv, max_queue=args.max_queue, max_new_tokens=max_new,
                **kw,
            )

        tok_s, req_s, dt = run_closed_loop(make_serving, workload)
        log(f"[{tag}] closed-loop capacity: {tok_s:,.0f} tok/s, "
            f"{req_s:.2f} req/s over {dt:.1f}s")
        for load in loads:
            rec = run_load(make_serving, workload, max(req_s * load, 1e-3),
                           seed=args.seed + int(load * 1000))
            prefix = "serving_overload" if args.overload else "serving"
            rec = {
                "metric": f"{prefix}_{model.replace('-', '_')}_{tag}kv_load{load:g}",
                "value": rec.pop("tokens_per_s"),
                "unit": "tokens/s",
                "kv_cache_dtype": tag,
                "load_fraction": load,
                **({"slo_ttft_ms": args.slo_ttft_ms} if args.overload else {}),
                "num_slots": slots,
                "prefill_chunk": chunk,
                "max_len": max_len,
                "requests": n_req,
                **rec,
            }
            emit(rec)
            log(f"[{tag}] load {load:g}x: {rec['value']} tok/s, "
                f"ttft p50/p99 {rec['ttft_p50_ms']}/{rec['ttft_p99_ms']} ms, "
                f"tpot p50/p99 {rec['tpot_p50_ms']}/{rec['tpot_p99_ms']} ms, "
                f"queue {rec['queue_depth']}"
                + (f", shed_rate {rec['shed_rate']:.1%} "
                   f"(admitted p99 {rec['ttft_submit_p99_ms']} ms vs "
                   f"SLO {args.slo_ttft_ms:g})" if args.overload else ""))

    if args.trace:
        path = telemetry.export_trace(args.trace)
        log(f"trace exported -> {path}")


if __name__ == "__main__":
    main()
