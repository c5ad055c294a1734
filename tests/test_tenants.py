"""Multi-tenant fairness / SLO / quota / accounting tests (ISSUE 20;
docs/serving.md §Front-door).

Unit level: the token bucket's exact-accounting invariant
(``burst + refilled - consumed == tokens``), throttle retry_after
math, WFQ start-time fair queueing (a flooding tenant cannot starve a
quiet one), SLO-class → priority mapping, and config validation.
Pool level: per-tenant KV page quotas (over-quota allocs DEFER and the
budget frees at retire) and pinned-prefix quotas (over-quota pins
degrade to evictable entries).  Engine level: per-tenant billing at
retire reconciling exactly with the journal's
:func:`journal_tenant_totals`, SLO classes observable as scheduler
priorities, and journal replay bypassing the bucket (no double-charge
after a crash).
"""
import dataclasses
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config.config import (
    DeepSpeedConfigError,
    FrontdoorConfig,
    TenantsConfig,
)
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.frontdoor.tenants import (
    DEFAULT_TENANT,
    SLO_CLASSES,
    TenantRegistry,
    TenantThrottled,
    TokenBucket,
    journal_tenant_totals,
)
from deepspeed_tpu.serving.kvcache.pages import PagedKVPool

pytestmark = pytest.mark.serving

TINY = dataclasses.replace(gpt2.GPT2_TINY, remat=False)


@pytest.fixture(scope="module")
def eng():
    params = gpt2.init_params(TINY, seed=7)
    params["wpe"] = params["wpe"] * 40.0
    return deepspeed_tpu.init_inference(
        model_config=TINY, params=params, dtype=jnp.float32,
        max_out_tokens=TINY.n_positions,
    )


def _registry(**overrides):
    reg = TenantRegistry()
    reg._overrides = overrides
    return reg


def _invariant(b):
    assert b.burst + b.refilled - b.consumed == pytest.approx(b.tokens)


# ---------------------------------------------------------------------------
# token bucket
# ---------------------------------------------------------------------------

def test_token_bucket_refill_caps_at_burst_and_keeps_invariant():
    b = TokenBucket(rate=10.0, burst=20.0)
    b.refill(now=0.0)  # first touch only stamps the clock
    assert b.tokens == 20.0 and b.refilled == 0.0
    assert b.take(15.0, now=0.0) is None
    _invariant(b)
    b.refill(now=1.0)  # +10, 5 -> 15
    assert b.tokens == pytest.approx(15.0)
    b.refill(now=100.0)  # caps at burst, refilled counts only real adds
    assert b.tokens == pytest.approx(20.0)
    _invariant(b)


def test_token_bucket_take_deficit_returns_refill_time():
    b = TokenBucket(rate=4.0, burst=8.0)
    b.refill(now=0.0)
    assert b.take(6.0, now=0.0) is None
    # 2 left, cost 6: deficit 4 at 4/s -> 1s
    assert b.take(6.0, now=0.0) == pytest.approx(1.0)
    assert b.consumed == 6.0  # failed take consumes nothing
    _invariant(b)


def test_token_bucket_zero_rate_never_refills():
    b = TokenBucket(rate=0.0, burst=4.0)
    b.refill(now=0.0)
    assert b.take(4.0, now=0.0) is None
    assert b.take(1.0, now=1e9) == 60.0  # can never cover: long hint
    _invariant(b)


# ---------------------------------------------------------------------------
# registry: admission, priorities, WFQ
# ---------------------------------------------------------------------------

def test_registry_throttles_with_retry_after_and_counts():
    reg = _registry(acme={"refill_tokens_per_second": 2.0,
                          "burst_tokens": 10.0})
    reg.admit("acme", cost=8.0, now=0.0)
    with pytest.raises(TenantThrottled) as ei:
        reg.admit("acme", cost=8.0, now=0.0)
    # 2 tokens left, deficit 6 at 2/s -> 3s
    assert ei.value.retry_after == pytest.approx(3.0)
    snap = reg.snapshot()["acme"]
    assert snap["submitted"] == 2 and snap["throttled"] == 1
    # other tenants are untouched (default spec 0/0 = unlimited)
    for _ in range(50):
        reg.admit("quiet", cost=100.0, now=0.0)
    assert reg.snapshot()["quiet"]["throttled"] == 0


def test_registry_rate_limit_kill_switch():
    reg = _registry(acme={"refill_tokens_per_second": 1.0,
                          "burst_tokens": 1.0})
    reg.rate_limit_enabled = False
    for _ in range(10):
        reg.admit("acme", cost=100.0, now=0.0)


def test_priority_for_explicit_wins_then_slo_class():
    reg = _registry(gold={"slo_class": "gold"},
                    bronze={"slo_class": "bronze"})
    assert reg.priority_for("gold", None) == 0
    assert reg.priority_for("bronze", None) == 2
    assert reg.priority_for("unconfigured", None) == 1  # silver default
    assert reg.priority_for("bronze", 0) == 0  # explicit wins
    assert SLO_CLASSES == {"gold": 0, "silver": 1, "bronze": 2}


def _q(tenant, tag, priority=1):
    return SimpleNamespace(tenant=tenant, wfq_tag=tag, priority=priority)


def test_wfq_flooding_tenant_cannot_starve_quiet_one():
    """The noisy tenant's virtual clock advances with every submit; the
    quiet tenant's next tag stays at the global vtime, so it pops
    first no matter how deep the noisy backlog is."""
    reg = _registry()
    noisy = [_q("noisy", reg.tag("noisy", cost=10.0)) for _ in range(20)]
    quiet = _q("quiet", reg.tag("quiet", cost=10.0))
    queue = noisy + [quiet]  # quiet submitted LAST, behind 20 noisy
    # both head tags are 0.0 (nothing popped yet); after at most one
    # noisy pop the noisy clock is far ahead and quiet pops next —
    # NOT after the 20-deep backlog
    first_two = [queue.pop(reg.pick(queue)) for _ in range(2)]
    assert quiet in first_two
    # and within one tenant: priority first, then FIFO
    reg2 = _registry()
    a = _q("t", reg2.tag("t", 1.0), priority=1)
    b = _q("t", reg2.tag("t", 1.0), priority=0)
    c = _q("t", reg2.tag("t", 1.0), priority=0)
    assert [a, b, c][reg2.pick([a, b, c])] is b


def test_wfq_weight_scales_fair_share():
    """weight=2 advances the virtual clock half as fast — the heavy
    tenant gets twice the picks over an interleaved backlog."""
    reg = _registry(heavy={"weight": 2.0})
    queue = []
    for _ in range(6):
        queue.append(_q("heavy", reg.tag("heavy", cost=10.0)))
        queue.append(_q("light", reg.tag("light", cost=10.0)))
    picks = []
    for _ in range(9):
        i = reg.pick(queue)
        picks.append(queue.pop(i).tenant)
    assert picks.count("heavy") == 6 and picks.count("light") == 3


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_tenants_config_rejects_unknown_override_keys():
    with pytest.raises(DeepSpeedConfigError, match="unknown keys"):
        TenantsConfig.from_dict(
            {"overrides": {"acme": {"refill_rate": 1.0}}})
    with pytest.raises(DeepSpeedConfigError, match="slo_class"):
        TenantsConfig.from_dict(
            {"overrides": {"acme": {"slo_class": "platinum"}}})
    with pytest.raises(DeepSpeedConfigError, match="weight"):
        TenantsConfig.from_dict({"weight": 0.0})
    cfg = TenantsConfig.from_dict(
        {"enabled": True, "overrides": {"acme": {"burst_tokens": 5}}})
    assert cfg.overrides["acme"]["burst_tokens"] == 5


def test_frontdoor_config_validates():
    with pytest.raises(DeepSpeedConfigError, match="port"):
        FrontdoorConfig.from_dict({"port": 99999})
    with pytest.raises(DeepSpeedConfigError, match="stream_poll_seconds"):
        FrontdoorConfig.from_dict({"stream_poll_seconds": 0})
    with pytest.raises(DeepSpeedConfigError):
        FrontdoorConfig.from_dict({"bogus": 1})
    assert FrontdoorConfig.from_dict({"port": 0}).port == 0


# ---------------------------------------------------------------------------
# kv quotas (pool level, real device arrays)
# ---------------------------------------------------------------------------

class _KReq:
    def __init__(self, rid, prompt, max_new=2, tenant=None):
        self.request_id = rid
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new_tokens = max_new
        self.session_id = None
        self.tenant = tenant
        self.prefill_pos = 0
        self.prefix_hint = 0
        self.slot = None
        self.generated = []
        self.finish_reason = None


def _pool(**kw):
    kw.setdefault("page_len", 8)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("kv_dtype", jnp.float32)
    return PagedKVPool(2, 2, 2, 32, 4, **kw)


def test_kv_page_quota_defers_and_frees_at_retire():
    pool = _pool()
    pool.attach_tenants(_registry(capped={"kv_pages_max": 1}))
    # 6-token prompt + 2 new = 8 = exactly one fresh page
    r0 = _KReq("r0", [1, 2, 3, 4, 5, 6], tenant="capped")
    r0.slot = pool.alloc_request(r0)
    assert r0.slot is not None
    assert pool._tenant_pages["capped"] == 1
    # second alloc for the same tenant: over cap -> DEFERS (None)
    r1 = _KReq("r1", [9, 10, 11, 12, 13, 14], tenant="capped")
    assert pool.alloc_request(r1) is None
    assert pool.tenant_quota_defers == 1
    assert pool.tenants.snapshot()["capped"]["quota_defers"] == 1
    # a different tenant is unaffected — that is the point of the quota
    r2 = _KReq("r2", [20, 21, 22, 23, 24, 25], tenant="other")
    r2.slot = pool.alloc_request(r2)
    assert r2.slot is not None
    pool.retire(r2.slot, r2)
    # retiring the capped tenant's slot frees its budget
    pool.retire(r0.slot, r0)
    assert "capped" not in pool._tenant_pages
    r1.slot = pool.alloc_request(r1)
    assert r1.slot is not None
    pool.retire(r1.slot, r1)


def test_kv_page_quota_charges_only_fresh_pages():
    """Reused shared pages are free: a prefix hit under quota pressure
    must not count the shared pages against the reader's cap."""
    pool = _pool()
    pool.attach_tenants(_registry(reader={"kv_pages_max": 2}))
    r0 = _KReq("r0", [1, 2, 3, 4, 5, 6, 7, 8], max_new=2, tenant="writer")
    r0.slot = pool.alloc_request(r0)
    pool.learn_prefix(r0)
    pool.retire(r0.slot, r0)
    # reader hits the 8-token prefix (1 page reused) and needs pages
    # for the rest; the reuse is not charged
    r1 = _KReq("r1", [1, 2, 3, 4, 5, 6, 7, 8] + [30] * 8, max_new=2,
               tenant="reader")
    r1.slot = pool.alloc_request(r1)
    assert r1.slot is not None and r1.prefix_hint == 8
    assert pool._tenant_pages["reader"] <= 2
    pool.retire(r1.slot, r1)


def test_pinned_prefix_quota_degrades_to_unpinned():
    pool = _pool(pinned_prefixes=[[1, 2, 3, 4], [5, 6, 7, 8]])
    pool.attach_tenants(_registry(pinner={"pinned_prefixes_max": 1}))
    r0 = _KReq("r0", [1, 2, 3, 4, 9, 9], tenant="pinner")
    r0.slot = pool.alloc_request(r0)
    pool.learn_prefix(r0)
    pool.retire(r0.slot, r0)
    assert pool._tenant_pinned["pinner"] == 1
    assert pool.index.lookup(np.array([1, 2, 3, 4, 99])).pinned
    # second pinned spec for the same tenant: over quota -> the entry
    # survives but UNPINNED (evictable under pressure)
    r1 = _KReq("r1", [5, 6, 7, 8, 9, 9], tenant="pinner")
    r1.slot = pool.alloc_request(r1)
    pool.learn_prefix(r1)
    pool.retire(r1.slot, r1)
    assert pool.tenant_pin_rejects == 1
    assert pool._tenant_pinned["pinner"] == 1
    assert not pool.index.lookup(np.array([5, 6, 7, 8, 99])).pinned


# ---------------------------------------------------------------------------
# engine integration: billing + journal reconciliation + replay
# ---------------------------------------------------------------------------

def _run_all(srv, rids):
    for _ in range(3000):
        srv.step()
        if all(srv.scheduler.request(rid) is not None
               and srv.scheduler.request(rid).finish_time is not None
               for rid in rids):
            return
    raise AssertionError("requests did not finish")


def _prompt(seed, n=6):
    rng = np.random.default_rng(seed)
    return rng.integers(1, TINY.vocab_size, n, dtype=np.int32)


def test_engine_bills_tenants_and_journal_reconciles(eng, tmp_path):
    srv = ServingEngine(
        eng, num_slots=2, prefill_chunk=8, max_len=64,
        journal_dir=str(tmp_path / "journal"),
        tenants={"enabled": True},  # unlimited buckets, full accounting
    )
    rids = {}
    for i, tenant in enumerate(["acme", "acme", "globex", None]):
        rid = srv.submit(_prompt(seed=i), max_new_tokens=4, tenant=tenant)
        rids.setdefault(tenant or DEFAULT_TENANT, []).append(rid)
    _run_all(srv, [r for v in rids.values() for r in v])
    srv._journal_commit()
    snap = srv.tenants.snapshot()
    totals = journal_tenant_totals(str(tmp_path / "journal"))
    for tenant, ids in rids.items():
        gen = sum(len(srv.scheduler.request(r).generated) for r in ids)
        assert snap[tenant]["admitted"] == len(ids)
        assert snap[tenant]["billed_tokens"] == gen > 0
        # the journal's durable twin agrees EXACTLY
        assert totals[tenant]["admitted"] == len(ids)
        assert totals[tenant]["billed_tokens"] == gen
        assert totals[tenant]["retired"] == len(ids)


def test_slo_class_sets_scheduler_priority(eng):
    srv = ServingEngine(
        eng, num_slots=2, prefill_chunk=8, max_len=64,
        tenants={"enabled": True,
                 "overrides": {"gold_t": {"slo_class": "gold"},
                               "bronze_t": {"slo_class": "bronze"}}},
    )
    r_gold = srv.submit(_prompt(seed=20), max_new_tokens=2, tenant="gold_t")
    r_bronze = srv.submit(_prompt(seed=21), max_new_tokens=2,
                          tenant="bronze_t")
    r_explicit = srv.submit(_prompt(seed=22), max_new_tokens=2,
                            tenant="bronze_t", priority=0)
    assert srv.scheduler.request(r_gold).priority == 0
    assert srv.scheduler.request(r_bronze).priority == 2
    assert srv.scheduler.request(r_explicit).priority == 0


def test_replay_bypasses_bucket_no_double_charge(eng, tmp_path):
    """A journaled-but-unfinished request replays after a crash even
    though the tenant's bucket is empty: admission happened before the
    crash, and a replay must never double-charge."""
    jdir = str(tmp_path / "journal")
    tenants = {"enabled": True,
               "overrides": {"acme": {"refill_tokens_per_second": 0.0,
                                      "burst_tokens": 12.0}}}
    srv1 = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64,
                         journal_dir=jdir, tenants=tenants)
    rid = srv1.submit(_prompt(seed=30), max_new_tokens=4, tenant="acme")
    # bucket now at 2/12; the same submit again is throttled
    with pytest.raises(TenantThrottled):
        srv1.submit(_prompt(seed=31), max_new_tokens=4, tenant="acme")
    srv1._journal.close()  # "crash": rid never ran
    srv2 = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64,
                         journal_dir=jdir, tenants=tenants)
    assert srv2.recover() == [rid]
    snap = srv2.tenants.snapshot()["acme"]
    assert snap["replayed"] == 1 and snap["throttled"] == 0
    # the restarted registry's bucket starts full and the replay did
    # NOT charge it (a replay must never double-bill admission)
    assert snap["bucket_tokens"] == pytest.approx(12.0)
    _run_all(srv2, [rid])
    assert srv2.scheduler.request(rid).finish_time is not None


# ---------------------------------------------------------------------------
# isolation under a noisy neighbour, on the scheduler's own clock
# ---------------------------------------------------------------------------

def _drive_in_steps(srv, clock, schedule):
    """Open loop in virtual time: ``schedule`` is ``[(arrival_step,
    tenant, prompt)]``, the engine's clock reads one second a scheduler
    step.  Returns per-tenant TTFTs in steps and rejected submits."""
    from deepspeed_tpu.serving import ServingQueueFull

    pending = sorted(schedule, key=lambda a: a[0])
    rids, rejected, steps = {}, {}, 0
    while pending or srv.scheduler.has_work():
        while pending and pending[0][0] <= steps:
            _, tenant, prompt = pending.pop(0)
            try:
                rids[srv.submit(prompt, max_new_tokens=4, tenant=tenant)] = tenant
            except ServingQueueFull:
                rejected[tenant] = rejected.get(tenant, 0) + 1
        srv.step()
        steps += 1
        clock.now = float(steps)
        assert steps < 5000, "schedule did not drain"
    ttft = {}
    for rid, tenant in rids.items():
        r = srv.scheduler.request(rid)
        ttft.setdefault(tenant, []).append(r.first_token_step - r.submit_step)
    return ttft, rejected


def test_quiet_tenant_ttft_steps_hold_under_10x_noisy_neighbour(eng, monkeypatch):
    """The isolation gate: a quiet tenant's median submit-to-first-token
    in scheduler steps next to a neighbour offered 10x its token-bucket
    quota stays within 2x its solo value; the bucket throttles the
    neighbour and every quiet request completes.  Arrivals, the bucket
    and the TTFT all read scheduler steps, so host load cannot move it."""
    from deepspeed_tpu.serving import engine as serving_engine

    clock = SimpleNamespace(now=0.0, monotonic=lambda: clock.now)
    monkeypatch.setattr(serving_engine, "time", clock)
    rng = np.random.default_rng(0)
    prompts = [_prompt(seed=100 + i, n=int(rng.integers(4, 25))) for i in range(12)]
    cost = float(np.mean([len(p) + 4 for p in prompts]))

    # capacity in requests a step, from a closed loop of the same prompts
    srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64)
    for p in prompts:
        srv.submit(p, max_new_tokens=4)
    steps = 0
    while srv.scheduler.has_work():
        srv.step()
        steps += 1
    capacity = len(prompts) / steps
    noisy_quota = 0.3 * capacity  # the bucket's sustained rate, in requests a step

    def make():
        clock.now = 0.0
        noisy = {"refill_tokens_per_second": noisy_quota * cost,
                 "burst_tokens": 2.0 * cost, "slo_class": "bronze"}
        return ServingEngine(
            eng, num_slots=2, prefill_chunk=8, max_len=64,
            tenants={"enabled": True,
                     "overrides": {"quiet": {"slo_class": "gold"}, "noisy": noisy}},
        )

    quiet_at = np.cumsum(rng.exponential(1.0 / (0.4 * capacity), size=len(prompts)))
    quiet = [(a, "quiet", p) for a, p in zip(quiet_at, prompts)]
    n_noisy = int(10.0 * noisy_quota * quiet_at[-1]) + 1
    noisy_at = np.cumsum(rng.exponential(1.0 / (10.0 * noisy_quota), size=n_noisy))
    noisy = [(a, "noisy", prompts[i % len(prompts)]) for i, a in enumerate(noisy_at)]

    solo, _ = _drive_in_steps(make(), clock, quiet)
    mixed, rejected = _drive_in_steps(make(), clock, quiet + noisy)
    assert len(mixed["quiet"]) == len(prompts) and "quiet" not in rejected
    assert rejected.get("noisy", 0) > 0
    assert len(mixed.get("noisy", [])) >= 1
    ratio = np.median(mixed["quiet"]) / np.median(solo["quiet"])
    assert ratio <= 2.0, (solo, mixed)
