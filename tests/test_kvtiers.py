"""Hierarchical KV tiering tests (ISSUE 18; docs/serving.md §KV
tiering).

Coverage matrix: engine-level bit-match of a 3-turn tiered session fleet
vs the all-HBM paged pool (full T0 -> T1 -> T2 -> T0 cascade exercised);
residency-window tail demotion + promote-before-rebind; the idle-engine
satellite (``stats()``/``drain()`` tick the migration queue with no
steps running); T1 host-cap cascade to disk and demand promotion back;
``recover()`` trusting only manifest-committed stages (torn dirs
invisible, newest generation wins); the kill -9 mid-demotion chaos (a
committed session survives the crash, the torn one re-prefills, both
bit-identical); scheduler prefetch hints; tier-priced fleet affinity
(warm > host > disk, float-preserving router scoring); and compile
stability under an armed ds_san churn with tiering active (the
exactly-two-executables contract holds through swaps).
"""
import dataclasses
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.sanitizer import core as san_core
from deepspeed_tpu.analysis.sanitizer.core import Sanitizer
from deepspeed_tpu.config.config import SanitizerConfig
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.fleet import FleetRouter
from deepspeed_tpu.serving.kvcache import PageTierManager

pytestmark = pytest.mark.serving

TINY = dataclasses.replace(gpt2.GPT2_TINY, remat=False)


@pytest.fixture(scope="module")
def eng():
    """Position-sensitive engine (wpe scaled) shared across the module —
    tier scatter/gather bugs change generations instead of hiding."""
    params = gpt2.init_params(TINY, seed=7)
    params["wpe"] = params["wpe"] * 40.0
    return deepspeed_tpu.init_inference(
        model_config=TINY, params=params, dtype=jnp.float32,
        max_out_tokens=TINY.n_positions,
    )


def _prompts(n, lo, hi, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, TINY.vocab_size, rng.integers(lo, hi + 1), dtype=np.int32)
        for _ in range(n)
    ]


def _solo(eng, prompt, max_new):
    return np.asarray(eng.generate(prompt[None, :], max_new_tokens=max_new))[0]


def _tsrv(eng, tmp_path, tiers=None, **kw):
    """Tiered serving engine with test-sized defaults; ``tiers=None``
    builds the all-HBM reference over the same pool shape."""
    kw.setdefault("num_slots", 2)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_len", 64)
    kv = kw.pop("kvcache", {})
    kv.setdefault("enabled", True)
    kv.setdefault("page_len", 16)
    # one pool shape for the whole module — every test hits the same
    # compiled executables; tier pressure comes from the watermark and
    # host-cap knobs, not from shrinking the device pool
    kv.setdefault("num_pages", 24)
    if tiers is not None:
        t = {"enabled": True, "disk_dir": str(tmp_path / "t2")}
        t.update(tiers)
        kv["tiers"] = t
    return ServingEngine(eng, kvcache=kv, **kw)


def _turns(srv, n_turns=3, n_sess=3, seed=3, max_new=4):
    """Seeded multi-turn session schedule; returns generated arrays
    keyed by (turn, session)."""
    rng = np.random.default_rng(seed)
    out, hist = {}, {}
    for turn in range(n_turns):
        batch = []
        for s in range(n_sess):
            sid = f"sess-{s}"
            prev = hist.get(sid, np.array([], np.int32))
            prompt = np.concatenate(
                [prev, rng.integers(1, TINY.vocab_size, 10, dtype=np.int32)]
            ).astype(np.int32)
            rid = srv.submit(prompt, max_new_tokens=max_new,
                             temperature=0.0, session_id=sid)
            batch.append((rid, sid, prompt))
        res = srv.drain(max_steps=2000)
        for rid, sid, prompt in batch:
            gen = np.asarray(res[rid].generated, np.int32)
            hist[sid] = np.concatenate([prompt, gen]).astype(np.int32)
            out[(turn, sid)] = gen
    return out


# ---------------------------------------------------------------------------
# engine-level bit-match: tiered vs all-HBM under the same schedule
# ---------------------------------------------------------------------------

def test_tiered_multiturn_bit_identical_vs_all_hbm(eng, tmp_path):
    """The tentpole proof: a T0 pool a quarter of the working set, host
    and disk tiers absorbing the rest — same outputs, same two compiled
    executables, the full demote/promote cascade actually exercised."""
    ref = _turns(_tsrv(eng, tmp_path), n_sess=4)
    srv = _tsrv(eng, tmp_path,
                tiers={"host_pages": 8, "residency_window": 16,
                       "demote_watermark": 0.25, "demote_batch": 8})
    got = _turns(srv, n_sess=4)
    assert sorted(got) == sorted(ref)
    for key in ref:
        np.testing.assert_array_equal(got[key], ref[key], err_msg=str(key))
    st = srv.stats()["kvcache"]["tiers"]
    assert st["demote_t0_t1"] > 0, st
    assert st["demote_t1_t2"] > 0, st
    assert st["promote_t1_t0"] + st["promote_t2_t0"] > 0, st
    assert st["hits_t1"] + st["hits_t2"] > 0, st
    assert srv.prefill_compiles == 1 and srv.decode_compiles == 1
    srv._tiers.close()


def test_a_tier_tick_moves_pages_in_a_step_that_finds_a_chunk_unread(eng, tmp_path):
    """The default order of a step leaves a chunk that is not its
    prompt's last on the device; the tiers' ``tick`` of the next step
    gathers and scatters pages of the pool that chunk hands back, so its
    traffic queues behind the chunk and the outputs are the all-HBM
    engine's."""
    ref = _turns(_tsrv(eng, tmp_path), n_sess=4)
    srv = _tsrv(eng, tmp_path,
                tiers={"host_pages": 8, "residency_window": 16,
                       "demote_watermark": 0.25, "demote_batch": 8})
    assert srv.config.overlap_chunks is True
    tiers, tick, seen = srv._tiers, srv._tiers.tick, []

    def spy(now, hints=()):
        moved0, unread = tiers.demote_t0_t1 + tiers.promote_t1_t0 + tiers.promote_t2_t0, len(srv._unread_chunks)
        tick(now, hints=hints)
        seen.append((unread, tiers.demote_t0_t1 + tiers.promote_t1_t0 + tiers.promote_t2_t0 - moved0))

    tiers.tick = spy
    got = _turns(srv, n_sess=4)
    assert sorted(got) == sorted(ref)
    for key in ref:
        np.testing.assert_array_equal(got[key], ref[key], err_msg=str(key))
    assert any(unread and moved for unread, moved in seen), seen  # pages moved while a chunk was unread
    st = srv.stats()
    assert st["chunks_deferred"] > 0 and not srv._unread_chunks
    assert st["kvcache"]["tiers"]["demote_t0_t1"] > 0 and srv.prefill_compiles == 1 and srv.decode_compiles == 1
    srv._tiers.close()


def test_sessions_at_4x_device_kv_capacity_no_queue_full(eng, tmp_path):
    """The capacity gate: eight 3-turn sessions whose parked KV is four
    times the device pool's usable pages are all served — no submit
    raises ``ServingQueueFull``, the tiers swap, and every output is
    bit-identical to an all-HBM pool that holds the whole working set."""
    page_len, tail_len, budget, n_sess, n_turns = 16, 8, 4, 8, 3
    pages_for = lambda tokens: -(-tokens // page_len)
    working_set = n_sess * pages_for(n_turns * (tail_len + budget) - 1)
    per_request = pages_for(n_turns * (tail_len + budget)) + 1  # +1 copy-on-write page
    t0_pages = max(-(-working_set // 4), per_request + 1)
    assert working_set >= 4 * t0_pages
    rng = np.random.default_rng(0)
    tails = [[rng.integers(1, TINY.vocab_size, tail_len, dtype=np.int32)
              for _ in range(n_turns)] for _ in range(n_sess)]

    def run(srv):
        outputs, hist = [], [np.array([], np.int32)] * n_sess
        for turn in range(n_turns):
            prompts = [np.concatenate([hist[s], tails[s][turn]]).astype(np.int32)
                       for s in range(n_sess)]
            rids = [srv.submit(prompts[s], max_new_tokens=budget, session_id=f"sess-{s}")
                    for s in range(n_sess)]  # ServingQueueFull here fails the test
            res = srv.drain(max_steps=5000)
            for s, rid in enumerate(rids):
                gen = np.asarray(res[rid].generated, np.int32)
                outputs.append(gen)
                hist[s] = np.concatenate([prompts[s], gen])
        return outputs

    ref = run(_tsrv(eng, tmp_path, kvcache={"num_pages": 2 * working_set}))
    srv = _tsrv(eng, tmp_path, kvcache={"num_pages": t0_pages + 1},
                tiers={"host_pages": t0_pages, "residency_window": page_len,
                       "demote_watermark": 0.5, "demote_batch": 8, "prefetch_ahead": 2})
    got = run(srv)
    st = srv.stats()
    srv._tiers.close()
    assert len(got) == len(ref) == n_sess * n_turns
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    assert st.get("rejected", 0) == 0
    tiers = st["kvcache"]["tiers"]
    assert tiers["demote_t0_t1"] + tiers["promote_t1_t0"] + tiers["promote_t2_t0"] > 0, tiers


@pytest.mark.slow  # tier-1 wall budget; the kvcache-tiers CI job runs it
def test_tail_residency_window_demote_and_rebind(eng, tmp_path):
    """A parked session keeps only its residency window in T0; the tier
    manager holds the tail and pages it back in ahead of the rebind —
    turn 2 still bit-matches solo."""
    srv = _tsrv(eng, tmp_path,
                tiers={"residency_window": 16, "demote_batch": 4})
    p1 = _prompts(1, 30, 30, seed=11)[0]
    r1 = srv.submit(p1, max_new_tokens=4, temperature=0.0, session_id="s")
    res = srv.drain(max_steps=500)
    t1 = np.asarray(res[r1].tokens())
    np.testing.assert_array_equal(t1, _solo(eng, p1, 4))
    for _ in range(6):  # idle ticks trim the parked tail
        srv.stats()
    st = srv.pool.stats()["tiers"]
    assert st["tail_demotions"] >= 1, st
    assert srv._tiers.has_tail("s")
    p2 = np.concatenate([t1, _prompts(1, 4, 4, seed=12)[0]])
    r2 = srv.submit(p2, max_new_tokens=4, temperature=0.0, session_id="s")
    res = srv.drain(max_steps=500)
    np.testing.assert_array_equal(res[r2].tokens(), _solo(eng, p2, 4))
    st = srv.pool.stats()["tiers"]
    assert st["tail_promotions"] >= 1, st
    assert srv.stats()["kvcache"]["session_rebinds"] == 1
    srv._tiers.close()


# ---------------------------------------------------------------------------
# the idle-engine satellite: stats()/drain() tick the migration queue
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 wall budget; the kvcache-tiers CI job runs it
def test_idle_engine_stats_and_drain_tick_migrations(eng, tmp_path):
    """A quiescent engine must still drain pending demotions: no
    ``step()`` runs between the watermark drop and the assertions —
    only ``stats()`` and an empty ``drain()`` move the pages."""
    srv = _tsrv(eng, tmp_path,
                tiers={"host_pages": 2, "demote_batch": 2})
    _turns(srv, n_turns=1, n_sess=4, seed=21)
    before = srv.pool.stats()["tiers"]
    # nothing over the default watermark yet; tighten it post-hoc so the
    # idle ticks (and only they) are what demote
    srv._tiers.demote_watermark = 0.1
    for _ in range(8):
        srv.stats()
    mid = srv.pool.stats()["tiers"]
    assert mid["demote_t0_t1"] > before["demote_t0_t1"], (before, mid)
    srv.drain()  # empty drain must also tick (and pump the worker)
    time.sleep(0.3)
    srv.stats()
    after = srv.pool.stats()["tiers"]
    # host cap 2 forces the T1 -> T2 cascade through the idle ticks too
    assert after["demote_t1_t2"] > 0, after
    srv._tiers.close()


@pytest.mark.slow  # tier-1 wall budget; the kvcache-tiers CI job runs it
def test_host_cap_cascades_to_disk_and_promotes_back(eng, tmp_path):
    """T1 over ``host_pages`` pushes LRU entries to T2; a later turn for
    a disk-resident session pages it back (T2 hit) bit-identically."""
    srv = _tsrv(eng, tmp_path,
                tiers={"host_pages": 1, "demote_batch": 8})
    p1 = _prompts(1, 12, 12, seed=31)[0]
    r1 = srv.submit(p1, max_new_tokens=4, temperature=0.0, session_id="cold")
    t1 = np.asarray(srv.drain(max_steps=500)[r1].tokens())
    srv._tiers.flush(time.monotonic())  # all warm sessions -> T1 -> T2
    st = srv.pool.stats()["tiers"]
    assert st["disk_entries"] >= 1, st
    assert not srv.pool.sessions.warm()
    p2 = np.concatenate([t1, _prompts(1, 4, 4, seed=32)[0]])
    r2 = srv.submit(p2, max_new_tokens=4, temperature=0.0, session_id="cold")
    res = srv.drain(max_steps=500)
    np.testing.assert_array_equal(res[r2].tokens(), _solo(eng, p2, 4))
    st = srv.pool.stats()["tiers"]
    assert st["hits_t2"] + st["hits_t1"] >= 1, st
    assert srv.stats()["kvcache"]["session_rebinds"] == 1
    srv._tiers.close()


# ---------------------------------------------------------------------------
# recover(): manifest-gated trust
# ---------------------------------------------------------------------------

def test_recover_ignores_torn_stage_keeps_committed(eng, tmp_path):
    """A stage without its manifest (the shape a kill mid-demotion
    leaves) is never trusted; a committed entry next to it is."""
    srv = _tsrv(eng, tmp_path,
                tiers={"host_pages": 1})
    p1 = _prompts(1, 12, 12, seed=41)[0]
    r1 = srv.submit(p1, max_new_tokens=4, temperature=0.0, session_id="good")
    srv.drain(max_steps=500)
    srv._tiers.flush(time.monotonic())
    srv._tiers.close()
    t2 = tmp_path / "t2"
    committed = [d for d in os.listdir(t2) if d.startswith("sess_")]
    assert committed
    # hand-build a torn stage: payload + meta, no manifest
    torn = t2 / "sess_deadbeefdeadbeef-g99"
    torn.mkdir()
    np.savez(torn / "kv.npz", x=np.zeros(2))
    (torn / "meta.json").write_text(
        '{"kind": "session", "session_id": "torn", "tokens": [1, 2, 3],'
        ' "leaf_dtypes": {}}')
    srv2 = _tsrv(eng, tmp_path,
                  tiers={"host_pages": 1})
    found = srv2.pool.recover()
    assert "sess:good" in found, found
    assert all("torn" not in k for k in found), found
    assert srv2._tiers.has_session("good")
    assert not srv2._tiers.has_session("torn")
    srv2._tiers.close()


def test_recover_newest_generation_wins(eng, tmp_path):
    """Two committed generations of the same session (possible when a
    crash lands between a re-demotion and the old dir's removal):
    recover registers the newer and deletes the superseded dir."""
    pool = _tsrv(eng, tmp_path, kvcache={"enabled": True, "page_len": 16}).pool
    mgr = PageTierManager(pool, disk_dir=str(tmp_path / "gens"))
    old = {"kind": "session", "session_id": "s", "tokens": [1, 2],
           "parked_at": 1.0}
    new = {"kind": "session", "session_id": "s", "tokens": [1, 2, 3, 4],
           "parked_at": 2.0}
    leaves = {"L0.k": np.zeros((1, 16, 2, 4), np.float32)}
    mgr._write_t2("sess_aaaaaaaaaaaaaaaa-g1", old, leaves)
    mgr._write_t2("sess_aaaaaaaaaaaaaaaa-g2", new, leaves)
    found = mgr.recover()
    assert found == ["sess:s"]
    e = mgr._entries["sess:s"]
    assert e.dir_name.endswith("-g2") and e.tokens.shape[0] == 4
    assert sorted(os.listdir(tmp_path / "gens")) == ["sess_aaaaaaaaaaaaaaaa-g2"]
    assert mgr._dirgen >= 2  # fresh writes never collide with survivors
    mgr.close()


# ---------------------------------------------------------------------------
# chaos: kill -9 mid-demotion -> torn stage invisible, replay identical
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~8s: crash + rebuild over the same tier dirs
def test_kill9_mid_demotion_torn_invisible_bit_identical(eng, tmp_path):
    """The ``tier.demote`` fault site sits between the staged payload
    and the manifest.  Session A's demotion commits, the injected kill
    tears session B's mid-stage.  A fresh engine + PageTierManager over
    the same dirs trusts only A; a 3-turn continuation of both sessions
    stays bit-identical (A rebinds off T2, B re-prefills)."""
    seeds = {"a": 51, "b": 52}
    hist = {}
    for name, seed in seeds.items():
        p = _prompts(1, 12, 12, seed=seed)[0]
        hist[name] = _solo(eng, p, 4)

    def build():
        return _tsrv(eng, tmp_path,
                          tiers={"host_pages": 1, "demote_batch": 8})

    srv1 = build()
    for name, seed in seeds.items():
        p = _prompts(1, 12, 12, seed=seed)[0]
        r = srv1.submit(p, max_new_tokens=4, temperature=0.0, session_id=name)
        np.testing.assert_array_equal(
            srv1.drain(max_steps=500)[r].tokens(), hist[name])
    inj = faults.FaultInjector(seed=0).kill("tier.demote", after=1)
    with pytest.raises(faults.InjectedKill):
        with inj:
            # the flush submits both demotion writes; the first commits,
            # the second dies between stage and manifest and the error
            # pump re-raises the kill on this (the engine) thread
            srv1._tiers.flush(time.monotonic())
    committed = [d for d in os.listdir(tmp_path / "t2")
                 if os.path.exists(tmp_path / "t2" / d / "manifest.json")]
    assert len(committed) == 1, committed

    srv2 = build()
    found = srv2.pool.recover()
    assert len([k for k in found if k.startswith("sess:")]) == 1, found
    for turn in range(3):
        for name, seed in seeds.items():
            p = np.concatenate(
                [hist[name], _prompts(1, 4, 4, seed=seed + 10 * turn)[0]])
            r = srv2.submit(p, max_new_tokens=4, temperature=0.0,
                            session_id=name)
            got = np.asarray(srv2.drain(max_steps=500)[r].tokens())
            np.testing.assert_array_equal(got, _solo(eng, p, 4))
            hist[name] = got
    st = srv2.pool.stats()["tiers"]
    assert st["hits_t1"] + st["hits_t2"] >= 1, st  # A's spill was used
    srv2._tiers.close()


# ---------------------------------------------------------------------------
# scheduler prefetch hints
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 wall budget; the kvcache-tiers CI job runs it
def test_scheduler_upcoming_hints_priority_then_fifo(eng, tmp_path):
    srv = _tsrv(eng, tmp_path)
    ps = _prompts(3, 8, 8, seed=61)
    srv.submit(ps[0], max_new_tokens=2, priority=1)
    srv.submit(ps[1], max_new_tokens=2, priority=0, session_id="hot")
    srv.submit(ps[2], max_new_tokens=2, priority=1)
    hints = srv.scheduler.upcoming_hints(3)
    assert len(hints) == 3
    np.testing.assert_array_equal(hints[0][0], ps[1])  # priority first
    assert hints[0][1] == "hot"
    np.testing.assert_array_equal(hints[1][0], ps[0])  # then FIFO
    assert hints[1][1] is None
    srv.drain(max_steps=500)


@pytest.mark.slow  # tier-1 wall budget; the kvcache-tiers CI job runs it
def test_prefetch_hints_page_disk_sessions_back_in(eng, tmp_path):
    """With every session flushed to disk and more submissions than
    slots, the step-boundary tick sees the queued tail as hints and
    prefetches those sessions off T2 before their prefill runs."""
    srv = _tsrv(eng, tmp_path, num_slots=2,
                tiers={"host_pages": 1, "prefetch_ahead": 4})
    first = _turns(srv, n_turns=1, n_sess=4, seed=71)
    srv._tiers.flush(time.monotonic())
    assert srv.pool.stats()["tiers"]["disk_entries"] >= 3
    got = _turns(srv, n_turns=1, n_sess=4, seed=71)
    # the second schedule replays turn 1 then extends it: every session
    # output must match the first run's (bit-identity through T2)
    for key in first:
        np.testing.assert_array_equal(got[key], first[key], err_msg=str(key))
    st = srv.pool.stats()["tiers"]
    assert st["prefetch_jobs"] >= 1, st
    assert st["hits_t1"] + st["hits_t2"] >= 1, st
    srv._tiers.close()


# ---------------------------------------------------------------------------
# tier-priced fleet affinity
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 wall budget; the kvcache-tiers CI job runs it
def test_affinity_tokens_price_residency(eng, tmp_path):
    """The same cached session is worth 1.0x warm, 0.75x in host, 0.5x
    on disk — a warm replica outbids a tiered one, which still outbids
    a cold one."""
    srv = _tsrv(eng, tmp_path,
                tiers={"host_pages": 8})
    p1 = _prompts(1, 16, 16, seed=81)[0]
    r1 = srv.submit(p1, max_new_tokens=4, temperature=0.0, session_id="s")
    t1 = np.asarray(srv.drain(max_steps=500)[r1].tokens())
    probe = np.concatenate([t1, _prompts(1, 6, 6, seed=82)[0]])
    warm_aff = srv.pool.affinity_tokens(probe, session_id="s")
    assert warm_aff > 0
    assert warm_aff == srv.pool.prefix_hint_tokens(probe, session_id="s")
    sess = next(s for s in srv.pool.sessions.warm() if s.session_id == "s")
    with srv.pool._lock:
        assert srv._tiers.demote_session(sess, time.monotonic())
        # drop the learned prefix entries (they hold T0 pages, so they
        # price at full weight and would mask the session's discount)
        for e in list(srv.pool.index.entries()):
            srv.pool.index.remove(e)
            srv.pool._page_decref(e.pages)
    host_aff = srv.pool.affinity_tokens(probe, session_id="s")
    assert host_aff == pytest.approx(0.75 * warm_aff)
    srv._tiers.flush(time.monotonic())
    srv.stats()  # pump the worker's write completions
    assert srv.pool.stats()["tiers"]["disk_entries"] >= 1
    disk_aff = srv.pool.affinity_tokens(probe, session_id="s")
    assert disk_aff == pytest.approx(0.5 * warm_aff)
    # the un-priced hint still reports the full expected hit: admission
    # TTFT estimates use post-hit budgets regardless of residency
    assert srv.pool.prefix_hint_tokens(probe, session_id="s") == warm_aff
    srv._tiers.close()


class _PricedRep:
    def __init__(self, name, aff):
        self.name, self._aff = name, aff

    def alive(self):
        return True

    def estimate_ttft(self, prompt_len):
        return 0.01 if self.name == "cold" else 0.5

    def kv_affinity(self, prompt, session_id=None):
        return self._aff

    def queue_depth(self):
        return 0

    def degrade_level(self):
        return 0

    def draining(self):
        return False


def test_router_scoring_keeps_tier_price_fractions():
    """Float affinities must survive router scoring: 0.75x host beats
    0.5x disk for the same cached length, and both beat cold."""
    host = _PricedRep("host", 16 * 0.75)
    disk = _PricedRep("disk", 16 * 0.5)
    cold = _PricedRep("cold", 0.0)
    router = FleetRouter([cold, disk, host], clock=lambda: 0.0)
    prompt = np.arange(24, dtype=np.int32)
    assert router._pick(len(prompt), set(), 0.0, prompt=prompt,
                        session_id="s") == "host"
    assert router._pick(len(prompt), {"host"}, 0.0, prompt=prompt,
                        session_id="s") == "disk"
    assert router._pick(len(prompt), {"host", "disk"}, 0.0,
                        prompt=prompt, session_id="s") == "cold"


# ---------------------------------------------------------------------------
# compile stability: armed ds_san churn with tiering active
# ---------------------------------------------------------------------------

@pytest.fixture
def san():
    cfg = SanitizerConfig.from_dict(
        {"enabled": True, "checkers": ["recompile", "transfer"], "compile_budget": 2}
    )
    s = san_core.install(Sanitizer(cfg))
    try:
        yield s
    finally:
        san_core.uninstall()


def test_tiered_churn_ds_san_clean(eng, tmp_path, san):
    """The exactly-two-executables contract survives active tiering:
    demotions, tail trims, T2 round-trips and promote-before-rebind are
    all host-side table/page plumbing — one compiled prefill + one
    compiled decode, zero ds_san findings."""
    srv = _tsrv(eng, tmp_path,
                tiers={"host_pages": 4, "residency_window": 16,
                       "demote_watermark": 0.25, "demote_batch": 8})
    assert srv._sanitizer is san
    _turns(srv, n_turns=3, n_sess=4, seed=91)
    st = srv.pool.stats()["tiers"]
    assert st["demote_t0_t1"] > 0 and st["demote_t1_t2"] > 0, st
    assert srv.prefill_compiles == 1 and srv.decode_compiles == 1
    counts = san.recompile.compile_counts()
    assert counts.get("serving.prefill") == 1, counts
    assert counts.get("serving.decode") == 1, counts
    assert san.findings == [], [f.format() for f in san.findings]
    srv._tiers.close()
