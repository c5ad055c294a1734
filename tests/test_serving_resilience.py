"""Serving resilience tests (ISSUE 10; docs/serving.md §Resilience).

The chaos matrix: seeded kill mid-decode → restart → journal replay
with outputs bit-matching an uninterrupted run; SIGTERM mid-prefill →
graceful drain → exit 43 only after the journal commits; overload at
far-past-capacity → estimated-TTFT shed with ``retry_after`` + the
degradation ladder engaging and disengaging with hysteresis; injected
journal-commit failure → clean quarantine.  Plus the idle-engine
queued-deadline sweep regression and the journal's torn-tail /
compaction unit behavior.
"""
import dataclasses
import os
import signal
import time

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config.config import DeepSpeedConfigError, ServingConfig
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving import (
    PRIORITY_HIGH,
    PRIORITY_LOW,
    RequestJournal,
    ServingDraining,
    ServingEngine,
    ServingOverloaded,
    ServingQueueFull,
)
from deepspeed_tpu.serving import journal as journal_mod

TINY = dataclasses.replace(gpt2.GPT2_TINY, remat=False)


@pytest.fixture(scope="module")
def eng():
    """Position-sensitive engine (wpe scaled) shared across the module —
    slot/position bugs change generations instead of hiding."""
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


def _srv(eng, tmp_path=None, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_len", 64)
    if tmp_path is not None:
        kw.setdefault("journal_dir", str(tmp_path / "journal"))
    return ServingEngine(eng, **kw)


# ---------------------------------------------------------------------------
# journal unit behavior (no engine)
# ---------------------------------------------------------------------------

class _Req:
    """Duck-typed scheduler Request for journal unit tests."""

    def __init__(self, rid, prompt=(1, 2, 3), max_new=4, **kw):
        self.request_id = rid
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new_tokens = max_new
        self.eos_token_id = kw.get("eos")
        self.priority = kw.get("priority", 1)
        self.deadline_seconds = None
        self.do_sample = kw.get("do_sample", False)
        self.temperature = kw.get("temperature", 1.0)
        self.top_k = kw.get("top_k", 0)
        self.seed = kw.get("seed", 0)
        self.generated = kw.get("generated", [])
        self.finish_reason = kw.get("finish_reason")


def test_journal_submit_retire_incomplete(tmp_path):
    j = RequestJournal(str(tmp_path / "j"))
    for rid in range(4):
        j.record_submit(_Req(rid, prompt=[rid + 1], max_new=3 + rid))
    j.record_retire(_Req(1, finish_reason="length"))
    j.record_retire(_Req(3, finish_reason="eos"))
    j.commit()
    inc = j.incomplete()
    assert [e["id"] for e in inc] == [0, 2]
    assert inc[0]["prompt"] == [1] and inc[0]["max_new"] == 3
    # the admit record's EFFECTIVE budget (degradation clamp) wins
    r2 = _Req(2, max_new=2)
    j.record_admit(r2)
    j.commit()
    assert [e["max_new"] for e in j.incomplete()] == [3, 2]
    j.close()


def test_journal_torn_tail_dropped_and_corrupt_middle_raises(tmp_path):
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    j.record_submit(_Req(0))
    j.record_submit(_Req(1))
    j.commit()
    j.close()
    seg = os.path.join(path, sorted(os.listdir(path))[0])
    # torn tail: append half a record (a crash mid-append)
    with open(seg, "a") as f:
        f.write('{"t":"submit","id":2,')
    inc = journal_mod.incomplete_requests(path)
    assert [e["id"] for e in inc] == [0, 1]
    # corrupt a MIDDLE line -> not a torn tail -> raises
    with open(seg) as f:
        lines = f.readlines()
    lines[0] = lines[0][:-12] + "00000000\n"  # break the first record's crc
    with open(seg, "w") as f:
        f.writelines(lines)
    with pytest.raises(journal_mod.JournalError, match="not a torn tail"):
        journal_mod.incomplete_requests(path)


def test_journal_rotation_and_compaction_bounded(tmp_path):
    path = str(tmp_path / "j")
    j = RequestJournal(path, segment_records=4, keep_segments=2)
    for rid in range(40):
        j.record_submit(_Req(rid))
        if rid % 2 == 0:
            j.record_retire(_Req(rid, finish_reason="length"))
    j.commit()
    segs = [n for n in os.listdir(path) if n.startswith("wal_")]
    # compaction keeps the sealed-segment count bounded
    assert len(segs) <= 2 + 2, segs  # keep_segments + compact + active
    inc = j.incomplete()
    assert [e["id"] for e in inc] == [r for r in range(40) if r % 2 == 1]
    j.close()
    # a reopened journal starts a FRESH segment and sees the same set
    j2 = RequestJournal(path, segment_records=4, keep_segments=2)
    assert [e["id"] for e in j2.incomplete()] == [r for r in range(40) if r % 2 == 1]
    j2.close()


# ---------------------------------------------------------------------------
# chaos: seeded kill mid-decode -> restart -> replay parity
# ---------------------------------------------------------------------------

def test_kill_mid_decode_restart_replays_bit_identical(eng, tmp_path):
    """The acceptance proof (in-process InjectedKill form; the real
    ``kill -9`` form runs in tools/serving_chaos.py and the
    serving-chaos CI job): a death mid-decode loses the process state,
    a fresh engine over the same journal replays every incomplete
    request, and greedy AND seeded-sampling outputs bit-match the
    uninterrupted run."""
    prompts = _prompts(5, 4, 20, seed=1)
    budgets = [6, 3, 5, 2, 4]

    def submit_all(srv):
        rids = []
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            # request 2 samples (seeded) — replay must reproduce it too
            kw = dict(do_sample=True, temperature=0.9, top_k=8, seed=123) if i == 2 else {}
            rids.append(srv.submit(p, max_new_tokens=n, **kw))
        return rids

    # uninterrupted reference
    srv_ref = _srv(eng, tmp_path=None)
    rids_ref = submit_all(srv_ref)
    res_ref = srv_ref.drain(max_steps=500)
    expect = [res_ref[r].tokens() for r in rids_ref]

    # killed run: die on the 3rd decode dispatch
    srv1 = _srv(eng, tmp_path=tmp_path)
    rids1 = submit_all(srv1)
    inj = faults.FaultInjector(seed=0).kill("serving.decode", after=2)
    with pytest.raises(faults.InjectedKill):
        with inj:
            srv1.drain(max_steps=500)
    finished_before = set(srv1.scheduler._finished)

    # restart: a FRESH engine over the same journal dir
    srv2 = _srv(eng, tmp_path=tmp_path)
    replayed = srv2.recover()
    assert set(replayed) == set(rids1) - finished_before
    assert replayed, "the kill must leave incomplete requests"
    res2 = srv2.drain(max_steps=500)
    for rid, exp in zip(rids1, expect):
        if rid in replayed:
            np.testing.assert_array_equal(res2[rid].tokens(), exp)
    # idempotent: a second recover on the same engine is a no-op
    assert srv2.recover() == []


@pytest.mark.parametrize("site, after", [("serving.prefill", 3), ("serving.decode", 2)], ids=["at-a-chunks-launch", "at-a-decode-launch"])
def test_kill_with_a_chunk_in_flight_acknowledges_what_the_serial_step_does_and_replays(eng, tmp_path, site, after):
    """A death while the default step has a chunk on the device unread:
    the journal holds what the serial step's holds at the same launch —
    the retirements committed at their step's boundary, the requests in
    flight as incomplete — and a fresh engine replays those with the
    uninterrupted run's tokens."""
    prompts = [_prompts(1, n, n, seed=30 + n)[0] for n in (6, 30, 12)]  # one chunk, four, two; two slots
    budgets = [2, 4, 3]

    def killed(path, **kw):
        srv = _srv(eng, tmp_path=path, **kw)
        rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
        with pytest.raises(faults.InjectedKill):
            with faults.FaultInjector(seed=0).kill(site, after=after):
                srv.drain(max_steps=500)
        return srv, rids

    ref = _srv(eng)
    ref_ids = [ref.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    expect = [r.tokens() for r in map(ref.drain(max_steps=500).get, ref_ids)]

    serial, ids0 = killed(tmp_path / "serial", overlap_chunks=False)
    srv1, ids1 = killed(tmp_path / "default")
    assert len(srv1._unread_chunks) == 1 and not serial._unread_chunks  # the chunk before the one being launched
    acked = lambda srv, ids: [ids.index(i) for i in sorted(srv.scheduler._finished)]
    assert acked(srv1, ids1) == acked(serial, ids0) == [0]  # the short request retired, and was committed, steps before
    undone = lambda path, ids: [ids.index(e["id"]) for e in journal_mod.incomplete_requests(str(path / "journal"))]
    assert undone(tmp_path / "default", ids1) == undone(tmp_path / "serial", ids0) == [1, 2]

    srv2 = _srv(eng, tmp_path=tmp_path / "default")
    assert srv2.recover() == ids1[1:]
    res = srv2.drain(max_steps=500)
    for rid, exp in zip(ids1[1:], expect[1:]):
        np.testing.assert_array_equal(res[rid].tokens(), exp)


def test_recover_without_journal_or_empty_is_noop(eng, tmp_path):
    assert _srv(eng).recover() == []
    srv = _srv(eng, tmp_path=tmp_path)
    assert srv.recover() == []


def test_restart_submit_before_recover_never_reuses_journaled_ids(eng, tmp_path):
    """Id-reuse guard: a restarted process (fresh id counter) that
    submits BEFORE recover() must not hand out an incomplete journaled
    id — the new request's retire record would silently drop the old
    acknowledged request from the replay set."""
    from deepspeed_tpu.serving import scheduler as sched_mod

    srv = _srv(eng, tmp_path=tmp_path)
    old = srv.submit(_prompts(1, 6, 6, seed=21)[0], max_new_tokens=3)
    # "restart": the process-global id counter starts over...
    sched_mod._REQUEST_IDS._n = -1
    srv2 = _srv(eng, tmp_path=tmp_path)  # ...but the journal open bumps it
    fresh = srv2.submit(_prompts(1, 6, 6, seed=22)[0], max_new_tokens=3)
    assert fresh > old
    srv2.drain(max_steps=300)  # fresh request retires
    inc = journal_mod.incomplete_requests(str(tmp_path / "journal"))
    assert old in [e["id"] for e in inc]  # the acknowledged request survived
    assert srv2.recover() == [old]
    res = srv2.drain(max_steps=300)
    assert res[old].finish_reason == "length"


def test_journal_compacts_on_open_under_restart_loop(eng, tmp_path):
    """A crash-looping service constructs a journal per restart without
    ever reaching count-based rotation; construction-time compaction
    must bound the segment count anyway."""
    path = str(tmp_path / "j")
    for i in range(12):
        j = RequestJournal(path, segment_records=512, keep_segments=3)
        j.record_submit(_Req(i))
        if i % 2:
            j.record_retire(_Req(i, finish_reason="length"))
        j.commit()
        j.close()
    segs = [n for n in os.listdir(path) if n.startswith("wal_")]
    assert len(segs) <= 3 + 2, segs  # keep_segments + compact + active
    j = RequestJournal(path, segment_records=512, keep_segments=3)
    assert [e["id"] for e in j.incomplete()] == [i for i in range(12) if not i % 2]
    assert j.last_request_id == 11
    j.close()


# ---------------------------------------------------------------------------
# chaos: SIGTERM mid-prefill -> graceful drain -> exit 43
# ---------------------------------------------------------------------------

def test_sigterm_mid_prefill_drains_and_exits_43(eng, tmp_path):
    """SIGTERM while a multi-chunk prompt is mid-prefill: admission
    stops (ServingDraining with retry_after), the in-flight request
    finishes inside the drain budget, the queued one persists in the
    journal, and the exit code is 43 — raised only after the journal
    committed the drain record."""
    srv = _srv(eng, tmp_path=tmp_path, num_slots=1)
    long_prompt = _prompts(1, 24, 24, seed=3)[0]  # 3 chunks of 8
    r_flight = srv.submit(long_prompt, max_new_tokens=3)
    r_queued = srv.submit(_prompts(1, 6, 6, seed=4)[0], max_new_tokens=3)
    srv.install_watchdog(drain_deadline_seconds=60.0)
    try:
        srv.step()  # first chunk lands; prefill is mid-flight
        assert len(srv._unread_chunks) == 1  # noted, not read back: the default order of a step
        os.kill(os.getpid(), signal.SIGTERM)
        with pytest.raises(ServingDraining) as exc:
            srv.submit(_prompts(1, 4, 4, seed=5)[0], max_new_tokens=2)
        assert exc.value.retry_after is not None
        with pytest.raises(SystemExit) as e:
            srv.step()
        assert e.value.code == 43
    finally:
        srv._watchdog.uninstall()
    # the in-flight request drained; the queued one is durable undone work
    assert srv.result(r_flight).finish_reason == "length" and not srv._unread_chunks
    np.testing.assert_array_equal(srv.result(r_flight).tokens(), np.asarray(eng.generate(long_prompt[None, :], max_new_tokens=3))[0])
    inc = journal_mod.incomplete_requests(str(tmp_path / "journal"))
    assert [e["id"] for e in inc] == [r_queued]
    recs = journal_mod.read_records(str(tmp_path / "journal"))
    drains = [r for r in recs if r["t"] == "drain"]
    assert drains and drains[-1]["undone"] == [r_queued]
    # and the replayed queued request completes on a restarted engine
    srv2 = _srv(eng, tmp_path=tmp_path, num_slots=1)
    assert srv2.recover() == [r_queued]
    res = srv2.drain(max_steps=300)
    assert res[r_queued].finish_reason == "length"


def test_sigterm_past_the_drain_deadline_reads_the_unread_chunk_before_it_exits(eng, tmp_path):
    """No drain budget: the loop takes no step, the chunk the last step
    left on the device is read back before the books close, the request
    it belonged to is durable undone work, and the restarted engine
    serves it with the uninterrupted run's tokens."""
    srv = _srv(eng, tmp_path=tmp_path, num_slots=1)
    long_prompt = _prompts(1, 24, 24, seed=13)[0]  # 3 chunks of 8
    r_flight = srv.submit(long_prompt, max_new_tokens=3)
    srv.install_watchdog(drain_deadline_seconds=0.0)
    try:
        srv.step()
        assert len(srv._unread_chunks) == 1
        os.kill(os.getpid(), signal.SIGTERM)
        with pytest.raises(SystemExit) as e:
            srv.step()
        assert e.value.code == 43  # the journal committed the undone set
    finally:
        srv._watchdog.uninstall()
    assert not srv._unread_chunks and srv.stats()["chunks_deferred"] == 1
    drains = [r for r in journal_mod.read_records(str(tmp_path / "journal")) if r["t"] == "drain"]
    assert drains and drains[-1]["undone"] == [r_flight]
    srv2 = _srv(eng, tmp_path=tmp_path, num_slots=1)
    assert srv2.recover() == [r_flight]
    res = srv2.drain(max_steps=300)
    np.testing.assert_array_equal(res[r_flight].tokens(), np.asarray(eng.generate(long_prompt[None, :], max_new_tokens=3))[0])


def test_sigterm_journal_commit_failure_exits_1(eng, tmp_path):
    """Exit 43 must CERTIFY the commit: an injected commit failure at
    drain time quarantines the journal and exits 1 (crash contract)."""
    srv = _srv(eng, tmp_path=tmp_path, num_slots=1)
    srv.submit(_prompts(1, 6, 6, seed=6)[0], max_new_tokens=2)
    srv.install_watchdog(drain_deadline_seconds=60.0)
    try:
        srv.step()
        srv.step()  # served out under either order of a step: nothing is left for the drain loop to retire
        os.kill(os.getpid(), signal.SIGTERM)
        # the drain-record commit is the LAST commit; fail exactly there
        inj = faults.FaultInjector(seed=0).fail("serving.journal.commit", times=99)
        with inj:
            with pytest.raises(SystemExit) as e:
                srv.step()
        assert e.value.code == 1
    finally:
        srv._watchdog.uninstall()
    assert srv.stats()["journal"] == "quarantined"


def test_sigterm_without_journal_full_drain_is_43_undone_is_1(eng):
    # fully drained, nothing undone -> 43 even without a journal
    srv = _srv(eng, num_slots=1)
    srv.submit(_prompts(1, 6, 6, seed=7)[0], max_new_tokens=2)
    srv.step()  # in-flight (a QUEUED request would be undone: exit 1)
    srv.install_watchdog(drain_deadline_seconds=60.0)
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        with pytest.raises(SystemExit) as e:
            srv.step()
        assert e.value.code == 43
    finally:
        srv._watchdog.uninstall()
    # undone work with nowhere durable to live -> 1
    srv2 = _srv(eng, num_slots=1)
    srv2.submit(_prompts(1, 6, 6, seed=8)[0], max_new_tokens=2)
    srv2.submit(_prompts(1, 6, 6, seed=9)[0], max_new_tokens=2)  # queued
    srv2.install_watchdog(drain_deadline_seconds=0.0)  # no drain budget
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        with pytest.raises(SystemExit) as e:
            srv2.step()
        assert e.value.code == 1
    finally:
        srv2._watchdog.uninstall()


# ---------------------------------------------------------------------------
# chaos: overload -> shed with retry_after + degradation ladder
# ---------------------------------------------------------------------------

ORDERS = pytest.mark.parametrize("overlap", [True, False], ids=["default-order", "serial-order"])


@ORDERS
@pytest.mark.parametrize("chunks_per_step", [1, 2])
def test_ttft_estimate_walks_the_waiters_through_the_slots(eng, overlap, chunks_per_step):
    """The admission estimate in steps (the measured step pinned to one
    second) against the step that read each request's first token, at
    arrivals that find slots free, mid-prefill, decoding and waited
    for: what it took under the default order of a step and, on an
    engine with work, one step more — the step in progress when a
    request arrives, which a submit between two steps does not wait
    for — and no fewer under the serial one, which lets a slot go a
    step sooner."""
    srv = _srv(eng, max_queue=64, prefill_chunks_per_step=chunks_per_step, overlap_chunks=overlap)
    srv.scheduler.step_seconds_fn = lambda: 1.0
    rng = np.random.default_rng(5)
    prompts = _prompts(24, 3, 30, seed=15)
    est, ids = {}, []
    for i, p in enumerate(prompts):
        priced = srv.scheduler.admission.estimate_ttft_seconds(len(p), prompt=p) - srv.scheduler.has_work()
        ids.append(srv.submit(p, max_new_tokens=int(rng.integers(1, 10))))
        est[ids[-1]] = (priced, srv._step_count)
        for _ in range(int(rng.integers(0, 3))):  # bursts and lulls
            srv.step()
    res = srv.drain(max_steps=2000)
    took = {r: res[r].first_token_step - est[r][1] for r in ids}
    over = [est[r][0] - took[r] for r in ids]
    assert min(took.values()) < 5 < 20 < max(took.values())  # a slot free at arrival, and a long queue
    if overlap:
        assert not any(over), (over, took)
    else:
        assert min(over) == 0 and all(0 <= o <= t // 2 for o, t in zip(over, took.values())), (over, took)


@ORDERS
def test_overload_sheds_with_retry_after_and_keeps_admitted_ttft(eng, overlap):
    """Offered load far past capacity (every step costs an injected
    80ms, submits arrive back-to-back — twice what two slots of
    4-token requests serve): the estimated-TTFT shedder rejects with a
    positive ``retry_after`` and the ADMITTED requests' p99 TTFT stays
    within the configured SLO, under both orders of a step.  The
    estimate is one step over what a request takes under the default
    order; the injected cost is most of a step, so that this host's own
    time, 3 ms a step alone and several times that under the suite's
    workers, is little of it, and the SLO is six and a half such steps,
    of which the estimate admits five."""
    slo_ms = 540.0
    srv = _srv(eng, slo_ttft_ms=slo_ms, max_queue=256, overlap_chunks=overlap)
    prompts = _prompts(40, 6, 8, seed=10)
    inj = faults.FaultInjector(seed=0).latency("serving.decode", seconds=0.08)
    with inj:
        # warm: the EWMA must see the slow decode before the blast
        # (HIGH priority: an armed process-wide telemetry plane may hold
        # stale step walls from other engines, and warm-up must admit)
        srv.submit(prompts[0], max_new_tokens=12, priority=PRIORITY_HIGH)
        srv.drain(max_steps=50)
        admitted, sheds = [], []
        for p in prompts[1:]:
            try:
                admitted.append(srv.submit(p, max_new_tokens=4))
            except ServingOverloaded as exc:
                sheds.append(exc)
            srv.step()
        res = srv.drain(max_steps=2000)
    assert sheds, "4x+ overload must shed"
    assert admitted, "the shedder must not starve the engine"
    for exc in sheds:
        assert exc.retry_after is not None and exc.retry_after > 0
    ttfts = [
        (res[r].first_token_time - res[r].submit_time) * 1e3
        for r in admitted if res[r].first_token_time is not None
    ]
    assert ttfts
    p99 = float(np.percentile(ttfts, 99))
    assert p99 <= slo_ms, (p99, len(admitted), len(sheds))
    assert srv.stats()["shed"] == len(sheds)


def test_high_priority_bypasses_ttft_shed(eng):
    srv = _srv(eng, slo_ttft_ms=1.0, max_queue=64)  # absurdly tight SLO
    inj = faults.FaultInjector(seed=0).latency("serving.decode", seconds=0.02)
    with inj:
        # high-priority warm-up: must admit even against a stale armed-
        # plane step-wall window (order-independence in the full suite)
        srv.submit(
            _prompts(1, 6, 6, seed=11)[0], max_new_tokens=4,
            priority=PRIORITY_HIGH,
        )
        for _ in range(3):
            srv.step()
        with pytest.raises(ServingOverloaded):
            srv.submit(_prompts(1, 6, 6, seed=12)[0], max_new_tokens=4)
        rid = srv.submit(
            _prompts(1, 6, 6, seed=13)[0], max_new_tokens=2, priority=PRIORITY_HIGH
        )
        res = srv.drain(max_steps=500)
    assert res[rid].finish_reason == "length"


def test_degradation_ladder_engages_clamps_sheds_and_disengages(eng):
    """Sustained queue pressure climbs the ladder rung by rung: clamped
    admits, a shrunk prefill budget, shed low-priority waiters carrying
    retry_after — then hysteresis steps it back down once calm."""
    srv = _srv(
        eng, num_slots=1, max_queue=8, slo_ttft_ms=0.0,
        degrade_queue_watermark=0.5, degrade_engage_steps=2,
        degrade_disengage_steps=4, degrade_max_new_tokens=2,
    )
    prompts = _prompts(40, 6, 8, seed=14)
    srv.submit(prompts[0], max_new_tokens=24)
    levels = set()
    for i, p in enumerate(prompts[1:]):
        try:
            srv.submit(p, max_new_tokens=24, priority=PRIORITY_LOW if i % 2 else 1)
        except ServingQueueFull:
            pass
        srv.step()
        levels.add(srv.scheduler.ladder.level)
    assert levels >= {0, 1, 2, 3}, levels
    s = srv.stats()
    assert s["degrade_engagements"] >= 3
    res = srv.drain(max_steps=3000)
    shed = [r for r in res.values() if r.finish_reason == "shed"]
    clamped = [r for r in res.values() if r.degraded]
    assert shed and all(r.retry_after and r.retry_after > 0 for r in shed)
    assert clamped and all(r.max_new_tokens == 2 for r in clamped)
    assert all(len(r.generated) <= 2 for r in clamped)
    # calm: the ladder steps all the way back down (hysteresis pace)
    for _ in range(30):
        srv.step()
    assert srv.scheduler.ladder.level == 0
    assert srv.stats()["degrade_level"] == 0


def test_queue_full_rejection_carries_retry_after(eng):
    srv = _srv(eng, num_slots=1, max_queue=1)
    p = _prompts(3, 4, 4, seed=15)
    srv.submit(p[0], max_new_tokens=4)
    srv.step()
    srv.submit(p[1], max_new_tokens=4)
    with pytest.raises(ServingQueueFull) as e:
        srv.submit(p[2], max_new_tokens=4)
    assert not isinstance(e.value, ServingOverloaded)
    assert e.value.retry_after is not None and e.value.retry_after > 0
    srv.drain(max_steps=200)


def test_priority_admission_order(eng):
    """With one slot busy, a later high-priority submit is admitted
    before earlier normal/low ones (FIFO within a tier)."""
    srv = _srv(eng, num_slots=1)
    p = _prompts(4, 4, 4, seed=16)
    srv.submit(p[0], max_new_tokens=2)
    srv.step()  # p0 holds the slot
    r_low = srv.submit(p[1], max_new_tokens=2, priority=PRIORITY_LOW)
    r_norm = srv.submit(p[2], max_new_tokens=2)
    r_high = srv.submit(p[3], max_new_tokens=2, priority=PRIORITY_HIGH)
    res = srv.drain(max_steps=300)
    assert res[r_high].admit_step < res[r_norm].admit_step < res[r_low].admit_step


# ---------------------------------------------------------------------------
# chaos: injected journal-commit failure -> clean quarantine
# ---------------------------------------------------------------------------

def test_journal_commit_failure_quarantines_and_serving_continues(eng, tmp_path):
    srv = _srv(eng, tmp_path=tmp_path)
    p = _prompts(3, 5, 9, seed=17)
    r0 = srv.submit(p[0], max_new_tokens=3)
    inj = faults.FaultInjector(seed=0).fail("serving.journal.commit")
    with inj:
        r1 = srv.submit(p[1], max_new_tokens=3)  # commit fails -> quarantine
    s = srv.stats()
    assert s["journal"] == "quarantined"
    qdirs = [d for d in os.listdir(tmp_path) if d.startswith("journal.corrupt")]
    assert qdirs and not os.path.exists(tmp_path / "journal")
    # serving is unaffected: both requests (and a post-quarantine one) finish
    r2 = srv.submit(p[2], max_new_tokens=3)
    res = srv.drain(max_steps=300)
    assert {r0, r1, r2} <= set(res)
    assert all(res[r].finish_reason == "length" for r in (r0, r1, r2))


# ---------------------------------------------------------------------------
# satellite: idle-engine queued-deadline sweep (regression)
# ---------------------------------------------------------------------------

def test_idle_engine_deadline_sweep_via_stats_and_drain(eng):
    """Regression: a request waiting in an engine nobody steps must
    still expire via the host-side sweep in stats()/drain()."""
    srv = _srv(eng, num_slots=1)
    p = _prompts(2, 4, 4, seed=18)
    r1 = srv.submit(p[0], max_new_tokens=4)
    srv.step()  # r1 occupies the only slot
    r2 = srv.submit(p[1], max_new_tokens=4, deadline_seconds=1e-9)
    time.sleep(0.002)
    # NO step between submit and stats: the sweep must fire on its own
    s = srv.stats()
    assert s["expired"] == 1
    r = srv.result(r2)
    assert r.status == "expired" and r.finish_reason == "expired"
    srv.drain(max_steps=200)
    # drain() path: same sweep at entry even when a step never runs
    srv2 = _srv(eng, num_slots=1)
    srv2.submit(p[0], max_new_tokens=4)
    srv2.step()
    r4 = srv2.submit(p[1], max_new_tokens=4, deadline_seconds=1e-9)
    time.sleep(0.002)
    res = srv2.drain(max_steps=200)
    assert res[r4].finish_reason == "expired"


def test_shed_then_crash_never_resurrects_shed_requests(eng, tmp_path):
    """Regression (ISSUE 14 satellite): a rung-3 ladder shed must
    journal its reject record IMMEDIATELY — a crash right after the
    shed (no drain, no clean close) must not let recover() resurrect
    the shed request, and the shed verdict must carry ``retry_after``
    (the backpressure hint the fleet router keys on)."""
    srv = _srv(
        eng, tmp_path=tmp_path, num_slots=1, max_queue=8, slo_ttft_ms=0.0,
        degrade_queue_watermark=0.5, degrade_engage_steps=2,
        degrade_disengage_steps=4, degrade_max_new_tokens=2,
    )
    prompts = _prompts(40, 6, 8, seed=23)
    submitted = [srv.submit(prompts[0], max_new_tokens=24)]
    shed_ids = []
    for i, p in enumerate(prompts[1:]):
        try:
            submitted.append(
                srv.submit(p, max_new_tokens=24,
                           priority=PRIORITY_LOW if i % 2 else 1)
            )
        except ServingQueueFull:
            pass
        srv.step()
        shed_ids = [r.request_id for r in srv.scheduler._finished.values()
                    if r.finish_reason == "shed"]
        if shed_ids:
            break
    assert shed_ids, "the ladder must reach the shed rung"
    for rid in shed_ids:
        assert srv.result(rid).retry_after > 0  # hint rides the verdict
    # crash NOW: no drain, no final commit — only what the shed itself
    # committed survives (the bug was a reject record that only reached
    # the journal on the next unrelated commit)
    del srv
    srv2 = _srv(eng, tmp_path=tmp_path, num_slots=1)
    replayed = srv2.recover()
    assert not set(replayed) & set(shed_ids), (replayed, shed_ids)
    res = srv2.drain(max_steps=3000)
    assert all(res[r].finish_reason != "shed" for r in replayed)


def test_expired_via_sweep_is_durable_in_journal(eng, tmp_path):
    srv = _srv(eng, tmp_path=tmp_path, num_slots=1)
    p = _prompts(2, 4, 4, seed=19)
    srv.submit(p[0], max_new_tokens=4)
    srv.step()
    r2 = srv.submit(p[1], max_new_tokens=4, deadline_seconds=1e-9)
    time.sleep(0.002)
    srv.stats()  # sweep + commit
    inc = journal_mod.incomplete_requests(str(tmp_path / "journal"))
    assert r2 not in [e["id"] for e in inc]  # expired == retired, never replays
    srv.drain(max_steps=200)


# ---------------------------------------------------------------------------
# fault-plan / config plumbing
# ---------------------------------------------------------------------------

def test_fault_plan_latency_action_round_trips():
    inj = faults.FaultInjector(seed=0)
    inj.latency("serving.decode", seconds=0.02, times=3)
    inj.fail("serving.journal.commit")
    spec = inj.to_plan()
    inj2 = faults.FaultInjector.from_plan(spec)
    with inj2:
        t0 = time.monotonic()
        assert faults.check_latency("serving.decode") == pytest.approx(0.02)
        assert time.monotonic() - t0 >= 0.02
        with pytest.raises(faults.InjectedFault):
            faults.check("serving.journal.commit")
    # unbounded latency plans keep firing
    inj3 = faults.FaultInjector(seed=0).latency("serving.decode", seconds=0.0)
    with inj3:
        for _ in range(5):
            faults.check_latency("serving.decode")
    assert inj3.calls("serving.decode") == 5


def test_serving_resilience_config_validation():
    with pytest.raises(DeepSpeedConfigError, match="degrade_queue_watermark"):
        ServingConfig.from_dict({"degrade_queue_watermark": 1.5})
    with pytest.raises(DeepSpeedConfigError, match="degrade_engage_steps"):
        ServingConfig.from_dict({"degrade_engage_steps": 0})
    with pytest.raises(DeepSpeedConfigError, match="slo_ttft_ms"):
        ServingConfig.from_dict({"slo_ttft_ms": -1})
    with pytest.raises(DeepSpeedConfigError, match="drain_deadline_seconds"):
        ServingConfig.from_dict({"drain_deadline_seconds": -1})
    with pytest.raises(DeepSpeedConfigError, match="journal_segment_records"):
        ServingConfig.from_dict({"journal_segment_records": 0})
    c = ServingConfig.from_dict(
        {"slo_ttft_ms": 250, "journal_dir": "/tmp/j", "degrade_max_new_tokens": 0}
    )
    assert c.slo_ttft_ms == 250 and c.journal_dir == "/tmp/j"


def test_submit_priority_validation(eng):
    srv = _srv(eng)
    with pytest.raises(ValueError, match="priority"):
        srv.submit(_prompts(1, 4, 4, seed=20)[0], max_new_tokens=2, priority=7)
