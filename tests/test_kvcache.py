"""Paged KV subsystem tests (ISSUE 15; docs/serving.md §Paged KV &
prefix caching).

Coverage matrix: radix prefix-index units (insert / deepest lookup /
mid-edge split learning / LRU eviction order); page-pool refcount
accounting (COW pairs, garbage-page invariants, leak sweeps where every
live page must be accounted for by an index entry, a parked session, or
a mapped slot); the SlotKVPool double-free / duplicate-alloc
regressions; engine-level bit-match proofs (paged vs solo ``generate``
AND vs the kvcache-off slot pool, shared-prefix dedup, 3-turn session
rebind, spill → restore parity); the kill -9 mid-session chaos with
``recover()`` replaying bit-identically off re-registered spills;
compile stability under an armed ds_san churn (exactly one executable
per serving site, zero findings); paged flash-decode kernel parity in
interpret mode; and the fleet-affinity placement satellite (3-turn
session stickiness; hedge legs ignore affinity).
"""
import dataclasses
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.sanitizer import core as san_core
from deepspeed_tpu.analysis.sanitizer.core import Sanitizer
from deepspeed_tpu.config.config import SanitizerConfig
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving import (
    PagedKVPool,
    ServingEngine,
    SlotKVPool,
    SlotPoolError,
)
from deepspeed_tpu.serving.fleet import FleetRouter, LocalReplica
from deepspeed_tpu.serving.kvcache.pages import GARBAGE_PAGE
from deepspeed_tpu.serving.kvcache.prefix import PrefixEntry, PrefixIndex

pytestmark = pytest.mark.serving

TINY = dataclasses.replace(gpt2.GPT2_TINY, remat=False)


@pytest.fixture(scope="module")
def eng():
    """Position-sensitive engine (wpe scaled) shared across the module —
    slot/position/page bugs change generations instead of hiding."""
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


def _srv(eng, tmp_path=None, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_len", 64)
    kv = kw.pop("kvcache", {})
    kv.setdefault("enabled", True)
    kv.setdefault("page_len", 16)
    if tmp_path is not None:
        kw.setdefault("journal_dir", str(tmp_path / "journal"))
    return ServingEngine(eng, kvcache=kv, **kw)


class _KReq:
    """Duck-typed scheduler Request for pool-level tests."""

    def __init__(self, rid, prompt, max_new=4, sid=None, **kw):
        self.request_id = rid
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new_tokens = max_new
        self.session_id = sid
        self.prefill_pos = 0
        self.prefix_hint = 0
        self.slot = None
        self.generated = kw.get("generated", [])
        self.finish_reason = kw.get("finish_reason")


def _accounted_pages(pool):
    """Every page the host bookkeeping still has a claim on — the leak
    sweep asserts ``pages_live`` equals exactly this set's size."""
    pages = set()
    for e in pool.index.entries():
        pages.update(e.pages)
    for s in pool.sessions.warm():
        pages.update(s.pages)
    for ps in pool._slot_pages.values():
        pages.update(ps)
    return pages


def _assert_no_leaks(pool):
    acc = _accounted_pages(pool)
    assert pool.pages_live == len(acc), (
        f"pages_live={pool.pages_live} but only {len(acc)} pages are "
        "accounted for by entries/sessions/slots (leak or double-free)"
    )
    for p in range(1, pool.num_pages):
        assert (pool.refcount(p) > 0) == (p in acc), f"page {p} refcount drift"


# ---------------------------------------------------------------------------
# radix prefix index (no pool)
# ---------------------------------------------------------------------------

def test_prefix_index_insert_lookup_deepest():
    idx = PrefixIndex()
    a = idx.insert(PrefixEntry(tokens=np.array([1, 2, 3]), pages=[5]))
    b = idx.insert(PrefixEntry(tokens=np.array([1, 2, 3, 4, 5]), pages=[5, 6]))
    assert len(idx) == 2
    # deepest entry that prefixes the query wins
    hit = idx.lookup(np.array([1, 2, 3, 4, 5, 9, 9]), now=1.0)
    assert hit is b and b.hits == 1 and b.last_used == 1.0
    assert idx.lookup(np.array([1, 2, 3, 9])) is a
    assert idx.lookup(np.array([7, 7])) is None
    # stamp=False is the admission controller's side-effect-free path
    before = b.hits
    idx.lookup(np.array([1, 2, 3, 4, 5]), stamp=False)
    assert b.hits == before
    # first writer wins on a duplicate key; caller must release its pages
    dup = PrefixEntry(tokens=np.array([1, 2, 3]), pages=[99])
    assert idx.insert(dup) is a


def test_prefix_index_common_prefix_len_counts_mid_edge():
    """The split-point lever: two prompts sharing a system prompt never
    prefix each other, but their common run must still be discoverable
    (lookup can't see it — no entry terminates mid-edge)."""
    idx = PrefixIndex()
    idx.insert(PrefixEntry(tokens=np.array([1, 2, 3, 4, 10, 11]), pages=[2, 3]))
    q = np.array([1, 2, 3, 4, 20, 21])
    assert idx.lookup(q) is None
    assert idx.common_prefix_len(q) == 4
    assert idx.common_prefix_len(np.array([1, 2, 3, 4, 10, 11, 12])) == 6
    assert idx.common_prefix_len(np.array([9, 9])) == 0
    # inserting the shared run makes it a real (lookup-able) entry
    shared = idx.insert(PrefixEntry(tokens=np.array([1, 2, 3, 4]), pages=[2]))
    assert idx.lookup(q) is shared


def test_prefix_index_remove_and_evict_order():
    idx = PrefixIndex()
    cold = idx.insert(PrefixEntry(tokens=np.array([1, 2]), pages=[2],
                                  last_used=1.0))
    warm = idx.insert(PrefixEntry(tokens=np.array([3, 4]), pages=[3],
                                  last_used=9.0))
    pin = idx.insert(PrefixEntry(tokens=np.array([5, 6]), pages=[4],
                                 pinned=True, last_used=0.0))
    assert idx.evict_candidates() == [cold, warm]  # pinned never offered
    assert idx.remove(cold) and not idx.remove(cold)
    assert idx.lookup(np.array([1, 2, 9])) is None
    assert idx.lookup(np.array([5, 6, 9])) is pin


# ---------------------------------------------------------------------------
# paged pool: refcounts, COW, sessions, leak sweep (real device arrays)
# ---------------------------------------------------------------------------

def _pool(**kw):
    kw.setdefault("page_len", 8)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("kv_dtype", jnp.float32)
    return PagedKVPool(2, 2, 2, 32, 4, **kw)


def test_paged_pool_shape_math_and_garbage_page():
    pool = _pool()
    assert pool.pages_per_slot == 4
    assert pool.num_pages == 1 + 2 * 2 * 4
    assert pool.refcount(GARBAGE_PAGE) == 1  # permanently held
    assert pool.pages_live == 0
    s = pool.alloc("ra")
    assert s is not None and pool.pages_live == pool.pages_per_slot
    assert GARBAGE_PAGE not in pool._slot_pages[s]
    with pytest.raises(SlotPoolError):
        pool.alloc("ra")  # duplicate owner
    pool.free(s)
    assert pool.pages_live == 0
    with pytest.raises(SlotPoolError):
        pool.free(s)  # double free
    _assert_no_leaks(pool)


def test_paged_pool_prefix_hit_cow_and_leak_sweep():
    pool = _pool()
    r0 = _KReq("r0", [1, 2, 3, 4, 5, 6], max_new=2)
    r0.slot = pool.alloc_request(r0)
    assert r0.slot is not None and r0.prefill_pos == 0
    pool.learn_prefix(r0)  # 6 tokens -> entry holds its ref on page 1
    entry_pages = pool.index.lookup(np.array([1, 2, 3, 4, 5, 6, 7])).pages
    pool.retire(r0.slot, r0)
    # reader with the same 6-token start: aligned hit = 4 (chunk=4),
    # tail page is partially filled and shared -> COW
    r1 = _KReq("r1", [1, 2, 3, 4, 5, 6, 9, 9], max_new=2)
    r1.slot = pool.alloc_request(r1)
    assert (r1.prefill_pos, r1.prefix_hint) == (4, 4)
    cow = pool.consume_cow(r1.slot)
    assert cow != (GARBAGE_PAGE, GARBAGE_PAGE)
    assert cow[0] == entry_pages[0] and cow[1] == pool._slot_pages[r1.slot][0]
    assert pool.consume_cow(r1.slot) == (GARBAGE_PAGE, GARBAGE_PAGE)  # consumed
    assert pool.cow_copies == 1 and pool.tokens_saved == 4
    # the entry still holds its page after the reader retires
    pool.retire(r1.slot, r1)
    assert pool.refcount(entry_pages[0]) == 1
    _assert_no_leaks(pool)
    # a fresh reader re-hits without any COW source still mapped
    r2 = _KReq("r2", [1, 2, 3, 4, 5, 6, 7, 8], max_new=2)
    r2.slot = pool.alloc_request(r2)
    assert r2.prefix_hint == 4
    pool.retire(r2.slot, r2)
    _assert_no_leaks(pool)


def test_paged_pool_hit_alignment_respects_chunk_and_first_token():
    pool = _pool()  # chunk=4
    r0 = _KReq("r0", list(range(1, 13)), max_new=2)  # 12 tokens
    r0.slot = pool.alloc_request(r0)
    pool.learn_prefix(r0)
    pool.retire(r0.slot, r0)
    # full-prompt re-submit: hit caps at plen-1 then floors to chunk
    r1 = _KReq("r1", list(range(1, 13)), max_new=2)
    r1.slot = pool.alloc_request(r1)
    assert r1.prefix_hint == 8  # min(12, 11) -> 8
    pool.retire(r1.slot, r1)
    # sub-chunk overlap is not a hit (prefill restarts on chunk bounds)
    r2 = _KReq("r2", [1, 2, 3, 99], max_new=2)
    r2.slot = pool.alloc_request(r2)
    assert r2.prefix_hint == 0
    pool.retire(r2.slot, r2)
    _assert_no_leaks(pool)


def test_paged_pool_session_park_rebind_and_ttl_drop():
    pool = _pool(session_ttl_seconds=5.0)
    r0 = _KReq("r0", [1, 2, 3, 4], max_new=3, sid="chat",
               generated=[7, 8, 9], finish_reason="eos")
    r0.slot = pool.alloc_request(r0, now=0.0)
    pool.retire(r0.slot, r0, now=0.0)
    sess = pool.sessions.peek("chat")
    assert sess is not None and sess.cached_len == 6  # prompt + gen[:-1]
    # turn 2 extends the parked history -> rebind consumes the session
    t2 = [1, 2, 3, 4, 7, 8, 30, 31]
    r1 = _KReq("r1", t2, max_new=2, sid="chat")
    r1.slot = pool.alloc_request(r1, now=1.0)
    assert r1.prefix_hint == 4  # aligned_hit(6, 8) with chunk=4
    assert pool.session_rebinds == 1 and pool.sessions.peek("chat") is None
    # divergent history parks untouched, misses
    pool.retire(r1.slot, r1, now=1.0)
    r2 = _KReq("r2", [1, 2, 3, 4], max_new=3, sid="other",
               generated=[5, 6], finish_reason="length")
    r2.slot = pool.alloc_request(r2, now=1.0)
    pool.retire(r2.slot, r2, now=1.0)
    r3 = _KReq("r3", [9, 9, 9, 9, 9], max_new=2, sid="other")
    r3.slot = pool.alloc_request(r3, now=2.0)
    assert r3.prefix_hint == 0 and pool.sessions.peek("other") is not None
    pool.retire(r3.slot, r3, now=2.0)
    # TTL sweep drops the cold session (no spill dir) and frees pages
    assert pool.sweep(now=100.0) == 1
    assert pool.sessions.peek("other") is None
    _assert_no_leaks(pool)


def test_paged_pool_spill_restore_roundtrip(tmp_path):
    """Cold-session spill → fresh pool → recover() → rebind restores
    page CONTENT bit-identically (the uint16-view bfloat16 round trip)."""
    spill = str(tmp_path / "spill")
    pool = _pool(spill_dir=spill, kv_dtype=jnp.bfloat16)
    rng = np.random.default_rng(3)
    fill = rng.standard_normal(
        (pool.n_layer, pool.num_pages, pool.heads, pool.page_len, pool.head_dim)
    ).astype(jnp.bfloat16)
    pool.swap(jnp.asarray(fill), jnp.asarray((fill * 2).astype(fill.dtype)))
    r0 = _KReq("r0", [1, 2, 3, 4, 5], max_new=3, sid="chat",
               generated=[6, 7], finish_reason="eos")
    r0.slot = pool.alloc_request(r0)
    kept = list(pool._slot_pages[r0.slot][:1])  # 6 cached tokens -> 1 page
    want_k = np.asarray(fill[:, kept])
    pool.retire(r0.slot, r0)
    assert pool.spill_sessions(now=0.0) == 1
    assert pool.sessions.is_spilled("chat")
    # fresh pool over the same spill dir (the kill -9 shape: device
    # pages and host index died; only the manifest-gated spill survives)
    pool2 = _pool(spill_dir=spill, kv_dtype=jnp.bfloat16)
    assert pool2.recover() == ["chat"]
    r1 = _KReq("r1", [1, 2, 3, 4, 5, 6, 30, 31], max_new=2, sid="chat")
    r1.slot = pool2.alloc_request(r1)
    assert r1.prefix_hint == 4 and pool2.stats()["session_restores"] == 1
    got_k = np.asarray(
        jnp.take(pool2.k, jnp.asarray(pool2._slot_pages[r1.slot][:1]), axis=1)
    )
    np.testing.assert_array_equal(got_k, want_k)
    pool2.retire(r1.slot, r1)
    _assert_no_leaks(pool2)


def test_paged_pool_reclaims_cold_entries_under_pressure():
    # 5 usable pages (1 garbage + 5): learned entries must be evicted,
    # coldest first, when a new request needs their pages
    pool = _pool(num_pages=6)
    for i, rid in enumerate(("r0", "r1")):
        r = _KReq(rid, [10 * i + 1, 10 * i + 2, 10 * i + 3, 10 * i + 4,
                        10 * i + 5], max_new=2)
        r.slot = pool.alloc_request(r, now=float(i))
        pool.learn_prefix(r, now=float(i))
        pool.retire(r.slot, r, now=float(i))
    assert pool.stats()["prefix_entries"] == 2
    big = _KReq("big", list(range(200, 224)), max_new=8)  # wants all 4 pages
    big.slot = pool.alloc_request(big, now=5.0)
    assert big.slot is not None
    assert pool.evictions >= 1
    pool.retire(big.slot, big, now=5.0)
    _assert_no_leaks(pool)


# ---------------------------------------------------------------------------
# SlotKVPool regressions (the satellite bugfix)
# ---------------------------------------------------------------------------

def test_slot_pool_duplicate_request_id_raises():
    pool = SlotKVPool(2, 2, 4, 32, 16, jnp.float32)
    pool.alloc("ra")
    with pytest.raises(SlotPoolError, match="already owns"):
        pool.alloc("ra")
    pool.alloc("rb")  # distinct id still fine
    assert pool.free_slots == 0 and pool.alloc("rc") is None


def test_slot_pool_double_free_raises():
    pool = SlotKVPool(2, 2, 4, 32, 16, jnp.float32)
    s = pool.alloc("ra")
    pool.free(s)
    with pytest.raises(SlotPoolError):
        pool.free(s)
    assert pool.alloc("ra") is not None  # freed id may re-alloc


# ---------------------------------------------------------------------------
# engine-level bit-match: shared-prefix dedup + two-executable contract
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~11s: 3 engine builds + 8-prompt solo sweep (kvcache CI job)
def test_paged_engine_bitmatch_solo_and_slot_pool(eng):
    """The tentpole proof: shared-prefix traffic through the paged
    engine produces greedy outputs bit-matching BOTH solo ``generate``
    and a kvcache-off engine, with real dedup (hits, tokens saved) and
    exactly one executable per serving site."""
    shared = _prompts(1, 24, 24, seed=11)[0]
    tails = _prompts(6, 4, 12, seed=12)
    prompts = [np.concatenate([shared, t]) for t in tails] + _prompts(2, 6, 14, seed=13)
    srv = _srv(eng)
    off = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64)
    assert isinstance(srv.pool, PagedKVPool)
    rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
    rids_off = [off.submit(p, max_new_tokens=4) for p in prompts]
    res = srv.drain(max_steps=600)
    res_off = off.drain(max_steps=600)
    for p, rid, rid_off in zip(prompts, rids, rids_off):
        exp = _solo(eng, p, 4)
        np.testing.assert_array_equal(res[rid].tokens(), exp)
        np.testing.assert_array_equal(res_off[rid_off].tokens(), exp)
    kv = srv.stats()["kvcache"]
    # the first two shared prompts fill both slots before any learning
    # lands, so they can't hit; most of the rest must
    assert kv["prefix_hits"] >= 3 and kv["tokens_saved"] >= 3 * 16
    assert kv["cow_copies"] >= 1
    assert srv.prefill_compiles == 1 and srv.decode_compiles == 1
    assert srv.pool.live_slots == 0
    _assert_no_leaks(srv.pool)


def test_shared_prefix_mix_halves_prefill_tokens_bit_identical(eng):
    """The count gate of a shared-prefix deployment: an 80 %-shared
    system-prompt batch plus three 3-turn sessions, the same schedule
    with the paged pool and with the slot pool — the prefix cache
    computes at least 2x fewer prefill tokens (every chunk dispatched
    is counted), hits on at least half its lookups, rebinds parked
    sessions, and every greedy output is bit-identical."""
    rng = np.random.default_rng(0)
    tail = lambda lo, hi: rng.integers(1, TINY.vocab_size, int(rng.integers(lo, hi + 1)), dtype=np.int32)
    system = tail(32, 32)
    batch = [np.concatenate([system, tail(2, 8)]) if i % 5 != 4 else tail(16, 32)
             for i in range(10)]
    sess_tails = [[tail(2, 4) for _ in range(3)] for _ in range(3)]

    def run(srv):
        computed, outputs = [], []
        launch_prefill = srv._launch_prefill  # every chunk handed to the device, whichever the order of the step
        srv._launch_prefill = lambda job: (computed.append(job.length), launch_prefill(job))[1]

        def go(prompts, **kw):
            rids = [srv.submit(p, max_new_tokens=4, **dict(kw, **e)) for p, e in prompts]
            res = srv.drain(max_steps=2000)
            outputs.extend(np.asarray(res[rid].tokens()) for rid in rids)
            return outputs[-len(rids):]

        go([(system, {})])  # seeds the shared prefix; both runs pay it
        go([(p, {}) for p in batch])
        hist = [np.concatenate([system, sess_tails[s][0]]) for s in range(3)]
        for turn in range(3):
            outs = go([(hist[s], {"session_id": f"sess-{s}"}) for s in range(3)])
            if turn < 2:
                hist = [np.concatenate([outs[s], sess_tails[s][turn + 1]]) for s in range(3)]
        return sum(computed), outputs

    off_tokens, off_out = run(ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64))
    srv = _srv(eng)
    on_tokens, on_out = run(srv)
    assert len(on_out) == len(off_out) == 20
    for a, b in zip(on_out, off_out):
        np.testing.assert_array_equal(a, b)
    kv = srv.stats()["kvcache"]
    assert off_tokens >= 2 * on_tokens, (off_tokens, on_tokens)
    assert off_tokens - on_tokens == kv["tokens_saved"]
    assert kv["hit_rate"] >= 0.5, kv
    assert kv["session_rebinds"] >= 1, kv
    _assert_no_leaks(srv.pool)


@pytest.mark.parametrize("chunks_per_step", [1, 2])
def test_prefix_hit_with_a_copy_on_write_pair_rides_a_chunk_left_unread(eng, chunks_per_step):
    """Under the default order of a step a reader's first chunk carries
    the copy-on-write pair of its prefix hit and is not waited for (it
    is not the prompt's last): the page is copied on the device before
    the chunk writes, behind whatever is in flight, the prompt is
    learned as a prefix when its last chunk is read, and the tokens are
    the serial step's and the solo run's."""
    rng = np.random.default_rng(21)
    tail = lambda n: rng.integers(1, TINY.vocab_size, n, dtype=np.int32)
    system = tail(24)  # a page and a half: a hit on it shares the half-filled page
    donor, readers = system, [np.concatenate([system, tail(n)]) for n in (27, 19)]
    deeper = np.concatenate([readers[0], tail(6)])  # hits the first reader's whole prompt, learned from its awaited last chunk

    def run(**kw):
        srv = _srv(eng, num_slots=3, prefill_chunks_per_step=chunks_per_step, **kw)
        r = srv.submit(donor, max_new_tokens=16)
        for _ in range(-(-len(donor) // 8 // chunks_per_step)):
            srv.step()  # the donor's prompt is learned, and it decodes on while the readers prefill
        cow0 = srv.pool.cow_copies
        rids = [srv.submit(p, max_new_tokens=4) for p in readers]
        srv.step()
        first_step = (srv.pool.cow_copies - cow0, len(srv._unread_chunks), [srv.result(i).prefill_pos for i in rids])
        res = srv.drain(max_steps=300)
        out = [res[i].tokens() for i in [r] + rids]
        saved = srv.pool.tokens_saved
        last = srv.submit(deeper, max_new_tokens=3)
        out.append(srv.drain(max_steps=300)[last].tokens())
        return out, first_step, srv.pool.tokens_saved - saved, srv

    serial, first0, deep0, _ = run(overlap_chunks=False)
    got, first, deep, srv = run()
    for a, b, (p, n) in zip(got, serial, [(donor, 16), (readers[0], 4), (readers[1], 4), (deeper, 3)]):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, _solo(eng, p, n))
    # both readers were admitted onto the shared half page; their first chunks (from position 24, past the hit) carry the
    # pairs, and those of this step were dispatched and left unread
    assert first[0] == first0[0] == 2 and first[1] == chunks_per_step and first0[1] == 0
    assert first[2] == first0[2] and min(first[2]) >= 24
    assert deep == deep0 == 48  # 51 tokens of the first reader's prompt, floored to the chunk
    st = srv.stats()
    assert st["kvcache"]["cow_copies"] >= 2 and st["chunks_deferred"] > 0 and not srv._unread_chunks
    _assert_no_leaks(srv.pool)


def test_paged_engine_pinned_prefix_hits_first_traffic(eng):
    """A pinned system prompt is seeded by the FIRST request that
    carries it and never evicted; admission sees the hint."""
    pin = _prompts(1, 16, 16, seed=21)[0]
    srv = _srv(eng, kvcache={"enabled": True, "page_len": 16,
                             "pinned_prefixes": [pin.tolist()]})
    p1 = np.concatenate([pin, _prompts(1, 6, 6, seed=22)[0]])
    r1 = srv.submit(p1, max_new_tokens=3)
    res1 = srv.drain(max_steps=300)
    entry = srv.pool.index.lookup(np.concatenate([pin, [1]]))
    assert entry is not None and entry.pinned
    p2 = np.concatenate([pin, _prompts(1, 8, 8, seed=23)[0]])
    assert srv.pool.prefix_hint_tokens(p2) == 16
    r2 = srv.submit(p2, max_new_tokens=3)
    res = srv.drain(max_steps=300)
    np.testing.assert_array_equal(res[r2].tokens(), _solo(eng, p2, 3))
    assert res1[r1].finish_reason and srv.stats()["kvcache"]["prefix_hits"] >= 1


@pytest.mark.slow  # ~5s: 3 chained turns x (serving + solo) (kvcache CI job)
def test_paged_engine_session_three_turns_bitmatch(eng):
    """Durable-session tentpole: three chat turns under one session_id
    each rebind the previous turn's pages; every turn bit-matches a solo
    run over the full transcript prompt."""
    srv = _srv(eng, prefill_chunk=4, max_len=64)
    history = _prompts(1, 8, 8, seed=31)[0]
    for turn in range(3):
        rid = srv.submit(history, max_new_tokens=4, session_id="chat")
        res = srv.drain(max_steps=300)
        got = np.asarray(res[rid].tokens())  # full sequence: prompt + gen
        np.testing.assert_array_equal(got, _solo(eng, history, 4))
        history = np.concatenate([got, _prompts(1, 3, 5, seed=40 + turn)[0]])
    kv = srv.stats()["kvcache"]
    assert kv["session_rebinds"] == 2 and kv["session_parks"] == 3
    assert kv["tokens_saved"] > 0
    assert srv.prefill_compiles == 1 and srv.decode_compiles == 1


def test_paged_engine_session_spill_restore_bitmatch(eng, tmp_path):
    """Cold session spilled to disk (stage → manifest protocol), then a
    later turn restores it on demand — still bit-identical."""
    srv = _srv(eng, prefill_chunk=4, max_len=64,
               kvcache={"enabled": True, "page_len": 16,
                        "spill_dir": str(tmp_path / "spill")})
    p1 = _prompts(1, 8, 8, seed=51)[0]
    r1 = srv.submit(p1, max_new_tokens=4, session_id="s")
    res = srv.drain(max_steps=300)
    t1 = np.asarray(res[r1].tokens())  # full sequence: prompt + gen
    assert srv.pool.spill_sessions(time.monotonic()) == 1
    assert srv.pool.sessions.is_spilled("s")
    p2 = np.concatenate([t1, _prompts(1, 4, 4, seed=52)[0]])
    r2 = srv.submit(p2, max_new_tokens=4, session_id="s")
    res = srv.drain(max_steps=300)
    np.testing.assert_array_equal(res[r2].tokens(), _solo(eng, p2, 4))
    kv = srv.stats()["kvcache"]
    assert kv["session_spills"] == 1 and kv["session_restores"] == 1
    assert kv["session_rebinds"] == 1


# ---------------------------------------------------------------------------
# chaos: kill -9 mid-session -> recover() replays bit-identically
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~6s: crash + full rebuild over the same dirs (kvcache CI job)
def test_kill9_mid_session_recover_bit_identical(eng, tmp_path):
    """The crash-safety satellite: turn 1 of a session completes and its
    spill lands; the process dies mid-decode on turn 2.  A fresh engine
    over the same journal + spill dirs must re-register the spill and
    replay turn 2 bit-identically to an uninterrupted run."""
    p1 = _prompts(1, 8, 8, seed=61)[0]
    t1 = _solo(eng, p1, 4)  # full sequence: prompt + gen
    p2 = np.concatenate([t1, _prompts(1, 4, 4, seed=62)[0]])
    expect2 = _solo(eng, p2, 6)
    extra = _prompts(2, 6, 12, seed=63)
    expect_extra = [_solo(eng, p, 3) for p in extra]

    def build():
        return _srv(eng, tmp_path=tmp_path, prefill_chunk=4, max_len=64,
                    kvcache={"enabled": True, "page_len": 16,
                             "spill_dir": str(tmp_path / "spill")})

    srv1 = build()
    r1 = srv1.submit(p1, max_new_tokens=4, session_id="chat")
    res = srv1.drain(max_steps=300)
    np.testing.assert_array_equal(res[r1].tokens(), t1)
    srv1.pool.spill_sessions(time.monotonic())
    rid2 = srv1.submit(p2, max_new_tokens=6, session_id="chat")
    rids_x = [srv1.submit(p, max_new_tokens=3) for p in extra]
    inj = faults.FaultInjector(seed=0).kill("serving.decode", after=1)
    with pytest.raises(faults.InjectedKill):
        with inj:
            srv1.drain(max_steps=500)

    srv2 = build()
    replayed = srv2.recover()
    assert rid2 in replayed
    assert srv2.pool.sessions.is_spilled("chat")  # spill re-registered
    res2 = srv2.drain(max_steps=500)
    np.testing.assert_array_equal(res2[rid2].tokens(), expect2)
    for rid, exp in zip(rids_x, expect_extra):
        if rid in replayed:
            np.testing.assert_array_equal(res2[rid].tokens(), exp)
    assert srv2.stats()["kvcache"]["session_rebinds"] >= 1
    assert srv2.pool.live_slots == 0
    _assert_no_leaks(srv2.pool)


# ---------------------------------------------------------------------------
# compile stability under an armed ds_san churn
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


def test_paged_compile_stability_churn_ds_san_clean(eng, san):
    """The two-executable contract survives paged churn: prefix hits,
    COW pairs, session rebinds and table rebinds are all traced values —
    one compiled prefill + one compiled decode, zero ds_san findings."""
    srv = _srv(eng, prefill_chunk=8, max_len=64)
    assert srv._sanitizer is san
    shared = _prompts(1, 16, 16, seed=71)[0]
    rids = [srv.submit(np.concatenate([shared, t]), max_new_tokens=3)
            for t in _prompts(3, 4, 10, seed=72)]
    rids.append(srv.submit(_prompts(1, 30, 30, seed=73)[0], max_new_tokens=3))
    srv.step()
    srv.step()
    rids.append(srv.submit(shared, max_new_tokens=3, session_id="s"))
    res = srv.drain(max_steps=500)
    # turn 2: tokens() (prompt + gen) extends the parked session by one
    rids.append(srv.submit(np.asarray(res[rids[-1]].tokens()),
                           max_new_tokens=3, session_id="s"))
    res.update(srv.drain(max_steps=500))
    assert sorted(res) == sorted(rids)
    assert srv.prefill_compiles == 1 and srv.decode_compiles == 1
    counts = san.recompile.compile_counts()
    assert counts.get("serving.prefill") == 1, counts
    assert counts.get("serving.decode") == 1, counts
    assert san.findings == [], [f.format() for f in san.findings]


# ---------------------------------------------------------------------------
# paged flash-decode kernel parity (interpret mode off-TPU)
# ---------------------------------------------------------------------------

def test_flash_decode_paged_matches_gather_reference():
    from deepspeed_tpu.ops.kernels import flash_decode as fd
    from deepspeed_tpu.ops.transformer import inference as inf

    B, H, P, page_len, d = 2, 2, 3, 128, 16
    num_pages = 1 + B * P
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((num_pages, H, page_len, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((num_pages, H, page_len, d)), jnp.float32)
    table = jnp.asarray(
        np.arange(1, num_pages, dtype=np.int32).reshape(B, P))
    pos = jnp.asarray(np.array([37, 2 * page_len + 5], np.int32))
    assert fd.decode_paged_supported(B, H, P, page_len, d)
    out = fd.flash_decode_paged(q, kc, vc, table, pos)
    gk = inf.paged_gather(kc, table)
    gv = inf.paged_gather(vc, table)
    ref = inf.cache_attention(q, gk, gv, pos, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_cache_write_respects_write_mask():
    from deepspeed_tpu.ops.transformer import inference as inf

    B, H, page_len, d = 2, 2, 8, 4
    num_pages, P = 5, 2
    cache = jnp.zeros((num_pages, H, page_len, d), jnp.float32)
    t = jnp.ones((B, H, 1, d), jnp.float32)
    table = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
    pos = jnp.asarray(np.array([3, 9], np.int32))
    mask = jnp.asarray(np.array([True, False]))
    out = inf.paged_cache_write(cache, t, table, pos, write_mask=mask)
    got = np.asarray(out)
    assert got[1, :, 3].all()  # slot 0 wrote page 1 row 3
    assert not got[3:5].any()  # masked slot 1 touched nothing real
    # the redirected write lands only on the garbage page
    assert got[1:, :, :].sum() == got[1, :, 3].sum()


# ---------------------------------------------------------------------------
# fleet affinity (the router satellite)
# ---------------------------------------------------------------------------

class _FakeRep:
    """Minimal router-facing replica for placement unit tests."""

    def __init__(self, name, ttft, affinity=0):
        self.name = name
        self._ttft = ttft
        self._aff = affinity

    def alive(self):
        return True

    def estimate_ttft(self, prompt_len):
        return self._ttft

    def kv_affinity(self, prompt, session_id=None):
        return self._aff

    def queue_depth(self):
        return 0

    def degrade_level(self):
        return 0

    def draining(self):
        return False


def test_pick_prefers_affinity_but_hedge_ignores_it():
    fast = _FakeRep("fast", ttft=0.01)
    warm = _FakeRep("warm", ttft=0.5, affinity=32)
    router = FleetRouter([fast, warm], clock=lambda: 0.0)
    prompt = np.arange(40, dtype=np.int32)
    # routed placement: the warm cache beats the faster queue
    assert router._pick(len(prompt), set(), 0.0, prompt=prompt,
                        session_id="s") == "warm"
    assert router.affinity_routes == 1
    # the hedge shape (no prompt): pure least-TTFT, affinity invisible
    assert router._pick(len(prompt), set(), 0.0) == "fast"
    assert router.affinity_routes == 1
    # an excluded affinity winner falls back cleanly
    assert router._pick(len(prompt), {"warm"}, 0.0, prompt=prompt) == "fast"


def test_fleet_session_stickiness_three_turns(eng, tmp_path):
    """3-turn session against a 2-replica fleet: after turn 1 lands
    somewhere, affinity pins every later turn to that replica, and the
    final turn still bit-matches solo."""
    def factory(name):
        d = str(tmp_path / name / "journal")

        def build():
            return _srv(eng, prefill_chunk=4, max_len=64, journal_dir=d)

        return build

    reps = [LocalReplica(f"r{i}", factory(f"r{i}")) for i in range(2)]
    router = FleetRouter(reps)
    history = _prompts(1, 8, 8, seed=81)[0]
    homes = []
    for turn in range(3):
        h = router.submit(history, max_new_tokens=4, session_id="chat")
        homes.append(router.handle(h).replica)  # before drain pops it
        res = router.drain(max_steps=400)
        got = np.asarray(res[h].tokens())  # full sequence: prompt + gen
        np.testing.assert_array_equal(got, _solo(eng, history, 4))
        history = np.concatenate([got, _prompts(1, 3, 4, seed=90 + turn)[0]])
    assert homes[1] == homes[0] and homes[2] == homes[0], homes
    assert router.affinity_routes >= 2
    home = router._replicas[homes[0]].engine
    assert home.stats()["kvcache"]["session_rebinds"] == 2


# ---------------------------------------------------------------------------
# hybrid cache kind: K/V pages of some layers + a per-slot state group
# ---------------------------------------------------------------------------

def _hybrid_pool(**kw):
    from deepspeed_tpu.serving.kvcache.pages import HybridKV, PerHeadKV

    kind = HybridKV(paged_layers=2, pages=PerHeadKV(2, 8, jnp.float32),
                    state={"s": (6, (4, 8, 8), jnp.float32), "conv": (6, (3, 96), jnp.float32)})
    return PagedKVPool(8, 3, 0, 64, 0, jnp.float32, page_len=16, num_pages=13, prefill_chunk=16, kind=kind, **kw)


def test_hybrid_kind_has_pages_for_its_paged_layers_only_and_a_slot_axis_group():
    pool = _hybrid_pool()
    assert pool.k.shape == pool.v.shape == (2, 13, 2, 16, 8)  # 2 of the 8 layers, not n_layer
    assert pool.state["s"].shape == (6, 3, 4, 8, 8) and pool.state["s"].dtype == jnp.float32
    assert pool.state["conv"].shape == (6, 3, 3, 96)
    state_bytes = 6 * 3 * (4 * 8 * 8 + 3 * 96) * 4
    assert pool.state_bytes() == state_bytes and pool.cache_bytes() == 2 * 2 * 13 * 2 * 16 * 8 * 4 + state_bytes
    assert "2 of 8 layers" in pool.shape_math() and "state per slot (s: 6 layers x 4 x 8 x 8 float32 + conv: 6 layers x 3 x 96 float32)" in pool.shape_math()
    st = pool.stats()
    assert st["kind"] == pool.kind.describe(8, 13, 16) and st["state_bytes"] == state_bytes
    assert st["state_leaves"] == {"s": 6 * 3 * 4 * 8 * 8 * 4, "conv": 6 * 3 * 3 * 96 * 4}  # the leaves the family declared
    # swap keeps the state unless handed a new one
    new = {k: v + 1 for k, v in pool.state.items()}
    pool.swap(pool.k, pool.v)
    assert float(pool.state["s"].max()) == 0.0
    pool.swap(pool.k, pool.v, new)
    assert float(pool.state["s"].min()) == 1.0


def test_hybrid_kind_refuses_prefix_hits_session_rebinds_and_spill_and_says_so(tmp_path):
    from deepspeed_tpu.serving.kvcache.pages import REUSE_OFF

    pool = _hybrid_pool()
    prompt = np.arange(1, 41, dtype=np.int32)
    r1 = _KReq(1, prompt, max_new=4, sid="chat", generated=[5, 6, 7, 8], finish_reason="length")
    r1.slot = pool.alloc_request(r1)
    assert r1.prefill_pos == 0
    pool.learn_prefix(r1)                      # nothing is learned
    assert len(pool.index) == 0
    pool.retire(r1.slot, r1)                   # nothing is parked
    assert pool.sessions.peek("chat") is None and pool.pages_live == 0
    r2 = _KReq(2, prompt, max_new=4, sid="chat")   # same prompt, same session: a miss, prefilled from position 0
    r2.slot = pool.alloc_request(r2)
    assert (r2.prefill_pos, r2.prefix_hint) == (0, 0) and pool.consume_cow(r2.slot) == (GARBAGE_PAGE, GARBAGE_PAGE)
    assert pool.prefix_hint_tokens(prompt, "chat") == 0
    st = pool.stats()
    assert st["reuse"] == REUSE_OFF and st["sessions_unbound"] == 2
    assert (st["prefix_hits"], st["session_rebinds"], st["cow_copies"], st["session_parks"]) == (0, 0, 0, 0)
    pool.retire(r2.slot, r2)
    _assert_no_leaks(pool)
    with pytest.raises(SlotPoolError, match="prefix reuse is off"):
        _hybrid_pool(spill_dir=str(tmp_path))
    with pytest.raises(SlotPoolError, match="prefix reuse is off"):
        _hybrid_pool(pinned_prefixes=[[1, 2, 3]])
    with pytest.raises(SlotPoolError, match="prefix reuse is off"):
        pool.attach_tiers(object())
    with pytest.raises(SlotPoolError, match="prefix reuse is off"):
        pool.import_sessions(str(tmp_path))


def _hybrid_latent_pool(**kw):
    from deepspeed_tpu.serving.kvcache.pages import HybridKV, LatentKV

    kind = HybridKV(paged_layers=1, pages=LatentKV(24, jnp.float32),
                    state={"s": (4, (4, 8, 8), jnp.float32), "conv": (4, (3, 96), jnp.float32)})
    return PagedKVPool(5, 3, 0, 64, 0, jnp.float32, page_len=16, num_pages=13, prefill_chunk=16, kind=kind, **kw)


def test_hybrid_kind_over_latent_pages_has_one_latent_leaf_for_its_paged_layers_and_a_slot_axis_group():
    pool = _hybrid_latent_pool()
    assert pool.k.shape == (1, 13, 24, 16) and pool.v is None  # one of the five layers; positions along the lanes; no V
    assert pool.state["s"].shape == (4, 3, 4, 8, 8) and pool.state["conv"].shape == (4, 3, 3, 96)
    state_bytes = 4 * 3 * (4 * 8 * 8 + 3 * 96) * 4
    page_bytes = 1 * 13 * 24 * 16 * 4
    assert pool.state_bytes() == state_bytes and pool.cache_bytes() == page_bytes + state_bytes
    assert "1 x (1 of 5 layers x 13 pages x 24 latent x 16 page_len)" in pool.shape_math()
    st = pool.stats()
    assert st["kind"] == pool.kind.describe(5, 13, 16) and st["page_kind"] == "LatentKV"
    assert st["page_leaves"] == {"k": page_bytes} and st["state_leaves"] == {"s": 4 * 3 * 4 * 8 * 8 * 4, "conv": 4 * 3 * 3 * 96 * 4}
    assert not pool.reuse and pool.kind.pages_hold_all is False  # whatever LatentKV says of itself
    # the per-head hybrid says its own page kind and leaves the same way
    st2 = _hybrid_pool().stats()
    assert st2["page_kind"] == "PerHeadKV" and st2["page_leaves"] == {"k": 2 * 13 * 2 * 16 * 8 * 4, "v": 2 * 13 * 2 * 16 * 8 * 4}


def test_hybrid_kind_over_latent_pages_allocates_frees_and_refuses_prefix_reuse_as_the_per_head_one(tmp_path):
    from deepspeed_tpu.serving.kvcache.pages import REUSE_OFF

    pool = _hybrid_latent_pool()
    prompt = np.arange(1, 41, dtype=np.int32)
    r1 = _KReq(1, prompt, max_new=4, sid="chat", generated=[5, 6, 7, 8], finish_reason="length")
    r1.slot = pool.alloc_request(r1)
    assert r1.prefill_pos == 0 and pool.pages_live == 3  # 44 positions: three pages of 16
    assert (np.asarray(pool.table(r1.slot))[:3] > 0).all() and not np.asarray(pool.table(r1.slot))[3:].any()
    pool.learn_prefix(r1)
    assert len(pool.index) == 0
    pool.retire(r1.slot, r1)
    assert pool.sessions.peek("chat") is None and pool.pages_live == 0 and pool.free_slots == 3
    r2 = _KReq(2, prompt, max_new=4, sid="chat")  # same prompt, same session: a miss, prefilled from position 0
    r2.slot = pool.alloc_request(r2)
    assert (r2.prefill_pos, r2.prefix_hint) == (0, 0) and pool.consume_cow(r2.slot) == (GARBAGE_PAGE, GARBAGE_PAGE)
    st = pool.stats()
    assert st["reuse"] == REUSE_OFF and st["sessions_unbound"] == 2 and (st["prefix_hits"], st["cow_copies"]) == (0, 0)
    pool.retire(r2.slot, r2)
    _assert_no_leaks(pool)
    with pytest.raises(SlotPoolError, match="prefix reuse is off"):
        _hybrid_latent_pool(spill_dir=str(tmp_path))
    with pytest.raises(SlotPoolError, match="prefix reuse is off"):
        _hybrid_latent_pool(pinned_prefixes=[[1, 2, 3]])


@pytest.mark.parametrize("family", ["solar_open2", "zaya"])
def test_the_per_head_hybrid_families_pools_are_what_they_were(family):
    """Solar-Open2's and ZAYA1's pools, built through their ``cache_kind``
    over the page kind they now name: the buffers' shapes, dtypes and the
    kind's description as before the hybrid kind took a page kind."""
    import importlib

    mod = importlib.import_module(f"deepspeed_tpu.models.{family}")
    cfg = mod.SOLAR_OPEN2_TINY if family == "solar_open2" else mod.ZAYA_TINY
    kind = mod.cache_kind(cfg, jnp.bfloat16)
    k, v = kind.buffers(cfg.n_layer, 9, 16)
    state = kind.state_buffers(2)
    if family == "solar_open2":
        assert k.shape == v.shape == (2, 9, 2, 16, 16) and k.dtype == jnp.bfloat16
        assert {n: (b.shape, str(b.dtype)) for n, b in state.items()} == {
            "s": ((6, 2, 4, 16, 16), "float32"), "conv": ((6, 2, 3, 192), "bfloat16")}
        assert kind.describe(8, 9, 16) == ("pages 2 x (2 of 8 layers x 9 pages x 2 heads x 16 page_len x 16 head_dim) + state per slot "
                                           "(s: 6 layers x 4 x 16 x 16 float32 + conv: 6 layers x 3 x 192 bfloat16)")
    else:
        assert k.shape == v.shape and k.shape[0] == cfg.n_layer and k.shape[1:] == (9, cfg.cca.kv_heads, 16, cfg.cca.head_dim)
        assert set(state) == {"conv", "vshift"} and state["conv"].shape[:2] == (cfg.n_layer, 2)
        assert kind.describe(cfg.n_layer, 9, 16).startswith(f"pages 2 x ({cfg.n_layer} of {cfg.n_layer} layers x 9 pages x ")
    assert not any(np.asarray(b, np.float32).any() for b in (k, v, *state.values()))
    assert type(kind.pages).__name__ == "PerHeadKV" and kind.pages_hold_all is False


@pytest.mark.parametrize("kind_name", ["per_head", "latent"])
def test_page_only_kinds_are_unchanged_by_the_state_group(kind_name):
    from deepspeed_tpu.serving.kvcache.pages import LatentKV, PerHeadKV

    if kind_name == "per_head":
        pool = PagedKVPool(3, 2, 2, 64, 8, jnp.float32, page_len=16, num_pages=9, prefill_chunk=16)
        assert isinstance(pool.kind, PerHeadKV) and pool.k.shape == pool.v.shape == (3, 9, 2, 16, 8)
        want = 2 * 3 * 9 * 2 * 16 * 8 * 4
    else:
        pool = PagedKVPool(3, 2, 0, 64, 0, jnp.float32, page_len=16, num_pages=9, prefill_chunk=16, kind=LatentKV(24, jnp.float32))
        assert pool.k.shape == (3, 9, 24, 16) and pool.v is None
        want = 3 * 9 * 24 * 16 * 4
    assert pool.state is None and pool.reuse and pool.state_bytes() == 0 and pool.cache_bytes() == want
    st = pool.stats()
    assert not {"kind", "state_bytes", "reuse", "sessions_unbound"} & set(st)
    # prefix reuse still works: the second request hits what the first taught
    prompt = np.arange(1, 41, dtype=np.int32)
    r1 = _KReq(1, prompt)
    r1.slot = pool.alloc_request(r1)
    pool.learn_prefix(r1)
    r2 = _KReq(2, prompt)
    r2.slot = pool.alloc_request(r2)
    assert r2.prefill_pos == 32 and pool.stats()["prefix_hits"] == 1


@pytest.mark.parametrize("group", [1, 2, 8])
def test_grouped_flash_decode_paged_matches_the_gather_and_lax_path(group):
    from deepspeed_tpu.ops.kernels import flash_decode as fd
    from deepspeed_tpu.ops.transformer import inference as inf

    B, Hkv, P, page_len, d = 3, 2, 3, 128, 16
    H, num_pages = Hkv * group, 1 + B * P
    rng = np.random.default_rng(group)
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((num_pages, Hkv, page_len, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((num_pages, Hkv, page_len, d)), jnp.float32)
    table = np.arange(1, num_pages, dtype=np.int32).reshape(B, P)
    table[0, 1:] = 0                              # a short row: its unmapped entries are the garbage page
    table = jnp.asarray(table)
    pos = jnp.asarray(np.array([37, 2 * page_len + 5, page_len - 1], np.int32))
    out = fd.flash_decode_paged(q, kc, vc, table, pos)
    ref = inf.paged_cache_attention(q, kc, vc, table, pos, use_kernel=False)  # gather, KV heads repeated, cache_attention
    assert out.shape == (B, H, 1, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # by hand for one (row, query head): head i attends KV head i // group
    b, i = 1, H - 1
    n = int(pos[b]) + 1
    keys = np.concatenate([np.asarray(kc[p, i // group]) for p in np.asarray(table[b])])[:n]
    vals = np.concatenate([np.asarray(vc[p, i // group]) for p in np.asarray(table[b])])[:n]
    s = keys @ np.asarray(q[b, i, 0]) / np.sqrt(d)
    p_ = np.exp(s - s.max())
    np.testing.assert_allclose(np.asarray(out[b, i, 0]), (p_ / p_.sum()) @ vals, rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="whole groups"):
        fd.flash_decode_paged(q[:, : H - 1] if H > 1 else jnp.zeros((B, 3, 1, d)), kc, vc, table, pos)


def test_paged_chunk_attention_and_slice_writes_are_the_whole_sequence_attention():
    """A chunk written through ``paged_cache_write_slices`` and attended
    block by block equals plain causal attention over the sequence."""
    from deepspeed_tpu.ops.transformer import inference as inf

    Hkv, G, page_len, d, T = 2, 2, 8, 8, 16
    rng = np.random.default_rng(0)
    seq = 40
    q = jnp.asarray(rng.standard_normal((1, Hkv * G, seq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, Hkv, seq, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, Hkv, seq, d)), jnp.float32)
    kp, vp = jnp.zeros((2, 9, Hkv, page_len, d)), jnp.zeros((2, 9, Hkv, page_len, d))
    table = jnp.asarray([[5, 2, 7, 1, 3, 8, 0, 0]], jnp.int32)
    outs = []
    for start in range(0, 48, T):
        n = min(T, seq - start)
        pad = lambda t: jnp.pad(t[:, :, start:start + n], ((0, 0), (0, 0), (0, T - n), (0, 0)))  # noqa: E731
        pos = jnp.asarray([start], jnp.int32)
        kp = inf.paged_cache_write_slices(kp, 1, pad(k), table, pos)
        vp = inf.paged_cache_write_slices(vp, 1, pad(v), table, pos)
        outs.append(inf.paged_chunk_attention(pad(q), kp[1], vp[1], table, pos, block_pages=3)[:, :, :n])
    got = jnp.concatenate(outs, axis=2)
    kr, vr = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kr) / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(kp[0]).any()  # the other layer untouched
    # a decode row with write_mask False goes to the garbage page
    one = jnp.ones((2, Hkv, 1, d))
    t2 = jnp.asarray([[5, 2, 7, 1, 3, 8, 0, 0], [4, 6, 0, 0, 0, 0, 0, 0]], jnp.int32)
    out = inf.paged_cache_write_slices(jnp.zeros_like(kp), 0, one, t2, jnp.asarray([9, 3], jnp.int32), jnp.asarray([True, False]))
    assert np.asarray(out[0, 2, :, 1]).all() and not np.asarray(out[0, 4]).any() and np.asarray(out[0, 0, :, 0]).all()


@pytest.mark.parametrize("start,T", [(3, 16), (5, 8), (13, 4), (8, 16), (0, 12), (30, 24), (60, 16)])
def test_slice_writes_split_a_chunk_at_the_page_edges_wherever_it_starts(start, T):
    """A chunk that starts inside a page (a prefix hit, any later caller)
    lands where the scatter puts it: no block is clamped onto a page
    boundary; what would pass the slot's last page is dropped, and a
    masked row goes to the garbage page."""
    from deepspeed_tpu.ops.transformer import inference as inf

    H, page_len, d = 2, 8, 4
    rng = np.random.default_rng(start * 31 + T)
    pool = jnp.asarray(rng.standard_normal((2, 12, H, page_len, d)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((2, H, T, d)), jnp.float32)
    table = jnp.asarray([[5, 2, 7, 1, 3, 8, 9, 10], [4, 6, 11, 0, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([start, 2], jnp.int32)
    mask = jnp.asarray([True, False])
    got = np.asarray(inf.paged_cache_write_slices(pool, 1, t, table, pos, mask))
    want = np.array(pool)
    for j in range(T):  # row 0, position by position; row 1 is masked: only the garbage page may change
        at = start + j
        if at < table.shape[1] * page_len:
            want[1, int(table[0, at // page_len]), :, at % page_len] = np.asarray(t[0, :, j])
    live = [p for p in range(12) if p != 0]
    np.testing.assert_array_equal(got[1, live], want[1, live])
    np.testing.assert_array_equal(got[0], np.asarray(pool[0]))  # the other layer untouched
    if start + T <= table.shape[1] * page_len:  # inside the slot: the scatter agrees
        ref = np.asarray(inf.paged_cache_write(pool[1], t, table, pos, mask))
        np.testing.assert_array_equal(got[1, live], ref[live])


# ---------------------------------------------------------------------------
# a decode step's rows through one aliased Mosaic call a leaf (ISSUE 49):
# ``paged_kv_write`` in interpret mode against the scatter, and the rule
# on the pool's shape that confines it
# ---------------------------------------------------------------------------

# name -> (layers, pages, H, page_len, d, kv dtype): the GPT-2 serve cells' page, a second narrow one, the int8 pair
WRITE_POOLS = {
    "bf16_25x128x64": (2, 11, 25, 128, 64, jnp.bfloat16),
    "f32_3x256x32": (3, 11, 3, 256, 32, jnp.float32),
    "int8_pair_4x128x64": (2, 11, 4, 128, 64, "int8"),
}
# name -> (pos of five rows over slots of two pages, write_mask or None); ``E`` is the slot's last position
WRITE_ROWS = {
    "offsets_0_and_last_and_the_slots_end": (("0", "L", "E", "7", "P"), None),
    "masked_rows_between_live_ones": (("3", "0", "L", "E", "9"), (True, False, False, True, False)),
    "one_row_writes": (("5", "6", "E", "8", "9"), (False, False, True, False, False)),
    "all_rows_masked": (("1", "L", "E", "4", "5"), (False,) * 5),
}


def _write_case(pool_name, rows_name):
    from deepspeed_tpu.ops.transformer import inference as inf

    L, NP, H, page_len, d, dt = WRITE_POOLS[pool_name]
    at, mask = WRITE_ROWS[rows_name]
    rng = np.random.default_rng(len(pool_name) * 100 + len(rows_name))
    if dt == "int8":
        pool = {"q": jnp.asarray(rng.integers(-127, 128, (L, NP, H, page_len, d)), jnp.int8),
                "s": jnp.asarray(rng.random((L, NP, H, page_len, 1)), jnp.float32)}
    else:
        pool = jnp.asarray(rng.standard_normal((L, NP, H, page_len, d)), dt)
    B, P = len(at), 2
    table = jnp.asarray(1 + rng.permutation(B * P).reshape(B, P), jnp.int32)  # page 0 is the garbage page
    named = {"L": page_len - 1, "P": page_len, "E": P * page_len - 1}
    pos = jnp.asarray([named.get(a) if a in named else int(a) for a in at], jnp.int32)
    t = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.bfloat16)
    return inf, pool, t, table, pos, None if mask is None else jnp.asarray(mask)


def _assert_layer_is_the_scatters(inf, got, pool, layer, t, table, pos, mask):
    """Layer ``layer`` of ``got`` is the scatter's result on every page
    but the garbage page, bit for bit, leaf by leaf."""
    want = inf.paged_cache_write(jax.tree.map(lambda a: a[layer], pool), t, table, pos, mask)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g[layer, 1:]), np.asarray(w[1:]))


@pytest.mark.parametrize("rows_name", list(WRITE_ROWS))
@pytest.mark.parametrize("pool_name", list(WRITE_POOLS))
def test_paged_kv_write_is_the_scatter_bit_for_bit_on_every_page_but_the_garbage_page(pool_name, rows_name):
    """One position a row into a pool of narrow heads through
    ``paged_kv_write`` (Pallas interpret mode), ``layer`` a Python int:
    the layer reads what ``paged_cache_write`` leaves on every real
    page, the other layers are untouched, and the real pages of rows
    that do not write keep what they held."""
    inf, pool, t, table, pos, mask = _write_case(pool_name, rows_name)
    assert inf.decode_write_takes_kernel(pool, True) and not inf.decode_write_takes_kernel(pool, False)
    got = inf.paged_cache_write_slices(pool, 1, t, table, pos, mask, use_kernel=True)
    _assert_layer_is_the_scatters(inf, got, pool, 1, t, table, pos, mask)
    for g, p in zip(jax.tree.leaves(got), jax.tree.leaves(pool)):
        np.testing.assert_array_equal(np.asarray(g[0]), np.asarray(p[0]))
        if mask is not None and not np.asarray(mask).any():  # nothing written: every real page as it was
            np.testing.assert_array_equal(np.asarray(g[1, 1:]), np.asarray(p[1, 1:]))


@pytest.mark.parametrize("rows_name", ["offsets_0_and_last_and_the_slots_end", "masked_rows_between_live_ones"])
@pytest.mark.parametrize("pool_name", list(WRITE_POOLS))
def test_paged_kv_write_takes_a_traced_layer_inside_a_loop_and_a_plan_built_once(pool_name, rows_name):
    """``layer`` the counter of a ``lax.fori_loop`` and the write's plan
    built once outside it, as a decode program's layer scan has them:
    every layer holds its own rows (scaled by the layer, so that a write
    into the wrong layer shows)."""
    inf, pool, t, table, pos, mask = _write_case(pool_name, rows_name)
    L = jax.tree.leaves(pool)[0].shape[0]
    plan = inf.decode_write_plan(pool, table, pos, mask, use_kernel=True)
    assert plan is not None and inf.decode_write_plan(pool, table, pos, mask, use_kernel=False) is None
    rows = lambda l: (t.astype(jnp.float32) * (l + 1)).astype(t.dtype)  # noqa: E731
    body = lambda l, c: inf.paged_cache_write_slices(c, l, rows(l), table, pos, mask, use_kernel=True, plan=plan)  # noqa: E731
    got = jax.jit(lambda c: jax.lax.fori_loop(0, L, body, c))(pool)
    for layer in range(L):
        _assert_layer_is_the_scatters(inf, got, pool, layer, rows(layer), table, pos, mask)


@pytest.mark.parametrize("pool_name", list(WRITE_POOLS))
def test_paged_kv_write_under_jit_takes_the_donated_pool_and_hands_it_back(pool_name):
    inf, pool, t, table, pos, mask = _write_case(pool_name, "masked_rows_between_live_ones")
    kept = jax.tree.map(jnp.copy, pool)
    write = jax.jit(lambda c: inf.paged_cache_write_slices(c, 0, t, table, pos, mask, use_kernel=True), donate_argnums=0)
    got = write(pool)
    assert all(a.is_deleted() for a in jax.tree.leaves(pool))  # donated, and taken
    _assert_layer_is_the_scatters(inf, got, kept, 0, t, table, pos, mask)


def _count_primitives(jaxpr, counts=None):
    """Primitive name -> occurrences in a jaxpr and the jaxprs under its
    equations, a ``pallas_call``'s own body left out."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _count_primitives(sub, counts)
    return counts


# name -> (pool (layers, pages, H, page_len, d), int8 pair?, T): whole-lane heads at one position and narrow ones over a chunk
SLICED = {
    "solar_open2_8x128x128": ((1, 9, 8, 128, 128), False, 1),
    "zaya1_2x128x128": ((2, 9, 2, 128, 128), False, 1),
    "keye_4x128x128": ((2, 9, 4, 128, 128), False, 1),
    "int8_pair_of_whole_lane_heads": ((2, 9, 2, 128, 128), True, 1),
    "narrow_heads_in_small_pages": ((2, 9, 4, 16, 8), False, 1),
    "a_chunk_into_narrow_heads": ((2, 9, 25, 128, 64), False, 64),
    "a_chunk_into_the_narrow_int8_pair": ((2, 9, 4, 128, 64), True, 200),
    "a_chunk_into_whole_lane_heads": ((2, 9, 2, 128, 128), False, 130),
}


def _abstract_write(shape, quant, T, B=3):
    from deepspeed_tpu.ops.transformer import inference as inf

    sds = jax.ShapeDtypeStruct
    pool = {"q": sds(shape, jnp.int8), "s": sds(shape[:-1] + (1,), jnp.float32)} if quant else sds(shape, jnp.bfloat16)
    args = (pool, sds((B, shape[2], T, shape[4]), jnp.bfloat16), sds((B, 2), jnp.int32), sds((B,), jnp.int32), sds((B,), jnp.bool_))
    return lambda use_kernel: jax.make_jaxpr(
        lambda c, t, table, pos, m: inf.paged_cache_write_slices(c, 1, t, table, pos, m, use_kernel=use_kernel))(*args)


@pytest.mark.parametrize("name", list(SLICED))
def test_the_mosaic_write_is_confined_a_pool_of_whole_lane_heads_and_every_chunk_keep_their_slices(name, monkeypatch):
    """Where ``d`` is a multiple of 128 (the Solar-Open2, ZAYA1 and Keye
    pools), where pages are not whole lane rows, and for a chunk at any
    ``d``, ``paged_cache_write_slices`` traces to the same jaxpr armed
    and not: the ``dynamic_update_slice``s it held before ISSUE 49 — one
    a row and leaf at one position, one a row, leaf and page a chunk can
    touch — and no ``pallas_call``."""
    shape, quant, T = SLICED[name]
    B, leaves = 3, 2 if quant else 1
    traced = _abstract_write(shape, quant, T, B)
    monkeypatch.setenv("DS_KERNELS", "1")
    armed, by_default_armed, not_armed = traced(True), traced(None), traced(False)
    assert str(armed) == str(by_default_armed) == str(not_armed)
    counts = _count_primitives(armed.jaxpr)
    assert "pallas_call" not in counts
    assert counts["dynamic_update_slice"] == leaves * B * (1 if T == 1 else -(-T // shape[3]) + 1)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8_pair"])
def test_the_mosaic_write_takes_a_narrow_pools_one_position_whole(quant, monkeypatch):
    """The narrow pool at one position, armed: one ``pallas_call`` a leaf
    and no ``dynamic_update_slice``; the suite off (``DS_KERNELS=0``),
    the slices as ever."""
    traced = _abstract_write((2, 9, 25, 128, 64), quant, 1)
    monkeypatch.setenv("DS_KERNELS", "1")
    counts = _count_primitives(traced(None).jaxpr)
    assert counts["pallas_call"] == (2 if quant else 1) and "dynamic_update_slice" not in counts and "scatter" not in counts
    monkeypatch.setenv("DS_KERNELS", "0")
    counts = _count_primitives(traced(None).jaxpr)
    assert "pallas_call" not in counts and counts["dynamic_update_slice"] == 3 * (2 if quant else 1)


# ---------------------------------------------------------------------------
# the per-head pool written in place (ISSUE 40): what the old scatter,
# gather and layer scan guaranteed, asked of the slices that replaced them
# ---------------------------------------------------------------------------

_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%?\S+ = (\S+?)\s([a-z][a-z\-]*)\(", re.M)
_HLO_DIMS = re.compile(r"\[([\d,]+)\]")


def _pool_shaped(hlo, pool_shape):
    """``(opcode, dims)`` of every instruction of an optimized HLO text
    whose result is shaped like the stacked pool, one layer of it, or
    its merged-leading-dims view (any trailing width: codes or scales)."""
    L, NP, H, PL = pool_shape[:4]
    out = []
    for m in _HLO_INSTR.finditer(hlo):
        for dims in _HLO_DIMS.findall(m.group(1)):
            t = tuple(int(x) for x in dims.split(","))
            if t[:-1] in ((L, NP, H, PL), (1, NP, H, PL), (NP, H, PL), (L * NP, H, PL)):
                out.append((m.group(2), t))
    return out


@pytest.mark.parametrize("which", ["prefill", "decode"])
@pytest.mark.parametrize("kv", ["model", "int8"])
def test_paged_gpt2_programs_update_the_pool_in_place(eng, kv, which):
    """Both programs of the paged per-head pool (bf16/f32 and the int8
    code+scale pair) hand the whole pool back aliased, keep less beside
    it than one layer's K+V, and hold no scatter, transpose, copy,
    gather, slice or concatenate shaped like the pool or a layer of it:
    only in-place slice updates (the parent's programs held all six)."""
    srv = ServingEngine(eng, num_slots=4, prefill_chunk=8, max_len=128, kv_cache_dtype=kv,
                        kvcache={"enabled": True, "page_len": 16})
    shape = jax.tree.leaves(srv.pool.k)[0].shape
    assert shape == (TINY.n_layer, 65, TINY.n_head, 16, TINY.head_dim)
    compiled = srv.compiled_step(which)
    m = compiled.memory_analysis()
    layer_kv = srv.pool.cache_bytes() // TINY.n_layer
    assert m.alias_size_in_bytes >= srv.pool.cache_bytes()
    # the allowance: a chunk's activations, logits and sampling scratch at this size (~0.45 MB measured)
    assert m.temp_size_in_bytes < layer_kv + 768 * 1024, (m.temp_size_in_bytes, layer_kv)
    hlo = compiled.as_text()
    assert " scatter(" not in hlo
    moved = {op for op, _ in _pool_shaped(hlo, shape)} - {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
                                                          "dynamic-update-slice", "fusion"}
    assert moved == set(), moved
    st = srv.stats()
    assert st["kv_write_form"] == "slices, in place"
    if which == "prefill":
        assert st["prefill_attend_form"].startswith("blockwise")


def _serve_all(srv, waves, max_new):
    """Submit ``waves`` (lists of prompts) one after the other — a wave
    is drained before the next is admitted, so the later ones can hit
    what the earlier ones taught the pool — and return the tokens."""
    out = []
    for wave in waves:
        rids = [srv.submit(p, max_new_tokens=max_new) for p in wave]
        res = srv.drain(max_steps=800)
        out += [res[r].tokens() for r in rids]
    return out


def _parity_traffic(case):
    """``(engine kwargs, waves of prompts, max_new)`` of one parity case."""
    if case == "a_chunk_and_a_decode_step_across_a_page_edge":
        # chunks of 12 over pages of 16: the chunk [12, 24) straddles the edge
        # at 16, the 13-token prompt's decode steps walk over it
        return dict(prefill_chunk=12, max_len=96), [_prompts(1, 13, 13, seed=21) + _prompts(1, 30, 30, seed=22)], 8
    if case == "a_prefix_hit_that_starts_inside_a_page_with_a_copy_on_write":
        # 24 shared tokens = a page and a half: the hit's last page is shared
        # and partial (copy-on-write), the first chunk starts at 24, inside it
        # (the index learns the split from the second prompt: the third and fourth hit)
        shared = _prompts(1, 24, 24, seed=23)[0]
        tails = _prompts(4, 5, 11, seed=24)
        return dict(prefill_chunk=8, max_len=64), [[np.concatenate([shared, t])] for t in tails], 5
    if case == "a_masked_row_on_the_garbage_page":
        # three slots, two requests: the short one decodes through the long
        # one's five prefill chunks (its row masked) beside a slot that is empty
        return dict(prefill_chunk=8, max_len=64, num_slots=3), [_prompts(1, 4, 4, seed=25) + _prompts(1, 40, 40, seed=26)], 10
    raise AssertionError(case)


@pytest.mark.parametrize("kv", ["model", "int8"])
@pytest.mark.parametrize("case", [
    "a_chunk_and_a_decode_step_across_a_page_edge",
    "a_prefix_hit_that_starts_inside_a_page_with_a_copy_on_write",
    "a_masked_row_on_the_garbage_page",
])
def test_paged_tokens_are_the_slot_pools_and_generates(eng, case, kv):
    """The paged engine's greedy tokens equal the slot-contiguous pool's
    on the same schedule — and solo ``generate()``'s, for the pool in the
    model's dtype — where the slice writes and the blockwise chunk
    attention differ most from the scatter and the gathered logical
    cache they replaced."""
    kw, waves, max_new = _parity_traffic(case)
    kw.setdefault("num_slots", 2)
    paged = ServingEngine(eng, kv_cache_dtype=kv, kvcache={"enabled": True, "page_len": 16}, **kw)
    slot = ServingEngine(eng, kv_cache_dtype=kv, **kw)
    got, want = _serve_all(paged, waves, max_new), _serve_all(slot, waves, max_new)
    for p, g, w in zip([p for wave in waves for p in wave], got, want):
        np.testing.assert_array_equal(g, w)
        if kv == "model":
            np.testing.assert_array_equal(g, _solo(eng, p, max_new))
    kvs = paged.stats()["kvcache"]
    if "copy_on_write" in case:
        assert kvs["prefix_hits"] >= 2 and kvs["cow_copies"] >= 1 and kvs["tokens_saved"] >= 2 * 24
    assert paged.prefill_compiles == 1 and paged.decode_compiles == 1
    assert paged.pool.live_slots == 0
    _assert_no_leaks(paged.pool)


@pytest.mark.parametrize("d", [8, 128], ids=["head_narrower_than_the_lanes", "head_of_whole_lanes"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_slice_writes_and_chunk_attention_are_the_scatter_and_the_gathered_cache(d, quant):
    """Three chunks written into layer 1 of a stacked pool by slices and
    attended block by block equal the scatter (``paged_cache_write``) and
    the gathered logical cache (``paged_cache_attention``'s lax form) —
    for both ways a chunk reads the pool (pages gathered a block at a
    time; a narrow head's pages sliced out once) and for the int8 pair."""
    from deepspeed_tpu.ops.transformer import inference as inf

    H, page_len, T, P = 2, 8, 12, 6
    rng = np.random.default_rng(d + quant)
    kp, vp = inf.init_kv_cache(2, 9, H, page_len, d, "int8" if quant else jnp.float32)
    kr, vr = (jax.tree.map(lambda a: a[1], c) for c in (kp, vp))  # the scatter's copy of layer 1
    table = jnp.asarray([[5, 2, 7, 1, 3, 8]], jnp.int32)
    for start in (0, 12, 24):
        k, v, q = (jnp.asarray(rng.standard_normal((1, H, T, d)), jnp.float32) for _ in range(3))
        pos = jnp.asarray([start], jnp.int32)
        kp, vp = (inf.paged_cache_write_slices(c, 1, t, table, pos) for c, t in ((kp, k), (vp, v)))
        kr, vr = (inf.paged_cache_write(c, t, table, pos) for c, t in ((kr, k), (vr, v)))
        for got, ref in ((kp, kr), (vp, vr)):
            jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b)), got, ref)
        kc, vc, tab = inf.layer_pages(kp, vp, table, 1)
        got = inf.paged_chunk_attention(q, kc, vc, tab, pos, block_pages=2)
        want = inf.paged_cache_attention(q, kr, vr, table, pos, use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)
    assert not any(np.asarray(a[0]).any() for a in jax.tree.leaves((kp, vp)))  # the other layer untouched


def test_page_copy_is_one_page_of_every_layer_and_the_identity_on_itself():
    from deepspeed_tpu.ops.transformer import inference as inf

    rng = np.random.default_rng(3)
    pool = {"q": jnp.asarray(rng.integers(-127, 127, (3, 6, 2, 4, 8)), jnp.int8),
            "s": jnp.asarray(rng.standard_normal((3, 6, 2, 4, 1)), jnp.float32)}
    out = jax.jit(inf.page_copy)(pool, jnp.int32(2), jnp.int32(5))
    for name, buf in pool.items():
        want = np.array(buf)
        want[:, 5] = want[:, 2]
        np.testing.assert_array_equal(np.asarray(out[name]), want)
    same = inf.page_copy(pool, jnp.int32(GARBAGE_PAGE), jnp.int32(GARBAGE_PAGE))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), same, pool)


def _work_case(name, P, page_len):
    """``(pos, live)`` of three rows: what a decode step's work list is built from."""
    full = P * page_len - 1
    return {
        "ragged": ([37, 2 * page_len + 5, page_len - 1], None),
        "some_rows_not_live": ([37, 2 * page_len + 5, page_len + 1], [True, False, True]),
        "no_row_live": ([37, 2 * page_len + 5, 3], [False, False, False]),
        "every_page_filled": ([full, full, full], None),
        "pos_on_a_page_boundary": ([page_len, 2 * page_len, 0], [True, True, True]),
    }[name]


WORK_CASES = ["ragged", "some_rows_not_live", "no_row_live", "every_page_filled", "pos_on_a_page_boundary"]


WORK_FORMS = {"multi_head_d64": (2, 1, 64, False), "grouped_d128": (2, 4, 128, False), "int8_pool": (2, 1, 64, True),
              # multi-head attention with every head of a page in one program, an odd count of them
              "five_heads_a_program_d64": (5, 1, 64, False), "five_heads_a_program_d128": (5, 1, 128, False),
              "five_heads_a_program_d64_int8": (5, 1, 64, True), "five_heads_a_program_d128_int8": (5, 1, 128, True)}


@pytest.mark.parametrize("case", WORK_CASES)
@pytest.mark.parametrize("form", list(WORK_FORMS))
def test_flash_decode_paged_walks_its_work_list(form, case):
    """The paged decode kernel over a work list against the gather + lax
    form, for the shapes the serve programs hand it — multi-head with a
    head of half a lane row (``(d, page_len)`` tiles), grouped queries
    at a whole one, the int8 code + scale pair, multi-head with every
    head of a page in one program at both tile forms and both pools —
    and the lists a step can hold.  A row the list does not visit reads 0."""
    from deepspeed_tpu.ops.kernels import flash_decode as fd
    from deepspeed_tpu.ops.transformer import inference as inf

    Hkv, group, d, quant = WORK_FORMS[form]
    B, P, page_len, L = 3, 3, 128, 2
    H, num_pages = Hkv * group, 1 + B * P
    rng = np.random.default_rng(WORK_CASES.index(case) + 7 * group + quant + 11 * Hkv)
    kp, vp = inf.init_kv_cache(L, num_pages, Hkv, page_len, d, "int8" if quant else jnp.bfloat16)
    table = jnp.asarray(np.arange(1, num_pages, dtype=np.int32).reshape(B, P))
    rows = lambda: jnp.asarray(rng.standard_normal((B, Hkv, P * page_len, d)), jnp.bfloat16)  # noqa: E731
    zero = jnp.zeros((B,), jnp.int32)
    kp, vp = inf.paged_cache_write_slices(kp, 1, rows(), table, zero), inf.paged_cache_write_slices(vp, 1, rows(), table, zero)
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.bfloat16)
    pos, live = _work_case(case, P, page_len)
    pos = jnp.asarray(np.array(pos, np.int32))
    live = None if live is None else jnp.asarray(live)
    kc, vc, tab = inf.layer_pages(kp, vp, table, 1)
    assert fd.paged_tile(kc, P) == (Hkv, 1)  # every KV head a program; three pages a slot: no span but 1
    work = fd.paged_work_list(pos, live, page_len, P)
    got = fd.flash_decode_paged(q, kc, vc, tab, pos, work=work)
    want = np.asarray(inf.paged_cache_attention(q, kc, vc, tab, pos, use_kernel=False), np.float32)
    if live is not None:
        want = np.where(np.asarray(live)[:, None, None, None], want, 0.0)
    assert got.shape == (B, H, 1, d)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=2e-2, rtol=2e-2)
    # the seam hands the list on, and builds it from ``pos`` where it is given none
    seam = inf.paged_cache_attention(q, kc, vc, tab, pos, use_kernel=True, work=work)
    np.testing.assert_array_equal(np.asarray(seam, np.float32), np.asarray(got, np.float32))
    if live is None:
        five = fd.flash_decode_paged(q, kc, vc, tab, pos)
        np.testing.assert_array_equal(np.asarray(five, np.float32), np.asarray(got, np.float32))


def test_engine_counts_the_pages_a_decode_step_walks_against_the_grid_of_every_page(eng):
    """``stats()["decode_pages_walked"]`` is the work list's items summed
    over the decode steps — each decoding row's ``pos // page_len + 1`` —
    and ``["decode_pages_grid"]`` what a grid over every page of every
    slot walks; host-side sums, on every paged engine."""
    srv = _srv(eng, num_slots=2, max_len=64, kvcache={"page_len": 16})
    lens, new = (5, 30), 6
    rids = [srv.submit(np.arange(1, n + 1, dtype=np.int32), max_new_tokens=new) for n in lens]
    res = srv.drain(max_steps=300)
    assert [len(res[rid].tokens()) for rid in rids] == [n + new for n in lens]
    st = srv.stats()
    # the first token is the prefill's; the g-th decode step of a request attends positions 0 ... prompt + g - 1
    assert st["decode_pages_walked"] == sum((n + g - 1) // 16 + 1 for n in lens for g in range(1, new))
    assert st["decode_pages_grid"] % (2 * 4) == 0 and new - 1 <= st["decode_pages_grid"] // (2 * 4) <= 2 * (new - 1)
    assert 0 < st["decode_pages_walked"] < st["decode_pages_grid"]
    assert "paged_decode_walk" not in st  # pages of 16 rows: the gather + lax form ran, and nothing walked a list
    # the tile the kernel would read off this pool: every KV head a program, the slot's four pages one item
    from deepspeed_tpu.ops.kernels.flash_decode import paged_tile

    assert paged_tile(srv.pool.k, srv.pool.pages_per_slot) == (TINY.n_head, 4)
    assert st["decode_grid_steps"] == len(lens) * (new - 1)       # one item a decoding row a step, one block of heads
    assert st["decode_pages_read"] == 4 * st["decode_grid_steps"] >= st["decode_pages_walked"]  # the spans' masked tails included
    off = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64)
    assert not {"decode_pages_walked", "decode_grid_steps", "decode_pages_read"} & set(off.stats())


def test_paged_engine_decodes_through_the_work_list_kernel_as_through_the_gather_form(monkeypatch):
    """A paged engine whose pages qualify for the kernel (128 rows),
    armed: its decode program walks the work list inside the layer
    scan — slots that are empty or still prefilling are rows the list
    does not visit — and emits what the gather + lax form emits."""
    cfg = dataclasses.replace(TINY, n_positions=256)
    params = gpt2.init_params(cfg, seed=7)
    params["wpe"] = params["wpe"] * 40.0
    inf_eng = deepspeed_tpu.init_inference(model_config=cfg, params=params, dtype=jnp.float32, max_out_tokens=256)
    prompts = [np.arange(1, n + 1, dtype=np.int32) % 500 + 1 for n in (5, 140, 70)]  # one, two and one page: three requests over two slots

    def served(armed):
        monkeypatch.setenv("DS_KERNELS", armed)
        srv = ServingEngine(inf_eng, num_slots=2, prefill_chunk=64, max_len=256, kvcache={"enabled": True, "page_len": 128})
        rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
        res = srv.drain(max_steps=400)
        return [np.asarray(res[r].tokens()) for r in rids], srv.stats()

    got, st = served("1")
    want, st_off = served("0")
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    # two pages a slot: both in one item, under both heads' one program
    assert st["paged_decode_walk"] == f"work list, {cfg.n_head} heads x 2 pages" and "paged_decode_walk" not in st_off
    # ... and wrote its rows through ``paged_kv_write`` (heads of 16 in pages of 128), the other through slices
    assert (st["kv_write_form"], st_off["kv_write_form"]) == ("paged_kv_write a decode step, slices a chunk, in place", "slices, in place")
    assert st["decode_pages_walked"] == st_off["decode_pages_walked"] > 0
    for s_ in (st, st_off):  # host-side sums under the tile the pool's shape gives, whichever form ran
        assert s_["decode_pages_read"] == 2 * s_["decode_grid_steps"] and s_["decode_grid_steps"] == st["decode_grid_steps"]
    assert st["decode_pages_walked"] / st["decode_grid_steps"] > 1.0  # the row of 140 tokens: two pages a grid step
    assert st["decode_pages_walked"] < st["decode_pages_read"]       # the rows of one page: the second is read and masked


def test_paged_work_list_is_the_live_rows_filled_pages_in_slot_then_page_order():
    from deepspeed_tpu.ops.kernels.flash_decode import paged_work_list

    page_len, P = 128, 4
    pos = jnp.asarray(np.array([130, 5, 4 * 128 - 1, 128, 9000], np.int32))
    live = jnp.asarray([True, False, True, True, True])
    slot, page, n, visited = (np.asarray(a) for a in paged_work_list(pos, live, page_len, P))
    # rows 0, 2, 3, 4: 2, 4, 2 and (a position past the slot is held to its last page) 4 pages
    want = [(0, 0), (0, 1), (2, 0), (2, 1), (2, 2), (2, 3), (3, 0), (3, 1), (4, 0), (4, 1), (4, 2), (4, 3)]
    assert slot.shape == page.shape == (5 * P,) and slot.dtype == page.dtype == np.int32
    assert n.shape == (1,) and int(n[0]) == len(want)
    assert list(zip(slot[: len(want)].tolist(), page[: len(want)].tolist())) == want
    # past the count the last item repeats: a grid step that is not walked fetches nothing new
    assert set(zip(slot[len(want):].tolist(), page[len(want):].tolist())) == {want[-1]}
    np.testing.assert_array_equal(visited, np.asarray(live))
    # every row live where nobody says otherwise
    slot, page, n, visited = (np.asarray(a) for a in paged_work_list(pos[:2], None, page_len, P))
    assert int(n[0]) == 3 and list(zip(slot[:3].tolist(), page[:3].tolist())) == [(0, 0), (0, 1), (1, 0)] and visited.all()
    # no row live: no item, and the padding names a block that exists
    slot, page, n, visited = (np.asarray(a) for a in paged_work_list(pos, jnp.zeros((5,), bool), page_len, P))
    assert int(n[0]) == 0 and not slot.any() and not page.any() and not visited.any()


def test_paged_work_list_over_spans_is_the_live_rows_filled_spans_in_slot_then_span_order():
    from deepspeed_tpu.ops.kernels.flash_decode import paged_work_list

    page_len, P = 128, 8
    pos = jnp.asarray(np.array([130, 5, 8 * 128 - 1, 4 * 128, 9000, 0], np.int32))
    live = jnp.asarray([True, False, True, True, True, True])
    filled = [2, 0, 8, 5, 8, 1]  # pages a live row holds (a position past the slot is held to its last page)
    for span in (1, 2, 4, 8):
        slot, at, n, visited = (np.asarray(a) for a in paged_work_list(pos, live, page_len, P, span))
        want = [(b, s) for b, f in enumerate(filled) for s in range(-(-f // span))]
        assert slot.shape == at.shape == (6 * P // span,) and slot.dtype == at.dtype == np.int32
        assert int(n[0]) == len(want) and list(zip(slot[: len(want)].tolist(), at[: len(want)].tolist())) == want
        assert set(zip(slot[len(want):].tolist(), at[len(want):].tolist())) <= {want[-1]}  # the padding repeats the last item
        np.testing.assert_array_equal(visited, np.asarray(live))
    # span 1 is the list of pages; no row live: no item; a span that does not tile the slot is refused
    for a, b in zip(paged_work_list(pos, live, page_len, P), paged_work_list(pos, live, page_len, P, 1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    slot, at, n, visited = (np.asarray(a) for a in paged_work_list(pos, jnp.zeros((6,), bool), page_len, P, 4))
    assert int(n[0]) == 0 and not slot.any() and not at.any() and not visited.any()
    with pytest.raises(ValueError, match="do not tile"):
        paged_work_list(pos, live, page_len, 6, 4)


@pytest.mark.parametrize("shape,dtype,pages_per_slot,tile", [
    ((48, 129, 25, 128, 64), jnp.bfloat16, 8, (25, 1)),     # GPT-2 XL: 800 KB a page of 25 heads, one page an item
    ((48, 129, 25, 128, 64), jnp.int8, 8, (25, 2)),         # its int8 codes: half the bytes, two pages
    ((1, 2560, 8, 128, 128), jnp.bfloat16, 64, (8, 2)),     # Solar-Open2's GQA layer: 512 KB a page
    ((20, 2049, 2, 128, 128), jnp.bfloat16, 64, (2, 8)),    # ZAYA1: 128 KB a page, eight of them
    ((8, 4225, 4, 128, 128), jnp.bfloat16, 264, (4, 4)),    # Keye-like: 256 KB a page, four (as sparse_decode.span_of)
    ((2, 64, 96, 128, 128), jnp.bfloat16, 16, (16, 1)),     # a many-headed multi-head pool: a proper divisor under the budget
    ((2, 64, 7, 128, 128), jnp.float32, 9, (7, 1)),         # nine pages a slot: no span but 1 divides
    ((2, 64, 2, 128, 16), jnp.float32, 6, (2, 2)),          # six: 2, not 4
    ((2, 64, 3, 512, 256), jnp.float32, 8, (1, 1)),         # one head of one page is past the budget: one a program all the same
], ids=["gpt2_xl", "gpt2_xl_int8", "solar_open2", "zaya1", "keye_like", "mha_96_heads", "nine_pages", "six_pages", "a_pair_past_the_budget"])
def test_paged_tile_is_read_off_the_pool_shape(shape, dtype, pages_per_slot, tile):
    from deepspeed_tpu.ops.kernels import flash_decode as fd

    pool = jax.ShapeDtypeStruct(shape, dtype)
    assert fd.paged_tile(pool, pages_per_slot) == tile
    assert fd.paged_tile({"q": pool, "s": jax.ShapeDtypeStruct(shape[:-1] + (1,), jnp.float32)}, pages_per_slot) == tile
    assert fd.paged_tile(jax.ShapeDtypeStruct(shape[1:], dtype), pages_per_slot) == tile  # a layer's pages: the same tile
    heads, span = tile
    pair = 2 * shape[-2] * shape[-1] * jnp.dtype(dtype).itemsize
    assert shape[2] % heads == 0 and pages_per_slot % span == 0
    assert heads * span * pair <= fd.TILE_BYTES or (heads, span) == (1, 1)


SPANS = {1: 3, 2: 6, 4: 4, 8: 8}  # pages an item: pages a slot that give it (2 KV heads x 128 x 128 bf16: 128 KB a page)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("span", list(SPANS))
def test_flash_decode_paged_walks_spans_of_pages(span, quant):
    """An item of the work list is a span of 1, 2, 4 or 8 consecutive
    pages of a row, each an operand of its own (the int8 pool's scales
    follow their pages): against the gather + lax form with a row whose
    last span reaches past its position — over pages that hold large
    finite garbage —, a row that ends on a span's last position, a row
    that does not decode, and a step in which none does."""
    from deepspeed_tpu.ops.kernels import flash_decode as fd
    from deepspeed_tpu.ops.transformer import inference as inf

    P = SPANS[span]
    B, Hkv, group, d, page_len, L = 4, 2, 4, 128, 128, 2
    H, num_pages = Hkv * group, 1 + B * P
    rng = np.random.default_rng(span + 10 * quant)
    kp, vp = inf.init_kv_cache(L, num_pages, Hkv, page_len, d, "int8" if quant else jnp.bfloat16)
    table = jnp.asarray(np.arange(1, num_pages, dtype=np.int32).reshape(B, P))
    pos = np.array([page_len + 3, P * page_len - 1, 37, span * page_len - 1], np.int32)  # the last: a whole first span
    live = np.array([True, True, False, True])
    # every page of every slot written: past a row's position lies large finite garbage, not zeros
    rows = rng.standard_normal((2, B, Hkv, P * page_len, d))
    past = np.arange(P * page_len)[None, :] > pos[:, None]
    rows = np.where(past[None, :, None, :, None], 3e4 * np.sign(rows), rows)
    zero = jnp.zeros((B,), jnp.int32)
    kp, vp = (inf.paged_cache_write_slices(c, 1, jnp.asarray(r, jnp.bfloat16), table, zero) for c, r in ((kp, rows[0]), (vp, rows[1])))
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.bfloat16)
    kc, vc, tab = inf.layer_pages(kp, vp, table, 1)
    assert fd.paged_tile(kc, P) == (Hkv, span)
    want = np.asarray(inf.paged_cache_attention(q, kc, vc, tab, jnp.asarray(pos), use_kernel=False), np.float32)
    assert np.isfinite(want).all()
    for mask in (live, np.zeros((B,), bool), None):
        work = fd.paged_work_list(jnp.asarray(pos), None if mask is None else jnp.asarray(mask), page_len, P, span)
        assert int(work[2][0]) == sum(int(p) // (page_len * span) + 1 for p, m in zip(pos, np.ones(B, bool) if mask is None else mask) if m)
        got = np.asarray(fd.flash_decode_paged(q, kc, vc, tab, jnp.asarray(pos), work=work), np.float32)
        np.testing.assert_allclose(got, want if mask is None else np.where(mask[:, None, None, None], want, 0.0), atol=2e-2, rtol=2e-2)
    # the five-argument call builds the same list; a list built under another span is refused, not walked
    np.testing.assert_array_equal(np.asarray(fd.flash_decode_paged(q, kc, vc, tab, jnp.asarray(pos)), np.float32), got)
    if span > 1:
        with pytest.raises(ValueError, match="not one over spans"):
            fd.flash_decode_paged(q, kc, vc, tab, jnp.asarray(pos), work=fd.paged_work_list(jnp.asarray(pos), None, page_len, P))


@pytest.mark.parametrize("d", [64, 128], ids=["pages_tiled_d_by_page_len", "pages_tiled_page_len_by_d"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_flash_decode_paged_reads_both_tile_forms(d, quant):
    """The paged decode kernel against the gather + lax form at a head
    of half a lane row (handed ``(d, page_len)`` tiles) and of a whole
    one (``(page_len, d)``), a bf16 pool and the int8 pair, through the
    merged view of a stacked pool at a layer other than the first."""
    from deepspeed_tpu.ops.kernels import flash_decode as fd
    from deepspeed_tpu.ops.transformer import inference as inf

    B, H, P, page_len, L = 2, 2, 3, 128, 2
    num_pages = 1 + B * P
    rng = np.random.default_rng(d + quant)
    kp, vp = inf.init_kv_cache(L, num_pages, H, page_len, d, "int8" if quant else jnp.bfloat16)
    table = jnp.asarray(np.arange(1, num_pages, dtype=np.int32).reshape(B, P))
    rows = lambda: jnp.asarray(rng.standard_normal((B, H, P * page_len, d)), jnp.bfloat16)  # noqa: E731
    zero = jnp.zeros((B,), jnp.int32)
    kp, vp = inf.paged_cache_write_slices(kp, 1, rows(), table, zero), inf.paged_cache_write_slices(vp, 1, rows(), table, zero)
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.bfloat16)
    pos = jnp.asarray(np.array([37, 2 * page_len + 5], np.int32))
    kc, vc, tab = inf.layer_pages(kp, vp, table, 1)
    assert fd.decode_paged_supported(B, H, P, page_len, d)
    got = fd.flash_decode_paged(q, kc, vc, tab, pos)
    want = inf.paged_cache_attention(q, kc, vc, tab, pos, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)


def test_indexed_kind_third_leaf_is_written_shared_copied_on_write_freed_and_reused(tmp_path):
    """A cache kind whose pages carry a third leaf (an indexer key a
    position): the leaf is written by slice writes under the same page
    table as K and V, shared with them on a prefix hit, copied with them
    on write, spilled and restored under its own name, and its pages go
    back to the free list and out again with theirs."""
    from deepspeed_tpu.ops.transformer import inference as inf
    from deepspeed_tpu.ops.transformer import sparse_attention as dsa
    from deepspeed_tpu.serving.kvcache.pages import IndexedKV, _named_leaves

    L, Hkv, d, di, page_len = 2, 2, 8, 4, 8
    pool = PagedKVPool(L, 2, Hkv, 64, d, jnp.float32, page_len=page_len, num_pages=12, prefill_chunk=4,
                       kind=IndexedKV(Hkv, d, di, jnp.float32), spill_dir=str(tmp_path))
    assert set(pool.k) == {"k", "idx"} and pool.k["k"].shape == pool.v.shape == (L, 12, Hkv, page_len, d)
    assert pool.k["idx"].shape == (L, 12, di, page_len) and pool.state is None and pool.reuse  # positions along the lanes
    st = pool.stats()
    assert st["page_leaves"] == {"k": L * 12 * Hkv * page_len * d * 4, "v": L * 12 * Hkv * page_len * d * 4, "idx": L * 12 * page_len * di * 4}
    assert sum(st["page_leaves"].values()) == pool.cache_bytes() and "indexer keys" in st["kind"] and "reuse" not in st
    assert set(_named_leaves(pool.k, pool.v)) == {"k.k", "k.idx", "v"} and "float32" in pool.shape_math()

    r0 = _KReq("r0", list(range(1, 15)), max_new=2)  # 14 tokens: a full page and a partly filled one
    r0.slot = pool.alloc_request(r0)
    table = jnp.asarray(pool.table(r0.slot))[None]
    rows = jnp.arange(14 * di, dtype=jnp.float32).reshape(1, 14, di) + 1.0
    kv = jnp.arange(14 * Hkv * d, dtype=jnp.float32).reshape(1, 14, Hkv, d).transpose(0, 2, 1, 3) + 1.0
    for layer in range(L):  # a chunk that starts inside a page (position 3), after a first one of three
        for at, n in ((0, 3), (3, 11)):
            pos = jnp.asarray([at], jnp.int32)
            pool.k = {"k": inf.paged_cache_write_slices(pool.k["k"], layer, kv[:, :, at:at + n] * (layer + 1), table, pos),
                      "idx": dsa.index_cache_write(pool.k["idx"], layer, rows[:, at:at + n] * (layer + 1), table, pos)}
            pool.v = inf.paged_cache_write_slices(pool.v, layer, -kv[:, :, at:at + n] * (layer + 1), table, pos)
    ctx = np.asarray(dsa.index_context(pool.k["idx"], 1, table))[0]
    np.testing.assert_array_equal(ctx[:14], 2 * np.asarray(rows[0]))
    assert not ctx[14:].any() and not np.asarray(pool.k["idx"][:, GARBAGE_PAGE]).any()
    one = dsa.index_cache_write(pool.k["idx"], 0, jnp.full((1, 1, di), 7.0), table, jnp.asarray([14], jnp.int32), jnp.asarray([False]))
    assert (np.asarray(one[0, GARBAGE_PAGE, :, 0]) == 7.0).all() and np.array_equal(np.asarray(one[:, 1:]), np.asarray(pool.k["idx"][:, 1:]))

    pool.learn_prefix(r0)
    shared = list(pool._slot_pages[r0.slot])
    r1 = _KReq("r1", list(range(1, 15)) + [99, 98], max_new=2)  # starts with r0's prompt: a hit of 12, the tail page copied on write
    r1.slot = pool.alloc_request(r1)
    assert r1.prefill_pos == 12 and pool._slot_pages[r1.slot][0] == shared[0] and pool.refcount(shared[0]) == 3
    src, dst = pool.consume_cow(r1.slot)
    assert (src, dst) == (shared[1], pool._slot_pages[r1.slot][1]) and dst != src
    pool.k, pool.v = inf.page_copy(pool.k, src, dst), inf.page_copy(pool.v, src, dst)  # what a prefill program does with the pair
    for name, buf in _named_leaves(pool.k, pool.v).items():
        np.testing.assert_array_equal(np.asarray(buf[:, dst]), np.asarray(buf[:, src]), err_msg=name)
    assert np.asarray(pool.k["idx"][:, dst]).any()

    # spilled and restored under its own name, with K and V
    host = pool._gather_host([shared[0], dst])
    assert set(host) == {"k.k", "k.idx", "v"} and host["k.idx"].shape == (L, 2, di, page_len)
    before = np.asarray(pool.k["idx"][:, shared[0]])
    pool._scatter_device([dst, shared[0]], host)  # swapped
    np.testing.assert_array_equal(np.asarray(pool.k["idx"][:, dst]), before)

    free0 = pool.pages_free
    pool.retire(r0.slot, r0)
    pool.retire(r1.slot, r1)
    assert pool.refcount(dst) == 0 and pool.pages_free > free0  # r1's private pages are free again; the entry keeps the shared ones
    _assert_no_leaks(pool)
    r2 = _KReq("r2", list(range(50, 80)), max_new=2)
    r2.slot = pool.alloc_request(r2)
    assert dst in pool._slot_pages[r2.slot] or pool.pages_free < free0 + 2  # freed pages go out again
    pool.retire(r2.slot, r2)
    _assert_no_leaks(pool)


# ---------------------------------------------------------------------------
# two page groups in one pool: pages by length + a ring of pages a slot (WindowedKV)
# ---------------------------------------------------------------------------

def _windowed_pool(num_pages=41, max_len=256, **kw):
    from deepspeed_tpu.serving.kvcache.pages import WindowedKV

    kind = WindowedKV(full_layers=2, window_layers=3, kv_heads=2, head_dim=8, window=40, dtype=jnp.float32)
    return PagedKVPool(5, 3, 0, max_len, 0, jnp.float32, page_len=16, num_pages=num_pages, prefill_chunk=32, kind=kind, **kw)


def test_windowed_kind_has_pages_by_length_for_its_full_layers_and_a_ring_a_slot_for_its_window_layers():
    pool = _windowed_pool()
    assert pool.k.shape == pool.v.shape == (2, 41, 2, 16, 8)  # the two full layers of the five
    # window 40 on pages of 16: the page of the newest position and the three the window reaches back over, + the garbage page
    assert pool.kind.ring_pages(16) == 4 and pool.state["wk"].shape == pool.state["wv"].shape == (3, 1 + 3 * 4, 2, 16, 8)
    full_bytes, window_bytes = 2 * 2 * 41 * 2 * 16 * 8 * 4, 2 * 3 * 13 * 2 * 16 * 8 * 4
    assert pool.state_bytes() == window_bytes and pool.cache_bytes() == full_bytes + window_bytes
    st = pool.stats()
    g = st["groups"]
    geometry = lambda layers: {"kv_heads": 2, "k_dim": 8, "v_dim": 8, "position_bytes": layers * 2 * 16 * 4}  # noqa: E731  one geometry for both groups
    assert g["full"] == {"layers": 2, **geometry(2), "pages_per_slot": "by length, up to 16", "positions_per_slot": "by length, up to 256",
                         "bytes": full_bytes}
    assert g["window"] == {"layers": 3, **geometry(3), "window": 40, "pages_per_slot": 4, "positions_per_slot": 64, "slots_live": 0,
                           "bytes": window_bytes}
    assert st["page_kind"] == "PerHeadKV" and st["state_leaves"] == {"wk": window_bytes // 2, "wv": window_bytes // 2}
    math = pool.shape_math()
    assert "full-attention pages by length 2 x (2 of 5 layers x 41 pages" in math and "window ring per slot (3 layers x 4 pages" in math
    assert st["kind"] == pool.kind.describe(5, 41, 16) and "window 40" in st["kind"]
    assert not pool.reuse and pool.kind.pages_hold_all is False


def test_windowed_kind_takes_a_geometry_a_group_and_a_value_width_and_stores_each_leaf_as_wide_as_it_is():
    """MiMo-V2's pool at a small size: 2 KV heads on the full group's pages, 4 on the window group's rings, keys 24 wide beside
    values 16 wide in both — four leaves of four shapes under one allocator, none padded to another."""
    from deepspeed_tpu.serving.kvcache.pages import PerHeadKV, WindowedKV

    kind = WindowedKV(full_layers=2, window_layers=3, kv_heads=2, head_dim=24, window=6, dtype=jnp.bfloat16, v_dim=16,
                      window_pages=PerHeadKV(4, 24, jnp.bfloat16, 16))
    pool = PagedKVPool(5, 3, 0, 128, 0, jnp.bfloat16, page_len=8, num_pages=33, prefill_chunk=16, kind=kind)
    assert pool.k.shape == (2, 33, 2, 8, 24) and pool.v.shape == (2, 33, 2, 8, 16)
    assert kind.ring_pages(8) == 2 and pool.state["wk"].shape == (3, 1 + 3 * 2, 4, 8, 24) and pool.state["wv"].shape == (3, 7, 4, 8, 16)
    full_bytes, window_bytes = 2 * 33 * 8 * 2 * (24 + 16) * 2, 3 * 7 * 8 * 4 * (24 + 16) * 2
    assert pool.state_bytes() == window_bytes and pool.cache_bytes() == full_bytes + window_bytes
    g = pool.stats()["groups"]
    assert {k: g["full"][k] for k in ("kv_heads", "k_dim", "v_dim", "position_bytes", "bytes")} == \
        {"kv_heads": 2, "k_dim": 24, "v_dim": 16, "position_bytes": 2 * 2 * 40 * 2, "bytes": full_bytes}
    assert {k: g["window"][k] for k in ("kv_heads", "k_dim", "v_dim", "position_bytes", "bytes", "pages_per_slot")} == \
        {"kv_heads": 4, "k_dim": 24, "v_dim": 16, "position_bytes": 3 * 4 * 40 * 2, "bytes": window_bytes, "pages_per_slot": 2}
    assert pool.stats()["state_leaves"] == {"wk": window_bytes * 24 // 40, "wv": window_bytes * 16 // 40}
    math = pool.shape_math()
    assert "(K 24 + V 16)" in math and "4 heads" in math and "window 6" in math
    # the allocator never looks inside: a request takes pages by its length and its slot's ring, and gives both back
    r = _KReq(1, np.arange(1, 41, dtype=np.int32), max_new=8)
    r.slot = pool.alloc_request(r)
    assert pool.pages_live == 6 and pool.stats()["groups"]["window"]["slots_live"] == 1
    pool.retire(r.slot, r)
    _assert_no_leaks(pool)
    # a value width on the default kind too: the int8 pair follows it
    k8, v8 = PerHeadKV(2, 24, "int8", 16).buffers(1, 3, 8)
    assert k8["q"].shape == (1, 3, 2, 8, 24) and v8["q"].shape == (1, 3, 2, 8, 16) and v8["s"].shape == (1, 3, 2, 8, 1)
    assert PerHeadKV(2, 24, "int8", 16).position_bytes() == 2 * 48 and PerHeadKV(2, 8, jnp.float32).v_dim == 8


@pytest.mark.parametrize("max_len", [64, 256, 4096])
def test_a_window_layers_cache_a_slot_does_not_grow_with_the_request_or_with_max_len(max_len):
    pool = _windowed_pool(num_pages=1 + 3 * (max_len // 16), max_len=max_len)
    want = pool.stats()["groups"]["window"]
    assert (want["pages_per_slot"], want["positions_per_slot"]) == (4, 64)  # sliding_window and page_len alone
    for n in (3, 40, max_len - 8):
        r = _KReq(n, np.arange(1, n + 1, dtype=np.int32), max_new=8)
        r.slot = pool.alloc_request(r)
        g = pool.stats()["groups"]
        assert pool.pages_live == -(-(n + 8) // 16)  # the full group: pages by the request's length
        assert {k: v for k, v in g["window"].items() if k != "slots_live"} == {k: v for k, v in want.items() if k != "slots_live"}
        assert g["window"]["slots_live"] == 1 and pool.state["wk"].shape[1] == 1 + 3 * 4
        pool.retire(r.slot, r)  # both groups go back: the pages to the free list, the ring with the slot
        assert pool.pages_live == 0 and pool.free_slots == 3 and pool.stats()["groups"]["window"]["slots_live"] == 0
    _assert_no_leaks(pool)


def test_a_long_request_waits_for_full_pages_only_and_reuse_is_off_and_says_why(tmp_path):
    from deepspeed_tpu.serving.kvcache.pages import REUSE_OFF

    pool = _windowed_pool(num_pages=21)  # 20 usable pages: one slot's 16 and a few
    long = _KReq(1, np.arange(1, 241, dtype=np.int32), max_new=8, sid="agent", generated=[5, 6, 7, 8], finish_reason="length")
    long.slot = pool.alloc_request(long)
    assert pool.pages_live == 16 and pool.free_slots == 2
    waits = _KReq(2, np.arange(1, 101, dtype=np.int32), max_new=8)  # 7 pages of the 4 that are left: it waits, slots and rings to spare
    assert pool.alloc_request(waits) is None and pool.stats()["alloc_waits"] == 1 and pool.free_slots == 2
    short = _KReq(3, np.arange(1, 41, dtype=np.int32), max_new=8)  # 3 pages: it fits beside the long one
    short.slot = pool.alloc_request(short)
    assert short.slot is not None and pool.stats()["groups"]["window"]["slots_live"] == 2
    pool.learn_prefix(long)
    assert len(pool.index) == 0  # nothing is learned: a full layer's page does not hold what the window layers' rings lapped over
    pool.retire(long.slot, long)
    assert pool.sessions.peek("agent") is None  # nothing is parked
    waits.slot = pool.alloc_request(waits)
    assert waits.slot is not None and waits.prefill_pos == 0
    st = pool.stats()
    assert st["reuse"] == REUSE_OFF and "ring" in REUSE_OFF and (st["prefix_hits"], st["session_rebinds"], st["cow_copies"]) == (0, 0, 0)
    for r in (short, waits):
        pool.retire(r.slot, r)
    _assert_no_leaks(pool)
    with pytest.raises(SlotPoolError, match="prefix reuse is off"):
        _windowed_pool(spill_dir=str(tmp_path))
    with pytest.raises(SlotPoolError, match="prefix reuse is off"):
        _windowed_pool(pinned_prefixes=[[1, 2, 3]])
    with pytest.raises(SlotPoolError, match="prefix reuse is off"):
        pool.attach_tiers(object())


def test_a_chunk_that_is_not_whole_pages_is_refused_by_the_windowed_kind():
    from deepspeed_tpu.serving.kvcache.pages import WindowedKV

    kind = WindowedKV(1, 1, 2, 8, 40, jnp.float32)
    with pytest.raises(SlotPoolError, match="whole pages"):
        PagedKVPool(2, 2, 0, 96, 0, jnp.float32, page_len=16, num_pages=13, prefill_chunk=24, kind=kind)
