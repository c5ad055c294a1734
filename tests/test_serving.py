"""Continuous-batching serving engine tests (docs/serving.md).

Coverage per ISSUE 7: slot alloc/free/reuse, admit/evict mid-decode with
per-request output parity vs solo ``generate()`` runs, chunked-prefill
parity, pool-full/queue-full rejection, the int8-KV slot pool, the
compile-stability proof (churning live set -> exactly one decode
executable, ds_san clean), queue-wait deadlines, phase-attribution
stats, and the ``max_out_tokens`` bounding satellite."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.sanitizer import core as san_core
from deepspeed_tpu.analysis.sanitizer.core import Sanitizer
from deepspeed_tpu.config.config import DeepSpeedConfigError, SanitizerConfig, ServingConfig
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving import ServingEngine, ServingQueueFull, SlotKVPool, SlotPoolError

TINY = dataclasses.replace(gpt2.GPT2_TINY, remat=False)


def _engine(cfg=TINY, seed=7, **kw):
    """Position-sensitive engine (wpe scaled up) so slot/position
    bookkeeping bugs change generations instead of hiding."""
    params = gpt2.init_params(cfg, seed=seed)
    params["wpe"] = params["wpe"] * 40.0
    kw.setdefault("max_out_tokens", cfg.n_positions)
    return deepspeed_tpu.init_inference(model_config=cfg, params=params, dtype=jnp.float32, **kw)


def _prompts(n, lo, hi, seed=0, vocab=TINY.vocab_size):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, rng.integers(lo, hi + 1), dtype=np.int32) for _ in range(n)]


def _solo(eng, prompt, max_new):
    return np.asarray(eng.generate(prompt[None, :], max_new_tokens=max_new))[0]


# ---------------------------------------------------------------------------
# slot pool
# ---------------------------------------------------------------------------

def test_slot_pool_alloc_free_reuse():
    pool = SlotKVPool(2, 3, 4, 32, 16, jnp.float32)
    assert pool.free_slots == 3 and pool.live_slots == 0
    a, b, c = pool.alloc("ra"), pool.alloc("rb"), pool.alloc("rc")
    assert sorted((a, b, c)) == [0, 1, 2]
    assert pool.alloc("rd") is None  # pool full: graceful None
    assert pool.owner(a) == "ra"
    pool.free(b)
    assert pool.free_slots == 1
    # FIFO reuse: the freed slot comes back
    assert pool.alloc("re") == b
    pool.free(a)
    pool.free(b)
    pool.free(c)
    with pytest.raises(SlotPoolError):
        pool.free(b)  # double free


def test_slot_pool_int8_bytes_halved():
    f32 = SlotKVPool(2, 4, 4, 64, 16, jnp.float32)
    q = SlotKVPool(2, 4, 4, 64, 16, "int8")
    assert isinstance(q.k, dict) and q.k["q"].dtype == jnp.int8
    assert q.cache_bytes() < 0.4 * f32.cache_bytes()
    assert "int8" in q.shape_math()


# ---------------------------------------------------------------------------
# continuous batching: churn parity vs solo generate()
# ---------------------------------------------------------------------------

def test_churn_parity_vs_solo_generate():
    """Requests admitted and retired mid-decode (2 slots, 5 ragged
    requests incl. multi-chunk prompts) must each reproduce their own
    solo generate() run token for token."""
    eng = _engine()
    srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64, max_new_tokens=6)
    prompts = _prompts(5, 3, 20, seed=1)
    budgets = [6, 3, 5, 2, 4]
    rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts[:3], budgets[:3])]
    srv.step()
    srv.step()
    # late arrivals land while earlier requests are mid-decode
    rids += [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts[3:], budgets[3:])]
    res = srv.drain(max_steps=200)
    assert sorted(res) == sorted(rids)
    for rid, p, n in zip(rids, prompts, budgets):
        got = res[rid].tokens()
        np.testing.assert_array_equal(got, _solo(eng, p, n))
        assert res[rid].finish_reason == "length"
    # 5 requests over 2 slots: slots were reused
    assert srv.stats()["finished"] == 5
    assert srv.pool.free_slots == 2


def test_chunked_prefill_parity():
    """A prompt spanning several chunks (with an unaligned tail) must
    match solo generate(), and mid-prefill chunks must never stall or
    corrupt an in-flight decode."""
    eng = _engine(seed=9)
    srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64, max_new_tokens=4)
    rng = np.random.default_rng(3)
    short = rng.integers(1, TINY.vocab_size, 4, dtype=np.int32)
    long_ = rng.integers(1, TINY.vocab_size, 27, dtype=np.int32)  # 4 chunks, tail=3
    r_short = srv.submit(short, max_new_tokens=8)
    srv.step()  # short prefills + starts decoding
    r_long = srv.submit(long_, max_new_tokens=4)
    res = srv.drain(max_steps=200)
    np.testing.assert_array_equal(res[r_short].tokens(), _solo(eng, short, 8))
    np.testing.assert_array_equal(res[r_long].tokens(), _solo(eng, long_, 4))


POOLS = pytest.mark.parametrize("pool", [{}, {"kvcache": {"enabled": True, "page_len": 8}},
                                         {"prefill_chunks_per_step": 2, "kvcache": {"enabled": True, "page_len": 8}}],
                                 ids=["slots", "paged", "paged-two-chunks-a-step"])


@POOLS
def test_overlap_chunks_serves_the_serial_steps_tokens(pool):
    """The default order of a step (``serving.overlap_chunks``) hands the
    same two programs to the device in the same order on the same pool,
    ahead of the host's reads: every request's tokens are the serial
    step's, a chunk that is not its prompt's last is left unread for one
    step, and a request decodes from the step after its last chunk."""
    eng = _engine()
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, TINY.vocab_size, n, dtype=np.int32), m) for n, m in ((20, 6), (37, 9), (5, 4), (50, 7), (16, 5), (33, 8), (3, 3))]

    def serve(**kw):
        srv = ServingEngine(eng, config={"num_slots": 3, "max_len": 64, "prefill_chunk": 8, **pool, **kw})
        rids = [srv.submit(p, max_new_tokens=m) for p, m in reqs[:5]]
        srv.step()
        unread_after_first_step = len(srv._unread_chunks)
        srv.step()
        rids += [srv.submit(p, max_new_tokens=m) for p, m in reqs[5:]]  # late arrivals, while a chunk is in flight
        res = srv.drain(max_steps=500)
        return [list(res[r].generated) for r in rids], unread_after_first_step, srv

    serial, none_unread, srv0 = serve(overlap_chunks=False)
    overlapped, unread, srv = serve()
    assert srv.config.overlap_chunks is True and overlapped == serial and none_unread == 0
    assert unread == pool.get("prefill_chunks_per_step", 1)  # the 20-token prompt's first chunks: progress noted, token unread
    assert srv.scheduler.has_work() is False and srv.stats()["finished"] == 7 and not srv._unread_chunks
    assert (srv.prefill_compiles, srv.decode_compiles) == (1, 1)
    chunks = sum(-(-len(p) // 8) for p, _ in reqs)
    assert srv.timeline.summary()["programs"] == chunks + srv._decode_steps  # every chunk ran once, read late or not
    # a prompt's last chunk is waited for, every other left unread a step; the serial step waits for them all
    st, st0 = srv.stats(), srv0.stats()
    assert (st["chunks_awaited"], st["chunks_deferred"]) == (len(reqs), chunks - len(reqs))
    assert (st0["chunks_awaited"], st0["chunks_deferred"]) == (chunks, 0)
    assert ServingEngine(eng, num_slots=1, max_len=64, prefill_chunk=8).stats()["chunks_deferred"] == 0  # there from the start


@POOLS
def test_cancel_expiry_and_slot_reuse_with_a_chunk_unread(pool):
    """The request whose chunk the default step left unread is cancelled:
    its slot is free at once and the next occupant's chunks are
    dispatched behind the one in flight, a queued request past its
    deadline expires in that same step, and every other request's tokens
    are its solo run's.  (Queue-wait deadlines expire queued requests
    only, and this scheduler preempts no admitted request: a cancel is
    the one way a request with a chunk in flight leaves early.)"""
    eng = _engine()
    rng = np.random.default_rng(11)
    p_dec, p_long, p_next, p_late = (rng.integers(1, TINY.vocab_size, n, dtype=np.int32) for n in (6, 44, 21, 9))
    srv = ServingEngine(eng, config={"num_slots": 2, "max_len": 64, "prefill_chunk": 8, **pool})
    r_dec = srv.submit(p_dec, max_new_tokens=12)
    srv.step()  # r_dec's only chunk: it decodes from the next step
    r_long = srv.submit(p_long, max_new_tokens=4)
    srv.step()
    srv.step()
    assert srv._unread_chunks and all(job.req.request_id == r_long for job, _ in srv._unread_chunks)
    r_next = srv.submit(p_next, max_new_tokens=5)  # queued: both slots are taken
    r_late = srv.submit(p_late, max_new_tokens=3, deadline_seconds=1e-9)
    assert srv.cancel(r_long) is True and srv.cancel(r_long) is False
    assert srv.pool.free_slots == 1 and srv._unread_chunks  # the chunk is still the device's
    srv.step()  # reads it back, expires r_late, admits r_next into the freed slot
    assert not any(job.req.request_id == r_long for job, _ in srv._unread_chunks)
    assert srv.result(r_late).status == "expired" and srv.result(r_next).slot is not None
    res = srv.drain(max_steps=300)
    assert res[r_long].status == "cancelled" and res[r_long].generated == []
    np.testing.assert_array_equal(res[r_dec].tokens(), _solo(eng, p_dec, 12))
    np.testing.assert_array_equal(res[r_next].tokens(), _solo(eng, p_next, 5))
    st = srv.stats()
    assert not srv._unread_chunks and (st["cancelled"], st["expired"], st["finished"]) == (1, 1, 2)
    # r_dec's one chunk, r_long's of two steps before the cancel, r_next's three: the two prompts' last were waited for
    launched = 1 + 2 * pool.get("prefill_chunks_per_step", 1) + 3
    assert st["chunks_awaited"] == 2 and st["chunks_awaited"] + st["chunks_deferred"] == st["programs"] - srv._decode_steps == launched
    assert srv.pool.free_slots == 2


def test_a_step_that_read_nothing_back_leaves_the_service_rate_alone():
    """The admission controller's measured step (``_step_wall_ewma``)
    averages the steps that waited for a program: under the default
    order the step whose only program is a chunk left unread has timed
    the host alone, and the next one, which reads that chunk, counts."""
    eng = _engine()
    for overlap in (True, False):
        srv = ServingEngine(eng, num_slots=1, max_len=64, prefill_chunk=8, overlap_chunks=overlap)
        srv.submit(_prompts(1, 12, 12, seed=3)[0], max_new_tokens=3)
        srv.drain(max_steps=50)  # both programs compiled, a first reading taken
        seeded = srv._step_wall_ewma
        assert seeded is not None and seeded == srv.scheduler.step_seconds_fn()
        srv.submit(_prompts(1, 30, 30, seed=4)[0], max_new_tokens=2)
        srv.step()  # the default order: dispatched and noted, not waited for; the serial step waits for every chunk
        assert len(srv._unread_chunks) == overlap and (srv._step_wall_ewma == seeded) is overlap
        srv.step()
        assert srv._step_wall_ewma != seeded  # this one read the chunk before its own


def test_eos_retires_at_token_granularity():
    """Declaring a known generated token as EOS must retire the request
    the step that token appears, freeing its slot for the queue."""
    eng = _engine()
    prompt = _prompts(1, 6, 6, seed=5)[0]
    solo = _solo(eng, prompt, 6)
    eos = int(solo[prompt.shape[0] + 2])  # third generated token
    srv = ServingEngine(eng, num_slots=1, prefill_chunk=8, max_len=64)
    rid = srv.submit(prompt, max_new_tokens=6, eos_token_id=eos)
    res = srv.drain(max_steps=100)
    r = res[rid]
    got = r.tokens()
    # stops AT the eos token; prefix matches the solo run
    assert got[-1] == eos
    np.testing.assert_array_equal(got, solo[: got.shape[0]])
    assert r.finish_reason == "eos"


def test_first_token_eos_and_single_token_budget():
    eng = _engine()
    prompt = _prompts(1, 5, 5, seed=6)[0]
    solo = _solo(eng, prompt, 1)
    first = int(solo[-1])
    srv = ServingEngine(eng, num_slots=1, prefill_chunk=8, max_len=64)
    # budget of one: retires straight out of prefill
    r1 = srv.submit(prompt, max_new_tokens=1)
    # first token == eos: same
    r2 = srv.submit(prompt, max_new_tokens=4, eos_token_id=first)
    res = srv.drain(max_steps=50)
    np.testing.assert_array_equal(res[r1].tokens(), solo)
    np.testing.assert_array_equal(res[r2].tokens(), solo)
    assert res[r1].finish_reason == "length"
    assert res[r2].finish_reason == "eos"


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_queue_full_rejection_and_capacity_validation():
    eng = _engine()
    srv = ServingEngine(eng, num_slots=1, prefill_chunk=8, max_len=32, max_queue=1)
    p = _prompts(3, 4, 4, seed=2)
    srv.submit(p[0], max_new_tokens=4)
    srv.step()  # p0 takes the slot
    srv.submit(p[1], max_new_tokens=4)  # waits (1 queued == max_queue)
    with pytest.raises(ServingQueueFull, match="max_queue=1"):
        srv.submit(p[2], max_new_tokens=4)
    assert srv.stats()["rejected"] == 1
    # requests that can never fit the pool are rejected with the numbers
    with pytest.raises(ValueError, match=r"31\+4 = 35 exceeds the serving capacity 32"):
        srv.submit(np.ones(31, np.int32), max_new_tokens=4)
    srv.drain(max_steps=100)


def test_queue_deadline_expires_waiters():
    eng = _engine()
    srv = ServingEngine(eng, num_slots=1, prefill_chunk=8, max_len=32)
    p = _prompts(2, 4, 4, seed=3)
    r1 = srv.submit(p[0], max_new_tokens=6)
    srv.step()  # r1 occupies the only slot
    # deadline 0s from submit: expired at the next tick, never admitted
    r2 = srv.submit(p[1], max_new_tokens=4, deadline_seconds=1e-9)
    res = srv.drain(max_steps=100)
    assert res[r2].status == "expired"
    assert res[r2].finish_reason == "expired"
    assert res[r2].generated == []
    assert res[r1].finish_reason == "length"
    assert srv.stats()["expired"] == 1


def test_serving_config_validation():
    with pytest.raises(DeepSpeedConfigError, match="multiple of"):
        ServingConfig.from_dict({"max_len": 100, "prefill_chunk": 64})
    with pytest.raises(DeepSpeedConfigError, match="num_slots"):
        ServingConfig.from_dict({"num_slots": 0})
    with pytest.raises(DeepSpeedConfigError, match="kv_cache_dtype"):
        ServingConfig.from_dict({"kv_cache_dtype": "fp8"})
    with pytest.raises(DeepSpeedConfigError, match="Unknown config key"):
        ServingConfig.from_dict({"num_slot": 4})
    # serving block parses inside the full config surface
    from deepspeed_tpu.config.config import DeepSpeedConfig

    c = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "serving": {"num_slots": 4, "prefill_chunk": 16, "max_len": 64}})
    assert c.serving.num_slots == 4 and c.serving.max_len == 64
    # pool max_len above the engine capacity is refused with the numbers
    eng = _engine()
    with pytest.raises(ValueError, match="generation capacity"):
        ServingEngine(eng, num_slots=1, prefill_chunk=8, max_len=TINY.n_positions + 8)


# ---------------------------------------------------------------------------
# int8 KV slot pool
# ---------------------------------------------------------------------------

def test_int8_kv_slot_pool():
    """kv_cache_dtype='int8' serves through the quantized pool: tokens
    agree with the f32-pool serve in bulk (cache rounding can flip
    near-ties), shapes/retirement identical, pool bytes halved."""
    eng = _engine(seed=11)
    kw = dict(num_slots=2, prefill_chunk=8, max_len=64)
    prompts = _prompts(3, 5, 14, seed=4)
    srv_f = ServingEngine(eng, **kw)
    srv_q = ServingEngine(eng, kv_cache_dtype="int8", **kw)
    assert isinstance(srv_q.pool.k, dict)
    assert srv_q.pool.cache_bytes() < 0.4 * srv_f.pool.cache_bytes()
    outs = {}
    for tag, srv in (("f", srv_f), ("q", srv_q)):
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        res = srv.drain(max_steps=200)
        outs[tag] = [res[r].tokens() for r in rids]
        assert srv.stats()["decode_compiles"] == 1
    agree = np.mean([
        (a == b).mean() for a, b in zip(outs["f"], outs["q"])
    ])
    assert agree > 0.85, (agree, outs)


# ---------------------------------------------------------------------------
# compile stability under an armed ds_san run
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


def test_compile_stability_churn_ds_san_clean(san):
    """The acceptance proof: a churning live set — admits/retires at
    token granularity including chunked prefill of a >= 384-token prompt
    — runs against exactly ONE compiled decode executable (and one
    prefill executable), with zero sanitizer findings."""
    cfg = dataclasses.replace(TINY, n_positions=512)
    eng = _engine(cfg=cfg)
    srv = ServingEngine(eng, num_slots=2, prefill_chunk=128, max_len=512,
                        max_new_tokens=4)
    assert srv._sanitizer is san
    rng = np.random.default_rng(8)
    long_prompt = rng.integers(1, cfg.vocab_size, 384, dtype=np.int32)  # 3 chunks
    shorts = _prompts(4, 3, 40, seed=9, vocab=cfg.vocab_size)
    rids = [srv.submit(long_prompt, max_new_tokens=4)]
    rids.append(srv.submit(shorts[0], max_new_tokens=3))
    srv.step()
    srv.step()
    rids += [srv.submit(p, max_new_tokens=3) for p in shorts[1:]]
    res = srv.drain(max_steps=300)
    assert sorted(res) == sorted(rids)
    # exactly one executable per serving site across the whole churn
    assert srv.decode_compiles == 1
    assert srv.prefill_compiles == 1
    counts = san.recompile.compile_counts()
    assert counts.get("serving.decode") == 1, counts
    assert counts.get("serving.prefill") == 1, counts
    # ds_san clean: no recompiles, no implicit transfers
    assert san.findings == [], [f.format() for f in san.findings]
    # and the long prompt still decodes correctly under the armed run
    np.testing.assert_array_equal(res[rids[0]].tokens(), _solo(eng, long_prompt, 4))


# ---------------------------------------------------------------------------
# phase attribution / stats
# ---------------------------------------------------------------------------

def test_serving_stats_and_phase_attribution():
    eng = _engine()
    srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64)
    for p in _prompts(3, 4, 12, seed=12):
        srv.submit(p, max_new_tokens=4)
    srv.drain(max_steps=100)
    s = srv.stats()
    for key in ("prefill_ms", "decode_ms", "sched_ms", "queue_depth", "live_slots",
                "steps_per_s", "submitted", "finished", "rejected", "expired",
                "pool_bytes", "kv_dtype", "decode_compiles"):
        assert key in s, key
    assert s["submitted"] == s["finished"] == 3
    assert s["decode_ms"] > 0.0  # fenced: decode really is attributed
    assert s["prefill_ms"] > 0.0
    assert s["live_slots"] > 0.0
    assert s["kv_dtype"] == "float32"


# ---------------------------------------------------------------------------
# satellite: max_out_tokens actually bounds/validates
# ---------------------------------------------------------------------------

def test_max_out_tokens_validated_at_init():
    with pytest.raises(ValueError, match="max_out_tokens must be >= 1"):
        deepspeed_tpu.init_inference(model_config=TINY, dtype=jnp.float32, max_out_tokens=0)


def test_generate_overflow_raises_with_derived_numbers():
    eng = deepspeed_tpu.init_inference(model_config=TINY, dtype=jnp.float32, max_out_tokens=16)
    toks = np.ones((1, 10), np.int32)
    with pytest.raises(ValueError, match=r"10\+8 = 18 exceeds the generation capacity"):
        eng.generate(toks, max_new_tokens=8)
    # n_positions is the binding constraint when max_out_tokens is larger
    eng2 = deepspeed_tpu.init_inference(model_config=TINY, dtype=jnp.float32,
                                        max_out_tokens=4096)
    assert eng2.generation_capacity == TINY.n_positions
    with pytest.raises(ValueError, match=rf"n_positions={TINY.n_positions}"):
        eng2.generate(np.ones((1, TINY.n_positions), np.int32), max_new_tokens=1)


def test_forward_beyond_n_positions_raises():
    eng = deepspeed_tpu.init_inference(model_config=TINY, dtype=jnp.float32)
    bad = np.ones((1, TINY.n_positions + 4), np.int32)
    with pytest.raises(ValueError, match="exceeds the model's n_positions"):
        eng.forward(bad)


# ---------------------------------------------------------------------------
# external-cache prefill/decode entry points
# ---------------------------------------------------------------------------

def test_external_cache_entry_points_match_generate():
    """The engine's externally-owned-cache surface (init_cache/prefill/
    decode_step) must reproduce generate() greedy token for token."""
    eng = _engine()
    prompt = _prompts(1, 6, 6, seed=13)[0]
    N = 5
    T = prompt.shape[0]
    solo = _solo(eng, prompt, N)
    k, v = eng.init_cache(batch=1, max_len=T + N)
    logits, k, v = eng.prefill(prompt[None, :], k, v)
    tok = int(np.asarray(jnp.argmax(logits[0, -1])))
    got = [tok]
    for s in range(N - 1):
        logits, k, v = eng.decode_step(np.asarray([[tok]], np.int32), k, v, T + s)
        tok = int(np.asarray(jnp.argmax(logits[0, -1])))
        got.append(tok)
    np.testing.assert_array_equal(np.asarray(got), solo[T:])
    # capacity validation carries the derived numbers
    with pytest.raises(ValueError, match="generation capacity"):
        eng.init_cache(batch=1, max_len=TINY.n_positions + 1)
    with pytest.raises(ValueError, match="exceeds the cache capacity"):
        eng.prefill(np.ones((1, T + N + 1), np.int32), k, v)
    # decoding past the cache end must raise, not silently clamp the
    # write to the last position forever
    with pytest.raises(ValueError, match=rf"pos={T + N} \+ T=1 exceeds"):
        eng.decode_step(np.asarray([[tok]], np.int32), k, v, T + N)


# ---------------------------------------------------------------------------
# per-slot sampling (temperature / top-k / seed) in the pooled decode step
# ---------------------------------------------------------------------------

def test_sampling_reproducible_across_slot_churn():
    """A sampled request's tokens depend only on (seed, position) — the
    same request must reproduce its output exactly when the pool is
    busy with different neighbors and the slot assignment differs."""
    eng = _engine()

    def run(extra_first):
        srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64)
        prompts = _prompts(3, 4, 10, seed=5)
        rids = {}
        if extra_first:
            # occupy slot 0 with a greedy request so the sampled one
            # lands in a different slot than in the other run
            rids["g"] = srv.submit(prompts[1], max_new_tokens=6)
            srv.step()
        rids["s"] = srv.submit(
            prompts[0], max_new_tokens=8, do_sample=True, temperature=0.9,
            top_k=16, seed=123,
        )
        res = srv.drain(max_steps=300)
        return res[rids["s"]].tokens()

    a = run(False)
    b = run(True)
    np.testing.assert_array_equal(a, b)


def test_mixed_pool_greedy_still_bit_matches_solo():
    """Greedy requests must bit-match solo generate() even while a
    sampling request shares the pool (flags select per slot)."""
    eng = _engine(seed=11)
    srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64)
    prompts = _prompts(2, 4, 12, seed=6)
    r_greedy = srv.submit(prompts[0], max_new_tokens=6)
    r_samp = srv.submit(
        prompts[1], max_new_tokens=6, do_sample=True, temperature=1.3, top_k=8, seed=77
    )
    res = srv.drain(max_steps=300)
    np.testing.assert_array_equal(res[r_greedy].tokens(), _solo(eng, prompts[0], 6))
    assert len(res[r_samp].generated) == 6
    # the one-decode-executable contract survives the sampling inputs
    assert srv.decode_compiles == 1 and srv.prefill_compiles == 1


def test_top_k_one_equals_greedy():
    """top_k=1 leaves only the argmax above the threshold — sampling
    with any temperature must then produce the greedy tokens."""
    eng = _engine(seed=3)
    srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64)
    p = _prompts(1, 5, 9, seed=8)[0]
    rid = srv.submit(p, max_new_tokens=6, do_sample=True, temperature=2.5, top_k=1, seed=9)
    res = srv.drain(max_steps=200)
    np.testing.assert_array_equal(res[rid].tokens(), _solo(eng, p, 6))


def test_sampling_validation():
    eng = _engine()
    srv = ServingEngine(eng, num_slots=1, prefill_chunk=8, max_len=32, max_top_k=16)
    p = _prompts(1, 4, 6, seed=2)[0]
    with pytest.raises(ValueError, match="max_top_k"):
        srv.submit(p, max_new_tokens=2, do_sample=True, top_k=17)
    with pytest.raises(ValueError, match="temperature"):
        srv.submit(p, max_new_tokens=2, do_sample=True, temperature=0.0)
    with pytest.raises(DeepSpeedConfigError, match="max_top_k"):
        ServingEngine(eng, num_slots=1, prefill_chunk=8, max_len=32, max_top_k=0)


# ---------------------------------------------------------------------------
# a family on two page groups (window + full attention): Laguna behind the one seam
# ---------------------------------------------------------------------------

def _laguna_served(**kw):
    import deepspeed_tpu
    from deepspeed_tpu.models import laguna

    inf = deepspeed_tpu.init_inference(model_config=laguna.LAGUNA_TINY, dtype=jnp.float32, max_out_tokens=96)
    srv = ServingEngine(inf, config={"num_slots": 3, "max_len": 96, "prefill_chunk": 16, "kvcache": {"enabled": True, "page_len": 4}, **kw})
    rng = np.random.default_rng(0)
    rids = [srv.submit(rng.integers(1, 256, n, dtype=np.int32), max_new_tokens=m) for n, m in ((37, 9), (5, 12), (50, 6), (20, 20), (33, 5))]
    done = srv.drain(max_steps=500)
    return [list(done[r].generated) for r in rids], srv


def test_the_overlapped_step_and_the_serial_step_give_the_same_tokens_on_two_page_groups():
    serial, _ = _laguna_served(overlap_chunks=False)
    overlapped, srv = _laguna_served()
    assert overlapped == serial and (srv.prefill_compiles, srv.decode_compiles) == (1, 1)
    st = srv.stats()
    # the forms the two programs took, and the ring: window 8 on pages of 4 is 3 pages = 12 positions a slot, under a chunk of 16
    assert st["swa_ring_positions"] == 12 and st["swa_decode_form"].startswith("jnp over the ring") and st["swa_chunk_form"].startswith("banded jnp")
    g = st["kvcache"]["groups"]
    assert (g["full"]["layers"], g["window"]["layers"], g["window"]["pages_per_slot"], g["window"]["slots_live"]) == (2, 3, 3, 0)
    assert g["full"]["bytes"] + g["window"]["bytes"] == srv.pool.cache_bytes() == st["pool_bytes"]
    assert st["kvcache"]["reuse"].startswith("off:") and st["hybrid"]["state_resets_in_program"] == 5
    assert st["moe"]["dropped_assignments"] == 0 and st["chunks_deferred"] > 0
    # what the newest decode step's routers chose stays on the device for a check of the served program
    kept = srv.decode_kept
    assert kept["experts"].shape == kept["router_logits"].shape == (4, 3, 4) and kept["router_logits"].dtype == jnp.float32


def test_the_windowed_kind_lives_in_the_paged_pool_only_and_has_no_int8_form():
    import deepspeed_tpu
    from deepspeed_tpu.models import laguna

    inf = deepspeed_tpu.init_inference(model_config=laguna.LAGUNA_TINY, dtype=jnp.float32, max_out_tokens=96)
    with pytest.raises(ValueError, match="paged pool only"):
        ServingEngine(inf, config={"num_slots": 2, "max_len": 96, "prefill_chunk": 16})
    with pytest.raises(ValueError, match="no int8 form"):
        ServingEngine(inf, config={"num_slots": 2, "max_len": 96, "prefill_chunk": 16, "kv_cache_dtype": "int8",
                                   "kvcache": {"enabled": True, "page_len": 4}})
    with pytest.raises(Exception, match="whole pages"):
        ServingEngine(inf, config={"num_slots": 2, "max_len": 96, "prefill_chunk": 6, "kvcache": {"enabled": True, "page_len": 4}})


def test_the_engine_the_scheduler_and_the_staging_do_not_know_the_new_family_or_its_cache_kind():
    import os

    import deepspeed_tpu.serving as serving

    root = os.path.dirname(serving.__file__)
    for name in ("engine.py", "scheduler.py", "staging.py"):
        text = open(os.path.join(root, name)).read().lower()
        assert "laguna" not in text and "windowedkv" not in text and "ring_table" not in text, name
