"""DeepSeek-V2 on the serving path (CPU, tiny size): the latent cache
kind of ``PagedKVPool`` (copy-on-write, prefix hit, session spill and
restore on the one buffer), the family seam of the engines, the
partition rules.  The comparisons with the plain reference live with the
benchmark (tests/bench/test_deepseek_v2.py)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models import deepseek_v2 as ds
from deepspeed_tpu.models import family_of, gpt2
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.kvcache import GARBAGE_PAGE, LatentKV, PagedKVPool, PerHeadKV
from tests.test_kvcache import _KReq, _assert_no_leaks

TINY = ds.DEEPSEEK_V2_TINY


def _latent_pool(**kw):
    kw.setdefault("page_len", 8)
    kw.setdefault("prefill_chunk", 4)
    return PagedKVPool(2, 2, 0, 32, 0, None, kind=LatentKV(12, kw.pop("dtype", jnp.float32)), **kw)


@pytest.fixture(scope="module")
def inf():
    return deepspeed_tpu.init_inference(model_config=TINY, dtype=jnp.float32, max_out_tokens=128, seed=3)


def _srv(inf, **kw):
    kv = {"enabled": True, "page_len": 16, **kw.pop("kvcache", {})}
    return ServingEngine(inf, config={"num_slots": 3, "max_len": 128, "prefill_chunk": 16, "max_new_tokens": 16,
                                      "kvcache": kv, **kw})


def _alone(inf, prompt, n, **kw):
    """The tokens a fresh engine gives the prompt, nothing shared."""
    srv = _srv(inf, **kw)
    rid = srv.submit(prompt, max_new_tokens=n)
    return list(srv.drain(max_steps=400)[rid].generated)


def test_latent_kind_is_one_buffer_with_the_page_axis_where_the_allocator_expects_it():
    pool = _latent_pool()
    assert pool.v is None and pool.k.shape == (2, pool.num_pages, 12, 8)
    assert pool.cache_bytes() == 2 * pool.num_pages * 12 * 8 * 4
    assert "12 latent" in pool.shape_math() and "1 x (" in pool.shape_math()
    assert isinstance(PagedKVPool(2, 2, 2, 32, 4, jnp.float32, page_len=8).kind, PerHeadKV)  # the default kind
    s = pool.alloc("ra")
    assert GARBAGE_PAGE not in pool._slot_pages[s] and pool.pages_live == pool.pages_per_slot
    pool.free(s)
    _assert_no_leaks(pool)


def test_latent_pool_prefix_hit_and_copy_on_write_bookkeeping():
    pool = _latent_pool()
    r0 = _KReq("r0", [1, 2, 3, 4, 5, 6], max_new=2)
    r0.slot = pool.alloc_request(r0)
    pool.learn_prefix(r0)
    entry_pages = pool.index.lookup(np.array([1, 2, 3, 4, 5, 6, 7])).pages
    pool.retire(r0.slot, r0)
    r1 = _KReq("r1", [1, 2, 3, 4, 5, 6, 9, 9], max_new=2)
    r1.slot = pool.alloc_request(r1)
    assert (r1.prefill_pos, r1.prefix_hint) == (4, 4)
    src, dst = pool.consume_cow(r1.slot)
    assert src == entry_pages[0] and dst == pool._slot_pages[r1.slot][0] and pool.cow_copies == 1
    # the engine's copy-on-write, as the prefill program applies it to every buffer the kind has
    rng = np.random.default_rng(0)
    pool.swap(jnp.asarray(rng.standard_normal(pool.k.shape), jnp.float32), None)
    cow = lambda b: b.at[:, dst].set(b[:, src])  # noqa: E731
    k, v = jax.tree.map(cow, pool.k), jax.tree.map(cow, pool.v)
    assert v is None
    np.testing.assert_array_equal(np.asarray(k[:, dst]), np.asarray(pool.k[:, src]))
    pool.retire(r1.slot, r1)
    _assert_no_leaks(pool)


def test_latent_pool_session_spill_and_restore_round_trip(tmp_path):
    spill = str(tmp_path / "spill")
    pool = _latent_pool(spill_dir=spill, dtype=jnp.bfloat16)
    fill = np.random.default_rng(3).standard_normal(pool.k.shape).astype(jnp.bfloat16)
    pool.swap(jnp.asarray(fill), None)
    r0 = _KReq("r0", [1, 2, 3, 4, 5], max_new=3, sid="chat", generated=[6, 7], finish_reason="eos")
    r0.slot = pool.alloc_request(r0)
    kept = list(pool._slot_pages[r0.slot][:1])
    want = np.asarray(fill[:, kept])
    pool.retire(r0.slot, r0)
    assert pool.spill_sessions(now=0.0) == 1 and pool.sessions.is_spilled("chat")
    pool2 = _latent_pool(spill_dir=spill, dtype=jnp.bfloat16)
    assert pool2.recover() == ["chat"]
    r1 = _KReq("r1", [1, 2, 3, 4, 5, 6, 30, 31], max_new=2, sid="chat")
    r1.slot = pool2.alloc_request(r1)
    assert r1.prefix_hint == 4 and pool2.stats()["session_restores"] == 1 and pool2.v is None
    np.testing.assert_array_equal(np.asarray(jnp.take(pool2.k, jnp.asarray(pool2._slot_pages[r1.slot][:1]), axis=1)), want)
    pool2.retire(r1.slot, r1)
    _assert_no_leaks(pool2)


def test_cache_write_by_slices_and_by_the_scatter_fallback_land_the_same_rows():
    from deepspeed_tpu.ops.transformer.latent_attention import latent_cache_write

    rng = np.random.default_rng(4)
    table = jnp.asarray([[3, 1, 4, 2], [5, 6, 0, 0]], jnp.int32)
    pos = jnp.asarray([8, 0], jnp.int32)
    for T in (1, 8, 16, 12):  # one position; inside a page; whole pages; neither (the scatter fallback)
        rows = jnp.asarray(rng.standard_normal((2, T, 12)), jnp.float32)
        pool = latent_cache_write(jnp.zeros((2, 7, 12, 8), jnp.float32), 1, rows, table, pos)
        want = np.zeros((2, 7, 12, 8), np.float32)
        for b in range(2):
            for t in range(T):
                at = int(pos[b]) + t
                want[1, int(table[b, at // 8]), :, at % 8] = np.asarray(rows[b, t])
        np.testing.assert_array_equal(np.asarray(pool), want)
    masked = latent_cache_write(jnp.zeros((2, 7, 12, 8), jnp.float32), 0, rows[:, :1], table, pos,
                                write_mask=jnp.asarray([True, False]))
    assert float(jnp.abs(masked[0, 5]).sum()) == 0.0 and float(jnp.abs(masked[0, 1]).sum()) > 0.0  # row 1 went to the garbage page


def test_family_seam_picks_the_module_from_the_config():
    assert family_of(TINY) is ds and family_of(gpt2.GPT2_TINY) is gpt2 and family_of(object()) is None
    assert ds.CAUSAL_LM and gpt2.CAUSAL_LM


def test_served_through_the_shared_engine_two_programs_and_expert_counters(inf):
    assert inf._family is ds and inf._causal and not inf._is_gpt
    srv = _srv(inf)
    assert isinstance(srv.pool.kind, LatentKV) and srv.pool.v is None
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, TINY.vocab_size, n, dtype=np.int32) for n in (20, 37, 9, 50)]
    ids = [srv.submit(p, max_new_tokens=5) for p in prompts]
    res = srv.drain(max_steps=400)
    assert srv.prefill_compiles == 1 and srv.decode_compiles == 1
    for rid, p in zip(ids, prompts):
        assert list(res[rid].generated) == _alone(inf, p, 5)  # batching, paging and slot churn change nothing
    moe = srv.stats()["moe"]
    assert moe["dropped_assignments"] == 0 and moe["assignments_computed"] == moe["assignments_routed_held"] > 0
    assert len(moe["tokens_per_expert"]) == TINY.n_moe_layers and len(moe["tokens_per_expert"][0]) == TINY.n_routed_experts
    # every real token of every step makes top-k assignments in each expert layer, all experts held here
    tokens = sum(len(p) for p in prompts) + sum(len(res[r].generated) - 1 for r in ids)
    assert moe["assignments_computed"] == tokens * TINY.num_experts_per_tok * TINY.n_moe_layers
    assert moe["load_max_over_mean"] >= 1.0 and moe["decode_steps"] >= 4
    srv.reset_moe_counters()
    assert "moe" not in srv.stats()


def test_shared_prefix_hits_the_latent_pool_and_changes_no_token(inf):
    srv = _srv(inf, prefill_chunk=8)  # half a page: a hit may end inside a shared page -> copy-on-write
    rng = np.random.default_rng(1)
    head = rng.integers(1, TINY.vocab_size, 40, dtype=np.int32)
    prompts = [np.concatenate([head, rng.integers(1, TINY.vocab_size, n, dtype=np.int32)]) for n in (5, 9, 7)]
    for p in prompts:  # the second prompt teaches the index the common run, the third hits it
        rid = srv.submit(p, max_new_tokens=4)
        res = srv.drain(max_steps=400)
    kv = srv.stats()["kvcache"]
    assert kv["prefix_hits"] >= 1 and kv["tokens_saved"] >= 40 and kv["cow_copies"] >= 1
    assert list(res[rid].generated) == _alone(inf, prompts[-1], 4, prefill_chunk=8)


def test_session_spill_and_restore_on_the_latent_pool_changes_no_token(inf, tmp_path):
    srv = _srv(inf, kvcache={"spill_dir": str(tmp_path / "spill")})
    rng = np.random.default_rng(2)
    p1 = rng.integers(1, TINY.vocab_size, 24, dtype=np.int32)
    r1 = srv.submit(p1, max_new_tokens=4, session_id="s")
    t1 = np.asarray(srv.drain(max_steps=400)[r1].tokens())
    assert srv.pool.spill_sessions(time.monotonic()) == 1 and srv.pool.sessions.is_spilled("s")
    p2 = np.concatenate([t1, rng.integers(1, TINY.vocab_size, 6, dtype=np.int32)])
    r2 = srv.submit(p2, max_new_tokens=4, session_id="s")
    res = srv.drain(max_steps=400)
    kv = srv.stats()["kvcache"]
    assert kv["session_spills"] == 1 and kv["session_restores"] == 1 and kv["session_rebinds"] == 1
    assert list(res[r2].generated) == _alone(inf, p2, 4)


def test_what_the_family_does_not_do_is_refused_with_a_reason(inf):
    with pytest.raises(ValueError, match="paged pool only"):
        ServingEngine(inf, config={"num_slots": 2, "max_len": 64, "prefill_chunk": 16})
    with pytest.raises(ValueError, match="no int8 form"):
        _srv(inf, kv_cache_dtype="int8")
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        inf.forward(np.zeros((1, 8), np.int32))
    with pytest.raises(ValueError, match="GPT-family"):
        inf.generate(np.zeros((1, 8), np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="experts_held"):
        ds.DeepseekV2Config(experts_held=(150, 20))
    with pytest.raises(ValueError, match="only yarn"):
        ds.DeepseekV2Config.from_hf({"rope_scaling": {"type": "linear", "factor": 2}})


def test_partition_rules_name_the_expert_axis_for_the_held_dimension():
    from deepspeed_tpu.analysis.shard.speccheck import audit_builtin_tables
    from deepspeed_tpu.sharding.rules import rules_for_config

    rules = rules_for_config(TINY)
    shapes = ds.param_shapes(TINY)
    moe_layer = shapes["layers"][-1]
    assert rules.spec("layers/2/experts_gu", moe_layer["experts_gu"]) == P("expert", None, None)
    assert rules.spec("layers/2/experts_down", moe_layer["experts_down"]) == P("expert", None, None)
    assert rules.spec("embed", shapes["embed"]) == rules.spec("head", shapes["head"]) == P("model", None)
    for name in ("q_a", "kv_b", "o", "router", "shared_gu"):  # replicated: data-parallel attention
        assert rules.spec(f"layers/2/{name}", moe_layer[name]) in (None, P())
    assert not [f for f in audit_builtin_tables() if "deepseek_v2" in str(f)]
