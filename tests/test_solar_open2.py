"""Solar-Open2 at the tiny size on the CPU, seeded weights: the program
(its forward on the hybrid cache — K/V pages + per-slot recurrent state —
its routing, its share) against ``benchmark/reference_solar_open2.py``,
and the family through ``init_inference`` → ``ServingEngine``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_solar_open2 as W
from benchmark.reference_solar_open2 import Reference
from deepspeed_tpu.models import solar_open2 as so
from deepspeed_tpu.moe.layer import dropless_held_experts, sigmoid_topk

HF = {"model_type": "solar_open2", "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256, "intermediate_size": 160, "moe_intermediate_size": 32,
      "rms_norm_eps": 1e-5, "gqa_layers": [0, 4], "use_gqa_gate": True, "use_rope": False, "first_k_dense_replace": 0,
      "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "tie_word_embeddings": False, "n_routed_experts": 16,
      "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 4,
      "max_position_embeddings": 4096,
      "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None}}
SEED = 2 ** 31 + 9
SLOTS, PAGES_PER_SLOT, PAGE_LEN, CHUNK = 4, 8, 8, 16


def _program(dims):
    cfg = so.SolarOpen2Config.from_hf(dims, experts_held=dims.get("experts_held"), vocab_held=dims.get("vocab_held"))
    return cfg, W.program_params(SEED, dims, jnp.float32)


class _Cache:
    """The hybrid cache of SLOTS slots, each slot's pages its own."""

    def __init__(self, cfg):
        kind = so.cache_kind(cfg, jnp.float32)
        self.k, self.v = kind.buffers(cfg.n_layer, 1 + SLOTS * PAGES_PER_SLOT, PAGE_LEN)
        self.state = kind.state_buffers(SLOTS)
        self.tables = jnp.asarray(1 + np.arange(SLOTS * PAGES_PER_SLOT, dtype=np.int32).reshape(SLOTS, PAGES_PER_SLOT))


def _prefill(cfg, params, cache, slot, toks):
    """Chunk by chunk, the last chunk padded; returns the logits at the prompt's last token."""
    for start in range(0, len(toks), CHUNK):
        n = min(CHUNK, len(toks) - start)
        t = np.full((1, CHUNK), 7, np.int32)  # a padded tail of real-looking ids: it must not count
        t[0, :n] = toks[start:start + n]
        logits, cache.k, cache.v, cache.state, aux = so.forward_with_cache(
            params, jnp.asarray(t), cache.k, cache.v, cache.state, jnp.asarray([start], jnp.int32), cfg,
            cache.tables[slot][None], slot=jnp.asarray([slot], jnp.int32),
            row_valid=jnp.asarray((np.arange(CHUNK) < n)[None]), take=jnp.asarray([n - 1], jnp.int32))
    return np.asarray(logits)[0], aux


def _decode(cfg, params, cache, feed):
    """One decode step: ``feed`` maps slot -> (token, position); the other rows do not decode."""
    t, pos, mask = np.full((SLOTS, 1), 3, np.int32), np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), bool)
    for s, (tok, p) in feed.items():
        t[s, 0], pos[s], mask[s] = tok, p, True
    logits, cache.k, cache.v, cache.state, aux = so.forward_with_cache(
        params, jnp.asarray(t), cache.k, cache.v, cache.state, jnp.asarray(pos), cfg, cache.tables,
        write_mask=jnp.asarray(mask), row_valid=jnp.asarray(mask[:, None]))
    return np.asarray(logits), aux


@pytest.mark.parametrize("share,n_prompt", [(None, 37), ((4, 8), 37), (None, 32), (None, 5)])
def test_chunked_prefill_then_decode_on_the_hybrid_cache_is_the_references_full_forward(share, n_prompt):
    dims = dict(HF) if share is None else {**HF, "experts_held": list(share), "vocab_held": 128}
    cfg, params = _program(dims)
    toks = np.random.default_rng(0).integers(1, 128, n_prompt + 8, dtype=np.int32)
    want = np.asarray(Reference(dims, SEED).logits(toks[None])[0])
    cache = _Cache(cfg)
    with jax.default_matmul_precision("highest"):
        got, aux = _prefill(cfg, params, cache, 2, toks[:n_prompt])  # across chunk boundaries, a padded tail
        np.testing.assert_allclose(got, want[n_prompt - 1], atol=2e-4)
        assert aux.shape == (8, cfg.held[1] + 1) and int(aux[:, :-1].sum()) == int(aux[:, -1].sum())
        for i in range(n_prompt, n_prompt + 8):  # decode, the other three rows not decoding
            logits, _ = _decode(cfg, params, cache, {2: (toks[i], i)})
            np.testing.assert_allclose(logits[2], want[i], atol=2e-4)


def test_two_requests_interleaved_and_a_slot_reused_by_a_second_request():
    """Slot 1 serves request A, then — without any reset from outside —
    request B, while slot 3 decodes request C throughout: B's logits are
    those of B alone (its state started from zero, nothing of A leaks),
    and C never notices."""
    cfg, params = _program(dict(HF))
    rng = np.random.default_rng(1)
    a, b, c = (rng.integers(1, 256, n, dtype=np.int32) for n in (29, 22, 30))
    ref = Reference(dict(HF), SEED)
    want_b, want_c = np.asarray(ref.logits(b[None])[0]), np.asarray(ref.logits(c[None])[0])
    cache = _Cache(cfg)
    with jax.default_matmul_precision("highest"):
        _prefill(cfg, params, cache, 1, a[:25])
        _prefill(cfg, params, cache, 3, c[:18])
        for i in range(4):  # A and C decode side by side
            logits, _ = _decode(cfg, params, cache, {1: (a[25 + i], 25 + i), 3: (c[18 + i], 18 + i)})
            np.testing.assert_allclose(logits[3], want_c[18 + i], atol=2e-4)
        assert float(jnp.abs(cache.state["s"][:, 1]).max()) > 0  # A's state stands in the slot
        got, _ = _prefill(cfg, params, cache, 1, b[:17])  # B takes the slot: position 0 starts from zero
        np.testing.assert_allclose(got, want_b[16], atol=2e-4)
        for i in range(5):
            logits, _ = _decode(cfg, params, cache, {1: (b[17 + i], 17 + i), 3: (c[22 + i], 22 + i)})
            np.testing.assert_allclose(logits[1], want_b[17 + i], atol=2e-4)
            np.testing.assert_allclose(logits[3], want_c[22 + i], atol=2e-4)
        # a step in which slot 1 does not decode leaves its state and its pages' content alone
        s_before, conv_before = np.asarray(cache.state["s"][:, 1]), np.asarray(cache.state["conv"][:, 1])
        _decode(cfg, params, cache, {3: (c[27], 27)})
        assert np.array_equal(s_before, np.asarray(cache.state["s"][:, 1]))
        assert np.array_equal(conv_before, np.asarray(cache.state["conv"][:, 1]))


def test_sigmoid_topk_is_the_references_choice_including_a_near_tie():
    dims = dict(HF)
    ref = Reference(dims, SEED)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 64)), jnp.float32)
    want_idx, want_w = ref.routing(0, x)
    sp = W.shared_params(W.seed_key(SEED), 0, dims)
    h = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-5)
    logits = jnp.dot(h, sp["router"], precision=jax.lax.Precision.HIGHEST)
    idx, w = sigmoid_topk(logits, sp["router_bias"], 4, 1.0, True)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(want_idx), -1))
    np.testing.assert_allclose(np.sort(np.asarray(w), -1), np.sort(np.asarray(want_w), -1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)  # renormalised, scale 1
    # a near-tie: experts 2 and 5 two millionths apart at the edge of the top-2 (a few float32 steps of the sigmoid); the larger wins, whichever it is
    base = np.full((2, 8), -3.0, np.float32)
    base[:, 0] = 2.0
    base[0, 2], base[0, 5] = 1.0, np.float32(1.000002)
    base[1, 5], base[1, 2] = 1.0, np.float32(1.000002)
    idx, _ = sigmoid_topk(jnp.asarray(base), None, 2, renormalize=False)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 5] and sorted(np.asarray(idx)[1].tolist()) == [0, 2]
    # the bias selects and never weighs
    bias = jnp.zeros((8,)).at[7].set(10.0)
    idx, w = sigmoid_topk(jnp.asarray(base), bias, 2, scale=2.5, renormalize=False)
    assert (np.asarray(idx)[:, 0] == 7).all()
    np.testing.assert_allclose(np.asarray(w)[:, 0], 2.5 / (1 + np.exp(3.0)), rtol=1e-5)


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    dims = {**HF, "n_routed_experts": 32, "num_experts_per_tok": 8}
    ref = Reference(dims, SEED)
    h = jnp.asarray(np.random.default_rng(3).standard_normal((24, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, shared = ref.moe_parts(1, h, held=(0, 32))
        parts = [ref.moe_parts(1, h, held=(4 * r, 4))[0] for r in range(8)]
        np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole + shared), atol=1e-5)
        # the program's share equals the reference's share, rank by rank
        sp = W.shared_params(W.seed_key(SEED), 1, dims)
        x = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + 1e-5)
        idx, w = sigmoid_topk(jnp.dot(x, sp["router"], precision=jax.lax.Precision.HIGHEST), sp["router_bias"], 8)
        for r in (0, 5):
            ex = [W.expert_params(W.seed_key(SEED), 1, 4 * r + e, dims) for e in range(4)]
            out, counts = dropless_held_experts(x, idx, w, jnp.stack([e["gu"] for e in ex]),
                                                jnp.stack([e["down"] for e in ex]), (4 * r, 4))
            np.testing.assert_allclose(np.asarray(out), np.asarray(parts[r]), atol=1e-5)
            assert int(counts[:-1].sum()) == int(counts[-1])


def test_no_assignment_is_dropped_under_a_deliberately_skewed_router():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32).at[:, 5].add(20.0).at[:, 6].add(10.0)
    idx, w = sigmoid_topk(logits, None, 4)
    assert (np.asarray(idx)[:, 0] == 5).all() and (np.asarray(idx)[:, 1] == 6).all()
    gu = jnp.asarray(rng.standard_normal((8, 16, 16)), jnp.float32) * 0.1
    down = jnp.asarray(rng.standard_normal((8, 8, 16)), jnp.float32) * 0.1
    out, counts = dropless_held_experts(x, idx, w, gu, down, (4, 8))
    assert int(counts[1]) == 64 and int(counts[2]) == 64  # experts 5 and 6 take every token
    assert int(counts[:-1].sum()) == int(counts[-1]) and np.isfinite(np.asarray(out)).all()


def test_from_hf_takes_the_published_keys_and_refuses_what_is_not_implemented():
    cfg = so.SolarOpen2Config.from_hf(HF, experts_held=[4, 8], vocab_held=128)
    assert (cfg.num_hidden_layers, cfg.gqa_layers, cfg.kda_layers) == (8, (0, 4), (1, 2, 3, 5, 6, 7))
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_size, cfg.kda_rank) == (4, 16, 4, 16)
    assert cfg.held == (4, 8) and cfg.vocab_rows == 128 and cfg.n_layer == 8
    cut = so.SolarOpen2Config.from_hf(HF, num_hidden_layers=4)
    assert cut.gqa_layers == (0,) and cut.kda_layers == (1, 2, 3)  # gqa_layers follows from the depth
    for bad in ({"use_rope": True}, {"first_k_dense_replace": 1}, {"kda_use_full_proj": True}, {"use_gqa_gate": False},
                {"tie_word_embeddings": True}, {"n_group": 2}, {"scoring_func": "softmax"},
                {"linear_attn_config": {**HF["linear_attn_config"], "num_kv_heads": 2}}):
        with pytest.raises(ValueError, match="not implemented"):
            so.SolarOpen2Config.from_hf({**HF, **bad})
    with pytest.raises(ValueError, match="experts_held"):
        so.SolarOpen2Config.from_hf(HF, experts_held=[12, 8])
    shapes = so.param_shapes(so.SOLAR_OPEN2_TINY)
    assert "conv" not in shapes["layers"][0] and "gate" in shapes["layers"][4] and "A_log" in shapes["layers"][5]
    p = so.init_params(so.SOLAR_OPEN2_TINY, seed=1)
    decay = np.exp(-np.exp(p["layers"][1]["A_log"])[:, None] * np.log1p(np.exp(p["layers"][1]["dt_bias"])).reshape(4, 16))
    assert 0.15 < decay.min() and decay.max() < 0.9995  # neither 0 nor 1
    assert not p["layers"][1]["router_bias"].any() and (p["layers"][1]["o_norm"] == 1).all()


@pytest.fixture(scope="module")
def served():
    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine

    inf = deepspeed_tpu.init_inference(model_config=so.SOLAR_OPEN2_TINY, dtype=jnp.float32, max_out_tokens=128, seed=3)
    srv = ServingEngine(inf, config={"num_slots": 3, "max_len": 128, "prefill_chunk": 16,
                                     "kvcache": {"enabled": True, "page_len": 16}})
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 256, n, dtype=np.int32), m) for n, m in ((20, 6), (37, 9), (5, 4), (50, 7), (16, 5), (33, 8), (3, 3))]
    ids = [srv.submit(p, max_new_tokens=m, session_id="s1" if i == 2 else None) for i, (p, m) in enumerate(reqs)]
    return srv, reqs, ids, srv.drain()


def test_init_inference_serves_the_family_on_the_normal_path(served):
    srv, reqs, ids, done = served
    st = srv.stats()
    assert (st["prefill_compiles"], st["decode_compiles"]) == (1, 1)  # still exactly two executables, seven requests over three slots
    assert all(len(done[i].generated) == m for i, (_, m) in zip(ids, reqs))
    assert st["moe"]["dropped_assignments"] == 0 and len(st["moe"]["tokens_per_expert"]) == 8
    hy = st["hybrid"]
    assert hy["state_bytes"] == srv.pool.state_bytes() > 0 and hy["state_resets_in_program"] == 7
    assert 1.0 <= hy["decode_rows_updated_mean"] <= 3.0
    assert st["kda_prefill_form"].startswith("chunked jnp") and st["gqa_prefill_form"].startswith("blockwise")
    assert st["kda_decode_kernel"] is False and st["gqa_decode_kernel"] is False and "not armed" in st["kda_decode_fallback"]
    assert st["pool_bytes"] == srv.pool.cache_bytes()


def test_served_tokens_are_the_greedy_tokens_of_a_lone_forward(served):
    """What the engine emitted for a request that shared the pool with
    six others equals a lone chunk-free teacher-forced forward's argmax."""
    srv, reqs, ids, done = served
    cfg, params = srv.engine.model_config, srv.engine.params
    for j in (1, 3):  # slot-sharing, multi-chunk prompts; slots reused by later requests
        prompt, gen = reqs[j][0], done[ids[j]].generated
        seq = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        pad = -(-len(seq) // 16) * 16
        kind = so.cache_kind(cfg, jnp.float32)
        k, v = kind.buffers(cfg.n_layer, 1 + pad // 16, 16)
        state = kind.state_buffers(1)
        t = np.zeros((1, pad), np.int32)
        t[0, :len(seq)] = seq
        table, slot0, pos0 = jnp.arange(1, 1 + pad // 16, dtype=jnp.int32)[None], jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)
        valid = jnp.asarray((np.arange(pad) < len(seq))[None])
        for i, tok in enumerate(gen):
            logits = so.forward_with_cache(params, jnp.asarray(t), k, v, state, pos0, cfg, table, slot=slot0, row_valid=valid,
                                           take=jnp.asarray([len(prompt) - 1 + i], jnp.int32))[0]
            top2 = np.sort(np.asarray(logits)[0])[-2:]
            assert int(jnp.argmax(logits[0])) == tok or top2[1] - np.asarray(logits)[0, tok] < 1e-4


def test_compiled_step_takes_the_state_donated(served):
    srv = served[0]
    for which in ("prefill", "decode"):
        m = srv.compiled_step(which).memory_analysis()
        # K, V and the state group all come back aliased: nothing of the pool is copied
        assert m.alias_size_in_bytes >= srv.pool.cache_bytes()
