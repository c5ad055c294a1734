"""GigaChat3.5 at the tiny size on the CPU, seeded weights: the program
(its forward on the hybrid cache over latent pages — a latent buffer +
per-slot recurrent state — its norms, its gate, its clamp, its routing,
its share) against ``benchmark/reference_gigachat35.py``, and the family
through ``init_inference`` → ``ServingEngine``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_gigachat35 as W
from benchmark.reference_gigachat35 import Reference
from benchmark.reference_gigachat35 import gated_norm as ref_norm
from deepspeed_tpu.models import gigachat35 as gc
from deepspeed_tpu.moe.layer import dropless_held_experts, sigmoid_topk, swiglu_gate

HF = {"model_type": "gigachat3_5", "vocab_size": 256, "max_position_embeddings": 4096, "hidden_size": 64,
      "intermediate_size": 96, "moe_intermediate_size": 32, "num_hidden_layers": 8, "nextn_is_sparse": False,
      "num_attention_heads": 4, "n_shared_experts": 1, "n_routed_experts": 16, "routed_scaling_factor": 2.5,
      "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16, "qk_head_dim": 24,
      "n_group": 1, "topk_group": 1, "num_experts_per_tok": 4, "first_k_dense_replace": 1, "norm_topk_prob": True,
      "rope_interleave": True, "num_key_value_heads": 4, "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 100000,
      "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                       "original_max_position_embeddings": 16, "type": "yarn"},
      "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
      "gated_attention": True, "use_shared_expert_sigmoid": False, "use_mla_scaling_factor": True,
      "linear_attention_type": "GigaChat35GatedDeltaNet", "full_attention_layers": [3, 7],
      "linear_key_head_dim": 16, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "linear_num_key_heads": 2,
      "linear_num_value_heads": 4, "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered", "linear_sigmoid_gate_scale": 2,
      "linear_attn_o_norm_eps": 1e-6, "swiglu_limit": 10, "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
SEED = 2 ** 31 + 11
SLOTS, PAGES_PER_SLOT, PAGE_LEN, CHUNK = 4, 8, 8, 16


def _program(dims):
    cfg = gc.GigaChat35Config.from_hf(dims, experts_held=dims.get("experts_held"), vocab_held=dims.get("vocab_held"))
    return cfg, W.program_params(SEED, dims, jnp.float32)


class _Cache:
    """The hybrid cache of SLOTS slots, each slot's pages its own."""

    def __init__(self, cfg):
        kind = gc.cache_kind(cfg, jnp.float32)
        self.k, self.v = kind.buffers(cfg.n_layer, 1 + SLOTS * PAGES_PER_SLOT, PAGE_LEN)
        self.state = kind.state_buffers(SLOTS)
        self.tables = jnp.asarray(1 + np.arange(SLOTS * PAGES_PER_SLOT, dtype=np.int32).reshape(SLOTS, PAGES_PER_SLOT))


def _prefill(cfg, params, cache, slot, toks, **kw):
    """Chunk by chunk, the last chunk padded; returns the logits at the prompt's last token."""
    for start in range(0, len(toks), CHUNK):
        n = min(CHUNK, len(toks) - start)
        t = np.full((1, CHUNK), 7, np.int32)  # a padded tail of real-looking ids: it must not count
        t[0, :n] = toks[start:start + n]
        logits, cache.k, cache.state, aux = gc.forward_with_cache(
            params, jnp.asarray(t), cache.k, cache.state, jnp.asarray([start], jnp.int32), cfg,
            cache.tables[slot][None], slot=jnp.asarray([slot], jnp.int32),
            row_valid=jnp.asarray((np.arange(CHUNK) < n)[None]), take=jnp.asarray([n - 1], jnp.int32), **kw)
    return np.asarray(logits)[0], aux


def _decode(cfg, params, cache, feed, **kw):
    """One decode step: ``feed`` maps slot -> (token, position); the other rows do not decode."""
    t, pos, mask = np.full((SLOTS, 1), 3, np.int32), np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), bool)
    for s, (tok, p) in feed.items():
        t[s, 0], pos[s], mask[s] = tok, p, True
    logits, cache.k, cache.state, aux = gc.forward_with_cache(
        params, jnp.asarray(t), cache.k, cache.state, jnp.asarray(pos), cfg, cache.tables,
        write_mask=jnp.asarray(mask), row_valid=jnp.asarray(mask[:, None]), **kw)
    return np.asarray(logits), aux


@pytest.mark.parametrize("share,n_prompt", [(None, 37), ((4, 8), 37), (None, 32), (None, 5)])
def test_chunked_prefill_then_decode_on_the_hybrid_cache_is_the_references_full_forward(share, n_prompt):
    dims = dict(HF) if share is None else {**HF, "experts_held": list(share), "vocab_held": 128}
    cfg, params = _program(dims)
    toks = np.random.default_rng(0).integers(1, 128, n_prompt + 8, dtype=np.int32)
    want = np.asarray(Reference(dims, SEED).logits(toks[None])[0])
    cache = _Cache(cfg)
    with jax.default_matmul_precision("highest"):
        got, aux = _prefill(cfg, params, cache, 2, toks[:n_prompt])  # across chunk and page boundaries, a padded tail
        np.testing.assert_allclose(got, want[n_prompt - 1], atol=3e-4)
        assert aux.shape == (7, cfg.held[1] + 1) and int(aux[:, :-1].sum()) == int(aux[:, -1].sum())  # 7 expert layers of 8
        for i in range(n_prompt, n_prompt + 8):  # decode, the other three rows not decoding
            logits, _ = _decode(cfg, params, cache, {2: (toks[i], i)})
            np.testing.assert_allclose(logits[2], want[i], atol=3e-4)


def test_a_slot_reused_by_a_second_request_starts_from_zero_and_the_caches_are_the_references():
    cfg, params = _program(HF)
    rng = np.random.default_rng(1)
    a, b = rng.integers(1, 256, 40, dtype=np.int32), rng.integers(1, 256, 21, dtype=np.int32)
    cache = _Cache(cfg)
    ref = Reference(HF, SEED)
    with jax.default_matmul_precision("highest"):
        _prefill(cfg, params, cache, 1, a)
        _decode(cfg, params, cache, {1: (9, 40)})
        got, _ = _prefill(cfg, params, cache, 1, b)  # the same slot, the same pages, no reset program
        np.testing.assert_allclose(got, np.asarray(ref.logits(b[None])[0])[-1], atol=3e-4)
        states, rows = ref.traces(b, len(b), np.arange(len(b)))
    np.testing.assert_allclose(np.asarray(cache.state["s"][:, 1]), states, atol=1e-5)  # 6 delta-rule layers' S
    pages = np.asarray(cache.k)[:, np.asarray(cache.tables[1])]  # (latent layers, pages, width, page_len)
    held = pages.transpose(0, 1, 3, 2).reshape(2, PAGES_PER_SLOT * PAGE_LEN, cfg.cache_width)[:, :len(b)]
    np.testing.assert_allclose(held, rows, atol=1e-5)  # [c_kv | k_pe] of both latent layers, every position
    assert not np.asarray(cache.state["s"][:, 0]).any() and not np.asarray(cache.state["s"][:, 2:]).any()


def test_two_slots_decode_together_and_a_masked_row_keeps_its_state():
    cfg, params = _program(HF)
    rng = np.random.default_rng(2)
    a, b = rng.integers(1, 256, 19, dtype=np.int32), rng.integers(1, 256, 9, dtype=np.int32)
    ref = Reference(HF, SEED)
    cache = _Cache(cfg)
    with jax.default_matmul_precision("highest"):
        _prefill(cfg, params, cache, 0, a[:16])
        _prefill(cfg, params, cache, 3, b[:8])
        before = np.asarray(cache.state["s"][:, 3])
        la, _ = _decode(cfg, params, cache, {0: (a[16], 16)})  # slot 3 does not decode
        np.testing.assert_array_equal(np.asarray(cache.state["s"][:, 3]), before)
        lb, _ = _decode(cfg, params, cache, {0: (a[17], 17), 3: (b[8], 8)})
        np.testing.assert_allclose(lb[0], np.asarray(ref.logits(a[None, :18])[0])[-1], atol=3e-4)
        np.testing.assert_allclose(lb[3], np.asarray(ref.logits(b[None])[0])[-1], atol=3e-4)


# ---------------------------------------------------------------------------
# the assumed forms, each against its formula and against its other reading
# ---------------------------------------------------------------------------

def test_the_norm_is_a_sigmoid_gain_scaled_by_two_and_not_one_plus_w():
    rng = np.random.default_rng(3)
    x, w = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32), jnp.asarray(rng.normal(size=(64,)) * 0.5, jnp.float32)
    got = np.asarray(gc.gated_norm(x, w, 1e-6, 2.0))
    unit = np.asarray(x) / np.sqrt(np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, unit * 2.0 / (1.0 + np.exp(-np.asarray(w))), rtol=1e-5)
    np.testing.assert_allclose(got, np.asarray(ref_norm(x, w, {"rms_norm_eps": 1e-6, "layernorm_gating_weight": 2})), rtol=1e-5)
    other = unit * (1.0 + np.asarray(w))  # the other reading of ZeroCenteredGatedNorm
    assert np.abs(got - other).max() > 0.05  # with w drawn at 0.5 the two readings are different functions
    np.testing.assert_allclose(np.asarray(gc.gated_norm(x, jnp.zeros((64,)), 1e-6, 2.0)), unit, rtol=1e-5)  # and the same at w = 0
    # the seeded gains are not 0: what is compared is what is implemented
    nw = W.norm_params(W.seed_key(SEED), 1, HF)
    assert set(nw) == set(gc.NORMS) and all(0.3 < float(jnp.std(v)) < 0.7 for v in nw.values())
    assert 0.3 < float(jnp.std(W.final_gain(W.seed_key(SEED), HF))) < 0.7


def test_the_clamped_swiglu_is_active_past_the_limit():
    g = jnp.asarray([-30.0, -1.0, 0.5, 9.0, 10.0, 12.0, 40.0])
    u = jnp.asarray([-25.0, -10.0, 3.0, 11.0, -11.0, 2.0, 30.0])
    got = np.asarray(swiglu_gate(g, u, 10.0))
    want = np.asarray(jax.nn.silu(jnp.minimum(g, 10.0))) * np.clip(np.asarray(u), -10.0, 10.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = np.asarray(swiglu_gate(g, u))
    assert np.abs(got - plain)[[3, 4, 5, 6]].min() > 1e-6 and np.allclose(got[[1, 2]], plain[[1, 2]])
    # through the held experts: inputs large enough that the clamp binds
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(6, 16)) * 40.0, jnp.float32)
    w_gu, w_down = jnp.asarray(rng.normal(size=(4, 16, 16)), jnp.float32), jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    idx, w = jnp.asarray(rng.integers(0, 4, (6, 2)), jnp.int32), jnp.ones((6, 2), jnp.float32)
    with jax.default_matmul_precision("highest"):
        clamped, _ = dropless_held_experts(x, idx, w, w_gu, w_down, (0, 4), swiglu_limit=10.0)
        free, _ = dropless_held_experts(x, idx, w, w_gu, w_down, (0, 4))
        want = sum((idx == e).sum(-1)[:, None] * (swiglu_gate(*jnp.split(x @ w_gu[e], 2, axis=-1), 10.0) @ w_down[e])
                   for e in range(4))  # weight 1 an assignment: an expert chosen twice counts twice
    np.testing.assert_allclose(np.asarray(clamped), np.asarray(want), rtol=1e-4, atol=1e-3)
    assert np.abs(np.asarray(clamped) - np.asarray(free)).max() > 1.0


def test_sigmoid_topk_at_256_outputs_is_a_plain_topk():
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(33, 256)) * 1.7, jnp.float32)
    idx, w = sigmoid_topk(logits, jnp.zeros((256,)), 8, 2.5, True)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    order = np.argsort(-s, axis=-1)[:, :8]
    assert (np.sort(np.asarray(idx), -1) == np.sort(order, -1)).all()
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(w), 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    # a bias selects and never weighs
    bias = jnp.zeros((256,)).at[7].set(5.0)
    idx_b, w_b = sigmoid_topk(logits, bias, 8, 2.5, True)
    assert (np.asarray(idx_b) == 7).any(-1).all()
    at7 = np.take_along_axis(np.asarray(w_b), np.argmax(np.asarray(idx_b) == 7, -1)[:, None], -1)[:, 0]
    chosen_b = np.take_along_axis(s, np.asarray(idx_b), -1)
    np.testing.assert_allclose(at7, 2.5 * s[:, 7] / chosen_b.sum(-1), rtol=1e-5)


def test_gated_latent_attention_is_the_references_with_the_gate_non_trivial():
    """One latent layer alone, chunked prefill + decode through the
    latent pages, against the reference's mixer — and against the same
    mixer with the gate left out, which it must differ from."""
    from benchmark.reference_gigachat35 import mla as ref_mla

    cfg, params = _program(HF)
    lp = params["layers"][3]
    ap = W.mla_params(W.seed_key(SEED), 3, HF)
    rng = np.random.default_rng(6)
    T = 24
    u = jnp.asarray(rng.normal(size=(1, T, 64)), jnp.float32)
    kind = gc.cache_kind(cfg, jnp.float32)
    pool, _ = kind.buffers(cfg.n_layer, 1 + PAGES_PER_SLOT, PAGE_LEN)
    table = jnp.arange(1, 1 + PAGES_PER_SLOT, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        want, rows = ref_mla(ap, u[0], HF, "float32")
        y, pool = gc.mla_mixer(cfg, lp, u[:, :16], pool, 1, jnp.asarray([0], jnp.int32), table)  # a chunk of 16, then 8 tokens one by one
        got = [y[0]]
        for t in range(16, T):
            y, pool = gc.mla_mixer(cfg, lp, u[:, t:t + 1], pool, 1, jnp.asarray([t], jnp.int32), table)
            got.append(y[0])
        got = jnp.concatenate(got)
        ungated, _ = ref_mla({**ap, "gate": jnp.zeros_like(ap["gate"])}, u[0], HF, "float32")  # sigmoid(0) = 1/2 everywhere
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.abs(np.asarray(want) - 2.0 * np.asarray(ungated)).max() > 1e-3  # the gate is not a constant
    held = np.asarray(pool)[1, 1:1 + 3].transpose(0, 2, 1).reshape(3 * PAGE_LEN, cfg.cache_width)
    np.testing.assert_allclose(held, np.asarray(rows), atol=1e-5)
    assert abs(cfg.softmax_scale - 24 ** -0.5 * (0.1 * np.log(8.0) + 1.0) ** 2) < 1e-9  # 1.208^2 at factor 8


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """An expert layer of the program told each of its 16-way shares in
    turn: the shares' routed outputs plus the shared expert counted once
    are the uncut reference's layer."""
    dims = dict(HF)
    ref = Reference(dims, SEED)
    key = W.seed_key(SEED)
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(12, 64)), jnp.float32)
    l = 2
    with jax.default_matmul_precision("highest"):
        whole_routed, shared = ref.moe_parts(l, h, (0, 16))
        r = ref_norm(h, W.norm_params(key, l, dims)["ffn_in_w"], dims)
        sp = W.shared_params(key, l, dims)
        logits = jnp.dot(r, sp["router"], precision=jax.lax.Precision.HIGHEST)
        idx, w = sigmoid_topk(logits, sp["router_bias"], 4, 2.5, True)
        total = jnp.zeros_like(h)
        for first in range(16):  # one expert a share
            ep = W.expert_params(key, l, first, dims)
            part, counts = dropless_held_experts(r, idx, w, ep["gu"][None], ep["down"][None], (first, 1), swiglu_limit=10.0)
            total = total + part
        assert int(jnp.sum((idx >= 0).astype(jnp.int32))) == 12 * 4
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole_routed), atol=2e-5)
        # the whole layer: routed over all shares + the shared expert once, through the sandwich's last norm
        want = ref.ffn(l, h, (0, 16))
        got = h + ref_norm(total + shared, W.norm_params(key, l, dims)["ffn_out_w"], dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_from_hf_reads_the_published_keys_and_refuses_what_is_not_implemented():
    cfg = gc.GigaChat35Config.from_hf(HF)
    assert (cfg.full_attention_layers, cfg.linear_layers) == ((3, 7), (0, 1, 2, 4, 5, 6))
    assert (cfg.rope_factor, cfg.rope_original_max_position_embeddings, cfg.rope_theta) == (8, 16, 100000)
    assert (cfg.linear_conv_width, cfg.cache_width, cfg.swiglu_limit, cfg.layernorm_gating_weight) == (2 * 32 + 64, 40, 10, 2)
    cut = gc.GigaChat35Config.from_hf(HF, num_hidden_layers=5, full_attention_layers=[4], experts_held=[0, 4])
    assert cut.full_attention_layers == (4,) and cut.held == (0, 4)
    published = gc.GigaChat35Config()
    assert (published.hidden_size, published.linear_conv_width, published.full_attention_layers[:2]) == (7168, 16384, (3, 7))
    assert abs(published.softmax_scale - 192 ** -0.5 * 1.2079 ** 2) < 1e-4
    for bad, why in (({"norm_type": "RMSNorm"}, "norm_type"), ({"layernorm_type": "pre"}, "layernorm_type"),
                     ({"gated_attention": False}, "gated_attention"), ({"n_group": 8}, "grouped routing"),
                     ({"linear_gating_type": "swish"}, "linear_gating_type"), ({"tie_word_embeddings": True}, "tie_word")):
        with pytest.raises(ValueError, match=why):
            gc.GigaChat35Config.from_hf({**HF, **bad})
    with pytest.raises(ValueError, match="multi-token-prediction"):
        gc.GigaChat35Config.from_hf({**HF, "num_nextn_predict_layers": 2})


def test_param_shapes_follow_the_two_independent_choices_a_layer():
    shapes = gc.param_shapes(gc.GIGACHAT35_TINY)
    kinds = [("kv_a" in lp, "mlp_gu" in lp) for lp in shapes["layers"]]
    assert kinds == [(False, True)] + [(l in (3, 7), False) for l in range(1, 8)]  # (latent?, dense?)
    assert shapes["layers"][0]["qkv"] == (64, 2 * 2 * 16 + 4 * 16) and shapes["layers"][0]["conv"] == (4, 128)
    assert shapes["layers"][3]["gate"] == (64, 64) and shapes["layers"][1]["experts_gu"] == (16, 64, 64)
    p = gc.init_params(gc.GIGACHAT35_TINY, seed=1)
    assert p["layers"][1]["mixer_in_w"].std() > 0.3 and p["final_w"].std() > 0.3 and (p["layers"][3]["q_a_norm"] == 1).all()
    assert not p["layers"][1]["router_bias"].any()


# ---------------------------------------------------------------------------
# through init_inference -> ServingEngine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine

    inf = deepspeed_tpu.init_inference(model_config=gc.GIGACHAT35_TINY, dtype=jnp.float32, max_out_tokens=128, seed=3)
    srv = ServingEngine(inf, config={"num_slots": 3, "max_len": 128, "prefill_chunk": 16,
                                     "kvcache": {"enabled": True, "page_len": 16}})
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 256, n, dtype=np.int32), m) for n, m in ((20, 6), (37, 9), (5, 4), (50, 7), (16, 5), (33, 8), (3, 3))]
    ids = [srv.submit(p, max_new_tokens=m, session_id="s1" if i == 2 else None) for i, (p, m) in enumerate(reqs)]
    return srv, reqs, ids, srv.drain()


def test_init_inference_serves_the_family_on_the_normal_path(served):
    srv, reqs, ids, done = served
    st = srv.stats()
    assert (st["prefill_compiles"], st["decode_compiles"]) == (1, 1)  # exactly two executables, seven requests over three slots
    assert all(len(done[i].generated) == m for i, (_, m) in zip(ids, reqs))
    assert st["moe"]["dropped_assignments"] == 0 and len(st["moe"]["tokens_per_expert"]) == 7
    hy = st["hybrid"]
    assert hy["state_bytes"] == srv.pool.state_bytes() > 0 and hy["state_resets_in_program"] == 7
    assert st["gdn_prefill_form"].startswith("chunked jnp, scalar decay") and st["mla_prefill_form"].startswith("blockwise jnp")
    assert st["gdn_decode_kernel"] is False and st["mla_decode_kernel"] is False and "not armed" in st["gdn_decode_fallback"]
    assert st["moe_router_form"].startswith("sigmoid_topk") and "moe_grouped_kernel" in st
    kv = st["kvcache"]
    assert kv["page_kind"] == "LatentKV" and kv["reuse"].startswith("off:") and "2 of 8 layers" in kv["kind"] and "latent" in kv["kind"]
    assert kv["page_leaves"] == {"k": 2 * kv["num_pages"] * 40 * 16 * 4}  # one latent leaf, no V
    assert kv["state_leaves"] == {"s": 6 * 3 * 4 * 16 * 16 * 4, "conv": 6 * 3 * 3 * 128 * 4}
    assert st["pool_bytes"] == srv.pool.cache_bytes() == sum(kv["page_leaves"].values()) + sum(kv["state_leaves"].values())
    assert srv.pool.v is None


def test_served_tokens_are_the_greedy_tokens_of_a_lone_forward(served):
    """What the engine emitted for a request that shared the pool with
    six others equals a lone chunk-free teacher-forced forward's argmax."""
    srv, reqs, ids, done = served
    cfg, params = srv.engine.model_config, srv.engine.params
    for j in (3,):  # a multi-chunk prompt (50 tokens) in a slot that earlier requests had used
        prompt, gen = reqs[j][0], done[ids[j]].generated
        seq = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        pad = -(-len(seq) // 16) * 16
        kind = gc.cache_kind(cfg, jnp.float32)
        k, _ = kind.buffers(cfg.n_layer, 1 + pad // 16, 16)
        state = kind.state_buffers(1)
        t = np.zeros((1, pad), np.int32)
        t[0, :len(seq)] = seq
        table, slot0, pos0 = jnp.arange(1, 1 + pad // 16, dtype=jnp.int32)[None], jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)
        valid = jnp.asarray((np.arange(pad) < len(seq))[None])
        lone = jax.jit(lambda take: gc.forward_with_cache(params, jnp.asarray(t), k, state, pos0, cfg, table, slot=slot0,
                                                          row_valid=valid, take=take)[0])
        for i, tok in enumerate(gen):
            logits = lone(jnp.asarray([len(prompt) - 1 + i], jnp.int32))
            assert int(jnp.argmax(logits[0])) == tok or np.sort(np.asarray(logits)[0])[-1] - np.asarray(logits)[0, tok] < 1e-4


def _like_served(srv, **cfg):
    from deepspeed_tpu.serving import ServingEngine

    return ServingEngine(srv.engine, config={"num_slots": 3, "max_len": 128, "prefill_chunk": 16,
                                             "kvcache": {"enabled": True, "page_len": 16}, **cfg})


def test_overlap_chunks_serves_the_same_tokens_and_counts_every_chunk(served):
    """The default order of a step (``serving.overlap_chunks``) on the
    hybrid pool over latent pages: the tokens, the experts' counters
    (every chunk's, the unread ones read a step late) and the state's
    resets are the serial step's."""
    srv, reqs, ids, done = served
    assert srv.config.overlap_chunks is True
    serial = _like_served(srv, overlap_chunks=False)
    theirs = [serial.submit(p, max_new_tokens=m, session_id="s1" if i == 2 else None) for i, (p, m) in enumerate(reqs)]
    got = serial.drain()
    assert [got[i].generated for i in theirs] == [done[i].generated for i in ids]
    a, b = serial.stats(), srv.stats()
    assert b["moe"]["tokens_per_expert"] == a["moe"]["tokens_per_expert"] and b["moe"]["dropped_assignments"] == 0
    assert b["hybrid"]["state_resets_in_program"] == a["hybrid"]["state_resets_in_program"] == 7
    assert (b["prefill_compiles"], b["decode_compiles"]) == (1, 1) and not srv._unread_chunks
    chunks = sum(-(-len(p) // 16) for p, _ in reqs)
    assert (a["chunks_awaited"], a["chunks_deferred"]) == (chunks, 0)
    assert (b["chunks_awaited"], b["chunks_deferred"]) == (len(reqs), chunks - len(reqs))  # a prompt's last chunk is waited for


@pytest.mark.parametrize("overlap", [True, False], ids=["default-order", "serial-order"])
def test_a_fault_between_two_dispatches_runs_no_program_of_the_recurrent_state_twice(served, overlap):
    """``faults.check("serving.prefill")`` fires after the default step
    has handed its decode program over.  That program advanced the
    rows' recurrent state, so it is read back and noted before the fault
    goes up, and the engine serves on with the fault-free run's tokens
    (the serial step has nothing in flight at that site)."""
    from deepspeed_tpu.resilience import faults

    srv, reqs, ids, done = served
    again = _like_served(srv, overlap_chunks=overlap)
    mine = [again.submit(p, max_new_tokens=m, session_id="s1" if i == 2 else None) for i, (p, m) in enumerate(reqs)]
    raised = 0
    for after in (3, 2):  # the 4th chunk's launch, then the 3rd after it: rows are decoding, prompts are mid-prefill
        with faults.FaultInjector(seed=0).fail("serving.prefill", times=1, after=after):
            while not raised or again.scheduler.has_work():
                try:
                    again.step()
                except faults.InjectedFault:
                    raised += 1
                    break
    got = again.drain(max_steps=500)
    assert raised == 2 and not again.scheduler.has_work() and not again._unread_chunks
    assert [got[i].generated for i in mine] == [done[i].generated for i in ids]


def test_compiled_step_takes_the_pool_and_the_state_donated(served):
    srv = served[0]
    for which in ("prefill", "decode"):
        m = srv.compiled_step(which).memory_analysis()
        assert m.alias_size_in_bytes >= srv.pool.cache_bytes()  # the latent pool and the state group come back aliased
