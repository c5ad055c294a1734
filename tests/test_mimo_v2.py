"""MiMo-V2 at the tiny size on the CPU, seeded weights: the program (its
forward on two page groups of unequal geometry — 2 KV heads on pages by
length, 4 on a ring a slot, keys wider than values —, its sinks, its
rotary by kind, its routing, its share) against
``benchmark/reference_mimo.py``, through ``ServingEngine``; and the paged
kernels' ``d_v != d`` and ``sink`` forms in interpret mode."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_mimo as W
from benchmark.reference_mimo import Reference
from deepspeed_tpu.models import mimo_v2 as mm
from deepspeed_tpu.ops.kernels.flash_chunk import flash_chunk_paged
from deepspeed_tpu.ops.kernels.flash_decode import flash_decode_paged, paged_tile, paged_work_list
from deepspeed_tpu.ops.transformer import inference as inf

# the published key set at a tiny size: both kinds of layer, 8 query heads on 2 (full) / 4 (window) KV heads, keys 24 wide over
# values 16 (8 of the 24 rotated), sinks on the window layers, a window of 6 (smaller than a page of 8 and than a chunk of 16),
# a dense first layer, 16 experts top-4 and no shared one
HF = {"model_type": "mimo_v2_flash", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 4,
      "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 24, "v_head_dim": 16, "swa_num_attention_heads": 8,
      "swa_num_key_value_heads": 4, "swa_head_dim": 24, "swa_v_head_dim": 16, "sliding_window": 6, "sliding_window_size": 6,
      "attention_chunk_size": 6, "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1], "rope_theta": 5000000,
      "swa_rope_theta": 10000, "partial_rotary_factor": 0.334, "attention_value_scale": 0.707, "attention_bias": False,
      "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False, "layernorm_epsilon": 1e-5, "hidden_act": "silu",
      "moe_intermediate_size": 32, "n_routed_experts": 16, "n_shared_experts": None, "num_experts_per_tok": 4, "norm_topk_prob": True,
      "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc", "routed_scaling_factor": None,
      "tie_word_embeddings": False, "max_position_embeddings": 4096}
SEED = 2 ** 31 + 11
PAGE_LEN, CHUNK = 8, 16  # a ring of 2 pages = 16 positions: a chunk of 16 laps it


def _engine(dims, slots=3, max_len=96):
    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine

    cfg = mm.MiMoV2Config.from_hf(dims, experts_held=dims.get("experts_held"), vocab_held=dims.get("vocab_held"))
    inf_engine = deepspeed_tpu.init_inference(model_config=cfg, params=W.program_params(SEED, dims, jnp.float32), dtype=jnp.float32,
                                              max_out_tokens=max_len)
    return cfg, ServingEngine(inf_engine, config={"num_slots": slots, "max_len": max_len, "prefill_chunk": CHUNK, "max_new_tokens": 32,
                                                  "kvcache": {"enabled": True, "page_len": PAGE_LEN}})


def test_the_engine_serves_a_share_chunked_prefill_then_decode_through_both_groups_as_the_references_full_forward():
    dims = {**HF, "experts_held": [4, 8], "vocab_held": 128}
    with jax.default_matmul_precision("highest"):
        cfg, srv = _engine(dims)
        rng = np.random.default_rng(0)
        # 53: three chunks and a tail (the ring laps inside a chunk and across chunks); 32: ends on a chunk boundary; 5: shorter than the window
        prompts = [rng.integers(1, 128, n, dtype=np.int32) for n in (53, 32, 5)]
        ids = [srv.submit(p, max_new_tokens=12) for p in prompts]
        done = srv.drain()
        ref = Reference(dims, SEED)
        for rid, p in zip(ids, prompts):
            got = np.asarray(done[rid].generated, np.int32)
            assert len(got) == 12
            # teacher-forced on what the engine emitted: the reference's logit of every emitted token is its row's largest
            logits = np.asarray(ref.logits(np.concatenate([p, got])[None])[0])[len(p) - 1: len(p) + 11]
            assert float(np.max(logits.max(-1) - logits[np.arange(12), got])) < 2e-4
    groups = srv.stats()["kv_groups"]
    assert groups["full"]["kv_heads"] == 2 and groups["window"]["kv_heads"] == 4
    assert (groups["full"]["k_dim"], groups["full"]["v_dim"]) == (24, 16) == (groups["window"]["k_dim"], groups["window"]["v_dim"])
    st = srv.stats()
    assert "sink" in st["swa_decode_form"] and "24 / values 16" in st["swa_chunk_form"] and st["swa_ring_positions"] == 16
    assert st["moe"]["dropped_assignments"] == 0


def test_the_logits_of_a_decode_step_are_the_references_and_a_missing_sink_or_scale_is_seen():
    """The forward itself, logits against logits: prefill in chunks, decode past a lap of the ring."""
    cfg = mm.MiMoV2Config.from_hf(HF)
    params = W.program_params(SEED, HF, jnp.float32)
    kind = mm.cache_kind(cfg, jnp.float32)
    P = 12
    k, v = kind.buffers(cfg.n_layer, 1 + P, PAGE_LEN)
    state = kind.state_buffers(1, PAGE_LEN, CHUNK)
    assert k.shape == (2, 1 + P, 2, PAGE_LEN, 24) and v.shape == (2, 1 + P, 2, PAGE_LEN, 16)
    assert state["wk"].shape == (2, 3, 4, PAGE_LEN, 24) and state["wv"].shape == (2, 3, 4, PAGE_LEN, 16)
    table = jnp.asarray(1 + np.arange(P, dtype=np.int32)[None])
    toks = np.random.default_rng(3).integers(1, 256, 48, dtype=np.int32)
    want = np.asarray(Reference(HF, SEED).logits(toks[None])[0])
    n_prompt = 37
    step = jax.jit(lambda tok, k, v, state, pos: mm.forward_with_cache(params, tok, k, v, state, pos, cfg, table, write_mask=jnp.asarray([True])))
    with jax.default_matmul_precision("highest"):
        for start in range(0, n_prompt, CHUNK):
            n = min(CHUNK, n_prompt - start)
            t = np.full((1, CHUNK), 7, np.int32)
            t[0, :n] = toks[start:start + n]
            logits, k, v, state, _ = mm.forward_with_cache(
                params, jnp.asarray(t), k, v, state, jnp.asarray([start], jnp.int32), cfg, table, slot=jnp.asarray([0], jnp.int32),
                row_valid=jnp.asarray((np.arange(CHUNK) < n)[None]), take=jnp.asarray([n - 1], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[0], want[n_prompt - 1], atol=2e-4)
        for i in range(n_prompt, 48):  # over a page boundary of the ring (40)
            logits, k, v, state, _ = step(jnp.asarray([[toks[i]]]), k, v, state, jnp.asarray([i], jnp.int32))
            np.testing.assert_allclose(np.asarray(logits)[0], want[i], atol=2e-4)
    # the variants the controls use are different functions at this size too
    for variant in ({"no_sink": True}, {"window": 7}, {"window_kv_heads": 2}):
        other = np.asarray(Reference(HF, SEED, **variant).logits(toks[None])[0])
        assert np.abs(other - want).max() > 1e-3, variant


def test_the_shares_routed_parts_add_up_to_the_uncut_expert_layer_and_the_bias_selects_without_weighing():
    ref = Reference(HF, SEED)
    toks = np.random.default_rng(2).integers(1, 256, 24, dtype=np.int32)
    keep = []
    ref.hidden(toks, keep=keep)
    h = keep[2]  # the input of sparse layer 2's feed-forward
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_part(2, h, held=(0, 16))
        parts = [ref.moe_part(2, h, held=(2 * i, 2)) for i in range(8)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=1e-5)
    assert float(jnp.abs(whole).max()) > 1e-3
    from deepspeed_tpu.moe.layer import dropless_held_experts, sigmoid_topk

    dims = {**HF, "experts_held": [4, 2]}
    cfg = mm.MiMoV2Config.from_hf(dims, experts_held=(4, 2))
    lp = W.program_params(SEED, dims, jnp.float32)["layers"][2]
    with jax.default_matmul_precision("highest"):
        x = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + 1e-5)
        idx, w = sigmoid_topk(x @ lp["router"], lp["router_bias"], 4, cfg.routed_scale, True)
        got, counts = dropless_held_experts(x, idx, w, lp["experts_gu"], lp["experts_down"], cfg.held)
    np.testing.assert_allclose(np.asarray(got), np.asarray(parts[2]), atol=1e-5)
    ridx, rw, _ = ref.routing(2, h)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(ridx), -1))
    np.testing.assert_allclose(np.asarray(rw).sum(-1), 1.0, atol=1e-5)  # renormalised; routed_scaling_factor null is 1
    assert float(jnp.abs(lp["router_bias"]).max()) > 0 and int(counts[-1]) == int(np.isin(np.asarray(idx), (4, 5)).sum())


def test_from_hf_reads_the_catalogs_config_verbatim():
    row = next(r for r in map(json.loads, open("/opt/skills/guides/model-configs/architectures.jsonl")) if r.get("name") == "MiMo-V2-Flash")
    cfg = mm.MiMoV2Config.from_hf(row["config"])
    assert cfg == mm.MiMoV2Config()  # the defaults are the published model
    assert cfg.full_layers == (0, 5, 11, 17, 23, 29, 35, 41, 47) and len(cfg.window_layers) == 39
    assert cfg.geometry(mm.FULL) == (64, 4, 192, 128) and cfg.geometry(mm.WINDOW) == (64, 8, 192, 128)
    assert cfg.rotary_dim(mm.FULL) == 64 == cfg.rotary_dim(mm.WINDOW) and cfg.routed_scale == 1.0
    assert cfg.has_sink(mm.WINDOW) and not cfg.has_sink(mm.FULL) and cfg.moe_layer_freq[0] == 0 and set(cfg.moe_layer_freq[1:]) == {1}
    # the stage-0 share: the per-layer lists keep their first eight entries
    cut = mm.MiMoV2Config.from_hf(row["config"], num_hidden_layers=8, experts_held=(0, 16), vocab_held=19072)
    assert cut.full_layers == (0, 5) and cut.window_layers == (1, 2, 3, 4, 6, 7) and cut.held == (0, 16)
    shapes = mm.param_shapes(cut)
    assert shapes["layers"][0]["qkv"] == (4096, 64 * 192 + 4 * 192 + 4 * 128) and shapes["layers"][1]["qkv"] == (4096, 64 * 192 + 8 * 192 + 8 * 128)
    assert shapes["layers"][1]["sink"] == (64,) and "sink" not in shapes["layers"][0] and shapes["layers"][1]["o"] == (8192, 4096)
    assert "mlp_gu" in shapes["layers"][0] and shapes["layers"][1]["experts_gu"] == (16, 4096, 4096) and shapes["layers"][1]["router"] == (4096, 256)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(shapes, is_leaf=lambda s: isinstance(s, tuple)))
    assert abs(n - 3.93e9) < 0.01e9  # ISSUE 55's table: 7.86 GB in bf16
    kind = mm.cache_kind(cut, jnp.bfloat16)
    assert (kind.paged_layers, kind.window_layers, kind.window, kind.ring_pages(128)) == (2, 6, 128, 2)
    assert kind.pages.position_bytes() * 2 == 5120 and kind.window_pages.position_bytes() * 6 == 30720


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 8), ("n_shared_experts", 1), ("attention_bias", True),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"), ("add_full_attention_sink_bias", True),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}), ("sliding_window_size", 64),
])
def test_from_hf_refuses_what_the_family_does_not_compute(key, value):
    with pytest.raises(ValueError, match="not implemented"):
        mm.MiMoV2Config.from_hf({**HF, key: value})


@pytest.mark.parametrize("bad", [{"hybrid_layer_pattern": [0, 1, 2, 0]}, {"moe_layer_freq": [0, 1, 1]}, {"swa_num_key_value_heads": 3},
                                 {"experts_held": (12, 8)}, {"vocab_held": 0}])
def test_a_config_that_does_not_hold_together_is_refused(bad):
    share = {k: v for k, v in bad.items() if k in ("experts_held", "vocab_held")}
    with pytest.raises(ValueError):
        mm.MiMoV2Config.from_hf({**HF, **{k: v for k, v in bad.items() if k not in share}}, **share)


# ---------------------------------------------------------------------------
# the paged kernels with keys wider than values and a sink (interpret mode)
# ---------------------------------------------------------------------------

def _lse_reference(q, K, V, sink=None):
    """One row's attention written out: ``q (H, d)``, ``K (S, Hkv, d)``, ``V (S, Hkv, dv)``, a sink a head."""
    H, d = q.shape
    Hkv = K.shape[1]
    s = np.einsum("hgd,shd->hgs", q.reshape(Hkv, H // Hkv, d), K) / np.sqrt(d)
    m = s.max(-1, keepdims=True) if sink is None else np.maximum(s.max(-1, keepdims=True), sink.reshape(Hkv, -1, 1))
    e = np.exp(s - m)
    z = e.sum(-1, keepdims=True) + (0 if sink is None else np.exp(sink.reshape(Hkv, -1, 1) - m))
    return np.einsum("hgs,shd->hgd", e / z, V).reshape(H, V.shape[-1])


@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("sunk", [False, True])
def test_flash_decode_paged_with_values_narrower_than_keys_and_a_sink_is_the_written_out_softmax(window, sunk):
    B, H, Hkv, d, dv, page_len, P = 3, 16, 2, 192, 128, 128, 4
    rng = np.random.default_rng(4)
    R = inf.ring_pages_for(window, page_len) if window else P
    k = jnp.asarray(rng.standard_normal((1 + B * R, Hkv, page_len, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1 + B * R, Hkv, page_len, dv)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.float32)
    sink = jnp.asarray(rng.standard_normal((H,)) + 3.0, jnp.float32) if sunk else None
    table = inf.ring_table(jnp.arange(B), R, P)  # a ring of 2 pages under a window of 128; every page its own without one
    pos = jnp.asarray([5, 300, 511], jnp.int32)
    live = jnp.asarray([True, True, False])
    sds = lambda w: jax.ShapeDtypeStruct((9, 12, page_len, w), jnp.bfloat16)  # noqa: E731
    assert paged_tile(sds(192), 1, sds(128)) == (12, 1) and paged_tile(sds(192), 1) == (6, 1)  # the tile is sized by K + V as wide as each is
    work = paged_work_list(pos, live, page_len, P, paged_tile(k, P, v)[1], window)
    got = np.asarray(flash_decode_paged(q, k, v, table, pos, work=work, window=window, sink=sink))
    assert got.shape == (B, H, 1, dv) and np.abs(got[2]).max() == 0
    for b in (0, 1):
        kp = np.arange(max(int(pos[b]) - window + 1, 0) if window else 0, int(pos[b]) + 1)
        pages = np.asarray(table)[b, kp // page_len]
        want = _lse_reference(np.asarray(q)[b, :, 0], np.asarray(k)[pages, :, kp % page_len], np.asarray(v)[pages, :, kp % page_len],
                              None if sink is None else np.asarray(sink))
        np.testing.assert_allclose(got[b, :, 0], want, atol=1e-4)
    if window:  # the jnp form of the window layers' decode is the same function
        np.testing.assert_allclose(np.asarray(inf.window_cache_attention(q, k, v, table, pos, window, use_kernel=False, sink=sink))[:2],
                                   got[:2], atol=1e-4)


def test_flash_chunk_paged_with_keys_192_wide_in_the_lanes_form_and_values_128_is_the_jnp_walk():
    B, H, Hkv, d, dv, page_len, P, T = 2, 8, 2, 192, 128, 128, 4, 128
    rng = np.random.default_rng(6)
    k = jnp.asarray(rng.standard_normal((1 + B * P, Hkv, page_len, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1 + B * P, Hkv, page_len, dv)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, T, d)), jnp.float32)
    table = jnp.asarray(1 + np.arange(B * P, dtype=np.int32).reshape(B, P))
    pos = jnp.asarray([0, 256], jnp.int32)
    got = np.asarray(flash_chunk_paged(q, k, v, table, pos, tile=(128, 2)))
    want = np.asarray(inf.paged_chunk_attention(q, k, v, table, pos, use_kernel=False))
    assert got.shape == want.shape == (B, H, T, dv)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_window_chunk_with_a_sink_is_the_decode_form_a_position_at_a_time():
    B, H, Hkv, d, dv, page_len, window, P, T = 1, 8, 4, 24, 16, 8, 6, 8, 16
    rng = np.random.default_rng(8)
    R = inf.ring_pages_for(window, page_len)
    wk = jnp.asarray(rng.standard_normal((1 + R, Hkv, page_len, d)), jnp.float32)
    wv = jnp.asarray(rng.standard_normal((1 + R, Hkv, page_len, dv)), jnp.float32)
    ring = inf.ring_table(jnp.asarray([0]), R, P)
    q = jnp.asarray(rng.standard_normal((B, H, T, d)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((B, Hkv, T, d)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((B, Hkv, T, dv)), jnp.float32)
    sink = jnp.asarray(rng.standard_normal((H,)) + 1.0, jnp.float32)
    pos = jnp.asarray([16], jnp.int32)  # the ring holds the chunk before this one
    got = np.asarray(inf.window_chunk_attention(q, kk, vv, wk, wv, ring, pos, window, sink=sink))
    assert got.shape == (B, H, T, dv)
    wk2 = inf.ring_chunk_write(wk[None], 0, kk, ring, pos, jnp.asarray([T]), R)[0]
    wv2 = inf.ring_chunk_write(wv[None], 0, vv, ring, pos, jnp.asarray([T]), R)[0]
    for t in (T - 1, T - 4):  # positions whose whole window the ring still holds after the chunk's write
        one = np.asarray(inf.window_cache_attention(q[:, :, t:t + 1], wk2, wv2, ring, pos + t, window, use_kernel=False, sink=sink))
        np.testing.assert_allclose(got[:, :, t], one[:, :, 0], atol=1e-5)
    without = np.asarray(inf.window_chunk_attention(q, kk, vv, wk, wv, ring, pos, window))
    assert np.abs(without - got).max() > 1e-2  # the column takes mass


def test_without_a_sink_and_with_one_width_the_paged_calls_trace_what_they_traced():
    """``sink=None`` and ``d_v == d`` are Python's branches: the jaxpr of a call names no sink operand and the parent's shapes."""
    B, H, d, page_len, P = 2, 4, 128, 128, 2
    k = jnp.zeros((1 + B * P, 2, page_len, d), jnp.bfloat16)
    q = jnp.zeros((B, H, 1, d), jnp.bfloat16)
    table = jnp.asarray(1 + np.arange(B * P, dtype=np.int32).reshape(B, P))
    pos = jnp.asarray([3, 200], jnp.int32)
    plain = str(jax.make_jaxpr(lambda *a: flash_decode_paged(*a, interpret=False))(q, k, k, table, pos))
    sunk = str(jax.make_jaxpr(lambda *a: flash_decode_paged(*a, interpret=False, sink=jnp.zeros((H,))))(q, k, k, table, pos))
    assert plain.count("f32[4,1]") < sunk.count("f32[4,1]") and "name=flash_decode_paged" in plain
