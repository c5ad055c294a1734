"""Laguna at the tiny size on the CPU, seeded weights: the program (its
forward on two page groups — pages by length for the full layers, a ring
of pages a slot for the window layers — its rotary by kind, its routing,
its share) against ``benchmark/reference_laguna.py``, and the window
layers' decode kernel in interpret mode."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_laguna as W
from benchmark.reference_laguna import Reference
from deepspeed_tpu.models import laguna as lg
from deepspeed_tpu.ops.kernels.flash_decode import flash_decode_paged, paged_tile, paged_work_list
from deepspeed_tpu.ops.transformer import inference as inf

FULL, SLIDING = lg.FULL, lg.SLIDING
# the published key set at a tiny size: a period and a layer, 4 / 6 query heads on 2 KV heads (the published 6 : 9 a KV
# head at a third), a window of 8, a dense first layer, 16 experts top-4 + a shared one
HF = {"model_type": "laguna", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 5,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "max_position_embeddings": 4096, "attention_bias": False,
      "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
      "shared_expert_intermediate_size": 32, "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
      "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 8,
      "rope_parameters": {FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 8, "original_max_position_embeddings": 64,
                                 "beta_slow": 1, "beta_fast": 32, "attention_factor": 1.2, "partial_rotary_factor": 0.5},
                          SLIDING: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
      "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL], "moe_apply_router_weight_on_input": False,
      "mlp_layer_types": ["dense"] + ["sparse"] * 4, "gating_types": ["per_head"] * 5, "moe_routed_scaling_factor": 2.5,
      "num_attention_heads_per_layer": [4, 6, 6, 6, 4], "moe_router_logit_softcapping": 0}
SEED = 2 ** 31 + 9
SLOTS, PAGES_PER_SLOT, PAGE_LEN, CHUNK = 4, 24, 4, 16  # a ring of 3 pages = 12 positions: a chunk of 16 laps it


def _program(dims):
    cfg = lg.LagunaConfig.from_hf(dims, experts_held=dims.get("experts_held"), vocab_held=dims.get("vocab_held"))
    return cfg, W.program_params(SEED, dims, jnp.float32)


class _Cache:
    """Both page groups of SLOTS slots, each slot's full-attention pages its own."""

    def __init__(self, cfg):
        kind = lg.cache_kind(cfg, jnp.float32)
        self.k, self.v = kind.buffers(cfg.n_layer, 1 + SLOTS * PAGES_PER_SLOT, PAGE_LEN)
        self.state = kind.state_buffers(SLOTS, PAGE_LEN, CHUNK)
        self.ring_pages = kind.ring_pages(PAGE_LEN)
        self.tables = jnp.asarray(1 + np.arange(SLOTS * PAGES_PER_SLOT, dtype=np.int32).reshape(SLOTS, PAGES_PER_SLOT))


def _prefill(cfg, params, cache, slot, toks):
    """Chunk by chunk, the last chunk padded; returns the logits at the prompt's last token."""
    for start in range(0, len(toks), CHUNK):
        n = min(CHUNK, len(toks) - start)
        t = np.full((1, CHUNK), 7, np.int32)  # a padded tail of real-looking ids: it must not count, nor lap the ring
        t[0, :n] = toks[start:start + n]
        logits, cache.k, cache.v, cache.state, aux = lg.forward_with_cache(
            params, jnp.asarray(t), cache.k, cache.v, cache.state, jnp.asarray([start], jnp.int32), cfg,
            cache.tables[slot][None], slot=jnp.asarray([slot], jnp.int32),
            row_valid=jnp.asarray((np.arange(CHUNK) < n)[None]), take=jnp.asarray([n - 1], jnp.int32))
    return np.asarray(logits)[0], aux


def _decode(cfg, params, cache, feed):
    """One decode step: ``feed`` maps slot -> (token, position); the other rows do not decode."""
    t, pos, mask = np.full((SLOTS, 1), 3, np.int32), np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), bool)
    for s, (tok, p) in feed.items():
        t[s, 0], pos[s], mask[s] = tok, p, True
    logits, cache.k, cache.v, cache.state, aux = lg.forward_with_cache(
        params, jnp.asarray(t), cache.k, cache.v, cache.state, jnp.asarray(pos), cfg, cache.tables,
        write_mask=jnp.asarray(mask), row_valid=jnp.asarray(mask[:, None]))
    return np.asarray(logits), aux


# contexts of 3 rings and more (a ring is 12 positions): the ring laps inside a chunk and across chunks; a prompt that ends on a
# chunk boundary, one with a tail of one token, one shorter than the window, one shorter than a page
@pytest.mark.parametrize("share,n_prompt", [(None, 53), ((4, 8), 53), (None, 48), (None, 33), (None, 37), (None, 5), (None, 3)])
def test_chunked_prefill_then_decode_through_both_groups_is_the_references_full_forward(share, n_prompt):
    dims = dict(HF) if share is None else {**HF, "experts_held": list(share), "vocab_held": 128}
    cfg, params = _program(dims)
    toks = np.random.default_rng(0).integers(1, 128, n_prompt + 14, dtype=np.int32)
    want = np.asarray(Reference(dims, SEED).logits(toks[None])[0])
    cache = _Cache(cfg)
    assert cache.ring_pages == 3 and cache.state["wk"].shape == (3, 1 + SLOTS * 3, 2, PAGE_LEN, 16)
    with jax.default_matmul_precision("highest"):
        got, aux = _prefill(cfg, params, cache, 2, toks[:n_prompt])
        np.testing.assert_allclose(got, want[n_prompt - 1], atol=2e-4)
        assert aux.shape == (4, cfg.held[1] + 1) and int(aux[:, :-1].sum()) == int(aux[:, -1].sum())
        for i in range(n_prompt, n_prompt + 14):  # decode past a lap of the ring, the other three rows not decoding
            logits, _ = _decode(cfg, params, cache, {2: (toks[i], i)})
            np.testing.assert_allclose(logits[2], want[i], atol=2e-4)


def test_two_slots_decode_side_by_side_and_a_slot_is_reused_without_a_reset():
    cfg, params = _program(HF)
    rng = np.random.default_rng(1)
    a, b, c = (rng.integers(1, 256, n, dtype=np.int32) for n in (41, 22, 30))
    ref = Reference(HF, SEED)
    cache = _Cache(cfg)
    with jax.default_matmul_precision("highest"):
        _prefill(cfg, params, cache, 0, a[:35])
        _prefill(cfg, params, cache, 3, b[:17])
        for i in range(5):
            logits, _ = _decode(cfg, params, cache, {0: (a[35 + i], 35 + i), 3: (b[17 + i], 17 + i)})
        np.testing.assert_allclose(logits[0], np.asarray(ref.logits(a[None, :40])[0])[39], atol=2e-4)
        np.testing.assert_allclose(logits[3], np.asarray(ref.logits(b[None, :22])[0])[21], atol=2e-4)
        # slot 0 goes to a new, shorter request: what the old one left in its ring and pages is outside every mask
        got, _ = _prefill(cfg, params, cache, 0, c[:21])
        np.testing.assert_allclose(got, np.asarray(ref.logits(c[None, :21])[0])[20], atol=2e-4)
        logits, _ = _decode(cfg, params, cache, {0: (c[21], 21)})
        np.testing.assert_allclose(logits[0], np.asarray(ref.logits(c[None, :22])[0])[21], atol=2e-4)


def test_the_eight_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_expert_layer():
    ref = Reference(HF, SEED)
    toks = np.random.default_rng(2).integers(1, 256, 24, dtype=np.int32)
    keep = []
    ref.hidden(toks, keep=keep)
    h = keep[2]  # the input of sparse layer 2's feed-forward
    with jax.default_matmul_precision("highest"):
        whole, shared = ref.moe_parts(2, h, held=(0, 16))
        parts = [ref.moe_parts(2, h, held=(2 * i, 2))[0] for i in range(8)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=1e-5)
    assert float(jnp.abs(whole).max()) > 1e-3 and float(jnp.abs(shared).max()) > 1e-4
    # ... and the program's held-expert call on a share is that share's part
    from deepspeed_tpu.moe.layer import dropless_held_experts, softmax_topk

    dims = {**HF, "experts_held": [4, 2]}
    cfg, params = _program(dims)
    lp = params["layers"][2]
    with jax.default_matmul_precision("highest"):
        x = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + 1e-6)
        idx, w = softmax_topk(x @ lp["router"], 4, True)
        got, counts = dropless_held_experts(x, idx, w * 2.5, lp["experts_gu"], lp["experts_down"], cfg.held)
    np.testing.assert_allclose(np.asarray(got), np.asarray(parts[2]), atol=1e-5)
    ridx, rw, _ = ref.routing(2, h)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(ridx), -1))
    np.testing.assert_allclose(np.asarray(rw).sum(-1), 2.5, atol=1e-5)  # renormalised, times the scaling factor
    assert int(counts[-1]) == int(np.isin(np.asarray(idx), (4, 5)).sum())


def test_rotary_by_kind_a_full_layer_rotates_half_a_head_under_yarn_and_scales_cos_and_sin():
    cfg = lg.LagunaConfig.from_hf(HF)
    assert (cfg.rotary_dim(FULL), cfg.rotary_dim(SLIDING)) == (8, 16)
    # the public YaRN rule, written out once more: dim 8, theta 5e5, factor 8, original 64, beta 32 / 1
    base = 500000.0 ** (-np.arange(0, 8, 2) / 8)
    turns = lambda beta: 8 * math.log(64 / (beta * 2 * math.pi)) / (2 * math.log(500000.0))  # noqa: E731
    lo, hi = max(math.floor(turns(32)), 0), min(math.ceil(turns(1)), 7)
    ramp = np.clip((np.arange(4) - lo) / max(hi - lo, 1e-3), 0, 1)
    np.testing.assert_allclose(lg.inv_freq(cfg, FULL), base / 8 * ramp + base * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(lg.inv_freq(cfg, SLIDING), 10000.0 ** (-np.arange(0, 16, 2) / 16), rtol=1e-6)
    pos = jnp.asarray([[0, 5, 70]])
    cos, sin = lg.rope_cos_sin(cfg, FULL, pos)
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), 1.2 ** 2, rtol=1e-5)  # attention_factor on both
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 3, 16)), jnp.float32)
    y = lg.rotate(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(y[..., 8:]), np.asarray(x[..., 8:]))  # the unrotated half passes
    np.testing.assert_allclose(np.asarray(y[0, 0, :8]), 1.2 * np.asarray(x[0, 0, :8]), rtol=1e-6)  # position 0: the factor alone
    # the reference's rotary is the same function
    from benchmark import reference_laguna as R

    for kind in (FULL, SLIDING):
        np.testing.assert_allclose(R.inv_freq(HF, kind), lg.inv_freq(cfg, kind), rtol=1e-6)


def test_from_hf_reads_the_catalogs_config_verbatim():
    row = next(r for r in map(json.loads, open("/opt/skills/guides/model-configs/architectures.jsonl")) if r.get("name") == "Laguna-S-2.1")
    cfg = lg.LagunaConfig.from_hf(row["config"])
    assert cfg == lg.LagunaConfig()  # the defaults are the published model
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads, cfg.sliding_window) == (48, 3072, 128, 8, 512)
    assert len(cfg.full_layers) == 12 and len(cfg.sliding_layers) == 36 and cfg.full_layers[:3] == (0, 4, 8)
    assert {cfg.num_attention_heads_per_layer[l] for l in cfg.full_layers} == {48}
    assert {cfg.num_attention_heads_per_layer[l] for l in cfg.sliding_layers} == {72}
    assert cfg.mlp_layer_types[0] == "dense" and set(cfg.mlp_layer_types[1:]) == {"sparse"}
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size) == (256, 10, 1024, 1024)
    assert (cfg.full_rope_theta, cfg.full_rope_factor, cfg.full_rope_original_max_position_embeddings) == (500000, 128, 8192)
    assert abs(cfg.full_rope_attention_factor - 1.4852030263919618) < 1e-12 and cfg.rotary_dim(FULL) == 64 and cfg.rotary_dim(SLIDING) == 128
    assert cfg.moe_routed_scaling_factor == 2.5 and cfg.intermediate_size == 12288 and cfg.vocab_size == 100352
    # the stage-0 share: the per-layer lists keep their first twelve entries
    cut = lg.LagunaConfig.from_hf(row["config"], num_hidden_layers=12, experts_held=(0, 32), vocab_held=12544)
    assert cut.full_layers == (0, 4, 8) and len(cut.sliding_layers) == 9 and cut.held == (0, 32) and cut.vocab_rows == 12544
    shapes = lg.param_shapes(cut)
    assert shapes["layers"][0]["qkv"] == (3072, (48 + 16) * 128) and shapes["layers"][1]["qkv"] == (3072, (72 + 16) * 128)
    assert shapes["layers"][1]["gate"] == (3072, 72) and shapes["layers"][1]["o"] == (72 * 128, 3072)
    assert "mlp_gu" in shapes["layers"][0] and shapes["layers"][1]["experts_gu"] == (32, 3072, 2048) and shapes["layers"][1]["router"] == (3072, 256)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(shapes, is_leaf=lambda s: isinstance(s, tuple)))
    assert abs(n - 4325.5e6) < 0.1e6  # ISSUE 52's table: 8.65 GB in bf16
    kind = lg.cache_kind(cut, jnp.bfloat16)
    assert (kind.paged_layers, kind.window_layers, kind.window) == (3, 9, 512)


@pytest.mark.parametrize("key,value", [
    ("gating", "per-element"), ("gating_types", ["per_head"] * 4 + ["none"]), ("moe_router_logit_softcapping", 30.0),
    ("moe_apply_router_weight_on_input", True), ("tie_word_embeddings", True), ("attention_bias", True), ("decoder_sparse_step", 2),
    ("rope_parameters", {FULL: {"rope_type": "llama3"}, SLIDING: {"rope_type": "default"}}),
    ("rope_parameters", {FULL: {"rope_type": "yarn"}, SLIDING: {"rope_type": "linear"}}),
])
def test_from_hf_refuses_what_the_family_does_not_compute(key, value):
    with pytest.raises(ValueError, match="not implemented"):
        lg.LagunaConfig.from_hf({**HF, key: value})


@pytest.mark.parametrize("bad", [
    {"layer_types": [FULL, "chunked_attention", SLIDING, SLIDING, FULL]}, {"num_attention_heads_per_layer": [4, 5, 6, 6, 4]},
    {"mlp_layer_types": ["dense"] * 4}, {"experts_held": (12, 8)}, {"vocab_held": 0},
])
def test_a_config_that_does_not_hold_together_is_refused(bad):
    with pytest.raises(ValueError):
        lg.LagunaConfig.from_hf({**HF, **{k: v for k, v in bad.items() if k not in ("experts_held", "vocab_held")}},
                                **{k: v for k, v in bad.items() if k in ("experts_held", "vocab_held")})


# ---------------------------------------------------------------------------
# the window layers' decode: its work list, its kernel (interpret mode), its ring
# ---------------------------------------------------------------------------

def test_the_window_work_list_holds_only_the_rings_spans_and_a_row_that_does_not_decode_costs_no_item():
    page_len, P, window = 128, 16, 512
    pos = jnp.asarray([100, 511, 512, 1999, 700], jnp.int32)
    live = jnp.asarray([True, True, True, True, False])
    for span in (1, 2):
        slot, at, n, seen = paged_work_list(pos, live, page_len, P, span, window)
        items = [(int(s), int(a)) for s, a in zip(np.asarray(slot)[: int(n[0])], np.asarray(at)[: int(n[0])])]
        want = [(b, s) for b, p in enumerate(np.asarray(pos)) if bool(live[b])
                for s in range(max(int(p) - window + 1, 0) // (page_len * span), int(p) // (page_len * span) + 1)]
        assert items == want and np.array_equal(np.asarray(seen), np.asarray(live))
        assert all(b != 4 for b, _ in items)  # the row that does not decode
        assert max(sum(1 for b, _ in items if b == r) for r in range(4)) <= -(-(window - 1) // (page_len * span)) + 1
    # without a window the list is what it was: every filled span
    slot, at, n, _ = paged_work_list(pos, live, page_len, P, 1)
    assert int(n[0]) == sum(int(p) // page_len + 1 for p in np.asarray(pos)[:4])
    # a row with fill < window reads what it has: spans 0 .. pos
    slot, at, n, _ = paged_work_list(jnp.asarray([300], jnp.int32), None, page_len, P, 1, window)
    assert [int(a) for a in np.asarray(at)[: int(n[0])]] == [0, 1, 2]


@pytest.mark.parametrize("heads,kv_heads", [(18, 2), (12, 2)])  # 9 and 6 query heads a KV head: the published groups
def test_swa_decode_paged_on_a_lapped_ring_is_the_jnp_form(heads, kv_heads):
    B, d, page_len, window, P = 4, 128, 128, 300, 16
    R = inf.ring_pages_for(window, page_len)
    assert R == 4
    rng = np.random.default_rng(0)
    wk, wv = (jnp.asarray(rng.standard_normal((1 + B * R, kv_heads, page_len, d)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, heads, 1, d)), jnp.float32)
    ring = inf.ring_table(jnp.arange(B), R, P)
    assert ring.shape == (B, P) and int(ring[1, 0]) == 1 + R and int(ring[1, R]) == 1 + R and int(ring.min()) == 1  # page 0 is garbage
    pos = jnp.asarray([5, 700, 1300, 2047], jnp.int32)  # before a window is full; laps of the ring; the slot's last position
    live = jnp.asarray([True, True, False, True])
    work = paged_work_list(pos, live, page_len, P, paged_tile(wk, P)[1], window)
    got = np.asarray(inf.window_cache_attention(q, wk, wv, ring, pos, window, use_kernel=True, work=work))
    want = np.asarray(inf.window_cache_attention(q, wk, wv, ring, pos, window, use_kernel=False))
    np.testing.assert_allclose(got[[0, 1, 3]], want[[0, 1, 3]], atol=1e-4)
    assert np.abs(got[2]).max() == 0  # rows no item visits read 0
    # the jnp form against the positions written out: row 1 at 700 attends 401 .. 700, which lie in logical pages 3, 4, 5
    kp = np.arange(401, 701)
    pages = np.asarray(ring)[1, kp // page_len]
    K, V = np.asarray(wk)[pages, :, kp % page_len], np.asarray(wv)[pages, :, kp % page_len]  # (300, kv_heads, d)
    G = heads // kv_heads
    s = np.einsum("hgd,shd->hgs", np.asarray(q)[1, :, 0].reshape(kv_heads, G, d), K) / np.sqrt(d)
    p = np.exp(s - s.max(-1, keepdims=True))
    np.testing.assert_allclose(want[1, :, 0], np.einsum("hgs,shd->hgd", p / p.sum(-1, keepdims=True), V).reshape(heads, d), atol=1e-4)


def test_flash_decode_paged_without_a_window_is_untouched_by_the_window_form():
    B, H, d, page_len, P = 3, 4, 128, 128, 4
    rng = np.random.default_rng(5)
    k, v = (jnp.asarray(rng.standard_normal((1 + B * P, 2, page_len, d)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.float32)
    table = jnp.asarray(1 + np.arange(B * P, dtype=np.int32).reshape(B, P))
    pos = jnp.asarray([0, 130, 511], jnp.int32)
    full = np.asarray(flash_decode_paged(q, k, v, table, pos))
    np.testing.assert_allclose(full, np.asarray(inf.paged_cache_attention(q, k, v, table, pos, use_kernel=False)), atol=1e-4)
    # a window as long as the slot is full attention
    np.testing.assert_allclose(np.asarray(flash_decode_paged(q, k, v, table, pos, window=P * page_len)), full, atol=1e-5)


def test_a_chunks_write_keeps_the_rings_last_pages_up_to_the_last_real_token():
    page_len, R, T, P = 4, 3, 16, 12
    pool = jnp.zeros((2, 1 + 2 * R, 1, page_len, 2), jnp.float32)
    ring = inf.ring_table(jnp.asarray([1]), R, P)
    rows = jnp.broadcast_to(jnp.arange(1, T + 1, dtype=jnp.float32)[None, None, :, None], (1, 1, T, 2))
    # a full chunk at position 16: logical pages 4..7, the ring keeps 5, 6, 7 -> ring pages 2, 0, 1 of slot 1
    out = np.asarray(inf.ring_chunk_write(pool, 1, rows, ring, jnp.asarray([16]), jnp.asarray([16]), R))
    assert [int(out[1, 1 + R + j, 0, 0, 0]) for j in range(R)] == [9, 13, 5] and np.abs(out[0]).max() == 0
    # 6 real tokens: the last real page is logical 5; pages 4 and 5 are kept (3 was an earlier chunk's), 6 and 7 go to the garbage page
    out = np.asarray(inf.ring_chunk_write(pool, 1, rows, ring, jnp.asarray([16]), jnp.asarray([6]), R))
    assert [int(out[1, 1 + R + j, 0, 0, 0]) for j in range(R)] == [0, 1, 5]
    assert int(out[1, 0, 0, 0, 0]) in (9, 13)  # the garbage page took the padded tail's pages
    assert inf.ring_pages_for(512, 128) == 5 and inf.ring_pages_for(8, 4) == 3 and inf.ring_pages_for(1, 128) == 1 and inf.ring_pages_for(129, 128) == 2
