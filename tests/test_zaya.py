"""ZAYA at the tiny size on the CPU, seeded weights: the program (its
forward on the hybrid cache in which every layer has K/V pages and a
per-slot convolution tail, its router with the carry across layers, its
share) against ``benchmark/reference_zaya1.py``, and the family through
``init_inference`` → ``ServingEngine``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_zaya1 as W
from benchmark.reference_zaya1 import Reference
from deepspeed_tpu.models import zaya
from deepspeed_tpu.moe.layer import dropless_held_experts, mlp_top1

HF = {"model_type": "zaya", "hidden_size": 64, "num_hidden_layers": 3, "layer_types": ["hybrid"] * 3, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256, "moe_intermediate_size": 32, "router_hidden_size": 16,
      "rms_norm_eps": 1e-5, "num_experts": 8, "num_experts_per_tok": 1, "cca_time0": 2, "cca_time1": 2,
      "partial_rotary_factor": 0.5, "rope_parameters": {"hybrid": {"rope_theta": 5000000}}, "tie_word_embeddings": True,
      "sliding_window": None, "max_position_embeddings": 4096}
SEED = 2 ** 31 + 9
SLOTS, PAGES_PER_SLOT, PAGE_LEN, CHUNK = 4, 8, 8, 16


def _program(dims):
    cfg = zaya.ZayaConfig.from_hf(dims, experts_held=dims.get("experts_held"))
    return cfg, W.program_params(SEED, dims, jnp.float32)


class _Cache:
    """The hybrid cache of SLOTS slots, each slot's pages its own."""

    def __init__(self, cfg):
        kind = zaya.cache_kind(cfg, jnp.float32)
        self.k, self.v = kind.buffers(cfg.n_layer, 1 + SLOTS * PAGES_PER_SLOT, PAGE_LEN)
        self.state = kind.state_buffers(SLOTS)
        self.tables = jnp.asarray(1 + np.arange(SLOTS * PAGES_PER_SLOT, dtype=np.int32).reshape(SLOTS, PAGES_PER_SLOT))


def _prefill(cfg, params, cache, slot, toks, forget_tail=False):
    """Chunk by chunk, the last chunk padded; returns the logits at the
    prompt's last token.  ``forget_tail``: the slot's tail is zeroed in
    front of every chunk — the fault the benchmark's second control plants."""
    for start in range(0, len(toks), CHUNK):
        n = min(CHUNK, len(toks) - start)
        t = np.full((1, CHUNK), 7, np.int32)  # a padded tail of real-looking ids: it must not count
        t[0, :n] = toks[start:start + n]
        if forget_tail:
            cache.state = {name: buf.at[:, slot].set(0) for name, buf in cache.state.items()}
        logits, cache.k, cache.v, cache.state, aux = zaya.forward_with_cache(
            params, jnp.asarray(t), cache.k, cache.v, cache.state, jnp.asarray([start], jnp.int32), cfg,
            cache.tables[slot][None], slot=jnp.asarray([slot], jnp.int32),
            row_valid=jnp.asarray((np.arange(CHUNK) < n)[None]), take=jnp.asarray([n - 1], jnp.int32))
    return np.asarray(logits)[0], aux


def _decode(cfg, params, cache, feed):
    """One decode step: ``feed`` maps slot -> (token, position); the other rows do not decode."""
    t, pos, mask = np.full((SLOTS, 1), 3, np.int32), np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), bool)
    for s, (tok, p) in feed.items():
        t[s, 0], pos[s], mask[s] = tok, p, True
    logits, cache.k, cache.v, cache.state, aux = zaya.forward_with_cache(
        params, jnp.asarray(t), cache.k, cache.v, cache.state, jnp.asarray(pos), cfg, cache.tables,
        write_mask=jnp.asarray(mask), row_valid=jnp.asarray(mask[:, None]))
    return np.asarray(logits), aux


# 37 and 5: the chunk of 16 does not divide the prompt (a padded tail, the slot's tail left at the last real token);
# 32: it does (the tail left at the chunk's last row); a share of the experts beside the whole
@pytest.mark.parametrize("share,n_prompt", [(None, 37), ((4, 4), 37), (None, 32), (None, 5)])
def test_chunked_prefill_then_decode_on_the_hybrid_cache_is_the_references_full_forward(share, n_prompt):
    dims = dict(HF) if share is None else {**HF, "experts_held": list(share), "vocab_size": 128}
    cfg, params = _program(dims)
    toks = np.random.default_rng(0).integers(1, 128, n_prompt + 8, dtype=np.int32)
    want = np.asarray(Reference(dims, SEED).logits(toks[None])[0])
    cache = _Cache(cfg)
    with jax.default_matmul_precision("highest"):
        got, aux = _prefill(cfg, params, cache, 2, toks[:n_prompt])  # across chunk boundaries, a padded tail
        np.testing.assert_allclose(got, want[n_prompt - 1], atol=2e-5)
        assert aux.shape == (3, cfg.held[1] + 1) and int(aux[:, :-1].sum()) == int(aux[:, -1].sum())
        assert int(aux[:, -1].max()) <= min(CHUNK, n_prompt)  # the padded tail is not counted
        for i in range(n_prompt, n_prompt + 8):  # decode, the other three rows not decoding
            logits, _ = _decode(cfg, params, cache, {2: (toks[i], i)})
            np.testing.assert_allclose(logits[2], want[i], atol=2e-5)


def test_a_tail_zeroed_at_chunk_starts_is_not_the_references_forward():
    """The same comparison with the slot's convolution tail and value
    shift forgotten in front of every chunk: positions c, c + 1 mix zeros
    where the chunk before ended, the error rides the cache from there on,
    and the tight tolerance of the test above refuses it."""
    cfg, params = _program(dict(HF))
    toks = np.random.default_rng(0).integers(1, 128, 45, dtype=np.int32)
    want = np.asarray(Reference(dict(HF), SEED).logits(toks[None])[0])
    cache = _Cache(cfg)
    with jax.default_matmul_precision("highest"):
        got, _ = _prefill(cfg, params, cache, 2, toks[:37], forget_tail=True)
        assert np.abs(got - want[36]).max() > 1e-3
        # one chunk only: nothing was there to forget, and the program is the reference again
        cache = _Cache(cfg)
        got, _ = _prefill(cfg, params, cache, 2, toks[:13], forget_tail=True)
        np.testing.assert_allclose(got, want[12], atol=2e-5)


def test_a_slot_reused_by_a_second_request_needs_no_reset():
    """Slot 1 serves request A, then — without any reset from outside —
    request B, while slot 3 decodes request C throughout: B's logits are
    those of B alone (its tail read as zero at position 0, nothing of A
    leaks), and C never notices."""
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
            np.testing.assert_allclose(logits[3], want_c[18 + i], atol=2e-5)
        assert float(jnp.abs(cache.state["conv"][:, 1]).max()) > 0 and float(jnp.abs(cache.state["vshift"][:, 1]).max()) > 0
        got, _ = _prefill(cfg, params, cache, 1, b[:17])  # B takes the slot: A's tail stands there, and is not read
        np.testing.assert_allclose(got, want_b[16], atol=2e-5)
        for i in range(5):
            logits, _ = _decode(cfg, params, cache, {1: (b[17 + i], 17 + i), 3: (c[22 + i], 22 + i)})
            np.testing.assert_allclose(logits[1], want_b[17 + i], atol=2e-5)
            np.testing.assert_allclose(logits[3], want_c[22 + i], atol=2e-5)
        # a step in which slot 1 does not decode leaves its tail and its pages' content alone
        before = {name: np.asarray(buf[:, 1]) for name, buf in cache.state.items()}
        page = np.asarray(cache.k[:, int(cache.tables[1, 2])])
        _decode(cfg, params, cache, {3: (c[27], 27)})
        assert all(np.array_equal(before[name], np.asarray(buf[:, 1])) for name, buf in cache.state.items())
        assert np.array_equal(page, np.asarray(cache.k[:, int(cache.tables[1, 2])]))


def test_cache_rows_are_the_references_mixed_keys_and_shifted_values():
    """What the pages hold after a chunked prefill and two decode steps:
    the reference's mixed, normalised, rotated ``k`` and shifted ``v`` of
    every position — the benchmark's ``kv_boundary_rel_err`` at all of them."""
    cfg, params = _program(dict(HF))
    toks = np.random.default_rng(6).integers(1, 256, 39, dtype=np.int32)
    kv = []
    Reference(dict(HF), SEED).hidden(toks, kv_at=np.arange(39), kv=kv)
    cache = _Cache(cfg)
    with jax.default_matmul_precision("highest"):
        _prefill(cfg, params, cache, 0, toks[:37])
        for i in (37, 38):
            _decode(cfg, params, cache, {0: (toks[i], i)})
    pages, offs = np.asarray(cache.tables[0])[np.arange(39) // PAGE_LEN], np.arange(39) % PAGE_LEN
    for layer, (k, v) in enumerate(kv):
        # k rows are sqrt(d) * tau long (up to 32 here): float32 steps of their own size
        np.testing.assert_allclose(np.asarray(cache.k[layer])[pages, :, offs], k, atol=1e-4)
        np.testing.assert_allclose(np.asarray(cache.v[layer])[pages, :, offs], v, atol=2e-5)


def test_mlp_top1_is_the_references_router_with_the_carry_across_layers():
    dims = dict(HF)
    ref, key = Reference(dims, SEED), W.seed_key(SEED)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 64)), jnp.float32)
    r_ref = r = jnp.zeros((40, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for layer in range(3):  # the same input to every layer's router: only the carry differs
            e, s, r_ref = ref.routing(layer, x, r_ref)
            h = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-5)
            idx, w, r = mlp_top1(h, r, W.router_params(key, layer, dims), 1e-5)
            assert idx.shape == (40, 1) and idx.dtype == jnp.int32 and np.array_equal(np.asarray(idx)[:, 0], np.asarray(e))
            np.testing.assert_allclose(np.asarray(w)[:, 0], np.asarray(s), atol=1e-6)
            np.testing.assert_allclose(np.asarray(r), np.asarray(r_ref), atol=1e-6)
        # the carry matters: layer 2's router without it is another router
        _, _, lone = mlp_top1(h, jnp.zeros_like(r), W.router_params(key, 2, dims), 1e-5)
        assert float(jnp.abs(lone - r).max()) > 1e-3
    # the bias selects and never weighs; the weight is the chosen expert's softmax score, over all experts
    rp = {**W.router_params(key, 0, dims), "router_bias": jnp.zeros((8,)).at[7].set(10.0)}
    idx, w, _ = mlp_top1(h, jnp.zeros_like(r), rp, 1e-5)
    _, w0, _ = mlp_top1(h, jnp.zeros_like(r), W.router_params(key, 0, dims), 1e-5)
    assert (np.asarray(idx) == 7).all() and (np.asarray(w) <= np.asarray(w0) + 1e-7).all() and float(w.max()) < 1.0
    # bf16 inputs: everything after the input is float32 still
    idx16, w16, r16 = mlp_top1(h.astype(jnp.bfloat16), jnp.zeros_like(r), jax.tree.map(lambda a: a.astype(jnp.bfloat16), rp), 1e-5)
    assert w16.dtype == jnp.float32 and r16.dtype == jnp.float32


def test_the_two_expert_shares_add_up_to_the_uncut_layer():
    dims = {**HF, "num_experts": 16}
    ref, key = Reference(dims, SEED), W.seed_key(SEED)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((48, 64)), jnp.float32)
    r_prev = jnp.asarray(np.random.default_rng(4).standard_normal((48, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, r_whole = ref.moe_part(1, x, r_prev, held=(0, 16))
        parts = [ref.moe_part(1, x, r_prev, held=(8 * rank, 8)) for rank in range(2)]
        np.testing.assert_allclose(np.asarray(parts[0][0] + parts[1][0]), np.asarray(whole), atol=1e-6)
        assert all(np.array_equal(np.asarray(r), np.asarray(r_whole)) for _, r in parts)  # every chip computes the router alike
        assert float(jnp.abs(parts[0][0]).max()) > 0 and float(jnp.abs(parts[1][0]).max()) > 0
        # the program's share equals the reference's share, rank by rank; no assignment dropped
        h = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-5)
        idx, w, _ = mlp_top1(h, r_prev, W.router_params(key, 1, dims), 1e-5)
        routed = 0
        for rank in range(2):
            ex = [W.expert_params(key, 1, 8 * rank + e, dims) for e in range(8)]
            out, counts = dropless_held_experts(h, idx, w, jnp.stack([e["gu"] for e in ex]), jnp.stack([e["down"] for e in ex]),
                                                (8 * rank, 8))
            np.testing.assert_allclose(np.asarray(out), np.asarray(parts[rank][0]), atol=1e-6)
            assert int(counts[:-1].sum()) == int(counts[-1])
            routed += int(counts[-1])
        assert routed == 48  # top-1: every token takes exactly one expert, on one chip or the other


def test_from_hf_takes_the_published_keys_and_refuses_what_is_not_implemented():
    cfg = zaya.ZayaConfig.from_hf(HF, experts_held=[4, 4], vocab_held=128)
    assert (cfg.num_hidden_layers, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (3, 4, 2, 16)
    assert cfg.rope_theta == 5e6 and cfg.held == (4, 4) and cfg.vocab_rows == 128 and cfg.n_layer == 3
    sz = cfg.cca
    assert (sz.group, sz.channels, sz.shift_width, sz.rotary_dim) == (2, 96, 16, 8)
    for bad in ({"layer_types": ["hybrid", "hybrid_sliding", "hybrid"]}, {"sliding_window": 4096}, {"num_experts_per_tok": 2},
                {"cca_time0": 4}, {"tie_word_embeddings": False}, {"attention_bias": True}, {"hidden_act": "gelu"}):
        with pytest.raises(ValueError, match="not implemented"):
            zaya.ZayaConfig.from_hf({**HF, **bad})
    with pytest.raises(ValueError, match="experts_held"):
        zaya.ZayaConfig.from_hf(HF, experts_held=[6, 4])
    shapes = zaya.param_shapes(cfg)
    assert "head" not in shapes and shapes["embed"] == (128, 64) and shapes["layers"][0]["experts_gu"] == (4, 64, 64)
    assert shapes["layers"][0]["qkv"] == (64, 96 + 2 * 16) and shapes["layers"][0]["conv1"] == (2, 6, 16, 16)
    kind = zaya.cache_kind(cfg, jnp.bfloat16)
    assert kind.paged_layers == 3 and kind.pages_hold_all is False  # every layer has pages, and a page is not all of a position
    assert {name: (layers, shape) for name, (layers, shape, _) in kind.state.items()} == {"conv": (3, (2, 96)), "vshift": (3, (16,))}
    p = zaya.init_params(zaya.ZAYA_TINY, seed=1)
    assert not p["layers"][1]["router_bias"].any() and 3.0 <= p["layers"][1]["tau"].min() and p["layers"][1]["tau"].max() <= 4.0
    assert np.abs(p["layers"][0]["res_attn"][0] - 1).max() < 0.5 and np.abs(p["layers"][0]["res_attn"][0] - 1).max() > 0  # near the identity, not at it
    assert np.abs(p["layers"][1]["router_w3"].mean(0)).max() < 1e-6 < np.abs(p["layers"][1]["router_w1"].mean(0)).max()  # centred over the fan-in


@pytest.fixture(scope="module")
def served():
    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine

    inf = deepspeed_tpu.init_inference(model_config=zaya.ZAYA_TINY, dtype=jnp.float32, max_out_tokens=128, seed=3)
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
    assert st["moe"]["dropped_assignments"] == 0 and np.asarray(st["moe"]["tokens_per_expert"]).shape == (3, 8)
    hy = st["hybrid"]
    assert hy["state_bytes"] == srv.pool.state_bytes() == 3 * 3 * (2 * 96 + 16) * 4 and hy["state_resets_in_program"] == 7
    assert 1.0 <= hy["decode_rows_updated_mean"] <= 3.0
    assert st["cca_prefill_form"].startswith("blockwise") and st["moe_router_form"].startswith("mlp_top1")
    assert st["cca_decode_kernel"] is False and "not armed" in st["cca_decode_fallback"] and "not armed" in st["moe_grouped_fallback"]
    kv = st["kvcache"]
    assert kv["reuse"].startswith("off:") and kv["state_leaves"] == {"conv": 3 * 3 * 2 * 96 * 4, "vshift": 3 * 3 * 16 * 4}
    assert "3 of 3 layers" in kv["kind"] and "conv: 3 layers x 2 x 96 float32 + vshift: 3 layers x 16 float32" in kv["kind"]
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
        kind = zaya.cache_kind(cfg, jnp.float32)
        k, v = kind.buffers(cfg.n_layer, 1 + pad // 16, 16)
        state = kind.state_buffers(1)
        t = np.zeros((1, pad), np.int32)
        t[0, :len(seq)] = seq
        table, slot0, pos0 = jnp.arange(1, 1 + pad // 16, dtype=jnp.int32)[None], jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)
        valid = jnp.asarray((np.arange(pad) < len(seq))[None])
        for i, tok in enumerate(gen):
            logits = zaya.forward_with_cache(params, jnp.asarray(t), k, v, state, pos0, cfg, table, slot=slot0, row_valid=valid,
                                             take=jnp.asarray([len(prompt) - 1 + i], jnp.int32))[0]
            assert int(jnp.argmax(logits[0])) == tok or np.asarray(logits)[0].max() - np.asarray(logits)[0, tok] < 1e-4


def test_compiled_step_takes_the_pages_and_the_tail_donated(served):
    srv = served[0]
    for which in ("prefill", "decode"):
        m = srv.compiled_step(which).memory_analysis()
        # K, V and the tail all come back aliased: nothing of the pool is copied
        assert m.alias_size_in_bytes >= srv.pool.cache_bytes()
