"""The Keye family (``deepspeed_tpu/models/keye.py``): chunked prefill then
decode through the three-leaf paged cache against
``benchmark/reference_keye.py``'s full forward — logits, the selected
sets, unequal rotary streams, the sum of the eight expert shares — and the
family through ``ServingEngine``'s normal path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_keye as W
from benchmark.reference_keye import Reference
from deepspeed_tpu.models import keye

HF = {"model_type": "KeyeVL2", "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
      "head_dim": 16, "vocab_size": 256, "moe_intermediate_size": 32, "rms_norm_eps": 1e-6, "num_experts": 8,
      "num_experts_per_tok": 2, "norm_topk_prob": True, "rope_theta": 10000000, "rope_scaling": {"mrope_section": [2, 3, 3]},
      "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2, "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                    "q_chunk_size": 512, "topk": 16},
      "decoder_sparse_step": 1, "mlp_only_layers": [], "tie_word_embeddings": False, "sliding_window": None,
      "max_position_embeddings": 4096}
SEED = 2 ** 31 + 11
SLOTS, PAGES_PER_SLOT, PAGE_LEN, CHUNK = 3, 32, 8, 16


def _program(dims):
    cfg = keye.KeyeConfig.from_hf(dims, experts_held=dims.get("experts_held"))
    return cfg, W.program_params(SEED, dims, jnp.float32)


class _Cache:
    """The three-leaf cache of SLOTS slots, each slot's pages its own, in a shuffled order."""

    def __init__(self, cfg):
        kind = keye.cache_kind(cfg, jnp.float32)
        self.k, self.v = kind.buffers(cfg.n_layer, 1 + SLOTS * PAGES_PER_SLOT, PAGE_LEN)
        pages = 1 + np.random.default_rng(1).permutation(SLOTS * PAGES_PER_SLOT).astype(np.int32)
        self.tables = jnp.asarray(pages.reshape(SLOTS, PAGES_PER_SLOT))


def _prefill(cfg, params, cache, slot, toks, positions3=None, sink=None):
    """Chunk by chunk, the last chunk padded; returns the logits at the prompt's last token."""
    with jax.default_matmul_precision("highest"):
        for start in range(0, len(toks), CHUNK):
            n = min(CHUNK, len(toks) - start)
            t = np.full((1, CHUNK), 7, np.int32)  # a padded tail of real-looking ids: it must not count
            t[0, :n] = toks[start:start + n]
            p3 = None
            if positions3 is not None:
                p3 = np.zeros((3, 1, CHUNK), np.int32)
                p3[:, 0, :n] = positions3[:, start:start + n]
            logits, cache.k, cache.v, aux = keye.forward_with_cache(
                params, jnp.asarray(t), cache.k, cache.v, jnp.asarray([start], jnp.int32), cfg, cache.tables[slot][None],
                row_valid=jnp.asarray((np.arange(CHUNK) < n)[None]), take=jnp.asarray([n - 1], jnp.int32),
                positions3=None if p3 is None else jnp.asarray(p3), selection_sink=sink)
    return np.asarray(logits)[0], aux


def _decode(cfg, params, cache, feed, sink=None):
    """One decode step: ``feed`` maps slot -> (token, position); the other rows do not decode."""
    t, pos, mask = np.full((SLOTS, 1), 3, np.int32), np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), bool)
    for s, (tok, p) in feed.items():
        t[s, 0], pos[s], mask[s] = tok, p, True
    with jax.default_matmul_precision("highest"):
        logits, cache.k, cache.v, aux = keye.forward_with_cache(
            params, jnp.asarray(t), cache.k, cache.v, jnp.asarray(pos), cfg, cache.tables, write_mask=jnp.asarray(mask),
            row_valid=jnp.asarray(mask[:, None]), selection_sink=sink)
    return np.asarray(logits), aux


# contexts on both sides of topk = 16: 8 stays under it (everything selected), 13 + 8 crosses it while decoding,
# 37, 45 and 200 are past it from the second chunk on; 32 is whole chunks; a share of the experts beside the whole
@pytest.mark.parametrize("share,n_prompt", [(None, 8), (None, 13), (None, 37), ((4, 4), 45), (None, 32), (None, 200)])
def test_chunked_prefill_then_decode_on_the_paged_cache_is_the_references_full_forward(share, n_prompt):
    dims = dict(HF) if share is None else {**HF, "experts_held": list(share), "vocab_size": 128}
    cfg, params = _program(dims)
    toks = np.random.default_rng(0).integers(1, 128, n_prompt + 8, dtype=np.int32)
    want = np.asarray(Reference(dims, SEED).logits(toks[None])[0])
    cache, slot = _Cache(cfg), 1
    logits, aux = _prefill(cfg, params, cache, slot, toks[:n_prompt])
    np.testing.assert_allclose(logits, want[n_prompt - 1], rtol=2e-4, atol=2e-5)
    assert aux.shape == (cfg.n_layer, cfg.held[1] + 1) and (np.asarray(aux)[:, :-1].sum(1) == np.asarray(aux)[:, -1]).all()
    for t in range(n_prompt, n_prompt + 8):
        logits, aux = _decode(cfg, params, cache, {slot: (int(toks[t]), t)})
        np.testing.assert_allclose(logits[slot], want[t], rtol=2e-4, atol=2e-5)
        assert (np.asarray(aux)[:, -1] <= cfg.num_experts_per_tok).all()  # one real token: the rows that do not decode are not counted


def test_selected_sets_are_the_references_at_every_position_and_layer():
    cfg, params = _program(HF)
    n_prompt, n = 45, 53
    toks = np.random.default_rng(3).integers(1, 256, n, dtype=np.int32)
    want: list = []
    cuts: list = []
    Reference(HF, SEED).hidden(toks, selected_at=np.arange(n), selected=want, cuts=cuts)  # a layer: (n, n) bool, (n,) the score each was cut at
    cache, slot, sink = _Cache(cfg), 0, []
    _prefill(cfg, params, cache, slot, toks[:n_prompt], sink=sink)
    chunks = -(-n_prompt // CHUNK)
    for l in range(cfg.n_layer):
        got = np.concatenate([np.asarray(sink[c * cfg.n_layer + l][0])[0] for c in range(chunks)])[:n_prompt, :n]
        np.testing.assert_array_equal(got, want[l][:n_prompt])
        assert (got.sum(1) == np.minimum(np.arange(n_prompt) + 1, cfg.select_topk)).all()
        # the score a selection was cut at is the reference's topk-th largest; there is none while fewer than topk positions exist
        cut = np.concatenate([np.asarray(sink[c * cfg.n_layer + l][1])[0] for c in range(chunks)])[:n_prompt]
        full = np.arange(n_prompt) >= cfg.select_topk - 1
        assert np.isnan(cut[~full]).all() and np.isnan(cuts[l][:n_prompt][~full]).all()
        np.testing.assert_allclose(cut[full], cuts[l][:n_prompt][full], rtol=1e-4, atol=1e-6)
    for t in range(n_prompt, n):
        sink = []
        _decode(cfg, params, cache, {slot: (int(toks[t]), t)}, sink=sink)
        for l in range(cfg.n_layer):
            m, cut = (np.asarray(x) for x in sink[l])
            np.testing.assert_array_equal(m[slot, 0, :n], want[l][t])
            np.testing.assert_allclose(cut[slot, 0], cuts[l][t], rtol=1e-4, atol=1e-6)
            assert np.isnan(np.delete(cut[:, 0], slot)).all()
            assert not m[[s for s in range(SLOTS) if s != slot]].any()  # a row that does not decode selects nothing


def test_three_stream_rotary_with_unequal_streams_is_the_references():
    cfg, params = _program(HF)
    n = 40
    rng = np.random.default_rng(4)
    toks = rng.integers(1, 256, n, dtype=np.int32)
    # an image-like stretch: the temporal stream stands still while height and width walk a grid
    streams = np.stack([np.concatenate([np.arange(10), np.full(20, 10), 11 + np.arange(10)]),
                        np.concatenate([np.arange(10), 10 + np.arange(20) // 5, 14 + np.arange(10)]),
                        np.concatenate([np.arange(10), 10 + np.arange(20) % 5, 15 + np.arange(10)])]).astype(np.int32)
    ref = Reference(HF, SEED)
    want = np.asarray(ref.head(ref.hidden(toks, positions3=streams)))
    equal = np.asarray(ref.head(ref.hidden(toks)))
    assert np.abs(want - equal).max() > 1e-3  # the streams matter
    logits, _ = _prefill(cfg, params, _Cache(cfg), 2, toks, positions3=streams)
    np.testing.assert_allclose(logits, want[n - 1], rtol=2e-4, atol=2e-5)


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """A layer's routed output, share by share (one of the eight experts
    each): the program's share is the reference's, and the shares —
    attention, router and norms are what every chip computes alike, counted
    once — sum to the uncut reference's layer."""
    from deepspeed_tpu.models.deepseek_v2 import rms_norm
    from deepspeed_tpu.moe.layer import dropless_held_experts, softmax_topk

    ref = Reference(HF, SEED)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, idx, w = ref.moe(1, x, held=(0, 8))
        total = np.zeros((24, 64), np.float32)
        for first in range(8):
            dims = {**HF, "experts_held": [first, 1]}
            cfg, params = _program(dims)
            lp = params["layers"][1]
            flat = rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
            pi, pw = softmax_topk(jnp.dot(flat, lp["router"], precision=jax.lax.Precision.HIGHEST), 2, True)
            np.testing.assert_array_equal(np.asarray(pi), np.asarray(idx))  # the router is all experts wide on every share
            part, counts = dropless_held_experts(flat, pi, pw, lp["experts_gu"], lp["experts_down"], cfg.held)
            theirs, _, _ = Reference(dims, SEED).moe(1, x)
            np.testing.assert_allclose(np.asarray(part), np.asarray(theirs - x), rtol=2e-4, atol=2e-6)
            assert int(counts[-1]) == int((np.asarray(idx) == first).sum())  # nothing dropped
            total += np.asarray(part)
    np.testing.assert_allclose(total, np.asarray(whole - x), rtol=2e-4, atol=2e-6)


def test_config_from_published_keys_refuses_what_is_not_implemented():
    cfg = keye.KeyeConfig.from_hf(HF, experts_held=[4, 4], vocab_held=128)
    assert (cfg.held, cfg.vocab_rows, cfg.select_topk, cfg.index_n_heads, cfg.index_head_dim, cfg.index_rotary_dim) == ((4, 4), 128, 16, 2, 8, 4)
    assert cfg.mrope_section == (2, 3, 3) and cfg.n_layer == 2 and cfg.n_positions == 4096
    for bad in ({"tie_word_embeddings": True}, {"mlp_only_layers": [0]}, {"decoder_sparse_step": 2}, {"attention_bias": True},
                {"hidden_act": "gelu"}, {"sa_config": {**HF["sa_config"], "indexer_num_kv_heads": 2}}):
        with pytest.raises(ValueError, match="not implemented"):
            keye.KeyeConfig.from_hf({**HF, **bad})
    with pytest.raises(ValueError, match="outside"):
        keye.KeyeConfig.from_hf(HF, experts_held=[6, 4])
    with pytest.raises(ValueError, match="mrope_section"):
        keye.KeyeConfig.from_hf({**HF, "rope_scaling": {"mrope_section": [2, 3, 4]}})
    published = keye.KeyeConfig()  # the defaults are the published model
    assert (published.num_hidden_layers, published.num_experts, published.num_experts_per_tok, published.select_topk) == (48, 128, 8, 2048)
    shapes = keye.param_shapes(cfg)
    assert shapes["head"] == (64, 128) and shapes["layers"][0]["experts_gu"] == (4, 64, 64) and shapes["layers"][0]["router"] == (64, 8)
    assert shapes["layers"][0]["index_q"] == (64, 16) and shapes["layers"][0]["index_k"] == (64, 8) and shapes["layers"][0]["index_w"] == (64, 2)
    p = keye.init_params(keye.KEYE_TINY, seed=1)
    assert (p["layers"][0]["index_k_gain"] == 1).all() and not p["layers"][0]["index_k_bias"].any()


@pytest.fixture(scope="module")
def served():
    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine

    inf = deepspeed_tpu.init_inference(model_config=keye.KEYE_TINY, dtype=jnp.float32, max_out_tokens=128, seed=3)
    srv = ServingEngine(inf, config={"num_slots": 3, "max_len": 128, "prefill_chunk": 16,
                                     "kvcache": {"enabled": True, "page_len": 16}})
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 256, n, dtype=np.int32), m) for n, m in ((20, 6), (37, 9), (5, 4), (50, 7), (16, 5), (33, 8), (3, 3))]
    reqs.append((np.concatenate([reqs[3][0], rng.integers(1, 256, 9, dtype=np.int32)]), 6))  # starts with request 3's whole prompt
    ids = [srv.submit(p, max_new_tokens=m) for p, m in reqs]
    return srv, reqs, ids, srv.drain()


def test_init_inference_serves_the_family_on_the_normal_path(served):
    srv, reqs, ids, done = served
    st = srv.stats()
    assert (st["prefill_compiles"], st["decode_compiles"]) == (1, 1)  # exactly two executables, eight requests over three slots
    assert all(len(done[i].generated) == m for i, (_, m) in zip(ids, reqs))
    assert st["moe"]["dropped_assignments"] == 0 and np.asarray(st["moe"]["tokens_per_expert"]).shape == (2, 8)
    assert st["dsa_select_form"].startswith("threshold by bisection") and st["dsa_prefill_form"].startswith("paged_chunk_attention")
    assert st["dsa_decode_kernel"].startswith("lax") and st["dsa_index_form"].startswith("lax einsum") and st["moe_router_form"].startswith("softmax_topk")
    # a decoding row of fill f could attend f positions and keeps min(f, 16): summed over the decode steps by the host
    fills = [n + g for (p, m) in reqs for n in [len(p)] for g in range(1, m)]
    assert st["dsa_positions_attendable"] == sum(fills) and st["dsa_positions_selected"] == sum(min(f, 16) for f in fills)
    kv = st["kvcache"]
    L, NP = 2, srv.pool.num_pages
    assert kv["page_leaves"] == {"k": L * NP * 2 * 16 * 16 * 4, "v": L * NP * 2 * 16 * 16 * 4, "idx": L * NP * 16 * 8 * 4}
    assert "indexer keys (2 layers" in kv["kind"] and "reuse" not in kv  # prefix reuse stays on for this kind
    assert kv["prefix_hits"] >= 1 and kv["tokens_saved"] >= 32 and st["kv_dtype"] == "float32"
    assert st["pool_bytes"] == srv.pool.cache_bytes() == sum(kv["page_leaves"].values())
    # the walk's counters are read off the K leaf of the three: one block of heads, the slot's eight pages one item
    steps = sum(m - 1 for _, m in reqs)
    assert st["decode_grid_steps"] == steps and st["decode_pages_read"] == 8 * steps >= st["decode_pages_walked"] == sum(
        (f - 1) // 16 + 1 for f in fills)
    assert srv.pool.shape_math().count("float32") == 1


def test_served_tokens_are_the_greedy_tokens_of_a_lone_forward(served):
    """What the engine emitted for a request that shared the pool with
    seven others — one of them on pages it shares with another, indexer
    keys and all — equals a lone chunk-free teacher-forced forward's argmax."""
    srv, reqs, ids, done = served
    cfg, params = srv.engine.model_config, srv.engine.params
    for j in (1, 3, 7):  # slot-sharing, multi-chunk prompts; 7 starts on request 3's pages (a prefix hit)
        prompt, gen = reqs[j][0], done[ids[j]].generated
        seq = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        pad = -(-len(seq) // 16) * 16
        k, v = keye.cache_kind(cfg, jnp.float32).buffers(cfg.n_layer, 1 + pad // 16, 16)
        t = np.zeros((1, pad), np.int32)
        t[0, :len(seq)] = seq
        table = jnp.arange(1, 1 + pad // 16, dtype=jnp.int32)[None]
        for i, tok in enumerate(gen):
            logits = keye.forward_with_cache(params, jnp.asarray(t), k, v, jnp.zeros((1,), jnp.int32), cfg, table,
                                             take=jnp.asarray([len(prompt) - 1 + i], jnp.int32))[0]
            assert int(jnp.argmax(logits[0])) == tok or np.asarray(logits)[0].max() - np.asarray(logits)[0, tok] < 1e-4


def test_the_decode_program_hands_back_what_its_newest_step_selected():
    """``serving_forward`` says ``decode_keeps``: the served decode
    executable returns each layer's selection mask and the score it was
    cut at, and the engine leaves them on the device (``decode_kept``)
    until its next step — what the benchmark's ``correct`` reads, no
    program of its own.  Held here against a lone forward's selection at
    the same position; a family that keeps nothing has ``decode_kept`` None."""
    import deepspeed_tpu
    from deepspeed_tpu.models import deepseek_v2
    from deepspeed_tpu.serving import ServingEngine

    inf = deepspeed_tpu.init_inference(model_config=keye.KEYE_TINY, dtype=jnp.float32, max_out_tokens=128, seed=3)
    srv = ServingEngine(inf, config={"num_slots": 3, "max_len": 128, "prefill_chunk": 16, "kvcache": {"enabled": True, "page_len": 16}})
    assert srv.decode_keeps and srv.decode_kept is None
    prompt = np.random.default_rng(9).integers(1, 256, 41, dtype=np.int32)
    rid = srv.submit(prompt, max_new_tokens=8)
    q = srv.result(rid)
    while len(q.generated) < 4:
        srv.step()
    kept = srv.decode_kept
    cfg, slot = srv.engine.model_config, q.slot
    assert kept["selected"].shape == (cfg.n_layer, 3, 128) and kept["threshold"].shape == (cfg.n_layer, 3)
    seq = np.concatenate([prompt, np.asarray(q.generated[:-1], np.int32)])  # what the slot has consumed
    t = len(seq) - 1
    assert int(kept["pos"][slot]) == t
    others = [s for s in range(3) if s != slot]
    assert not np.asarray(kept["selected"])[:, others].any() and np.isnan(np.asarray(kept["threshold"])[:, others]).all()
    k, v = keye.cache_kind(cfg, jnp.float32).buffers(cfg.n_layer, 1 + 8, 16)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    padded = np.zeros((1, 48), np.int32)
    padded[0, :t] = seq[:t]
    _, k, v, _ = keye.forward_with_cache(srv.engine.params, jnp.asarray(padded), k, v, jnp.zeros((1,), jnp.int32), cfg, table)
    sink: list = []
    keye.forward_with_cache(srv.engine.params, jnp.asarray(seq[None, t:]), k, v, jnp.asarray([t], jnp.int32), cfg, table,
                            write_mask=jnp.ones((1,), bool), selection_sink=sink)
    for l, (mask, cut) in enumerate(sink):
        np.testing.assert_array_equal(np.asarray(kept["selected"])[l, slot], np.asarray(mask)[0, 0])
        np.testing.assert_allclose(np.asarray(kept["threshold"])[l, slot], np.asarray(cut)[0, 0], rtol=1e-5)
        assert np.asarray(mask).sum() == cfg.select_topk
    srv.drain()
    other = ServingEngine(deepspeed_tpu.init_inference(model_config=deepseek_v2.DEEPSEEK_V2_TINY, dtype=jnp.float32, max_out_tokens=64, seed=3),
                          config={"num_slots": 2, "max_len": 64, "prefill_chunk": 16, "kvcache": {"enabled": True, "page_len": 16}})
    other.submit(prompt[:20], max_new_tokens=3)
    other.drain()
    assert not other.decode_keeps and other.decode_kept is None


def test_compiled_step_takes_all_three_leaves_donated(served):
    srv = served[0]
    for which in ("prefill", "decode"):
        m = srv.compiled_step(which).memory_analysis()
        assert m.alias_size_in_bytes >= srv.pool.cache_bytes()  # K, V and the indexer keys come back aliased: nothing of the pool is copied


def test_a_paged_slot_is_whole_pages_not_whole_chunks():
    """The cell's slots are 264 pages of 128 = 33,792 positions under
    chunks of 2,048: 16.5 chunks.  A paged pool's ``max_len`` is a
    multiple of ``page_len`` only under a kind that says its chunk writes
    drop what lies past the slot's last page (``IndexedKV``); the
    slot-contiguous pool and every other paged kind keep the
    chunk-multiple rule (``latent_cache_write`` clips onto the slot's last
    page, which would overwrite valid rows)."""
    import deepspeed_tpu
    from deepspeed_tpu.config.config import DeepSpeedConfigError, ServingConfig
    from deepspeed_tpu.serving import ServingEngine

    with pytest.raises(DeepSpeedConfigError, match="multiple of"):
        ServingConfig.from_dict({"max_len": 96, "prefill_chunk": 64})
    assert ServingConfig.from_dict({"max_len": 96, "prefill_chunk": 64, "kvcache": {"enabled": True, "page_len": 16}}).max_len == 96
    from deepspeed_tpu.models import deepseek_v2, gpt2

    for other in (deepseek_v2.DEEPSEEK_V2_TINY, gpt2.GPT2_TINY):  # LatentKV, PerHeadKV
        inf = deepspeed_tpu.init_inference(model_config=other, dtype=jnp.float32, max_out_tokens=96, seed=3)
        with pytest.raises(DeepSpeedConfigError, match="multiple of prefill_chunk"):
            ServingEngine(inf, config={"num_slots": 2, "max_len": 96, "prefill_chunk": 64, "kvcache": {"enabled": True, "page_len": 16}})
    inf = deepspeed_tpu.init_inference(model_config=keye.KEYE_TINY, dtype=jnp.float32, max_out_tokens=96, seed=3)
    srv = ServingEngine(inf, config={"num_slots": 2, "max_len": 96, "prefill_chunk": 64, "kvcache": {"enabled": True, "page_len": 16}})
    prompt = np.random.default_rng(5).integers(1, 256, 90, dtype=np.int32)  # the second chunk runs 32 positions past the slot's end
    rid = srv.submit(prompt, max_new_tokens=5)
    gen = srv.drain()[rid].generated
    cfg, params = srv.engine.model_config, srv.engine.params
    seq = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
    k, v = keye.cache_kind(cfg, jnp.float32).buffers(cfg.n_layer, 1 + 6, 16)
    t = np.zeros((1, 96), np.int32)
    t[0, :len(seq)] = seq
    table = jnp.arange(1, 7, dtype=jnp.int32)[None]
    for i, tok in enumerate(gen):
        logits = keye.forward_with_cache(params, jnp.asarray(t), k, v, jnp.zeros((1,), jnp.int32), cfg, table,
                                         take=jnp.asarray([len(prompt) - 1 + i], jnp.int32))[0]
        assert int(jnp.argmax(logits[0])) == tok or np.asarray(logits)[0].max() - np.asarray(logits)[0, tok] < 1e-4
