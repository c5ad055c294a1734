"""Inference stack tests: KV-cache decode parity, generation, kernel
injection from HF transformers models, TP inference, int8 weight
quantization (reference coverage: inference/engine.py + module_inject +
ops/transformer/inference)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.ops.transformer.inference import (
    DeepSpeedInferenceConfig,
    forward_with_cache,
    init_kv_cache,
)

TINY = dataclasses.replace(gpt2.GPT2_TINY, remat=False)


def _icfg(cfg, max_len, dtype=jnp.float32):
    return DeepSpeedInferenceConfig(
        hidden_size=cfg.n_embd, heads=cfg.n_head, layer_norm_eps=cfg.layer_norm_epsilon,
        dtype=dtype, max_out_tokens=max_len, use_flash_attention=False,
    )


def test_cached_forward_matches_full_forward():
    """Prefill+decode through the KV cache must reproduce the training
    model's logits token by token."""
    cfg = TINY
    params = jax.tree.map(jnp.asarray, gpt2.init_params(cfg, seed=1))
    B, T = 2, 10
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (B, T), dtype=np.int32)
    ref_logits = gpt2.apply(params, jnp.asarray(toks), cfg, deterministic=True)

    icfg = _icfg(cfg, T)
    k, v = init_kv_cache(cfg.n_layer, B, cfg.n_head, T, cfg.head_dim, jnp.float32)
    # prefill the first 4 tokens, then decode the rest one at a time
    logits, k, v = forward_with_cache(params, jnp.asarray(toks[:, :4]), k, v, 0, icfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits[:, :4]), rtol=2e-4, atol=2e-4)
    for t in range(4, T):
        step_logits, k, v = forward_with_cache(params, jnp.asarray(toks[:, t : t + 1]), k, v, t, icfg)
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]), np.asarray(ref_logits[:, t]), rtol=2e-4, atol=2e-4
        )


def test_chunked_continuation_uses_cache():
    """T>1 append at pos>0 (chunked prefill) must attend to the cached
    prefix, not just the new chunk."""
    cfg = TINY
    params = jax.tree.map(jnp.asarray, gpt2.init_params(cfg, seed=4))
    B, T = 2, 12
    toks = np.random.default_rng(4).integers(0, cfg.vocab_size, (B, T), dtype=np.int32)
    ref_logits = gpt2.apply(params, jnp.asarray(toks), cfg, deterministic=True)
    icfg = _icfg(cfg, T)
    k, v = init_kv_cache(cfg.n_layer, B, cfg.n_head, T, cfg.head_dim, jnp.float32)
    _, k, v = forward_with_cache(params, jnp.asarray(toks[:, :4]), k, v, 0, icfg)
    # append a 4-token chunk at pos=4, then another at pos=8
    log2, k, v = forward_with_cache(params, jnp.asarray(toks[:, 4:8]), k, v, jnp.int32(4), icfg)
    log3, k, v = forward_with_cache(params, jnp.asarray(toks[:, 8:12]), k, v, jnp.int32(8), icfg)
    np.testing.assert_allclose(np.asarray(log2), np.asarray(ref_logits[:, 4:8]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(log3), np.asarray(ref_logits[:, 8:12]), rtol=2e-4, atol=2e-4)


def test_generate_greedy_matches_naive_loop():
    eng = deepspeed_tpu.init_inference(
        model_config=TINY, mp_size=1, dtype=jnp.float32, max_out_tokens=64
    )
    B, T, N = 2, 8, 6
    toks = np.random.default_rng(1).integers(0, TINY.vocab_size, (B, T), dtype=np.int32)
    out = np.asarray(eng.generate(toks, max_new_tokens=N))
    assert out.shape == (B, T + N)
    np.testing.assert_array_equal(out[:, :T], toks)
    # naive greedy loop with the full forward
    cur = toks.copy()
    for _ in range(N):
        logits = np.asarray(eng.forward(cur))
        cur = np.concatenate([cur, logits[:, -1].argmax(-1)[:, None].astype(np.int32)], axis=1)
    np.testing.assert_array_equal(out, cur)


def test_generate_sampling_and_eos():
    eng = deepspeed_tpu.init_inference(model_config=TINY, dtype=jnp.float32)
    toks = np.zeros((1, 4), np.int32)
    out = np.asarray(eng.generate(toks, max_new_tokens=8, do_sample=True, temperature=0.9, top_k=5, seed=3))
    assert out.shape == (1, 12)
    assert (out[:, 4:] < TINY.vocab_size).all()
    # eos short-circuit: declare the first greedily-generated token to be
    # eos — every later position must then be filled with eos
    greedy = np.asarray(eng.generate(toks, max_new_tokens=8))
    eos = int(greedy[0, 4])
    out2 = np.asarray(eng.generate(toks, max_new_tokens=8, eos_token_id=eos))
    assert (out2[0, 4:] == eos).any()
    first_eos = int(np.argmax(out2[0, 4:] == eos))
    assert (out2[0, 4 + first_eos :] == eos).all()


def test_tp_inference_matches_single_device():
    cfg = TINY
    params = gpt2.init_params(cfg, seed=2)
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 8), dtype=np.int32)
    eng1 = deepspeed_tpu.init_inference(model_config=cfg, params=params, mp_size=1, dtype=jnp.float32)
    eng4 = deepspeed_tpu.init_inference(model_config=cfg, params=params, mp_size=4, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(eng1.forward(toks)), np.asarray(eng4.forward(toks)), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_array_equal(
        np.asarray(eng1.generate(toks, max_new_tokens=4)),
        np.asarray(eng4.generate(toks, max_new_tokens=4)),
    )


# ---------------------------------------------------------------------------
# kernel injection from HF transformers (offline tiny models, random init)
# ---------------------------------------------------------------------------

def test_hf_gpt2_injection_matches_hf_forward():
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    hf_cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    torch.manual_seed(0)
    hf_model = transformers.GPT2LMHeadModel(hf_cfg).eval()
    toks = np.random.default_rng(0).integers(0, 128, (2, 10), dtype=np.int64)
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(toks)).logits.numpy()

    eng = deepspeed_tpu.init_inference(model=hf_model, dtype=jnp.float32)
    ours = np.asarray(eng.forward(toks.astype(np.int32)))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-3, atol=2e-3)
    out = eng.generate(toks.astype(np.int32), max_new_tokens=4)
    assert out.shape == (2, 14)


def test_hf_gptneo_injection_matches_hf_forward():
    """GPT-Neo has no 1/sqrt(head_dim) attention scale in HF; the policy
    must fold the compensation into the q projection."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    hf_cfg = transformers.GPTNeoConfig(
        vocab_size=128, max_position_embeddings=64, hidden_size=32, num_layers=2,
        num_heads=4, attention_types=[[["global"], 2]], intermediate_size=64,
        resid_dropout=0.0, embed_dropout=0.0, attention_dropout=0.0,
    )
    torch.manual_seed(0)
    hf_model = transformers.GPTNeoForCausalLM(hf_cfg).eval()
    toks = np.random.default_rng(0).integers(0, 128, (2, 10), dtype=np.int64)
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(toks)).logits.numpy()
    eng = deepspeed_tpu.init_inference(model=hf_model, dtype=jnp.float32)
    ours = np.asarray(eng.forward(toks.astype(np.int32)))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-3, atol=2e-3)


def test_megatron_policy_qkv_deinterleave():
    """A synthetic Megatron state dict whose per-head-interleaved QKV was
    built from known q|k|v matrices must round-trip exactly."""
    from deepspeed_tpu.inference.injection import MegatronLayerPolicy

    d, n_head, n_layer, vocab, seq = 8, 2, 1, 32, 16
    hd = d // n_head
    rng = np.random.default_rng(0)
    q, k, v = (rng.standard_normal((d, d)).astype(np.float32) for _ in range(3))
    # megatron layout: output rows grouped per head as (head, [q,k,v], hd)
    fused = np.concatenate(
        [np.concatenate([q[h * hd : (h + 1) * hd], k[h * hd : (h + 1) * hd], v[h * hd : (h + 1) * hd]])
         for h in range(n_head)]
    )  # (3d, d) rows = outputs (torch Linear layout)
    sd = {
        "language_model.embedding.word_embeddings.weight": rng.standard_normal((vocab, d)).astype(np.float32),
        "language_model.embedding.position_embeddings.weight": rng.standard_normal((seq, d)).astype(np.float32),
        "language_model.transformer.layers.0.input_layernorm.weight": np.ones(d, np.float32),
        "language_model.transformer.layers.0.input_layernorm.bias": np.zeros(d, np.float32),
        "language_model.transformer.layers.0.attention.query_key_value.weight": fused,
        "language_model.transformer.layers.0.attention.query_key_value.bias": np.zeros(3 * d, np.float32),
        "language_model.transformer.layers.0.attention.dense.weight": rng.standard_normal((d, d)).astype(np.float32),
        "language_model.transformer.layers.0.attention.dense.bias": np.zeros(d, np.float32),
        "language_model.transformer.layers.0.post_attention_layernorm.weight": np.ones(d, np.float32),
        "language_model.transformer.layers.0.post_attention_layernorm.bias": np.zeros(d, np.float32),
        "language_model.transformer.layers.0.mlp.dense_h_to_4h.weight": rng.standard_normal((4 * d, d)).astype(np.float32),
        "language_model.transformer.layers.0.mlp.dense_h_to_4h.bias": np.zeros(4 * d, np.float32),
        "language_model.transformer.layers.0.mlp.dense_4h_to_h.weight": rng.standard_normal((d, 4 * d)).astype(np.float32),
        "language_model.transformer.layers.0.mlp.dense_4h_to_h.bias": np.zeros(d, np.float32),
        "language_model.transformer.final_layernorm.weight": np.ones(d, np.float32),
        "language_model.transformer.final_layernorm.bias": np.zeros(d, np.float32),
    }
    from types import SimpleNamespace

    cfg, params = MegatronLayerPolicy.convert(sd, hf_config=SimpleNamespace(num_attention_heads=n_head))
    # contiguous q|k|v on the output (column) axis after conversion
    np.testing.assert_allclose(params["blocks"]["qkv_w"][0][:, :d], q.T, rtol=1e-6)
    np.testing.assert_allclose(params["blocks"]["qkv_w"][0][:, d : 2 * d], k.T, rtol=1e-6)
    np.testing.assert_allclose(params["blocks"]["qkv_w"][0][:, 2 * d :], v.T, rtol=1e-6)
    assert cfg.n_layer == 1 and cfg.n_embd == d


def test_hf_bert_injection_matches_hf_encoder():
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    hf_cfg = transformers.BertConfig(
        vocab_size=100, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    )
    torch.manual_seed(0)
    hf_model = transformers.BertModel(hf_cfg).eval()
    toks = np.random.default_rng(0).integers(0, 100, (2, 12), dtype=np.int64)
    with torch.no_grad():
        hf_hidden = hf_model(torch.tensor(toks)).last_hidden_state.numpy()

    eng = deepspeed_tpu.init_inference(model=hf_model, dtype=jnp.float32)
    ours = np.asarray(eng.forward(toks.astype(np.int32)))
    np.testing.assert_allclose(ours, hf_hidden, rtol=2e-3, atol=2e-3)


def test_int8_weight_quantization_close():
    cfg = TINY
    params = gpt2.init_params(cfg, seed=3)
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, (1, 8), dtype=np.int32)
    ref = deepspeed_tpu.init_inference(model_config=cfg, params=params, dtype=jnp.float32)
    q = deepspeed_tpu.init_inference(model_config=cfg, params=params, dtype=jnp.float32, quantize_bits=8, quantize_groups=4)
    a, b = np.asarray(ref.forward(toks)), np.asarray(q.forward(toks))
    # int8 grouped quantization should stay close in logit space
    assert np.mean(np.abs(a - b)) < 0.1 * (np.mean(np.abs(a)) + 1e-6)


def test_checkpoint_roundtrip_to_inference(tmp_path):
    """Train-engine checkpoint → inference engine param load."""
    cfg = TINY
    model_fn, init_fn, tp_fn = gpt2.make_model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=init_fn(), config=config, tp_spec_fn=tp_fn
    )
    batch = {"input_ids": np.random.default_rng(0).integers(0, cfg.vocab_size, (16, 16), dtype=np.int32)}
    engine.train_batch(batch)
    engine.save_checkpoint(str(tmp_path), tag="step1")

    eng = deepspeed_tpu.init_inference(
        model_config=cfg, checkpoint=str(tmp_path), dtype=jnp.float32
    )
    expect = np.asarray(engine.state["params"]["lnf_g"], np.float32)
    np.testing.assert_allclose(np.asarray(eng.params["lnf_g"], np.float32), expect, rtol=1e-6)


def _position_sensitive_engine(seed=7):
    """Engine whose outputs strongly depend on position (wpe scaled up):
    position bookkeeping bugs change generations instead of hiding
    behind a degenerate constant-token model."""
    params = gpt2.init_params(TINY, seed=seed)
    params["wpe"] = params["wpe"] * 40.0
    return deepspeed_tpu.init_inference(model_config=TINY, params=params, dtype=jnp.float32)


def test_left_padded_generate_matches_unpadded():
    """A left-padded prompt must generate the same continuation as the
    same prompt unpadded (positions + padding mask correct)."""
    eng = _position_sensitive_engine()
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, TINY.vocab_size, (1, 6), dtype=np.int32)

    out_ref = np.asarray(eng.generate(prompt, max_new_tokens=5))  # unpadded

    pad = 4
    padded = np.concatenate([np.zeros((1, pad), np.int32), prompt], axis=1)
    mask = np.concatenate([np.zeros((1, pad), np.int32), np.ones((1, 6), np.int32)], axis=1)
    out_padded = np.asarray(eng.generate(padded, max_new_tokens=5, attention_mask=mask))

    np.testing.assert_array_equal(out_padded[:, pad + 6 :], out_ref[:, 6:])


def test_ragged_batch_generate():
    """Two prompts of different lengths in one batch, left-padded: each
    must match its own single-prompt generation."""
    eng = _position_sensitive_engine(seed=8)
    rng = np.random.default_rng(8)
    p1 = rng.integers(1, TINY.vocab_size, (1, 8), dtype=np.int32)
    p2 = rng.integers(1, TINY.vocab_size, (1, 5), dtype=np.int32)
    ref1 = np.asarray(eng.generate(p1, max_new_tokens=4))[:, 8:]
    ref2 = np.asarray(eng.generate(p2, max_new_tokens=4))[:, 5:]

    batch = np.zeros((2, 8), np.int32)
    mask = np.zeros((2, 8), np.int32)
    batch[0], mask[0] = p1[0], 1
    batch[1, 3:], mask[1, 3:] = p2[0], 1
    out = np.asarray(eng.generate(batch, max_new_tokens=4, attention_mask=mask))
    np.testing.assert_array_equal(out[0, 8:], ref1[0])
    np.testing.assert_array_equal(out[1, 8:], ref2[0])


def test_right_padded_mask_rejected_and_all_ones_fast_path():
    eng = deepspeed_tpu.init_inference(model_config=TINY, dtype=jnp.float32)
    toks = np.ones((1, 6), np.int32)
    with pytest.raises(ValueError, match="LEFT-padded"):
        eng.generate(toks, max_new_tokens=2, attention_mask=np.array([[1, 1, 1, 1, 0, 0]]))
    # all-ones mask must produce the identical result to no mask
    a = np.asarray(eng.generate(toks, max_new_tokens=4))
    b = np.asarray(eng.generate(toks, max_new_tokens=4, attention_mask=np.ones((1, 6), np.int32)))
    np.testing.assert_array_equal(a, b)


def test_true_int8_serving_close_and_packed():
    """quantize_bits=8 on a GPT model packs weights as int8+scales; the
    matmuls run on int8 at rest and outputs stay close to fp."""
    cfg = TINY
    params = gpt2.init_params(cfg, seed=3)
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, (1, 8), dtype=np.int32)
    ref = deepspeed_tpu.init_inference(model_config=cfg, params=params, dtype=jnp.float32)
    q8 = deepspeed_tpu.init_inference(model_config=cfg, params=params, dtype=jnp.float32, quantize_bits=8)
    assert q8._packed_int8
    # weights really are int8 on device
    assert q8.params["blocks"]["qkv_w"]["q"].dtype == jnp.int8
    assert q8.params["blocks"]["qkv_w"]["s"].dtype == jnp.float32
    a, b = np.asarray(ref.forward(toks)), np.asarray(q8.forward(toks))
    assert np.mean(np.abs(a - b)) < 0.05 * (np.mean(np.abs(a)) + 1e-6)
    # greedy generations agree on a well-separated model
    out_ref = np.asarray(ref.generate(toks, max_new_tokens=4))
    out_q8 = np.asarray(q8.generate(toks, max_new_tokens=4))
    assert out_q8.shape == out_ref.shape


def test_int8_tp_serving():
    cfg = TINY
    params = gpt2.init_params(cfg, seed=4)
    toks = np.random.default_rng(4).integers(0, cfg.vocab_size, (1, 8), dtype=np.int32)
    q1 = deepspeed_tpu.init_inference(model_config=cfg, params=params, dtype=jnp.float32, quantize_bits=8)
    q4 = deepspeed_tpu.init_inference(model_config=cfg, params=params, dtype=jnp.float32, quantize_bits=8, mp_size=4)
    np.testing.assert_allclose(
        np.asarray(q1.forward(toks)), np.asarray(q4.forward(toks)), rtol=3e-4, atol=3e-4
    )


def test_param_staging_paths_numerically_equal(monkeypatch):
    """r4 engine-build paths must all yield the SAME sharded params:
    (a) host init via chunked flat staging (tiny chunk cap forces many
    chunks, pinning the chunk-boundary reassembly), (b) the same host
    init passed as caller params, (c) device-resident caller params
    (jitted cast path — and the caller's tree must SURVIVE init,
    no donation of non-owned arrays)."""
    import deepspeed_tpu.inference.engine as eng_mod
    from deepspeed_tpu.models import gpt2

    monkeypatch.setattr(eng_mod, "_STAGE_CHUNK_BYTES", 4096)
    host = gpt2.init_params(gpt2.GPT2_TINY, seed=3)
    e_host = deepspeed_tpu.init_inference(model="tiny", seed=3, max_out_tokens=32)
    e_caller = deepspeed_tpu.init_inference(model=None, model_config=gpt2.GPT2_TINY,
                                            params=host, max_out_tokens=32)
    dev = jax.tree.map(jnp.asarray, host)
    e_dev = deepspeed_tpu.init_inference(model=None, model_config=gpt2.GPT2_TINY,
                                         params=dev, max_out_tokens=32)
    for a, b, c in zip(jax.tree.leaves(e_host.params), jax.tree.leaves(e_caller.params),
                       jax.tree.leaves(e_dev.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(c, np.float32))
    # caller trees survive engine init (no donation of non-owned arrays)
    _ = [np.asarray(l) for l in jax.tree.leaves(host)]
    _ = [np.asarray(l) for l in jax.tree.leaves(dev)]


def test_int8_pack_device_equals_host():
    """pack_int8_tree must produce identical quantization whether the
    tree is host numpy (per-leaf) or device-resident (single jitted
    pack with donation)."""
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.runtime.weight_quantizer import pack_int8_tree

    host = gpt2.init_params(gpt2.GPT2_TINY, seed=5)
    p_host = pack_int8_tree(host)
    dev = jax.tree.map(jnp.asarray, host)
    p_dev = pack_int8_tree(dev, donate=True)
    assert jax.tree_util.tree_structure(p_host) == jax.tree_util.tree_structure(p_dev)
    for a, b in zip(jax.tree.leaves(p_host), jax.tree.leaves(p_dev)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8:
            # quantized payloads must match exactly...
            np.testing.assert_array_equal(a, b)
        else:
            # ...scales may differ at fp32 ulp level (eager vs jitted
            # reduction fusion order)
            np.testing.assert_allclose(a, b, rtol=1e-6)


def test_init_on_device_generates():
    """init_on_device engines must build and generate (structure/shape
    parity with host init is pinned in tests/test_models.py-style
    checks; values are an independent random stream)."""
    e = deepspeed_tpu.init_inference(model="tiny", max_out_tokens=32, init_on_device=True)
    out = e.generate(np.zeros((2, 4), np.int32), max_new_tokens=4)
    assert np.asarray(out).shape == (2, 8)
    e8 = deepspeed_tpu.init_inference(model="tiny", max_out_tokens=32,
                                      init_on_device=True, quantize_bits=8)
    out8 = e8.generate(np.zeros((2, 4), np.int32), max_new_tokens=4)
    assert np.asarray(out8).shape == (2, 8)


def test_int8_kv_cache_generate_matches_bf16():
    """kv_cache_dtype='int8' (r5: per-row absmax cache quantization)
    must reproduce the bf16-cache generation almost always — greedy
    decode tolerates the ~0.4% cache rounding except at near-ties."""
    import dataclasses as _dc

    import deepspeed_tpu

    cfg = _dc.replace(gpt2.GPT2_TINY, n_layer=2)
    params = gpt2.init_params(cfg, seed=3)
    kw = dict(model_config=cfg, params=params, mp_size=1)
    e_bf = deepspeed_tpu.init_inference(**kw)
    e_q = deepspeed_tpu.init_inference(kv_cache_dtype="int8", **kw)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16), dtype=np.int32)
    out_bf = np.asarray(e_bf.generate(prompts, max_new_tokens=12))
    out_q = np.asarray(e_q.generate(prompts, max_new_tokens=12))
    assert out_bf.shape == out_q.shape == (2, 28)
    # token-level agreement: allow a few near-tie flips, require the bulk
    agree = (out_bf == out_q).mean()
    assert agree > 0.85, (agree, out_bf, out_q)


def test_int8_kv_cache_bytes_halved():
    """The int8 cache's HBM bytes are ~half the bf16 cache's."""
    from deepspeed_tpu.ops.transformer.inference import init_kv_cache

    kb, vb = init_kv_cache(4, 2, 4, 128, 64, jnp.bfloat16)
    kq, vq = init_kv_cache(4, 2, 4, 128, 64, "int8")
    b_bf = kb.size * kb.dtype.itemsize
    b_q = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(kq))
    assert b_q < 0.6 * b_bf, (b_q, b_bf)


@pytest.mark.parametrize("layers,window,refused", [(["global", "local"], 16, True), (["global", "local"], 64, False), (["global", "global"], 16, False)])
def test_gptneo_policy_refuses_a_local_layer_whose_window_is_shorter_than_the_context(layers, window, refused):
    """A local-attention layer is not full attention: the policy raises by
    name instead of computing another model's mathematics (ROADMAP D18);
    a window that covers the context is full attention and goes on (here
    to the state dict it was not given)."""
    import types

    from deepspeed_tpu.inference.injection import HFGPTNEOLayerPolicy

    hf = types.SimpleNamespace(attention_layers=layers, window_size=window, max_position_embeddings=64)
    with pytest.raises(NotImplementedError if refused else (AttributeError, TypeError, KeyError), match="local window of 16" if refused else None):
        HFGPTNEOLayerPolicy.convert(None, hf_config=hf)
