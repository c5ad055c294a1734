"""BERT family — the bing_bert workload model (BASELINE config 2:
BERT-large pretraining, ZeRO 1/2 + FusedAdam; reference tests carry a
full in-tree BERT in ``tests/unit/modeling.py``).

Same TPU-idiomatic structure as gpt2.py: stacked blocks + lax.scan,
flash attention (non-causal), TP specs on the weights.  Pre-LN variant
(the reference's fused "stochastic_transformer" kernels target pre-LN
BERT; ``tests/unit/modelingpreln.py``) with a config switch for post-LN.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention.flash_attention import flash_attention, mha_reference
from deepspeed_tpu.models.gpt2 import _dropout, _layer_norm


CAUSAL_LM = False  # models/__init__.py: what the engines ask of a family
PARTITION_RULES = "bert"  # the family's table in sharding/rules.py


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_dropout_prob: float = 0.0
    attention_probs_dropout_prob: float = 0.0
    layer_norm_eps: float = 1e-12
    pre_layer_norm: bool = True
    use_flash_attention: bool = True
    remat: bool = True
    # lax.scan unroll factor for the layer loop (see gpt2.GPT2Config)
    scan_unroll: int = 1

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def num_params(self) -> int:
        d, l, i = self.hidden_size, self.num_hidden_layers, self.intermediate_size
        per_layer = 4 * d * d + 2 * d * i + 9 * d + i
        emb = (self.vocab_size + self.max_position_embeddings + self.type_vocab_size) * d + 2 * d
        return emb + l * per_layer + 2 * d


BERT_TINY = BertConfig(vocab_size=512, max_position_embeddings=128, hidden_size=64, num_hidden_layers=2, num_attention_heads=4, intermediate_size=128)
BERT_BASE = BertConfig()
BERT_LARGE = BertConfig(hidden_size=1024, num_hidden_layers=24, num_attention_heads=16, intermediate_size=4096)

PRESETS = {"tiny": BERT_TINY, "bert-base": BERT_BASE, "bert-large": BERT_LARGE}


def init_params(cfg: BertConfig, seed: int = 0) -> Dict[str, Any]:
    rng = np.random.default_rng(seed)
    d, l, i = cfg.hidden_size, cfg.num_hidden_layers, cfg.intermediate_size

    def n(*shape, s=0.02):
        return (rng.standard_normal(shape) * s).astype(np.float32)

    def z(*shape):
        return np.zeros(shape, np.float32)

    def o(*shape):
        return np.ones(shape, np.float32)

    return {
        "tok_emb": n(cfg.vocab_size, d),
        "pos_emb": n(cfg.max_position_embeddings, d),
        "type_emb": n(cfg.type_vocab_size, d),
        "emb_ln_g": o(d),
        "emb_ln_b": z(d),
        "blocks": {
            "ln1_g": o(l, d), "ln1_b": z(l, d),
            "qkv_w": n(l, d, 3 * d), "qkv_b": z(l, 3 * d),
            "proj_w": n(l, d, d), "proj_b": z(l, d),
            "ln2_g": o(l, d), "ln2_b": z(l, d),
            "fc_w": n(l, d, i), "fc_b": z(l, i),
            "fc_proj_w": n(l, i, d), "fc_proj_b": z(l, d),
        },
        "pooler_w": n(d, d),
        "pooler_b": z(d),
        # MLM head: transform + tied decoder bias; NSP head
        "mlm_dense_w": n(d, d),
        "mlm_dense_b": z(d),
        "mlm_ln_g": o(d),
        "mlm_ln_b": z(d),
        "mlm_bias": z(cfg.vocab_size),
        "nsp_w": n(d, 2),
        "nsp_b": z(2),
    }


def init_params_device(cfg: BertConfig, seed: int = 0, dtype=jnp.float32):
    """Random init generated ON DEVICE (same tree structure/shapes as
    ``init_params``, independent random stream) — see
    ``models/gpt2.init_params_device`` for when to use which."""
    d, l, i = cfg.hidden_size, cfg.num_hidden_layers, cfg.intermediate_size

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def n(shape, s=0.02):
            return (jax.random.normal(next(ks), shape, jnp.float32) * s).astype(dtype)

        z = lambda *shape: jnp.zeros(shape, dtype)
        o = lambda *shape: jnp.ones(shape, dtype)
        return {
            "tok_emb": n((cfg.vocab_size, d)),
            "pos_emb": n((cfg.max_position_embeddings, d)),
            "type_emb": n((cfg.type_vocab_size, d)),
            "emb_ln_g": o(d),
            "emb_ln_b": z(d),
            "blocks": {
                "ln1_g": o(l, d), "ln1_b": z(l, d),
                "qkv_w": n((l, d, 3 * d)), "qkv_b": z(l, 3 * d),
                "proj_w": n((l, d, d)), "proj_b": z(l, d),
                "ln2_g": o(l, d), "ln2_b": z(l, d),
                "fc_w": n((l, d, i)), "fc_b": z(l, i),
                "fc_proj_w": n((l, i, d)), "fc_proj_b": z(l, d),
            },
            "pooler_w": n((d, d)),
            "pooler_b": z(d),
            "mlm_dense_w": n((d, d)),
            "mlm_dense_b": z(d),
            "mlm_ln_g": o(d),
            "mlm_ln_b": z(d),
            "mlm_bias": z(cfg.vocab_size),
            "nsp_w": n((d, 2)),
            "nsp_b": z(2),
        }

    # out_shardings=None: init params land unsharded; the engine shards
    # them on first scoped step (docs/ds_lint.md, bare-jit)
    return jax.jit(build, out_shardings=None)(jax.random.PRNGKey(seed))


def tp_spec_fn(path: str, shape) -> Optional[P]:
    """Adapter over the partition-rule engine's ``bert`` family table
    (sharding/rules.py) — the single source of truth for this layout."""
    from deepspeed_tpu.sharding.rules import rules_for_family

    return rules_for_family("bert").spec(path, shape)


def _bert_block(cfg: BertConfig, x, lp, mask_bias, rng, deterministic):
    B, T, D = x.shape
    H, hd = cfg.num_attention_heads, cfg.head_dim
    r1 = r2 = r_attn = None
    if rng is not None:
        r1, r2, r_attn = jax.random.split(rng, 3)

    def attn_part(h):
        qkv = h @ lp["qkv_w"].astype(h.dtype) + lp["qkv_b"].astype(h.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        def heads(t):
            return t.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
        q, k, v = heads(q), heads(k), heads(v)
        # padding-mask bias + attention-probability dropout go through
        # the fused path natively (flash_attention falls back to
        # mha_reference for shapes its grid can't serve)
        rate = 0.0 if deterministic or r_attn is None else cfg.attention_probs_dropout_prob
        if cfg.use_flash_attention:
            out = flash_attention(q, k, v, causal=False, bias=mask_bias, dropout_rate=rate, dropout_rng=r_attn)
        else:
            m4 = None
            if rate > 0.0:
                m4 = jax.random.bernoulli(r_attn, 1.0 - rate, (B, H, T, T)).astype(jnp.uint8)
            out = mha_reference(q, k, v, causal=False, bias=mask_bias, dropout_mask=m4, keep_prob=1.0 - rate)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, D)
        return out @ lp["proj_w"].astype(out.dtype) + lp["proj_b"].astype(out.dtype)

    def mlp_part(h):
        h = h @ lp["fc_w"].astype(h.dtype) + lp["fc_b"].astype(h.dtype)
        h = jax.nn.gelu(h, approximate=False)
        return h @ lp["fc_proj_w"].astype(h.dtype) + lp["fc_proj_b"].astype(h.dtype)

    eps = cfg.layer_norm_eps
    if cfg.pre_layer_norm:
        x = x + _dropout(attn_part(_layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)), cfg.hidden_dropout_prob, r1, deterministic)
        x = x + _dropout(mlp_part(_layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)), cfg.hidden_dropout_prob, r2, deterministic)
    else:
        x = _layer_norm(x + _dropout(attn_part(x), cfg.hidden_dropout_prob, r1, deterministic), lp["ln1_g"], lp["ln1_b"], eps)
        x = _layer_norm(x + _dropout(mlp_part(x), cfg.hidden_dropout_prob, r2, deterministic), lp["ln2_g"], lp["ln2_b"], eps)
    return x


def encode(params, input_ids, cfg: BertConfig, token_type_ids=None, attention_mask=None, rng=None, deterministic=True):
    B, T = input_ids.shape
    dtype = params["blocks"]["qkv_w"].dtype
    x = jnp.take(params["tok_emb"], input_ids, axis=0) + params["pos_emb"][:T][None]
    if token_type_ids is None:
        # BERT semantics: absent segment ids mean "all segment A" — the
        # type-0 embedding is still added (HF does the same).
        x = x + params["type_emb"][0][None, None]
    else:
        x = x + jnp.take(params["type_emb"], token_type_ids, axis=0)
    x = _layer_norm(x.astype(dtype), params["emb_ln_g"], params["emb_ln_b"], cfg.layer_norm_eps)

    mask_bias = None
    if attention_mask is not None:
        neg = jnp.asarray(-1e9, jnp.float32)
        mask_bias = jnp.where(attention_mask[:, None, None, :].astype(bool), 0.0, neg)

    L = cfg.num_hidden_layers
    layer_rngs = jax.random.split(rng, L) if rng is not None else jnp.zeros((L, 2), jnp.uint32)
    block = functools.partial(_bert_block, cfg)

    def scan_body(carry, xs):
        lp, lr = xs
        return block(carry, lp, mask_bias, lr if rng is not None else None, deterministic), None

    if cfg.remat:
        scan_body = jax.checkpoint(scan_body, prevent_cse=False)
    x, _ = jax.lax.scan(scan_body, x, (params["blocks"], layer_rngs), unroll=max(1, cfg.scan_unroll))
    return x


def mlm_nsp_loss(params, batch, rng=None, cfg: BertConfig = None, deterministic=False):
    """Pretraining loss: masked-LM + next-sentence prediction.

    ``batch``: input_ids, masked_lm_labels (-100 = unmasked), optional
    token_type_ids / attention_mask / next_sentence_label.
    """
    x = encode(
        params,
        batch["input_ids"],
        cfg,
        token_type_ids=batch.get("token_type_ids"),
        attention_mask=batch.get("attention_mask"),
        rng=rng,
        deterministic=deterministic,
    )
    # MLM
    h = x @ params["mlm_dense_w"].astype(x.dtype) + params["mlm_dense_b"].astype(x.dtype)
    h = jax.nn.gelu(h, approximate=False)
    h = _layer_norm(h, params["mlm_ln_g"], params["mlm_ln_b"], cfg.layer_norm_eps)
    logits = (h @ params["tok_emb"].T.astype(h.dtype)).astype(jnp.float32) + params["mlm_bias"]
    labels = batch["masked_lm_labels"]
    valid = labels >= 0
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid.astype(jnp.float32)
    mlm_loss = jnp.sum(nll) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)

    loss = mlm_loss
    if "next_sentence_label" in batch:
        pooled = jnp.tanh(x[:, 0] @ params["pooler_w"].astype(x.dtype) + params["pooler_b"].astype(x.dtype))
        nsp_logits = (pooled @ params["nsp_w"].astype(pooled.dtype) + params["nsp_b"].astype(pooled.dtype)).astype(jnp.float32)
        nsp_labels = batch["next_sentence_label"]
        nsp = jax.nn.logsumexp(nsp_logits, axis=-1) - jnp.take_along_axis(nsp_logits, nsp_labels[..., None], axis=-1)[..., 0]
        loss = loss + jnp.mean(nsp)
    return loss


def make_model(cfg: BertConfig):
    def model_fn(params, batch, rng):
        # rng=None ⇒ eval mode (engine passes None from eval_batch/predict)
        deterministic = rng is None or cfg.hidden_dropout_prob == 0.0
        return mlm_nsp_loss(params, batch, rng=rng, cfg=cfg, deterministic=deterministic)

    return model_fn, functools.partial(init_params, cfg), tp_spec_fn
