"""Kernel injection — HF/Megatron model → TPU-native fused inference.

The reference swaps ``nn.Module`` children for fused CUDA modules at
runtime (``module_inject/replace_module.py:89`` ``replace_transformer_layer``,
policies in ``module_inject/replace_policy.py``: ``HFBertLayerPolicy`` :43,
``HFGPT2LayerPolicy`` :195, ``HFGPTNEOLayerPolicy`` :102,
``MegatronLayerPolicy`` :146).  In a functional JAX world the analog is a
**pytree transform**: a policy maps the source model's weights into this
framework's stacked-block parameter layout, after which the whole network
runs through the fused inference path (``ops/transformer/inference.py``).

Tensor-parallel slicing (reference ``ReplaceWithTensorSlicing``,
``replace_module.py:11-88``, ``qkv_copy`` :24) becomes PartitionSpecs over
the ``model`` mesh axis — GSPMD does the physical slicing when params are
``device_put`` with those shardings, so the "copy loop" disappears.

Policies accept either a live ``torch.nn.Module`` (transformers model) or
a plain ``{name: ndarray}`` state dict plus a config object.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from deepspeed_tpu.utils.logging import logger


def _to_numpy(t) -> np.ndarray:
    if isinstance(t, np.ndarray):
        return t
    # torch tensor (cpu or otherwise) without importing torch eagerly
    detach = getattr(t, "detach", None)
    if detach is not None:
        return detach().cpu().numpy()
    return np.asarray(t)


def _state_dict_of(model) -> Dict[str, np.ndarray]:
    if isinstance(model, dict):
        return {k: _to_numpy(v) for k, v in model.items()}
    sd = model.state_dict()
    return {k: _to_numpy(v) for k, v in sd.items()}


def _stack(sd: Dict[str, np.ndarray], fmt: str, n_layer: int, transpose: bool = False) -> np.ndarray:
    mats = [sd[fmt.format(i)] for i in range(n_layer)]
    if transpose:
        mats = [m.T for m in mats]
    return np.ascontiguousarray(np.stack(mats).astype(np.float32))


class DSPolicy:
    """Base policy: subclasses declare how to read one architecture.

    ``convert(model)`` returns ``(model_config, params)`` where ``params``
    is the stacked GPT-2/BERT-layout pytree used by models/ and
    ops/transformer/inference.py.
    """

    architectures: Tuple[str, ...] = ()

    @classmethod
    def matches(cls, model) -> bool:
        cfg = getattr(model, "config", None)
        archs = tuple(getattr(cfg, "architectures", None) or ()) if cfg is not None else ()
        name = type(model).__name__
        return any(a in cls.architectures for a in archs) or name in cls.architectures


class HFGPT2LayerPolicy(DSPolicy):
    """transformers GPT-2 (reference ``replace_policy.py:195``).

    HF GPT-2 uses Conv1D (weights already (in, out)) so no transpose; the
    fused c_attn is the same q|k|v concat our blocks use.
    """

    # (GPT2ForSequenceClassification is deliberately absent: its score
    # head has no analog in the fused LM layout)
    architectures = ("GPT2LMHeadModel", "GPT2Model")

    @classmethod
    def convert(cls, model, hf_config=None):
        from deepspeed_tpu.models.gpt2 import GPT2Config

        sd = _state_dict_of(model)
        hf = hf_config if hf_config is not None else model.config
        # tolerate both GPT2Model ("h.0...") and GPT2LMHeadModel ("transformer.h.0...")
        prefix = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
        n_layer = hf.n_layer
        cfg = GPT2Config(
            vocab_size=hf.vocab_size,
            n_positions=hf.n_positions,
            n_embd=hf.n_embd,
            n_layer=n_layer,
            n_head=hf.n_head,
            layer_norm_epsilon=hf.layer_norm_epsilon,
            remat=False,
        )
        p = prefix

        def stacked(key, transpose=False):
            return _stack(sd, p + "h.{}." + key, n_layer, transpose=transpose)

        params = {
            "wte": sd[p + "wte.weight"].astype(np.float32),
            "wpe": sd[p + "wpe.weight"].astype(np.float32),
            "blocks": {
                "ln1_g": stacked("ln_1.weight"),
                "ln1_b": stacked("ln_1.bias"),
                "qkv_w": stacked("attn.c_attn.weight"),
                "qkv_b": stacked("attn.c_attn.bias"),
                "proj_w": stacked("attn.c_proj.weight"),
                "proj_b": stacked("attn.c_proj.bias"),
                "ln2_g": stacked("ln_2.weight"),
                "ln2_b": stacked("ln_2.bias"),
                "fc_w": stacked("mlp.c_fc.weight"),
                "fc_b": stacked("mlp.c_fc.bias"),
                "fc_proj_w": stacked("mlp.c_proj.weight"),
                "fc_proj_b": stacked("mlp.c_proj.bias"),
            },
            "lnf_g": sd[p + "ln_f.weight"].astype(np.float32),
            "lnf_b": sd[p + "ln_f.bias"].astype(np.float32),
        }
        return cfg, params


class HFGPTNEOLayerPolicy(DSPolicy):
    """transformers GPT-Neo (reference ``replace_policy.py:102``).

    GPT-Neo uses separate (out, in) Linear q/k/v without biases for q/k/v
    weights' layout, so weights are transposed and q|k|v concatenated.
    Local-attention layers attend over a window of ``window_size``
    positions, which the GPT-2 layout this policy converts to does not
    compute: a model with a ``local`` layer whose window is shorter than
    its context is **refused by name** (full attention there would be
    another model's mathematics under this one's name).  A window that
    covers ``max_position_embeddings`` is full attention and converts.
    The band mask itself exists (``ops/transformer/inference.py``'s
    window forms, the Laguna family's); carrying a per-layer window
    through the GPT-2 block is ROADMAP R2's, not done.
    """

    architectures = ("GPTNeoForCausalLM", "GPTNeoModel")

    @classmethod
    def convert(cls, model, hf_config=None):
        from deepspeed_tpu.models.gpt2 import GPT2Config

        hf = hf_config if hf_config is not None else model.config
        local = [i for i, kind in enumerate(getattr(hf, "attention_layers", None) or ()) if kind == "local"]
        if local and int(getattr(hf, "window_size", 0) or 0) < int(hf.max_position_embeddings):
            raise NotImplementedError(
                f"HFGPTNEOLayerPolicy: layers {local} attend over a local window of {hf.window_size} positions "
                f"(context {hf.max_position_embeddings}); the GPT-2 layout computes full attention, which is not this model"
            )
        sd = _state_dict_of(model)
        prefix = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
        n_layer = hf.num_layers
        d = hf.hidden_size
        cfg = GPT2Config(
            vocab_size=hf.vocab_size,
            n_positions=hf.max_position_embeddings,
            n_embd=d,
            n_layer=n_layer,
            n_head=hf.num_heads,
            layer_norm_epsilon=hf.layer_norm_epsilon,
            remat=False,
        )
        p = prefix
        # HF GPT-Neo applies NO 1/sqrt(head_dim) attention scaling; our
        # attention paths always scale, so fold sqrt(head_dim) into the
        # query projection to cancel it.
        q_scale = float(np.sqrt(d // hf.num_heads))
        qkv_w, qkv_b, blocks = [], [], {}
        for i in range(n_layer):
            base = f"{p}h.{i}.attn.attention."
            parts_w = [sd[base + f"{n}_proj.weight"].T for n in ("q", "k", "v")]
            parts_w[0] = parts_w[0] * q_scale
            qkv_w.append(np.concatenate(parts_w, axis=1))
            parts_b = [
                np.asarray(sd.get(base + f"{n}_proj.bias", np.zeros(d, np.float32)), np.float32)
                for n in ("q", "k", "v")
            ]
            parts_b[0] = parts_b[0] * q_scale
            qkv_b.append(np.concatenate(parts_b))
        blocks["qkv_w"] = np.stack(qkv_w).astype(np.float32)
        blocks["qkv_b"] = np.stack(qkv_b).astype(np.float32)
        blocks["ln1_g"] = _stack(sd, p + "h.{}.ln_1.weight", n_layer)
        blocks["ln1_b"] = _stack(sd, p + "h.{}.ln_1.bias", n_layer)
        blocks["proj_w"] = _stack(sd, p + "h.{}.attn.attention.out_proj.weight", n_layer, transpose=True)
        blocks["proj_b"] = _stack(sd, p + "h.{}.attn.attention.out_proj.bias", n_layer)
        blocks["ln2_g"] = _stack(sd, p + "h.{}.ln_2.weight", n_layer)
        blocks["ln2_b"] = _stack(sd, p + "h.{}.ln_2.bias", n_layer)
        blocks["fc_w"] = _stack(sd, p + "h.{}.mlp.c_fc.weight", n_layer, transpose=True)
        blocks["fc_b"] = _stack(sd, p + "h.{}.mlp.c_fc.bias", n_layer)
        blocks["fc_proj_w"] = _stack(sd, p + "h.{}.mlp.c_proj.weight", n_layer, transpose=True)
        blocks["fc_proj_b"] = _stack(sd, p + "h.{}.mlp.c_proj.bias", n_layer)
        params = {
            "wte": sd[p + "wte.weight"].astype(np.float32),
            "wpe": sd[p + "wpe.weight"].astype(np.float32),
            "blocks": blocks,
            "lnf_g": sd[p + "ln_f.weight"].astype(np.float32),
            "lnf_b": sd[p + "ln_f.bias"].astype(np.float32),
        }
        return cfg, params


class HFBertLayerPolicy(DSPolicy):
    """transformers BERT (reference ``replace_policy.py:43``) → the
    post-LN BERT layout in ``models/bert.py``."""

    architectures = ("BertModel", "BertForMaskedLM", "BertForPreTraining", "BertForSequenceClassification")

    @classmethod
    def convert(cls, model, hf_config=None):
        from deepspeed_tpu.models.bert import BertConfig

        sd = _state_dict_of(model)
        hf = hf_config if hf_config is not None else model.config
        prefix = "bert." if any(k.startswith("bert.") for k in sd) else ""
        n_layer = hf.num_hidden_layers
        cfg = BertConfig(
            vocab_size=hf.vocab_size,
            max_position_embeddings=hf.max_position_embeddings,
            type_vocab_size=hf.type_vocab_size,
            hidden_size=hf.hidden_size,
            num_hidden_layers=n_layer,
            num_attention_heads=hf.num_attention_heads,
            intermediate_size=hf.intermediate_size,
            layer_norm_eps=hf.layer_norm_eps,
            pre_layer_norm=False,
            remat=False,
        )
        p = prefix + "encoder.layer.{}."
        qkv_w, qkv_b = [], []
        for i in range(n_layer):
            base = p.format(i) + "attention.self."
            qkv_w.append(np.concatenate([sd[base + f"{n}.weight"].T for n in ("query", "key", "value")], axis=1))
            qkv_b.append(np.concatenate([sd[base + f"{n}.bias"] for n in ("query", "key", "value")]))
        blocks = {
            "qkv_w": np.stack(qkv_w).astype(np.float32),
            "qkv_b": np.stack(qkv_b).astype(np.float32),
            "proj_w": _stack(sd, p + "attention.output.dense.weight", n_layer, transpose=True),
            "proj_b": _stack(sd, p + "attention.output.dense.bias", n_layer),
            "ln1_g": _stack(sd, p + "attention.output.LayerNorm.weight", n_layer),
            "ln1_b": _stack(sd, p + "attention.output.LayerNorm.bias", n_layer),
            "fc_w": _stack(sd, p + "intermediate.dense.weight", n_layer, transpose=True),
            "fc_b": _stack(sd, p + "intermediate.dense.bias", n_layer),
            "fc_proj_w": _stack(sd, p + "output.dense.weight", n_layer, transpose=True),
            "fc_proj_b": _stack(sd, p + "output.dense.bias", n_layer),
            "ln2_g": _stack(sd, p + "output.LayerNorm.weight", n_layer),
            "ln2_b": _stack(sd, p + "output.LayerNorm.bias", n_layer),
        }
        e = prefix + "embeddings."
        d = hf.hidden_size
        params = {
            "tok_emb": sd[e + "word_embeddings.weight"].astype(np.float32),
            "pos_emb": sd[e + "position_embeddings.weight"].astype(np.float32),
            "type_emb": sd[e + "token_type_embeddings.weight"].astype(np.float32),
            "emb_ln_g": sd[e + "LayerNorm.weight"].astype(np.float32),
            "emb_ln_b": sd[e + "LayerNorm.bias"].astype(np.float32),
            "blocks": blocks,
            "pooler_w": (
                sd[prefix + "pooler.dense.weight"].T.astype(np.float32)
                if prefix + "pooler.dense.weight" in sd
                else np.zeros((d, d), np.float32)
            ),
            "pooler_b": sd.get(prefix + "pooler.dense.bias", np.zeros(d, np.float32)).astype(np.float32),
            "mlm_dense_w": np.zeros((d, d), np.float32),
            "mlm_dense_b": np.zeros(d, np.float32),
            "mlm_ln_g": np.ones(d, np.float32),
            "mlm_ln_b": np.zeros(d, np.float32),
            "mlm_bias": np.zeros(hf.vocab_size, np.float32),
            "nsp_w": np.zeros((d, 2), np.float32),
            "nsp_b": np.zeros(2, np.float32),
        }
        # MLM head if present (BertForMaskedLM / ForPreTraining)
        mlm = "cls.predictions."
        if mlm + "transform.dense.weight" in sd:
            params["mlm_dense_w"] = sd[mlm + "transform.dense.weight"].T.astype(np.float32)
            params["mlm_dense_b"] = sd[mlm + "transform.dense.bias"].astype(np.float32)
            params["mlm_ln_g"] = sd[mlm + "transform.LayerNorm.weight"].astype(np.float32)
            params["mlm_ln_b"] = sd[mlm + "transform.LayerNorm.bias"].astype(np.float32)
            params["mlm_bias"] = sd[mlm + "bias"].astype(np.float32)
        if "cls.seq_relationship.weight" in sd:
            params["nsp_w"] = sd["cls.seq_relationship.weight"].T.astype(np.float32)
            params["nsp_b"] = sd["cls.seq_relationship.bias"].astype(np.float32)
        return cfg, params


class MegatronLayerPolicy(DSPolicy):
    """Megatron-LM GPT checkpoints (reference ``replace_policy.py:146``).

    Megatron stores transformer weights as (out, in) Linears under
    ``language_model.transformer.layers.N.*`` with fused
    query_key_value; row/column TP shards must be pre-merged (the
    checkpoint-loader's ``MegatronSDLoader.merge`` analog in
    inference/checkpoint.py does this).
    """

    architectures = ("GPT2Model_megatron", "MegatronGPT")

    @classmethod
    def matches(cls, model) -> bool:
        # Megatron checkpoints usually arrive as plain state dicts —
        # probe for the transformer key prefix.
        if isinstance(model, dict):
            return "language_model.transformer.layers.0.input_layernorm.weight" in model
        sd = model.state_dict() if hasattr(model, "state_dict") else {}
        return super().matches(model) or (
            "language_model.transformer.layers.0.input_layernorm.weight" in sd
        )

    @classmethod
    def convert(cls, model, hf_config=None):
        from deepspeed_tpu.models.gpt2 import GPT2Config

        sd = _state_dict_of(model)
        cfgsrc = hf_config if hf_config is not None else getattr(model, "config", None)
        p = "language_model.transformer.layers.{}."
        n_layer = 0
        while (p.format(n_layer) + "input_layernorm.weight") in sd:
            n_layer += 1
        if n_layer == 0:
            raise ValueError("not a Megatron GPT state dict (no transformer.layers.*)")
        wte = sd["language_model.embedding.word_embeddings.weight"].astype(np.float32)
        wpe = sd["language_model.embedding.position_embeddings.weight"].astype(np.float32)
        d = wte.shape[1]
        n_head = getattr(cfgsrc, "num_attention_heads", None) or max(1, d // 64)
        cfg = GPT2Config(
            vocab_size=wte.shape[0], n_positions=wpe.shape[0], n_embd=d,
            n_layer=n_layer, n_head=n_head, remat=False,
        )
        # Megatron stores the fused QKV output dim per-head interleaved:
        # (heads, 3, head_dim).  Our blocks expect contiguous q|k|v, so
        # permute to (3, heads, head_dim) (the reference's megatron
        # qkv-reorder in replace_module.py does the inverse on inject).
        hd = d // n_head

        def deinterleave_w(w):  # w: (d, 3d) after transpose, columns = outputs
            return w.reshape(d, n_head, 3, hd).transpose(0, 2, 1, 3).reshape(d, 3 * d)

        def deinterleave_b(b):
            return b.reshape(n_head, 3, hd).transpose(1, 0, 2).reshape(3 * d)

        qkv_w = _stack(sd, p + "attention.query_key_value.weight", n_layer, transpose=True)
        qkv_b = _stack(sd, p + "attention.query_key_value.bias", n_layer)
        blocks = {
            "ln1_g": _stack(sd, p + "input_layernorm.weight", n_layer),
            "ln1_b": _stack(sd, p + "input_layernorm.bias", n_layer),
            "qkv_w": np.stack([deinterleave_w(w) for w in qkv_w]),
            "qkv_b": np.stack([deinterleave_b(b) for b in qkv_b]),
            "proj_w": _stack(sd, p + "attention.dense.weight", n_layer, transpose=True),
            "proj_b": _stack(sd, p + "attention.dense.bias", n_layer),
            "ln2_g": _stack(sd, p + "post_attention_layernorm.weight", n_layer),
            "ln2_b": _stack(sd, p + "post_attention_layernorm.bias", n_layer),
            "fc_w": _stack(sd, p + "mlp.dense_h_to_4h.weight", n_layer, transpose=True),
            "fc_b": _stack(sd, p + "mlp.dense_h_to_4h.bias", n_layer),
            "fc_proj_w": _stack(sd, p + "mlp.dense_4h_to_h.weight", n_layer, transpose=True),
            "fc_proj_b": _stack(sd, p + "mlp.dense_4h_to_h.bias", n_layer),
        }
        params = {
            "wte": wte,
            "wpe": wpe,
            "blocks": blocks,
            "lnf_g": sd["language_model.transformer.final_layernorm.weight"].astype(np.float32),
            "lnf_b": sd["language_model.transformer.final_layernorm.bias"].astype(np.float32),
        }
        return cfg, params


# Generic-policy registry, walked in order (reference replace_policy.py
# keeps the same list-of-policies shape).
ALL_POLICIES = [HFGPT2LayerPolicy, HFGPTNEOLayerPolicy, HFBertLayerPolicy, MegatronLayerPolicy]


def replace_transformer_layer(model, policy: Optional[type] = None, hf_config=None):
    """Reference ``replace_transformer_layer`` (``replace_module.py:89``) —
    here: resolve a policy and convert the whole model to the fused
    native parameter layout.  Returns ``(model_config, params)``."""
    if policy is not None:
        return policy.convert(model, hf_config=hf_config)
    for pol in ALL_POLICIES:
        if pol.matches(model):
            logger.info(f"injection: matched policy {pol.__name__}")
            return pol.convert(model, hf_config=hf_config)
    raise ValueError(
        f"No injection policy for {type(model).__name__}; pass injection_policy= "
        f"(available: {[p.__name__ for p in ALL_POLICIES]})"
    )
