"""GigaChat3.5: a sixth decoder family, on the serving path.

A **hybrid** of two mixers — three gated-delta-rule layers to one
latent-attention layer (``full_attention_layers``) — with the
feed-forward kind chosen independently a layer (a dense SwiGLU in the
first ``first_k_dense_replace`` layers, a mixture of experts after), and
**four norms a layer** (``layernorm_type`` ``pre_post``: the sandwich)::

    x <- x + N(Mixer_l(N(x; w1)); w2)     l in full_attention_layers: gated latent attention (MLA)
                                          else: the gated delta rule, ops/transformer/linear_attention.py
    x <- x + N(FFN_l(N(x; w3)); w4)       l < first_k_dense_replace: SwiGLU; else sigmoid router, top-k, one shared expert

``N(x; w) = x rsqrt(mean(x^2) + eps) * g sigmoid(w)`` with ``g =
layernorm_gating_weight`` = 2 (``ZeroCenteredGatedNorm``: ``w = 0`` is
gain 1).  No bias anywhere, an untied head; every SwiGLU is clamped
(``swiglu_limit``: ``moe/layer.py::swiglu_gate``).  The family is *told
its share* like DeepSeek-V2 (``experts_held``, ``vocab_held``): the
router keeps its published width, what absent experts would add is left
out.  The multi-token-prediction modules (``num_nextn_predict_layers``)
are no part of the next-token forward and are not implemented.

**Latent attention** (the DeepSeek-V3 form, as ``models/deepseek_v2.py``
at other numbers): ``c_q = RMS(u W_qa)``, ``q = c_q W_qb`` per head
``[q_nope | q_pe]``; ``[c_kv | k_pe] = u W_kva``, ``c_kv <- RMS(c_kv)``;
rotary with YaRN on ``q_pe``, ``k_pe`` (half layout); ``[k_nope | v] =
c_kv W_kvb``; causal softmax at ``(nope + rope)^-1/2 m^2``, ``m = 0.1
mscale_all_dim ln(factor) + 1`` (``use_mla_scaling_factor``); ``y =
(sigmoid(u W_g) * o) W_o`` with an elementwise gate ``W_g: D -> H v``
(``gated_attention``).  The cache holds ``[c_kv | k_pe]`` a position.

**Gated delta rule** (Gated DeltaNet, arXiv:2412.06464): ``[q | k | v] =
SiLU(conv([u W_q | u W_k | u W_v]))``, one causal depthwise convolution
over all ``2 Hk dk + Hv dv`` channels; ``z = u W_z``, ``beta = sigmoid(u
W_b)``, ``g = -exp(A_log) softplus(u W_a + dt_bias)`` — **one scalar a
value head**; ``q, k`` L2-normalised a head, ``q`` scaled by ``dk^-1/2``;
value head ``h`` reads query / key head ``h // (Hv / Hk)``; per value
head ``S <- exp(g) S``, ``S <- S + k (beta (v - S^T k))^T``, ``o = S^T
q``; ``y = (RMS_head(o) (1 + w_n) * gs sigmoid(z)) W_o`` with ``gs =
linear_sigmoid_gate_scale``.

Serving runs through ``ServingEngine`` on a **hybrid cache over latent
pages** (``serving/kvcache/pages.py::HybridKV`` over ``LatentKV``): one
latent buffer for the latent-attention layers only, and per slot a
float32 recurrent state + the convolution's last inputs for every
delta-rule layer.  A prefill chunk runs the delta-rule layers in the
chunked form from the slot's state — from **zero where the chunk starts
at position 0** — and latent attention in the expanded form; a decode
step runs the recurrence in place (``gdn_decode`` on the chip) and the
absorbed form (``mla_decode_paged``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.deepseek_v2 import apply_rope, rms_norm, rope_cos_sin, seeded_tree

CAUSAL_LM = True
# DeepSeek-V2's deployment and its leaves' names: ``experts_gu`` / ``experts_down`` (held, ...) over ``expert``,
# ``embed`` and ``head`` (vocabulary, hidden) over the vocabulary, both mixers, shared experts, router and norms replicated
PARTITION_RULES = "deepseek_v2"


@dataclasses.dataclass(frozen=True)
class GigaChat35Config:
    """The published ``config.json`` keys that shape the model
    (``rope_scaling`` flattened to ``rope_*``), plus the share held here."""

    vocab_size: int = 128256
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 40
    full_attention_layers: Tuple[int, ...] = tuple(range(3, 40, 4))
    first_k_dense_replace: int = 3
    # latent attention
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 100000.0
    rope_factor: float = 8.0
    rope_original_max_position_embeddings: int = 32768
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # the gated delta rule
    linear_num_key_heads: int = 32
    linear_num_value_heads: int = 64
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_sigmoid_gate_scale: float = 2.0
    linear_attn_o_norm_eps: float = 1e-6
    # feed-forward
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    swiglu_limit: float = 10.0
    # norms
    layernorm_gating_weight: float = 2.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    num_nextn_predict_layers: int = 0
    # the share held here; None = everything
    experts_held: Optional[Tuple[int, int]] = None  # (first, count)
    vocab_held: Optional[int] = None  # rows 0 .. vocab_held - 1

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held={self.experts_held} outside the {self.n_routed_experts} routed experts")
        if not 1 <= self.vocab_rows <= self.vocab_size:
            raise ValueError(f"vocab_held={self.vocab_held} outside the vocabulary of {self.vocab_size}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds num_hidden_layers")
        if any(not 0 <= l < self.num_hidden_layers for l in self.full_attention_layers):
            raise ValueError(f"full_attention_layers={self.full_attention_layers} outside the {self.num_hidden_layers} layers")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_value_heads is not a multiple of linear_num_key_heads")
        if self.num_nextn_predict_layers:
            raise ValueError("multi-token-prediction modules are not implemented (no part of the next-token forward): "
                             "num_nextn_predict_layers must be 0")

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "GigaChat35Config":
        """From the keys of a published ``config.json``; ``share`` gives
        ``experts_held`` / ``vocab_held`` and may cut the depth
        (``full_attention_layers`` then keeps the layers that remain).
        What this family does not implement is refused."""
        rs = hf.get("rope_scaling") or {}
        refused = [why for bad, why in (
            (hf.get("norm_type", "ZeroCenteredGatedNorm") != "ZeroCenteredGatedNorm", f"norm_type {hf.get('norm_type')!r}"),
            (hf.get("layernorm_type", "pre_post") != "pre_post", f"layernorm_type {hf.get('layernorm_type')!r}"),
            (not hf.get("gated_attention", True), "gated_attention false"),
            (not hf.get("use_mla_scaling_factor", True), "use_mla_scaling_factor false"),
            (hf.get("linear_attention_type", "GigaChat35GatedDeltaNet") != "GigaChat35GatedDeltaNet",
             f"linear_attention_type {hf.get('linear_attention_type')!r}"),
            (hf.get("linear_gating_type", "gated_rmsnorm_sigmoid_zero_centered") != "gated_rmsnorm_sigmoid_zero_centered",
             f"linear_gating_type {hf.get('linear_gating_type')!r}"),
            (hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1, "grouped routing (n_group / topk_group > 1)"),
            (hf.get("use_shared_expert_sigmoid", False), "use_shared_expert_sigmoid"),
            (hf.get("tie_word_embeddings", False), "tie_word_embeddings"),
            (hf.get("attention_bias", False), "attention_bias"),
            (hf.get("hidden_act", "silu") != "silu", f"hidden_act {hf.get('hidden_act')!r}"),
            (bool(rs) and rs.get("type") != "yarn", f"rope_scaling type {rs.get('type')!r}"),
        ) if bad]
        if refused:
            raise ValueError("GigaChat35Config: not implemented: " + "; ".join(refused))
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        for k in ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim"):
            if k in rs:
                kw["rope_" + k] = rs[k]
        kw.update(share)
        depth = kw.get("num_hidden_layers", cls.num_hidden_layers)
        kw["full_attention_layers"] = tuple(int(l) for l in kw.get("full_attention_layers", cls.full_attention_layers) if l < depth)
        if kw.get("experts_held") is not None:
            kw["experts_held"] = tuple(int(v) for v in kw["experts_held"])
        return cls(**kw)

    # -- derived ------------------------------------------------------------
    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held if self.experts_held is not None else (0, self.n_routed_experts)

    @property
    def vocab_rows(self) -> int:
        return self.vocab_held if self.vocab_held is not None else self.vocab_size

    @property
    def linear_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_hidden_layers) if l not in self.full_attention_layers)

    @property
    def cache_width(self) -> int:
        """Numbers a latent-attention layer caches a position: one row for all heads."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0 if self.rope_factor > 1 else 1.0
        return self.qk_head_dim ** -0.5 * m * m

    @property
    def linear_qk_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_v_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def linear_conv_width(self) -> int:
        """Channels of a delta-rule layer's one convolution: q | k | v."""
        return 2 * self.linear_qk_width + self.linear_v_width

    # what the engines read of any causal-LM family
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings


# tests and chip_smoke.py: every mechanism present (a dense layer, two periods, 2 value heads a key head, 16 experts), nothing wide
GIGACHAT35_TINY = GigaChat35Config(
    vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=8,
    full_attention_layers=(3, 7), first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_original_max_position_embeddings=64,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, max_position_embeddings=4096,
)

# the four gains of a layer's sandwich, in order of use
NORMS = ("mixer_in_w", "mixer_out_w", "ffn_in_w", "ffn_out_w")


def gated_norm(x, w, eps: float, scale: float):
    """``N(x; w) = x rsqrt(mean(x^2) + eps) * scale sigmoid(w)``: an
    RMSNorm whose gain is a sigmoid gate of the learned vector (``scale``
    2: ``w = 0`` is gain 1)."""
    x32 = x.astype(jnp.float32)
    gain = scale * jax.nn.sigmoid(w.astype(jnp.float32))
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps) * gain).astype(x.dtype)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: GigaChat35Config) -> Dict[str, Any]:
    """The parameter tree as shapes; the conventions of
    ``models/deepseek_v2.py``: ``layers`` a list with one dict a layer,
    gate and up projections one matrix (``*_gu``, gate columns first), a
    layer's held experts stacked on a leading ``held`` dim, q | k | v of
    a delta-rule mixer one matrix (``qkv``).  A layer's dict is the
    union of its mixer's leaves and its feed-forward's."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    F, Fe, Fs, held = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.moe_intermediate_size * cfg.n_shared_experts, cfg.held[1]
    norms = {name: (D,) for name in NORMS}
    mla = {"q_a": (D, cfg.q_lora_rank), "q_a_norm": (cfg.q_lora_rank,), "q_b": (cfg.q_lora_rank, H * cfg.qk_head_dim),
           "kv_a": (D, cfg.cache_width), "kv_a_norm": (cfg.kv_lora_rank,),
           "kv_b": (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
           "gate": (D, H * cfg.v_head_dim), "o": (H * cfg.v_head_dim, D)}
    gdn = {"qkv": (D, cfg.linear_conv_width), "conv": (cfg.linear_conv_kernel_dim, cfg.linear_conv_width),
           "z": (D, cfg.linear_v_width), "b": (D, Hv), "a": (D, Hv), "dt_bias": (Hv,), "A_log": (Hv,),
           "o_norm_w": (dv,), "o": (cfg.linear_v_width, D)}
    dense = {"mlp_gu": (D, 2 * F), "mlp_down": (F, D)}
    moe = {"router": (D, cfg.n_routed_experts), "router_bias": (cfg.n_routed_experts,),
           "shared_gu": (D, 2 * Fs), "shared_down": (Fs, D),
           "experts_gu": (held, D, 2 * Fe), "experts_down": (held, Fe, D)}
    return {"embed": (cfg.vocab_rows, D), "head": (cfg.vocab_rows, D), "final_w": (D,),
            "layers": [{**norms, **(mla if l in cfg.full_attention_layers else gdn),
                        **(dense if l < cfg.first_k_dense_replace else moe)} for l in range(cfg.num_hidden_layers)]}


def special_leaf(name: str, key, shape) -> Optional[jnp.ndarray]:
    """The leaves that are not a normal(0.02) matrix: every gain of ``N``
    and the delta rule's output-norm gain normal(0, 0.5) — **not 0**, so
    that ``2 sigmoid(w)`` and ``1 + w`` are different functions of what
    was drawn —, ``A_log`` and ``dt_bias`` drawn so that a head's decay
    is neither 0 nor 1 (``exp(A_log)`` uniform in [1, 16],
    ``softplus(dt_bias)`` log-uniform in [0.001, 0.1]), the convolution
    taps normal(0.5), the router's selection bias 0."""
    if name in NORMS or name in ("final_w", "o_norm_w"):
        return jax.random.normal(key, shape, jnp.float32) * 0.5
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(0.001), np.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    if name == "conv":
        return jax.random.normal(key, shape, jnp.float32) * 0.5
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    return None


def init_params_device(cfg: GigaChat35Config, seed: int = 0, dtype=jnp.bfloat16, std: float = 0.02):
    """Random parameters made on the default device, one leaf at a time
    (``deepseek_v2.seeded_tree``; :func:`special_leaf` for the rest)."""
    return seeded_tree(param_shapes(cfg), cfg.num_hidden_layers, seed, dtype, std,
                       residual=("o", "mlp_down", "shared_down", "experts_down"), special=special_leaf)


def init_params(cfg: GigaChat35Config, seed: int = 0):
    """Host float32 tree (small configurations: tests, the engine's
    default when it is handed no parameters)."""
    return jax.tree.map(np.asarray, init_params_device(cfg, seed=seed, dtype=jnp.float32))


def cache_kind(cfg: GigaChat35Config, dtype):
    """The family's cache kind for :class:`PagedKVPool`: latent pages of
    the latent-attention layers, a per-slot state of the delta-rule layers."""
    from deepspeed_tpu.serving.kvcache.pages import HybridKV, LatentKV

    n = len(cfg.linear_layers)
    return HybridKV(len(cfg.full_attention_layers), LatentKV(cfg.cache_width, dtype), {
        "s": (n, (cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim), jnp.float32),
        "conv": (n, (cfg.linear_conv_kernel_dim - 1, cfg.linear_conv_width), dtype)})


# ---------------------------------------------------------------------------
# forward on the hybrid cache
# ---------------------------------------------------------------------------

def mla_mixer(cfg: GigaChat35Config, lp: Dict[str, Any], u, pool, paged_layer: int, pos, page_table,
              write_mask=None, use_kernel: Optional[bool] = None, trace_notes: Optional[dict] = None):
    """Gated latent attention of ``u (B, T, D)``, the layer's normed
    input, at per-row write offsets ``pos (B,)``: writes the rows'
    latents into ``pool[paged_layer]`` through ``page_table`` and attends
    over the cache — absorbed for ``T == 1`` (decode), expanded otherwise
    (a prefill chunk).  Returns ``(y (B, T, D), pool)``."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.kernels.mla_decode import mla_decode_supported
    from deepspeed_tpu.ops.transformer import latent_attention as la

    B, T, _ = u.shape
    H, dn, dr, dv, dc = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
    q = (rms_norm(u @ lp["q_a"], lp["q_a_norm"], cfg.rms_norm_eps) @ lp["q_b"]).reshape(B, T, H, dn + dr)
    kv = u @ lp["kv_a"]
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    cos, sin = rope_cos_sin(cfg, positions)  # (B, T, dr/2)
    q_pe = apply_rope(q[..., dn:], cos[:, :, None, :], sin[:, :, None, :])
    row = jnp.concatenate([rms_norm(kv[..., :dc], lp["kv_a_norm"], cfg.rms_norm_eps),
                           apply_rope(kv[..., dc:], cos, sin)], axis=-1)  # (B, T, dc + dr): what is cached
    pool = la.latent_cache_write(pool, paged_layer, row, page_table, pos, write_mask)
    w_kvb = lp["kv_b"].reshape(dc, H, dn + dv)
    with jax.named_scope("mla.attend"):
        if T == 1:
            armed = _kernels.flash_decode_armed() if use_kernel is None else use_kernel
            fits = mla_decode_supported(H, pool.shape[3], pool.shape[2], dc)
            if trace_notes is not None:
                why_not = "" if armed and fits else ("kernel suite not armed" if not armed else f"unsupported page geometry (page_len {pool.shape[3]})")
                trace_notes.update(mla_decode_kernel=not why_not, mla_decode_fallback=why_not)
            attn = la.absorbed_attention(q[..., :dn], q_pe, pool, paged_layer, page_table, pos, w_kvb, dn,
                                         cfg.softmax_scale, use_kernel=armed)
        else:
            attn = la.expanded_attention(q[..., :dn], q_pe, pool, paged_layer, page_table, pos, w_kvb, dn, cfg.softmax_scale,
                                         use_kernel=use_kernel, trace_notes=trace_notes)
            if trace_notes is not None:
                trace_notes["mla_prefill_form"] = ("mla_prefill (expanded, block by block)" if trace_notes["mla_prefill_kernel"] else
                                                   f"blockwise jnp (expanded_attention): {trace_notes['mla_prefill_fallback']}")
    gate = jax.nn.sigmoid(u @ lp["gate"])
    return (gate * attn.reshape(B, T, H * dv)) @ lp["o"], pool


def gdn_mixer(cfg: GigaChat35Config, lp: Dict[str, Any], u, state: Dict[str, Any], state_layer: int, pos, slot=None,
              write_mask=None, row_valid=None, use_kernel: Optional[bool] = None, trace_notes: Optional[dict] = None):
    """The gated delta rule of ``u (B, T, D)``, the layer's normed input,
    on layer ``state_layer`` of the per-slot ``state``.

    ``slot (B,)`` given: a **prefill chunk** of the slots named — the
    chunked form from each slot's state (zero where ``pos == 0``: a
    fresh request), the state left untouched by tokens whose
    ``row_valid`` is False, the convolution's memory at the last valid
    inputs.  ``slot`` None: a **decode step**, row ``b`` is slot ``b``
    and rows with ``write_mask`` False keep their state.  Returns ``(y
    (B, T, D), state)``."""
    from deepspeed_tpu.ops.transformer import linear_attention as la
    from deepspeed_tpu.ops.transformer.inference import state_rows, state_rows_write

    B, T, _ = u.shape
    Hk, Hv, dk, dv = cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    Wk = cfg.linear_qk_width
    f32 = jnp.float32
    decode = slot is None
    with jax.named_scope("gdn.conv"):
        qkv = u @ lp["qkv"]
        if decode:
            conv0 = state["conv"][state_layer]
            n_valid = None
        else:
            fresh = (pos == 0)
            conv0 = jnp.where(fresh[:, None, None], 0, state_rows(state["conv"], state_layer, slot))
            n_valid = None if row_valid is None else jnp.sum(row_valid.astype(jnp.int32), axis=1)
        y, conv1 = la.short_conv(qkv, lp["conv"], conv0, n_valid)
    q = la.l2norm(y[..., :Wk].reshape(B, T, Hk, dk)) * dk ** -0.5
    k = la.l2norm(y[..., Wk: 2 * Wk].reshape(B, T, Hk, dk))
    v = y[..., 2 * Wk:].reshape(B, T, Hv, dv)
    g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus((u @ lp["a"]).astype(f32) + lp["dt_bias"].astype(f32))  # (B, T, Hv)
    beta = jax.nn.sigmoid((u @ lp["b"]).astype(f32))
    if decode:
        mask = jnp.ones((B,), bool) if write_mask is None else write_mask.astype(bool)
        with jax.named_scope("gdn.step"):
            o, s = la.decode_step(state["s"], state_layer, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], mask,
                                  use_kernel=use_kernel, trace_notes=trace_notes)
        o = o[:, None]
        conv = state["conv"].at[state_layer].set(jnp.where(mask[:, None, None], conv1, conv0))
    else:
        if row_valid is not None:
            # a chunk's padded tail: beta = 0 and g = 0 leave the state as it was
            g = jnp.where(row_valid[:, :, None], g, 0.0)
            beta = jnp.where(row_valid[:, :, None], beta, 0.0)
        s0 = jnp.where(fresh[:, None, None, None], 0.0, state_rows(state["s"], state_layer, slot))
        with jax.named_scope("gdn.chunk"):
            o, s1 = la.chunked(s0, la.share_heads(q, Hv, 2), la.share_heads(k, Hv, 2), v, g[..., None], beta)
        if trace_notes is not None:
            trace_notes["gdn_prefill_form"] = f"chunked jnp, scalar decay (chunks of {min(la.CHUNK, T)})"
        s = state_rows_write(state["s"], state_layer, slot, s1)
        conv = state_rows_write(state["conv"], state_layer, slot, conv1)
    # a zero-centred gain and a sigmoid gate scaled by ``linear_sigmoid_gate_scale``
    o32 = o.astype(f32)
    o32 = o32 * jax.lax.rsqrt(jnp.mean(jnp.square(o32), -1, keepdims=True) + cfg.linear_attn_o_norm_eps) * (1.0 + lp["o_norm_w"].astype(f32))
    gate = cfg.linear_sigmoid_gate_scale * jax.nn.sigmoid((u @ lp["z"]).astype(f32)).reshape(B, T, Hv, dv)
    return (o32 * gate).reshape(B, T, Hv * dv).astype(u.dtype) @ lp["o"], {"s": s, "conv": conv}


def _swiglu(x, w_gu, w_down, limit):
    from deepspeed_tpu.moe.layer import swiglu_gate

    g, u = jnp.split(x @ w_gu, 2, axis=-1)
    return swiglu_gate(g, u, limit) @ w_down


def forward_with_cache(params: Dict[str, Any], tokens, pool, state, pos, cfg: GigaChat35Config, page_table,
                       slot=None, write_mask=None, row_valid=None, take=None, use_kernel: Optional[bool] = None,
                       routing_sink: Optional[list] = None, trace_notes: Optional[dict] = None):
    """One network step on the hybrid cache over latent pages.

    ``tokens (B, T)``; ``pool`` the ``(latent layers, pages, width,
    page_len)`` buffer; ``state`` the per-slot group ``{"s", "conv"}``
    (``HybridKV.state_buffers``); ``pos (B,)`` per-row write offsets;
    ``page_table (B, pages_per_slot)``.  ``slot (B,)`` names the slots of
    a **prefill chunk**'s rows; ``slot`` None is a **decode step** (row
    ``b`` is slot ``b``), where ``write_mask (B,)`` False sends a row's
    latent write to the garbage page and leaves its state alone.
    ``row_valid (B, T)`` marks the real tokens (a chunk's padded tail is
    computed, and kept out of the state and the counters); ``take (B,)``
    picks the position whose logits are wanted (default: the last).
    Returns ``(logits (B, V) float32, pool, state, aux)`` with ``aux
    (expert layers, held + 1) int32`` as
    ``deepseek_v2.forward_with_cache`` returns it.  ``routing_sink`` is
    given each expert layer's chosen experts ``(B * T, top_k)``."""
    from deepspeed_tpu.moe.layer import dropless_held_experts, sigmoid_topk

    B, T = tokens.shape
    eps, gs, limit = cfg.rms_norm_eps, cfg.layernorm_gating_weight, cfg.swiglu_limit
    N = lambda t, w: gated_norm(t, w, eps, gs)  # noqa: E731
    x = jnp.take(params["embed"], tokens, axis=0)
    valid = None if row_valid is None else row_valid.reshape(B * T)
    aux = []
    paged_layer = state_layer = 0
    for layer, lp in enumerate(params["layers"]):
        u = N(x, lp["mixer_in_w"])
        if layer in cfg.full_attention_layers:
            y, pool = mla_mixer(cfg, lp, u, pool, paged_layer, pos, page_table, write_mask, use_kernel, trace_notes)
            paged_layer += 1
        else:
            y, state = gdn_mixer(cfg, lp, u, state, state_layer, pos, slot, write_mask, row_valid, use_kernel, trace_notes)
            state_layer += 1
        x = x + N(y, lp["mixer_out_w"])
        r = N(x, lp["ffn_in_w"])
        if "mlp_gu" in lp:  # a dense layer
            x = x + N(_swiglu(r, lp["mlp_gu"], lp["mlp_down"], limit), lp["ffn_out_w"])
            continue
        flat = r.reshape(B * T, -1)
        with jax.named_scope("moe.router"):
            logits = jnp.dot(flat.astype(jnp.float32), lp["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
            idx, w = sigmoid_topk(logits, lp["router_bias"], cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.norm_topk_prob)
        if trace_notes is not None:
            trace_notes["moe_router_form"] = "sigmoid_topk (float32, highest; renormalised, scaled)"
        if routing_sink is not None:
            routing_sink.append(idx)
        routed, counts = dropless_held_experts(flat, idx, w, lp["experts_gu"], lp["experts_down"], cfg.held, valid,
                                               trace_notes=trace_notes, swiglu_limit=limit)
        ffn = (routed + _swiglu(flat, lp["shared_gu"], lp["shared_down"], limit)).reshape(x.shape)
        x = x + N(ffn, lp["ffn_out_w"])
        aux.append(counts)
    take = jnp.full((B,), T - 1, jnp.int32) if take is None else take
    last = jnp.take_along_axis(x, take[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(N(last, params["final_w"]), params["head"].T, preferred_element_type=jnp.float32)
    aux = jnp.stack(aux) if aux else jnp.zeros((0, cfg.held[1] + 1), jnp.int32)
    return logits, pool, state, aux


def serving_forward(cfg: GigaChat35Config):
    """The family seam of ``ServingEngine`` (docs/serving.md §Model
    families): ``fwd(params, tokens, k, v, pos, page_table=, write_mask=,
    row_valid=, take=, state=, slot=) -> (logits, k, v, state, aux)`` —
    ``k`` is the latent pool of the latent-attention layers, ``v`` None
    (the page kind has no second buffer), ``state`` the delta-rule
    layers' per-slot group.  ``slot`` is the prefill chunk's slot (a
    decode step passes None: its rows are the slots).
    ``fwd.trace_notes`` holds the forms the two programs compiled:
    ``gdn_decode_kernel`` / ``_fallback``, ``gdn_prefill_form``,
    ``mla_decode_kernel`` / ``_fallback``, ``mla_prefill_kernel`` /
    ``_fallback``, ``mla_prefill_form``, ``moe_router_form``,
    ``moe_grouped_kernel`` / ``_fallback``."""
    notes: Dict[str, Any] = {}

    def fwd(params, tokens, k, v, pos, page_table, write_mask=None, row_valid=None, take=None, state=None, slot=None):
        logits, k, state, aux = forward_with_cache(params, tokens, k, state, pos, cfg, page_table, slot=slot,
                                                   write_mask=write_mask, row_valid=row_valid, take=take, trace_notes=notes)
        return logits, k, v, state, aux

    fwd.trace_notes = notes
    return fwd
