"""DeepSeek-V2: a second decoder family, on the serving path.

Multi-head **latent** attention (MLA), a dense SwiGLU layer followed by
mixture-of-experts layers (softmax router over all routed experts,
group-limited greedy top-k, shared experts), RMSNorm, rotary positions
with YaRN, no bias anywhere, an untied output head.

The family is *told its share*: ``experts_held = (first, count)`` names
the routed experts this chip holds of every expert layer (the router
keeps its published width; what the absent experts would have added is
left out, there is no stand-in for the absent chips), ``vocab_held``
the rows of the vocabulary (a sliced vocabulary is a smaller one).

Serving runs through ``ServingEngine`` on a **paged latent cache**: one
buffer ``(layers, pages, kv_lora_rank + qk_rope_head_dim, page_len)``
holding ``[RMS(c_kv) | RoPE(k_pe)]`` per position, one row for all
heads (``serving/kvcache/pages.py::LatentKV``).  A prefill chunk
attends in the *expanded* form (K and V rebuilt per head from the
cached latents, block by block over the chunk's context), a decode
step in the *absorbed* form (``W_UK`` folded into the query, ``W_UV``
into the output; ``ops/kernels/mla_decode.py`` on the chip).  Both
read and write the pool in the one layout; neither copies it.

RoPE layout: the rotary dimensions are used in the *half* layout
(dimension ``i`` pairs with ``i + rope/2``).  The published checkpoint
stores them interleaved and permutes at run time; loading published
weights means applying that permutation to the rope columns of ``q_b``
and ``kv_a`` once.  With seeded weights it is a relabelling.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CAUSAL_LM = True
PARTITION_RULES = "deepseek_v2"  # the family's table in sharding/rules.py


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """The published ``config.json`` keys that shape the model, plus the
    share held here."""

    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 163840
    # rope_scaling (type "yarn")
    rope_factor: float = 40.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    # the share held here; None = everything
    experts_held: Optional[Tuple[int, int]] = None  # (first, count)
    vocab_held: Optional[int] = None  # rows 0 .. vocab_held - 1

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError(f"n_routed_experts={self.n_routed_experts} is not a multiple of n_group={self.n_group}")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held={self.experts_held} outside the {self.n_routed_experts} routed experts")
        if not 1 <= self.vocab_rows <= self.vocab_size:
            raise ValueError(f"vocab_held={self.vocab_held} outside the vocabulary of {self.vocab_size}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds num_hidden_layers")

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "DeepseekV2Config":
        """From the keys of a published ``config.json`` (unknown keys are
        ignored); ``share`` gives ``experts_held`` / ``vocab_held`` and
        may cut ``num_hidden_layers``."""
        if hf.get("topk_method", "group_limited_greedy") != "group_limited_greedy" or \
                hf.get("scoring_func", "softmax") != "softmax" or hf.get("moe_layer_freq", 1) != 1:
            raise ValueError("only softmax scoring, group_limited_greedy routing and moe_layer_freq 1 are implemented")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        rs = hf.get("rope_scaling") or {}
        if rs and rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {rs.get('type')!r}: only yarn is implemented")
        for k in ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim"):
            if k in rs:
                kw["rope_" + k] = rs[k]
        kw.update(share)
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
    def n_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def cache_width(self) -> int:
        """Numbers cached per position and layer: one row for all heads."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0 if self.rope_factor > 1 else 1.0
        return self.qk_head_dim ** -0.5 * m * m

    # what the engines read of any causal-LM family
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings


# tests and chip_smoke.py: every mechanism present, nothing wide
DEEPSEEK_V2_TINY = DeepseekV2Config(
    vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=16, n_shared_experts=2, num_experts_per_tok=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.0, max_position_embeddings=4096, rope_original_max_position_embeddings=64,
)


# ---------------------------------------------------------------------------
# rotary positions with YaRN
# ---------------------------------------------------------------------------

def yarn_inv_freq(cfg: DeepseekV2Config) -> np.ndarray:
    """Per frequency pair ``i``: the base frequency where the pair turns
    often within the original context (``i < lo``), the base over
    ``factor`` where it turns less than once (``i > hi``), a linear ramp
    between."""
    dim = cfg.qk_rope_head_dim
    base = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return base.astype(np.float32)

    def correction(beta):
        return dim * math.log(cfg.rope_original_max_position_embeddings / (beta * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    lo = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    hi = min(math.ceil(correction(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    m = 1.0 - ramp
    return ((1.0 - m) * base / cfg.rope_factor + m * base).astype(np.float32)


def rope_cos_sin(cfg: DeepseekV2Config, positions) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``cos, sin`` of shape ``positions.shape + (rope/2,)``, float32.
    With ``mscale == mscale_all_dim`` the published multiplier on both
    is 1 (the scale sits in :attr:`DeepseekV2Config.softmax_scale`)."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(yarn_inv_freq(cfg))
    mult = 1.0
    if cfg.rope_factor > 1 and cfg.rope_mscale != cfg.rope_mscale_all_dim:
        g = lambda s: 0.1 * s * math.log(cfg.rope_factor) + 1.0  # noqa: E731
        mult = g(cfg.rope_mscale) / g(cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * mult, jnp.sin(ang) * mult


def apply_rope(x, cos, sin):
    """Rotate the last dimension of ``x`` (half layout) — ``cos``/``sin``
    broadcast against ``x[..., : rope/2]``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def rms_norm(x, g, eps: float):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps) * g.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: DeepseekV2Config) -> Dict[str, Any]:
    """The parameter tree as shapes.  ``layers`` is a list, one dict a
    layer (the dense ones first): nothing is stacked over layers, so a
    layer's matrices reach a kernel as the buffers they are — a static
    slice of a stacked expert matrix fed to the grouped matmul is a copy
    of 1.9 GB a layer.  Gate and up projections are one matrix (``*_gu``:
    gate columns first); a layer's routed experts are stacked on a
    leading ``held`` dim."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    held = cfg.held[1]
    attn = {
        "attn_norm": (D,), "q_a": (D, cfg.q_lora_rank), "q_a_norm": (cfg.q_lora_rank,),
        "q_b": (cfg.q_lora_rank, H * cfg.qk_head_dim),
        "kv_a": (D, cfg.cache_width), "kv_a_norm": (cfg.kv_lora_rank,),
        "kv_b": (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o": (H * cfg.v_head_dim, D), "ffn_norm": (D,),
    }
    F, Fe, Fs = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.moe_intermediate_size * cfg.n_shared_experts
    dense = {**attn, "mlp_gu": (D, 2 * F), "mlp_down": (F, D)}
    moe = {**attn, "router": (D, cfg.n_routed_experts), "shared_gu": (D, 2 * Fs), "shared_down": (Fs, D),
           "experts_gu": (held, D, 2 * Fe), "experts_down": (held, Fe, D)}
    return {"embed": (cfg.vocab_rows, D), "head": (cfg.vocab_rows, D), "norm_f": (D,),
            "layers": [dict(dense) for _ in range(cfg.n_dense_layers)] + [dict(moe) for _ in range(cfg.n_moe_layers)]}


def seeded_tree(shapes: Dict[str, Any], n_layers: int, seed: int, dtype, std: float = 0.02,
                residual: Tuple[str, ...] = ("o", "mlp_down", "shared_down", "experts_down"), special=None):
    """A tree of shapes (leaves are tuples) as random parameters made on
    the default device, one leaf at a time (no float32 copy of the whole
    tree ever exists): normal(``std``), the ``residual`` projections
    scaled by ``1 / sqrt(2 n_layers)``, every ``*norm`` / ``norm_f`` gain
    1.  ``special(name, key, shape)`` may return a leaf's float32 values
    itself (None: the rule above).  Shared by the decoder families."""
    proj = std / math.sqrt(2 * n_layers)
    key = jax.random.PRNGKey(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = str(path[-1].key)
        if name.endswith("norm") or name == "norm_f":
            leaves.append(jnp.ones(shape, dtype))
            continue
        own = special(name, jax.random.fold_in(key, i), shape) if special is not None else None
        if own is None:
            own = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * (proj if name in residual else std)
        leaves.append(own.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def init_params_device(cfg: DeepseekV2Config, seed: int = 0, dtype=jnp.bfloat16, std: float = 0.02):
    """Random parameters made on the default device (:func:`seeded_tree`)."""
    return seeded_tree(param_shapes(cfg), cfg.num_hidden_layers, seed, dtype, std)


def init_params(cfg: DeepseekV2Config, seed: int = 0):
    """Host float32 tree (small configurations: tests, the engine's
    default when it is handed no parameters)."""
    return jax.tree.map(np.asarray, init_params_device(cfg, seed=seed, dtype=jnp.float32))


def cache_kind(cfg: DeepseekV2Config, dtype):
    """The family's cache kind for :class:`PagedKVPool`: one latent row
    per position and layer."""
    from deepspeed_tpu.serving.kvcache.pages import LatentKV

    return LatentKV(cfg.cache_width, dtype)


# ---------------------------------------------------------------------------
# forward with the paged latent cache
# ---------------------------------------------------------------------------

def _swiglu(x, w_gu, w_down):
    gu = x @ w_gu
    g, u = jnp.split(gu, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_down


def mla_block(cfg: DeepseekV2Config, lp: Dict[str, Any], x, pool, layer: int, pos, page_table,
              write_mask=None, use_kernel: Optional[bool] = None, trace_notes: Optional[dict] = None):
    """``x + MLA(RMS(x))`` for ``x (B, T, D)`` at per-row write offsets
    ``pos (B,)``: writes the rows' latents into ``pool[layer]`` through
    ``page_table`` and attends over the cache — absorbed for ``T == 1``
    (decode), expanded otherwise (a prefill chunk, which tells
    ``trace_notes`` the form it took)."""
    from deepspeed_tpu.ops.transformer import latent_attention as la

    B, T, _ = x.shape
    H, dn, dr, dv, dc = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
    h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q = (rms_norm(h @ lp["q_a"], lp["q_a_norm"], cfg.rms_norm_eps) @ lp["q_b"]).reshape(B, T, H, dn + dr)
    kv = h @ lp["kv_a"]
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    cos, sin = rope_cos_sin(cfg, positions)  # (B, T, dr/2)
    q_pe = apply_rope(q[..., dn:], cos[:, :, None, :], sin[:, :, None, :])
    row = jnp.concatenate([rms_norm(kv[..., :dc], lp["kv_a_norm"], cfg.rms_norm_eps),
                           apply_rope(kv[..., dc:], cos, sin)], axis=-1)  # (B, T, dc + dr): what is cached
    pool = la.latent_cache_write(pool, layer, row, page_table, pos, write_mask)
    w_kvb = lp["kv_b"].reshape(dc, H, dn + dv)
    if T == 1:
        attn = la.absorbed_attention(q[..., :dn], q_pe, pool, layer, page_table, pos, w_kvb, dn,
                                     cfg.softmax_scale, use_kernel=use_kernel)
    else:
        attn = la.expanded_attention(q[..., :dn], q_pe, pool, layer, page_table, pos, w_kvb, dn, cfg.softmax_scale,
                                     use_kernel=use_kernel, trace_notes=trace_notes)
    return x + attn.reshape(B, T, H * dv) @ lp["o"], pool


def forward_with_cache(params: Dict[str, Any], tokens, pool, pos, cfg: DeepseekV2Config, page_table,
                       write_mask=None, row_valid=None, take=None, use_kernel: Optional[bool] = None,
                       routing_sink: Optional[list] = None, trace_notes: Optional[dict] = None):
    """One network step on the paged latent cache.

    ``tokens (B, T)``; ``pool`` the ``(layers, pages, width, page_len)``
    latent buffer; ``pos (B,)`` per-row write offsets; ``page_table (B,
    pages_per_slot)``; ``write_mask (B,)`` False sends a row's write to
    the garbage page; ``row_valid (B, T)`` marks the real tokens (the
    expert counters leave the others out — they are still computed);
    ``take (B,)`` picks the one position per row whose logits are wanted
    (default: the last).  Returns ``(logits (B, V) float32, pool, aux)``
    with ``aux (moe layers, held + 1) int32``: per held expert the
    tokens computed for it, and in the last column the assignments the
    router sent to held experts (``moe/layer.py::dropless_held_experts``).
    ``routing_sink``, a list, is given each expert layer's chosen experts
    ``(B * T, top_k)`` (benchmark/control_deepseek_v2.py compares them
    with the reference's); ``trace_notes``, a dict, is told while
    tracing which form a chunk's attention took
    (``latent_attention.expanded_attention``) and which the held
    experts' grouped matmuls (``dropless_held_experts``).
    """
    from deepspeed_tpu.moe.layer import dropless_held_experts, group_limited_topk

    B, T = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    valid = None if row_valid is None else row_valid.reshape(B * T)
    aux = []
    for layer, lp in enumerate(params["layers"]):
        x, pool = mla_block(cfg, lp, x, pool, layer, pos, page_table, write_mask, use_kernel, trace_notes)
        h = rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
        if "mlp_gu" in lp:  # a leading dense layer
            x = x + _swiglu(h, lp["mlp_gu"], lp["mlp_down"])
            continue
        flat = h.reshape(B * T, -1)
        probs = jax.nn.softmax(jnp.dot(flat.astype(jnp.float32), lp["router"].astype(jnp.float32),
                                       precision=jax.lax.Precision.HIGHEST), axis=-1)
        idx, w = group_limited_topk(probs, cfg.n_group, cfg.topk_group, cfg.num_experts_per_tok,
                                    cfg.routed_scaling_factor, cfg.norm_topk_prob)
        if routing_sink is not None:
            routing_sink.append(idx)
        routed, counts = dropless_held_experts(flat, idx, w, lp["experts_gu"], lp["experts_down"], cfg.held, valid,
                                               trace_notes=trace_notes)
        x = x + (routed + _swiglu(flat, lp["shared_gu"], lp["shared_down"])).reshape(x.shape)
        aux.append(counts)
    take = jnp.full((B,), T - 1, jnp.int32) if take is None else take
    last = jnp.take_along_axis(x, take[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(rms_norm(last, params["norm_f"], cfg.rms_norm_eps), params["head"].T,
                     preferred_element_type=jnp.float32)
    aux = jnp.stack(aux) if aux else jnp.zeros((0, cfg.held[1] + 1), jnp.int32)
    return logits, pool, aux


def serving_forward(cfg: DeepseekV2Config):
    """The family seam of ``ServingEngine`` (docs/serving.md): the
    model's own step on its own cache kind.  ``fwd(params, tokens, k, v,
    pos, page_table=, write_mask=, row_valid=, take=, state=, slot=) ->
    (logits, k, v, state, aux)`` — ``k`` is the latent pool, ``v`` and
    ``state`` are None (this kind has no slot-axis group, and no use for
    the ``slot``).  ``fwd.trace_notes``
    holds what the programs traced through it said of themselves (which
    form a prefill chunk's attention compiled to, and each program's
    grouped expert matmuls): ``stats()`` shows it."""
    notes: Dict[str, Any] = {}

    def fwd(params, tokens, k, v, pos, page_table, write_mask=None, row_valid=None, take=None, state=None, slot=None):
        logits, k, aux = forward_with_cache(params, tokens, k, pos, cfg, page_table, write_mask=write_mask,
                                            row_valid=row_valid, take=take, trace_notes=notes)
        return logits, k, v, state, aux

    fwd.trace_notes = notes

    return fwd
