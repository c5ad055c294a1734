"""Solar-Open2: a third decoder family, on the serving path.

A **hybrid** of two mixers, three linear-attention layers to one
softmax-attention layer, every layer followed by a mixture of experts::

    x <- x + Mixer_l(RMS(x))      l in gqa_layers: gated grouped-query attention, no positional term
                                  else: Kimi Delta Attention (KDA), ops/transformer/linear_attention.py
    x <- x + MoE(RMS(x))          sigmoid router over all routed experts, top-k, one shared expert

RMSNorm everywhere, no bias, no rotary (``use_rope`` false), an untied
head.  The family is *told its share* like DeepSeek-V2
(``experts_held``, ``vocab_held``): the router keeps its published
width, what absent experts would add is left out.

**Gated GQA layer**: ``q = h W_q`` (H heads), ``k, v = h W_k, h W_v``
(Hkv heads), causal softmax with query head ``i`` on KV head ``i // (H /
Hkv)``, ``y = (sigmoid(h W_g) * attn) W_o`` with an elementwise gate
``W_g: D -> H head_dim``.

**KDA layer**: ``[q | k | v] = SiLU(conv4(h W_qkv))`` (a causal depthwise
convolution per channel); ``q, k`` L2-normalised per head (``q`` also
scaled by ``dk^-1/2``); per-channel log-decay ``g = -exp(A_log) *
softplus(h W_a_down W_a_up + dt_bias)``; ``beta = 2 sigmoid(h W_beta)``
(``kda_allow_neg_eigval``: the 2); the delta-rule recurrence on a
``(dk, dv)`` state per head; ``y = (RMS_head(o) * sigmoid(h W_g_down
W_g_up)) W_o``.

Serving runs through ``ServingEngine`` on a **hybrid cache**
(``serving/kvcache/pages.py::HybridKV``): K/V pages for the GQA layers
only, and per slot a recurrent state + the convolution's last three
inputs for every KDA layer.  A prefill chunk runs the KDA layers in the
chunked form from the slot's state — from **zero where the chunk starts
at position 0**, so a slot needs no reset between requests — and
attends block by block over its context in the GQA layers; a decode
step runs the recurrence in place (``ops/kernels/kda_decode.py`` on the
chip) and the grouped ``flash_decode_paged``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.deepseek_v2 import _swiglu, rms_norm, seeded_tree

CAUSAL_LM = True
# one deployment, one table: held experts over ``expert``, embedding and head over the vocabulary, every kind of
# attention, shared experts, router and norms replicated
PARTITION_RULES = "deepseek_v2"


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """The published ``config.json`` keys that shape the model (the
    nested ``linear_attn_config`` flattened to ``kda_*``; keys no layer
    reads — ``intermediate_size`` with no dense layer, the rotary keys
    with ``use_rope`` false — are not carried), plus the share held here."""

    vocab_size: int = 196608
    hidden_size: int = 4096
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    kda_num_heads: int = 64
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    kda_allow_neg_eigval: bool = True
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    # the share held here; None = everything
    experts_held: Optional[Tuple[int, int]] = None  # (first, count)
    vocab_held: Optional[int] = None  # rows 0 .. vocab_held - 1

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held={self.experts_held} outside the {self.n_routed_experts} routed experts")
        if not 1 <= self.vocab_rows <= self.vocab_size:
            raise ValueError(f"vocab_held={self.vocab_held} outside the vocabulary of {self.vocab_size}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads is not a multiple of num_key_value_heads")
        if any(not 0 <= l < self.num_hidden_layers for l in self.gqa_layers):
            raise ValueError(f"gqa_layers={self.gqa_layers} outside the {self.num_hidden_layers} layers")

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "SolarOpen2Config":
        """From the keys of a published ``config.json``; ``share`` gives
        ``experts_held`` / ``vocab_held`` and may cut
        ``num_hidden_layers`` (``gqa_layers`` then keeps the layers that
        remain).  What this family does not implement is refused."""
        lin = hf.get("linear_attn_config") or {}
        refused = [why for bad, why in (
            (hf.get("use_rope", False), "use_rope"),
            (hf.get("first_k_dense_replace", 0) != 0, "a leading dense layer (first_k_dense_replace > 0)"),
            (hf.get("kda_use_full_proj", False), "kda_use_full_proj"),
            (hf.get("tie_word_embeddings", False), "tie_word_embeddings"),
            (not hf.get("use_gqa_gate", True), "use_gqa_gate false"),
            (lin.get("num_kv_heads") not in (None, lin.get("num_heads")), "grouped KDA heads (linear_attn_config.num_kv_heads)"),
            (hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1, "grouped routing (n_group / topk_group > 1)"),
            (hf.get("scoring_func", "sigmoid") != "sigmoid", f"scoring_func {hf.get('scoring_func')!r}"),
        ) if bad]
        if refused:
            raise ValueError("SolarOpen2Config: not implemented: " + "; ".join(refused))
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        for theirs, ours in (("num_heads", "kda_num_heads"), ("head_dim", "kda_head_dim"), ("short_conv_kernel_size", "kda_conv_size")):
            if theirs in lin:
                kw[ours] = lin[theirs]
        kw.update(share)
        depth = kw.get("num_hidden_layers", cls.num_hidden_layers)
        kw["gqa_layers"] = tuple(int(l) for l in kw.get("gqa_layers", cls.gqa_layers) if l < depth)
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
    def kda_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_hidden_layers) if l not in self.gqa_layers)

    @property
    def kda_rank(self) -> int:
        """Rank of the decay and output-gate projections' low-rank pairs: the head size."""
        return self.kda_head_dim

    @property
    def kda_width(self) -> int:
        """``heads x head_dim`` of one of q, k, v of a KDA layer."""
        return self.kda_num_heads * self.kda_head_dim

    # what the engines read of any causal-LM family
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings


# tests and chip_smoke.py: every mechanism present (two periods, 2 KV heads x 2 groups, 16 experts), nothing wide
SOLAR_OPEN2_TINY = SolarOpen2Config(
    vocab_size=256, hidden_size=64, moe_intermediate_size=32, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, gqa_layers=(0, 4), kda_num_heads=4, kda_head_dim=16,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4, max_position_embeddings=4096,
)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: SolarOpen2Config) -> Dict[str, Any]:
    """The parameter tree as shapes; the conventions of
    ``models/deepseek_v2.py``: ``layers`` a list with one dict a layer
    (nothing stacked over layers), gate and up projections one matrix
    (``*_gu``, gate columns first), a layer's held experts stacked on a
    leading ``held`` dim, q | k | v of a mixer one matrix (``qkv``)."""
    D, H, Hkv, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Hl, dl, r, W = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_rank, cfg.kda_width
    Fe, Fs, held = cfg.moe_intermediate_size, cfg.moe_intermediate_size * cfg.n_shared_experts, cfg.held[1]
    moe = {"attn_norm": (D,), "ffn_norm": (D,), "router": (D, cfg.n_routed_experts), "router_bias": (cfg.n_routed_experts,),
           "shared_gu": (D, 2 * Fs), "shared_down": (Fs, D),
           "experts_gu": (held, D, 2 * Fe), "experts_down": (held, Fe, D)}
    gqa = {**moe, "qkv": (D, (H + 2 * Hkv) * hd), "gate": (D, H * hd), "o": (H * hd, D)}
    kda = {**moe, "qkv": (D, 3 * W), "conv": (cfg.kda_conv_size, 3 * W), "a_down": (D, r), "a_up": (r, W),
           "dt_bias": (W,), "A_log": (Hl,), "beta": (D, Hl), "g_down": (D, r), "g_up": (r, W),
           "o_norm": (dl,), "o": (W, D)}
    return {"embed": (cfg.vocab_rows, D), "head": (cfg.vocab_rows, D), "norm_f": (D,),
            "layers": [dict(gqa if l in cfg.gqa_layers else kda) for l in range(cfg.num_hidden_layers)]}


def special_leaf(name: str, key, shape) -> Optional[jnp.ndarray]:
    """The leaves that are not a normal(0.02) matrix: ``A_log`` and
    ``dt_bias`` drawn so that decays are neither 0 nor 1 (``exp(A_log)``
    uniform in [1, 16], ``softplus(dt_bias)`` log-uniform in [0.001,
    0.1]: a channel forgets over tens to thousands of tokens), the
    convolution taps normal(0.5) (a depthwise tap has a fan-in of 4), the
    router's selection bias 0."""
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


def init_params_device(cfg: SolarOpen2Config, seed: int = 0, dtype=jnp.bfloat16, std: float = 0.02):
    """Random parameters made on the default device, one leaf at a time
    (``deepseek_v2.seeded_tree``; :func:`special_leaf` for the rest)."""
    return seeded_tree(param_shapes(cfg), cfg.num_hidden_layers, seed, dtype, std,
                       residual=("o", "shared_down", "experts_down"), special=special_leaf)


def init_params(cfg: SolarOpen2Config, seed: int = 0):
    """Host float32 tree (small configurations: tests, the engine's
    default when it is handed no parameters)."""
    return jax.tree.map(np.asarray, init_params_device(cfg, seed=seed, dtype=jnp.float32))


def cache_kind(cfg: SolarOpen2Config, dtype):
    """The family's cache kind for :class:`PagedKVPool`: K/V pages of the
    GQA layers, a per-slot state of the KDA layers."""
    from deepspeed_tpu.serving.kvcache.pages import HybridKV, PerHeadKV

    n = len(cfg.kda_layers)
    return HybridKV(len(cfg.gqa_layers), PerHeadKV(cfg.num_key_value_heads, cfg.head_dim, dtype), {
        "s": (n, (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.float32),
        "conv": (n, (cfg.kda_conv_size - 1, 3 * cfg.kda_width), dtype)})


# ---------------------------------------------------------------------------
# forward on the hybrid cache
# ---------------------------------------------------------------------------

def gqa_block(cfg: SolarOpen2Config, lp: Dict[str, Any], x, k_pool, v_pool, paged_layer: int, pos, page_table,
              write_mask=None, use_kernel: Optional[bool] = None, trace_notes: Optional[dict] = None, work=None):
    """``x + GatedGQA(RMS(x))`` for ``x (B, T, D)`` at per-row write
    offsets ``pos (B,)``: writes the rows' K and V into layer
    ``paged_layer`` of the pools through ``page_table`` and attends over
    the cache — the grouped ``flash_decode_paged`` over the step's
    ``work`` list (or the gather + lax form) for ``T == 1``, block by
    block over the context otherwise."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.kernels.flash_decode import decode_paged_supported
    from deepspeed_tpu.ops.transformer import inference as inf

    B, T, _ = x.shape
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    qkv = h @ lp["qkv"]
    heads = lambda t, n: t.reshape(B, T, n, hd).transpose(0, 2, 1, 3)  # noqa: E731  (B, n, T, hd)
    q, k, v = heads(qkv[..., : H * hd], H), heads(qkv[..., H * hd: (H + Hkv) * hd], Hkv), heads(qkv[..., (H + Hkv) * hd:], Hkv)
    k_pool = inf.paged_cache_write_slices(k_pool, paged_layer, k, page_table, pos, write_mask)
    v_pool = inf.paged_cache_write_slices(v_pool, paged_layer, v, page_table, pos, write_mask)
    kc, vc = k_pool[paged_layer], v_pool[paged_layer]
    if T == 1:
        armed = _kernels.flash_decode_armed() if use_kernel is None else use_kernel
        fits = decode_paged_supported(B, H, page_table.shape[1], kc.shape[2], hd)
        if trace_notes is not None:
            why_not = "" if armed and fits else ("kernel suite not armed" if not armed else f"unsupported page geometry (page_len {kc.shape[2]})")
            trace_notes.update(gqa_decode_kernel=not why_not, gqa_decode_fallback=why_not)
        attn = inf.paged_cache_attention(q, kc, vc, page_table, pos, use_kernel=armed, work=work, trace_notes=trace_notes)
    else:
        attn = inf.paged_chunk_attention(q, kc, vc, page_table, pos, use_kernel=use_kernel, trace_notes=trace_notes)
        if trace_notes is not None:
            trace_notes["gqa_prefill_form"] = inf.chunk_attention_note(trace_notes)
    attn = attn.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    return x + (jax.nn.sigmoid(h @ lp["gate"]) * attn) @ lp["o"], k_pool, v_pool


def kda_block(cfg: SolarOpen2Config, lp: Dict[str, Any], x, state: Dict[str, Any], state_layer: int, pos, slot=None,
              write_mask=None, row_valid=None, use_kernel: Optional[bool] = None, trace_notes: Optional[dict] = None):
    """``x + KDA(RMS(x))`` for ``x (B, T, D)`` on layer ``state_layer``
    of the per-slot ``state``.

    ``slot (B,)`` given: a **prefill chunk** of the slots named — the
    chunked form from each slot's state (zero where ``pos == 0``: a
    fresh request), the state left untouched by tokens whose
    ``row_valid`` is False, the convolution's memory at the last valid
    inputs.  ``slot`` None: a **decode step**, row ``b`` is slot ``b``
    and rows with ``write_mask`` False keep their state."""
    from deepspeed_tpu.ops.transformer import linear_attention as la
    from deepspeed_tpu.ops.transformer.inference import state_rows, state_rows_write

    B, T, _ = x.shape
    Hl, dl, W = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_width
    f32 = jnp.float32
    h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    qkv = h @ lp["qkv"]
    decode = slot is None
    if decode:
        conv0 = state["conv"][state_layer]
        n_valid = None
    else:
        fresh = (pos == 0)
        conv0 = jnp.where(fresh[:, None, None], 0, state_rows(state["conv"], state_layer, slot))
        n_valid = None if row_valid is None else jnp.sum(row_valid.astype(jnp.int32), axis=1)
    y, conv1 = la.short_conv(qkv, lp["conv"], conv0, n_valid)
    heads = lambda t: t.reshape(B, T, Hl, dl)  # noqa: E731
    q = la.l2norm(heads(y[..., :W])) * dl ** -0.5
    k = la.l2norm(heads(y[..., W: 2 * W]))
    v = heads(y[..., 2 * W:])
    a = ((h @ lp["a_down"]) @ lp["a_up"]).astype(f32) + lp["dt_bias"].astype(f32)
    g = -jnp.exp(lp["A_log"].astype(f32))[:, None] * heads(jax.nn.softplus(a))
    beta = (2.0 if cfg.kda_allow_neg_eigval else 1.0) * jax.nn.sigmoid((h @ lp["beta"]).astype(f32))
    if row_valid is not None and not decode:
        # a chunk's padded tail: beta = 0 and g = 0 leave the state as it was
        g = jnp.where(row_valid[:, :, None, None], g, 0.0)
        beta = jnp.where(row_valid[:, :, None], beta, 0.0)
    if decode:
        mask = jnp.ones((B,), bool) if write_mask is None else write_mask.astype(bool)
        o, s = la.decode_step(state["s"], state_layer, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], mask,
                              use_kernel=use_kernel, trace_notes=trace_notes)
        o = o[:, None]
        conv = state["conv"].at[state_layer].set(jnp.where(mask[:, None, None], conv1, conv0))
    else:
        s0 = jnp.where(fresh[:, None, None, None], 0.0, state_rows(state["s"], state_layer, slot))
        o, s1 = la.chunked(s0, q, k, v, g, beta)
        if trace_notes is not None:
            trace_notes["kda_prefill_form"] = f"chunked jnp (chunks of {min(la.CHUNK, T)})"
        s = state_rows_write(state["s"], state_layer, slot, s1)
        conv = state_rows_write(state["conv"], state_layer, slot, conv1)
    gate = jax.nn.sigmoid(((h @ lp["g_down"]) @ lp["g_up"]).astype(f32))
    o = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps) * heads(gate)
    return x + o.reshape(B, T, W).astype(x.dtype) @ lp["o"], {"s": s, "conv": conv}


def forward_with_cache(params: Dict[str, Any], tokens, k_pool, v_pool, state, pos, cfg: SolarOpen2Config, page_table,
                       slot=None, write_mask=None, row_valid=None, take=None, use_kernel: Optional[bool] = None,
                       routing_sink: Optional[list] = None, trace_notes: Optional[dict] = None):
    """One network step on the hybrid cache.

    ``tokens (B, T)``; ``k_pool`` / ``v_pool`` the ``(gqa layers, pages,
    kv heads, page_len, head_dim)`` buffers; ``state`` the per-slot group
    ``{"s", "conv"}`` (``HybridKV.state_buffers``); ``pos (B,)`` per-row
    write offsets; ``page_table (B, pages_per_slot)``.  ``slot (B,)``
    names the slots of a **prefill chunk**'s rows; ``slot`` None is a
    **decode step** (row ``b`` is slot ``b``), where ``write_mask (B,)``
    False sends a row's K/V write to the garbage page and leaves its
    state alone.  ``row_valid (B, T)`` marks the real tokens (a chunk's
    padded tail is computed, and kept out of the state and the
    counters); ``take (B,)`` picks the position whose logits are wanted
    (default: the last).  Returns ``(logits (B, V) float32, k_pool,
    v_pool, state, aux)`` with ``aux (layers, held + 1) int32`` as
    ``deepseek_v2.forward_with_cache`` returns it.  ``routing_sink`` is
    given each layer's chosen experts ``(B * T, top_k)``."""
    from deepspeed_tpu.moe.layer import dropless_held_experts, sigmoid_topk
    from deepspeed_tpu.ops.kernels.flash_decode import paged_tile, paged_work_list

    B, T = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    valid = None if row_valid is None else row_valid.reshape(B * T)
    aux = []
    paged_layer = state_layer = 0
    P = page_table.shape[1]
    work = paged_work_list(pos, write_mask, k_pool.shape[3], P, paged_tile(k_pool, P)[1]) if T == 1 else None  # once, for every GQA layer
    for layer, lp in enumerate(params["layers"]):
        if layer in cfg.gqa_layers:
            x, k_pool, v_pool = gqa_block(cfg, lp, x, k_pool, v_pool, paged_layer, pos, page_table, write_mask,
                                          use_kernel, trace_notes, work)
            paged_layer += 1
        else:
            x, state = kda_block(cfg, lp, x, state, state_layer, pos, slot, write_mask, row_valid, use_kernel, trace_notes)
            state_layer += 1
        h = rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
        flat = h.reshape(B * T, -1)
        logits = jnp.dot(flat.astype(jnp.float32), lp["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        idx, w = sigmoid_topk(logits, lp["router_bias"], cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.norm_topk_prob)
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
    return logits, k_pool, v_pool, state, jnp.stack(aux)


def serving_forward(cfg: SolarOpen2Config):
    """The family seam of ``ServingEngine`` (docs/serving.md §Model
    families): ``fwd(params, tokens, k, v, pos, page_table=, write_mask=,
    row_valid=, take=, state=, slot=) -> (logits, k, v, state, aux)``.
    ``slot`` is the prefill chunk's slot (a decode step passes None: its
    rows are the slots).  ``fwd.trace_notes`` holds the forms the two
    programs compiled: ``kda_decode_kernel`` / ``_fallback``,
    ``kda_prefill_form``, ``gqa_decode_kernel`` / ``_fallback``,
    ``paged_decode_walk``, ``gqa_prefill_form`` (from
    ``chunk_attention_kernel`` / ``_fallback``), ``moe_grouped_kernel``
    / ``_fallback``."""
    notes: Dict[str, Any] = {}

    def fwd(params, tokens, k, v, pos, page_table, write_mask=None, row_valid=None, take=None, state=None, slot=None):
        return forward_with_cache(params, tokens, k, v, state, pos, cfg, page_table, slot=slot, write_mask=write_mask,
                                  row_valid=row_valid, take=take, trace_notes=notes)

    fwd.trace_notes = notes
    return fwd
