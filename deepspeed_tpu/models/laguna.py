"""Laguna: a seventh decoder family, on the serving path — **window and
full attention mixed**, with head counts that differ by layer kind.

Every layer is two pre-norm residual sublayers::

    x <- x + W_o (gate * Attn_l(RMS(x)))    grouped-query softmax attention, H_l query heads on Hkv KV heads
    x <- x + FFN_l(RMS(x))                  a dense SwiGLU (``mlp_layer_types[l] == "dense"``) or a mixture of experts

``layer_types[l]`` says what a layer attends over and how it is
rotated: a **full** layer (``full_attention``) over every earlier
position, its first ``partial_rotary_factor * head_dim`` dimensions
rotated under YaRN inverse frequencies with ``cos`` and ``sin``
multiplied by ``attention_factor``; a **sliding** layer
(``sliding_attention``) over the last ``sliding_window`` positions, the
query's own among them, all dimensions rotated, plain.
``num_attention_heads_per_layer[l]`` is the layer's query heads (more on
the sliding layers than on the full ones); KV heads and head size are
the same everywhere.  ``gating: per-head``: one sigmoid scalar a head
and position, from the layer's normed input, on the attention output in
front of ``W_o``.  The mixture: softmax over all experts in float32,
top-k, renormalised, times ``moe_routed_scaling_factor``, plus one shared
expert, ungated.  RMSNorm everywhere, no bias, no q/k norm, an untied
head.  The family is *told its share* like the other MoE families
(``experts_held``, ``vocab_held``).

Serving runs through ``ServingEngine`` on **two page groups in one
pool** (``serving/kvcache/pages.py::WindowedKV``): the full layers' K/V
on pages by length (``pool.k`` / ``pool.v``), the sliding layers' on a
ring of ``ceil((window - 1) / page_len) + 1`` pages a slot
(``pool.state["wk"]`` / ``["wv"]``) whatever the request's length.  A
decode step attends through the paged decode kernel in both — the window
layers under the kernel name ``swa_decode_paged``, over a work list of
the window's spans; a prefill chunk walks its context block by block in
the full layers and a band of it in the window layers
(``ops/transformer/inference.py::window_chunk_attention``), then writes
the ring.

RoPE layout: *half* (dimension ``i`` of the rotated part pairs with ``i
+ rot / 2``), as the public rotary utilities rotate.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.deepseek_v2 import _swiglu, apply_rope, rms_norm, seeded_tree

CAUSAL_LM = True
# one deployment, one table: held experts over ``expert``, embedding and head over the vocabulary, attention of both
# kinds, shared expert, router and norms replicated
PARTITION_RULES = "deepseek_v2"

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The published ``config.json`` keys that shape the model
    (``rope_parameters`` flattened by layer kind to ``full_rope_*`` /
    ``sliding_rope_*``), plus the share held here."""

    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    layer_types: Tuple[str, ...] = tuple(FULL if l % 4 == 0 else SLIDING for l in range(48))
    num_attention_heads_per_layer: Tuple[int, ...] = tuple(48 if l % 4 == 0 else 72 for l in range(48))
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 47
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    # rope_parameters["full_attention"] (rope_type "yarn")
    full_rope_theta: float = 500000.0
    full_rope_factor: float = 128.0
    full_rope_original_max_position_embeddings: int = 8192
    full_rope_beta_fast: float = 32.0
    full_rope_beta_slow: float = 1.0
    full_rope_attention_factor: float = 1.4852030263919618
    full_partial_rotary_factor: float = 0.5
    # rope_parameters["sliding_attention"] (rope_type "default")
    sliding_rope_theta: float = 10000.0
    sliding_partial_rotary_factor: float = 1.0
    # the share held here; None = everything
    experts_held: Optional[Tuple[int, int]] = None  # (first, count)
    vocab_held: Optional[int] = None  # rows 0 .. vocab_held - 1

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held={self.experts_held} outside the {self.num_experts} experts")
        if not 1 <= self.vocab_rows <= self.vocab_size:
            raise ValueError(f"vocab_held={self.vocab_held} outside the vocabulary of {self.vocab_size}")
        L = self.num_hidden_layers
        for name in ("layer_types", "num_attention_heads_per_layer", "mlp_layer_types"):
            if len(getattr(self, name)) != L:
                raise ValueError(f"{name} has {len(getattr(self, name))} entries for {L} layers")
        if any(t not in (FULL, SLIDING) for t in self.layer_types):
            raise ValueError(f"layer_types {set(self.layer_types)}: only {FULL} and {SLIDING} are implemented")
        if any(t not in ("dense", "sparse") for t in self.mlp_layer_types):
            raise ValueError(f"mlp_layer_types {set(self.mlp_layer_types)}: only dense and sparse are implemented")
        if any(h % self.num_key_value_heads for h in self.num_attention_heads_per_layer):
            raise ValueError("every layer's query heads must be whole groups over num_key_value_heads")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window={self.sliding_window}")
        for kind in (FULL, SLIDING):
            if self.rotary_dim(kind) % 2 or not 0 < self.rotary_dim(kind) <= self.head_dim:
                raise ValueError(f"{kind}: a rotated part of {self.rotary_dim(kind)} dimensions is not an even part of a head of {self.head_dim}")

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "LagunaConfig":
        """From the keys of a published ``config.json``; ``share`` gives
        ``experts_held`` / ``vocab_held`` and may cut
        ``num_hidden_layers`` (the per-layer lists then keep their first
        entries).  What this family does not compute is refused."""
        rope = hf.get("rope_parameters") or {}
        rf, rs = rope.get(FULL) or {}, rope.get(SLIDING) or {}
        gates = {str(g).replace("_", "-") for g in [hf.get("gating", "per-head"), *(hf.get("gating_types") or ())]}
        refused = [why for bad, why in (
            (gates != {"per-head"}, f"gating {sorted(gates)} (only per-head)"),
            (hf.get("moe_router_logit_softcapping", 0) not in (0, 0.0, None), "moe_router_logit_softcapping"),
            (hf.get("moe_apply_router_weight_on_input", False), "moe_apply_router_weight_on_input"),
            (hf.get("tie_word_embeddings", False), "a tied head"),
            (hf.get("attention_bias", False), "attention_bias"),
            (hf.get("decoder_sparse_step", 1) != 1, "decoder_sparse_step != 1"),
            (rf.get("rope_type", "yarn") != "yarn", f"full_attention rope_type {rf.get('rope_type')!r}"),
            (rs.get("rope_type", "default") != "default", f"sliding_attention rope_type {rs.get('rope_type')!r}"),
        ) if bad]
        if refused:
            raise ValueError("LagunaConfig: not implemented: " + "; ".join(refused))
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        for theirs, ours in (("rope_theta", "full_rope_theta"), ("factor", "full_rope_factor"),
                             ("original_max_position_embeddings", "full_rope_original_max_position_embeddings"),
                             ("beta_fast", "full_rope_beta_fast"), ("beta_slow", "full_rope_beta_slow"),
                             ("attention_factor", "full_rope_attention_factor"), ("partial_rotary_factor", "full_partial_rotary_factor")):
            if theirs in rf:
                kw[ours] = rf[theirs]
        for theirs, ours in (("rope_theta", "sliding_rope_theta"), ("partial_rotary_factor", "sliding_partial_rotary_factor")):
            if theirs in rs:
                kw[ours] = rs[theirs]
        kw.update(share)
        depth = int(kw.get("num_hidden_layers", cls.num_hidden_layers))
        if "mlp_layer_types" not in kw and "mlp_only_layers" in hf:
            kw["mlp_layer_types"] = ["dense" if l in hf["mlp_only_layers"] else "sparse" for l in range(depth)]
        if "num_attention_heads_per_layer" not in kw and "num_attention_heads" in kw:
            kw["num_attention_heads_per_layer"] = [kw["num_attention_heads"]] * depth
        for name in ("layer_types", "num_attention_heads_per_layer", "mlp_layer_types"):
            kw[name] = tuple(kw.get(name, getattr(cls, name)))[:depth]
        if kw.get("experts_held") is not None:
            kw["experts_held"] = tuple(int(v) for v in kw["experts_held"])
        return cls(**kw)

    # -- derived ------------------------------------------------------------
    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held if self.experts_held is not None else (0, self.num_experts)

    @property
    def vocab_rows(self) -> int:
        return self.vocab_held if self.vocab_held is not None else self.vocab_size

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, t in enumerate(self.layer_types) if t == FULL)

    @property
    def sliding_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, t in enumerate(self.layer_types) if t == SLIDING)

    def rotary_dim(self, kind: str) -> int:
        return int(round(self.head_dim * (self.full_partial_rotary_factor if kind == FULL else self.sliding_partial_rotary_factor)))

    # what the engines read of any causal-LM family
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings


# tests and chip_smoke.py: every mechanism present (a period and a layer, 2 KV heads under 4 / 6 query heads — the published
# 6 : 9 a KV head at a third — a window of 8, a dense first layer, 16 experts + a shared one), nothing wide
LAGUNA_TINY = LagunaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=160, num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=8, layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL), num_attention_heads_per_layer=(4, 6, 6, 6, 4),
    mlp_layer_types=("dense",) + ("sparse",) * 4, num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, max_position_embeddings=4096, full_rope_factor=8.0,
    full_rope_original_max_position_embeddings=64,
)


# ---------------------------------------------------------------------------
# rotary positions by layer kind
# ---------------------------------------------------------------------------

def inv_freq(cfg: LagunaConfig, kind: str) -> np.ndarray:
    """Inverse frequencies of a layer kind's rotated pairs: a sliding
    layer's plain ``theta ** (-2i / rot)``; a full layer's under YaRN —
    the base frequency where a pair turns often within the original
    context, the base over ``factor`` where it turns less than once, a
    linear ramp between (``deepseek_v2.yarn_inv_freq``'s rule on this
    family's keys)."""
    dim = cfg.rotary_dim(kind)
    theta = cfg.full_rope_theta if kind == FULL else cfg.sliding_rope_theta
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if kind == SLIDING or cfg.full_rope_factor <= 1:
        return base.astype(np.float32)

    def correction(beta):
        return dim * math.log(cfg.full_rope_original_max_position_embeddings / (beta * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(correction(cfg.full_rope_beta_fast)), 0)
    hi = min(math.ceil(correction(cfg.full_rope_beta_slow)), dim - 1)
    keep = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float64) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((1.0 - keep) * base / cfg.full_rope_factor + keep * base).astype(np.float32)


def rope_cos_sin(cfg: LagunaConfig, kind: str, positions) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``cos, sin`` of shape ``positions.shape + (rot / 2,)``, float32; a full layer's multiplied by ``attention_factor``."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq(cfg, kind))
    mult = cfg.full_rope_attention_factor if kind == FULL else 1.0
    return jnp.cos(ang) * mult, jnp.sin(ang) * mult


def rotate(x, cos, sin):
    """The first ``2 * cos.shape[-1]`` dimensions of ``x (..., head_dim)`` rotated (half layout), the rest passed."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate([apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: LagunaConfig) -> Dict[str, Any]:
    """The parameter tree as shapes; the conventions of
    ``models/deepseek_v2.py``: ``layers`` a list with one dict a layer
    (nothing stacked over layers — here the layers differ in shape:
    ``qkv``, ``gate`` and ``o`` are as wide as the layer's heads), gate
    and up projections one matrix (``*_gu``, gate columns first), a
    layer's held experts stacked on a leading ``held`` dim, ``W_q | W_k |
    W_v`` one matrix (``qkv``); ``gate`` is the headwise gate ``D ->
    H_l``."""
    D, Hkv, hd = cfg.hidden_size, cfg.num_key_value_heads, cfg.head_dim
    Fe, Fs, held = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size, cfg.held[1]
    dense = {"mlp_gu": (D, 2 * cfg.intermediate_size), "mlp_down": (cfg.intermediate_size, D)}
    sparse = {"router": (D, cfg.num_experts), "shared_gu": (D, 2 * Fs), "shared_down": (Fs, D),
              "experts_gu": (held, D, 2 * Fe), "experts_down": (held, Fe, D)}

    def layer(l):
        H = cfg.num_attention_heads_per_layer[l]
        return {"attn_norm": (D,), "ffn_norm": (D,), "qkv": (D, (H + 2 * Hkv) * hd), "gate": (D, H), "o": (H * hd, D),
                **(dense if cfg.mlp_layer_types[l] == "dense" else sparse)}

    return {"embed": (cfg.vocab_rows, D), "head": (cfg.vocab_rows, D), "norm_f": (D,),
            "layers": [layer(l) for l in range(cfg.num_hidden_layers)]}


def init_params_device(cfg: LagunaConfig, seed: int = 0, dtype=jnp.bfloat16, std: float = 0.02):
    """Random parameters made on the default device, one leaf at a time (``deepseek_v2.seeded_tree``)."""
    return seeded_tree(param_shapes(cfg), cfg.num_hidden_layers, seed, dtype, std)


def init_params(cfg: LagunaConfig, seed: int = 0):
    """Host float32 tree (small configurations: tests, the engine's default when it is handed no parameters)."""
    return jax.tree.map(np.asarray, init_params_device(cfg, seed=seed, dtype=jnp.float32))


def cache_kind(cfg: LagunaConfig, dtype):
    """The family's cache kind for :class:`PagedKVPool`: pages by length
    for the full layers, a ring of pages a slot for the sliding ones."""
    from deepspeed_tpu.serving.kvcache.pages import WindowedKV

    return WindowedKV(len(cfg.full_layers), len(cfg.sliding_layers), cfg.num_key_value_heads, cfg.head_dim, cfg.sliding_window, dtype)


# ---------------------------------------------------------------------------
# forward on the two page groups
# ---------------------------------------------------------------------------

def attention_block(cfg: LagunaConfig, lp: Dict[str, Any], x, layer: int, caches, tables, pos, rope, n_valid, write_mask=None,
                    use_kernel: Optional[bool] = None, trace_notes: Optional[dict] = None, work=None):
    """``x + W_o (gate * Attn(RMS(x)))`` of layer ``layer`` for ``x (B,
    T, D)`` at per-row write offsets ``pos (B,)``.  ``caches = (K, V)``
    the stacked pools of the layer's **group** (the full group's pages,
    or the window group's rings), ``tables`` that group's table ``(B,
    pages_per_slot)`` — the pool's page table, or ``ring_table`` — and
    ``rope`` its kind's ``(cos, sin)``; the layer's index **within its
    group** is read off ``layer_types``.  Returns ``(x, K, V)``."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.transformer import inference as inf

    B, T, _ = x.shape
    kind = cfg.layer_types[layer]
    H, Hkv, hd = cfg.num_attention_heads_per_layer[layer], cfg.num_key_value_heads, cfg.head_dim
    at = sum(1 for t in cfg.layer_types[:layer] if t == kind)  # the layer's place in its group's stacked pools
    k_pool, v_pool = caches
    h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    qkv = h @ lp["qkv"]
    heads = lambda t, n: t.reshape(B, T, n, hd).transpose(0, 2, 1, 3)  # noqa: E731  (B, n, T, hd)
    cos, sin = (t[:, None] for t in rope)  # (B, 1, T, rot / 2)
    q = rotate(heads(qkv[..., : H * hd], H), cos, sin)
    k = rotate(heads(qkv[..., H * hd: (H + Hkv) * hd], Hkv), cos, sin)
    v = heads(qkv[..., (H + Hkv) * hd:], Hkv)
    armed = _kernels.flash_decode_armed() if use_kernel is None else use_kernel
    if kind == SLIDING and T > 1:
        # a chunk attends its own rows where they are and the window's earlier pages in the ring, then the ring takes what it keeps
        ring_pages = inf.ring_pages_for(cfg.sliding_window, k_pool.shape[3])
        kc, vc, table = inf.layer_pages(k_pool, v_pool, tables, at)
        attn = inf.window_chunk_attention(q, k, v, kc, vc, table, pos, cfg.sliding_window)
        k_pool = inf.ring_chunk_write(k_pool, at, k, tables, pos, n_valid, ring_pages)
        v_pool = inf.ring_chunk_write(v_pool, at, v, tables, pos, n_valid, ring_pages)
        if trace_notes is not None:
            trace_notes["swa_chunk_form"] = (f"banded jnp (window_chunk_attention): query blocks of {min(T, 256)} over the window's "
                                             f"{(ring_pages - 1) * k_pool.shape[3]} earlier positions + their own")
    else:
        k_pool = inf.paged_cache_write_slices(k_pool, at, k, tables, pos, write_mask, use_kernel)
        v_pool = inf.paged_cache_write_slices(v_pool, at, v, tables, pos, write_mask, use_kernel)
        kc, vc, table = inf.layer_pages(k_pool, v_pool, tables, at)
        if kind == SLIDING:
            attn = inf.window_cache_attention(q, kc, vc, table, pos, cfg.sliding_window, use_kernel=armed, work=work, trace_notes=trace_notes)
        elif T == 1:
            attn = inf.paged_cache_attention(q, kc, vc, table, pos, use_kernel=armed, work=work, trace_notes=trace_notes)
        else:
            attn = inf.paged_chunk_attention(q, kc, vc, table, pos, use_kernel=armed, trace_notes=trace_notes)
            if trace_notes is not None:
                trace_notes["gqa_prefill_form"] = inf.chunk_attention_note(trace_notes)
    gate = jax.nn.sigmoid(h @ lp["gate"])  # (B, T, H): one scalar a head and position
    attn = attn.transpose(0, 2, 1, 3) * gate[..., None].astype(attn.dtype)
    return x + attn.reshape(B, T, H * hd) @ lp["o"], k_pool, v_pool


def forward_with_cache(params: Dict[str, Any], tokens, k_pool, v_pool, state, pos, cfg: LagunaConfig, page_table,
                       slot=None, write_mask=None, row_valid=None, take=None, use_kernel: Optional[bool] = None,
                       routing_sink: Optional[list] = None, trace_notes: Optional[dict] = None):
    """One network step on the two page groups.

    ``tokens (B, T)``; ``k_pool`` / ``v_pool`` the full group ``(full
    layers, pages, kv heads, page_len, head_dim)``; ``state = {"wk",
    "wv"}`` the window group ``(sliding layers, 1 + slots * ring_pages,
    ...)``; ``pos (B,)`` per-row write offsets; ``page_table (B,
    pages_per_slot)`` the full group's.  ``slot (B,)`` names the slots of
    a **prefill chunk**'s rows (``pos`` whole pages); ``slot`` None is a
    **decode step** (row ``b`` is slot ``b``), where ``write_mask (B,)``
    False sends a row's writes to each group's garbage page.
    ``row_valid (B, T)`` marks the real tokens (a chunk's padded tail is
    computed, kept out of the counters, and its pages out of the ring);
    ``take (B,)`` picks the position whose logits are wanted (default:
    the last).  Returns ``(logits (B, V) float32, k_pool, v_pool, state,
    aux)`` with ``aux (sparse layers, held + 1) int32`` as
    ``deepseek_v2.forward_with_cache`` returns it.  ``routing_sink`` is
    given, a sparse layer, ``(chosen experts (B * T, top_k) int32, the
    router's logits of the chosen, float32)``."""
    from deepspeed_tpu.moe.layer import dropless_held_experts, softmax_topk
    from deepspeed_tpu.ops.kernels.flash_decode import paged_tile, paged_work_list
    from deepspeed_tpu.ops.transformer.inference import ring_pages_for, ring_table

    B, T = tokens.shape
    page_len, P = k_pool.shape[3], page_table.shape[1]
    if T > 1 and T % page_len:
        raise ValueError(f"laguna: a prefill chunk of {T} tokens is not whole pages of {page_len}")
    R = ring_pages_for(cfg.sliding_window, page_len)
    if state["wk"].shape[1] % R != 1:
        raise ValueError(f"laguna: the window group's {state['wk'].shape[1]} pages are not 1 + slots x {R} ring pages")
    ring = ring_table(jnp.arange(B, dtype=jnp.int32) if slot is None else slot, R, P)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    rope = {kind: rope_cos_sin(cfg, kind, positions) for kind in (FULL, SLIDING)}
    n_valid = jnp.full((B,), T, jnp.int32) if row_valid is None else jnp.sum(row_valid.astype(jnp.int32), axis=1)
    # a decode step's two work lists, once for all layers of a group: the filled spans, and the window's
    span = paged_tile(k_pool, P)[1]
    work = {FULL: paged_work_list(pos, write_mask, page_len, P, span),
            SLIDING: paged_work_list(pos, write_mask, page_len, P, span, cfg.sliding_window)} if T == 1 else {FULL: None, SLIDING: None}
    if trace_notes is not None:
        trace_notes["swa_ring_positions"] = R * page_len
    x = jnp.take(params["embed"], tokens, axis=0)
    valid = None if row_valid is None else row_valid.reshape(B * T)
    wk, wv = state["wk"], state["wv"]
    aux = []
    for layer, lp in enumerate(params["layers"]):
        kind = cfg.layer_types[layer]
        if kind == FULL:
            x, k_pool, v_pool = attention_block(cfg, lp, x, layer, (k_pool, v_pool), page_table, pos, rope[kind], n_valid, write_mask,
                                                use_kernel, trace_notes, work[kind])
        else:
            x, wk, wv = attention_block(cfg, lp, x, layer, (wk, wv), ring, pos, rope[kind], n_valid, write_mask,
                                        use_kernel, trace_notes, work[kind])
        h = rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
        if "mlp_gu" in lp:  # a dense layer
            x = x + _swiglu(h, lp["mlp_gu"], lp["mlp_down"])
            continue
        flat = h.reshape(B * T, -1)
        with jax.named_scope("moe.router"):
            logits = jnp.dot(flat.astype(jnp.float32), lp["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
            idx, w = softmax_topk(logits, cfg.num_experts_per_tok, cfg.norm_topk_prob)
            w = w * cfg.moe_routed_scaling_factor
        if trace_notes is not None:
            trace_notes["moe_router_form"] = f"softmax_topk (float32, highest; renormalised) x {cfg.moe_routed_scaling_factor}"
        if routing_sink is not None:
            routing_sink.append((idx, jnp.take_along_axis(logits, idx, axis=-1)))
        routed, counts = dropless_held_experts(flat, idx, w, lp["experts_gu"], lp["experts_down"], cfg.held, valid,
                                               trace_notes=trace_notes)
        x = x + (routed + _swiglu(flat, lp["shared_gu"], lp["shared_down"])).reshape(x.shape)
        aux.append(counts)
    take = jnp.full((B,), T - 1, jnp.int32) if take is None else take
    last = jnp.take_along_axis(x, take[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(rms_norm(last, params["norm_f"], cfg.rms_norm_eps), params["head"].T, preferred_element_type=jnp.float32)
    aux = jnp.stack(aux) if aux else jnp.zeros((0, cfg.held[1] + 1), jnp.int32)
    return logits, k_pool, v_pool, {"wk": wk, "wv": wv}, aux


def serving_forward(cfg: LagunaConfig):
    """The family seam of ``ServingEngine`` (docs/serving.md §Model
    families): ``fwd(params, tokens, k, v, pos, page_table=, write_mask=,
    row_valid=, take=, state=, slot=) -> (logits, k, v, state, aux)``.
    ``slot`` is the prefill chunk's slot (a decode step passes None: its
    rows are the slots); ``state`` is the window group.
    ``fwd.trace_notes`` holds the forms the two programs compiled:
    ``swa_decode_form``, ``swa_chunk_form``, ``swa_ring_positions``,
    ``paged_decode_walk``, ``gqa_prefill_form`` (from
    ``chunk_attention_kernel`` / ``_fallback``), ``moe_router_form``,
    ``moe_grouped_kernel`` / ``_fallback``.

    ``fwd.decode_keeps``: the decode program hands back, beside its
    tokens, what its routers chose — ``kept={"experts": (sparse layers,
    slots, top_k) int32, "router_logits": the same shape float32, "pos":
    (slots,)}`` — which the engine leaves on the device as
    ``ServingEngine.decode_kept`` until the next step (21 KB at 24 slots;
    nothing fetches it but a check of the served program: the router's
    precision is read off the logits' mantissas)."""
    notes: Dict[str, Any] = {}

    def fwd(params, tokens, k, v, pos, page_table, write_mask=None, row_valid=None, take=None, state=None, slot=None,
            kept: Optional[dict] = None):
        sink = [] if kept is not None else None
        out = forward_with_cache(params, tokens, k, v, state, pos, cfg, page_table, slot=slot, write_mask=write_mask,
                                 row_valid=row_valid, take=take, routing_sink=sink, trace_notes=notes)
        if kept is not None:
            kept.update(experts=jnp.stack([i for i, _ in sink]), router_logits=jnp.stack([l for _, l in sink]), pos=pos)
        return out

    fwd.trace_notes = notes
    fwd.decode_keeps = True
    return fwd
