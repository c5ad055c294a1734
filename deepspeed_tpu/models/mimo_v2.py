"""MiMo-V2: a ninth decoder family, on the serving path — **window and
full attention mixed, with a KV geometry a layer kind**, keys wider than
values, and a learned **attention sink** on the window layers.

Every layer is two pre-norm residual sublayers::

    x <- x + W_o Attn_l(RMS(x))     grouped-query softmax attention, H query heads on Hkv_l KV heads, keys dk / values dv wide
    x <- x + FFN_l(RMS(x))          a dense SwiGLU (``moe_layer_freq[l] == 0``) or a mixture of experts

``hybrid_layer_pattern[l]`` says what a layer attends over: ``0`` a
**full** layer — every earlier position, ``num_key_value_heads`` KV
heads of ``head_dim`` / ``v_head_dim``, ``rope_theta`` —, ``1`` a
**window** layer — the last ``sliding_window`` positions, the query's own
among them, ``swa_num_key_value_heads`` KV heads of ``swa_head_dim`` /
``swa_v_head_dim``, ``swa_rope_theta``.  In both the first ``int(dk *
partial_rotary_factor)`` dimensions of q and k are rotated (half layout,
no scaling) and the rest pass; the values are multiplied by
``attention_value_scale``; scores are scaled by ``1 / sqrt(dk)``.  A kind
that says ``add_*_attention_sink_bias`` carries one learned logit a head
(``sink (H,)``): **one more column of every query's softmax**, which
takes mass and carries no value.  The mixture: ``sigmoid`` scores over
all experts in float32, the top-k by ``score + e_bias`` (the bias selects
and never weighs), the chosen scores renormalised, times
``routed_scaling_factor`` (null = 1); no shared expert.  RMSNorm
everywhere, no bias, no q/k norm, an untied head.  The family is *told
its share* like the other MoE families (``experts_held``, ``vocab_held``).

Serving runs through ``ServingEngine`` on **two page groups in one pool,
each of its own geometry** (``serving/kvcache/pages.py::WindowedKV``): the
full layers' K/V on pages by length (``pool.k`` / ``pool.v``), the window
layers' on a ring of ``ceil((window - 1) / page_len) + 1`` pages a slot
(``pool.state["wk"]`` / ``["wv"]``); K and V stored as wide as they are.
A decode step attends through the paged decode kernel in both — the
window layers under the kernel name ``swa_decode_paged`` with the sink as
an operand; a prefill chunk walks its context block by block in the full
layers (``flash_chunk_paged`` where it serves) and a band of it in the
window layers (``inference.window_chunk_attention``), then writes the
ring.

What this family does not compute is refused by :meth:`MiMoV2Config.from_hf`
(a sink on the full layers, grouped or softmax routing, shared experts,
rope scaling); the multi-token-prediction layers the model card names
have no key in ``config.json`` and are not here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.deepseek_v2 import _swiglu, rms_norm, seeded_tree
from deepspeed_tpu.models.laguna import rotate

CAUSAL_LM = True
# one deployment, one table: held experts over ``expert``, embedding and head over the vocabulary, attention of both
# kinds, router and norms replicated
PARTITION_RULES = "deepseek_v2"

FULL, WINDOW = 0, 1  # hybrid_layer_pattern's values
_PATTERN = tuple(FULL if l in (0, 5, 11, 17, 23, 29, 35, 41, 47) else WINDOW for l in range(48))


@dataclasses.dataclass(frozen=True)
class MiMoV2Config:
    """The published ``config.json`` keys that shape the model, under
    their published names, plus the share held here."""

    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    hybrid_layer_pattern: Tuple[int, ...] = _PATTERN
    moe_layer_freq: Tuple[int, ...] = (0,) + (1,) * 47
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    layernorm_epsilon: float = 1e-5
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = None  # null: 1
    max_position_embeddings: int = 262144
    # the share held here; None = everything
    experts_held: Optional[Tuple[int, int]] = None  # (first, count)
    vocab_held: Optional[int] = None  # rows 0 .. vocab_held - 1

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held={self.experts_held} outside the {self.n_routed_experts} experts")
        if not 1 <= self.vocab_rows <= self.vocab_size:
            raise ValueError(f"vocab_held={self.vocab_held} outside the vocabulary of {self.vocab_size}")
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            if len(getattr(self, name)) != self.num_hidden_layers or any(v not in (0, 1) for v in getattr(self, name)):
                raise ValueError(f"{name} must hold a 0 or a 1 for each of the {self.num_hidden_layers} layers")
        if self.add_full_attention_sink_bias:
            raise ValueError("add_full_attention_sink_bias: a sink on the full layers is not implemented (their chunk kernel takes none)")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window={self.sliding_window}")
        for kind in (FULL, WINDOW):
            H, Hkv, dk, _ = self.geometry(kind)
            if H % Hkv:
                raise ValueError(f"{H} query heads are not whole groups over {Hkv} KV heads")
            if self.rotary_dim(kind) % 2 or not 0 < self.rotary_dim(kind) <= dk:
                raise ValueError(f"a rotated part of {self.rotary_dim(kind)} dimensions is not an even part of a key of {dk}")

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "MiMoV2Config":
        """From the keys of a published ``config.json``; ``share`` gives
        ``experts_held`` / ``vocab_held`` and may cut ``num_hidden_layers``
        (the per-layer lists then keep their first entries).  What this
        family does not compute is refused."""
        scaling = hf.get("rope_scaling") or {}
        refused = [why for bad, why in (
            (hf.get("scoring_func", "sigmoid") != "sigmoid", f"scoring_func {hf.get('scoring_func')!r} (only sigmoid)"),
            (hf.get("topk_method", "noaux_tc") != "noaux_tc", f"topk_method {hf.get('topk_method')!r} (only noaux_tc)"),
            (hf.get("n_group", 1) not in (1, None) or hf.get("topk_group", 1) not in (1, None), "grouped routing (n_group / topk_group != 1)"),
            (hf.get("n_shared_experts") not in (None, 0), "n_shared_experts"),
            (hf.get("attention_bias", False), "attention_bias"),
            (hf.get("tie_word_embeddings", False), "a tied head"),
            (hf.get("hidden_act", "silu") != "silu", f"hidden_act {hf.get('hidden_act')!r}"),
            (hf.get("add_full_attention_sink_bias", False), "add_full_attention_sink_bias"),
            (scaling.get("rope_type", scaling.get("type", "default")) != "default", f"rope_scaling {scaling}"),
            (hf.get("sliding_window_size", hf.get("sliding_window")) != hf.get("sliding_window"), "sliding_window_size != sliding_window"),
        ) if bad]
        if refused:
            raise ValueError("MiMoV2Config: not implemented: " + "; ".join(refused))
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw.update(share)
        depth = int(kw.get("num_hidden_layers", cls.num_hidden_layers))
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            kw[name] = tuple(int(v) for v in kw.get(name, getattr(cls, name)))[:depth]
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
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, t in enumerate(self.hybrid_layer_pattern) if t == FULL)

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, t in enumerate(self.hybrid_layer_pattern) if t == WINDOW)

    def geometry(self, kind: int) -> Tuple[int, int, int, int]:
        """``(query heads, KV heads, key width, value width)`` of a layer kind."""
        if kind == FULL:
            return self.num_attention_heads, self.num_key_value_heads, self.head_dim, self.v_head_dim
        return self.swa_num_attention_heads, self.swa_num_key_value_heads, self.swa_head_dim, self.swa_v_head_dim

    def rotary_dim(self, kind: int) -> int:
        return int(self.geometry(kind)[2] * self.partial_rotary_factor)

    def has_sink(self, kind: int) -> bool:
        return self.add_full_attention_sink_bias if kind == FULL else self.add_swa_attention_sink_bias

    @property
    def routed_scale(self) -> float:
        return 1.0 if self.routed_scaling_factor is None else float(self.routed_scaling_factor)

    # what the engines read of any causal-LM family
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings


# tests and chip_smoke.py: every mechanism present (both kinds of layer, 2 / 4 KV heads under 8 query heads, keys 24 wide
# over values 16, 8 of the 24 rotated, sinks, a window of 6 — smaller than a page and than a chunk —, a dense first layer,
# 16 experts and no shared one), nothing wide
MIMO_V2_TINY = MiMoV2Config(
    vocab_size=256, hidden_size=64, intermediate_size=160, num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
    head_dim=24, v_head_dim=16, swa_num_attention_heads=8, swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
    sliding_window=6, hybrid_layer_pattern=(FULL, WINDOW, WINDOW, FULL), moe_layer_freq=(0, 1, 1, 1), n_routed_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=32, max_position_embeddings=4096,
)


def rope_cos_sin(cfg: MiMoV2Config, kind: int, positions) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``cos, sin`` of shape ``positions.shape + (rot / 2,)``, float32: plain ``theta ** (-2i / rot)``, the kind's ``theta``."""
    rot = cfg.rotary_dim(kind)
    theta = cfg.rope_theta if kind == FULL else cfg.swa_rope_theta
    inv = (float(theta) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)).astype(np.float32)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv)
    return jnp.cos(ang), jnp.sin(ang)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: MiMoV2Config) -> Dict[str, Any]:
    """The parameter tree as shapes; the conventions of
    ``models/deepseek_v2.py``: ``layers`` a list with one dict a layer
    (the kinds differ in shape), gate and up projections one matrix
    (``*_gu``, gate columns first), a layer's held experts stacked on a
    leading ``held`` dim, ``W_q | W_k | W_v`` one matrix (``qkv``: ``H dk
    + Hkv dk + Hkv dv`` columns), ``o`` from ``H dv``; ``sink (H,)`` on a
    kind that has one; ``router_bias`` is ``e_bias``."""
    D, F, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.held[1]
    dense = {"mlp_gu": (D, 2 * cfg.intermediate_size), "mlp_down": (cfg.intermediate_size, D)}
    sparse = {"router": (D, cfg.n_routed_experts), "router_bias": (cfg.n_routed_experts,),
              "experts_gu": (held, D, 2 * F), "experts_down": (held, F, D)}

    def layer(l):
        kind = cfg.hybrid_layer_pattern[l]
        H, Hkv, dk, dv = cfg.geometry(kind)
        return {"attn_norm": (D,), "ffn_norm": (D,), "qkv": (D, (H + Hkv) * dk + Hkv * dv), "o": (H * dv, D),
                **({"sink": (H,)} if cfg.has_sink(kind) else {}), **(sparse if cfg.moe_layer_freq[l] else dense)}

    return {"embed": (cfg.vocab_rows, D), "head": (cfg.vocab_rows, D), "norm_f": (D,),
            "layers": [layer(l) for l in range(cfg.num_hidden_layers)]}


def special_leaf(name: str, key, shape):
    """The leaves that are not normal(std) matrices: the sinks normal(1)
    (a logit beside scores of order one), the routers' selection bias
    normal(0.02)."""
    if name == "sink":
        return jax.random.normal(key, shape, jnp.float32)
    if name == "router_bias":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    return None


def init_params_device(cfg: MiMoV2Config, seed: int = 0, dtype=jnp.bfloat16, std: float = 0.02):
    """Random parameters made on the default device, one leaf at a time (``deepseek_v2.seeded_tree``)."""
    return seeded_tree(param_shapes(cfg), cfg.num_hidden_layers, seed, dtype, std, residual=("o", "mlp_down", "experts_down"),
                       special=special_leaf)


def init_params(cfg: MiMoV2Config, seed: int = 0):
    """Host float32 tree (small configurations: tests, the engine's default when it is handed no parameters)."""
    return jax.tree.map(np.asarray, init_params_device(cfg, seed=seed, dtype=jnp.float32))


def cache_kind(cfg: MiMoV2Config, dtype):
    """The family's cache kind for :class:`PagedKVPool`: pages by length
    for the full layers, a ring of pages a slot for the window ones —
    each group its own KV heads, keys and values as wide as published."""
    from deepspeed_tpu.serving.kvcache.pages import PerHeadKV, WindowedKV

    _, Hkv, dk, dv = cfg.geometry(FULL)
    _, wkv, wdk, wdv = cfg.geometry(WINDOW)
    return WindowedKV(len(cfg.full_layers), len(cfg.window_layers), Hkv, dk, cfg.sliding_window, dtype, v_dim=dv,
                      window_pages=PerHeadKV(wkv, wdk, dtype, wdv))


# ---------------------------------------------------------------------------
# forward on the two page groups
# ---------------------------------------------------------------------------

def attention_block(cfg: MiMoV2Config, lp: Dict[str, Any], x, layer: int, caches, tables, pos, rope, n_valid, write_mask=None,
                    use_kernel: Optional[bool] = None, trace_notes: Optional[dict] = None, work=None, plan=None):
    """``x + W_o Attn(RMS(x))`` of layer ``layer`` for ``x (B, T, D)`` at
    per-row write offsets ``pos (B,)``.  ``caches = (K, V)`` the stacked
    pools of the layer's **group**, ``tables`` that group's table — the
    pool's page table, or ``ring_table`` — ``rope`` its kind's ``(cos,
    sin)``, ``work`` / ``plan`` a decode step's work list and K write
    plan under that group's geometry; the layer's index **within its
    group** is read off ``hybrid_layer_pattern``.  Returns ``(x, K, V)``."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.transformer import inference as inf

    B, T, _ = x.shape
    kind = cfg.hybrid_layer_pattern[layer]
    H, Hkv, dk, dv = cfg.geometry(kind)
    at = sum(1 for t in cfg.hybrid_layer_pattern[:layer] if t == kind)  # the layer's place in its group's stacked pools
    k_pool, v_pool = caches
    h = rms_norm(x, lp["attn_norm"], cfg.layernorm_epsilon)
    qkv = h @ lp["qkv"]
    heads = lambda t, n, d: t.reshape(B, T, n, d).transpose(0, 2, 1, 3)  # noqa: E731  (B, n, T, d)
    cos, sin = (t[:, None] for t in rope)  # (B, 1, T, rot / 2)
    q = rotate(heads(qkv[..., : H * dk], H, dk), cos, sin)
    k = rotate(heads(qkv[..., H * dk: (H + Hkv) * dk], Hkv, dk), cos, sin)
    v = heads(qkv[..., (H + Hkv) * dk:], Hkv, dv) * jnp.asarray(cfg.attention_value_scale, qkv.dtype)
    sink = lp["sink"].astype(jnp.float32) if "sink" in lp else None
    armed = _kernels.flash_decode_armed() if use_kernel is None else use_kernel
    said = f"keys {dk} / values {dv} wide, {Hkv} KV heads"
    if kind == WINDOW and T > 1:
        # a chunk attends its own rows where they are and the window's earlier pages in the ring, then the ring takes what it keeps
        ring_pages = inf.ring_pages_for(cfg.sliding_window, k_pool.shape[3])
        kc, vc, table = inf.layer_pages(k_pool, v_pool, tables, at)
        attn = inf.window_chunk_attention(q, k, v, kc, vc, table, pos, cfg.sliding_window, sink=sink)
        k_pool = inf.ring_chunk_write(k_pool, at, k, tables, pos, n_valid, ring_pages)
        v_pool = inf.ring_chunk_write(v_pool, at, v, tables, pos, n_valid, ring_pages)
        if trace_notes is not None:
            trace_notes["swa_chunk_form"] = (f"banded jnp (window_chunk_attention): query blocks of {min(T, 256)} over the window's "
                                             f"{(ring_pages - 1) * k_pool.shape[3]} earlier positions + their own; {said}"
                                             + ("" if sink is None else ", a sink column a head"))
    else:
        k_pool = inf.paged_cache_write_slices(k_pool, at, k, tables, pos, write_mask, use_kernel, plan)
        v_pool = inf.paged_cache_write_slices(v_pool, at, v, tables, pos, write_mask, use_kernel)
        kc, vc, table = inf.layer_pages(k_pool, v_pool, tables, at)
        if kind == WINDOW:
            attn = inf.window_cache_attention(q, kc, vc, table, pos, cfg.sliding_window, use_kernel=armed, work=work,
                                              trace_notes=trace_notes, sink=sink)
        elif T == 1:
            attn = inf.paged_cache_attention(q, kc, vc, table, pos, use_kernel=armed, work=work, trace_notes=trace_notes)
        else:
            with jax.named_scope("full.chunk"):
                attn = inf.paged_chunk_attention(q, kc, vc, table, pos, use_kernel=armed, trace_notes=trace_notes)
            if trace_notes is not None:
                trace_notes["gqa_prefill_form"] = inf.chunk_attention_note(trace_notes) + f"; {said}"
        if trace_notes is not None and T == 1:
            forms = [("K", inf.decode_write_takes_kernel(k_pool, use_kernel)), ("V", inf.decode_write_takes_kernel(v_pool, use_kernel))]
            trace_notes["kv_write_form"] = "; ".join(f"{n} ({w} wide): {inf.KV_WRITE_FORMS[t]}" for (n, t), w in zip(forms, (dk, dv)))
    return x + attn.transpose(0, 2, 1, 3).reshape(B, T, H * dv) @ lp["o"], k_pool, v_pool


def forward_with_cache(params: Dict[str, Any], tokens, k_pool, v_pool, state, pos, cfg: MiMoV2Config, page_table,
                       slot=None, write_mask=None, row_valid=None, take=None, use_kernel: Optional[bool] = None,
                       routing_sink: Optional[list] = None, trace_notes: Optional[dict] = None):
    """One network step on the two page groups — ``laguna.forward_with_cache``'s
    contract: ``k_pool`` / ``v_pool`` the full group ``(full layers,
    pages, kv heads, page_len, dk | dv)``, ``state = {"wk", "wv"}`` the
    window group ``(window layers, 1 + slots * ring_pages, swa kv heads,
    page_len, dk | dv)``; ``slot (B,)`` names the slots of a **prefill
    chunk**'s rows (``pos`` whole pages), ``slot`` None is a **decode
    step**.  Returns ``(logits (B, V) float32, k_pool, v_pool, state,
    aux)``; ``routing_sink`` is given, a sparse layer, ``(chosen experts
    (B * T, top_k) int32, the router's logits of the chosen, float32)``."""
    from deepspeed_tpu.moe.layer import dropless_held_experts, sigmoid_topk
    from deepspeed_tpu.ops.kernels.flash_decode import paged_tile, paged_work_list
    from deepspeed_tpu.ops.transformer.inference import decode_write_plan, ring_pages_for, ring_table

    B, T = tokens.shape
    page_len, P = k_pool.shape[3], page_table.shape[1]
    if T > 1 and T % page_len:
        raise ValueError(f"mimo_v2: a prefill chunk of {T} tokens is not whole pages of {page_len}")
    R = ring_pages_for(cfg.sliding_window, page_len)
    wk, wv = state["wk"], state["wv"]
    if wk.shape[1] % R != 1:
        raise ValueError(f"mimo_v2: the window group's {wk.shape[1]} pages are not 1 + slots x {R} ring pages")
    ring = ring_table(jnp.arange(B, dtype=jnp.int32) if slot is None else slot, R, P)
    tables = {FULL: page_table, WINDOW: ring}
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    rope = {kind: rope_cos_sin(cfg, kind, positions) for kind in (FULL, WINDOW)}
    n_valid = jnp.full((B,), T, jnp.int32) if row_valid is None else jnp.sum(row_valid.astype(jnp.int32), axis=1)
    work, plan = {FULL: None, WINDOW: None}, {FULL: None, WINDOW: None}
    if T == 1:
        # a decode step's work lists and K write plans, once for all layers of a group, each under its group's own tile
        for kind, (kp, vp) in ((FULL, (k_pool, v_pool)), (WINDOW, (wk, wv))):
            span = paged_tile(kp, P, vp)[1]
            work[kind] = paged_work_list(pos, write_mask, page_len, P, span, cfg.sliding_window if kind == WINDOW else None)
            plan[kind] = decode_write_plan(kp, tables[kind], pos, write_mask, use_kernel)
    if trace_notes is not None:
        trace_notes["swa_ring_positions"] = R * page_len
    x = jnp.take(params["embed"], tokens, axis=0)
    valid = None if row_valid is None else row_valid.reshape(B * T)
    aux = []
    for layer, lp in enumerate(params["layers"]):
        kind = cfg.hybrid_layer_pattern[layer]
        caches = (k_pool, v_pool) if kind == FULL else (wk, wv)
        x, *caches = attention_block(cfg, lp, x, layer, caches, tables[kind], pos, rope[kind], n_valid, write_mask, use_kernel,
                                     trace_notes, work[kind], plan[kind])
        if kind == FULL:
            k_pool, v_pool = caches
        else:
            wk, wv = caches
        h = rms_norm(x, lp["ffn_norm"], cfg.layernorm_epsilon)
        if "mlp_gu" in lp:  # a dense layer
            x = x + _swiglu(h, lp["mlp_gu"], lp["mlp_down"])
            continue
        flat = h.reshape(B * T, -1)
        with jax.named_scope("moe.router"):
            logits = jnp.dot(flat.astype(jnp.float32), lp["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
            idx, w = sigmoid_topk(logits, lp["router_bias"], cfg.num_experts_per_tok, cfg.routed_scale, cfg.norm_topk_prob)
        if trace_notes is not None:
            trace_notes["moe_router_form"] = "sigmoid_topk (float32, highest; the bias selects; renormalised)"
        if routing_sink is not None:
            routing_sink.append((idx, jnp.take_along_axis(logits, idx, axis=-1)))
        routed, counts = dropless_held_experts(flat, idx, w, lp["experts_gu"], lp["experts_down"], cfg.held, valid,
                                               trace_notes=trace_notes)
        x = x + routed.reshape(x.shape)
        aux.append(counts)
    take = jnp.full((B,), T - 1, jnp.int32) if take is None else take
    last = jnp.take_along_axis(x, take[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(rms_norm(last, params["norm_f"], cfg.layernorm_epsilon), params["head"].T, preferred_element_type=jnp.float32)
    aux = jnp.stack(aux) if aux else jnp.zeros((0, cfg.held[1] + 1), jnp.int32)
    return logits, k_pool, v_pool, {"wk": wk, "wv": wv}, aux


def serving_forward(cfg: MiMoV2Config):
    """The family seam of ``ServingEngine`` (docs/serving.md §Model
    families), as ``laguna.serving_forward``: ``state`` is the window
    group, ``slot`` the prefill chunk's slot.  ``fwd.trace_notes`` holds
    the forms the two programs compiled — ``swa_decode_form``,
    ``swa_chunk_form``, ``kv_write_form`` (each says the sink and the
    widths), ``swa_ring_positions``, ``paged_decode_walk``,
    ``gqa_prefill_form``, ``moe_router_form``, ``moe_grouped_kernel`` /
    ``_fallback`` — and ``fwd.decode_keeps`` that the decode program hands
    back what its routers chose (``ServingEngine.decode_kept``)."""
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
