"""ZAYA: a fourth decoder family, on the serving path.

Every layer is two residual sublayers, the same in all layers::

    x <- res(x, CCA(RMS(x)))           compressed convolutional attention, ops/transformer/compressed_attention.py
    x, r <- res(x, MoE(RMS(x), r))     top-1 of an MLP router whose down-projection ``r`` is carried across layers
    res(x, y) = (a_r * x + b_r) + (a_o * y + b_o)    learned vectors of the hidden size, a set a sublayer

RMSNorm everywhere, no bias in any projection, rotary on half of each
head's dimensions, a **tied** head.  The stack carries ``(x, r)``: the
router of layer ``l`` adds ``gamma_l`` times the previous layer's
``r`` to its own (``moe/layer.py::mlp_top1``).  The family is *told
its share* like the other two MoE families (``experts_held``,
``vocab_held``): the router keeps its published width and top-1, what
absent experts would add is left out, and the tied head is over the
held rows of the embedding.

Serving runs through ``ServingEngine`` on a **hybrid cache**
(``serving/kvcache/pages.py::HybridKV``) in which **every layer has
both geometries**: K/V pages with the mixed, normalised, rotated keys
and the shifted values, and per slot the convolution tail (``conv``: the
last two ``[q~ ; k~]`` rows; ``vshift``: the last ``h W_v2``).  A prefill
chunk reads the tail as zero where it starts at position 0, so a slot
needs no reset between requests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.deepseek_v2 import rms_norm, seeded_tree

CAUSAL_LM = True
PARTITION_RULES = "zaya"  # the family's table in sharding/rules.py


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    """The published ``config.json`` keys that shape the model (the
    rotary base from ``rope_parameters.hybrid``), plus the share held here."""

    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    # the share held here; None = everything
    experts_held: Optional[Tuple[int, int]] = None  # (first, count)
    vocab_held: Optional[int] = None  # rows 0 .. vocab_held - 1

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held={self.experts_held} outside the {self.num_experts} experts")
        if not 1 <= self.vocab_rows <= self.vocab_size:
            raise ValueError(f"vocab_held={self.vocab_held} outside the vocabulary of {self.vocab_size}")
        if self.num_attention_heads % self.num_key_value_heads or self.num_key_value_heads % 2:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads, and that even (the value shift)")

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "ZayaConfig":
        """From the keys of a published ``config.json``; ``share`` gives
        ``experts_held`` / ``vocab_held``.  What this family does not
        implement is refused."""
        from deepspeed_tpu.ops.transformer.compressed_attention import TAPS

        refused = [why for bad, why in (
            (any(t != "hybrid" for t in hf.get("layer_types", ())), "layer types other than 'hybrid' (sliding-window layers)"),
            (hf.get("sliding_window") is not None, "sliding_window"),
            (hf.get("num_experts_per_tok", 1) != 1, "more than one expert a token"),
            (hf.get("cca_time0", TAPS) != TAPS or hf.get("cca_time1", TAPS) != TAPS, f"convolutions of other than {TAPS} taps"),
            (not hf.get("tie_word_embeddings", True), "an untied head"),
            (hf.get("attention_bias", False) or hf.get("lm_head_bias", False), "biases"),
            (hf.get("hidden_act", "silu") != "silu", f"hidden_act {hf.get('hidden_act')!r}"),
        ) if bad]
        if refused:
            raise ValueError("ZayaConfig: not implemented: " + "; ".join(refused))
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        rope = (hf.get("rope_parameters") or {}).get("hybrid") or {}
        if "rope_theta" in rope:
            kw["rope_theta"] = float(rope["rope_theta"])
        kw.update(share)
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
    def cca(self):
        from deepspeed_tpu.ops.transformer.compressed_attention import Sizes

        return Sizes(self.num_attention_heads, self.num_key_value_heads, self.head_dim,
                     int(self.head_dim * self.partial_rotary_factor), float(self.rope_theta))

    # what the engines read of any causal-LM family
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings


# tests and chip_smoke.py: every mechanism present (2 KV heads x 2 groups, 8 experts, a carried router), nothing wide
ZAYA_TINY = ZayaConfig(vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
                       head_dim=16, num_experts=8, moe_intermediate_size=32, router_hidden_size=16,
                       max_position_embeddings=4096)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

RES_ROWS = 4  # a sublayer's residual vectors: a_r, b_r, a_o, b_o


def param_shapes(cfg: ZayaConfig) -> Dict[str, Any]:
    """The parameter tree as shapes; the conventions of
    ``models/deepseek_v2.py``: ``layers`` a list with one dict a layer
    (nothing stacked over layers), gate and up projections one matrix
    (``experts_gu``, gate columns first), a layer's held experts stacked
    on a leading ``held`` dim, ``W_q | W_k | W_v1 | W_v2`` one matrix
    (``qkv``).  No ``head``: the embedding is the head."""
    D, F, R, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.router_hidden_size, cfg.num_experts
    sz = cfg.cca
    layer = {"attn_norm": (D,), "ffn_norm": (D,), "res_attn": (RES_ROWS, D), "res_moe": (RES_ROWS, D),
             "qkv": (D, sz.channels + 2 * sz.shift_width), "conv0": (cfg.cca_time0, sz.channels),
             "conv1": (cfg.cca_time1, sz.heads + sz.kv_heads, sz.head_dim, sz.head_dim), "tau": (sz.kv_heads,),
             "o": (sz.heads * sz.head_dim, D),
             "router_down": (D, R), "router_gamma": (), "router_norm": (R,), "router_w1": (R, R), "router_w2": (R, R),
             "router_w3": (R, E), "router_bias": (E,),
             "experts_gu": (cfg.held[1], D, 2 * F), "experts_down": (cfg.held[1], F, D)}
    return {"embed": (cfg.vocab_rows, D), "norm_f": (D,), "layers": [dict(layer) for _ in range(cfg.num_hidden_layers)]}


def special_leaf(name: str, key, shape) -> Optional[jnp.ndarray]:
    """The leaves that are not a normal(0.02) matrix: the residual vectors
    near the identity and not at it (scales ``1 + normal(0.05)``, shifts
    ``normal(0.002)``); the depthwise taps normal(0.45) and the grouped
    taps normal(0.9 fan_in^-1/2) (a head's 2 x d inputs) with the
    temperature on k uniform in [3, 4] — peaked attention (at 1 a softmax
    over random keys is their mean, the same for every position) that
    falls mostly on the position's own key, which the q-k mean sets apart
    from the random ones, and not on whichever of a few near-tied far keys
    wins; the router's hidden layers normal(R^-1/2), the later two with
    zero mean over their fan-in (GELU's positive mean is otherwise a
    token-independent preference for some experts); the depth averaging
    coefficient uniform in [0.25, 0.75]; the selection bias 0."""
    f32 = jnp.float32
    if name in ("res_attn", "res_moe"):
        n = jax.random.normal(key, shape, f32)
        return jnp.stack([1.0 + 0.05 * n[0], 0.002 * n[1], 1.0 + 0.05 * n[2], 0.002 * n[3]])
    if name == "conv0":
        return jax.random.normal(key, shape, f32) * 0.45
    if name == "conv1":
        return jax.random.normal(key, shape, f32) * 0.9 * (shape[0] * shape[2]) ** -0.5
    if name in ("router_w1", "router_w2", "router_w3"):
        w = jax.random.normal(key, shape, f32) * shape[0] ** -0.5
        return w if name == "router_w1" else w - jnp.mean(w, axis=0, keepdims=True)
    if name == "tau":
        return jax.random.uniform(key, shape, f32, 3.0, 4.0)
    if name == "router_gamma":
        return jax.random.uniform(key, shape, f32, 0.25, 0.75)
    if name == "router_bias":
        return jnp.zeros(shape, f32)
    return None


def init_params_device(cfg: ZayaConfig, seed: int = 0, dtype=jnp.bfloat16, std: float = 0.02):
    """Random parameters made on the default device, one leaf at a time
    (``deepseek_v2.seeded_tree``; :func:`special_leaf` for the rest)."""
    # no projection is scaled down by the depth: under a tied head a residual stream that stays near the
    # embedding predicts every position's own input token
    return seeded_tree(param_shapes(cfg), cfg.num_hidden_layers, seed, dtype, std, residual=(), special=special_leaf)


def init_params(cfg: ZayaConfig, seed: int = 0):
    """Host float32 tree (small configurations: tests, the engine's
    default when it is handed no parameters)."""
    return jax.tree.map(np.asarray, init_params_device(cfg, seed=seed, dtype=jnp.float32))


def cache_kind(cfg: ZayaConfig, dtype):
    """The family's cache kind for :class:`PagedKVPool`: **every** layer
    has K/V pages and, per slot, its convolution tail."""
    from deepspeed_tpu.ops.transformer.compressed_attention import TAPS
    from deepspeed_tpu.serving.kvcache.pages import HybridKV, PerHeadKV

    L, sz = cfg.num_hidden_layers, cfg.cca
    return HybridKV(L, PerHeadKV(sz.kv_heads, sz.head_dim, dtype), {"conv": (L, (TAPS, sz.channels), dtype),
                                                                    "vshift": (L, (sz.shift_width,), dtype)})


# ---------------------------------------------------------------------------
# forward on the hybrid cache
# ---------------------------------------------------------------------------

def res(x, y, vec):
    """``(a_r * x + b_r) + (a_o * y + b_o)`` with ``vec (4, D)``, in float32, back in ``x``'s dtype."""
    v = vec.astype(jnp.float32)
    return ((v[0] * x.astype(jnp.float32) + v[1]) + (v[2] * y.astype(jnp.float32) + v[3])).astype(x.dtype)


def forward_with_cache(params: Dict[str, Any], tokens, k_pool, v_pool, state, pos, cfg: ZayaConfig, page_table,
                       slot=None, write_mask=None, row_valid=None, take=None, use_kernel: Optional[bool] = None,
                       routing_sink: Optional[list] = None, trace_notes: Optional[dict] = None):
    """One network step on the hybrid cache.

    ``tokens (B, T)``; ``k_pool`` / ``v_pool`` the ``(layers, pages, kv
    heads, page_len, head_dim)`` buffers; ``state`` the per-slot group
    ``{"conv", "vshift"}`` (``HybridKV.state_buffers``); ``pos (B,)``
    per-row write offsets; ``page_table (B, pages_per_slot)``.  ``slot
    (B,)`` names the slots of a **prefill chunk**'s rows; ``slot`` None
    is a **decode step** (row ``b`` is slot ``b``), where ``write_mask
    (B,)`` False sends a row's K/V write to the garbage page and leaves
    its tail alone.  ``row_valid (B, T)`` marks the real tokens (a
    chunk's padded tail is computed, and kept out of the tail and the
    counters); ``take (B,)`` picks the position whose logits are wanted
    (default: the last).  Returns ``(logits (B, V) float32, k_pool,
    v_pool, state, aux)`` with ``aux (layers, held + 1) int32`` as
    ``deepseek_v2.forward_with_cache`` returns it.  ``routing_sink`` is
    given each layer's chosen expert ``(B * T, 1)``."""
    from deepspeed_tpu.moe.layer import dropless_held_experts, mlp_top1
    from deepspeed_tpu.ops.transformer import compressed_attention as cca
    from deepspeed_tpu.ops.kernels.flash_decode import paged_tile, paged_work_list

    B, T = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    P = page_table.shape[1]
    work = paged_work_list(pos, write_mask, k_pool.shape[3], P, paged_tile(k_pool, P)[1]) if T == 1 else None  # once, for every layer
    r = jnp.zeros((B * T, cfg.router_hidden_size), jnp.float32)  # the router's carry: nothing before the first layer
    valid = None if row_valid is None else row_valid.reshape(B * T)
    aux = []
    for layer, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        o, k_pool, v_pool, state = cca.attention(cfg.cca, lp["qkv"], lp["conv0"], lp["conv1"], lp["tau"], h, k_pool, v_pool,
                                                 state, layer, pos, page_table, slot, write_mask, row_valid, use_kernel,
                                                 trace_notes, work)
        x = res(x, o @ lp["o"], lp["res_attn"])
        flat = rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps).reshape(B * T, -1)
        with jax.named_scope("moe.router"):
            idx, w, r = mlp_top1(flat, r, lp, cfg.rms_norm_eps)
        if trace_notes is not None:
            trace_notes["moe_router_form"] = "mlp_top1 (float32, highest)"
        if routing_sink is not None:
            routing_sink.append(idx)
        routed, counts = dropless_held_experts(flat, idx, w, lp["experts_gu"], lp["experts_down"], cfg.held, valid,
                                               trace_notes=trace_notes)
        x = res(x, routed.reshape(x.shape), lp["res_moe"])
        aux.append(counts)
    take = jnp.full((B,), T - 1, jnp.int32) if take is None else take
    last = jnp.take_along_axis(x, take[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(rms_norm(last, params["norm_f"], cfg.rms_norm_eps), params["embed"].T,
                     preferred_element_type=jnp.float32)  # the tied head, over the rows held
    return logits, k_pool, v_pool, state, jnp.stack(aux)


def serving_forward(cfg: ZayaConfig):
    """The family seam of ``ServingEngine`` (docs/serving.md §Model
    families): ``fwd(params, tokens, k, v, pos, page_table=, write_mask=,
    row_valid=, take=, state=, slot=) -> (logits, k, v, state, aux)``.
    ``slot`` is the prefill chunk's slot (a decode step passes None: its
    rows are the slots).  ``fwd.trace_notes`` holds the forms the two
    programs compiled: ``cca_decode_kernel`` / ``_fallback``,
    ``paged_decode_walk``, ``cca_prefill_form`` (from
    ``chunk_attention_kernel`` / ``_fallback``), ``moe_router_form``,
    ``moe_grouped_kernel`` / ``_fallback``."""
    notes: Dict[str, Any] = {}

    def fwd(params, tokens, k, v, pos, page_table, write_mask=None, row_valid=None, take=None, state=None, slot=None):
        return forward_with_cache(params, tokens, k, v, state, pos, cfg, page_table, slot=slot, write_mask=write_mask,
                                  row_valid=row_valid, take=take, trace_notes=notes)

    fwd.trace_notes = notes
    return fwd
