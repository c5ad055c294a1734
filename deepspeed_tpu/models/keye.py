"""Keye: a fifth decoder family, on the serving path — the language model
of a vision-language model whose attention is **learned sparse
attention** (``ops/transformer/sparse_attention.py``).

Every layer is two pre-norm residual sublayers, the same in all layers::

    x <- x + W_o DSA(RMS(x))      grouped-query attention over the positions an indexer selects
    x <- x + MoE(RMS(x))          softmax over all experts in float32, top-k, renormalised; no shared expert

RMSNorm everywhere, no bias in any projection, RMSNorm over each head's
dims on q and k, rotary in **three position streams** (temporal, height,
width: ``mrope_section``), an **untied** head.  The family is *told its
share* like the other MoE families (``experts_held``, ``vocab_held``):
the router keeps its published width and top-k, what absent experts would
add is left out, embedding and head are the rows held.  The vision tower
is not here (its widths are not published where this was written from):
the language model takes token ids, and a text token carries its position
in all three streams — ``forward_with_cache`` takes unequal streams, the
serving path feeds equal ones.

Serving runs through ``ServingEngine`` on a cache kind whose pages carry
**three leaves** (``serving/kvcache/pages.py::IndexedKV``): K, V and one
indexer key a position, under one page table; prefix reuse stays on.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.deepseek_v2 import rms_norm, seeded_tree

CAUSAL_LM = True
PARTITION_RULES = "keye"  # the family's table in sharding/rules.py: its head is (hidden, vocabulary)


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """The published ``config.json`` keys that shape the language model
    (``sa_config``'s flattened: ``index_*``, ``select_topk``), plus the
    share held here."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10000000.0
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    # sa_config: the indexer and its selection
    index_n_heads: int = 16
    index_head_dim: int = 64
    select_topk: int = 2048
    index_rotary_dim: int = 32  # assumed: the first half of an indexer head is rotated (temporal stream)
    # the share held here; None = everything
    experts_held: Optional[Tuple[int, int]] = None  # (first, count)
    vocab_held: Optional[int] = None  # rows 0 .. vocab_held - 1

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held={self.experts_held} outside the {self.num_experts} experts")
        if not 1 <= self.vocab_rows <= self.vocab_size:
            raise ValueError(f"vocab_held={self.vocab_held} outside the vocabulary of {self.vocab_size}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if sum(self.mrope_section) != self.head_dim // 2:
            raise ValueError(f"mrope_section {self.mrope_section} does not split the {self.head_dim // 2} frequency pairs of a head")
        if self.index_rotary_dim % 2 or self.index_rotary_dim > self.index_head_dim:
            raise ValueError(f"index_rotary_dim={self.index_rotary_dim} is not an even part of index_head_dim={self.index_head_dim}")

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "KeyeConfig":
        """From the keys of a published ``config.json`` (the language
        model's: ``text_config`` where nested); ``share`` gives
        ``experts_held`` / ``vocab_held``.  What this family does not
        implement is refused."""
        hf = {**hf, **(hf.get("text_config") or {})}
        sa = hf.get("sa_config") or {}
        rope = hf.get("rope_scaling") or hf.get("rope_parameters") or {}
        refused = [why for bad, why in (
            (hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers"), "dense MLP layers among the sparse ones"),
            (hf.get("tie_word_embeddings", False), "a tied head"),
            (hf.get("attention_bias", False), "biases"),
            (hf.get("hidden_act", "silu") != "silu", f"hidden_act {hf.get('hidden_act')!r}"),
            (hf.get("sliding_window") is not None and hf.get("use_sliding_window", False), "sliding_window"),
            (hf.get("shared_expert_intermediate_size"), "a shared expert"),
            (sa.get("indexer_num_kv_heads", 1) != 1, "an indexer with more than one key head"),
        ) if bad]
        if refused:
            raise ValueError("KeyeConfig: not implemented: " + "; ".join(refused))
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        if "mrope_section" in rope:
            kw["mrope_section"] = rope["mrope_section"]
        for ours, theirs in (("index_n_heads", "indexer_num_heads"), ("index_head_dim", "indexer_head_dim"), ("select_topk", "topk")):
            if theirs in sa:
                kw[ours] = int(sa[theirs])
        if "mrope_section" in kw:
            kw["mrope_section"] = tuple(int(v) for v in kw["mrope_section"])
        if "index_head_dim" in kw and "index_rotary_dim" not in kw:
            kw["index_rotary_dim"] = kw["index_head_dim"] // 2
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
    def dsa(self):
        from deepspeed_tpu.ops.transformer.sparse_attention import Sizes

        return Sizes(self.num_attention_heads, self.num_key_value_heads, self.head_dim, self.index_n_heads, self.index_head_dim,
                     self.index_rotary_dim, self.select_topk, float(self.rope_theta), tuple(self.mrope_section))

    # what the engines read of any causal-LM family
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings


# tests and chip_smoke.py: every mechanism present (2 KV heads x 2 groups, 8 experts top-2, a 16-position selection), nothing wide
KEYE_TINY = KeyeConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                       head_dim=16, mrope_section=(2, 3, 3), num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
                       index_n_heads=2, index_head_dim=8, index_rotary_dim=4, select_topk=16, max_position_embeddings=4096)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: KeyeConfig) -> Dict[str, Any]:
    """The parameter tree as shapes; the conventions of
    ``models/deepseek_v2.py``: ``layers`` a list with one dict a layer,
    gate and up projections one matrix (``experts_gu``, gate columns
    first), a layer's held experts stacked on a leading ``held`` dim,
    ``W_q | W_k | W_v`` one matrix (``qkv``).  ``intermediate_size`` of
    the published config is unused: every layer is sparse."""
    D, F, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    H, Hkv, d, Hi, di = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.index_n_heads, cfg.index_head_dim
    layer = {"attn_norm": (D,), "ffn_norm": (D,), "qkv": (D, (H + 2 * Hkv) * d), "q_norm": (d,), "k_norm": (d,), "o": (H * d, D),
             "index_q": (D, Hi * di), "index_k": (D, di), "index_w": (D, Hi), "index_k_gain": (di,), "index_k_bias": (di,),
             "router": (D, E), "experts_gu": (cfg.held[1], D, 2 * F), "experts_down": (cfg.held[1], F, D)}
    return {"embed": (cfg.vocab_rows, D), "norm_f": (D,), "head": (D, cfg.vocab_rows),
            "layers": [dict(layer) for _ in range(cfg.num_hidden_layers)]}


def special_leaf(name: str, key, shape) -> Optional[jnp.ndarray]:
    """The leaves that are not a normal(0.02) matrix: the indexer key's LayerNorm gain 1 and bias 0."""
    if name == "index_k_gain":
        return jnp.ones(shape, jnp.float32)
    if name == "index_k_bias":
        return jnp.zeros(shape, jnp.float32)
    return None


def init_params_device(cfg: KeyeConfig, seed: int = 0, dtype=jnp.bfloat16, std: float = 0.02):
    """Random parameters made on the default device, one leaf at a time (``deepseek_v2.seeded_tree``)."""
    return seeded_tree(param_shapes(cfg), cfg.num_hidden_layers, seed, dtype, std, residual=("o", "experts_down"), special=special_leaf)


def init_params(cfg: KeyeConfig, seed: int = 0):
    """Host float32 tree (small configurations: tests, the engine's default when it is handed no parameters)."""
    return jax.tree.map(np.asarray, init_params_device(cfg, seed=seed, dtype=jnp.float32))


def cache_kind(cfg: KeyeConfig, dtype):
    """The family's cache kind for :class:`PagedKVPool`: K, V and an indexer key a position, under one page table."""
    from deepspeed_tpu.serving.kvcache.pages import IndexedKV

    return IndexedKV(cfg.num_key_value_heads, cfg.head_dim, cfg.index_head_dim, dtype)


# ---------------------------------------------------------------------------
# forward on the three-leaf cache
# ---------------------------------------------------------------------------

def forward_with_cache(params: Dict[str, Any], tokens, k_pool, v_pool, pos, cfg: KeyeConfig, page_table, write_mask=None,
                       row_valid=None, take=None, positions3=None, use_kernel: Optional[bool] = None,
                       routing_sink: Optional[list] = None, selection_sink: Optional[list] = None,
                       trace_notes: Optional[dict] = None):
    """One network step on the three-leaf cache.

    ``tokens (B, T)``; ``k_pool = {"k": (layers, pages, kv heads,
    page_len, head_dim), "idx": (layers, pages, page_len, index dim)}``
    and ``v_pool`` the V pages; ``pos (B,)`` per-row write offsets;
    ``page_table (B, pages_per_slot)``.  ``T == 1`` is a **decode step**
    (``write_mask (B,)`` False sends a row's writes to the garbage page),
    ``T > 1`` a **prefill chunk**.  ``positions3 (3, B, T)`` are the rotary
    position streams (default: ``pos + t`` in all three — text).
    ``row_valid (B, T)`` marks the real tokens for the counters; ``take
    (B,)`` picks the position whose logits are wanted (default: the last).
    Returns ``(logits (B, V) float32, k_pool, v_pool, aux)`` with ``aux
    (layers, held + 1) int32`` as ``deepseek_v2.forward_with_cache``
    returns it.  ``routing_sink`` is given each layer's chosen experts
    ``(B * T, top_k)``, ``selection_sink`` each layer's ``(selection mask
    (B, T, pages_per_slot * page_len), threshold (B, T))``: the mask its
    attention read and the float32 index score it was cut at."""
    from deepspeed_tpu.moe.layer import dropless_held_experts, softmax_topk
    from deepspeed_tpu.ops.kernels.sparse_decode import work_list
    from deepspeed_tpu.ops.transformer import sparse_attention as dsa

    B, T = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if positions3 is None:
        positions3 = jnp.broadcast_to(pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :], (3, B, T))
    work = work_list(pos, write_mask, k_pool["k"], page_table.shape[1]) if T == 1 else None  # once, for every layer
    valid = None if row_valid is None else row_valid.reshape(B * T)
    aux = []
    for layer, lp in enumerate(params["layers"]):
        u = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        o, k_pool, v_pool = dsa.attention(cfg.dsa, lp, u, k_pool, v_pool, layer, pos, positions3, page_table, write_mask,
                                          use_kernel, trace_notes, work, cfg.rms_norm_eps, selection_sink)
        x = x + (o @ lp["o"]).astype(x.dtype)
        flat = rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps).reshape(B * T, -1)
        with jax.named_scope("moe.router"):
            logits = jnp.dot(flat.astype(jnp.float32), lp["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
            idx, w = softmax_topk(logits, cfg.num_experts_per_tok, cfg.norm_topk_prob)
        if trace_notes is not None:
            trace_notes["moe_router_form"] = "softmax_topk (float32, highest; renormalised)"
        if routing_sink is not None:
            routing_sink.append(idx)
        routed, counts = dropless_held_experts(flat, idx, w, lp["experts_gu"], lp["experts_down"], cfg.held, valid,
                                               trace_notes=trace_notes)
        x = x + routed.reshape(x.shape)
        aux.append(counts)
    take = jnp.full((B,), T - 1, jnp.int32) if take is None else take
    last = jnp.take_along_axis(x, take[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(rms_norm(last, params["norm_f"], cfg.rms_norm_eps), params["head"], preferred_element_type=jnp.float32)
    return logits, k_pool, v_pool, jnp.stack(aux)


def serving_forward(cfg: KeyeConfig):
    """The family seam of ``ServingEngine`` (docs/serving.md §Model
    families): ``fwd(params, tokens, k, v, pos, page_table=, write_mask=,
    row_valid=, take=, state=, slot=) -> (logits, k, v, state, aux)``; the
    kind has no per-slot state (``state`` passes through, None).
    ``fwd.trace_notes`` holds the forms the two programs compiled:
    ``dsa_index_form``, ``dsa_prefill_index_form``, ``dsa_select_form``,
    ``dsa_decode_kernel``, ``dsa_prefill_form`` (from
    ``chunk_attention_kernel`` / ``_fallback``), ``moe_router_form``,
    ``moe_grouped_kernel`` / ``_fallback``.

    ``fwd.decode_keeps``: the decode program hands back, beside its
    tokens, what its step selected — ``kept={"selected": (layers, slots,
    positions) bool, "threshold": (layers, slots) float32, "pos":
    (slots,)}``, the masks its attention read and the index scores they
    were cut at — which the engine leaves on the device as
    ``ServingEngine.decode_kept`` until the next step (4 MB at 16 slots of
    33,792; nothing fetches it but a check of the served program)."""
    notes: Dict[str, Any] = {}

    def fwd(params, tokens, k, v, pos, page_table, write_mask=None, row_valid=None, take=None, state=None, slot=None,
            kept: Optional[dict] = None):
        sink = [] if kept is not None else None
        logits, k, v, aux = forward_with_cache(params, tokens, k, v, pos, cfg, page_table, write_mask=write_mask,
                                               row_valid=row_valid, take=take, selection_sink=sink, trace_notes=notes)
        if kept is not None:
            kept.update(selected=jnp.stack([m[:, 0] for m, _ in sink]), threshold=jnp.stack([t[:, 0] for _, t in sink]), pos=pos)
        return logits, k, v, state, aux

    fwd.trace_notes = notes
    fwd.decode_keeps = True
    return fwd
