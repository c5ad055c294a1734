"""The language model of Keye-VL-2.0 in plain ``jax.numpy``: the
reference the program's served tokens, cache rows and selections are held
against.

float32 with ``highest`` matmul precision, no kernel, no cache, no
chunking, no batching; the full score matrix of the indexer and of the
attention a block of queries at a time, ``jax.lax.top_k`` for the
selected set, one block of weights live at a time (a layer's attention,
one expert) — the weights come from :mod:`weights_keye` and the seed,
never from the program under test.

The equations (``x`` a token's hidden state, ``RMS`` RMSNorm with a
learned gain, eps ``rms_norm_eps``, no bias in any projection):

* layer ``l`` (all alike): ``x <- x + W_o DSA(RMS(x))``, then ``x <- x +
  MoE(RMS(x))``; after the last layer ``RMS``, then the **untied** head
  (the rows held).
* DSA, ``H`` = 32 query / ``Hkv`` = 4 KV heads of ``d`` = 128, ``G = H /
  Hkv``, for position ``t`` with ``u_t = RMS(x_t)``: ``q = u W_q``, ``k = u
  W_k``, ``v = u W_v``; RMSNorm over each head's ``d`` on q and k; rotary,
  half layout, theta 1e7, the 64 frequency pairs split 16 / 24 / 24 over
  three position streams (temporal, height, width).
  **Indexer**: ``qI = u W_qI`` (16 heads of 64), ``kI = LayerNorm(u W_kI)``
  (one key head of 64), ``w = u W_w`` (16); rotary (temporal stream, theta
  1e7) on the first 32 dims of each ``qI`` head and of ``kI``; ``I(t, s) =
  sum_j w_j relu(qI_j . kI_s) 64^-1/2 16^-1/2`` for ``s <= t``.  ``S_t`` =
  the ``topk`` = 2,048 positions of largest ``I(t, .)``, ties to the lower
  position; every ``s <= t`` while ``t < topk``.
  ``o_h = sum_{s in S_t} softmax_s(q_h . k_{h // G, s} / sqrt(d)) v_{h // G, s}``.
* MoE: ``p = softmax(RMS(x) W_g)`` over all 128 experts; the 8 largest,
  their weights divided by their sum; ``MoE = sum_e w_e E_e(RMS(x))`` over
  the chosen experts that are held, ``E_e`` SwiGLU at 768; no shared
  expert.

**Assumed** — not settled by the published ``config.json``, each also a
comment where it happens (the configuration file repeats them under
``assumed``): (1) ``qk_norm``: RMSNorm with a learned gain over each
head's 128 dims on q and k before rotary (the family's Qwen3-MoE
convention; the config's keys are that family's); (2) ``mrope_layout``:
the frequency pairs are split over the streams in *contiguous* sections
— pairs 0-15 temporal, 16-39 height, 40-63 width (the Qwen2-VL layout;
an interleaved one would permute pairs and change nothing for text); (3)
``indexer_query``: ``qI`` is projected from the hidden state (the config
has no query latent to project it from, as DeepSeek-V3.2's indexer has);
(4) ``indexer_norm_rope``: a LayerNorm (gain, bias) on ``kI`` and rotary
on the first half (32 dims) of each indexer head and of the key, by the
temporal position, as in the published DeepSeek-V3.2 indexer that
``described_as`` names — its Hadamard rotation of ``qI`` and ``kI`` is
left out (orthogonal: it changes no score in exact arithmetic, and exists
for an fp8 cache this model does not state); (5) ``indexer_chunks``:
``q_chunk_size`` / ``kv_chunk_size`` 512 are the tiles in which scores
are computed and change no result; (6) ``intermediate_size`` 6,144 is
unused (``decoder_sparse_step`` 1, ``mlp_only_layers`` []: every layer is
sparse); (7) the experts are the share ``experts_held`` and the
vocabulary the slice run — with no share given the model is whole; (8)
the vision tower is absent: token ids in, and a text token carries its
position in all three streams.

``precision`` rounds every matmul *operand* of the projections, the
attention products, the router and the experts before an exact float32
contraction (``"float32"`` the reference, ``"bfloat16"`` what the
configuration states for those, ``"int8"`` the control below it).  The
indexer stays float32 whatever ``precision`` is (the configuration states
float32 for it) unless ``index_precision`` says otherwise:
``"bfloat16"`` is the control that ranks with a bfloat16 indexer (its
operands rounded, and each score held in bfloat16); ``select="recent"`` is
the control that attends to the most recent ``topk`` positions.  Both are
put in the program's place by ``control_keye.py``, as the int8 control is:
the program itself has no such switch.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_keye as W
from .reference_gpt2 import _dot
from .reference_solar_open2 import rms, swiglu

QUERY_BLOCK = 256  # scores and attention are computed this many queries at a time: (32 heads x 256 x 33,792) float32 is 1.1 GB


def mrope(x, positions3, theta: float, sections: Tuple[int, ...]):
    """Three-stream rotary (half layout) on all dims of ``x (T, heads,
    d)``: frequency pair ``i`` turns by ``positions3[stream(i)] * theta^(-i
    / (d / 2))``, the streams taking ``sections`` pairs each, in order."""
    half = x.shape[-1] // 2
    stream = np.repeat(np.arange(len(sections)), sections)  # assumed 2: contiguous sections
    inv = jnp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half), jnp.float32)
    ang = jnp.asarray(positions3, jnp.float32).T[:, stream] * inv  # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rope_lead(x, positions, rot: int, theta: float):
    """One-stream rotary (half layout) on the first ``rot`` dims of ``x (T, heads, d)``."""
    half = rot // 2
    ang = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half), jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def indexer(ap: Dict[str, Any], u, positions, z: Dict[str, Any], eps: float, precision: str):
    """``(qI (T, Hi, di), kI (T, di), w (T, Hi))`` of one sequence's normed input ``u (T, D)``."""
    T = u.shape[0]
    qi = _dot("td,de->te", u, ap["index_q"], precision).reshape(T, z["Hi"], z["di"])  # assumed 3: from the hidden state
    ki = _dot("td,de->te", u, ap["index_k"], precision)
    w = _dot("td,dh->th", u, ap["index_w"], precision) * (z["di"] ** -0.5 * z["Hi"] ** -0.5)
    mu = jnp.mean(ki, -1, keepdims=True)  # assumed 4: LayerNorm on the key, rotary on the first half of the head
    ki = (ki - mu) * jax.lax.rsqrt(jnp.mean(jnp.square(ki - mu), -1, keepdims=True) + eps) * ap["index_k_gain"] + ap["index_k_bias"]
    return rope_lead(qi, positions, z["rot"], z["theta"]), rope_lead(ki[:, None], positions, z["rot"], z["theta"])[:, 0], w


def dsa(ap: Dict[str, Any], u, positions3, dims: Dict[str, Any], precision: str, index_precision: str = "float32",
        select: str = "indexer", selected_at=None):
    """Learned sparse attention of one sequence ``u (T, D)`` after its
    input norm.  Returns ``(the sublayer's output (T, D), k (T, Hkv, d), v
    (T, Hkv, d), kI (T, di), sel, cut)``: ``k`` normed and rotated, ``v`` and
    ``kI`` as attention and the indexer meet them — what a cache holds;
    ``sel (n, T)`` bool the selections of the queries ``selected_at (n,)``
    and ``cut (n,)`` the score each was cut at, its ``topk``-th largest
    (NaN while ``T <= topk``; both None: not kept)."""
    T, eps, z = u.shape[0], dims["rms_norm_eps"], W.sizes(dims)
    H, Hkv, d, topk = z["H"], z["Hkv"], z["d"], z["topk"]
    G = H // Hkv
    qkv = _dot("td,de->te", u, ap["qkv"], precision)
    q = rms(qkv[:, : H * d].reshape(T, H, d), ap["q_norm"], eps)  # assumed 1: per-head RMSNorm on q and k
    k = rms(qkv[:, H * d: (H + Hkv) * d].reshape(T, Hkv, d), ap["k_norm"], eps)
    v = qkv[:, (H + Hkv) * d:].reshape(T, Hkv, d)
    q = mrope(q, positions3, z["theta"], z["sections"]).reshape(T, Hkv, G, d)
    k = mrope(k, positions3, z["theta"], z["sections"])
    qi, ki, w = indexer(ap, u, positions3[0], z, eps, index_precision)
    pos = jnp.arange(T)
    qb = min(QUERY_BLOCK, T)  # assumed 5: the tile changes no result
    while T % qb:
        qb -= 1

    def block(i):
        rows = i * qb + jnp.arange(qb)
        causal = pos[None, :] <= rows[:, None]  # (qb, T)
        if select == "recent":
            score = jnp.broadcast_to(pos.astype(jnp.float32), (qb, T))
        else:
            qs, ws = jax.lax.dynamic_slice_in_dim(qi, i * qb, qb, axis=0), jax.lax.dynamic_slice_in_dim(w, i * qb, qb, axis=0)
            score = jnp.sum(ws[:, :, None] * jax.nn.relu(_dot("qhd,kd->qhk", qs, ki, index_precision)), axis=1)
            if index_precision == "bfloat16":  # the control: a score is held in bfloat16, not only made from rounded operands
                score = score.astype(jnp.bfloat16).astype(jnp.float32)
        score = jnp.where(causal, score, -jnp.inf)
        if T > topk:
            # lax.top_k keeps the lower index among equal values: ties to the lower position
            best, chosen = jax.lax.top_k(score, topk)
            sel = jnp.zeros((qb, T), bool).at[jnp.arange(qb)[:, None], chosen].set(True) & causal
            cut = jnp.where(rows >= topk - 1, best[:, -1], jnp.nan)
        else:
            sel, cut = causal, jnp.full((qb,), jnp.nan, jnp.float32)
        sc = _dot("qhgd,khd->hgqk", jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0), k, precision) * d ** -0.5
        p = jax.nn.softmax(jnp.where(sel[None, None], sc, -jnp.inf), axis=-1)
        return _dot("hgqk,khd->qhgd", p, v, precision), sel, cut

    o, sel, cut = jax.lax.map(block, jnp.arange(T // qb))
    kept, cuts = (None, None) if selected_at is None else (sel.reshape(T, T)[selected_at], cut.reshape(T)[selected_at])
    return _dot("te,ed->td", o.reshape(T, H * d), ap["o"], precision), k, v, ki, kept, cuts


def route(rp: Dict[str, Any], x, dims: Dict[str, Any], precision: str):
    """``(idx (T, k), weight (T, k))``: softmax over all experts, the top-k, renormalised."""
    p = jax.nn.softmax(_dot("td,de->te", x, rp["router"], precision), axis=-1)
    w, idx = jax.lax.top_k(p, dims["num_experts_per_tok"])
    if dims.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w


_SIZES = ("hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts",
          "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob", "rms_norm_eps", "vocab_size", "rope_theta")


def _sizes_key(dims: Dict[str, Any]) -> Tuple:
    """The sizes the programs below are made from, hashable: the nested groups flattened."""
    z = W.sizes(dims)
    return tuple((k, dims[k]) for k in _SIZES) + (("sa", tuple(sorted((k, v) for k, v in z.items()))), ("experts_held", W.held(dims)))


@functools.lru_cache(maxsize=None)
def _programs(sizes_key: Tuple, precision: str, index_precision: str, select: str):
    flat = dict(sizes_key)
    z = dict(flat.pop("sa"))
    dims = {**flat, "sa_config": {"indexer_head_dim": z["di"], "indexer_num_heads": z["Hi"], "topk": z["topk"]},
            "rope_scaling": {"mrope_section": z["sections"]}, "index_rotary_dim": z["rot"]}
    eps = dims["rms_norm_eps"]

    @jax.jit
    def embed(key, tokens):
        return jnp.take(W.embedding(key, dims), tokens, axis=0)  # assumed 7: a sliced vocabulary is a smaller vocabulary

    @jax.jit
    def attn_sublayer(key, l, x, positions3, selected_at):
        ap = W.attn_params(key, l, dims)
        y, k, v, ki, sel, cut = dsa(ap, rms(x, ap["attn_norm"], eps), positions3, dims, precision, index_precision, select, selected_at)
        return x + y, k, v, ki, sel, cut

    @functools.partial(jax.jit, static_argnames=("first", "count"))
    def moe_sublayer(key, l, x, first, count):
        """``x + sum over the chosen experts in [first, first + count) of w_e E_e(RMS(x))``, one expert's weights at a time."""
        h = rms(x, W.attn_params(key, l, dims)["ffn_norm"], eps)
        idx, w = route(W.router_params(key, l, dims), h, dims, precision)

        def one(e, acc):
            return acc + jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)[:, None] * swiglu(W.expert_params(key, l, e, dims), h, precision)

        return x + jax.lax.fori_loop(first, first + count, one, jnp.zeros_like(h)), idx, w

    @jax.jit
    def head(key, x):
        return _dot("td,vd->tv", rms(x, jnp.ones((dims["hidden_size"],), jnp.float32), eps), W.head(key, dims), precision)

    return {"embed": embed, "attn_sublayer": attn_sublayer, "moe_sublayer": moe_sublayer, "head": head}


class Reference:
    """The reference model of one configuration (``dims``: the published
    keys plus the share) and seed."""

    def __init__(self, dims: Dict[str, Any], seed: int, precision: str = "float32", index_precision: str = "float32",
                 select: str = "indexer"):
        self.dims = dict(dims)
        self.key = W.seed_key(seed)
        self.precision = precision
        self._p = _programs(_sizes_key(self.dims), precision, index_precision, select)

    def attention(self, l: int, x, positions3=None, selected_at=None):
        """``(x + W_o DSA(RMS(x)), k, v, kI, sel, cut)`` of layer ``l`` for one sequence ``x (T, D)``."""
        T = x.shape[0]
        positions3 = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (3, T)) if positions3 is None else jnp.asarray(positions3, jnp.int32)
        at = jnp.zeros((1,), jnp.int32) if selected_at is None else jnp.asarray(selected_at, jnp.int32)
        return self._p["attn_sublayer"](self.key, l, x, positions3, at)

    def moe(self, l: int, x, held: Optional[Tuple[int, int]] = None):
        """``(x + the held experts' part, idx, w)`` of layer ``l``; ``held`` defaults to the configuration's share."""
        first, count = held if held is not None else W.held(self.dims)
        return self._p["moe_sublayer"](self.key, l, x, first, count)

    def hidden(self, tokens, positions3=None, kv_at=None, kv: Optional[List] = None, selected_at=None,
               selected: Optional[List] = None, cuts: Optional[List] = None):
        """Final hidden states ``(T, D)`` of one sequence ``tokens (T,)``.
        ``kv``, a list, is given each layer's ``(k, v, kI)`` rows at the
        positions ``kv_at``; ``selected``, a list, each layer's selection
        masks ``(n, T)`` of the queries ``selected_at``, and ``cuts`` the
        index scores ``(n,)`` they were cut at."""
        with jax.default_matmul_precision("highest"):
            x = self._p["embed"](self.key, jnp.asarray(tokens, jnp.int32))
            for l in range(self.dims["num_hidden_layers"]):
                x, k, v, ki, sel, cut = self.attention(l, x, positions3, selected_at)
                if kv is not None:
                    at = np.asarray(kv_at)
                    kv.append((np.asarray(k[at]), np.asarray(v[at]), np.asarray(ki[at])))
                if selected is not None:
                    selected.append(np.asarray(sel))
                if cuts is not None:
                    cuts.append(np.asarray(cut))
                x, _, _ = self.moe(l, x)
            return x

    def head(self, rows):
        """Logits ``(n, rows held)`` of hidden states ``rows (n, D)``."""
        with jax.default_matmul_precision("highest"):
            return self._p["head"](self.key, rows)

    def logits(self, tokens, positions3=None):
        """``(B, T, rows held)`` for ``tokens (B, T)``, one sequence after another (no batching)."""
        tokens = np.asarray(tokens, np.int32)
        return jnp.stack([self.head(self.hidden(t, positions3)) for t in tokens])
