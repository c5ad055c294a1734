"""DeepSeek-V2's forward in plain ``jax.numpy``: the reference the
program's served tokens are held against.

float32 with ``highest`` matmul precision, no kernel, no cache, no
batching, latent attention in the **expanded** form only (keys and
values rebuilt per head from the latents, as the paper states it), one
block of weights live at a time (a layer's attention, one routed
expert) — the weights come from :mod:`weights_deepseek_v2` and the
seed, never from the program under test.  Attention is computed in
blocks of heads and queries and the experts one after another, so a
sequence of 8,192 positions at the published widths fits beside the
program's 11.8 GB.

The equations (``x`` a token's hidden state, ``RMS`` RMSNorm with a
learned gain, eps ``rms_norm_eps``, no bias anywhere):

* layer: ``h = x + MLA(RMS(x))``; ``y = h + FFN(RMS(h))``; the first
  ``first_k_dense_replace`` layers have the dense SwiGLU, the others the
  MoE.  After the last layer ``RMS``, then the untied head.
* MLA: ``c_q = RMS(x W_qa)``; ``q = c_q W_qb`` per head ``[q_nope |
  q_pe]``; ``[c_kv | k_pe] = x W_kva``, ``c_kv <- RMS(c_kv)``; per head
  ``[k_nope | v] = c_kv W_kvb``; ``score = (q_nope . k_nope + RoPE(q_pe)
  . RoPE(k_pe)) s``; causal softmax; ``o = sum p v``; output
  ``concat_heads(o) W_o``.
* RoPE with YaRN on the rope dimensions and the softmax scale ``s``:
  :func:`yarn_inv_freq`, :func:`softmax_scale`.
* MoE: ``p = softmax(x W_g)`` over all routed experts; a group's score
  is its largest ``p``; the ``topk_group`` best groups are kept; the
  ``num_experts_per_tok`` best experts of those are chosen with weight
  ``routed_scaling_factor * p`` (not renormalised);
  ``FFN(x) = sum_{e chosen and held} w_e E_e(x) + S(x)``.

Departures from the published model, each also a comment where it
happens: (1) the rotary dimensions are in the half layout (dimension
``i`` pairs with ``i + rope/2``) — the published code permutes its
interleaved layout into this one at run time, so with seeded weights it
is a relabelling; (2) only the experts ``experts_held`` and the rows
``vocab_held`` exist: what the absent experts would have added is left
out and that partial result goes on to the next layer, as in the
program (guide section 4: one chip's share of an expert-parallel
deployment) — with no share given the model is whole; (3) the depth is
what ``num_hidden_layers`` says.

``precision`` rounds every matmul *operand* before an exact float32
contraction (``"float32"`` the reference, ``"bfloat16"`` what the
configuration states, ``"int8"`` the control below it).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_deepseek_v2 as W
from .reference_gpt2 import _dot

HEAD_BLOCK, QUERY_BLOCK = 8, 1024  # attention is computed this many heads x queries at a time


def yarn_inv_freq(dims: Dict[str, Any]) -> np.ndarray:
    """Per frequency pair ``i`` of the ``r`` rope dimensions:
    ``(1 - m_i) theta^(-2i/r) / factor + m_i theta^(-2i/r)`` with
    ``m_i = 1 - clip((i - lo) / (hi - lo), 0, 1)`` and ``lo`` / ``hi`` the
    correction range ``floor / ceil(r ln(L0 / (beta 2 pi)) / (2 ln theta))``
    for ``beta_fast`` / ``beta_slow``."""
    r, theta, rs = dims["qk_rope_head_dim"], float(dims["rope_theta"]), dims["rope_scaling"]
    base = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)

    def correction(beta):
        return r * math.log(rs["original_max_position_embeddings"] / (beta * 2 * math.pi)) / (2 * math.log(theta))

    lo, hi = max(math.floor(correction(rs["beta_fast"])), 0), min(math.ceil(correction(rs["beta_slow"])), r - 1)
    m = 1.0 - np.clip((np.arange(r // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((1.0 - m) * base / rs["factor"] + m * base).astype(np.float32)


def softmax_scale(dims: Dict[str, Any]) -> float:
    """``(nope + rope)^(-1/2) (0.1 mscale_all_dim ln(factor) + 1)^2``; the
    cos/sin multiplier ``g(mscale) / g(mscale_all_dim)`` is 1 in the
    published config and is not applied."""
    rs = dims["rope_scaling"]
    if rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("mscale != mscale_all_dim: the cos/sin multiplier is not 1 and is not implemented here")
    g = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]) ** -0.5 * g * g


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rope(x, positions, inv_freq):
    """Half layout (departure 1): ``x (T, ..., r)`` at ``positions (T,)``."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq  # (T, r/2)
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],))
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def mla(ap: Dict[str, Any], x, dims: Dict[str, Any], precision: str):
    """Latent attention of one sequence ``x (T, D)`` after its input norm."""
    T = x.shape[0]
    H, dn, dr, dv, c = (dims["num_attention_heads"], dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
                        dims["v_head_dim"], dims["kv_lora_rank"])
    eps, inv_freq, s = dims["rms_norm_eps"], jnp.asarray(yarn_inv_freq(dims)), softmax_scale(dims)
    pos = jnp.arange(T)
    q = _dot("tc,ce->te", rms(_dot("td,dc->tc", x, ap["q_a"], precision), ap["q_a_norm"], eps), ap["q_b"], precision)
    q = q.reshape(T, H, dn + dr)
    kv = _dot("td,dc->tc", x, ap["kv_a"], precision)
    c_kv, k_pe = rms(kv[:, :c], ap["kv_a_norm"], eps), rope(kv[:, c:], pos, inv_freq)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, inv_freq)
    kvh = _dot("tc,ce->te", c_kv, ap["kv_b"], precision).reshape(T, H, dn + dv)
    k_nope, v = kvh[..., :dn], kvh[..., dn:]

    hb, qb = min(HEAD_BLOCK, H), min(QUERY_BLOCK, T)
    while H % hb:
        hb -= 1
    while T % qb:
        qb -= 1

    def block(i):
        h0, t0 = (i // (T // qb)) * hb, (i % (T // qb)) * qb
        qn = jax.lax.dynamic_slice(q_nope, (t0, h0, 0), (qb, hb, dn))
        qp = jax.lax.dynamic_slice(q_pe, (t0, h0, 0), (qb, hb, dr))
        kn = jax.lax.dynamic_slice_in_dim(k_nope, h0, hb, axis=1)
        vv = jax.lax.dynamic_slice_in_dim(v, h0, hb, axis=1)
        sc = (_dot("qhn,khn->hqk", qn, kn, precision) + _dot("qhr,kr->hqk", qp, k_pe, precision)) * s
        causal = pos[None, :] <= (t0 + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return _dot("hqk,khv->qhv", p, vv, precision)  # (qb, hb, dv)

    out = jax.lax.map(block, jnp.arange((H // hb) * (T // qb)))  # (nh * nq, qb, hb, dv)
    out = out.reshape(H // hb, T // qb, qb, hb, dv).transpose(1, 2, 0, 3, 4).reshape(T, H * dv)
    return _dot("te,ed->td", out, ap["o"], precision)


def swiglu(p: Dict[str, Any], x, precision: str):
    g, u = jnp.split(_dot("td,df->tf", x, p["gu"], precision), 2, axis=-1)
    return _dot("tf,fd->td", jax.nn.silu(g) * u, p["down"], precision)


def route(router, x, dims: Dict[str, Any], precision: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(idx (T, k), weight (T, k))``: group-limited greedy top-k of the
    softmax over all routed experts."""
    E, G = dims["n_routed_experts"], dims["n_group"]
    p = jax.nn.softmax(_dot("td,de->te", x, router, precision), axis=-1)
    _, best = jax.lax.top_k(p.reshape(-1, G, E // G).max(-1), dims["topk_group"])
    keep = jnp.zeros((p.shape[0], G), bool).at[jnp.arange(p.shape[0])[:, None], best].set(True)
    w, idx = jax.lax.top_k(jnp.where(jnp.repeat(keep, E // G, axis=1), p, 0.0), dims["num_experts_per_tok"])
    if dims.get("norm_topk_prob"):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * dims["routed_scaling_factor"]


def _dims_key(dims: Dict[str, Any]) -> Tuple:
    flat = {k: (tuple(sorted(v.items())) if isinstance(v, dict) else tuple(v) if isinstance(v, list) else v)
            for k, v in dims.items() if isinstance(v, (int, float, bool, dict, list, tuple))}
    return tuple(sorted(flat.items()))


@functools.lru_cache(maxsize=None)
def _programs(dims_key: Tuple, precision: str):
    dims = {k: (dict(v) if isinstance(v, tuple) and v and isinstance(v[0], tuple) else v) for k, v in dims_key}
    eps = dims["rms_norm_eps"]

    @jax.jit
    def embed(key, tokens):
        rows = W.vocab_rows(dims)  # departure 2: a sliced vocabulary is a smaller vocabulary
        return jnp.take(W.table_rows(key, "embed", rows, dims), tokens, axis=0)

    @jax.jit
    def attention(key, l, x):
        ap = W.attn_params(key, l, dims)
        return x + mla(ap, rms(x, ap["attn_norm"], eps), dims, precision)

    @jax.jit
    def dense_ffn(key, l, h):
        return h + swiglu(W.dense_mlp_params(key, l, dims), rms(h, W.norm_gains(dims)["ffn_norm"], eps), precision)

    @jax.jit
    def routing(key, l, h):
        x = rms(h, W.norm_gains(dims)["ffn_norm"], eps)
        return route(W.shared_params(key, l, dims)["router"], x, dims, precision)

    @functools.partial(jax.jit, static_argnames=("first", "count"))
    def routed_part(key, l, h, first, count):
        """``sum_{e chosen, first <= e < first + count} w_e E_e(x)``, one expert's weights at a time."""
        x = rms(h, W.norm_gains(dims)["ffn_norm"], eps)
        idx, w = route(W.shared_params(key, l, dims)["router"], x, dims, precision)

        def one(e, acc):
            we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            return acc + we[:, None] * swiglu(W.expert_params(key, l, e, dims), x, precision)

        return jax.lax.fori_loop(first, first + count, one, jnp.zeros_like(x))

    @jax.jit
    def shared_part(key, l, h):
        x = rms(h, W.norm_gains(dims)["ffn_norm"], eps)
        return swiglu(W.shared_params(key, l, dims), x, precision)

    @jax.jit
    def logits(key, x):
        rows = W.vocab_rows(dims)
        return _dot("td,vd->tv", rms(x, jnp.ones((x.shape[-1],), jnp.float32), eps),  # the final norm's gain is 1 too
                    W.table_rows(key, "head", rows, dims), precision)

    return {"embed": embed, "attention": attention, "dense_ffn": dense_ffn, "routing": routing,
            "routed_part": routed_part, "shared_part": shared_part, "logits": logits}


class Reference:
    """The reference model of one configuration (``dims``: the published
    keys plus the share) and seed."""

    def __init__(self, dims: Dict[str, Any], seed: int, precision: str = "float32"):
        self.dims = dict(dims)
        self.key = W.seed_key(seed)
        self.precision = precision
        self._p = _programs(_dims_key(self.dims), precision)

    def is_dense(self, l: int) -> bool:
        return l < self.dims["first_k_dense_replace"]

    def moe_parts(self, l: int, h, held: Optional[Tuple[int, int]] = None):
        """``(routed part of the experts held, shared experts' part)`` of
        expert layer ``l`` for ``h (T, D)``, the layer's input after
        attention.  ``held`` defaults to the configuration's share."""
        first, count = held if held is not None else W.held(self.dims)
        return self._p["routed_part"](self.key, l, h, first, count), self._p["shared_part"](self.key, l, h)

    def routing(self, l: int, h):
        return self._p["routing"](self.key, l, h)

    def layer(self, l: int, x, keep=None):
        """One decoder layer on one sequence ``x (T, D)``.  ``keep``, a
        list, is given the expert layer's input after attention."""
        h = self._p["attention"](self.key, l, x)
        if self.is_dense(l):
            return self._p["dense_ffn"](self.key, l, h)
        if keep is not None:
            keep.append(h)
        routed, shared = self.moe_parts(l, h)
        return h + routed + shared

    def hidden(self, tokens, keep=None):
        """Final hidden states ``(T, D)`` of one sequence ``tokens (T,)``."""
        with jax.default_matmul_precision("highest"):
            x = self._p["embed"](self.key, jnp.asarray(tokens, jnp.int32))
            for l in range(self.dims["num_hidden_layers"]):
                x = self.layer(l, x, keep)
            return x

    def logits(self, tokens):
        """``(B, T, rows held)`` for ``tokens (B, T)``, one sequence after another (no batching)."""
        tokens = np.asarray(tokens, np.int32)
        with jax.default_matmul_precision("highest"):
            return jnp.stack([self._p["logits"](self.key, self.hidden(t)) for t in tokens])
