"""Laguna's forward in plain ``jax.numpy``: the reference the program's
served tokens are held against.

float32 with ``highest`` matmul precision, no kernel, no cache, no
batching: one sequence at a time, a **full causal forward** — every
layer's attention over the whole sequence with the layer kind's mask
**written as a mask** (a sliding layer's band is ``i - window < j <=
i``; nothing here knows of a ring, a page or a chunk) — computed in
blocks of KV heads and queries, one block of weights live at a time (a
layer's mixer, one routed expert).  The weights come from
:mod:`weights_laguna` and the seed, never from the program under test.

The equations (``h = RMS(x; g, eps)`` RMSNorm with a learned gain, no
bias anywhere; ``H_l`` the layer's query heads, ``G = H_l / Hkv``):

* ``q = h W_q`` (``T, H_l, d``), ``k = h W_k``, ``v = h W_v`` (``T,
  Hkv, d``).
* rotary by layer kind (half layout: dimension ``i`` of the rotated part
  pairs with ``i + rot / 2``).  **full**: the first
  ``partial_rotary_factor * d`` dimensions of q and k, YaRN inverse
  frequencies (``factor``, ``original_max_position_embeddings``,
  ``beta_fast`` / ``beta_slow``: the base frequency where a pair turns
  often within the original context, the base over ``factor`` where it
  turns less than once, a linear ramp between), ``cos`` and ``sin``
  multiplied by ``attention_factor``; the other dimensions pass.
  **sliding**: all dimensions, ``theta`` as published, no scaling.
* ``a_h[i] = sum_j softmax_j(q_h[i] . k_{h // G}[j] / sqrt(d)) v_{h //
  G}[j]`` over ``j <= i`` (full) or ``i - window < j <= i`` (sliding:
  ``window`` positions, the query's own among them).
* ``gate = sigmoid(h W_gate)`` (``T, H_l``); ``x <- x + concat_h(gate_h
  a_h) W_o``.
* ``h2 = RMS(x)``; a dense layer: ``x <- x + W_down(silu(h2 W_gate') *
  h2 W_up)``; a sparse layer: ``p = softmax(h2 W_r)`` over **all**
  experts, float32; the ``num_experts_per_tok`` largest; ``w =
  moe_routed_scaling_factor * p_top / sum(p_top)``; ``x <- x + sum_{e
  chosen and held} w_e E_e(h2) + S(h2)``, ``E_e``, ``S`` SwiGLU.
* after the last layer ``RMS``, then the untied head.

Departures from the published description — the keys do not spell these
forms out; each is also a comment where it happens and an entry of the
configuration file's ``assumed``, with its other reading: (1) ``gating:
per-head`` is the *headwise* sigmoid gate on the attention output in
front of ``W_o``, from the layer's normed input (arXiv:2505.06708); (2)
the router scores by softmax (the Qwen-MoE key set); (3) the shared
expert is added ungated; (4) no q/k norm; the window holds ``window``
positions including the query's own; ``attention_factor`` multiplies
``cos`` and ``sin``; (5) the experts are the share ``experts_held`` and
the vocabulary the slice ``vocab_held`` — with no share given the model
is whole; (6) the depth is what ``num_hidden_layers`` says, the
per-layer lists as long.

``precision`` rounds every matmul *operand* of the projections, the
attention products and the experts before an exact float32 contraction
(``"float32"`` the reference, ``"bfloat16"`` what the configuration
states).  ``variant`` makes the controls of ``control_laguna.py`` —
variants of this reference put in the program's place: ``window_as_full``
(sliding layers attend causally over everything), ``no_gate`` (the gate
left out), ``sliding_heads_kept`` (only the first so many heads of a
sliding layer, the others zeroed), ``router`` (``"bfloat16"``: the
router's logits from operands rounded to bfloat16 and its softmax in
bfloat16 — the nearest precision below the float32 the configuration
states for it).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_laguna as W
from .reference_gpt2 import _dot

KV_HEAD_BLOCK, QUERY_BLOCK = 1, 1024  # attention is computed this many KV heads (with their groups) x queries at a time
FULL, SLIDING = "full_attention", "sliding_attention"


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def inv_freq(dims: Dict[str, Any], kind: str) -> np.ndarray:
    """Inverse frequencies of the rotated pairs of a layer kind (float64)."""
    rp = dims["rope_parameters"][kind]
    dim = int(round(dims["head_dim"] * rp.get("partial_rotary_factor", 1.0)))
    theta = float(rp["rope_theta"])
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type", "default") == "default":
        return base
    factor, original = float(rp["factor"]), float(rp["original_max_position_embeddings"])
    turns = lambda beta: dim * math.log(original / (beta * 2 * math.pi)) / (2 * math.log(theta))  # noqa: E731  the pair that turns beta times in the original context
    lo, hi = max(math.floor(turns(rp["beta_fast"])), 0), min(math.ceil(turns(rp["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def rotate(x, positions, dims: Dict[str, Any], kind: str):
    """``x (T, heads, d)`` rotated at ``positions (T,)`` as the layer kind says (half layout; departure 4: ``attention_factor`` on cos and sin)."""
    f = jnp.asarray(inv_freq(dims, kind), jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * f[None, :]
    mult = float(dims["rope_parameters"][kind].get("attention_factor", 1.0))
    cos, sin = (jnp.cos(ang) * mult)[:, None, :], (jnp.sin(ang) * mult)[:, None, :]
    half = f.shape[0]
    x1, x2, rest = x[..., :half], x[..., half: 2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(ap: Dict[str, Any], x, dims: Dict[str, Any], layer: int, precision: str, variant: Dict[str, Any]):
    """Gated grouped-query attention of one sequence ``x (T, D)`` after its input norm, layer ``layer``'s kind and heads."""
    T = x.shape[0]
    H, Hkv, hd = W.heads_of(dims, layer), dims["num_key_value_heads"], dims["head_dim"]
    G = H // Hkv
    kind = SLIDING if W.is_sliding(dims, layer) else FULL
    qkv = _dot("td,de->te", x, ap["qkv"], precision)
    pos = jnp.arange(T)
    q = rotate(qkv[:, : H * hd].reshape(T, H, hd), pos, dims, kind).reshape(T, Hkv, G, hd)
    k = rotate(qkv[:, H * hd: (H + Hkv) * hd].reshape(T, Hkv, hd), pos, dims, kind)  # departure 4: no q/k norm
    v = qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd)
    window = None if kind == FULL or variant.get("window_as_full") else int(dims["sliding_window"])
    hb, qb = min(KV_HEAD_BLOCK, Hkv), min(QUERY_BLOCK, T)
    while T % qb:
        qb -= 1

    def block(i):
        h0, t0 = (i // (T // qb)) * hb, (i % (T // qb)) * qb
        qs = jax.lax.dynamic_slice(q, (t0, h0, 0, 0), (qb, hb, G, hd))
        ks = jax.lax.dynamic_slice_in_dim(k, h0, hb, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, h0, hb, axis=1)
        sc = _dot("qhgd,khd->hgqk", qs, ks, precision) * hd ** -0.5
        qi = (t0 + jnp.arange(qb))[:, None]
        seen = pos[None, :] <= qi
        if window is not None:  # the band, as a mask: ``window`` positions, the query's own among them
            seen &= pos[None, :] > qi - window
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
        return _dot("hgqk,khd->qhgd", p, vs, precision)  # (qb, hb, G, hd)

    out = jax.lax.map(block, jnp.arange((Hkv // hb) * (T // qb)))
    out = out.reshape(Hkv // hb, T // qb, qb, hb, G, hd).transpose(1, 2, 0, 3, 4, 5).reshape(T, H, hd)
    if not variant.get("no_gate"):
        out = out * jax.nn.sigmoid(_dot("td,dh->th", x, ap["gate"], precision))[..., None]  # departure 1: headwise, from the normed input
    kept = variant.get("sliding_heads_kept")
    if kept is not None and kind == SLIDING:
        out = jnp.where((jnp.arange(H) < kept)[None, :, None], out, 0.0)
    return _dot("te,ed->td", out.reshape(T, H * hd), ap["o"], precision)


def swiglu(p: Dict[str, Any], x, precision: str):
    g, u = jnp.split(_dot("td,df->tf", x, p["gu"], precision), 2, axis=-1)
    return _dot("tf,fd->td", jax.nn.silu(g) * u, p["down"], precision)


def route(sp: Dict[str, Any], x, dims: Dict[str, Any], router: str) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(idx (T, k), weight (T, k), the chosen experts' logits (T, k) float32)``: softmax over all experts (departure 2), the
    top-k renormalised, times the scaling factor.  ``router`` is the precision of its logits' operands, of the logits and of
    its softmax (``"float32"``: what the configuration states)."""
    logits = _dot("td,de->te", x, sp["router"], router)
    if router != "float32":
        logits = logits.astype(jnp.bfloat16)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1).astype(jnp.float32), dims["num_experts_per_tok"])
    if dims.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * dims.get("moe_routed_scaling_factor", 1.0), jnp.take_along_axis(logits.astype(jnp.float32), idx, axis=-1)


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    return v


def _thaw(v):
    if isinstance(v, tuple) and v and all(isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str) for x in v):
        return {k: _thaw(x) for k, x in v}
    if isinstance(v, tuple):
        return [_thaw(x) for x in v]
    return v


@functools.lru_cache(maxsize=None)
def _programs(dims_key: Tuple, precision: str, variant_key: Tuple):
    dims, variant = _thaw(dims_key), dict(variant_key)
    eps, ones = dims["rms_norm_eps"], jnp.ones((dims["hidden_size"],), jnp.float32)  # every gain is 1
    router = variant.get("router", "float32")

    @jax.jit
    def embed(key, tokens):
        return jnp.take(W.table_rows(key, "embed", W.vocab_rows(dims), dims), tokens, axis=0)  # departure 5: a sliced vocabulary is a smaller one

    @functools.partial(jax.jit, static_argnames=("l",))
    def mixer(key, l, x):
        return x + attention(W.mixer_params(key, l, dims), rms(x, ones, eps), dims, l, precision, variant)

    @jax.jit
    def dense(key, l, h):
        return swiglu(W.dense_mlp_params(key, l, dims), rms(h, ones, eps), precision)

    @jax.jit
    def routing(key, l, h):
        return route(W.shared_params(key, l, dims), rms(h, ones, eps), dims, router)

    @functools.partial(jax.jit, static_argnames=("first", "count"))
    def routed_part(key, l, h, first, count):
        """``sum_{e chosen, first <= e < first + count} w_e E_e(x)``, one expert's weights at a time."""
        x = rms(h, ones, eps)
        idx, w, _ = route(W.shared_params(key, l, dims), x, dims, router)

        def one(e, acc):
            we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            return acc + we[:, None] * swiglu(W.expert_params(key, l, e, dims), x, precision)

        return jax.lax.fori_loop(first, first + count, one, jnp.zeros_like(x))

    @jax.jit
    def shared_part(key, l, h):
        return swiglu(W.shared_params(key, l, dims), rms(h, ones, eps), precision)  # departure 3: added ungated

    @jax.jit
    def logits(key, x):
        return _dot("td,vd->tv", rms(x, ones, eps), W.table_rows(key, "head", W.vocab_rows(dims), dims), precision)

    return {"embed": embed, "mixer": mixer, "dense": dense, "routing": routing, "routed_part": routed_part,
            "shared_part": shared_part, "logits": logits}


class Reference:
    """The reference model of one configuration (``dims``: the published
    keys plus the share) and seed; ``variant`` a control (see above)."""

    def __init__(self, dims: Dict[str, Any], seed: int, precision: str = "float32", **variant):
        self.dims = dict(dims)
        self.key = W.seed_key(seed)
        self.precision = precision
        self._p = _programs(_freeze(self.dims), precision, tuple(sorted(variant.items())))

    def moe_parts(self, l: int, h, held: Optional[Tuple[int, int]] = None):
        """``(routed part of the experts held, shared expert's part)`` of
        sparse layer ``l`` for ``h (T, D)``, the layer's input after its
        mixer.  ``held`` defaults to the configuration's share."""
        first, count = held if held is not None else W.held(self.dims)
        return self._p["routed_part"](self.key, l, h, first, count), self._p["shared_part"](self.key, l, h)

    def routing(self, l: int, h):
        """``(chosen experts (T, k), their weights, the router's logits of the chosen)`` of sparse layer ``l`` for its input ``h (T, D)``."""
        return self._p["routing"](self.key, l, h)

    def routings(self, tokens, at: int):
        """What every sparse layer's router chose at position ``at`` of one sequence ``tokens (T,)``, in layer order:
        ``(experts (sparse layers, k) int32, logits of the chosen (sparse layers, k) float32)``."""
        keep: list = []
        self.hidden(tokens, keep=keep, at=at)
        with jax.default_matmul_precision("highest"):
            rows = [self.routing(l, keep[l]) for l in range(self.dims["num_hidden_layers"]) if not W.is_dense(self.dims, l)]
        return np.stack([np.asarray(i[0]) for i, _, _ in rows]), np.stack([np.asarray(g[0], np.float32) for _, _, g in rows])

    def layer(self, l: int, x, keep=None, at: Optional[int] = None):
        """One decoder layer on one sequence ``x (T, D)``; ``keep``, a list, is given the feed-forward's input (row ``at`` of it alone, where given)."""
        h = self._p["mixer"](self.key, l, x)
        if keep is not None:
            keep.append(h if at is None else h[at: at + 1])
        if W.is_dense(self.dims, l):
            return h + self._p["dense"](self.key, l, h)
        routed, shared = self.moe_parts(l, h)
        return h + routed + shared

    def hidden(self, tokens, keep=None, at: Optional[int] = None):
        """Final hidden states ``(T, D)`` of one sequence ``tokens (T,)``."""
        with jax.default_matmul_precision("highest"):
            x = self._p["embed"](self.key, jnp.asarray(tokens, jnp.int32))
            for l in range(self.dims["num_hidden_layers"]):
                x = self.layer(l, x, keep, at)
            return x

    def logits(self, tokens):
        """``(B, T, rows held)`` for ``tokens (B, T)``, one sequence after another (no batching)."""
        tokens = np.asarray(tokens, np.int32)
        with jax.default_matmul_precision("highest"):
            return jnp.stack([self._p["logits"](self.key, self.hidden(t)) for t in tokens])
