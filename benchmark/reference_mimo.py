"""MiMo-V2-Flash's forward in plain ``jax.numpy``: the reference the
program's served tokens are held against.

float32 with ``highest`` matmul precision, no kernel, no cache, no
batching: one sequence at a time, a **full causal forward** — every
layer's attention over the whole sequence with the layer kind's mask
**written as a mask** (a window layer's band is ``i - window < j <= i``;
nothing here knows of a ring, a page or a chunk) and the sink **written
as a column of the softmax** — computed in blocks of KV heads and
queries, one block of weights live at a time (a layer's mixer, one routed
expert), so that 8,192 positions fit.  The weights come from
:mod:`weights_mimo` and the seed, never from the program under test.

The equations (``h = RMS(x; g, eps)`` RMSNorm with a learned gain, no
bias anywhere; by layer kind ``Hkv`` KV heads, ``G = H / Hkv``, keys
``dk`` and values ``dv`` wide, ``theta``):

* ``q = h W_q`` (``T, H, dk``), ``k = h W_k`` (``T, Hkv, dk``), ``v =
  attention_value_scale * (h W_v)`` (``T, Hkv, dv``).
* rotary: the first ``int(dk * partial_rotary_factor)`` dimensions of q
  and k (half layout: dimension ``i`` of the rotated part pairs with ``i
  + rot / 2``), inverse frequencies ``theta ** (-2i / rot)``, no scaling;
  the other dimensions pass.
* ``s_h[i, j] = q_h[i] . k_{h // G}[j] / sqrt(dk)`` over ``j <= i`` (full)
  or ``i - window < j <= i`` (window: ``window`` positions, the query's
  own among them); ``p = exp(s - m) / (sum_j exp(s - m) [+ exp(b_h -
  m)])`` — a kind with sinks has the learned logit ``b_h`` as one more
  column, which takes mass and carries no value; ``m`` the maximum over
  the row's scores and ``b_h``.
* ``a_h[i] = sum_j p_h[i, j] v_{h // G}[j]``; ``x <- x + concat_h(a_h) W_o``.
* ``h2 = RMS(x)``; a dense layer: ``x <- x + W_down(silu(h2 W_gate) * h2
  W_up)``; a sparse layer: ``s = sigmoid(h2 W_r)`` over **all** experts,
  float32; the ``num_experts_per_tok`` largest of ``s + e_bias``; ``w =
  s_top / sum(s_top)`` (times ``routed_scaling_factor``, null = 1); ``x
  <- x + sum_{e chosen and held} w_e E_e(h2)``, ``E_e`` SwiGLU; no shared
  expert.
* after the last layer ``RMS``, then the untied head.

Departures from the published description — the keys do not spell these
forms out; each is also a comment where it happens and an entry of the
configuration file's ``assumed``, with its other reading: (1) the sink is
a column of the softmax, not a bias added to every score; (2) the window
holds ``sliding_window`` positions including the query's own, and
``attention_chunk_size`` is not block-local attention; (3) the rotated
dimensions are the first ``int(dk * 0.334)``, half layout; (4)
``attention_value_scale`` multiplies ``v``; (5) ``routed_scaling_factor``
null is 1 and ``e_bias`` selects and never weighs; (6) no q/k norm; (7)
the experts are the share ``experts_held`` and the vocabulary the slice
``vocab_held`` — with no share given the model is whole; the depth is
what ``num_hidden_layers`` says; the multi-token-prediction layers are
not here (no key gives their shape).

``precision`` rounds every matmul *operand* of the projections, the
attention products and the experts before an exact float32 contraction
(``"float32"`` the reference, ``"bfloat16"`` what the configuration
states).  ``variant`` makes the controls of ``control_mimo.py`` —
variants of this reference put in the program's place: ``no_sink`` (the
column left out), ``window`` (another window, 129), ``window_kv_heads``
(the window layers' query heads grouped over only the first so many KV
heads: 4, the full layers' grouping), ``no_value_scale``, ``theta_swapped``
(each kind rotated under the other's ``theta``), ``router``
(``"bfloat16"``: the router's logits from operands rounded to bfloat16 and
its sigmoid in bfloat16 — the nearest precision below the float32 the
configuration states for it).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_mimo as W
from .reference_gpt2 import _dot
from .reference_laguna import _freeze, _thaw, rms, swiglu  # noqa: F401  (the same RMSNorm and SwiGLU, one function each)

KV_HEAD_BLOCK, QUERY_BLOCK = 1, 512  # attention is computed this many KV heads (with their groups) x queries at a time


def rotate(x, positions, rot: int, theta: float):
    """``x (T, heads, d)`` with its first ``rot`` dimensions rotated at ``positions (T,)`` (half layout, departure 3), the rest passed."""
    f = jnp.asarray(float(theta) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot), jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * f[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    half = rot // 2
    x1, x2, rest = x[..., :half], x[..., half: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(ap: Dict[str, Any], x, dims: Dict[str, Any], layer: int, precision: str, variant: Dict[str, Any]):
    """Grouped-query attention of one sequence ``x (T, D)`` after its input norm, layer ``layer``'s kind."""
    T = x.shape[0]
    H, Hkv, dk, dv = W.geometry(dims, layer)
    windowed = W.is_window(dims, layer)
    theta = dims["swa_rope_theta"] if windowed != bool(variant.get("theta_swapped")) else dims["rope_theta"]
    rot = int(dk * dims["partial_rotary_factor"])
    qkv = _dot("td,de->te", x, ap["qkv"], precision)
    pos = jnp.arange(T)
    q = rotate(qkv[:, : H * dk].reshape(T, H, dk), pos, rot, theta)
    k = rotate(qkv[:, H * dk: (H + Hkv) * dk].reshape(T, Hkv, dk), pos, rot, theta)  # departure 6: no q/k norm
    v = qkv[:, (H + Hkv) * dk:].reshape(T, Hkv, dv)
    if not variant.get("no_value_scale"):
        v = v * dims["attention_value_scale"]  # departure 4: on v (on the output it is the same function: the sink carries no value)
    if windowed and variant.get("window_kv_heads"):  # the control: the window layers' heads grouped as the full layers' are
        Hkv = int(variant["window_kv_heads"])
        k, v = k[:, :Hkv], v[:, :Hkv]
    G = H // Hkv
    q = q.reshape(T, Hkv, G, dk)
    window = int(variant.get("window") or dims["sliding_window"]) if windowed else None  # departure 2
    sink = ap["sink"].reshape(Hkv, G) if "sink" in ap and not variant.get("no_sink") else None
    hb, qb = min(KV_HEAD_BLOCK, Hkv), min(QUERY_BLOCK, T)
    while T % qb:
        qb -= 1

    def block(i):
        h0, t0 = (i // (T // qb)) * hb, (i % (T // qb)) * qb
        qs = jax.lax.dynamic_slice(q, (t0, h0, 0, 0), (qb, hb, G, dk))
        ks = jax.lax.dynamic_slice_in_dim(k, h0, hb, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, h0, hb, axis=1)
        sc = _dot("qhgd,khd->hgqk", qs, ks, precision) * dk ** -0.5
        qi = (t0 + jnp.arange(qb))[:, None]
        seen = pos[None, :] <= qi
        if window is not None:  # the band, as a mask: ``window`` positions, the query's own among them
            seen &= pos[None, :] > qi - window
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        if sink is None:
            p = jax.nn.softmax(sc, axis=-1)
        else:  # departure 1: one more column of the softmax, which takes mass and carries no value
            b = jax.lax.dynamic_slice_in_dim(sink, h0, hb, axis=0)[:, :, None, None]
            m = jnp.maximum(jnp.max(sc, axis=-1, keepdims=True), b)
            e = jnp.exp(sc - m)
            p = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(b - m))
        return _dot("hgqk,khd->qhgd", p, vs, precision)  # (qb, hb, G, dv)

    out = jax.lax.map(block, jnp.arange((Hkv // hb) * (T // qb)))
    out = out.reshape(Hkv // hb, T // qb, qb, hb, G, dv).transpose(1, 2, 0, 3, 4, 5).reshape(T, H * dv)
    return _dot("te,ed->td", out, ap["o"], precision)


def route(rp: Dict[str, Any], x, dims: Dict[str, Any], router: str) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(idx (T, k), weight (T, k), the chosen experts' logits (T, k) float32)``: sigmoid over all experts, the top-k by
    score + e_bias (departure 5: the bias selects and never weighs), the chosen scores renormalised.  ``router`` is the
    precision of its logits' operands, of the logits and of its sigmoid (``"float32"``: what the configuration states)."""
    logits = _dot("td,de->te", x, rp["router"], router)
    if router != "float32":
        logits = logits.astype(jnp.bfloat16)
    s = jax.nn.sigmoid(logits).astype(jnp.float32)
    _, idx = jax.lax.top_k(s + rp["router_bias"], dims["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if dims.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * (dims.get("routed_scaling_factor") or 1.0), jnp.take_along_axis(logits.astype(jnp.float32), idx, axis=-1)


@functools.lru_cache(maxsize=None)
def _programs(dims_key: Tuple, precision: str, variant_key: Tuple):
    dims, variant = _thaw(dims_key), dict(variant_key)
    eps, ones = dims["layernorm_epsilon"], jnp.ones((dims["hidden_size"],), jnp.float32)  # every gain is 1
    router = variant.get("router", "float32")

    @jax.jit
    def embed(key, tokens):
        return jnp.take(W.table_rows(key, "embed", W.vocab_rows(dims), dims), tokens, axis=0)  # departure 7: a sliced vocabulary is a smaller one

    @functools.partial(jax.jit, static_argnames=("l",))
    def mixer(key, l, x):
        return x + attention(W.mixer_params(key, l, dims), rms(x, ones, eps), dims, l, precision, variant)

    @jax.jit
    def dense(key, l, h):
        return swiglu(W.dense_mlp_params(key, l, dims), rms(h, ones, eps), precision)

    @jax.jit
    def routing(key, l, h):
        return route(W.router_params(key, l, dims), rms(h, ones, eps), dims, router)

    @functools.partial(jax.jit, static_argnames=("first", "count"))
    def routed_part(key, l, h, first, count):
        """``sum_{e chosen, first <= e < first + count} w_e E_e(x)``, one expert's weights at a time."""
        x = rms(h, ones, eps)
        idx, w, _ = route(W.router_params(key, l, dims), x, dims, router)

        def one(e, acc):
            we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            return acc + we[:, None] * swiglu(W.expert_params(key, l, e, dims), x, precision)

        return jax.lax.fori_loop(first, first + count, one, jnp.zeros_like(x))

    @jax.jit
    def logits(key, x):
        return _dot("td,vd->tv", rms(x, ones, eps), W.table_rows(key, "head", W.vocab_rows(dims), dims), precision)

    return {"embed": embed, "mixer": mixer, "dense": dense, "routing": routing, "routed_part": routed_part, "logits": logits}


class Reference:
    """The reference model of one configuration (``dims``: the published
    keys plus the share) and seed; ``variant`` a control (see above)."""

    def __init__(self, dims: Dict[str, Any], seed: int, precision: str = "float32", **variant):
        self.dims = dict(dims)
        self.key = W.seed_key(seed)
        self.precision = precision
        self._p = _programs(_freeze(self.dims), precision, tuple(sorted(variant.items())))

    def moe_part(self, l: int, h, held: Optional[Tuple[int, int]] = None):
        """The routed part of the experts held of sparse layer ``l`` for ``h (T, D)``, the layer's input after its
        mixer.  ``held`` defaults to the configuration's share."""
        first, count = held if held is not None else W.held(self.dims)
        return self._p["routed_part"](self.key, l, h, first, count)

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
        return h + self.moe_part(l, h)

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
