"""ZAYA1's forward in plain ``jax.numpy``: the reference the program's
served tokens and cache rows are held against.

float32 with ``highest`` matmul precision, no kernel, no cache, no
chunking, no batching; attention as full causal attention computed in
blocks of queries, one block of weights live at a time (a layer's
attention, one expert) — the weights come from :mod:`weights_zaya1` and
the seed, never from the program under test.

The equations (``x`` a token's hidden state, ``RMS`` RMSNorm with a
learned gain, eps ``rms_norm_eps``, no bias anywhere):

* layer ``l`` (all alike): ``x <- res_a(x, CCA(RMS(x)))``, then ``x <-
  res_m(x, MoE(RMS(x), r))`` with ``res(x, y) = (a_r * x + b_r) + (a_o * y
  + b_o)``; after the last layer ``RMS``, then the **tied** head (the
  embedding's rows held).
* CCA, ``H`` query / ``Hkv`` KV heads of ``d``, ``G = H / Hkv``, for
  position ``t`` with ``h_t = RMS(x_t)``: ``q~_t = h_t W_q``, ``k~_t = h_t
  W_k``; values shifted by one position for half the KV heads ``v_t =
  [h_t W_v1 ; h_{t-1} W_v2]`` (``h_{-1} = 0``); the channels ``[q~ ; k~]``
  mixed along the sequence, causally, zeros before its start: a
  depthwise convolution of ``cca_time0`` = 2 taps, then a grouped one of
  ``cca_time1`` = 2 taps (a group a head, ``d -> d``); the q-k mean added,
  ``q[h] = q^[h] + (q~[h] + k~[h // G]) / 2``, ``k[g] = k^[g] + (mean_{h in
  g} q~[h] + k~[g]) / 2``; each head L2-normalised to length ``sqrt(d)``,
  ``k`` times its temperature ``tau[g]``; rotary (half layout) on the
  first ``partial_rotary_factor x d`` dims; causal softmax attention with
  scale ``d^-1/2``, query head ``h`` on KV head ``h // G``; ``W_o``.
* MoE: ``r = RMS(x) W_d + gamma_l r_prev`` (``r_prev`` the previous
  layer's ``r``, zero at layer 0); ``s = softmax(W_3 gelu(W_2 gelu(W_1
  RMS(r))))`` over all experts; ``e = argmax(s + bias)``; ``MoE = s_e
  E_e(RMS(x))`` if ``e`` is held, else nothing; ``E_e`` SwiGLU.  ``r`` is
  carried to the next layer.

**Assumed** — not in the published ``config.json``, each also a comment
where it happens (the configuration file repeats them under
``assumed``): (1) ``residual_scaling``: the form of ``res`` (elementwise
vectors of the hidden size, a set a sublayer, seeded near the identity);
(2) ``depth_averaging``: the coefficient ``gamma_l`` as one learned scalar
a layer; (3) ``router_mlp``: two hidden GELU (erf) layers of
``router_hidden_size`` and an RMSNorm in front of them; (4)
``k_temperature``: a learned temperature on k, one a KV head; (5)
``conv_bias``: the convolutions have no bias and nothing non-linear
between them; (6) ``skip_expert``: **no** skip ("mixture-of-depths")
expert — ``num_experts`` 16 and ``num_experts_per_tok`` 1 count none, so
the router has 16 outputs; (7) ``value_shift``: which half of the KV
heads reads the token before (the second); (8) the experts are the share
``experts_held`` and the vocabulary the slice run — with no share given
the model is whole.

``precision`` rounds every matmul *operand* of the projections, the
grouped convolution, the attention products, the router and the experts
before an exact float32 contraction (``"float32"`` the reference,
``"bfloat16"`` what the configuration states, ``"int8"`` the control
below it).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_zaya1 as W
from .reference_gpt2 import _dot
from .reference_solar_open2 import l2norm, rms, swiglu

QUERY_BLOCK = 1024  # attention is computed this many queries at a time


def rope(x, rot: int, theta: float):
    """Rotary embedding (half layout) on the first ``rot`` dims of ``x (T, heads, d)`` at positions 0 .. T - 1."""
    half = rot // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * jnp.asarray(
        theta ** (-np.arange(half, dtype=np.float64) / half), jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def back(x, n: int = 1):
    """``x`` of ``n`` positions earlier, zeros before the sequence starts."""
    return jnp.concatenate([jnp.zeros((n,) + x.shape[1:], x.dtype), x[:-n]])


def cca(ap: Dict[str, Any], x, dims: Dict[str, Any], precision: str):
    """Compressed convolutional attention of one sequence ``x (T, D)``
    after its input norm.  Returns ``(the sublayer's output (T, D), k (T,
    Hkv, d), v (T, Hkv, d))``: ``k`` mixed, normalised, rotated and ``v``
    shifted, as attention meets them — what a cache holds."""
    T = x.shape[0]
    H, Hkv, d, rot, theta = W.cca_sizes(dims)
    G, C, S = H // Hkv, (H + Hkv) * d, Hkv // 2 * d
    proj = _dot("td,de->te", x, ap["qkv"], precision)
    z, v_now, v_back = proj[:, :C], proj[:, C: C + S], proj[:, C + S:]
    v = jnp.concatenate([v_now, back(v_back)], axis=-1).reshape(T, Hkv, d)  # assumed 7: the second half reads t - 1
    y0 = z * ap["conv0"][1] + back(z) * ap["conv0"][0]  # depthwise; assumed 5: no bias, nothing non-linear after it
    y0 = y0.reshape(T, H + Hkv, d)
    y = _dot("tgi,gio->tgo", y0, ap["conv1"][1], precision) + _dot("tgi,gio->tgo", back(y0), ap["conv1"][0], precision)
    zq, zk = z[:, : H * d].reshape(T, Hkv, G, d), z[:, H * d:].reshape(T, Hkv, d)
    q = y[:, :H].reshape(T, Hkv, G, d) + 0.5 * (zq + zk[:, :, None])  # the q-k mean
    k = y[:, H:] + 0.5 * (jnp.mean(zq, axis=2) + zk)
    q = rope(l2norm(q.reshape(T, H, d)) * d ** 0.5, rot, theta).reshape(T, Hkv, G, d)
    k = rope(l2norm(k) * (d ** 0.5 * ap["tau"])[:, None], rot, theta)  # assumed 4: a temperature a KV head
    pos = jnp.arange(T)
    qb = min(QUERY_BLOCK, T)
    while T % qb:
        qb -= 1

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        sc = _dot("qhgd,khd->hgqk", qs, k, precision) * d ** -0.5
        causal = pos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), axis=-1)
        return _dot("hgqk,khd->qhgd", p, v, precision)

    o = jax.lax.map(block, jnp.arange(T // qb)).reshape(T, H * d)
    return _dot("te,ed->td", o, ap["o"], precision), k, v


def route(rp: Dict[str, Any], x, r_prev, dims: Dict[str, Any], precision: str):
    """``(e (T,), s_e (T,), r (T, R))`` for ``x (T, D)`` after the
    sublayer's norm and the previous layer's ``r``."""
    r = _dot("td,dr->tr", x, rp["router_down"], precision) + rp["router_gamma"] * r_prev  # assumed 2: one scalar a layer
    h = rms(r, rp["router_norm"], dims["rms_norm_eps"])  # assumed 3: norm, two hidden GELU layers, the output layer
    h = jax.nn.gelu(_dot("tr,rs->ts", h, rp["router_w1"], precision), approximate=False)
    h = jax.nn.gelu(_dot("tr,rs->ts", h, rp["router_w2"], precision), approximate=False)
    s = jax.nn.softmax(_dot("tr,re->te", h, rp["router_w3"], precision), axis=-1)  # assumed 6: num_experts outputs, no skip expert
    e = jnp.argmax(s + rp["router_bias"], axis=-1)  # the bias selects and never weighs
    return e, jnp.take_along_axis(s, e[:, None], axis=-1)[:, 0], r


def res(x, y, vec):
    """Assumed 1: ``(a_r * x + b_r) + (a_o * y + b_o)``, ``vec (4, D)``."""
    return (vec[0] * x + vec[1]) + (vec[2] * y + vec[3])


_SIZES = ("hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim", "cca_time0",
          "cca_time1", "partial_rotary_factor", "num_experts", "moe_intermediate_size", "router_hidden_size", "rms_norm_eps",
          "vocab_size")


def _sizes_key(dims: Dict[str, Any]) -> Tuple:
    """The sizes the programs below are made from, hashable: the nested rotary base flattened to ``rope_theta``."""
    return tuple((k, dims[k]) for k in _SIZES) + (("rope_theta", W.cca_sizes(dims)[4]), ("experts_held", W.held(dims)))


@functools.lru_cache(maxsize=None)
def _programs(sizes_key: Tuple, precision: str):
    dims = dict(sizes_key)
    eps, ones = dims["rms_norm_eps"], jnp.ones((dims["hidden_size"],), jnp.float32)  # every hidden-size gain is 1

    @jax.jit
    def embed(key, tokens):
        return jnp.take(W.embedding(key, dims), tokens, axis=0)  # assumed 8: a sliced vocabulary is a smaller vocabulary

    @jax.jit
    def attn_sublayer(key, l, x):
        y, k, v = cca(W.cca_params(key, l, dims), rms(x, ones, eps), dims, precision)
        return res(x, y, W.router_params(key, l, dims)["res_attn"]), k, v

    @jax.jit
    def routing(key, l, x, r_prev):
        return route(W.router_params(key, l, dims), rms(x, ones, eps), r_prev, dims, precision)

    @functools.partial(jax.jit, static_argnames=("first", "count"))
    def routed_part(key, l, x, r_prev, first, count):
        """``s_e E_e(RMS(x))`` where ``first <= e < first + count``, one expert's weights at a time; and ``r``."""
        h = rms(x, ones, eps)
        e, s, r = route(W.router_params(key, l, dims), h, r_prev, dims, precision)

        def one(i, acc):
            return acc + jnp.where(e == i, s, 0.0)[:, None] * swiglu(W.expert_params(key, l, i, dims), h, precision)

        return jax.lax.fori_loop(first, first + count, one, jnp.zeros_like(h)), r

    @jax.jit
    def moe_residual(key, l, x, y):
        return res(x, y, W.router_params(key, l, dims)["res_moe"])

    @jax.jit
    def head(key, x):
        return _dot("td,vd->tv", rms(x, ones, eps), W.embedding(key, dims), precision)  # the tied head

    return {"embed": embed, "attn_sublayer": attn_sublayer, "routing": routing, "routed_part": routed_part,
            "moe_residual": moe_residual, "head": head}


class Reference:
    """The reference model of one configuration (``dims``: the published
    keys plus the share) and seed."""

    def __init__(self, dims: Dict[str, Any], seed: int, precision: str = "float32"):
        self.dims = dict(dims)
        self.key = W.seed_key(seed)
        self.precision = precision
        self._p = _programs(_sizes_key(self.dims), precision)

    def attention(self, l: int, x):
        """``(res(x, CCA(RMS(x))), k, v)`` of layer ``l`` for one sequence ``x (T, D)``."""
        return self._p["attn_sublayer"](self.key, l, x)

    def routing(self, l: int, x, r_prev):
        """``(e, s_e, r)`` of layer ``l`` for ``x (T, D)``, the MoE sublayer's input."""
        return self._p["routing"](self.key, l, x, r_prev)

    def moe_part(self, l: int, x, r_prev, held: Optional[Tuple[int, int]] = None):
        """``(what the experts held give (T, D), r)`` — before the
        residual scaling.  ``held`` defaults to the configuration's share."""
        first, count = held if held is not None else W.held(self.dims)
        return self._p["routed_part"](self.key, l, x, r_prev, first, count)

    def moe_residual(self, l: int, x, y):
        return self._p["moe_residual"](self.key, l, x, y)

    def hidden(self, tokens, kv_at=None, kv: Optional[List] = None):
        """Final hidden states ``(T, D)`` of one sequence ``tokens
        (T,)``.  ``kv``, a list, is given each layer's ``(k, v)`` rows at
        the positions ``kv_at``: ``(n, Hkv, d)`` each."""
        with jax.default_matmul_precision("highest"):
            x = self._p["embed"](self.key, jnp.asarray(tokens, jnp.int32))
            r = jnp.zeros((x.shape[0], self.dims["router_hidden_size"]), jnp.float32)
            for l in range(self.dims["num_hidden_layers"]):
                x, k, v = self.attention(l, x)
                if kv is not None:
                    kv.append((np.asarray(k[np.asarray(kv_at)]), np.asarray(v[np.asarray(kv_at)])))
                y, r = self.moe_part(l, x, r)
                x = self.moe_residual(l, x, y)
            return x

    def head(self, rows):
        """Logits ``(n, rows held)`` of hidden states ``rows (n, D)``."""
        with jax.default_matmul_precision("highest"):
            return self._p["head"](self.key, rows)

    def logits(self, tokens):
        """``(B, T, rows held)`` for ``tokens (B, T)``, one sequence after another (no batching)."""
        tokens = np.asarray(tokens, np.int32)
        return jnp.stack([self.head(self.hidden(t)) for t in tokens])
