"""Solar-Open2's forward in plain ``jax.numpy``: the reference the
program's served tokens are held against.

float32 with ``highest`` matmul precision, no kernel, no cache, no
batching; the linear-attention layers as the **token-by-token
recurrence**, the softmax layers as full causal attention computed in
blocks of heads and queries, one block of weights live at a time (a
layer's mixer, one routed expert) — the weights come from
:mod:`weights_solar_open2` and the seed, never from the program under
test.

The equations (``x`` a token's hidden state, ``RMS`` RMSNorm with a
learned gain, eps ``rms_norm_eps``, no bias, no rotary or other
positional term: ``use_rope`` is false):

* layer ``l``: ``h = x + Mixer_l(RMS(x))``; ``y = h + MoE(RMS(h))``
  (``first_k_dense_replace`` 0: every layer has the MoE).  After the last
  layer ``RMS``, then the untied head.
* gated GQA (``l`` in ``gqa_layers``): ``q = x W_q`` (``H`` heads), ``k =
  x W_k``, ``v = x W_v`` (``Hkv`` heads), causal ``softmax(q k^T /
  sqrt(head_dim))`` with query head ``i`` on KV head ``i // (H / Hkv)``;
  output ``(sigmoid(x W_g) * attn) W_o``.
* KDA (every other layer; arXiv:2510.26692 as ``fla``'s
  ``KimiDeltaAttention``): ``conv`` a causal depthwise convolution of 4
  taps over the sequence (zeros before its start) followed by SiLU; ``q =
  L2norm_head(conv(x W_q)) / sqrt(dk)``, ``k = L2norm_head(conv(x W_k))``,
  ``v = conv(x W_v)``; ``g = -exp(A_log[head]) softplus(x W_a_down W_a_up
  + dt_bias)`` per channel; ``beta = 2 sigmoid(x W_beta)``; per head
  ``S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t
  v_t^T`` from ``S_0 = 0``, ``o_t = S_t^T q_t``; output ``(RMS_head(o_t) *
  sigmoid(x W_g_down W_g_up)) W_o``.
* MoE: ``s = sigmoid(x W_r)`` over all routed experts; the
  ``num_experts_per_tok`` largest of ``s + bias`` are chosen, with weight
  ``s`` over the chosen ``s``'s sum (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``MoE(x) = sum_{e chosen and held} w_e E_e(x)
  + S(x)`` with ``E_e``, ``S`` SwiGLU.

Departures from the published description, each also a comment where it
happens (the configuration file repeats them under ``assumed``): (1) the
config does not give the gate shapes: the GQA gate is taken elementwise
(``D -> H head_dim``), the KDA decay and output-gate projections
low-rank with rank ``head_dim`` (``kda_use_full_proj`` false); (2) the
config does not name the router's scoring: sigmoid with a selection-only
bias is the family's convention; (3) the shared expert's width is
``moe_intermediate_size x n_shared_experts``; (4) the experts are the
share ``experts_held`` and the vocabulary the slice ``vocab_held`` — with
no share given the model is whole; (5) the depth is what
``num_hidden_layers`` says, ``gqa_layers`` the softmax layers among them.

``precision`` rounds every matmul *operand* of the projections, the
attention products and the experts before an exact float32 contraction
(``"float32"`` the reference, ``"bfloat16"`` what the configuration
states, ``"int8"`` the control below it); the recurrence's own products
against its float32 state are never rounded.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_solar_open2 as W
from .reference_gpt2 import _dot

HEAD_BLOCK, QUERY_BLOCK = 8, 1024  # softmax attention is computed this many KV-head groups x queries at a time
_HI = jax.lax.Precision.HIGHEST


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def gqa(ap: Dict[str, Any], x, dims: Dict[str, Any], precision: str):
    """Gated grouped-query attention of one sequence ``x (T, D)`` after its input norm."""
    T = x.shape[0]
    H, Hkv, hd = dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    G = H // Hkv
    qkv = _dot("td,de->te", x, ap["qkv"], precision)
    q = qkv[:, : H * hd].reshape(T, Hkv, G, hd)
    k = qkv[:, H * hd: (H + Hkv) * hd].reshape(T, Hkv, hd)
    v = qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd)
    pos = jnp.arange(T)
    hb, qb = min(max(1, HEAD_BLOCK // G), Hkv), min(QUERY_BLOCK, T)
    while Hkv % hb:
        hb -= 1
    while T % qb:
        qb -= 1

    def block(i):
        h0, t0 = (i // (T // qb)) * hb, (i % (T // qb)) * qb
        qs = jax.lax.dynamic_slice(q, (t0, h0, 0, 0), (qb, hb, G, hd))
        ks = jax.lax.dynamic_slice_in_dim(k, h0, hb, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, h0, hb, axis=1)
        sc = _dot("qhgd,khd->hgqk", qs, ks, precision) * hd ** -0.5  # no positional term (departure: none, use_rope false)
        causal = pos[None, :] <= (t0 + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), axis=-1)
        return _dot("hgqk,khd->qhgd", p, vs, precision)  # (qb, hb, G, hd)

    out = jax.lax.map(block, jnp.arange((Hkv // hb) * (T // qb)))
    out = out.reshape(Hkv // hb, T // qb, qb, hb, G, hd).transpose(1, 2, 0, 3, 4, 5).reshape(T, H * hd)
    gate = jax.nn.sigmoid(_dot("td,de->te", x, ap["gate"], precision))  # departure 1: an elementwise gate
    return _dot("te,ed->td", gate * out, ap["o"], precision)


def kda(ap: Dict[str, Any], x, dims: Dict[str, Any], precision: str, neg_eigval: bool = True, n=None):
    """Kimi Delta Attention of one sequence ``x (T, D)`` after its input
    norm, as the recurrence, one token after another.  Returns the
    layer's output and ``S_n``, the state the first ``n`` tokens leave
    behind (``n`` None: all ``T``; tokens from ``n`` on are read from it
    and do not change it)."""
    T = x.shape[0]
    Hl, dl, taps, _ = W.kda_sizes(dims)
    width = Hl * dl
    qkv = _dot("td,de->te", x, ap["qkv"], precision)
    ext = jnp.concatenate([jnp.zeros((taps - 1, 3 * width), jnp.float32), qkv])  # zeros before the sequence
    y = jax.nn.silu(sum(ext[j:j + T] * ap["conv"][j] for j in range(taps)))
    heads = lambda t: t.reshape(T, Hl, dl)  # noqa: E731
    q, k, v = l2norm(heads(y[:, :width])) * dl ** -0.5, l2norm(heads(y[:, width:2 * width])), heads(y[:, 2 * width:])
    # departure 1: the decay projection is the low-rank pair (kda_use_full_proj false), rank head_dim
    a = _dot("tr,re->te", _dot("td,dr->tr", x, ap["a_down"], precision), ap["a_up"], precision) + ap["dt_bias"]
    g = -jnp.exp(ap["A_log"])[:, None] * heads(jax.nn.softplus(a))
    beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(_dot("td,dh->th", x, ap["beta"], precision))

    def step(S, xs):
        qt, kt, vt, gt, bt, counted = xs
        S1 = S * jnp.exp(gt)[..., None]
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, S1, precision=_HI))
        S1 = S1 + kt[..., None] * u[:, None, :]
        return jnp.where(counted, S1, S), jnp.einsum("hk,hkv->hv", qt, S1, precision=_HI)

    counted = jnp.arange(T) < (T if n is None else n)
    S_n, o = jax.lax.scan(step, jnp.zeros((Hl, dl, dl), jnp.float32), (q, k, v, g, beta, counted))
    gate = jax.nn.sigmoid(_dot("tr,re->te", _dot("td,dr->tr", x, ap["g_down"], precision), ap["g_up"], precision))
    return _dot("te,ed->td", (rms(o, ap["o_norm"], dims["rms_norm_eps"]) * heads(gate)).reshape(T, width), ap["o"], precision), S_n


def swiglu(p: Dict[str, Any], x, precision: str):
    g, u = jnp.split(_dot("td,df->tf", x, p["gu"], precision), 2, axis=-1)
    return _dot("tf,fd->td", jax.nn.silu(g) * u, p["down"], precision)


def route(sp: Dict[str, Any], x, dims: Dict[str, Any], precision: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(idx (T, k), weight (T, k))``: top-k of ``sigmoid + bias``,
    weights the sigmoid scores over their sum (departure 2)."""
    s = jax.nn.sigmoid(_dot("td,de->te", x, sp["router"], precision))
    _, idx = jax.lax.top_k(s + sp["router_bias"], dims["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if dims.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * dims["routed_scaling_factor"]


def _dims_key(dims: Dict[str, Any]) -> Tuple:
    flat = {k: (tuple(sorted(v.items())) if isinstance(v, dict) else tuple(v) if isinstance(v, list) else v)
            for k, v in dims.items() if isinstance(v, (int, float, bool, dict, list, tuple))}
    return tuple(sorted(flat.items()))


@functools.lru_cache(maxsize=None)
def _programs(dims_key: Tuple, precision: str):
    dims = {k: (dict(v) if isinstance(v, tuple) and v and isinstance(v[0], tuple) else v) for k, v in dims_key}
    eps, ones = dims["rms_norm_eps"], jnp.ones((dims["hidden_size"],), jnp.float32)  # every hidden-size gain is 1

    @jax.jit
    def embed(key, tokens):
        rows = W.vocab_rows(dims)  # departure 4: a sliced vocabulary is a smaller vocabulary
        return jnp.take(W.table_rows(key, "embed", rows, dims), tokens, axis=0)

    @jax.jit
    def gqa_layer(key, l, x):
        return x + gqa(W.gqa_params(key, l, dims), rms(x, ones, eps), dims, precision)

    @jax.jit
    def kda_layer(key, l, x, n):
        y, S_n = kda(W.kda_params(key, l, dims), rms(x, ones, eps), dims, precision, dims.get("kda_allow_neg_eigval", True), n)
        return x + y, S_n

    @jax.jit
    def routing(key, l, h):
        return route(W.shared_params(key, l, dims), rms(h, ones, eps), dims, precision)

    @functools.partial(jax.jit, static_argnames=("first", "count"))
    def routed_part(key, l, h, first, count):
        """``sum_{e chosen, first <= e < first + count} w_e E_e(x)``, one expert's weights at a time."""
        x = rms(h, ones, eps)
        idx, w = route(W.shared_params(key, l, dims), x, dims, precision)

        def one(e, acc):
            we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            return acc + we[:, None] * swiglu(W.expert_params(key, l, e, dims), x, precision)

        return jax.lax.fori_loop(first, first + count, one, jnp.zeros_like(x))

    @jax.jit
    def shared_part(key, l, h):
        return swiglu(W.shared_params(key, l, dims), rms(h, ones, eps), precision)  # departure 3: one SwiGLU of the shared width

    @jax.jit
    def logits(key, x):
        return _dot("td,vd->tv", rms(x, ones, eps), W.table_rows(key, "head", W.vocab_rows(dims), dims), precision)

    return {"embed": embed, "gqa_layer": gqa_layer, "kda_layer": kda_layer, "routing": routing,
            "routed_part": routed_part, "shared_part": shared_part, "logits": logits}


class Reference:
    """The reference model of one configuration (``dims``: the published
    keys plus the share) and seed."""

    def __init__(self, dims: Dict[str, Any], seed: int, precision: str = "float32"):
        self.dims = dict(dims)
        self.key = W.seed_key(seed)
        self.precision = precision
        self._p = _programs(_dims_key(self.dims), precision)

    def moe_parts(self, l: int, h, held: Optional[Tuple[int, int]] = None):
        """``(routed part of the experts held, shared expert's part)`` of
        layer ``l`` for ``h (T, D)``, the layer's input after its mixer.
        ``held`` defaults to the configuration's share."""
        first, count = held if held is not None else W.held(self.dims)
        return self._p["routed_part"](self.key, l, h, first, count), self._p["shared_part"](self.key, l, h)

    def routing(self, l: int, h):
        return self._p["routing"](self.key, l, h)

    def mixer(self, l: int, x, n=None):
        """``(x + Mixer_l(RMS(x)), S_n)``: ``S_n (heads, dk, dv)`` is a
        linear-attention layer's state after the first ``n`` tokens
        (None: all of them), None for a softmax layer."""
        if W.is_gqa(self.dims, l):
            return self._p["gqa_layer"](self.key, l, x), None
        return self._p["kda_layer"](self.key, l, x, jnp.int32(x.shape[0] if n is None else n))

    def layer(self, l: int, x, keep=None, states=None, n=None):
        """One decoder layer on one sequence ``x (T, D)``.  ``keep``, a
        list, is given the MoE's input (the state after the mixer);
        ``states``, a list, a linear-attention layer's state after ``n``
        tokens."""
        h, S_n = self.mixer(l, x, n)
        if keep is not None:
            keep.append(h)
        if states is not None and S_n is not None:
            states.append(S_n)
        routed, shared = self.moe_parts(l, h)
        return h + routed + shared

    def hidden(self, tokens, keep=None, states=None, n=None):
        """Final hidden states ``(T, D)`` of one sequence ``tokens (T,)``."""
        with jax.default_matmul_precision("highest"):
            x = self._p["embed"](self.key, jnp.asarray(tokens, jnp.int32))
            for l in range(self.dims["num_hidden_layers"]):
                x = self.layer(l, x, keep, states, n)
            return x

    def states(self, tokens, n: int):
        """The recurrent state of every linear-attention layer, in layer
        order, after the first ``n`` of ``tokens (T,)``: ``(layers, heads,
        dk, dv)`` float32.  What follows ``n`` is padding: it cannot reach back."""
        out: list = []
        self.hidden(tokens, states=out, n=n)
        return np.stack([np.asarray(s) for s in out])

    def logits(self, tokens):
        """``(B, T, rows held)`` for ``tokens (B, T)``, one sequence after another (no batching)."""
        tokens = np.asarray(tokens, np.int32)
        with jax.default_matmul_precision("highest"):
            return jnp.stack([self._p["logits"](self.key, self.hidden(t)) for t in tokens])
