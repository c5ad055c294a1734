"""GigaChat3.5's forward in plain ``jax.numpy``: the reference the
program's served tokens, recurrent state and cached latents are held
against.

float32 with ``highest`` matmul precision, no kernel, no cache, no
batching; the delta-rule layers as the **token-by-token recurrence**, the
latent-attention layers in the **expanded** form as full causal attention
computed in blocks of heads and queries, one block of weights live at a
time (a layer's mixer, one routed expert) — the weights come from
:mod:`weights_gigachat35` and the seed, never from the program under
test.  The two multi-token-prediction modules of the published model are
no part of the next-token forward and are left out here as in the
program (``num_nextn_predict_layers`` 0).

The equations (``x`` a token's hidden state; no bias anywhere):

* ``N(x; w) = x rsqrt(mean(x^2) + eps) * g sigmoid(w)``, ``g =
  layernorm_gating_weight`` = 2 (*assumed 1*).
* layer ``l`` (*assumed 2*): ``x <- x + N(Mixer_l(N(x; w1)); w2)``; ``x <-
  x + N(FFN_l(N(x; w3)); w4)``.  ``Mixer_l`` is latent attention where
  ``l`` is in ``full_attention_layers``, else the gated delta rule;
  ``FFN_l`` the dense SwiGLU where ``l < first_k_dense_replace``, else the
  MoE.  After the last layer ``N``, then the untied head.
* latent attention (``u = N(x; w1)``): ``c_q = RMS(u W_qa)``; ``q = c_q
  W_qb`` per head ``[q_nope | q_pe]``; ``[c_kv | k_pe] = u W_kva``, ``c_kv
  <- RMS(c_kv)``; rotary with YaRN on ``q_pe``, ``k_pe`` (half layout:
  *departure 1*); per head ``[k_nope | v] = c_kv W_kvb``; ``score = (q_nope
  . k_nope + q_pe . k_pe) s`` with ``s = (nope + rope)^-1/2 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1`` (*assumed 3*); causal softmax; ``o = sum
  p v``; output ``(sigmoid(u W_g) * concat_heads(o)) W_o`` (*assumed 4*).
* gated delta rule (``u = N(x; w1)``; Gated DeltaNet, arXiv:2412.06464):
  ``[q | k | v] = SiLU(conv([u W_q | u W_k | u W_v]))``, a causal depthwise
  convolution of ``linear_conv_kernel_dim`` taps, zeros before the
  sequence, no bias (*assumed 5*); ``q, k`` L2-normalised a head, ``q``
  scaled by ``dk^-1/2``; ``g = -exp(A_log) softplus(u W_a + dt_bias)``,
  one scalar a value head; ``beta = sigmoid(u W_b)``; value head ``h``
  reads query / key head ``h // (Hv / Hk)`` (*assumed 6*); per value head
  ``S <- exp(g) S``, ``S <- S + k (beta (v - S^T k))^T`` from ``S = 0``,
  ``o = S^T q``; output ``(RMS_head(o) (1 + w_n) * gs sigmoid(u W_z)) W_o``,
  ``gs = linear_sigmoid_gate_scale`` (*assumed 7*).
* SwiGLU, everywhere (*assumed 8*): ``W_d (silu(min(a, L)) * clip(b, -L,
  L))``, ``[a | b] = x W_gu``, ``L = swiglu_limit``.
* MoE (``r = N(x; w3)``): ``s = sigmoid(r W_r)`` over all routed experts
  (*assumed 9*); the ``num_experts_per_tok`` largest of ``s + bias``
  chosen, weight ``s`` over the chosen ``s``'s sum (``norm_topk_prob``)
  times ``routed_scaling_factor``; ``FFN(r) = sum_{e chosen and held} w_e
  E_e(r) + S(r)``, the shared expert ungated.

**Assumed** — the config names these forms and not their formulas; each
is read as the key's own name and value give, the other reading beside
it (the configuration file repeats them): (1) ``norm_type``
``ZeroCenteredGatedNorm``, ``layernorm_gating_weight`` 2: the gain is
``2 sigmoid(w)`` — other reading: ``1 + w``; (2) ``layernorm_type``
``pre_post``: a norm before and after each of mixer and feed-forward —
other reading: pre-norm plus one post-norm on the layer's output; (3)
``use_mla_scaling_factor``: the YaRN softmax multiplier ``m^2`` — other
reading: a learned or per-layer query scale; (4) ``gated_attention``:
an elementwise sigmoid gate ``D -> H v`` on the attention output before
``W_o`` — other reading: one gate a head; (5) the convolution has no
bias and SiLU follows it; (6) consecutive value heads share a query / key
head — other reading: interleaved (``h % Hk``); (7)
``linear_gating_type`` ``gated_rmsnorm_sigmoid_zero_centered``,
``linear_sigmoid_gate_scale`` 2: gain ``1 + w_n``, gate ``2 sigmoid(z)`` —
other reading: gain ``2 sigmoid(w_n)``, gate ``silu(z)``; (8)
``swiglu_limit`` 10: the gate clamped above, the linear half on both
sides, on routed, shared and dense alike — other reading: routed experts
only, or with the ``(b + 1)`` offset of gpt-oss; (9) the router's scoring
is not in the config: sigmoid with a selection-only bias is the key
set's family (DeepSeek-V3) — other reading: softmax.

**Departures** from the published model: (1) the rotary dimensions are in
the half layout (``rope_interleave``: the published interleaved layout is
a relabelling under seeded weights); (2) only the experts
``experts_held`` and the rows ``vocab_held`` exist — with no share given
the model is whole; (3) the depth, the dense layers and the
latent-attention layers are what ``num_hidden_layers``,
``first_k_dense_replace`` and ``full_attention_layers`` say; (4) no
multi-token-prediction modules.

``precision`` rounds every matmul *operand* of the projections, the
attention products and the experts before an exact float32 contraction
(``"float32"`` the reference, ``"bfloat16"`` what the configuration
states, ``"int8"`` a control); the recurrence's own products against its
float32 state are never rounded.  Two further **controls** are variants
of this reference (the program has no switch for either):
``state_dtype="bfloat16"`` rounds the recurrent state to bfloat16 after
every token; ``decay=False`` leaves the decay out (``g = 0``: a delta
rule without its gate).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_gigachat35 as W
from .reference_deepseek_v2 import rms, rope, softmax_scale, yarn_inv_freq  # the YaRN softmax multiplier is DeepSeek-V2's (assumed 3)
from .reference_gpt2 import _dot
from .reference_solar_open2 import _dims_key, l2norm

HEAD_BLOCK, QUERY_BLOCK = 8, 1024  # softmax attention is computed this many heads x queries at a time
_HI = jax.lax.Precision.HIGHEST


def gated_norm(x, w, dims: Dict[str, Any]):
    """``N(x; w)``: assumed 1."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + dims["rms_norm_eps"]) \
        * (dims["layernorm_gating_weight"] * jax.nn.sigmoid(w))


def mla(ap: Dict[str, Any], u, dims: Dict[str, Any], precision: str):
    """Gated latent attention of one sequence ``u (T, D)`` after its
    input norm.  Returns the mixer's output and the rows a cache would
    hold, ``[c_kv | k_pe] (T, kv_lora_rank + rope)``."""
    T = u.shape[0]
    H, dn, dr, dv, c = (dims["num_attention_heads"], dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
                        dims["v_head_dim"], dims["kv_lora_rank"])
    eps, inv_freq, s = dims["rms_norm_eps"], jnp.asarray(yarn_inv_freq(dims)), softmax_scale(dims)
    pos = jnp.arange(T)
    q = _dot("tc,ce->te", rms(_dot("td,dc->tc", u, ap["q_a"], precision), ap["q_a_norm"], eps), ap["q_b"], precision)
    q = q.reshape(T, H, dn + dr)
    kv = _dot("td,dc->tc", u, ap["kv_a"], precision)
    c_kv, k_pe = rms(kv[:, :c], ap["kv_a_norm"], eps), rope(kv[:, c:], pos, inv_freq)  # departure 1: half layout
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
    gate = jax.nn.sigmoid(_dot("td,de->te", u, ap["gate"], precision))  # assumed 4: an elementwise gate
    return _dot("te,ed->td", gate * out, ap["o"], precision), jnp.concatenate([c_kv, k_pe], axis=-1)


def gdn(ap: Dict[str, Any], u, dims: Dict[str, Any], precision: str, n=None, state_dtype: str = "float32", decay: bool = True):
    """The gated delta rule of one sequence ``u (T, D)`` after its input
    norm, as the recurrence, one token after another.  Returns the
    mixer's output and ``S_n``, the state the first ``n`` tokens leave
    behind (``n`` None: all ``T``; tokens from ``n`` on are read from it
    and do not change it)."""
    T = u.shape[0]
    Hk, Hv, dk, dv, taps = W.gdn_sizes(dims)
    Wk, rep = Hk * dk, Hv // Hk
    qkv = _dot("td,de->te", u, ap["qkv"], precision)
    ext = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1]), jnp.float32), qkv])  # zeros before the sequence
    y = jax.nn.silu(sum(ext[j:j + T] * ap["conv"][j] for j in range(taps)))  # assumed 5: no bias
    q = l2norm(y[:, :Wk].reshape(T, Hk, dk)) * dk ** -0.5
    k = l2norm(y[:, Wk: 2 * Wk].reshape(T, Hk, dk))
    q, k = jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1)  # assumed 6: value head h reads query / key head h // rep
    v = y[:, 2 * Wk:].reshape(T, Hv, dv)
    g = -jnp.exp(ap["A_log"]) * jax.nn.softplus(_dot("td,dh->th", u, ap["a"], precision) + ap["dt_bias"])  # (T, Hv)
    if not decay:
        g = jnp.zeros_like(g)  # a control: the delta rule without its gate
    beta = jax.nn.sigmoid(_dot("td,dh->th", u, ap["b"], precision))
    # the state's precision between tokens: (exponent, mantissa) bits.  ``reduce_precision`` and not a pair of casts: XLA:TPU may
    # keep the excess precision of float32 -> bfloat16 -> float32 (it did: the control read a float32 state on the chip)
    held_bits = {"float32": (8, 23), "bfloat16": (8, 7), "float16": (5, 10)}[state_dtype]

    def step(S, xs):
        qt, kt, vt, gt, bt, counted = xs
        S1 = S * jnp.exp(gt)[:, None, None]
        w = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, S1, precision=_HI))
        S1 = S1 + kt[..., None] * w[:, None, :]
        S1 = jax.lax.reduce_precision(S1, *held_bits)  # a control rounds the state here, once a token
        return jnp.where(counted, S1, S), jnp.einsum("hk,hkv->hv", qt, S1, precision=_HI)

    counted = jnp.arange(T) < (T if n is None else n)
    S_n, o = jax.lax.scan(step, jnp.zeros((Hv, dk, dv), jnp.float32), (q, k, v, g, beta, counted))
    # assumed 7: a zero-centred gain, a sigmoid gate scaled by linear_sigmoid_gate_scale
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + dims["linear_attn_o_norm_eps"]) * (1.0 + ap["o_norm_w"])
    gate = dims["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(_dot("td,de->te", u, ap["z"], precision)).reshape(T, Hv, dv)
    return _dot("te,ed->td", (o * gate).reshape(T, Hv * dv), ap["o"], precision), S_n


def swiglu(p: Dict[str, Any], x, limit: float, precision: str):
    """Assumed 8: the clamped SwiGLU."""
    a, b = jnp.split(_dot("td,df->tf", x, p["gu"], precision), 2, axis=-1)
    return _dot("tf,fd->td", jax.nn.silu(jnp.minimum(a, limit)) * jnp.clip(b, -limit, limit), p["down"], precision)


def route(sp: Dict[str, Any], x, dims: Dict[str, Any], precision: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(idx (T, k), weight (T, k))``: top-k of ``sigmoid + bias``,
    weights the sigmoid scores over their sum, scaled (assumed 9)."""
    s = jax.nn.sigmoid(_dot("td,de->te", x, sp["router"], precision))
    _, idx = jax.lax.top_k(s + sp["router_bias"], dims["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if dims.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * dims["routed_scaling_factor"]


@functools.lru_cache(maxsize=None)
def _programs(dims_key: Tuple, precision: str, state_dtype: str, decay: bool):
    dims = {k: (dict(v) if isinstance(v, tuple) and v and isinstance(v[0], tuple) else v) for k, v in dims_key}
    limit = float(dims["swiglu_limit"])

    @jax.jit
    def embed(key, tokens):
        rows = W.vocab_rows(dims)  # departure 2: a sliced vocabulary is a smaller vocabulary
        return jnp.take(W.table_rows(key, "embed", rows, dims), tokens, axis=0)

    @jax.jit
    def mla_layer(key, l, x):
        nw = W.norm_params(key, l, dims)
        y, rows = mla(W.mla_params(key, l, dims), gated_norm(x, nw["mixer_in_w"], dims), dims, precision)
        return x + gated_norm(y, nw["mixer_out_w"], dims), rows  # assumed 2: the sandwich

    @jax.jit
    def gdn_layer(key, l, x, n):
        nw = W.norm_params(key, l, dims)
        y, S_n = gdn(W.gdn_params(key, l, dims), gated_norm(x, nw["mixer_in_w"], dims), dims, precision, n, state_dtype, decay)
        return x + gated_norm(y, nw["mixer_out_w"], dims), S_n

    def ffn_in(key, l, h):
        return gated_norm(h, W.norm_params(key, l, dims)["ffn_in_w"], dims)

    @jax.jit
    def dense_part(key, l, h):
        return swiglu(W.dense_mlp_params(key, l, dims), ffn_in(key, l, h), limit, precision)

    @jax.jit
    def routing(key, l, h):
        return route(W.shared_params(key, l, dims), ffn_in(key, l, h), dims, precision)

    @functools.partial(jax.jit, static_argnames=("first", "count"))
    def routed_part(key, l, h, first, count):
        """``sum_{e chosen, first <= e < first + count} w_e E_e(r)``, one expert's weights at a time."""
        r = ffn_in(key, l, h)
        idx, w = route(W.shared_params(key, l, dims), r, dims, precision)

        def one(e, acc):
            we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            return acc + we[:, None] * swiglu(W.expert_params(key, l, e, dims), r, limit, precision)

        return jax.lax.fori_loop(first, first + count, one, jnp.zeros_like(r))

    @jax.jit
    def shared_part(key, l, h):
        return swiglu(W.shared_params(key, l, dims), ffn_in(key, l, h), limit, precision)

    @jax.jit
    def ffn_out(key, l, h, f):
        return h + gated_norm(f, W.norm_params(key, l, dims)["ffn_out_w"], dims)

    @jax.jit
    def head(key, x):
        return _dot("td,vd->tv", gated_norm(x, W.final_gain(key, dims), dims),
                    W.table_rows(key, "head", W.vocab_rows(dims), dims), precision)

    return {"embed": embed, "mla_layer": mla_layer, "gdn_layer": gdn_layer, "dense_part": dense_part, "routing": routing,
            "routed_part": routed_part, "shared_part": shared_part, "ffn_out": ffn_out, "head": head}


class Reference:
    """The reference model of one configuration (``dims``: the published
    keys plus the share) and seed.  ``state_dtype`` and ``decay`` make the
    two controls the module's docstring names."""

    def __init__(self, dims: Dict[str, Any], seed: int, precision: str = "float32", state_dtype: str = "float32",
                 decay: bool = True):
        self.dims = dict(dims)
        self.key = W.seed_key(seed)
        self.precision = precision
        self._p = _programs(_dims_key(self.dims), precision, state_dtype, bool(decay))

    def moe_parts(self, l: int, h, held: Optional[Tuple[int, int]] = None):
        """``(routed part of the experts held, shared expert's part)`` of
        expert layer ``l`` for ``h (T, D)``, the layer's state after its
        mixer.  ``held`` defaults to the configuration's share."""
        first, count = held if held is not None else W.held(self.dims)
        return self._p["routed_part"](self.key, l, h, first, count), self._p["shared_part"](self.key, l, h)

    def routing(self, l: int, h):
        return self._p["routing"](self.key, l, h)

    def mixer(self, l: int, x, n=None):
        """``(x + N(Mixer_l(N(x))), S_n, rows)``: ``S_n (value heads, dk,
        dv)`` a delta-rule layer's state after the first ``n`` tokens
        (None: all of them), ``rows (T, width)`` what a latent-attention
        layer caches; the other is None."""
        if W.is_latent(self.dims, l):
            h, rows = self._p["mla_layer"](self.key, l, x)
            return h, None, rows
        h, S_n = self._p["gdn_layer"](self.key, l, x, jnp.int32(x.shape[0] if n is None else n))
        return h, S_n, None

    def ffn(self, l: int, h, held: Optional[Tuple[int, int]] = None):
        """``h + N(FFN_l(N(h)))`` for ``h (T, D)``, the state after the mixer."""
        if W.is_dense(self.dims, l):
            f = self._p["dense_part"](self.key, l, h)
        else:
            routed, shared = self.moe_parts(l, h, held)
            f = routed + shared
        return self._p["ffn_out"](self.key, l, h, f)

    def layer(self, l: int, x, keep=None, states=None, latents=None, n=None):
        """One decoder layer on one sequence ``x (T, D)``.  ``keep``, a
        list, is given the state after the mixer; ``states`` a delta-rule
        layer's state after ``n`` tokens; ``latents`` a latent-attention
        layer's cached rows."""
        h, S_n, rows = self.mixer(l, x, n)
        if keep is not None:
            keep.append(h)
        if states is not None and S_n is not None:
            states.append(S_n)
        if latents is not None and rows is not None:
            latents.append(rows)
        return self.ffn(l, h)

    def hidden(self, tokens, keep=None, states=None, latents=None, n=None):
        """Final hidden states ``(T, D)`` of one sequence ``tokens (T,)``."""
        with jax.default_matmul_precision("highest"):
            x = self._p["embed"](self.key, jnp.asarray(tokens, jnp.int32))
            for l in range(self.dims["num_hidden_layers"]):
                x = self.layer(l, x, keep, states, latents, n)
            return x

    def traces(self, tokens, n: int, at) -> Tuple[np.ndarray, np.ndarray]:
        """What the caches hold after the first ``n`` of ``tokens (T,)``,
        from one forward: every delta-rule layer's recurrent state
        ``(layers, value heads, dk, dv)`` float32, and every
        latent-attention layer's rows at the positions ``at``,
        ``(layers, len(at), width)``.  What follows ``n`` is padding: it
        cannot reach back."""
        states: List = []
        latents: List = []
        self.hidden(tokens, states=states, latents=latents, n=n)
        at = jnp.asarray(at, jnp.int32)
        return np.stack([np.asarray(s) for s in states]), np.stack([np.asarray(r[at]) for r in latents])

    def states(self, tokens, n: int):
        return self.traces(tokens, n, [0])[0]

    def logits(self, tokens):
        """``(B, T, rows held)`` for ``tokens (B, T)``, one sequence after another (no batching)."""
        tokens = np.asarray(tokens, np.int32)
        with jax.default_matmul_precision("highest"):
            return jnp.stack([self._p["head"](self.key, self.hidden(t)) for t in tokens])
