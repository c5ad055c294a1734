"""GPT-2's forward, loss and per-layer gradients in plain ``jax.numpy``.

float32 with ``highest`` matmul precision, no kernels, no cache, no
batching tricks, one layer's weights live at a time; the weights come
from :mod:`weights` and the seed, never from the program under test.
It follows the published model (pre-LayerNorm blocks, tanh-approximate
GELU — OpenAI's ``gelu_new`` —, learned positions, tied output head);
dropout is 0 as in the configurations.

``precision`` rounds every matmul *operand* before an exact float32
contraction, which is how the controls are made: ``"float32"`` (the
reference), ``"bfloat16"`` (what the configurations state), ``"int8"``
(per-tensor symmetric, the step below bf16 that a later PR might be
tempted by).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

BLOCK_MATRICES = ("qkv_w", "proj_w", "fc_w", "fc_proj_w")


def _round(x, precision: str):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
        return jnp.round(x / scale) * scale
    raise ValueError(f"unknown precision {precision!r} (float32|bfloat16|int8)")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _dot(spec: str, a, b, precision: str):
    """An exact float32 contraction of operands rounded to ``precision``
    — in the backward too: each operand's gradient is the contraction of
    the *rounded* cotangent with the other rounded operand, as a matmul
    unit of that precision would compute it.  (Differentiating through
    the rounding itself would give a gradient of zero.)"""
    return _contract(spec, _round(a, precision), _round(b, precision))


def _contract(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _dot_fwd(spec, a, b, precision):
    ra, rb = _round(a, precision), _round(b, precision)
    return _contract(spec, ra, rb), (ra, rb)


def _dot_bwd(spec, precision, rounded, dy):
    _, vjp = jax.vjp(functools.partial(_contract, spec), *rounded)
    return vjp(_round(dy, precision))


_dot.defvjp(_dot_fwd, _dot_bwd)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def block(lp: Dict[str, Any], x, n_head: int, eps: float, precision: str):
    """One transformer block on ``x (B, T, D)`` float32."""
    B, T, D = x.shape
    hd = D // n_head
    h = _layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)
    qkv = _dot("btd,de->bte", h, lp["qkv_w"], precision) + lp["qkv_b"]
    q, k, v = (t.reshape(B, T, n_head, hd) for t in jnp.split(qkv, 3, axis=-1))
    s = _dot("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    ctx = _dot("bhqk,bkhd->bqhd", p, v, precision).reshape(B, T, D)
    x = x + _dot("btd,de->bte", ctx, lp["proj_w"], precision) + lp["proj_b"]
    h = _layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)
    h = jax.nn.gelu(_dot("btd,de->bte", h, lp["fc_w"], precision) + lp["fc_b"], approximate=True)
    return x + _dot("btd,de->bte", h, lp["fc_proj_w"], precision) + lp["fc_proj_b"]


def _dims_key(dims: Dict[str, int]) -> Tuple:
    return tuple(sorted((k, v) for k, v in dims.items() if isinstance(v, (int, float))))


@functools.lru_cache(maxsize=None)
def _programs(dims_key: Tuple, precision: str):
    """The jitted pieces for one model size: embed, one block forward,
    head (log-probabilities), and one block's backward."""
    dims = dict(dims_key)
    n_head, eps = dims["n_head"], dims.get("layer_norm_epsilon", 1e-5)

    @jax.jit
    def embed(key, tokens):
        e = weights.embed_params(key, dims)
        return jnp.take(e["wte"], tokens, axis=0) + e["wpe"][: tokens.shape[1]][None]

    @jax.jit
    def layer(key, l, x):
        return block(weights.layer_params(key, l, dims), x, n_head, eps, precision)

    def _logits(key, x):
        e = weights.embed_params(key, dims)
        return _dot("btd,vd->btv", _layer_norm(x, e["lnf_g"], e["lnf_b"], eps), e["wte"], precision)

    logits = jax.jit(_logits)

    def _nll(key, x, tokens):
        lp = jax.nn.log_softmax(_logits(key, x)[:, :-1], axis=-1)
        return -jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]

    nll = jax.jit(_nll)
    # d(mean nll)/dx: the cotangent the last block's backward starts from
    head_grad = jax.jit(jax.grad(lambda x, key, tokens: jnp.mean(_nll(key, x, tokens))))

    @jax.jit
    def layer_bwd(key, l, x, dy):
        lp = weights.layer_params(key, l, dims)
        _, vjp = jax.vjp(lambda lp_, x_: block(lp_, x_, n_head, eps, precision), lp, x)
        dlp, dx = vjp(dy)
        return {n: dlp[n] for n in BLOCK_MATRICES}, dx

    return {"embed": embed, "layer": layer, "logits": logits, "nll": nll,
            "head_grad": head_grad, "layer_bwd": layer_bwd}


class Reference:
    """The reference model of one configuration and seed."""

    def __init__(self, dims: Dict[str, int], seed: int, precision: str = "float32"):
        self.dims = {k: v for k, v in dims.items() if isinstance(v, (int, float))}
        self.key = weights.seed_key(seed)
        self.precision = precision
        self._p = _programs(_dims_key(self.dims), precision)

    def hidden(self, tokens, keep_inputs: bool = False):
        """Final hidden states of ``tokens (B, T)``; with ``keep_inputs``
        also each block's input (what its backward needs)."""
        x = self._p["embed"](self.key, jnp.asarray(tokens, jnp.int32))
        inputs = []
        for l in range(self.dims["n_layer"]):
            if keep_inputs:
                inputs.append(x)
            x = self._p["layer"](self.key, l, x)
        return (x, inputs) if keep_inputs else x

    def logits(self, tokens):
        return self._p["logits"](self.key, self.hidden(tokens))

    def nll(self, tokens):
        """Per-position next-token negative log-probability ``(B, T-1)``."""
        tokens = jnp.asarray(tokens, jnp.int32)
        return self._p["nll"](self.key, self.hidden(tokens), tokens)

    def nll_and_block_grads(self, tokens):
        """``nll (B, T-1)`` and a generator of ``(layer, grads)`` from the
        last block to the first, ``grads`` being d(mean nll)/d(each of the
        block's four weight matrices).  One layer's weights and gradients
        are live at a time."""
        tokens = jnp.asarray(tokens, jnp.int32)
        x, inputs = self.hidden(tokens, keep_inputs=True)
        nll = self._p["nll"](self.key, x, tokens)
        dy = self._p["head_grad"](x, self.key, tokens)

        def sweep(dy=dy):
            for l in reversed(range(self.dims["n_layer"])):
                grads, dy = self._p["layer_bwd"](self.key, l, inputs[l], dy)
                yield l, grads

        return nll, sweep()

    def layer_init(self, l: int) -> Dict[str, Any]:
        return weights.layer_params(self.key, l, self.dims)
