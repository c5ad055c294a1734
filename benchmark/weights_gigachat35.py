"""Seeded GigaChat3.5 weights, owned by the benchmark.

The program under test and the plain reference are both given weights
made here from ``--seed`` alone, **one block at a time** (the rule of
:mod:`weights_deepseek_v2`): layer ``l`` from ``fold_in(key, l)``, its
mixer, its four norm gains, its dense SwiGLU or its router + shared
expert and routed expert ``e`` from further ``fold_in``s, the embedding
and the head in blocks of 128 rows.  Any share of the experts or of the
vocabulary is the same numbers as the same part of the whole.

``dims`` are the published ``config.json`` keys (``rope_scaling`` nested
as published) plus the share: ``experts_held = [first, count]`` and
``vocab_held``.

Assumed (the source publishes shapes, not an initialisation): every
matrix normal(0.02), the projections that write into the residual stream
(``o``, every ``down``) scaled by ``1 / sqrt(2 L)`` with L the layers
**run**; **every gain vector of the zero-centred gated norm ``N`` (four a
layer and the final one) and the delta rule's output-norm gain ``w_n``
normal(0, 0.5), not 0** — at 0 the two readings of the norm's name, ``2
sigmoid(w)`` and ``1 + w``, are the same function, and what is compared
would not be what is implemented; the two plain RMSNorm gains inside
latent attention 1; the router's selection bias 0; the convolution taps
normal(0.5) (a depthwise tap has a fan-in of 4); and the two decay
parameters drawn so that a head's decay is neither 0 nor 1
(Solar-Open2's rule): ``exp(A_log)`` uniform in [1, 16] and
``softplus(dt_bias)`` log-uniform in [0.001, 0.1], both a value head.
Matrices are stored input-dimension first (``x @ W``); gate and up
projections are one matrix, gate columns first; q | k | v of a
delta-rule mixer are one matrix.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import seed_key  # noqa: F401  (re-exported: the one seed -> key rule)
from .weights_deepseek_v2 import _n, _proj_std, _stacked, _swiglu_params, held, table_rows, vocab_rows  # noqa: F401

STD = 0.02
GAIN_STD = 0.5
_MIXER, _MLP, _SHARED, _EXPERTS, _NORMS = 0, 1, 2, 3, 4
_FINAL_FOLD = (1 << 20) + 2
NORMS = ("mixer_in_w", "mixer_out_w", "ffn_in_w", "ffn_out_w")


def is_latent(dims: Dict[str, Any], layer: int) -> bool:
    return layer in dims["full_attention_layers"]


def is_dense(dims: Dict[str, Any], layer: int) -> bool:
    return layer < dims["first_k_dense_replace"]


def linear_layers(dims: Dict[str, Any]):
    return [l for l in range(dims["num_hidden_layers"]) if not is_latent(dims, l)]


def gdn_sizes(dims: Dict[str, Any]):
    """``(key heads, value heads, dk, dv, conv taps)`` of a delta-rule layer."""
    return (int(dims["linear_num_key_heads"]), int(dims["linear_num_value_heads"]), int(dims["linear_key_head_dim"]),
            int(dims["linear_value_head_dim"]), int(dims["linear_conv_kernel_dim"]))


def norm_params(key, layer, dims) -> Dict[str, Any]:
    """A layer's four gains of ``N``; ``layer`` may be traced."""
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _NORMS), len(NORMS))
    return {name: _n(k, (dims["hidden_size"],), GAIN_STD) for name, k in zip(NORMS, ks)}


def final_gain(key, dims):
    return _n(jax.random.fold_in(key, _FINAL_FOLD), (dims["hidden_size"],), GAIN_STD)


def mla_params(key, layer, dims) -> Dict[str, Any]:
    """A gated latent-attention mixer; ``layer`` may be traced."""
    D, H = dims["hidden_size"], dims["num_attention_heads"]
    dn, dr, dv, c, cq = (dims["qk_nope_head_dim"], dims["qk_rope_head_dim"], dims["v_head_dim"], dims["kv_lora_rank"],
                         dims["q_lora_rank"])
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _MIXER), 6)
    return {"q_a": _n(ks[0], (D, cq), STD), "q_a_norm": jnp.ones((cq,), jnp.float32), "q_b": _n(ks[1], (cq, H * (dn + dr)), STD),
            "kv_a": _n(ks[2], (D, c + dr), STD), "kv_a_norm": jnp.ones((c,), jnp.float32),
            "kv_b": _n(ks[3], (c, H * (dn + dv)), STD), "gate": _n(ks[4], (D, H * dv), STD),
            "o": _n(ks[5], (H * dv, D), _proj_std(dims))}


def gdn_params(key, layer, dims) -> Dict[str, Any]:
    """A gated-delta-rule mixer; ``layer`` may be traced."""
    D = dims["hidden_size"]
    Hk, Hv, dk, dv, taps = gdn_sizes(dims)
    C = 2 * Hk * dk + Hv * dv
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _MIXER), 9)
    dt = jnp.exp(jax.random.uniform(ks[3], (Hv,), jnp.float32, math.log(0.001), math.log(0.1)))
    return {"qkv": _n(ks[0], (D, C), STD), "conv": _n(ks[1], (taps, C), 0.5),
            "A_log": jnp.log(jax.random.uniform(ks[2], (Hv,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "a": _n(ks[4], (D, Hv), STD), "b": _n(ks[5], (D, Hv), STD), "z": _n(ks[6], (D, Hv * dv), STD),
            "o_norm_w": _n(ks[7], (dv,), GAIN_STD), "o": _n(ks[8], (Hv * dv, D), _proj_std(dims))}


def dense_mlp_params(key, layer, dims) -> Dict[str, Any]:
    return _swiglu_params(jax.random.fold_in(jax.random.fold_in(key, layer), _MLP),
                          dims["hidden_size"], dims["intermediate_size"], dims)


def shared_params(key, layer, dims) -> Dict[str, Any]:
    """An expert layer's router (all routed experts wide), its selection bias (0) and its shared expert."""
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _SHARED))
    width = dims["moe_intermediate_size"] * dims["n_shared_experts"]
    return {"router": _n(k1, (dims["hidden_size"], dims["n_routed_experts"]), STD),
            "router_bias": jnp.zeros((dims["n_routed_experts"],), jnp.float32),
            **_swiglu_params(k2, dims["hidden_size"], width, dims)}


def expert_params(key, layer, expert, dims) -> Dict[str, Any]:
    """Routed expert ``expert`` (its index among ALL routed experts) of ``layer``."""
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, layer), _EXPERTS), expert)
    return _swiglu_params(k, dims["hidden_size"], dims["moe_intermediate_size"], dims)


def program_params(seed: int, dims: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree ``deepspeed_tpu.models.gigachat35`` takes, for the share
    ``dims`` states, made on the default device block by block, each cast
    to ``dtype`` as it is made (the gains, ``A_log``, ``dt_bias`` and the
    router's bias too: the program reads them back into float32)."""
    key = seed_key(seed)  # an argument of each maker: closed over, it is a constant of the program and every seed compiles its own
    first, count = held(dims)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731
    norms = jax.jit(lambda key, l: cast(norm_params(key, l, dims)))
    mla = jax.jit(lambda key, l: cast(mla_params(key, l, dims)))
    gdn = jax.jit(lambda key, l: cast(gdn_params(key, l, dims)))

    @jax.jit
    def dense(key, l):
        m = cast(dense_mlp_params(key, l, dims))
        return {"mlp_gu": m["gu"], "mlp_down": m["down"]}

    @jax.jit
    def shared(key, l):
        s = cast(shared_params(key, l, dims))
        return {"router": s["router"], "router_bias": s["router_bias"], "shared_gu": s["gu"], "shared_down": s["down"]}

    @jax.jit
    def expert(key, l, e):
        x = cast(expert_params(key, l, e, dims))
        return {"experts_gu": x["gu"], "experts_down": x["down"]}

    def ffn(l):
        if is_dense(dims, l):
            return dense(key, l)
        return {**shared(key, l), **_stacked(lambda e, l=l: expert(key, l, first + e), (count,))}

    rows = vocab_rows(dims)
    tree: Dict[str, Any] = {
        "embed": jax.jit(lambda key: table_rows(key, "embed", rows, dims).astype(dtype))(key),
        "head": jax.jit(lambda key: table_rows(key, "head", rows, dims).astype(dtype))(key),
        "final_w": jax.jit(lambda key: final_gain(key, dims).astype(dtype))(key),
    }
    tree["layers"] = [{**norms(key, l), **(mla(key, l) if is_latent(dims, l) else gdn(key, l)), **ffn(l)}
                      for l in range(dims["num_hidden_layers"])]
    return tree
