"""Seeded Solar-Open2 weights, owned by the benchmark.

The program under test and the plain reference are both given weights
made here from ``--seed`` alone, **one block at a time** (the rule of
:mod:`weights_deepseek_v2`): layer ``l`` from ``fold_in(key, l)``, its
mixer, its router + shared expert and routed expert ``e`` from further
``fold_in``s, the embedding and the head in blocks of 128 rows.  Any
share of the experts or of the vocabulary is the same numbers as the
same part of the whole.

``dims`` are the published ``config.json`` keys (``linear_attn_config``
nested as published) plus the share: ``experts_held = [first, count]``
and ``vocab_held``.

Assumed (the source publishes shapes, not an initialisation): every
matrix normal(0.02), the projections that write into the residual stream
(``o``, every ``down``) scaled by ``1 / sqrt(2 L)`` with L the layers
**run**, every RMSNorm gain 1 (the per-head output norm of a KDA layer
too), the router's selection bias 0, the convolution taps normal(0.5) (a
depthwise tap has a fan-in of 4), and the two decay parameters drawn so
that decays are neither 0 nor 1: ``exp(A_log)`` uniform in [1, 16] per
head, ``softplus(dt_bias)`` log-uniform in [0.001, 0.1] per channel — a
channel's memory lasts tens to thousands of tokens.  Matrices are stored
input-dimension first (``x @ W``); gate and up projections are one
matrix, gate columns first; q | k | v of a mixer are one matrix.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .weights import seed_key  # noqa: F401  (re-exported: the one seed -> key rule)
from .weights_deepseek_v2 import _n, _stacked, held, table_rows, vocab_rows  # noqa: F401

STD = 0.02
_MIXER, _SHARED, _EXPERTS = 0, 2, 3


def kda_sizes(dims: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """``(heads, head_dim, conv taps, low rank)`` of a KDA layer."""
    lin = dims["linear_attn_config"]
    return int(lin["num_heads"]), int(lin["head_dim"]), int(lin["short_conv_kernel_size"]), int(lin["head_dim"])


def is_gqa(dims: Dict[str, Any], layer: int) -> bool:
    return layer in dims["gqa_layers"]


def _proj_std(dims) -> float:
    return STD / math.sqrt(2 * dims["num_hidden_layers"])


def gqa_params(key, layer, dims) -> Dict[str, Any]:
    """A gated GQA mixer; ``layer`` may be traced."""
    D, H, Hkv, hd = dims["hidden_size"], dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _MIXER), 3)
    return {"qkv": _n(ks[0], (D, (H + 2 * Hkv) * hd), STD), "gate": _n(ks[1], (D, H * hd), STD),
            "o": _n(ks[2], (H * hd, D), _proj_std(dims))}


def kda_params(key, layer, dims) -> Dict[str, Any]:
    """A KDA mixer; ``layer`` may be traced."""
    D = dims["hidden_size"]
    Hl, dl, taps, r = kda_sizes(dims)
    W = Hl * dl
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _MIXER), 10)
    dt = jnp.exp(jax.random.uniform(ks[3], (W,), jnp.float32, math.log(0.001), math.log(0.1)))
    return {
        "qkv": _n(ks[0], (D, 3 * W), STD), "conv": _n(ks[1], (taps, 3 * W), 0.5),
        "A_log": jnp.log(jax.random.uniform(ks[2], (Hl,), jnp.float32, 1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "a_down": _n(ks[4], (D, r), STD), "a_up": _n(ks[5], (r, W), STD), "beta": _n(ks[6], (D, Hl), STD),
        "g_down": _n(ks[7], (D, r), STD), "g_up": _n(ks[8], (r, W), STD),
        "o_norm": jnp.ones((dl,), jnp.float32), "o": _n(ks[9], (W, D), _proj_std(dims)),
    }


def shared_params(key, layer, dims) -> Dict[str, Any]:
    """A layer's router (all routed experts wide), its selection bias (0) and its shared expert."""
    k1, k2, k3 = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _SHARED), 3)
    D, F = dims["hidden_size"], dims["moe_intermediate_size"] * dims["n_shared_experts"]
    return {"router": _n(k1, (D, dims["n_routed_experts"]), STD),
            "router_bias": jnp.zeros((dims["n_routed_experts"],), jnp.float32),
            "gu": _n(k2, (D, 2 * F), STD), "down": _n(k3, (F, D), _proj_std(dims))}


def expert_params(key, layer, expert, dims) -> Dict[str, Any]:
    """Routed expert ``expert`` (its index among ALL routed experts) of ``layer``."""
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, layer), _EXPERTS), expert))
    D, F = dims["hidden_size"], dims["moe_intermediate_size"]
    return {"gu": _n(k1, (D, 2 * F), STD), "down": _n(k2, (F, D), _proj_std(dims))}


def program_params(seed: int, dims: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree ``deepspeed_tpu.models.solar_open2`` takes, for the share
    ``dims`` states, made on the default device block by block, each
    cast to ``dtype`` as it is made (``A_log``, ``dt_bias`` and the
    router's bias too: the program reads them back into float32)."""
    key = seed_key(seed)  # an argument of each maker: closed over, it is a constant of the program and every seed compiles its own
    first, count = held(dims)
    D = dims["hidden_size"]
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731
    gqa = jax.jit(lambda key, l: cast(gqa_params(key, l, dims)))
    kda = jax.jit(lambda key, l: cast(kda_params(key, l, dims)))

    @jax.jit
    def shared(key, l):
        s = cast(shared_params(key, l, dims))
        return {"router": s["router"], "router_bias": s["router_bias"], "shared_gu": s["gu"], "shared_down": s["down"]}

    @jax.jit
    def expert(key, l, e):
        x = cast(expert_params(key, l, e, dims))
        return {"experts_gu": x["gu"], "experts_down": x["down"]}

    rows = vocab_rows(dims)
    norms = lambda: {"attn_norm": jnp.ones((D,), dtype), "ffn_norm": jnp.ones((D,), dtype)}  # noqa: E731  (a buffer each: the tree is donated)
    tree: Dict[str, Any] = {
        "embed": jax.jit(lambda key: table_rows(key, "embed", rows, dims).astype(dtype))(key),
        "head": jax.jit(lambda key: table_rows(key, "head", rows, dims).astype(dtype))(key),
        "norm_f": jnp.ones((D,), dtype),
    }
    tree["layers"] = [
        {**norms(), **(gqa(key, l) if is_gqa(dims, l) else kda(key, l)), **shared(key, l),
         **_stacked(lambda e, l=l: expert(key, l, first + e), (count,))}
        for l in range(dims["num_hidden_layers"])]
    return tree
