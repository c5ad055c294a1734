"""Seeded Laguna weights, owned by the benchmark.

The program under test and the plain reference are both given weights
made here from ``--seed`` alone, **one block at a time** (the rule of
:mod:`weights_deepseek_v2`): layer ``l`` from ``fold_in(key, l)``, its
mixer, its dense SwiGLU or its router + shared expert, and routed expert
``e`` from further ``fold_in``s, the embedding and the head in blocks of
128 rows.  Any share of the experts or of the vocabulary is the same
numbers as the same part of the whole.

``dims`` are the published ``config.json`` keys (``rope_parameters``
nested as published, the per-layer lists as long as the layers run) plus
the share: ``experts_held = [first, count]`` and ``vocab_held``.

Assumed (the source publishes shapes, not an initialisation): every
matrix normal(0.02) — **the headwise gate's too, not zero**: with a
normed input of 3,072 channels ``h W_gate`` is normal(1.1), so a gate is
anywhere in (0.1, 0.9) and an attention output without its gate is a
different number at every head and position, in every run (at zero every
gate would be one half and a missing gate a factor of two on every head
alike, which the output projection's scale hides from nothing, but the
control would read the same in every seed); the router zero-mean, so that
the 32 of 256 experts held here are sent an eighth of the assignments in
expectation and no expert is favoured before the input speaks; the
projections that write into the residual stream (``o``, every ``down``)
scaled by ``1 / sqrt(2 L)`` with L the layers **run**; every RMSNorm gain
1.  Matrices are stored input-dimension first (``x @ W``); gate and up
projections of a SwiGLU are one matrix, gate columns first; ``W_q | W_k |
W_v`` of a mixer are one matrix, as wide as the layer's heads.

What ``correct`` then rests on: with weights of this scale the logits of
the held vocabulary rows are normal(~1), the top two a rounding error
apart at many positions — which is why tokens are not compared and the
reference's logit gap of the emitted token is (``checks.py``) — and the
attention of every layer, the gate and the experts each move the logits
by more than bf16 rounding does, which is what the controls of
``control_laguna.py`` show on the chip.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .weights import seed_key  # noqa: F401  (re-exported: the one seed -> key rule)
from .weights_deepseek_v2 import _n, _stacked, table_rows  # noqa: F401

STD = 0.02
_MIXER, _MLP, _SHARED, _EXPERTS = 0, 1, 2, 3


def held(dims: Dict[str, Any]) -> Tuple[int, int]:
    first, count = dims.get("experts_held") or (0, dims["num_experts"])
    return int(first), int(count)


def vocab_rows(dims: Dict[str, Any]) -> int:
    return int(dims.get("vocab_held") or dims["vocab_size"])


def heads_of(dims: Dict[str, Any], layer: int) -> int:
    return int(dims["num_attention_heads_per_layer"][layer])


def is_sliding(dims: Dict[str, Any], layer: int) -> bool:
    return dims["layer_types"][layer] == "sliding_attention"


def is_dense(dims: Dict[str, Any], layer: int) -> bool:
    return dims["mlp_layer_types"][layer] == "dense"


def _proj_std(dims) -> float:
    return STD / math.sqrt(2 * dims["num_hidden_layers"])


def mixer_params(key, layer: int, dims) -> Dict[str, Any]:
    """Layer ``layer``'s attention: ``qkv``, the headwise ``gate`` and ``o``, as wide as the layer's heads (``layer`` a Python int)."""
    D, H, Hkv, hd = dims["hidden_size"], heads_of(dims, layer), dims["num_key_value_heads"], dims["head_dim"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _MIXER), 3)
    return {"qkv": _n(ks[0], (D, (H + 2 * Hkv) * hd), STD), "gate": _n(ks[1], (D, H), STD), "o": _n(ks[2], (H * hd, D), _proj_std(dims))}


def _swiglu_params(k, D: int, F: int, dims) -> Dict[str, Any]:
    k1, k2 = jax.random.split(k)
    return {"gu": _n(k1, (D, 2 * F), STD), "down": _n(k2, (F, D), _proj_std(dims))}


def dense_mlp_params(key, layer, dims) -> Dict[str, Any]:
    return _swiglu_params(jax.random.fold_in(jax.random.fold_in(key, layer), _MLP), dims["hidden_size"], dims["intermediate_size"], dims)


def shared_params(key, layer, dims) -> Dict[str, Any]:
    """A sparse layer's router (all experts wide) and its shared expert."""
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _SHARED))
    return {"router": _n(k1, (dims["hidden_size"], dims["num_experts"]), STD),
            **_swiglu_params(k2, dims["hidden_size"], dims["shared_expert_intermediate_size"], dims)}


def expert_params(key, layer, expert, dims) -> Dict[str, Any]:
    """Routed expert ``expert`` (its index among ALL experts) of ``layer``."""
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, layer), _EXPERTS), expert)
    return _swiglu_params(k, dims["hidden_size"], dims["moe_intermediate_size"], dims)


def program_params(seed: int, dims: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree ``deepspeed_tpu.models.laguna`` takes, for the share
    ``dims`` states, made on the default device block by block (a
    layer's mixer, one expert), each cast to ``dtype`` as it is made."""
    key = seed_key(seed)  # an argument of each maker: closed over, it is a constant of the program and every seed compiles its own
    first, count = held(dims)
    D = dims["hidden_size"]
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731
    mixer = jax.jit(lambda key, l: cast(mixer_params(key, l, dims)), static_argnums=1)  # two widths: two programs

    @jax.jit
    def dense(key, l):
        m = cast(dense_mlp_params(key, l, dims))
        return {"mlp_gu": m["gu"], "mlp_down": m["down"]}

    @jax.jit
    def shared(key, l):
        s = cast(shared_params(key, l, dims))
        return {"router": s["router"], "shared_gu": s["gu"], "shared_down": s["down"]}

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
        {**norms(), **mixer(key, l),
         **(dense(key, l) if is_dense(dims, l) else {**shared(key, l), **_stacked(lambda e, l=l: expert(key, l, first + e), (count,))})}
        for l in range(dims["num_hidden_layers"])]
    return tree
