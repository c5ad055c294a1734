"""Seeded MiMo-V2-Flash weights, owned by the benchmark.

The program under test and the plain reference are both given weights
made here from ``--seed`` alone, **one block at a time** (the rule of
:mod:`weights_deepseek_v2`): layer ``l`` from ``fold_in(key, l)``, its
mixer, its dense SwiGLU or its router, and routed expert ``e`` from
further ``fold_in``s, the embedding and the head in blocks of 128 rows.
Any share of the experts or of the vocabulary is the same numbers as the
same part of the whole.

``dims`` are the published ``config.json`` keys (the per-layer lists as
long as the layers run) plus the share: ``experts_held = [first, count]``
and ``vocab_held``.

Assumed (the source publishes shapes, not an initialisation): every
matrix normal(0.02); the projections that write into the residual stream
(``o``, every ``down``) scaled by ``1 / sqrt(2 L)`` with L the layers
**run**; every RMSNorm gain 1; the router zero-mean, so that the 16 of
256 experts held here are sent a sixteenth of the assignments in
expectation.  Matrices are stored input-dimension first (``x @ W``);
gate and up projections of a SwiGLU are one matrix, gate columns first;
``W_q | W_k | W_v`` of a mixer are one matrix (``H dk + Hkv dk + Hkv dv``
columns: the kinds differ in width).

**The sinks are drawn so that they matter.**  With a normed input of
``D`` channels a score ``q . k / sqrt(dk)`` is normal(``sigma = 0.02^2
D``: 1.64 at 4,096), so a full window's denominator is ``window x
exp(sigma^2 / 2)`` on the mean (490 at 128 positions).  A head's sink is
``b_h = ln(window) + sigma^2 / 2 + logit(r_h)`` with ``r_h`` uniform in
(0.15, 0.45): **the sink takes 0.1-0.5 of a window row's mass on the
mean** — a row whose window is not full yet gives it more — so an
attention that leaves the sink out is a different number at every head
and position, in every run (at ``b_h = 0`` the column would be 1 part in
490 and a missing sink under bf16 rounding).  ``e_bias`` is normal(0.02):
non-zero, and small against the spread of the sigmoid scores (~0.25) — it
moves the eighth choice at about one position in ten and never a weight.
Both are **rounded to bfloat16 here**, so that the program (whose weights
are bf16) and the reference (float32) read the same number: a selection
bias that differs in its last bits would move choices for no reason of
the program's.

What ``correct`` then rests on: with weights of this scale the logits of
the held vocabulary rows are normal(~1), the top two a rounding error
apart at many positions — which is why tokens are not compared and the
reference's logit gap of the emitted token is (``checks.py``) — and the
attention of every layer, the sink, the value scale and the experts each
move the logits by more than bf16 rounding does, which is what the
controls of ``control_mimo.py`` show on the chip.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .weights import seed_key  # noqa: F401  (re-exported: the one seed -> key rule)
from .weights_deepseek_v2 import _n, _stacked, table_rows  # noqa: F401

STD = 0.02
_MIXER, _MLP, _ROUTER, _EXPERTS = 0, 1, 2, 3


def held(dims: Dict[str, Any]) -> Tuple[int, int]:
    first, count = dims.get("experts_held") or (0, dims["n_routed_experts"])
    return int(first), int(count)


def vocab_rows(dims: Dict[str, Any]) -> int:
    return int(dims.get("vocab_held") or dims["vocab_size"])


def is_window(dims: Dict[str, Any], layer: int) -> bool:
    return int(dims["hybrid_layer_pattern"][layer]) == 1


def is_dense(dims: Dict[str, Any], layer: int) -> bool:
    return int(dims["moe_layer_freq"][layer]) == 0


def geometry(dims: Dict[str, Any], layer: int) -> Tuple[int, int, int, int]:
    """``(query heads, KV heads, key width, value width)`` of layer ``layer``'s kind."""
    pre = "swa_" if is_window(dims, layer) else ""
    return tuple(int(dims[pre + k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim", "v_head_dim"))


def has_sink(dims: Dict[str, Any], layer: int) -> bool:
    return bool(dims["add_swa_attention_sink_bias" if is_window(dims, layer) else "add_full_attention_sink_bias"])


def _proj_std(dims) -> float:
    return STD / math.sqrt(2 * dims["num_hidden_layers"])


def _bf16_exact(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def mixer_params(key, layer: int, dims) -> Dict[str, Any]:
    """Layer ``layer``'s attention: ``qkv``, ``o`` and — a kind with sinks — ``sink (H,)`` (``layer`` a Python int)."""
    D = dims["hidden_size"]
    H, Hkv, dk, dv = geometry(dims, layer)
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _MIXER), 3)
    out = {"qkv": _n(ks[0], (D, (H + Hkv) * dk + Hkv * dv), STD), "o": _n(ks[1], (H * dv, D), _proj_std(dims))}
    if has_sink(dims, layer):
        r = jax.random.uniform(ks[2], (H,), jnp.float32, 0.15, 0.45)
        sigma = STD * STD * D
        out["sink"] = _bf16_exact(math.log(dims["sliding_window"]) + sigma * sigma / 2 + jnp.log(r / (1 - r)))
    return out


def _swiglu_params(k, D: int, F: int, dims) -> Dict[str, Any]:
    k1, k2 = jax.random.split(k)
    return {"gu": _n(k1, (D, 2 * F), STD), "down": _n(k2, (F, D), _proj_std(dims))}


def dense_mlp_params(key, layer, dims) -> Dict[str, Any]:
    return _swiglu_params(jax.random.fold_in(jax.random.fold_in(key, layer), _MLP), dims["hidden_size"], dims["intermediate_size"], dims)


def router_params(key, layer, dims) -> Dict[str, Any]:
    """A sparse layer's router (all experts wide) and its selection bias ``e_bias``."""
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _ROUTER))
    E = dims["n_routed_experts"]
    return {"router": _n(k1, (dims["hidden_size"], E), STD), "router_bias": _bf16_exact(_n(k2, (E,), STD))}


def expert_params(key, layer, expert, dims) -> Dict[str, Any]:
    """Routed expert ``expert`` (its index among ALL experts) of ``layer``."""
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, layer), _EXPERTS), expert)
    return _swiglu_params(k, dims["hidden_size"], dims["moe_intermediate_size"], dims)


def program_params(seed: int, dims: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree ``deepspeed_tpu.models.mimo_v2`` takes, for the share
    ``dims`` states, made on the default device block by block (a
    layer's mixer, one expert), each cast to ``dtype`` as it is made."""
    key = seed_key(seed)  # an argument of each maker: closed over, it is a constant of the program and every seed compiles its own
    first, count = held(dims)
    D = dims["hidden_size"]
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731
    mixer = jax.jit(lambda key, l: cast(mixer_params(key, l, dims)), static_argnums=1)  # two kinds: two programs

    @jax.jit
    def dense(key, l):
        m = cast(dense_mlp_params(key, l, dims))
        return {"mlp_gu": m["gu"], "mlp_down": m["down"]}

    router = jax.jit(lambda key, l: cast(router_params(key, l, dims)))

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
         **(dense(key, l) if is_dense(dims, l) else {**router(key, l), **_stacked(lambda e, l=l: expert(key, l, first + e), (count,))})}
        for l in range(dims["num_hidden_layers"])]
    return tree
