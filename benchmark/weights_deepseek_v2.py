"""Seeded DeepSeek-V2 weights, owned by the benchmark.

The program under test and the plain reference are both given weights
made here from ``--seed`` alone, **one block at a time**: layer ``l``
is drawn from ``fold_in(key, l)``, routed expert ``e`` of it from a
further ``fold_in`` of ``e``, the embedding and the head in blocks of
128 rows.  So a block can be made again without the others, any share
of the experts or of the vocabulary is the same numbers as the same
part of the whole, and neither side ever holds a float32 copy of more
than a block (one expert layer at the published widths is 4.6 GB in
float32; the program's 10.3 GB of bfloat16 are on the chip meanwhile).

``dims`` are the published ``config.json`` keys plus the share:
``experts_held = [first, count]`` (default: all) and ``vocab_held``
(default: all rows).

Assumed (the source publishes shapes, not an initialisation for
benchmarks): every matrix normal(0.02), the projections that write
into the residual stream (``o``, every ``down``) scaled by
``1 / sqrt(2 L)`` with L the layers **run**, every RMSNorm gain 1.
Matrices are stored input-dimension first (``x @ W``); gate and up
projections are one matrix, gate columns first.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .weights import seed_key  # noqa: F401  (re-exported: the one seed -> key rule)

STD = 0.02
ROW_BLOCK = 128  # embedding / head rows per fold_in
_EMBED_FOLD, _HEAD_FOLD = 1 << 20, (1 << 20) + 1
_ATTN, _MLP, _SHARED, _EXPERTS = 0, 1, 2, 3


def held(dims: Dict[str, Any]) -> Tuple[int, int]:
    first, count = dims.get("experts_held") or (0, dims["n_routed_experts"])
    return int(first), int(count)


def vocab_rows(dims: Dict[str, Any]) -> int:
    return int(dims.get("vocab_held") or dims["vocab_size"])


def _proj_std(dims) -> float:
    return STD / math.sqrt(2 * dims["num_hidden_layers"])


def _n(k, shape, s):
    return jax.random.normal(k, shape, jnp.float32) * s


def norm_gains(dims) -> Dict[str, Any]:
    """A layer's four RMSNorm gains: all 1 (no key, no layer: nothing is drawn)."""
    one = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    return {"attn_norm": one(dims["hidden_size"]), "ffn_norm": one(dims["hidden_size"]),
            "q_a_norm": one(dims["q_lora_rank"]), "kv_a_norm": one(dims["kv_lora_rank"])}


def attn_params(key, layer, dims) -> Dict[str, Any]:
    """One layer's latent attention and the layer's norms; ``layer`` may be traced."""
    D, H = dims["hidden_size"], dims["num_attention_heads"]
    qk = dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
    c, r = dims["kv_lora_rank"], dims["qk_rope_head_dim"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _ATTN), 5)
    return {
        **norm_gains(dims),
        "q_a": _n(ks[0], (D, dims["q_lora_rank"]), STD),
        "q_b": _n(ks[1], (dims["q_lora_rank"], H * qk), STD),
        "kv_a": _n(ks[2], (D, c + r), STD),
        "kv_b": _n(ks[3], (c, H * (dims["qk_nope_head_dim"] + dims["v_head_dim"])), STD),
        "o": _n(ks[4], (H * dims["v_head_dim"], D), _proj_std(dims)),
    }


def _swiglu_params(k, D: int, F: int, dims) -> Dict[str, Any]:
    k1, k2 = jax.random.split(k)
    return {"gu": _n(k1, (D, 2 * F), STD), "down": _n(k2, (F, D), _proj_std(dims))}


def dense_mlp_params(key, layer, dims) -> Dict[str, Any]:
    return _swiglu_params(jax.random.fold_in(jax.random.fold_in(key, layer), _MLP),
                          dims["hidden_size"], dims["intermediate_size"], dims)


def shared_params(key, layer, dims) -> Dict[str, Any]:
    """An expert layer's router (all routed experts wide) and its shared experts (one SwiGLU)."""
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _SHARED))
    width = dims["moe_intermediate_size"] * dims["n_shared_experts"]
    return {"router": _n(k1, (dims["hidden_size"], dims["n_routed_experts"]), STD),
            **_swiglu_params(k2, dims["hidden_size"], width, dims)}


def expert_params(key, layer, expert, dims) -> Dict[str, Any]:
    """Routed expert ``expert`` (its index among ALL routed experts) of ``layer``."""
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, layer), _EXPERTS), expert)
    return _swiglu_params(k, dims["hidden_size"], dims["moe_intermediate_size"], dims)


def table_rows(key, which: str, rows: int, dims) -> jnp.ndarray:
    """The first ``rows`` rows of the embedding (``"embed"``) or the untied head (``"head"``)."""
    k = jax.random.fold_in(key, _EMBED_FOLD if which == "embed" else _HEAD_FOLD)
    blocks = -(-rows // ROW_BLOCK)
    t = jax.vmap(lambda b: _n(jax.random.fold_in(k, b), (ROW_BLOCK, dims["hidden_size"]), STD))(jnp.arange(blocks))
    return t.reshape(blocks * ROW_BLOCK, dims["hidden_size"])[:rows]


def _stacked(make, lead: Tuple[int, ...]) -> Dict[str, Any]:
    """``make(*index)`` blocks (dicts of arrays) for every index of
    ``lead``, stacked on new leading dims — written in place into
    buffers made once, so no second copy of the stack ever exists."""
    import itertools

    put = jax.jit(lambda bufs, block, idx: {k: bufs[k].at[tuple(idx)].set(block[k]) for k in bufs}, donate_argnums=0)
    bufs = None
    for idx in itertools.product(*(range(n) for n in lead)):
        block = make(*idx)
        if bufs is None:
            bufs = {k: jnp.zeros(lead + v.shape, v.dtype) for k, v in block.items()}
        bufs = put(bufs, block, jnp.asarray(idx, jnp.int32))
    return bufs


def program_params(seed: int, dims: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree ``deepspeed_tpu.models.deepseek_v2`` takes, for the share
    ``dims`` states, made on the default device block by block (a layer's
    attention, one expert), each cast to ``dtype`` as it is made."""
    key = seed_key(seed)  # an argument of each maker: closed over, it is a constant of the program and every seed compiles its own
    first, count = held(dims)
    n_dense, n_layers = dims["first_k_dense_replace"], dims["num_hidden_layers"]
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731
    attn = jax.jit(lambda key, l: cast(attn_params(key, l, dims)))

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
    tree: Dict[str, Any] = {
        "embed": jax.jit(lambda key: table_rows(key, "embed", rows, dims).astype(dtype))(key),
        "head": jax.jit(lambda key: table_rows(key, "head", rows, dims).astype(dtype))(key),
        "norm_f": jnp.ones((dims["hidden_size"],), dtype),
    }
    tree["layers"] = [{**attn(key, l), **dense(key, l)} for l in range(n_dense)] + [
        {**attn(key, l), **shared(key, l), **_stacked(lambda e, l=l: expert(key, l, first + e), (count,))}
        for l in range(n_dense, n_layers)]
    return tree
