"""Seeded GPT-2 weights, owned by the benchmark.

The program under test and the plain reference are both given weights
made here, from ``--seed`` alone: the program gets the whole stacked
tree (one jitted call, on the device), the reference asks for one
layer at a time.  Layer ``l`` is drawn from ``fold_in(key, l)``, so a
single layer can be made again without the other ``L - 1``.

Init follows GPT-2 (and ``deepspeed_tpu.models.gpt2.init_params``):
normal(0.02), position table normal(0.01), residual projections scaled
by ``1 / sqrt(2 L)``, LayerNorm gains 1, every bias 0.

``dims["kv_outlier"]`` (a configuration's ``weights`` option; absent or
0: none) gives every head one **outlier channel**, as trained
transformers have (Dettmers et al. 2022, "LLM.int8()", section 3): the
key and value biases of each head's first dimension are set to that
value, and the rows of the output projection that read those value
dimensions to 0.  In exact arithmetic nothing changes — a constant added
to every key shifts all of a query's scores alike, which softmax
ignores, and the constant value channel is read by no one — but a KV
store whose precision is a share of each row's largest entry (int8 with
a per-row scale) now rounds the other 63 channels coarsely, while one
with a floating exponent (bf16) does not.  That is what makes the
precision of the pool visible in the served tokens (PERF.md section 2).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

_EMBED_FOLD = 1 << 20  # fold_in tag of the embedding tables, clear of any layer index


def seed_key(seed: int):
    """A raw threefry key from any non-negative whole number (``--seed``
    may exceed 32 signed bits, which ``jax.random.PRNGKey`` refuses
    without x64)."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def layer_params(key, layer, dims: Dict[str, int]) -> Dict[str, Any]:
    """One block's float32 parameters; ``layer`` may be traced."""
    d, n_layer = dims["n_embd"], dims["n_layer"]
    std, proj_std = 0.02, 0.02 / np.sqrt(2 * n_layer)
    ks = jax.random.split(jax.random.fold_in(key, layer), 4)

    def n(k, shape, s):
        return jax.random.normal(k, shape, jnp.float32) * s

    qkv_b, proj_w = jnp.zeros((3 * d,), jnp.float32), n(ks[1], (d, d), proj_std)
    outlier = float(dims.get("kv_outlier", 0.0))
    if outlier:
        first = jnp.arange(dims["n_head"]) * (d // dims["n_head"])  # each head's first dimension
        qkv_b = qkv_b.at[d + first].set(outlier).at[2 * d + first].set(outlier)
        proj_w = proj_w.at[first].set(0.0)
    return {
        "ln1_g": jnp.ones((d,), jnp.float32), "ln1_b": jnp.zeros((d,), jnp.float32),
        "qkv_w": n(ks[0], (d, 3 * d), std), "qkv_b": qkv_b,
        "proj_w": proj_w, "proj_b": jnp.zeros((d,), jnp.float32),
        "ln2_g": jnp.ones((d,), jnp.float32), "ln2_b": jnp.zeros((d,), jnp.float32),
        "fc_w": n(ks[2], (d, 4 * d), std), "fc_b": jnp.zeros((4 * d,), jnp.float32),
        "fc_proj_w": n(ks[3], (4 * d, d), proj_std), "fc_proj_b": jnp.zeros((d,), jnp.float32),
    }


def embed_params(key, dims: Dict[str, int]) -> Dict[str, Any]:
    d = dims["n_embd"]
    k1, k2 = jax.random.split(jax.random.fold_in(key, _EMBED_FOLD))
    return {
        "wte": jax.random.normal(k1, (dims["vocab_size"], d), jnp.float32) * 0.02,
        "wpe": jax.random.normal(k2, (dims["n_positions"], d), jnp.float32) * 0.01,
        "lnf_g": jnp.ones((d,), jnp.float32), "lnf_b": jnp.zeros((d,), jnp.float32),
    }


def stacked_params(seed: int, dims: Dict[str, int], dtype=jnp.float32) -> Dict[str, Any]:
    """The tree ``models/gpt2.py`` takes (blocks stacked on a leading
    layer dim), made on the default device in one jitted call."""

    def build(key):
        blocks = jax.vmap(lambda l: layer_params(key, l, dims))(jnp.arange(dims["n_layer"]))
        tree = {**embed_params(key, dims), "blocks": blocks}
        return jax.tree.map(lambda a: a.astype(dtype), tree)

    return jax.jit(build)(seed_key(seed))
