"""Seeded ZAYA1 weights, owned by the benchmark.

The program under test and the plain reference are both given weights
made here from ``--seed`` alone, **one block at a time** (the rule of
:mod:`weights_deepseek_v2`): layer ``l`` from ``fold_in(key, l)``, its
attention, its router + residual vectors and expert ``e`` from further
``fold_in``s, the tied embedding in blocks of 128 rows.  Any share of the
experts or of the vocabulary is the same numbers as the same part of the
whole.

``dims`` are the published ``config.json`` keys plus the share:
``experts_held = [first, count]`` (default: all ``num_experts``) and the
vocabulary as run (a sliced vocabulary is a smaller one).

Assumed (the source publishes shapes, not an initialisation; the
configuration file repeats this under ``assumed.weights``): every
projection normal(0.02), **the projections into the residual stream
(``o``, every ``down``) too** — the head is tied, and a residual stream
that stays near the embedding makes every position predict its own input
token by a margin no rounding moves; at 0.02 the layers' outputs outgrow
the embedding within a few layers, as in a trained model.  Every RMSNorm
gain 1; the residual vectors near the identity and not at it (scales ``1
+ normal(0.05)``, shifts ``normal(0.002)``); the depth averaging
coefficient uniform in [0.25, 0.75] a layer; the router's selection bias 0.

Three choices make the seeded model **one that a serving precision can
be judged on**, as a trained model is (each read on the CPU at the
published widths with ``reference_zaya1.py`` in float32 against the same
with bf16 and int8 operands, and on the chip: PERF.md §6, PR 39):

* *the temperature on k uniform in* ``TAU`` *= [3, 4] a KV head and the
  convolutions' taps a little under unit gain* (depthwise normal(0.45),
  grouped normal(0.9 fan_in^-1/2)).  At a temperature of 1 a softmax over
  thousands of random keys is their average, every position's output the
  same mean value, and twenty layers of it make all positions' hidden
  states one vector (cosine 0.997) and the router send a whole batch to
  one expert: attention has to be **peaked**.  But peaked attention over
  thousands of *random* keys is decided by which of a few near-tied keys
  wins: at [4, 8] and unit-gain taps (the first seeding) a bf16 rounding
  upstream grew ~1.3 x a layer — the program's K rows stood 0.6 from the
  float32 reference's at layer 19 at 4–6 k of context and the int8
  control's 0.9: one no longer told from the other.  What a trained
  model has and random keys lack is *structure*: here the q-k mean gives
  a position's own key a cosine of ~0.43 with its query, so at [3, 4]
  about five sixths of the attention mass falls on the position itself
  with a margin no rounding moves, and one sixth (more as the context
  grows) on ~10 far keys: far pages still decide a sixth of every
  layer's output, and the error of a rounding stays where it was made
  (bf16: K rows 0.003 at layer 0, 0.018 at layer 19);
* *the router's two later matrices* (``router_w2``, ``router_w3``)
  *have zero mean over their fan-in*.  GELU's output has a positive
  mean; through a random matrix that mean is a token-independent
  preference for some experts: the first seeding sent up to 42 % of all
  tokens to one expert of 16 and none to others (fullest / mean 2.2–6.8
  by layer), where the deployment's balancing bias keeps a trained
  router even.  Centred, the fullest expert takes 1.3–2.0 x the mean and
  the emptiest 0.4–0.7 x, so a decode step of 64 rows touches nearly
  every held expert whatever the seed.

Matrices are stored input-dimension first (``x @
W``); gate and up projections are one matrix, gate columns first; ``W_q |
W_k | W_v1 | W_v2`` are one matrix.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .weights import seed_key  # the one seed -> key rule (the reference reads it from here)
from .weights_deepseek_v2 import _n, _stacked, table_rows

STD = 0.02
TAU = (3.0, 4.0)  # the temperature on k, uniform a KV head
CONV0_STD, CONV1_GAIN = 0.45, 0.9  # the depthwise taps' std; the grouped taps' std over fan_in^-1/2
_CCA, _ROUTER, _EXPERTS = 0, 2, 3


def held(dims: Dict[str, Any]) -> Tuple[int, int]:
    first, count = dims.get("experts_held") or (0, dims["num_experts"])
    return int(first), int(count)


def cca_sizes(dims: Dict[str, Any]) -> Tuple[int, int, int, int, float]:
    """``(heads, kv heads, head_dim, rotary dims, rope theta)``."""
    d = int(dims["head_dim"])
    theta = float(dims.get("rope_theta") or dims["rope_parameters"]["hybrid"]["rope_theta"])
    return int(dims["num_attention_heads"]), int(dims["num_key_value_heads"]), d, int(d * dims["partial_rotary_factor"]), theta


def cca_params(key, layer, dims) -> Dict[str, Any]:
    """A layer's compressed convolutional attention; ``layer`` may be traced."""
    D = dims["hidden_size"]
    H, Hkv, d, _, _ = cca_sizes(dims)
    C, S = (H + Hkv) * d, Hkv // 2 * d
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _CCA), 5)
    return {"qkv": _n(ks[0], (D, C + 2 * S), STD), "conv0": _n(ks[1], (dims["cca_time0"], C), CONV0_STD),
            "conv1": _n(ks[2], (dims["cca_time1"], H + Hkv, d, d), CONV1_GAIN * (dims["cca_time1"] * d) ** -0.5),
            "tau": jax.random.uniform(ks[3], (Hkv,), jnp.float32, *TAU), "o": _n(ks[4], (H * d, D), STD)}


def router_params(key, layer, dims) -> Dict[str, Any]:
    """A layer's router (all experts wide) and the residual vectors of its two sublayers."""
    D, R, E = dims["hidden_size"], dims["router_hidden_size"], dims["num_experts"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _ROUTER), 7)
    res = lambda k: (lambda n: jnp.stack([1.0 + 0.05 * n[0], 0.002 * n[1], 1.0 + 0.05 * n[2], 0.002 * n[3]]))(  # noqa: E731
        jax.random.normal(k, (4, D), jnp.float32))
    centred = lambda w: w - jnp.mean(w, axis=0, keepdims=True)  # noqa: E731  (zero mean over the fan-in: see the module's docstring)
    return {"router_down": _n(ks[0], (D, R), STD), "router_gamma": jax.random.uniform(ks[1], (), jnp.float32, 0.25, 0.75),
            "router_norm": jnp.ones((R,), jnp.float32), "router_w1": _n(ks[2], (R, R), R ** -0.5),
            "router_w2": centred(_n(ks[3], (R, R), R ** -0.5)), "router_w3": centred(_n(ks[4], (R, E), R ** -0.5)),
            "router_bias": jnp.zeros((E,), jnp.float32), "res_attn": res(ks[5]), "res_moe": res(ks[6])}


def expert_params(key, layer, expert, dims) -> Dict[str, Any]:
    """Expert ``expert`` (its index among ALL experts) of ``layer``."""
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, layer), _EXPERTS), expert))
    D, F = dims["hidden_size"], dims["moe_intermediate_size"]
    return {"gu": _n(k1, (D, 2 * F), STD), "down": _n(k2, (F, D), STD)}


def embedding(key, dims) -> jnp.ndarray:
    """The rows held of the tied embedding, float32."""
    return table_rows(key, "embed", int(dims["vocab_size"]), dims)


def program_params(seed: int, dims: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree ``deepspeed_tpu.models.zaya`` takes, for the share
    ``dims`` states, made on the default device block by block, each cast
    to ``dtype`` as it is made."""
    key = seed_key(seed)  # an argument of each maker: closed over, it is a constant of the program and every seed compiles its own
    first, count = held(dims)
    D = dims["hidden_size"]
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731
    cca = jax.jit(lambda key, l: cast(cca_params(key, l, dims)))
    router = jax.jit(lambda key, l: cast(router_params(key, l, dims)))

    @jax.jit
    def expert(key, l, e):
        x = cast(expert_params(key, l, e, dims))
        return {"experts_gu": x["gu"], "experts_down": x["down"]}

    norms = lambda: {"attn_norm": jnp.ones((D,), dtype), "ffn_norm": jnp.ones((D,), dtype)}  # noqa: E731  (a buffer each: the tree is donated)
    tree: Dict[str, Any] = {"embed": jax.jit(lambda key: embedding(key, dims).astype(dtype))(key), "norm_f": jnp.ones((D,), dtype)}
    tree["layers"] = [{**norms(), **cca(key, l), **router(key, l), **_stacked(lambda e, l=l: expert(key, l, first + e), (count,))}
                      for l in range(dims["num_hidden_layers"])]
    return tree
