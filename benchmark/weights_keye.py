"""Seeded weights of Keye-VL-2.0's language model, owned by the benchmark.

The program under test and the plain reference are both given weights
made here from ``--seed`` alone, **one block at a time** (the rule of
:mod:`weights_deepseek_v2`): layer ``l`` from ``fold_in(key, l)``, its
attention + indexer, its router and expert ``e`` from further
``fold_in``s, embedding and untied head in blocks of 128 rows.  Any share
of the experts or of the vocabulary is the same numbers as the same part
of the whole.

``dims`` are the published ``config.json`` keys (``sa_config`` nested as
published) plus the share: ``experts_held = [first, count]`` (default:
all ``num_experts``) and the vocabulary as run.

Assumed (the source publishes shapes, not an initialisation; the
configuration file repeats this under ``assumed.weights``): every
projection normal(0.02) but the three below; every hidden-size RMSNorm
gain 1; the indexer key's LayerNorm gain 1 and bias 0.  Three choices make
the seeded model **one whose selection and serving precision can be
judged**, as a trained model is (each read on the chip at the published
widths, a 12,288-token prefill of the bf16 program against
``reference_keye.py`` in float32 and with int8 operands: PERF.md section 6,
PR 45):

* *the residual stream keeps the token.*  The embedding's rows are
  normal(``EMBED_STD`` = 1.0) and the two projections that write into the
  stream are scaled to it: ``W_o`` normal(``O_STD`` = 0.005 = 0.02 / sqrt(2
  x 8)) and an expert's down-projection normal(``DOWN_STD`` = 0.2) — a
  layer's attention then adds ~0.3 of the embedding's norm and its held
  experts (an eighth of the layer's) as much for the tokens they serve, and
  after 8 layers about half of a hidden state's power is still its own
  token.  With every projection at 0.02 the first layer's output outgrows
  the embedding tenfold, every later layer reads what attention wrote, and
  two things follow, whichever way attention is seeded: *diffuse* attention
  (unit gains: logits N(0, 1), a mean over the selected positions) carries
  the values' common part through whole while averaging the rest down, so
  the common part grows by sqrt(n_eff) a layer and all hidden states are
  one vector by layer 3 (cosine 0.98-0.995); *peaked* attention (logits
  N(0, 3^2)) copies one or two selected keys, and since the indexer's
  choice is unrelated to the attention's weights (random weights: a
  trained indexer is trained to agree with it) a key that a bf16 rounding
  moves across the selection's threshold is now and then the one that
  carried the mass — the program's K rows stood 0.58 from the float32
  reference's at layer 7 and its selected sets overlapped by a third: not
  told from the int8 control (0.79).  A position's own key cannot be made
  the one that is always selected: the indexer's score is odd in the
  token's hidden state through its head weights;
* *attention is moderately peaked*: per-head RMSNorm gains ``Q_GAIN`` = 1.0
  on q and ``K_GAIN`` = 2.0 on k — logits N(0, 2^2), an effective ~40 of
  the 2,048 selected positions carrying a query's mass, the largest ~6 % of
  it: which positions are selected decides every layer's attention output,
  and no single position decides it;
* *the indexer's ranking is not a near-tie.*  Its key is LayerNorm'ed
  (unit variance a dim) and its query heads and head weights are plain
  projections of the normed input (std ~0.9 a dim): scores have a spread
  of ~0.5 over a row, ~3e-5 between neighbours in rank at 17 k positions,
  far above float32's resolution and of the order of a bfloat16
  rounding — the control that ranks in bfloat16 loses positions near the
  threshold, the float32 program only those the bf16 residual stream moves.

Matrices are stored input-dimension first (``x @ W``); gate and up
projections are one matrix, gate columns first; ``W_q | W_k | W_v`` are
one matrix.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .weights import seed_key  # the one seed -> key rule (the reference reads it from here)
from .weights_deepseek_v2 import _n, _stacked, table_rows

STD = 0.02
Q_GAIN, K_GAIN = 1.0, 2.0  # the per-head RMSNorm gains on q and k: attention logits N(0, 2^2)
EMBED_STD = 1.0  # the std of the embedding's rows
O_STD = 0.005  # the std of the attention's output projection: 0.02 / sqrt(2 x 8 layers)
DOWN_STD = 0.2  # the std of an expert's down-projection
_ATTN, _ROUTER, _EXPERTS = 0, 2, 3


def held(dims: Dict[str, Any]) -> Tuple[int, int]:
    first, count = dims.get("experts_held") or (0, dims["num_experts"])
    return int(first), int(count)


def sizes(dims: Dict[str, Any]) -> Dict[str, Any]:
    """The attention's and the indexer's sizes: ``H, Hkv, d, Hi, di, rot
    (the indexer head's rotated dims), topk, theta, sections``."""
    sa, rope = dims["sa_config"], dims.get("rope_scaling") or {}
    di = int(sa["indexer_head_dim"])
    return {"H": int(dims["num_attention_heads"]), "Hkv": int(dims["num_key_value_heads"]), "d": int(dims["head_dim"]),
            "Hi": int(sa["indexer_num_heads"]), "di": di, "rot": int(dims.get("index_rotary_dim", di // 2)), "topk": int(sa["topk"]),
            "theta": float(dims["rope_theta"]), "sections": tuple(int(s) for s in rope.get("mrope_section", dims.get("mrope_section")))}


def attn_params(key, layer, dims) -> Dict[str, Any]:
    """A layer's attention, its indexer and its two hidden-size norms; ``layer`` may be traced."""
    D, z = dims["hidden_size"], sizes(dims)
    H, Hkv, d, Hi, di = z["H"], z["Hkv"], z["d"], z["Hi"], z["di"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, layer), _ATTN), 7)  # 1 and 2 are not drawn from: the chip's readings were taken on these draws
    qkv = _n(ks[0], (D, (H + 2 * Hkv) * d), STD)
    one = lambda n, g=1.0: jnp.full((n,), g, jnp.float32)  # noqa: E731
    return {"attn_norm": one(D), "ffn_norm": one(D), "qkv": qkv, "q_norm": one(d, Q_GAIN), "k_norm": one(d, K_GAIN),
            "o": _n(ks[3], (H * d, D), O_STD), "index_q": _n(ks[4], (D, Hi * di), STD), "index_k": _n(ks[5], (D, di), STD),
            "index_w": _n(ks[6], (D, Hi), STD), "index_k_gain": one(di), "index_k_bias": jnp.zeros((di,), jnp.float32)}


def router_params(key, layer, dims) -> Dict[str, Any]:
    """A layer's router, all experts wide."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer), _ROUTER)
    return {"router": _n(k, (dims["hidden_size"], dims["num_experts"]), STD)}


def expert_params(key, layer, expert, dims) -> Dict[str, Any]:
    """Expert ``expert`` (its index among ALL experts) of ``layer``."""
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, layer), _EXPERTS), expert))
    D, F = dims["hidden_size"], dims["moe_intermediate_size"]
    return {"gu": _n(k1, (D, 2 * F), STD), "down": _n(k2, (F, D), DOWN_STD)}


def embedding(key, dims) -> jnp.ndarray:
    """The rows held of the embedding, float32."""
    return table_rows(key, "embed", int(dims["vocab_size"]), dims) * (EMBED_STD / STD)


def head(key, dims) -> jnp.ndarray:
    """The rows held of the untied head ``(rows, D)``, float32."""
    return table_rows(key, "head", int(dims["vocab_size"]), dims)


def program_params(seed: int, dims: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree ``deepspeed_tpu.models.keye`` takes, for the share
    ``dims`` states, made on the default device block by block, each cast
    to ``dtype`` as it is made."""
    key = seed_key(seed)  # an argument of each maker: closed over, it is a constant of the program and every seed compiles its own
    first, count = held(dims)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731
    attn = jax.jit(lambda key, l: cast(attn_params(key, l, dims)))
    router = jax.jit(lambda key, l: cast(router_params(key, l, dims)))

    @jax.jit
    def expert(key, l, e):
        x = cast(expert_params(key, l, e, dims))
        return {"experts_gu": x["gu"], "experts_down": x["down"]}

    tree: Dict[str, Any] = {"embed": jax.jit(lambda key: embedding(key, dims).astype(dtype))(key),
                            "head": jax.jit(lambda key: head(key, dims).T.astype(dtype))(key),
                            "norm_f": jnp.ones((dims["hidden_size"],), dtype)}
    tree["layers"] = [{**attn(key, l), **router(key, l), **_stacked(lambda e, l=l: expert(key, l, first + e), (count,))}
                      for l in range(dims["num_hidden_layers"])]
    return tree
