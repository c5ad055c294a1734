"""Expanded latent-attention prefill: one context block under an online softmax.

A prefill chunk's queries ``[q_nope | RoPE(q_pe)]`` (``T`` positions,
``H`` heads) meet one block of the slot's context whose per-head keys
and values XLA has just rebuilt from the cached latents
(``latent_attention.expanded_attention``): ``k_nope``, ``v`` per head and
the rotary keys ``k_pe`` — **one row per position for all heads**, so
the two score products stay two and no ``(S, H, nope + rope)`` key is
ever built.  The kernel takes the running ``(m, l, acc)`` of the
chunk's softmax, folds the block in and hands them back in place
(``input_output_aliases``): the float32 score tile ``(tq, ts)`` lives
and dies in VMEM, where the ``jnp`` form writes a ``(H, T, S)`` block
to HBM and reads it back for the maximum, the sum and the value
product.

Grid ``(B, H, T / tq, S / ts)``, key axis innermost and sequential:
``acc`` / ``m`` / ``l`` of one head and query tile stay resident over
it.  The positions ride as prefetched scalars, so a key tile that the
causal mask empties whole (``first key > last query``) skips its
compute and is not fetched (its block index repeats the last needed
one), and a tile no query is masked in skips the mask.  ``m`` and
``l`` are carried lane-replicated, ``(B, H, T, 128)``: a ``(tq, 1)``
column is a padded tile in VMEM and a 4-byte-row DMA from HBM.
Arithmetic as the ``jnp`` form: operands in their dtype (bf16 on the
chip), float32 scores and statistics, ``p`` cast for the value product,
float32 accumulator.  Inference only.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import register_op
from deepspeed_tpu.utils.device import pallas_interpret_default

NEG_INF = -1e30
STAT_LANES = 128  # m and l are carried replicated over one lane tile


def _tile(n: int, most: int) -> int:
    """The largest 128-multiple up to ``most`` (a power of two) that
    divides ``n``, else ``n`` whole (a block equal to the array's
    dimension is always legal)."""
    while most >= 128 and n % most:
        most //= 2
    return most if most >= 128 else n


def mla_prefill_supported(H: int, T: int, S: int, nope: int, rope: int, v: int) -> bool:
    """Shapes the compiled kernel serves: query and key tiles of whole
    128-row runs, head dimensions of whole bf16 sublane tiles and no
    wider than a lane tile (what has been compiled: 128 / 64 / 128 and
    32 / 64 / 32, in bf16 and float32).  Small test shapes take the
    ``jnp`` form."""
    return not (T % 128 or S % 128) and all(d % 16 == 0 and 16 <= d <= 128 for d in (nope, rope, v))


def _mla_prefill_kernel(pos_ref, k0_ref, qn_ref, qp_ref, kn_ref, kp_ref, v_ref, m_in, l_in, acc_in,
                        m_ref, l_ref, acc_ref, *, sm_scale: float, tq: int, ts: int):
    b, qi, si = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(si == 0)
    def _carry_in():
        m_ref[...] = m_in[...]
        l_ref[...] = l_in[...]
        acc_ref[...] = acc_in[...]

    # key ``k_first + c`` against query ``q_first + r``: attendable iff c - r <= q_first - k_first
    q_first = pos_ref[b] + qi * tq
    k_first = k0_ref[0] + si * ts

    def fold(masked: bool):
        s = jax.lax.dot_general(qn_ref[0, 0], kn_ref[0, 0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qp_ref[0, 0], kp_ref[0], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s = s * sm_scale  # (tq, ts)
        if masked:
            ahead = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(ahead <= q_first - k_first, s, NEG_INF)
        m_prev, l_prev = m_ref[0, 0], l_ref[0, 0]  # (tq, STAT_LANES), every lane alike
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[0, 0] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[0, 0] = m_new
        acc_ref[0, 0] = acc_ref[0, 0] * alpha[:, :1] + jnp.dot(p.astype(v_ref.dtype), v_ref[0, 0],
                                                               preferred_element_type=jnp.float32)

    live = k_first <= q_first + tq - 1           # some query of the tile reaches its first key
    clear = k_first + ts - 1 <= q_first          # its last key is behind every query

    @pl.when(live & clear)
    def _whole():
        fold(masked=False)

    @pl.when(live & jnp.logical_not(clear))
    def _diagonal():
        fold(masked=True)


def mla_prefill(q_nope, q_pe, k_nope, k_pe, v, pos, k_start, carry: Tuple, sm_scale: float,
                interpret: Optional[bool] = None):
    """Fold one context block into a chunk's running softmax.

    ``q_nope (B, H, T, nope)``, ``q_pe (B, H, T, rope)``: the chunk's
    queries, row ``t`` of batch row ``b`` at position ``pos[b] + t``;
    ``k_nope (B, H, S, nope)``, ``v (B, H, S, dv)``, ``k_pe (B, S, rope)``:
    the block's keys and values, key ``s`` at position ``k_start + s``
    (attendable iff that is ``<=`` the query's position); ``carry = (m,
    l, acc)`` with ``m``, ``l`` ``(B, H, T, STAT_LANES)`` float32 (every
    lane alike) and ``acc (B, H, T, dv)`` float32, updated in place.
    Start from ``m = NEG_INF``, ``l = 0``, ``acc = 0``; the attention is
    ``acc / l`` (``l == 0``: a row no key reached)."""
    B, H, T, nope = q_nope.shape
    rope, S, dv = q_pe.shape[-1], k_nope.shape[2], v.shape[-1]
    if interpret is None:
        interpret = pallas_interpret_default()
    tq, ts = _tile(T, 512), _tile(S, 1024)  # a (512, 1024) float32 score tile is 2 MB of the 16 MB of VMEM
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    k0 = jnp.asarray(k_start, jnp.int32).reshape(1)

    def key_tile(b, qi, si, pos_ref, k0_ref):
        # past the last tile any query of (b, qi) reaches, repeat it: not fetched again
        last = (pos_ref[b] + (qi + 1) * tq - 1 - k0_ref[0]) // ts
        return jnp.minimum(si, jnp.clip(last, 0, S // ts - 1))

    q_map = lambda b, h, qi, si, pos_ref, k0_ref: (b, h, qi, 0)  # noqa: E731
    kv_map = lambda b, h, qi, si, pos_ref, k0_ref: (b, h, key_tile(b, qi, si, pos_ref, k0_ref), 0)  # noqa: E731
    stat, acc = pl.BlockSpec((1, 1, tq, STAT_LANES), q_map), pl.BlockSpec((1, 1, tq, dv), q_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, T // tq, S // ts),
        in_specs=[
            pl.BlockSpec((1, 1, tq, nope), q_map),
            pl.BlockSpec((1, 1, tq, rope), q_map),
            pl.BlockSpec((1, 1, ts, nope), kv_map),
            pl.BlockSpec((1, ts, rope), lambda b, h, qi, si, pos_ref, k0_ref: (b, key_tile(b, qi, si, pos_ref, k0_ref), 0)),
            pl.BlockSpec((1, 1, ts, dv), kv_map),
            stat, stat, acc,
        ],
        out_specs=[stat, stat, acc],
    )
    m, l, a = carry
    return tuple(pl.pallas_call(
        functools.partial(_mla_prefill_kernel, sm_scale=float(sm_scale), tq=tq, ts=ts),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (m, l, a)],
        input_output_aliases={7: 0, 8: 1, 9: 2},  # operands counted from the prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mla_prefill",
    )(pos_vec, k0, q_nope, q_pe, k_nope, k_pe.astype(k_nope.dtype), v, m, l, a))


@register_op("mla_prefill", "pallas", "expanded latent-attention prefill: one context block under an online softmax")
def _load_mla_prefill():
    return mla_prefill
