"""Absorbed latent-attention decode over the paged latent pool.

One query token per live slot: every head's absorbed query
``[q_nope W_UK | RoPE(q_pe)]`` (``H x width``) meets the slot's cached
rows ``[c_kv | RoPE(k_pe)]`` — **one row per position for all heads** —
and the output is ``sum_p p * c_kv`` (``H x v_width``; the caller folds
``W_UV`` in afterwards).  Per cached position the kernel does
``2 H (width + v_width)`` operations on ``width`` cached numbers: at
H = 128, width 576, v_width 512 that is 241 FLOP a byte, the v5e's
ridge — the matmuls run on the MXU in the pool's dtype with float32
accumulation.

Modelled on ``flash_decode_paged``'s page-table walk: the page table
and the positions ride the grid as prefetched scalars, the block index
of the pool operand is ``(layer, pt[b, p], 0, 0)``, so each page
streams HBM to VMEM straight out of the whole pool — no layer slice, no
gather.  A page is ``(width, page_len)``: positions along the lanes
(``latent_attention.py`` says why), so the score product is a plain
matmul and the value product contracts the lanes of both operands.  Grid ``(slots, pages_per_slot)``, page axis sequential; pages
past a slot's position map to the garbage page (fetched once, since
consecutive equal block indices are not fetched again) and skip their
compute.  Inference only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import register_op
from deepspeed_tpu.utils.device import pallas_interpret_default

NEG_INF = -1e30


def mla_decode_supported(H: int, page_len: int, width: int, v_width: int) -> bool:
    """Shapes the compiled kernel serves: a page is one kv block, so
    ``page_len`` must be a lane-aligned run; the value part is the row's
    first ``v_width`` numbers and must end on a tile boundary.  Small
    test pools fall back to the gather + ``jnp`` form."""
    return page_len % 128 == 0 and v_width % 128 == 0 and v_width <= width and H % 8 == 0


def _mla_decode_paged_kernel(pt_ref, pos_ref, q_ref, page_ref, o_ref, m_ref, l_ref, acc_ref, *,
                             sm_scale: float, page_len: int, v_width: int):
    b, p_idx = pl.program_id(0), pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(p_idx * page_len <= pos_ref[b])
    def _page():
        q = q_ref[0]            # (H, width)
        rows = page_ref[0, 0]   # (width, page_len): THE page pt[b, p] of this layer
        s = jnp.dot(q, rows, preferred_element_type=jnp.float32) * sm_scale  # (H, page_len)
        key_idx = p_idx * page_len + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(key_idx <= pos_ref[b], s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:v_width], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(p_idx == pl.num_programs(1) - 1)
    def _emit():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def mla_decode_paged(q, pool, layer: int, page_table, pos, v_width: int, sm_scale: float,
                     interpret: Optional[bool] = None):
    """``q (B, H, width)`` absorbed queries against ``pool (layers, pages,
    width, page_len)`` at the static ``layer``; ``page_table (B, P)``,
    ``pos (B,)`` (key ``j`` attendable iff ``j <= pos[b]``).  Returns
    ``(B, H, v_width)`` in ``q``'s dtype."""
    B, H, width = q.shape
    _, _, pool_w, page_len = pool.shape
    if pool_w != width:
        raise ValueError(f"query width {width} does not match the pool's rows of {pool_w}")
    if interpret is None:
        interpret = pallas_interpret_default()
    table = jnp.asarray(page_table, jnp.int32)
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    kern = functools.partial(_mla_decode_paged_kernel, sm_scale=float(sm_scale), page_len=page_len, v_width=v_width)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, table.shape[1]),
        in_specs=[
            pl.BlockSpec((1, H, width), lambda b, p, pt, pv: (b, 0, 0)),
            pl.BlockSpec((1, 1, width, page_len), lambda b, p, pt, pv: (layer, pt[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, v_width), lambda b, p, pt, pv: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),        # m
            pltpu.VMEM((H, 1), jnp.float32),        # l
            pltpu.VMEM((H, v_width), jnp.float32),  # acc
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode_paged",
    )(table, pos_vec, q.astype(pool.dtype), pool)


@register_op("mla_decode_paged", "pallas", "absorbed latent-attention decode over the paged latent pool")
def _load_mla_decode():
    return mla_decode_paged
