"""Grouped expert matmul: each touched expert's weights stream once.

``out[rows of group e] = x[rows of group e] @ w[e]`` for rows sorted by
group — what ``jax.lax.ragged_dot`` computes — for the case a routed
layer's held experts make of it: many groups of a few rows each, where
the time is the weights' way from HBM and nothing else.  XLA's
``ragged_dot`` pays by the (group, row-tile) visit at a third of the
chip's bandwidth once the operand has thousands of rows (PERF.md §6,
PR 31); this kernel pays by the **touched expert**.

Grid ``(N / tn, visits)``, both sequential.  A *visit* is one group's
rows inside one row tile of ``tm`` rows (a group that crosses a tile
boundary is visited once a tile: ``groups + tiles - 1`` at most, and the
grid's second bound is the traced count, so what is not touched is not
walked).  The visit's step fetches ``w[e][:, n-th column block]`` —
``(K, tn)``, every row of the expert's matrix, so the product needs no
accumulator across steps and each weight byte is read once per visit —
while ``x``'s ``(tm, K)`` row tile and the ``(tm, tn)`` output tile keep
their block index over a tile's visits and stay in VMEM.  Inside the
step the group's rows are taken ``WINDOW`` (128) at a time from the
sublane-aligned row at or before its first: one MXU pass of 128 rows
for a group of up to 113, more passes for a hot expert, never a dropped
row and no pass for rows that belong to nobody.  Rows of the window that
are another group's are masked at the store.

The group table rides as prefetched scalars (``group_metadata``): row
offsets, and per visit its group and its row tile.  An empty group has
no visit and issues no DMA.  Rows at and past ``sum(sizes)`` are never
written: the caller masks them (``moe/layer.py::dropless_held_experts``).
Operands in their dtype (bf16 on the chip), float32 products, output in
the operands' dtype; the weights are read in the ``(G, K, N)`` layout
the parameter has.  Inference only.
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

WINDOW = 128                   # rows one MXU pass takes
ROW_ALIGN = 16                 # a bf16 sublane tile: where a window may start
WEIGHT_BLOCK_BYTES = 8 << 20   # the most one (K, tn) weight block may hold; two are in flight
VMEM_LIMIT_BYTES = 64 << 20    # of a v5e's 128 MB: two weight blocks, x's row tile twice (2 x 5 MB at K = 5120), the output tile


def _row_tile(rows: int) -> int:
    """Rows of ``x`` and ``out`` resident at a time: the largest of 512 /
    256 / 128 that divides ``rows``, else ``rows`` whole when that is a
    legal block (a sublane multiple, 128–512); 0 where neither holds."""
    for t in (512, 256, 128):
        if rows % t == 0:
            return t
    return rows if rows % ROW_ALIGN == 0 and WINDOW <= rows <= 512 else 0


def _col_tile(K: int, N: int, itemsize: int) -> int:
    """Columns of one weight block: the widest 128-multiple that divides
    ``N`` with ``K * tn`` inside ``WEIGHT_BLOCK_BYTES``."""
    best = 128
    for tn in range(128, N + 1, 128):
        if N % tn == 0 and K * tn * itemsize <= WEIGHT_BLOCK_BYTES:
            best = tn
    return best


def grouped_matmul_supported(rows: int, K: int, N: int, dtype) -> bool:
    """Shapes the compiled kernel serves: a row count that tiles
    (:func:`_row_tile`), ``K`` and ``N`` whole lane tiles, bf16 or
    float32, and a ``(K, 128)`` weight block that fits its budget."""
    dtype = jnp.dtype(dtype)
    return (dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) and _row_tile(rows) > 0
            and K % 128 == 0 and N % 128 == 0 and K * 128 * dtype.itemsize <= WEIGHT_BLOCK_BYTES)


def group_metadata(sizes: jnp.ndarray, rows: int, tm: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(offsets (G + 1,), group_of_visit, tile_of_visit (G + tiles - 1,),
    visits ())``: a non-empty group is visited once in each row tile its
    rows reach, in row order; entries past ``visits`` repeat the last
    group and are not walked."""
    G, tiles = sizes.shape[0], rows // tm
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    per_group = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    most = G + tiles - 1
    group_of = jnp.repeat(jnp.arange(G, dtype=jnp.int32), per_group, total_repeat_length=most)
    before = jnp.cumsum(per_group) - per_group
    tile_of = first[group_of] + jnp.arange(most, dtype=jnp.int32) - before[group_of]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends.astype(jnp.int32)])
    return offsets, group_of, jnp.clip(tile_of, 0, tiles - 1).astype(jnp.int32), jnp.sum(per_group).astype(jnp.int32)


def _grouped_matmul_kernel(offsets_ref, group_ref, tile_ref, x_ref, w_ref, o_ref, *, tm: int):
    v = pl.program_id(1)
    g, base = group_ref[v], tile_ref[v] * tm
    # the group's rows inside this tile, tile-relative
    lo = jnp.maximum(offsets_ref[g], base) - base
    hi = jnp.minimum(offsets_ref[g + 1], base + tm) - base
    first = lo // ROW_ALIGN * ROW_ALIGN

    def one_window(j, carry):
        at = pl.multiple_of(jnp.minimum(first + j * WINDOW, tm - WINDOW), ROW_ALIGN)
        rows = pl.ds(at, WINDOW)
        y = jnp.dot(x_ref[rows, :], w_ref[...], preferred_element_type=jnp.float32)
        row = at + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (row >= lo) & (row < hi)
        o_ref[rows, :] = jnp.where(mine, y, o_ref[rows, :].astype(jnp.float32)).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, jnp.where(hi > lo, pl.cdiv(hi - first, WINDOW), 0), one_window, 0)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray, *, tn: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """``x (M, K)`` sorted by group, ``w (G, K, N)``, ``sizes (G,)``
    int32 with ``sum(sizes) <= M``: ``out (M, N)`` in ``x``'s dtype with
    ``out[rows of group e] = x[rows of group e] @ w[e]``.  Rows at and
    past ``sum(sizes)`` hold whatever the buffer held.  ``tn`` overrides
    the column block (tuning)."""
    M, K = x.shape
    G, _, N = w.shape
    tm = _row_tile(M)
    if not grouped_matmul_supported(M, K, N, x.dtype) or w.dtype != x.dtype:
        raise ValueError(f"grouped_matmul: unsupported call (x {x.shape} {x.dtype}, w {w.shape} {w.dtype})")
    if interpret is None:
        interpret = pallas_interpret_default()
    tn = tn or _col_tile(K, N, x.dtype.itemsize)
    offsets, group_of, tile_of, visits = group_metadata(sizes.astype(jnp.int32), M, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # one step at least: a grid bound of zero is nothing a compiled program needs to meet
        grid=(N // tn, jnp.maximum(visits, 1)),
        in_specs=[
            pl.BlockSpec((tm, K), lambda n, v, offsets, group_of, tile_of: (tile_of[v], 0)),
            pl.BlockSpec((None, K, tn), lambda n, v, offsets, group_of, tile_of: (group_of[v], 0, n)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda n, v, offsets, group_of, tile_of: (tile_of[v], n)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(offsets, group_of, tile_of, x, w)


@register_op("moe_grouped_matmul", "pallas", "grouped expert matmul: each touched expert's weights streamed once")
def _load_grouped_matmul():
    return grouped_matmul
