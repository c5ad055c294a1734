"""A decode step's K/V rows written into the stacked page pool by one
aliased Mosaic call a pool and a layer.

Where a head is narrower than the 128 lanes the TPU stores ``(...,
page_len, d)`` with ``page_len`` in the lanes (``flash_decode.py``,
``lanes_hold_rows``): one position of one row is then a **column** of
``H x d`` values, one each in a lane row of its own, and XLA's
``dynamic_update_slice`` of it costs ~5 us whatever it moves — 1,536 of
them were half a GPT-2 XL decode step (PERF.md, PRs 46 and 49).  This
kernel takes the tile those values lie in instead: a grid step reads one
row's page ``(1, H, d, page_len)`` of the pool's own bytes (layers and
pages merged, the last two dims swapped: a bitcast), replaces lane
``off`` with the row's values and writes the tile back.  The pool is
aliased to the output (``input_output_aliases``), so a page no step
visits keeps what it held and a donated pool is updated where it lies.

Grid ``(writing rows,)``, sequential: the bound is the **traced count**
of rows whose ``write_mask`` holds, their indices compacted in front
(``kda_decode.compact_rows``), so a masked row costs no step and no DMA
and the garbage page is never a target twice.  Two writing rows never
name one page (copy-on-write), so no step reads what another wrote.
Prefetched scalars: each row's page and offset (``page_target``), the
compacted rows, their count, and the layer — the merged page index
``layer * pages + page`` is formed in the index map.  The rows' values
arrive as ``(B, d, H)``, whole in VMEM for the call: head ``h``'s column
is a static lane slice, broadcast along the lanes under the mask ``lane
== off``.  No arithmetic touches a value: the write is bit for bit the
slices'.  With no row writing the one step a grid must take rewrites
row 0's target, which is then the garbage page.  Inference only.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.kernels.kda_decode import compact_rows
from deepspeed_tpu.ops.registry import register_op
from deepspeed_tpu.utils.device import pallas_interpret_default


def paged_kv_write_supported(page_len: int, d: int) -> bool:
    """Pools the call serves, by their shape alone: a head narrower than
    the lanes — ``flash_decode_paged``'s rule for the tile it is handed —
    in pages of whole lane rows.  A pool of whole-lane heads is
    row-major and takes its rows as slices."""
    return d % 128 != 0 and page_len % 128 == 0


def _paged_kv_write_kernel(page_ref, off_ref, rows_ref, n_ref, layer_ref, cols_ref, pool_ref, out_ref):
    del page_ref, n_ref, layer_ref  # the index maps'
    b = rows_ref[pl.program_id(0)]
    _, H, d, page_len = out_ref.shape
    hit = jax.lax.broadcasted_iota(jnp.int32, (d, page_len), 1) == off_ref[b]
    cols = cols_ref[b]  # (d, H)
    for h in range(H):
        out_ref[0, h] = jnp.where(hit, cols[:, h:h + 1], pool_ref[0, h])


def paged_write_plan(page, off, write_mask=None):
    """The prefetched scalars of one decode step's writes, ``(page, off,
    rows, n)``: every row's target ``(B,)`` as ``inference.page_target``
    gives it, the indices of the rows whose ``write_mask`` holds
    (``None``: every row) compacted in front, and their count ``(1,)``.
    It depends on nothing a layer has, so a decode program builds it
    once and hands it to every layer's calls."""
    mask = jnp.ones(page.shape, bool) if write_mask is None else write_mask
    return (page.astype(jnp.int32), off.astype(jnp.int32)) + compact_rows(mask)


def paged_kv_write(pool, layer, t, plan, interpret: Optional[bool] = None):
    """Row ``b`` of ``t (B, H, 1, x)`` into position ``off[b]`` of page
    ``page[b]`` of layer ``layer`` (an int or a traced scalar) of the
    stacked ``pool (layers, pages, H, page_len, x)``, for the rows
    ``plan`` (:func:`paged_write_plan`) lists.  Returns the pool,
    updated in place where it was donated."""
    L, NP, H, page_len, d = pool.shape
    B = t.shape[0]
    if t.shape != (B, H, 1, d) or not paged_kv_write_supported(page_len, d):
        raise ValueError(f"paged_kv_write: rows {t.shape} into a pool {pool.shape}; callers must dispatch "
                         "through paged_kv_write_supported()")
    if interpret is None:
        interpret = pallas_interpret_default()
    tile = pl.BlockSpec((1, H, d, page_len), lambda i, page, off, rows, n, layer: (layer[0] * NP + page[rows[i]], 0, 0, 0))
    out = pl.pallas_call(
        _paged_kv_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # one step at least: a grid bound of zero is nothing a compiled program needs to meet
            grid=(jnp.maximum(plan[3][0], 1),),
            in_specs=[pl.BlockSpec((B, d, H), lambda i, *_: (0, 0, 0)), tile],
            out_specs=tile,
        ),
        out_shape=jax.ShapeDtypeStruct((L * NP, H, d, page_len), pool.dtype),
        # operands count the prefetched scalars: page, off, rows, n, layer, cols, pool -> the pool is the seventh
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_kv_write",
    )(*plan, jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.swapaxes(t[:, :, 0, :], 1, 2).astype(pool.dtype), jnp.swapaxes(pool.reshape(L * NP, H, page_len, d), 2, 3))
    return jnp.swapaxes(out, 2, 3).reshape(pool.shape)


@register_op("paged_kv_write", "pallas", "a decode step's K/V rows into a paged pool of narrow heads, one aliased call a pool and layer")
def _load_paged_kv_write():
    return paged_kv_write
