"""Mosaic kernels of learned sparse attention
(``ops/transformer/sparse_attention.py``): of a decode step, the indexer's
scores of every decoding row against its own cached indexer keys and the
attention of the row over the positions selected; of both programs, the
exact top-k's threshold (:func:`dsa_select_threshold`).

The two of the decode step walk the step's **work list** exactly as
``flash_decode_paged`` does — the list and the page table ride as
prefetched scalars, the list's length is the traced bound of the grid,
and a row that does not decode costs no grid step.  An item is a **span**
of :func:`span_of` consecutive pages of a row (``flash_decode.paged_tile``
sizes it off the K pages: 4 at Keye's pool, about a mebibyte of K + V a
grid step), each page an operand of its own found through the page table,
so a grid step's fixed cost is paid once for the span (the last span of a
row may reach past its position: those pages are read and masked).

* :func:`dsa_index_scores_paged` — an item is one page of one row's
  indexer keys, ``(index_dim, page_len)``: the row's ``index_heads``
  query heads against it (float32, ``highest``), ReLU, the heads summed
  under the row's weights: one ``(1, page_len)`` strip of scores.  Strips
  no item visits hold whatever the buffer held; the selection masks them
  (they lie past the row's position).
* :func:`dsa_sparse_decode` — ``flash_decode_paged`` itself, handed the
  row's selection as its ``mask`` operand (the kernel's name in a trace
  stays ``dsa_sparse_decode``).  It **walks every filled page** and
  masks: 2,048 selected positions scattered over a long row touch nearly
  every page, so the walk reads the row's K and V whole — the selection
  saves arithmetic, not bytes, and the kernel's share of a roofline that
  counts the selected rows only reads low
  (``benchmark/kernels/dsa_sparse_decode.py``, docs/kernels.md).

Layout demands: ``page_len`` a multiple of 128 and ``head_dim`` a
multiple of 128 (the page's positions are the rows of its tile);
the indexer leaf holds a page as ``(index_dim, page_len)`` — positions
along the lanes: with 64 numbers a position in the minor dim the compiler
relaid the whole leaf in front of every call (a copy of 8 layers of it a
layer: 28 % of the traced device time, builder's run, PR 45).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.kernels.flash_decode import flash_decode_paged, paged_tile, paged_work_list
from deepspeed_tpu.utils.device import pallas_interpret_default


def supported(B: int, H: int, Hkv: int, P: int, page_len: int, d: int, use_kernel: Optional[bool] = None) -> bool:
    """Whether a decode step of these shapes takes the two kernels: the
    kernel suite armed (``use_kernel`` None) or ``use_kernel`` True, and a
    page geometry their tiles fit."""
    from deepspeed_tpu.ops import kernels as _kernels

    armed = _kernels.flash_decode_armed() if use_kernel is None else bool(use_kernel)
    return armed and page_len % 128 == 0 and d % 128 == 0 and H % Hkv == 0 and B >= 1 and P >= 1


def _select_kernel(keys_ref, t_ref, p_ref, *, k: int, n: int):
    keys = keys_ref[...]                                             # (rows, n) int32, in the floats' order; invalid = INT32_MIN
    rows = keys.shape[0]
    count = lambda m: jnp.sum(m.astype(jnp.int32), axis=1, keepdims=True)  # noqa: E731
    top = jnp.int32(-2 ** 31)

    def value_bit(i, t):  # t: the threshold's bits found so far, as an unsigned number held in an int32
        cand = t | jax.lax.shift_left(jnp.int32(1), jnp.int32(31) - i)
        return jnp.where(count(keys >= (cand ^ top)) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((rows, 1), jnp.int32)) ^ top
    need = k - count(keys > t)
    tied = keys == t
    at = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    nbits = n.bit_length()

    def index_bit(i, p):
        cand = p | jax.lax.shift_left(jnp.int32(1), jnp.int32(nbits - 1) - i)
        return jnp.where(count(tied & (at < cand)) <= need, cand, p)

    p = jax.lax.fori_loop(0, nbits, index_bit, jnp.zeros((rows, 1), jnp.int32))
    t_ref[...] = jnp.broadcast_to(t, t_ref.shape)
    p_ref[...] = jnp.broadcast_to(p, p_ref.shape)


SELECT_ROWS = 8  # rows of scores a program holds in VMEM: 8 x 33,792 x 4 B = 1.1 MB


def select_supported(rows: int, n: int, use_kernel: Optional[bool] = None) -> bool:
    from deepspeed_tpu.ops import kernels as _kernels

    armed = _kernels.flash_decode_armed() if use_kernel is None else bool(use_kernel)
    return armed and rows % SELECT_ROWS == 0 and n % 128 == 0


def dsa_select_threshold(keys, k: int, interpret: Optional[bool] = None):
    """The exact top-``k`` of each row of ``keys (rows, n)`` int32 — scores
    mapped onto integers in the floats' order, ``INT32_MIN`` where a
    position may not be selected — as the pair ``(t (rows,), p (rows,))``:
    a position is selected where ``keys > t``, or ``keys == t`` and its
    index is below ``p`` (ties to the lower position).  ``SELECT_ROWS``
    rows a program, held in VMEM while the 32 counts that find the k-th
    largest value bit by bit and the ``log2 n`` that find the tie's last
    index run over them: the scores are read from HBM once, where the lax
    form (``sparse_attention.topk_mask``) reads them once a count."""
    rows, n = keys.shape
    if interpret is None:
        interpret = pallas_interpret_default()
    t, p = pl.pallas_call(
        functools.partial(_select_kernel, k=int(k), n=n),
        grid=(rows // SELECT_ROWS,),
        in_specs=[pl.BlockSpec((SELECT_ROWS, n), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((SELECT_ROWS, 128), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, 128), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret, name="dsa_select_threshold",
    )(keys)
    return t[:, 0], p[:, 0]


def span_of(k_pages, pages_per_slot: int) -> int:
    """Pages a grid step of both kernels takes (a *span*): ``flash_decode.paged_tile``'s, read off the K pages' shape
    ``(..., Hkv, page_len, d)`` — the one span rule of every paged decode kernel."""
    return paged_tile(k_pages, pages_per_slot)[1]


def work_list(pos, live, k_pages, pages_per_slot: int):
    """The step's work list for both kernels, built once for every layer:
    ``flash_decode.paged_work_list`` over spans of :func:`span_of` pages —
    the filled spans of the rows that decode, slot by slot."""
    return paged_work_list(pos, live, k_pages.shape[-2], pages_per_slot, span_of(k_pages, pages_per_slot))


def _index_scores_kernel(pt_ref, slot_ref, span_ref, n_ref, q_ref, w_ref, *rest, span: int, page_len: int):
    key_refs, o_ref = rest[:span], rest[span]

    @pl.when(n_ref[0] > 0)
    def _item():
        q, w = q_ref[0].astype(jnp.float32), w_ref[0]          # (Hi, di), (Hi, 1)
        for c in range(span):
            keys = key_refs[c][0].astype(jnp.float32)          # (di, page_len): a page's positions lie along the lanes
            dots = jax.lax.dot_general(q, keys, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
                                       preferred_element_type=jnp.float32)  # (Hi, page_len)
            o_ref[0, 0, :, c * page_len: (c + 1) * page_len] = jnp.sum(w * jnp.maximum(dots, 0.0), axis=0, keepdims=True)


def dsa_index_scores_paged(qi, w, idx_pool, layer: int, page_table, pos, work=None, interpret: Optional[bool] = None):
    """``I (B, P * page_len)`` float32: the indexer's scores of each row's
    single query ``qi (B, Hi, di)`` with head weights ``w (B, Hi)`` (both
    float32, scales folded into ``w``) against its own pages of layer
    ``layer`` of ``idx_pool (layers, pages, di, page_len)``.  ``work`` is
    :func:`work_list`'s, whose length says the span it was built under
    (None: every row's filled pages, one an item).  Positions on spans the
    work list does not visit are undefined."""
    B, Hi, di = qi.shape
    L, NP, _, page_len = idx_pool.shape
    P = page_table.shape[1]
    if interpret is None:
        interpret = pallas_interpret_default()
    if work is None:
        work = paged_work_list(jnp.clip(jnp.asarray(pos, jnp.int32).reshape(-1), 0, P * page_len - 1), None, page_len, P)
    slot, span, n, _ = work
    C = B * P // slot.shape[0]
    table = jnp.asarray(page_table, jnp.int32) + jnp.int32(layer * NP)  # the layer's pages where they lie (layers and pages merged)
    row = lambda i, pt, sl, sp, n: (sl[i], 0, 0)  # noqa: E731
    page = lambda c: (lambda i, pt, sl, sp, n: (pt[sl[i], sp[i] * C + c], 0, 0))  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(n[0], 1),),
        in_specs=[pl.BlockSpec((1, Hi, di), row), pl.BlockSpec((1, Hi, 1), row)]
                 + [pl.BlockSpec((1, di, page_len), page(c)) for c in range(C)],
        out_specs=pl.BlockSpec((1, 1, 1, C * page_len), lambda i, pt, sl, sp, n: (sl[i], sp[i], 0, 0)),
    )
    keys = idx_pool.reshape(L * NP, di, page_len)
    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, span=C, page_len=page_len), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P // C, 1, C * page_len), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret, name="dsa_index_scores_paged",
    )(table, slot, span, n, qi.astype(jnp.float32), w.astype(jnp.float32)[..., None], *([keys] * C))
    return out.reshape(B, P * page_len)


def dsa_sparse_decode(q, k_cache, v_cache, page_table, pos, mask, sm_scale: Optional[float] = None, work=None,
                      interpret: Optional[bool] = None):
    """Single-query grouped attention of each row over **the positions
    ``mask`` selects**: ``q (B, H, 1, d)``, caches ``(num_pages, Hkv,
    page_len, d)``, ``page_table (B, P)``, ``pos (B,)`` the rows' query
    positions, ``mask (B, P * page_len)`` bool (nothing past ``pos``).
    It is ``flash_decode_paged`` with the selection as its ``mask``
    operand — the same body, an item's products in three passes — under
    the kernel name ``dsa_sparse_decode``, walking ``work``
    (:func:`work_list`; None: every row's filled spans).  Rows no item
    visits, and rows that select nothing, read 0.  Returns ``(B, H, 1, d)``."""
    return flash_decode_paged(q, k_cache, v_cache, page_table, pos, sm_scale, work, interpret, mask=mask)
