"""A prefill chunk's attention over its slot's pages, scores in VMEM.

``inference.paged_chunk_attention`` as one Mosaic call: the chunk's
queries ``(B, H, T, d)`` at positions ``pos[b] + t`` walk the slot's K / V
pages **where they lie** in the pool — the page table rides as a
prefetched scalar and a page is an operand found through it in the index
map, as in ``flash_decode_paged``; no block of the context is gathered —
under an online softmax whose float32 score tile lives and dies in VMEM.
The ``jnp`` form writes one block's ``(H, T, S)`` float32 scores to HBM,
reads them for the maximum and for the sum, writes the probabilities and
reads them for the value product.

**Grouped queries share a tile**: the ``G = H / Hkv`` query heads of one
KV head are stacked into one ``(G * tq, d)`` query tile, so a K / V tile
is fetched once a KV head and is the MXU's stationary operand for ``G *
tq`` rows; the mask tile ``(tq, ts)`` — causal, and the caller's
``extra_mask`` (learned sparse attention's selection, carried as int8) —
is made once a tile and shared by the ``G`` heads.

Grid ``(B, Hkv, T / tq, blocks)``: a **block** is ``span`` consecutive
pages of the row (``ts = span * page_len`` keys), the key axis innermost
and sequential with ``(m, l, acc)`` of the query tile resident in VMEM
scratch over it (nothing of the softmax is carried through HBM); its
bound is traced, ``ceil((max(pos) + T) / ts)`` — the walk ends where the
furthest query's does; a slot's last block may reach past its pages
(the table's last entry is read again, behind the causal mask).  A block
the causal bound empties for the query
tile (``first key > last query``) skips its arithmetic and is not fetched
(its index repeats the last needed block's), a block no query is masked
in skips the causal mask.  Arithmetic as the ``jnp`` form and as
``mla_prefill``: operands in their dtype, float32 scores and statistics,
``p`` cast for the value product, float32 accumulator; a block in which a
query selected nothing adds nothing, a row nothing reached (``l == 0``)
reads 0.  Inference only.

**Values narrower than keys** (``d_v != d``: the V pool ``(pages, Hkv,
page_len, d_v)``): the score product contracts over ``d``, the value
product and the accumulator are ``d_v`` wide.  Keys whose width is not
whole lanes (192) lie in the pool with their positions in the lanes
(``flash_decode_paged``'s rule, an operand at a time): their tile is
handed over as ``(d, page_len)`` — the pool's own bytes — and the score
product contracts over its rows.

Left to the ``jnp`` form (:func:`flash_chunk_unsupported`): values
narrower than the 128 lanes (their pool lies with the positions in the
lanes: GPT-2), the int8 code + scale pool, chunks and pages that are not
whole 128-row tiles.
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
BLOCK_KEYS = 1024      # keys of a block
SCORE_BYTES = 8 << 20  # the float32 score tile (G * tq, ts) of a grid step
VMEM_LIMIT = 64 << 20  # of a v5e's 128 MiB: the score tile, its probabilities in float32 and in the value's dtype, the operands twice


def flash_chunk_unsupported(T: int, d: int, page_len: int, quant: bool, d_v: Optional[int] = None) -> str:
    """Why the kernel does not serve a chunk of these shapes, ``""``
    where it does: whole 128-row query and key tiles, values that fill
    the lanes (``d_v``; the keys' ``d`` where not given), an unquantised
    pool."""
    if quant:
        return "int8 pool (codes + scales)"
    d_v = d if d_v is None else d_v
    if d_v % 128:
        return f"head dim {d_v} is narrower than the 128 lanes (the pool lies with its positions in the lanes)"
    if T % 128 or page_len % 128:
        return f"chunk of {T} on pages of {page_len}: not whole 128-row tiles"
    return ""


def flash_chunk_supported(T: int, d: int, page_len: int, quant: bool = False, d_v: Optional[int] = None) -> bool:
    return not flash_chunk_unsupported(T, d, page_len, quant, d_v)


def chunk_tile(G: int, T: int, P: int, page_len: int):
    """``(tq, span)`` of a grid step, from the shapes alone: the pages
    that hold :data:`BLOCK_KEYS` keys (one at least, the slot's ``P`` at
    most; they need not divide ``P``), and the largest 128 * 2^n query
    rows a head, up to 512, that divide ``T`` and keep the ``G`` heads'
    float32 score tile within :data:`SCORE_BYTES` (one run of 128 at
    least).  Sized on the chip (PERF.md §6, PR 53): a step pays once a
    row for the softmax's two reductions over the lanes, so keys a block
    count until 1,024, and rows a tile (the MXU's stationary K / V tile
    reused) as far as VMEM goes."""
    span = max(1, min(P, BLOCK_KEYS // page_len))
    tq = 128
    while tq < 512 and T % (2 * tq) == 0 and G * 2 * tq * span * page_len * 4 <= SCORE_BYTES:
        tq *= 2
    return tq, span


def _flash_chunk_kernel(pt_ref, pos_ref, nb_ref, q_ref, *rest, sm_scale: float, span: int, masked: bool,
                        lanes_hold_rows: bool = False):
    del pt_ref  # the index maps' own
    k_refs, v_refs = rest[:span], rest[span: 2 * span]
    mask_ref = rest[2 * span] if masked else None
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    G, tq, d = q_ref.shape[2:]
    ts = span * v_refs[0].shape[2]
    b, qi, si = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(si == 0)
    def _start():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # key ``k_first + c`` against query ``q_first + r`` (of every head): attendable iff c - r <= q_first - k_first
    q_first = pos_ref[b] + qi * tq
    k_first = si * ts

    def fold(causal: bool):
        if lanes_hold_rows:  # keys with their positions in the lanes, (d, ts): the contraction moves
            k, v = jnp.concatenate([r[0, 0] for r in k_refs], axis=1), jnp.concatenate([r[0, 0] for r in v_refs], axis=0)
        else:
            k, v = (jnp.concatenate([r[0, 0] for r in refs], axis=0) for refs in (k_refs, v_refs))    # (ts, d): the block's pages, end to end
        s = jax.lax.dot_general(q_ref[0, 0].reshape(G * tq, d), k, (((1,), (0 if lanes_hold_rows else 1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale           # (G * tq, ts)
        ok = None
        if causal:
            ahead = jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 1) - jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 0)
            ok = ahead <= q_first - k_first
        if masked:
            chosen = mask_ref[0].astype(jnp.int32) != 0
            ok = chosen if ok is None else ok & chosen
        if ok is not None:  # one (tq, ts) mask for the G heads
            s = jnp.where(ok[None], s.reshape(G, tq, ts), NEG_INF).reshape(G * tq, ts)
        m_prev = m_ref[...]                                                                  # (G * tq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row nothing has reached yet still holds NEG_INF: taken against 0, its masked scores' exp is 0 and
        # not exp(0) — the block adds nothing to it (the jnp form's second select, a row at a time)
        p = jnp.exp(s - jnp.where(m_new == NEG_INF, 0.0, m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    live = k_first <= q_first + tq - 1           # some query of the tile reaches the block's first key
    clear = k_first + ts - 1 <= q_first          # its last key is behind every query

    @pl.when(live & clear)
    def _whole():
        fold(causal=False)

    @pl.when(live & jnp.logical_not(clear))
    def _diagonal():
        fold(causal=True)

    @pl.when(si == nb_ref[0] - 1)
    def _emit():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).reshape(o_ref.shape[2:]).astype(o_ref.dtype)


def flash_chunk_paged(q, k_cache, v_cache, page_table, pos, sm_scale: Optional[float] = None, extra_mask=None,
                      tile=None, interpret: Optional[bool] = None):
    """``q (B, H, T, d)`` at positions ``pos[b] + t`` (the chunk's own
    keys already written) against the pools ``(pages, Hkv, page_len, d)``
    through ``page_table (B, P)``; ``H`` a multiple of ``Hkv`` (query head
    ``i`` attends KV head ``i // (H / Hkv)``); ``extra_mask (B, T, P *
    page_len)`` bool, where given, a per-query selection applied beside
    the causal mask.  ``tile = (tq, span)`` overrides :func:`chunk_tile`
    (a sweep, a test).  Returns ``(B, H, T, d_v)`` in ``q``'s dtype (``d_v``
    the V pool's width) —
    :func:`inference.paged_chunk_attention`'s contract, which dispatches
    here; shapes outside :func:`flash_chunk_supported` are its ``jnp``
    form's."""
    quant = isinstance(k_cache, dict)
    B, H, T, d = q.shape
    _, Hkv, page_len, _ = (k_cache["q"] if quant else k_cache).shape
    d_v = (v_cache["q"] if quant else v_cache).shape[-1]
    P, G = page_table.shape[1], H // Hkv
    why_not = flash_chunk_unsupported(T, d, page_len, quant, d_v)
    if why_not or H % Hkv:
        raise ValueError(f"flash_chunk_paged cannot serve (H {H} / {Hkv}, T {T}, d {d}, page_len {page_len}): {why_not or 'heads are not whole groups'}; "
                         "callers dispatch through flash_chunk_supported()")
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = pallas_interpret_default()
    tq, span = tile or chunk_tile(G, T, P, page_len)
    ts, blocks = span * page_len, -(-P // span)  # the last block may reach past the slot's pages: behind the causal mask
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    # as far as the furthest query reaches, one block at least: the bound of the grid's key axis
    nb = jnp.clip((jnp.max(pos_vec) + T + ts - 1) // ts, 1, blocks).reshape(1)

    def key_block(b, qi, si, pos_ref):
        # past the last block any query of (b, qi) reaches, repeat it: not fetched again
        return jnp.minimum(si, jnp.clip((pos_ref[b] + (qi + 1) * tq - 1) // ts, 0, blocks - 1))

    q_map = lambda b, h, qi, si, pt, pv, nb: (b, h, 0, qi, 0)  # noqa: E731
    page = lambda j: (lambda b, h, qi, si, pt, pv, nb: (pt[b, jnp.minimum(key_block(b, qi, si, pv) * span + j, P - 1)], h, 0, 0))  # noqa: E731
    pages = [pl.BlockSpec((1, 1, page_len, d), page(j)) for j in range(span)]
    v_pages = pages
    lanes_hold_rows = d % 128 != 0  # keys whose width is not whole lanes lie with their positions in the lanes: the tile is (d, page_len)
    if lanes_hold_rows:
        k_cache = jnp.swapaxes(k_cache, 2, 3)
        pages = [pl.BlockSpec((1, 1, d, page_len), page(j)) for j in range(span)]
    if d_v != d:
        v_pages = [pl.BlockSpec((1, 1, page_len, d_v), page(j)) for j in range(span)]
    in_specs = [pl.BlockSpec((1, 1, G, tq, d), q_map)] + pages + v_pages
    args = [q.reshape(B, Hkv, G, T, d)] + [k_cache] * span + [v_cache] * span
    if extra_mask is not None:
        in_specs.append(pl.BlockSpec((1, tq, ts), lambda b, h, qi, si, pt, pv, nb: (b, qi, key_block(b, qi, si, pv))))
        args.append(extra_mask.astype(jnp.int8))  # a byte a position, as the bool it was: the cast fuses into its producer
    out = pl.pallas_call(
        functools.partial(_flash_chunk_kernel, sm_scale=float(sm_scale), span=span, masked=extra_mask is not None,
                          lanes_hold_rows=lanes_hold_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, Hkv, T // tq, nb[0]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, G, tq, d_v), q_map),
            scratch_shapes=[
                pltpu.VMEM((G * tq, 1), jnp.float32),   # m
                pltpu.VMEM((G * tq, 1), jnp.float32),   # l
                pltpu.VMEM((G * tq, d_v), jnp.float32),   # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, T, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="flash_chunk_paged",
    )(jnp.asarray(page_table, jnp.int32), pos_vec, nb, *args)
    return out.reshape(B, H, T, d_v)


@register_op("flash_chunk_paged", "pallas", "a prefill chunk's attention over its slot's pages: online softmax, scores in VMEM")
def _load_flash_chunk_paged():
    return flash_chunk_paged
