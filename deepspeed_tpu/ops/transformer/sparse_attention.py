"""Learned sparse attention on a paged cache: an **indexer** scores every
cached position for a query, an **exact top-k** picks the positions the
query attends to, and softmax attention runs over those only.

A layer keeps three things a position in the pool
(``serving/kvcache/pages.py::IndexedKV``): K and V of the grouped-query
attention, ``(layers, pages, kv_heads, page_len, head_dim)``, and one
**indexer key** of ``index_dim`` numbers, ``(layers, pages, index_dim,
page_len)`` (positions along the lanes), all under one page table.

For a query at position ``t`` with indexer query heads ``qI_j`` and head
weights ``w_j`` (float32 from the layer's normed input), the score of a
cached position ``s <= t`` is::

    I(t, s) = sum_j w_j * relu(qI_j . kI_s) * index_dim^-1/2 * index_heads^-1/2

``S_t`` = the ``topk`` positions of largest ``I(t, .)``, ties to the lower
position; every ``s <= t`` while ``t < topk``.  One selection a token a
layer, shared by all query heads.  **The selection is exact**:
:func:`topk_mask` finds the k-th largest score by bisection on the bits of
the float32 scores and the tie's last position by bisection on the
position — a mask, not a sorted list; ``jax.lax.top_k`` at this ``k``
sorts, and an approximate or block-wise selection is a different result.

Two forms of the attention over ``S_t``, the same mathematics:

* a **prefill chunk** is a dense blockwise product under the selection
  mask (``inference.paged_chunk_attention(..., extra_mask=)``): a
  per-query gather would move ``T x topk`` rows where the block walk reads
  each context row once;
* a **decode step** walks the rows' filled pages with the mask applied —
  ``ops/kernels/sparse_decode.py::dsa_sparse_decode`` on the chip (the
  paged decode kernel ``flash_decode_paged`` under the selection as its
  ``mask`` operand), the gathered lax form below elsewhere (its reference).

Rotary is **three-stream** (``mrope``): the frequency pairs of a head are
split ``sections`` = 16 / 24 / 24 over a temporal, a height and a width
position; a text token carries its position in all three, and the serving
path feeds equal streams.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
HI = jax.lax.Precision.HIGHEST
KV_BLOCK = 512  # the indexer's kv_chunk_size: the tile the chunk's scores are computed in (changes no result)


class Sizes(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    index_rot: int  # the leading dims of an indexer head that are rotated (temporal stream)
    topk: int
    theta: float
    sections: Tuple[int, ...]  # frequency pairs a position stream; sums to head_dim // 2


# ---------------------------------------------------------------------------
# rotary in three streams
# ---------------------------------------------------------------------------

def mrope_angles(positions, pairs: int, theta: float, sections: Optional[Tuple[int, ...]] = None):
    """Rotation angles ``(..., T, pairs)`` float32.  ``positions`` is
    ``(3, ..., T)`` (temporal, height, width) with ``sections`` saying how
    many of the ``pairs`` frequency pairs read each stream, in order — or
    ``(..., T)`` with ``sections`` None (one stream)."""
    inv = jnp.asarray(theta ** (-np.arange(pairs, dtype=np.float64) / pairs), jnp.float32)
    if sections is None:
        return positions[..., None].astype(jnp.float32) * inv
    if sum(sections) != pairs or positions.shape[0] != len(sections):
        raise ValueError(f"mrope sections {sections} do not split {pairs} frequency pairs over {positions.shape[0]} streams")
    stream = np.repeat(np.arange(len(sections)), sections)  # (pairs,)
    per_pair = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., stream]  # (..., T, pairs)
    return per_pair * inv


def rotate(x, angles):
    """Half-layout rotary on the first ``2 * pairs`` dims of ``x (..., T,
    heads, d)`` by ``angles (..., T, pairs)``; float32 in, float32 out."""
    pairs = angles.shape[-1]
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :pairs], x[..., pairs: 2 * pairs]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * pairs:]], axis=-1)


def head_rms(x, gain, eps: float):
    """RMSNorm over each head's dims, ``x (..., heads, d)`` float32."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain.astype(jnp.float32)


# ---------------------------------------------------------------------------
# the indexer
# ---------------------------------------------------------------------------

def index_project(sz: Sizes, u, w_qi, w_ki, w_w, ki_gain, ki_bias, positions, eps: float):
    """The indexer's query heads, key and head weights of ``u (B, T, D)``
    (the layer's normed input) at temporal ``positions (B, T)``.

    float32 products at ``highest`` precision from the weights as stored.
    The key is LayerNorm'ed (gain and bias), query heads and key are
    rotated on their first ``index_rot`` dims, and the head weights carry
    both scales.  Returns ``(qI (B, T, Hi, di), kI (B, T, di), w (B, T, Hi))``."""
    Hi, di = sz.index_heads, sz.index_dim
    dot = lambda a, b: jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32), precision=HI)  # noqa: E731
    qi = dot(u, w_qi).reshape(u.shape[:2] + (Hi, di))
    ki = dot(u, w_ki)
    w = dot(u, w_w) * (di ** -0.5 * Hi ** -0.5)
    mu = jnp.mean(ki, -1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(jnp.mean(jnp.square(ki - mu), -1, keepdims=True) + eps)
    ki = ki * ki_gain.astype(jnp.float32) + ki_bias.astype(jnp.float32)
    ang = mrope_angles(positions, sz.index_rot // 2, sz.theta)
    return rotate(qi, ang), rotate(ki[..., None, :], ang)[..., 0, :], w


def index_scores(qi, w, ki_ctx):
    """``I (B, T, S)`` float32 of queries ``qi (B, T, Hi, di)``, ``w (B,
    T, Hi)`` against keys ``ki_ctx (B, S, di)``."""
    dots = jnp.einsum("bthd,bsd->bths", qi, ki_ctx.astype(jnp.float32), precision=HI)
    return jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2)


def chunk_index_scores(qi, w, ki_ctx, n_ctx):
    """:func:`index_scores` of a chunk against a long context, ``KV_BLOCK``
    keys at a time as far as ``n_ctx`` (traced) reaches: the ``(B, T, Hi,
    KV_BLOCK)`` products are the most that exists.  Keys past ``n_ctx``
    read ``NEG``."""
    B, T = qi.shape[:2]
    S = ki_ctx.shape[1]
    blk = KV_BLOCK
    while S % blk:
        blk //= 2
    if S <= blk:
        return index_scores(qi, w, ki_ctx)

    def body(j, out):
        keys = jax.lax.dynamic_slice_in_dim(ki_ctx, j * blk, blk, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(out, index_scores(qi, w, keys), j * blk, axis=2)

    return jax.lax.fori_loop(0, jnp.minimum((n_ctx + blk - 1) // blk, S // blk), body, jnp.full((B, T, S), NEG, jnp.float32))


# ---------------------------------------------------------------------------
# the exact top-k, as a mask
# ---------------------------------------------------------------------------

def _threshold(keys, k: int):
    """``(t, p)`` of ``sparse_decode.dsa_select_threshold`` in lax: the
    k-th largest of ``keys (..., N)`` int32 found bit by bit (the bits
    held as an unsigned number in an int32), then the last index a tie may
    take.  A pass over ``keys`` a count: the kernel's reference."""
    N = keys.shape[-1]
    top = jnp.int32(-2 ** 31)
    count = lambda m: jnp.sum(m, axis=-1, dtype=jnp.int32, keepdims=True)  # noqa: E731

    def value_bit(i, t):
        cand = t | (jnp.int32(1) << (31 - i))
        return jnp.where(count(keys >= (cand ^ top)) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros(keys.shape[:-1] + (1,), jnp.int32)) ^ top
    need, tied = k - count(keys > t), keys == t
    at = jnp.arange(N, dtype=jnp.int32)
    nbits = N.bit_length()  # a shape: a Python int

    def index_bit(i, p):
        cand = p | (jnp.int32(1) << (nbits - 1 - i))
        return jnp.where(count(tied & (at < cand)) <= need, cand, p)

    return t, jax.lax.fori_loop(0, nbits, index_bit, jnp.zeros(keys.shape[:-1] + (1,), jnp.int32))


def topk_mask(scores, k: int, valid, use_kernel: Optional[bool] = None):
    """The ``k`` largest of ``scores (..., N)`` among ``valid (..., N)``,
    ties to the lower index, as a bool mask; all of ``valid`` where fewer
    than ``k`` are.  Returns ``(mask, threshold (...,) float32)``: the
    k-th largest score itself, the number the mask was cut at (NaN where
    fewer than ``k`` are ``valid``).  Exact: the scores become ``int32`` keys in the floats'
    order (``INT32_MIN`` where not ``valid``), the k-th largest key is
    found bit by bit (32 counts), then the last index a tie may take
    (``log2 N`` counts).  On the chip the counts run in
    ``sparse_decode.dsa_select_threshold`` (rows held in VMEM: the scores
    are read once, not once a count); :func:`_threshold` is its lax form."""
    from deepspeed_tpu.ops.kernels import sparse_decode as kern

    N = scores.shape[-1]
    rows = math.prod(scores.shape[:-1])
    b = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    keys = jnp.where(valid, jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b), jnp.int32(-2 ** 31))
    if kern.select_supported(rows, N, use_kernel):
        t, p = (x.reshape(scores.shape[:-1] + (1,)) for x in kern.dsa_select_threshold(keys.reshape(rows, N), k))
    else:
        t, p = _threshold(keys, k)
    mask = valid & ((keys > t) | ((keys == t) & (jnp.arange(N, dtype=jnp.int32) < p)))
    t = t[..., 0]
    return mask, jax.lax.bitcast_convert_type(jnp.where(t < 0, t ^ jnp.int32(0x7FFFFFFF), t), jnp.float32)


SELECT_BUCKET = 4096  # the selection of a long slot is computed over its first multiple of this many positions that holds the context


def topk_mask_upto(scores, k: int, valid, n_ctx, use_kernel: Optional[bool] = None):
    """:func:`topk_mask` of a slot that is mostly empty: the counts run
    over the first ``SELECT_BUCKET * i`` positions that hold ``n_ctx``
    (traced; nothing past it is ``valid``) — one branch a bucket in the
    program, the same mask whichever is taken."""
    N = scores.shape[-1]
    sizes = list(range(SELECT_BUCKET, N, SELECT_BUCKET)) + [N]
    if len(sizes) == 1:
        return topk_mask(scores, k, valid, use_kernel)

    def upto(n):
        def branch(s, v):
            mask, t = topk_mask(s[..., :n], k, v[..., :n], use_kernel)
            return jnp.pad(mask, [(0, 0)] * (s.ndim - 1) + [(0, N - n)]), t

        return branch

    bucket = jnp.sum(jnp.asarray(sizes[:-1], jnp.int32) < n_ctx)
    return jax.lax.switch(bucket, [upto(n) for n in sizes], scores, valid)


# ---------------------------------------------------------------------------
# the pool's third leaf
# ---------------------------------------------------------------------------

def index_cache_write(pool, layer: int, rows, page_table, pos, write_mask=None):
    """Write indexer keys ``rows (B, T, di)`` at logical positions
    ``pos[b] .. pos[b] + T - 1`` of row ``b`` into layer ``layer`` of the
    leaf ``(layers, pages, di, page_len)``, as ``dynamic_update_slice``s
    (a donated pool is updated in place): one position a row at a decode
    step (a column of its page), page by page for a chunk, wherever it
    starts (``inference.paged_cache_write_slices``, for a leaf whose
    positions lie along the lanes)."""
    from deepspeed_tpu.ops.transformer.inference import page_target

    page_len, P = pool.shape[3], page_table.shape[1]
    B, T, di = rows.shape
    rows = jnp.swapaxes(rows.astype(pool.dtype), 1, 2)  # (B, di, T)
    zero, layer = jnp.int32(0), jnp.asarray(layer, jnp.int32)
    if T == 1:
        for b in range(B):
            pid, off = page_target(page_table, b, pos[b], page_len, 1, write_mask)
            pool = jax.lax.dynamic_update_slice(pool, rows[b][None, None], (layer, pid, zero, off))
        return pool
    windows = -(-T // page_len) + 1
    r = jnp.arange(page_len, dtype=jnp.int32)
    padded = jnp.pad(rows, ((0, 0), (0, 0), (page_len, (windows + 1) * page_len - T - page_len)))
    for b in range(B):
        first, shift = pos[b] // page_len, pos[b] % page_len
        for i in range(windows):
            c = i * page_len + r - shift  # position r of logical page first + i is the chunk's index c
            covered = (c >= 0) & (c < T) & (first + i < P)
            pid, _ = page_target(page_table, b, (first + i) * page_len, page_len, page_len, write_mask)
            at = (layer, pid, zero, zero)
            old = jax.lax.dynamic_slice(pool, at, (1, 1, di, page_len))
            new = jax.lax.dynamic_slice_in_dim(padded[b], (i + 1) * page_len - shift, page_len, axis=1)
            pool = jax.lax.dynamic_update_slice(pool, jnp.where(covered[None, None, None, :], new[None, None], old), at)
    return pool


def index_context(pool, layer: int, page_table):
    """The rows' indexer keys in logical order, ``(B, P * page_len, di)``:
    their pages gathered from layer ``layer`` of the leaf."""
    B, P = page_table.shape
    pages, di, page_len = pool.shape[1:]
    flat = pool.reshape((-1,) + pool.shape[2:])  # layers and pages merged: a bitcast, no slice of the layer
    t = jnp.take(flat, (page_table + jnp.int32(layer * pages)).reshape(-1), axis=0)  # (B * P, di, page_len)
    return t.reshape(B, P, di, page_len).transpose(0, 1, 3, 2).reshape(B, P * page_len, di)


# ---------------------------------------------------------------------------
# one layer's attention on the pool
# ---------------------------------------------------------------------------

def selected_decode_reference(q, k_cache, v_cache, page_table, mask, sm_scale: float):
    """The lax form of a decode step's attention over a selection: ``q
    (B, H, 1, d)`` against the rows' gathered pages under ``mask (B, S)``.
    The Mosaic kernel's reference and the CPU's path."""
    from deepspeed_tpu.ops.transformer.inference import paged_gather

    B, H, _, d = q.shape
    gk, gv = paged_gather(k_cache, page_table), paged_gather(v_cache, page_table)  # (B, Hkv, S, d)
    Hkv = gk.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, d)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg, gk.astype(q.dtype), preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask[:, None, None, :], s, NEG)
    p = jnp.where(mask[:, None, None, :], jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgs,bhsd->bhgd", (p / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype), gv.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, 1, d).astype(q.dtype)


def attention(sz: Sizes, lp: Dict[str, Any], u, k_pool, v_pool, layer: int, pos, positions3, page_table,
              write_mask=None, use_kernel: Optional[bool] = None, trace_notes: Optional[dict] = None, work=None,
              eps: float = 1e-6, selection_sink: Optional[list] = None):
    """One layer's learned sparse attention of ``u (B, T, D)`` (the layer's
    normed input) on the pool: ``k_pool = {"k": K pages, "idx": indexer
    keys}``, ``v_pool`` the V pages.  ``pos (B,)`` the rows' write
    offsets; ``positions3 (3, B, T)`` the rotary position streams;
    ``T == 1`` is a decode step (``write_mask`` False sends a row's writes
    to the garbage page and selects nothing for it).  ``lp``: ``q``, ``k``,
    ``v`` (one matrix ``qkv``), ``q_norm``, ``k_norm``, ``index_q``,
    ``index_k``, ``index_w``, ``index_k_gain``, ``index_k_bias``.
    ``selection_sink``, a list, is given the layer's ``(selection mask (B,
    T, P * page_len), threshold (B, T))`` as :func:`topk_mask` returns
    them: the mask the attention below reads and the float32 score it was
    cut at.  Returns ``(o (B, T, H * d), k_pool, v_pool)``."""
    from deepspeed_tpu.ops.kernels import sparse_decode as kern
    from deepspeed_tpu.ops.transformer.inference import (
        chunk_attention_note, layer_pages, paged_cache_write_slices, paged_chunk_attention,
    )

    B, T, _ = u.shape
    H, Hkv, d = sz.heads, sz.kv_heads, sz.head_dim
    page_len, P = v_pool.shape[3], page_table.shape[1]
    S = P * page_len
    f32 = jnp.float32
    with jax.named_scope("dsa.qkv"):
        qkv = (u @ lp["qkv"]).astype(f32)
        q = qkv[..., : H * d].reshape(B, T, H, d)
        k = qkv[..., H * d: (H + Hkv) * d].reshape(B, T, Hkv, d)
        v = qkv[..., (H + Hkv) * d:].reshape(B, T, Hkv, d)
        ang = mrope_angles(positions3, d // 2, sz.theta, sz.sections)
        q = rotate(head_rms(q, lp["q_norm"], eps), ang).astype(u.dtype).transpose(0, 2, 1, 3)  # (B, H, T, d)
        k = rotate(head_rms(k, lp["k_norm"], eps), ang).astype(u.dtype).transpose(0, 2, 1, 3)
        k_pages = paged_cache_write_slices(k_pool["k"], layer, k, page_table, pos, write_mask)
        v_pool = paged_cache_write_slices(v_pool, layer, v.astype(u.dtype).transpose(0, 2, 1, 3), page_table, pos, write_mask)
    q_pos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B, T)
    at = jnp.arange(S, dtype=jnp.int32)
    reach = at[None, None, :] <= q_pos[:, :, None]  # (B, T, S): causal
    if write_mask is not None and T == 1:
        reach = reach & write_mask[:, None, None]
    decode_kernel = T == 1 and kern.supported(B, H, Hkv, P, page_len, d, use_kernel)
    with jax.named_scope("dsa.index"):
        qi, ki, w = index_project(sz, u, lp["index_q"], lp["index_k"], lp["index_w"], lp["index_k_gain"], lp["index_k_bias"],
                                  positions3[0], eps)
        idx_pool = index_cache_write(k_pool["idx"], layer, ki, page_table, pos, write_mask)
        if decode_kernel:
            scores = kern.dsa_index_scores_paged(qi[:, 0], w[:, 0], idx_pool, layer, page_table, pos, work)[:, None]
            form = "dsa_index_scores_paged (float32, highest; the rows' filled pages)"
        else:
            # the keys are read back as the cache holds them: a position's own too, so that a chunk and a decode step rank alike
            scores = chunk_index_scores(qi, w, index_context(idx_pool, layer, page_table), jnp.max(pos) + T)
            form = f"lax einsum by {KV_BLOCK} keys (float32, highest) over the gathered row"
    with jax.named_scope("dsa.select"):
        mask, threshold = topk_mask_upto(scores, sz.topk, reach, jnp.max(pos) + T, use_kernel)
    if selection_sink is not None:
        selection_sink.append((mask, threshold))
    k_pool = {"k": k_pages, "idx": idx_pool}
    with jax.named_scope("dsa.attend"):
        kc, vc, table = layer_pages(k_pages, v_pool, page_table, layer)
        if T == 1:
            if decode_kernel:
                o = kern.dsa_sparse_decode(q, kc, vc, table, pos, mask[:, 0], d ** -0.5, work)
            else:
                o = selected_decode_reference(q, kc, vc, table, mask[:, 0], d ** -0.5)
        else:
            o = paged_chunk_attention(q, kc, vc, table, pos, extra_mask=mask, use_kernel=use_kernel, trace_notes=trace_notes)
    if trace_notes is not None:
        trace_notes["dsa_index_form" if T == 1 else "dsa_prefill_index_form"] = form
        in_vmem = kern.select_supported(B * T, min(S, SELECT_BUCKET), use_kernel)
        trace_notes["dsa_select_form" if T == 1 else "dsa_prefill_select_form"] = (
            f"threshold by bisection on the float32 bits, ties by position (exact; a mask), over the context's first multiple of "
            f"{SELECT_BUCKET}; the counts " + ("in dsa_select_threshold (rows in VMEM)" if in_vmem else "in lax (a pass over the scores each)"))
        if T == 1:
            trace_notes["dsa_decode_kernel"] = ("dsa_sparse_decode (the rows' filled pages under the selection mask)"
                                                if decode_kernel else "lax: gathered rows under the selection mask")
        else:
            trace_notes["dsa_prefill_form"] = ("paged_chunk_attention, dense under the selection mask: "
                                               + chunk_attention_note(trace_notes, "blockwise jnp"))
    return o.transpose(0, 2, 1, 3).reshape(B, T, H * d), k_pool, v_pool
