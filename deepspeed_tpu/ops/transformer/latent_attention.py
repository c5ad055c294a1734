"""Multi-head latent attention (MLA) over a paged latent cache.

The cache holds, per position and layer, **one row for all heads**:
``[c_kv | RoPE(k_pe)]`` (``kv_lora_rank + qk_rope_head_dim`` numbers),
in one buffer ``(layers, pages, width, page_len)``
(``serving/kvcache/pages.py::LatentKV``): a page holds its positions
along the lanes and the row's numbers down the sublanes, because 576
is not a multiple of the 128-lane tile — a ``(page_len, 576)`` page is
one the TPU compiler pads to 640 lanes or relays out (it chose the
transposed device layout for that shape and copied the pool at every
use).  ``w_kvb (c, H, nope + v)``
expands a latent into per-head keys and values.  Two forms of the one
function:

* **expanded** (:func:`expanded_attention`, a prefill chunk): K and V
  are rebuilt per head from the cached latents of the chunk's context,
  ``block_pages`` pages at a time under an online softmax, for as many
  blocks as the context has — nothing of the slot's or the pool's size
  is materialised (one block's scores, statistics and value product in
  :func:`deepspeed_tpu.ops.kernels.mla_prefill.mla_prefill` when the
  kernel suite is armed, the ``jnp`` lines otherwise);
* **absorbed** (:func:`absorbed_attention`, decode): the key half of
  ``w_kvb`` is folded into the query, the value half into the output,
  so every head's query meets the shared row directly
  (:func:`deepspeed_tpu.ops.kernels.mla_decode.mla_decode_paged` when
  the kernel suite is armed, the gather + ``jnp`` form below otherwise).

Both index the pool by ``(layer, page)`` in place: a pool that flows
through a program donated comes out updated without a copy.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.inference import page_target

NEG_INF = -1e30


def latent_cache_write(pool, layer: int, rows, page_table, pos, write_mask=None):
    """Row ``rows[b, t]`` lands at logical position ``pos[b] + t`` of
    slot ``b`` in ``pool[layer]``, through ``page_table (B, P)``.
    ``write_mask (B,)`` False redirects a row's write to the garbage
    page (page 0, position 0).

    Written as ``dynamic_update_slice``s — one position a slot (decode),
    or the whole pages / the part of one page that a chunk starting on
    a chunk boundary covers — because those update a donated pool in
    place in whatever layout it has; an XLA scatter wants the row's
    numbers along the lanes and made the TPU compiler copy the pool
    into that layout and back, once a layer.  Chunks that neither tile
    pages nor fit one fall back to the scatter."""
    page_len = pool.shape[3]
    B, T, W = rows.shape
    rows = rows.astype(pool.dtype)
    zero = jnp.int32(0)
    if T % page_len == 0 or page_len % T == 0:
        span = min(T, page_len)  # positions one update covers: a page, or the chunk inside its page
        for b in range(B):
            for j in range(T // span):
                pid, off = page_target(page_table, b, pos[b] + j * span, page_len, span, write_mask)
                block = rows[b, j * span:(j + 1) * span].T[None, None]  # (1, 1, W, span)
                pool = jax.lax.dynamic_update_slice(pool, block, (jnp.int32(layer), pid, zero, off))
        return pool
    idx = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    idx = jnp.clip(idx, 0, page_table.shape[1] * page_len - 1)
    pid = jnp.take_along_axis(page_table, idx // page_len, axis=1)
    off = idx % page_len
    if write_mask is not None:
        keep = write_mask[:, None].astype(bool)
        pid, off = jnp.where(keep, pid, 0), jnp.where(keep, off, 0)
    return pool.at[layer, pid.reshape(-1), :, off.reshape(-1)].set(rows.reshape(B * T, W))


def _gather_slot(pool, layer: int, pages):
    """The rows of ``pages (..., n)`` of one layer: ``(..., n * page_len, width)``."""
    rows = jnp.swapaxes(pool[layer, pages], -1, -2)  # (..., n, page_len, W)
    return rows.reshape(pages.shape[:-1] + (pages.shape[-1] * pool.shape[3], pool.shape[2]))


def absorbed_attention_reference(q_abs, q_pe, pool, layer: int, page_table, pos, sm_scale: float):
    """The absorbed form in plain ``jnp`` over the gathered slot:
    ``q_abs (B, H, c)``, ``q_pe (B, H, r)`` against rows ``[c_kv | k_pe]``;
    returns ``sum_p p * c_kv`` as ``(B, H, c)`` float32.  Key ``j`` of
    row ``b`` is attendable iff ``j <= pos[b]``."""
    c = q_abs.shape[-1]
    rows = _gather_slot(pool, layer, page_table).astype(jnp.float32)  # (B, S, W)
    q = jnp.concatenate([q_abs, q_pe], axis=-1).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q, rows) * sm_scale
    ok = jnp.arange(rows.shape[1])[None, None, :] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p, rows[..., :c])


def absorbed_attention(q_nope, q_pe, pool, layer: int, page_table, pos, w_kvb, nope: int, sm_scale: float,
                       use_kernel: Optional[bool] = None):
    """Decode: ``q_nope (B, 1, H, nope)``, ``q_pe (B, 1, H, r)``; returns
    ``(B, 1, H, v)``."""
    if use_kernel is None:
        from deepspeed_tpu.ops import kernels as _kernels

        use_kernel = _kernels.flash_decode_armed()
    dt = q_nope.dtype
    q_abs = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_kvb[..., :nope].astype(dt))
    out = None
    if use_kernel:
        from deepspeed_tpu.ops.kernels.mla_decode import mla_decode_paged, mla_decode_supported

        B, H, c = q_abs.shape
        if mla_decode_supported(H, pool.shape[3], pool.shape[2], c):
            out = mla_decode_paged(jnp.concatenate([q_abs, q_pe[:, 0]], axis=-1), pool, layer, page_table, pos,
                                   v_width=c, sm_scale=sm_scale)
    if out is None:
        out = absorbed_attention_reference(q_abs, q_pe[:, 0], pool, layer, page_table, pos, sm_scale)
    return jnp.einsum("bhc,chv->bhv", out.astype(dt), w_kvb[..., nope:].astype(dt))[:, None]


def prefill_form(use_kernel: Optional[bool], H: int, T: int, S: int, nope: int, rope: int, dv: int):
    """Which form a prefill chunk of these shapes takes: ``(kernel,
    why_not)`` — the Mosaic kernel when the suite is armed (or
    ``use_kernel`` says so) and it serves the shapes, else the ``jnp``
    body and the reason; one line of the log for each distinct answer."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.kernels.mla_prefill import mla_prefill_supported

    if use_kernel is None:
        use_kernel = _kernels.flash_decode_armed()
    if not use_kernel:
        why_not = "kernel suite not armed"
    elif not mla_prefill_supported(H, T, S, nope, rope, dv):
        why_not = f"unsupported shape (H {H}, T {T}, S {S}, head dims {nope} / {rope} / {dv})"
    else:
        why_not = ""
    _kernels.warn_once(("mla_prefill", H, T, S, why_not),
                       f"kernels: a prefill chunk (H {H}, T {T}, context blocks of {S}) takes "
                       + (f"the jnp form of expanded_attention: {why_not}" if why_not else "mla_prefill"), level="info")
    return not why_not, why_not


def expanded_attention(q_nope, q_pe, pool, layer: int, page_table, pos, w_kvb, nope: int, sm_scale: float,
                       block_pages: int = 8, use_kernel: Optional[bool] = None, trace_notes: Optional[dict] = None):
    """A chunk: ``q_nope (B, T, H, nope)``, ``q_pe (B, T, H, r)`` at
    positions ``pos[b] + t`` against the slot's cache (the chunk's own
    rows are already written).  Walks the context ``block_pages`` pages
    at a time, as far as the furthest query reaches; returns
    ``(B, T, H, v)``.

    One block's scores, statistics and value product are
    :func:`deepspeed_tpu.ops.kernels.mla_prefill.mla_prefill` where
    :func:`prefill_form` says so, and the ``jnp`` lines below otherwise
    (the CPU, small shapes; the kernel's reference).  ``trace_notes``, a
    dict, is told which (``mla_prefill_kernel``, ``mla_prefill_fallback``:
    ``ServingEngine.stats()``), while tracing."""
    B, T, H, _ = q_nope.shape
    P, page_len = page_table.shape[1], pool.shape[3]
    c = w_kvb.shape[0]
    dv = w_kvb.shape[-1] - nope
    while P % block_pages:
        block_pages -= 1
    S = block_pages * page_len
    dt = q_nope.dtype
    n_blocks = jnp.minimum((jnp.max(pos) + T + S - 1) // S, P // block_pages)
    kernel, why_not = prefill_form(use_kernel, H, T, S, nope, q_pe.shape[-1], dv)
    if trace_notes is not None:
        trace_notes.update(mla_prefill_kernel=kernel, mla_prefill_fallback=why_not)

    def block_rows(j):
        pages = jax.lax.dynamic_slice_in_dim(page_table, j * block_pages, block_pages, axis=1)
        return _gather_slot(pool, layer, pages).astype(dt)  # (B, S, W)

    if kernel:
        from deepspeed_tpu.ops.kernels.mla_prefill import STAT_LANES, mla_prefill

        # head-major, as the kernel tiles them; the K/V expansion stays XLA's
        qn, qp = q_nope.transpose(0, 2, 1, 3), q_pe.transpose(0, 2, 1, 3)
        w_k, w_v = w_kvb[..., :nope].astype(dt), w_kvb[..., nope:].astype(dt)

        def body(j, carry):
            rows = block_rows(j)
            return mla_prefill(qn, qp, jnp.einsum("bsc,chn->bhsn", rows[..., :c], w_k), rows[..., c:],
                               jnp.einsum("bsc,chv->bhsv", rows[..., :c], w_v), pos, j * S, carry, sm_scale)

        stat = (B, H, T, STAT_LANES)  # m and l as the kernel carries them: every lane alike
    else:
        stat = (B, H, T)
        q_pos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B, T)

        def body(j, carry):
            m, l, acc = carry
            rows = block_rows(j)
            kv = jnp.einsum("bsc,chx->bshx", rows[..., :c], w_kvb.astype(dt))  # per-head keys and values
            s = jnp.einsum("bthn,bshn->bhts", q_nope, kv[..., :nope], preferred_element_type=jnp.float32)
            s = s + jnp.einsum("bthr,bsr->bhts", q_pe, rows[..., c:], preferred_element_type=jnp.float32)
            k_pos = j * S + jnp.arange(S, dtype=jnp.int32)
            ok = k_pos[None, None, None, :] <= q_pos[:, None, :, None]
            s = jnp.where(ok, s * sm_scale, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bhts,bshv->bhtv", p.astype(dt), kv[..., nope:],
                                                      preferred_element_type=jnp.float32)
            return m_new, l, acc

    init = (jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32), jnp.zeros((B, H, T, dv), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    l = l.reshape(B, H, T, -1)[..., 0]
    return (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).transpose(0, 2, 1, 3).astype(dt)
