"""Inference transformer ops — KV-cache prefill/decode path.

TPU-native replacement for the reference's latency-optimized inference
kernels (``csrc/transformer/inference/csrc/``: softmax.cu, normalize.cu,
gelu.cu bound in ``pt_binding.cpp:596-631``) and the Python module that
drives them (``ops/transformer/inference/transformer_inference.py``:
``DeepSpeedInferenceConfig`` :28, ``DeepSpeedTransformerInference`` with
"layer_past" KV-cache support).

Design (vs the reference's per-op CUDA kernels):

* Everything is expressed as jittable functions over a **static-shape KV
  cache** — XLA fuses bias+gelu, bias+residual, and layernorm chains that
  the reference hand-fused, and ``lax.dynamic_update_slice`` gives the
  in-place cache write (donated buffers make it a true in-place update).
* **Prefill** (T prompt tokens, empty cache) runs the flash-attention
  Pallas kernel over the prompt block, then writes K/V into the cache.
* **Decode** (T=1) attends the single query against the cache with a
  position mask — a skinny (1×S)·(S×d) matvec chain that XLA maps onto
  the MXU/VPU; no Python-visible loop.
* Tensor-parallel inference = PartitionSpecs on the weights (column-split
  qkv/fc, row-split projections) — GSPMD inserts the all-reduces the
  reference issues explicitly inside its fused kernels.

Layer-parameter layout matches ``models/gpt2.py`` blocks (a dict with
``ln1_*, qkv_*, proj_*, ln2_*, fc_*, fc_proj_*``), stacked on a leading
layer dim so the whole network scans.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import with_layout_constraint

from deepspeed_tpu.ops.attention.flash_attention import flash_attention, mha_reference
from deepspeed_tpu.ops.normalize import layer_norm as _ln
from deepspeed_tpu.ops.registry import register_op

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class DeepSpeedInferenceConfig:
    """Reference ``DeepSpeedInferenceConfig``
    (``ops/transformer/inference/transformer_inference.py:28``)."""

    hidden_size: int = 768
    heads: int = 12
    layer_norm_eps: float = 1e-5
    mp_size: int = 1
    dtype: Any = jnp.bfloat16
    max_out_tokens: int = 1024  # static KV-cache capacity
    pre_layer_norm: bool = True
    use_flash_attention: bool = True
    # MoE decode (used when the layer params carry gate_w/w1/b1/w2/b2);
    # eval capacity must match the train model's EVAL path (moe/layer.py
    # MoEConfig.eval_capacity_factor default) or decode diverges
    moe_top_k: int = 2
    moe_eval_capacity_factor: float = 2.0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.heads

    @classmethod
    def for_model(cls, mcfg, dtype, mp_size: int, max_len: int) -> "DeepSpeedInferenceConfig":
        """The fused-block config of a GPT-2-layout model config for a cache of capacity ``max_len``."""
        return cls(hidden_size=mcfg.n_embd, heads=mcfg.n_head, layer_norm_eps=mcfg.layer_norm_epsilon, mp_size=mp_size, dtype=dtype,
                   max_out_tokens=int(max_len), use_flash_attention=mcfg.use_flash_attention, moe_top_k=getattr(mcfg, "moe_top_k", 2))


def _wmm(h: jnp.ndarray, w) -> jnp.ndarray:
    """Weight matmul that understands int8-packed weights
    (``{"q": int8, "s": f32}`` from ``pack_int8_tree``): computes
    ``(h @ q) * s`` so the int8 tensor is what streams from HBM."""
    if isinstance(w, dict):
        from deepspeed_tpu.ops.quantizer.quantizer import int8_matmul

        return int8_matmul(h, w["q"], w["s"])
    return h @ w.astype(h.dtype)


def init_kv_cache(n_layer: int, batch: int, heads: int, max_len: int, head_dim: int, dtype=jnp.bfloat16,
                  v_dim: Optional[int] = None):
    """Static-capacity KV cache, stacked on a leading layer dim so it scans
    with the stacked blocks (the reference grows ``layer_past`` tensors
    per step; static shapes are the XLA-friendly equivalent).

    ``dtype="int8"``: each cache is a ``{"q": int8, "s": f32}`` pair —
    per-(b,h,pos) absmax row quantization over head_dim.  ~2× less HBM
    traffic per decoded token than bf16 for the cache read (the term
    that grows with context length).

    ``v_dim``: the value's width where it is not the key's (the V cache's
    last dim; each as wide as it is, neither padded to the other)."""
    shape = (n_layer, batch, heads, max_len, head_dim)
    v_shape = shape if v_dim is None else shape[:-1] + (int(v_dim),)
    if dtype == "int8" or dtype == jnp.int8:
        pair = lambda sh: {"q": jnp.zeros(sh, jnp.int8), "s": jnp.zeros(sh[:-1] + (1,), jnp.float32)}  # noqa: E731
        return pair(shape), pair(v_shape)
    return jnp.zeros(shape, dtype), jnp.zeros(v_shape, dtype)


def _kv_quant(t: jnp.ndarray):
    """(..., d) -> (int8 codes, f32 per-row scale): absmax over head_dim."""
    t32 = t.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(t32), axis=-1, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(t32 / s), -127, 127).astype(jnp.int8)
    return q, s


def _per_slot(pos) -> bool:
    """True when ``pos`` is a per-example (B,) write-offset vector — the
    continuous-batching slot-pool form (serving/) where every batch row
    is an independent sequence at its own position."""
    return getattr(pos, "ndim", 0) == 1


def slot_cache_write(cache, t, pos):
    """Per-slot cache write: row ``b`` of ``t`` (B, H, T, d) lands at
    ``[b, :, pos[b]:pos[b]+T, :]`` of ``cache`` (B, H, S, d).  The write
    start clamps like ``dynamic_update_slice`` — callers (the serving
    pool) must keep ``pos[b] + T <= S``."""
    return jax.vmap(
        lambda c, x, p: jax.lax.dynamic_update_slice(c, x, (0, p, 0))
    )(cache, t, pos)


def paged_gather(cache, page_table):
    """Materialize the logical slot view of a page pool: gather
    ``cache`` (num_pages, H, page_len, d) — or the int8 code+scale dict
    — through ``page_table`` (B, pages_per_slot) into the contiguous
    (B, H, pages_per_slot*page_len, d) layout :func:`cache_attention`
    consumes.  Unused table entries point at the reserved garbage page;
    their rows are never attendable (position mask), so the gathered
    view is value-identical to the slot-contiguous cache at every
    attendable position — the bit-match lever of the paged design
    (docs/serving.md §Paged KV & prefix caching)."""

    def g(buf):
        B, P = page_table.shape
        t = jnp.take(buf, page_table.reshape(-1), axis=0)
        t = t.reshape(B, P, buf.shape[1], buf.shape[2], buf.shape[3])
        return t.transpose(0, 2, 1, 3, 4).reshape(
            B, buf.shape[1], P * buf.shape[2], buf.shape[3]
        )

    if isinstance(cache, dict):
        return {name: g(buf) for name, buf in cache.items()}
    return g(cache)


def paged_cache_write(cache, t, page_table, pos, write_mask=None):
    """Per-slot token write through a page table: row ``b`` of ``t``
    (B, H, T, d) lands at logical positions ``pos[b]:pos[b]+T`` of slot
    ``b``, scattered into ``cache`` (num_pages, H, page_len, d) via
    ``page_table[b]``.  ``write_mask`` (B,) False redirects a row's
    writes to (garbage page, row 0) — how a fixed-shape decode step
    keeps non-decoding slots from touching real pages (the paged
    analogue of the safe-position invariant).  int8 caches quantize
    rows exactly like :func:`slot_cache_write`.

    **The reference form**, for tests and ``chip_smoke.py``: no serving
    program calls it.  An XLA scatter over the (page, position) dims
    relays a pool out and back on the TPU (PERF.md, PRs 32 and 40); the
    programs write through :func:`paged_cache_write_slices`."""
    quant = isinstance(cache, dict)
    page_len = (cache["q"] if quant else cache).shape[2]
    B, H, T, _ = t.shape
    idx = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B, T)
    idx = jnp.clip(idx, 0, page_table.shape[1] * page_len - 1)
    pid = jnp.take_along_axis(page_table, idx // page_len, axis=1)
    off = idx % page_len
    if write_mask is not None:
        keep = write_mask[:, None].astype(bool)
        pid = jnp.where(keep, pid, 0)
        off = jnp.where(keep, off, 0)
    pid_f, off_f = pid.reshape(-1), off.reshape(-1)

    def scat(buf, vals):  # vals (B, H, T, x) -> rows (B*T, H, x)
        rows = vals.transpose(0, 2, 1, 3).reshape(B * T, H, vals.shape[-1])
        return buf.at[pid_f, :, off_f, :].set(rows.astype(buf.dtype))

    if quant:
        cq, cs = _kv_quant(t)
        return {"q": scat(cache["q"], cq), "s": scat(cache["s"], cs)}
    return scat(cache, t)


# ``stats()["kv_write_form"]`` of a paged per-head pool, by :func:`decode_write_takes_kernel`
KV_WRITE_FORMS = {False: "slices, in place", True: "paged_kv_write a decode step, slices a chunk, in place"}


def page_target(page_table, b, at, page_len: int, span: int = 1, write_mask=None):
    """``(page id, offset)`` of logical position ``at`` of row ``b`` (an
    int, or every row's index beside every row's ``at``),
    clipped so that ``span`` positions from it fit the slot; a row whose
    ``write_mask`` is False goes to the garbage page (page 0, offset 0).
    The index arithmetic of every slice-wise cache write (here and
    ``latent_attention.latent_cache_write``)."""
    at = jnp.clip(at, 0, page_table.shape[1] * page_len - span)
    pid, off = page_table[b, at // page_len], at % page_len
    if write_mask is not None:
        pid, off = jnp.where(write_mask[b], pid, 0), jnp.where(write_mask[b], off, 0)
    return pid, off


def decode_write_takes_kernel(pool, use_kernel: Optional[bool] = None) -> bool:
    """Whether a decode step's rows go into ``pool`` (a stacked pool, or
    its int8 code+scale pair, which goes the way of its codes) through
    ``paged_kv_write``: the kernel suite armed (``use_kernel`` None) and
    a head narrower than the lanes in pages of whole lane rows — read
    off the pool's shape, as ``flash_decode_paged`` reads its tile."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.kernels.paged_kv_write import paged_kv_write_supported

    armed = _kernels.flash_decode_armed() if use_kernel is None else use_kernel
    return armed and paged_kv_write_supported(*(pool["q"] if isinstance(pool, dict) else pool).shape[3:])


def decode_write_plan(pool, page_table, pos, write_mask=None, use_kernel: Optional[bool] = None):
    """What a decode step's writes into ``pool`` share across its layers
    — ``paged_kv_write``'s prefetched scalars: every row's
    :func:`page_target` and the compacted list of the rows that write —
    or ``None`` where the pool takes its rows as slices
    (:func:`decode_write_takes_kernel`).  A layer loop builds it once,
    outside its body, and hands it to :func:`paged_cache_write_slices`."""
    if not decode_write_takes_kernel(pool, use_kernel):
        return None
    from deepspeed_tpu.ops.kernels.paged_kv_write import paged_write_plan

    page_len = jax.tree.leaves(pool)[0].shape[3]
    return paged_write_plan(*page_target(page_table, jnp.arange(page_table.shape[0]), pos, page_len, 1, write_mask), write_mask)


def paged_cache_write_slices(pool, layer, t, page_table, pos, write_mask=None, use_kernel: Optional[bool] = None, plan=None):
    """:func:`paged_cache_write` into layer ``layer`` (a Python int or a
    traced scalar) of a stacked pool ``(layers, num_pages, H, page_len,
    d)`` — or of the int8 code+scale pair of such pools, the rows
    quantized as :func:`paged_cache_write` does — written as
    ``dynamic_update_slice``s, because those update a donated pool in
    place in the layout it has.  An XLA scatter over the (page,
    position) dims wants ``page_len`` ahead of ``H`` in memory: on the
    TPU it copied both pools (2 x 1.3 GB at 5,121 pages of 8 x 128 x
    128) into that layout and back, every step.

    One position a row (decode) is one update where the pool is
    row-major — ``d`` of whole lane rows — and **one aliased Mosaic call
    for all rows** where a head is narrower than the lanes and the pool
    so lies with ``page_len`` in them (:func:`decode_write_takes_kernel`;
    ``ops/kernels/paged_kv_write.py``): a position is then a column of
    ``H x d`` lane rows, ~5 us an update whatever it moves, and the call
    rewrites the page's tile instead — the same bytes in the same place,
    bit for bit; ``plan``, where the caller built it once for all its
    layers (:func:`decode_write_plan`), saves building it here.  A chunk
    (``T > 1``) is
    written **page by page, wherever it starts**: each of the
    ``ceil(T / page_len) + 1`` pages it can touch is read, the positions
    the chunk covers replaced, and written back — a start inside a page
    (a prefix hit, any future caller) splits at the page edges instead of
    being clamped onto a boundary, and positions past the slot's last
    page are dropped."""
    if isinstance(pool, dict):
        cq, cs = _kv_quant(t)
        use_kernel = decode_write_takes_kernel(pool, use_kernel)  # the scales follow the codes
        return {"q": paged_cache_write_slices(pool["q"], layer, cq, page_table, pos, write_mask, use_kernel, plan),
                "s": paged_cache_write_slices(pool["s"], layer, cs, page_table, pos, write_mask, use_kernel, plan)}
    page_len, P = pool.shape[3], page_table.shape[1]
    B, H, T, d = t.shape
    t = t.astype(pool.dtype)
    zero, layer = jnp.int32(0), jnp.asarray(layer, jnp.int32)
    if T == 1:
        if decode_write_takes_kernel(pool, use_kernel):
            from deepspeed_tpu.ops.kernels.paged_kv_write import paged_kv_write

            return paged_kv_write(pool, layer, t, plan or decode_write_plan(pool, page_table, pos, write_mask, True))
        for b in range(B):
            pid, off = page_target(page_table, b, pos[b], page_len, 1, write_mask)
            pool = jax.lax.dynamic_update_slice(pool, t[b][None, None], (layer, pid, zero, off, zero))
        return pool
    windows = -(-T // page_len) + 1
    r = jnp.arange(page_len, dtype=jnp.int32)
    padded = jnp.pad(t, ((0, 0), (0, 0), (page_len, (windows + 1) * page_len - T - page_len), (0, 0)))
    for b in range(B):
        first, shift = pos[b] // page_len, pos[b] % page_len
        for i in range(windows):
            # position r of logical page first + i is the chunk's index c
            c = i * page_len + r - shift
            covered = (c >= 0) & (c < T) & (first + i < P)
            pid, _ = page_target(page_table, b, (first + i) * page_len, page_len, page_len, write_mask)
            at = (layer, pid, zero, zero, zero)
            old = jax.lax.dynamic_slice(pool, at, (1, 1, H, page_len, d))
            new = jax.lax.dynamic_slice_in_dim(padded[b], (i + 1) * page_len - shift, page_len, axis=1)
            pool = jax.lax.dynamic_update_slice(pool, jnp.where(covered[None, None, None, :, None], new[None, None], old), at)
    return pool


def page_copy(pool, src, dst):
    """Page ``src`` of every layer onto page ``dst`` of a stacked pool
    ``(layers, num_pages, ...)`` (any pytree of them): one slice read and
    one slice written in place — the copy-on-write of a prefill program.
    ``src == dst`` (the garbage page onto itself, when nothing pends) is
    the identity."""
    def one(buf):
        at = lambda p: (jnp.int32(0), jnp.asarray(p, jnp.int32)) + (jnp.int32(0),) * (buf.ndim - 2)  # noqa: E731
        page = jax.lax.dynamic_slice(buf, at(src), (buf.shape[0], 1) + buf.shape[2:])
        return jax.lax.dynamic_update_slice(buf, page, at(dst))

    return jax.tree.map(one, pool)


def layer_pages(k_pool, v_pool, page_table, layer):
    """Layer ``layer`` (an int or a traced scalar) of the stacked pools
    as the ``(pages, H, page_len, d)`` caches the paged attentions take,
    **where it lies**: the two leading dims merged (a bitcast) and the
    page table offset to match.  A ``pool[layer]`` slice in front of a
    kernel is a copy of the layer.  Returns ``(k, v, table)``."""
    pages = jax.tree.leaves(k_pool)[0].shape[1]
    merged = lambda c: jax.tree.map(lambda p: p.reshape((-1,) + p.shape[2:]), c)  # noqa: E731
    return merged(k_pool), merged(v_pool), page_table + jnp.asarray(layer * pages, jnp.int32)


def state_rows(buf, layer: int, slot):
    """Rows ``slot (B,)`` of one layer of a ``(layers, slots, ...)``
    per-slot state buffer (``HybridKV.state_buffers``)."""
    tail = buf.shape[2:]
    return jnp.concatenate([jax.lax.dynamic_slice(buf, (layer, slot[b]) + (0,) * len(tail), (1, 1) + tail)[0]
                            for b in range(slot.shape[0])], axis=0)


def state_rows_write(buf, layer: int, slot, rows):
    """The inverse, as ``dynamic_update_slice``s: a donated buffer is updated in place."""
    for b in range(slot.shape[0]):
        buf = jax.lax.dynamic_update_slice(buf, rows[b][None, None].astype(buf.dtype),
                                           (jnp.int32(layer), slot[b]) + (jnp.int32(0),) * (buf.ndim - 2))
    return buf


def paged_cache_attention(q, k_cache, v_cache, page_table, pos,
                          sm_scale: Optional[float] = None,
                          use_kernel: Optional[bool] = None,
                          work=None, trace_notes: Optional[dict] = None):
    """Attend (B,H,T,d) queries against a paged cache.  Single-query
    steps dispatch to the fused paged flash-decode kernel when the
    kernel suite is armed and the page geometry qualifies (the page
    table rides the grid as a prefetched scalar, so k/v pages stream
    straight from HBM without materializing the gather); otherwise the
    gather + :func:`cache_attention` lax path below is the numerics
    ground truth, bit-matching the slot-contiguous cache.

    ``work`` is the kernel's work list (``flash_decode.paged_work_list``
    under ``paged_tile``'s span; ``None``: the filled pages of every
    row): the kernel walks its items and nothing else, and the rows it
    does not visit read 0.  The lax path attends every row and does not
    read it.  ``trace_notes`` is told when the kernel took the step, and
    the tile of a grid step (``paged_decode_walk``)."""
    quant = isinstance(k_cache, dict)
    if use_kernel is None:
        from deepspeed_tpu.ops import kernels as _kernels

        use_kernel = _kernels.flash_decode_armed()
    if use_kernel and q.shape[2] == 1:
        from deepspeed_tpu.ops.kernels.flash_decode import (
            decode_paged_supported, flash_decode_paged, paged_tile,
        )

        B, H, _, d = q.shape
        page_len = (k_cache["q"] if quant else k_cache).shape[2]
        if decode_paged_supported(B, H, page_table.shape[1], page_len, d):
            if trace_notes is not None:
                heads, span = paged_tile(k_cache, page_table.shape[1], v_cache)
                trace_notes["paged_decode_walk"] = f"work list, {heads} heads x {span} page{'s' if span > 1 else ''}"
            return flash_decode_paged(
                q, k_cache, v_cache, page_table, pos, sm_scale=sm_scale, work=work
            )
    gk = paged_gather(k_cache, page_table)
    gv = paged_gather(v_cache, page_table)
    group = q.shape[1] // jax.tree.leaves(gk)[0].shape[1]
    if group > 1:  # grouped queries: query head i attends KV head i // group
        gk, gv = jax.tree.map(lambda a: jnp.repeat(a, group, axis=1), (gk, gv))
    return cache_attention(q, gk, gv, pos, sm_scale=sm_scale, use_kernel=False)


def chunk_attention_form(use_kernel: Optional[bool], quant: bool, H: int, Hkv: int, T: int, d: int, page_len: int,
                         d_v: Optional[int] = None):
    """Which form a prefill chunk of these shapes takes over its pages:
    ``(kernel, why_not)`` — ``flash_chunk_paged`` when the suite is armed
    (or ``use_kernel`` says so) and the kernel serves what the input
    shows, else the ``jnp`` walk and the reason; one line of the log for
    each distinct answer."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.kernels.flash_chunk import flash_chunk_unsupported

    if use_kernel is None:
        use_kernel = _kernels.flash_decode_armed()
    why_not = flash_chunk_unsupported(T, d, page_len, quant, d_v) if use_kernel else "kernel suite not armed"
    widths = d if d_v in (None, d) else f"{d} / {d_v}"
    _kernels.warn_once(("flash_chunk_paged", H, Hkv, T, widths, why_not),
                       f"kernels: a prefill chunk's attention over its pages ({H} / {Hkv} heads of {widths}, T {T}) takes "
                       + (f"the jnp form of paged_chunk_attention: {why_not}" if why_not else "flash_chunk_paged"), level="info")
    return not why_not, why_not


def chunk_attention_note(trace_notes: dict, jnp_form: str = "blockwise jnp (paged_chunk_attention)") -> str:
    """A family's form note (``gqa_prefill_form`` …) from what
    :func:`paged_chunk_attention` told ``trace_notes``: the kernel, or
    the ``jnp`` form and why."""
    if trace_notes["chunk_attention_kernel"]:
        return "flash_chunk_paged (the slot's pages where they lie, scores in VMEM)"
    return f"{jnp_form}: {trace_notes['chunk_attention_fallback']}"


def paged_chunk_attention(q, k_cache, v_cache, page_table, pos, sm_scale: Optional[float] = None,
                          block_pages: int = 4, extra_mask=None, use_kernel: Optional[bool] = None,
                          trace_notes: Optional[dict] = None):
    """A prefill chunk against a paged cache, **block by block over its
    context** under an online softmax: ``q (B, H, T, d)`` at positions
    ``pos[b] + t`` (the chunk's own keys already written), caches
    ``(num_pages, Hkv, page_len, d)`` — bf16/f32, or the int8 code+scale
    pair, whose scales fold in on the scores and the probabilities as in
    :func:`cache_attention` — with ``H`` a multiple of ``Hkv`` (query
    head ``i`` attends KV head ``i // (H / Hkv)``).  Walks
    ``block_pages`` pages at a time as far as the furthest query reaches:
    one block's ``(B, H, T, block_pages * page_len)`` float32 scores are
    the most that exists, never a slot's or the pool's length.  The
    pool is read where it lies — gathered a block at a time, or, a head
    narrower than the lanes, the rows' own pages sliced out once.
    ``extra_mask (B, T, P * page_len)`` bool, where given, is a per-query
    selection of the context applied beside the causal mask (learned
    sparse attention: the same dense walk, fewer keys let through).
    Returns ``(B, H, T, d_v)`` in ``q``'s dtype, ``d_v`` the V cache's
    width (the key's ``d`` everywhere but where a family's values are
    narrower than its keys).

    The walk is the Mosaic kernel
    :func:`deepspeed_tpu.ops.kernels.flash_chunk.flash_chunk_paged` — the
    score tile in VMEM, the pages read through the table — where
    :func:`chunk_attention_form` says so, by what the input shows, and
    the ``jnp`` lines below otherwise (a head narrower than the lanes,
    the int8 pool, small shapes, the CPU; the kernel's reference).
    ``trace_notes``, a dict, is told which (``chunk_attention_kernel``,
    ``chunk_attention_fallback``: ``ServingEngine.stats()``), while
    tracing."""
    quant = isinstance(k_cache, dict)
    B, H, T, d = q.shape
    _, Hkv, page_len, _ = (k_cache["q"] if quant else k_cache).shape
    dv = (v_cache["q"] if quant else v_cache).shape[-1]
    P, G = page_table.shape[1], H // Hkv
    kernel, why_not = chunk_attention_form(use_kernel, quant, H, Hkv, T, d, page_len, dv)
    if trace_notes is not None:
        trace_notes.update(chunk_attention_kernel=kernel, chunk_attention_fallback=why_not)
    if kernel:
        from deepspeed_tpu.ops.kernels.flash_chunk import flash_chunk_paged

        return flash_chunk_paged(q, k_cache, v_cache, page_table, pos, sm_scale=sm_scale, extra_mask=extra_mask)
    while P % block_pages:
        block_pages -= 1
    S = block_pages * page_len
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    n_blocks = jnp.minimum((jnp.max(pos) + T + S - 1) // S, P // block_pages)
    qg = q.reshape(B, Hkv, G, T, d)
    q_pos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B, T)

    if d % 128 or dv % 128:
        # A head narrower than the 128 lanes lies with its positions in
        # the lanes (flash_decode_paged).  A gather wants its operand
        # row-major, and a loop body that slices the pool picks a layout
        # of its own for it: either relays the whole pool out in front of
        # the loop.  So the rows' own pages are sliced out of the pool
        # here, in the layout it was handed in, and the loop walks that
        # copy of the rows' contexts (one layer of ``B`` slots)
        def context(cache):  # (B, Hkv, P * page_len, x)
            at = lambda page: (page,) + (jnp.int32(0),) * 3  # noqa: E731
            t = jnp.concatenate([jax.lax.dynamic_slice(cache, at(page_table[b, i]), (1,) + cache.shape[1:])
                                 for b in range(B) for i in range(P)], axis=0)
            return t.reshape(B, P, Hkv, page_len, cache.shape[-1]).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, P * page_len, -1)

        k_cache, v_cache = jax.tree.map(context, (k_cache, v_cache))

        def rows(ctx, j):  # (B, Hkv, S, x): block j of every row's context
            return jax.lax.dynamic_slice_in_dim(ctx, j * S, S, axis=2)
    else:
        def rows(cache, j):
            pages = jax.lax.dynamic_slice_in_dim(page_table, j * block_pages, block_pages, axis=1)
            t = jnp.take(cache, pages.reshape(-1), axis=0).reshape(B, block_pages, Hkv, page_len, cache.shape[-1])
            return t.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, S, cache.shape[-1])

    def operand(cache, j):  # the block's rows as a dot operand, and their per-row scales (B, Hkv, 1, 1, S) if int8
        if quant:
            return rows(cache["q"], j).astype(q.dtype), rows(cache["s"], j)[..., 0][:, :, None, None, :]
        return rows(cache, j).astype(q.dtype), None

    def body(j, carry):
        m, l, acc = carry
        k, k_scale = operand(k_cache, j)
        s = jnp.einsum("bhgtd,bhsd->bhgts", qg, k, preferred_element_type=jnp.float32) * sm_scale
        if quant:
            s = s * k_scale
        k_pos = j * S + jnp.arange(S, dtype=jnp.int32)
        ok = k_pos[None, None, :] <= q_pos[:, :, None]  # (B, T, S)
        if extra_mask is not None:
            ok = ok & jax.lax.dynamic_slice_in_dim(extra_mask, j * S, S, axis=2)
        s = jnp.where(ok[:, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if extra_mask is not None:  # a block in which a query selected nothing adds nothing (exp(0) of its all-masked row is 1)
            p = jnp.where(ok[:, None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None]
        if quant:
            v, v_scale = operand(v_cache, j)
            p = (p * v_scale).astype(q.dtype)
        else:  # traced in the order the wide-head programs always were: their lowered text is the parent's
            p = p.astype(q.dtype)
            v, _ = operand(v_cache, j)
        acc = acc + jnp.einsum("bhgts,bhsd->bhgtd", p, v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    stat = (B, Hkv, G, T)
    init = (jnp.full(stat, -1e30, jnp.float32), jnp.zeros(stat, jnp.float32), jnp.zeros(stat + (dv,), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    return (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).reshape(B, H, T, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# window layers: a per-slot ring of pages (docs/serving.md §Cache kinds, ``WindowedKV``)
# ---------------------------------------------------------------------------

def ring_pages_for(window: int, page_len: int) -> int:
    """Pages a slot's ring holds for a layer that attends over its last
    ``window`` positions (the query's own among them): the page the
    newest position lies in and the ``ceil((window - 1) / page_len)``
    before it — whatever the request's length."""
    return -(-(int(window) - 1) // int(page_len)) + 1


def ring_table(slot, ring_pages: int, pages_per_slot: int):
    """The window group's page table, made in the program from nothing
    but the rows' slots: **logical** page ``lp`` of slot ``s`` is page
    ``1 + s * ring_pages + lp % ring_pages`` of the group (page 0 is its
    garbage page), ``(B, pages_per_slot)`` int32.  Under it the paged
    writes and the paged decode kernel address a ring as they address
    pages by length; what a page holds of an earlier lap is outside the
    window and behind the band mask."""
    lap = jnp.arange(pages_per_slot, dtype=jnp.int32) % ring_pages
    return 1 + jnp.asarray(slot, jnp.int32)[:, None] * ring_pages + lap[None, :]


def ring_chunk_write(pool, layer, t, ring, pos, n_valid, ring_pages: int):
    """A prefill chunk's rows ``t (B, Hkv, T, d)`` at **page-aligned**
    ``pos (B,)`` into layer ``layer`` of the window group ``pool
    (layers, pages, Hkv, page_len, d)``: of the chunk's ``T / page_len``
    pages, those the ring keeps — the last ``ring_pages`` up to the page
    of the row's last real token (``n_valid (B,)`` real tokens; the
    padded tail's pages would lap over what a decode step still needs) —
    go to their ring pages as whole-page slices, the others to the
    group's garbage page."""
    page_len = pool.shape[3]
    B, H, T, d = t.shape
    t = t.astype(pool.dtype)
    zero, layer = jnp.int32(0), jnp.asarray(layer, jnp.int32)
    for b in range(B):
        first = pos[b] // page_len
        last = (pos[b] + n_valid[b] - 1) // page_len
        for i in range(T // page_len):
            lp = first + i
            keep = (lp <= last) & (lp > last - ring_pages)
            pid = jnp.where(keep, ring[b, jnp.clip(lp, 0, ring.shape[1] - 1)], 0)
            pool = jax.lax.dynamic_update_slice(pool, t[b, :, i * page_len:(i + 1) * page_len][None, None], (layer, pid, zero, zero, zero))
    return pool


def sink_softmax(s, sink):
    """Softmax over the last axis of float32 scores ``s`` with **one more
    column** of logit ``sink`` (broadcast against ``s[..., :1]``) in the
    maximum and the denominator: the column takes its share of the mass
    and is not returned — the probabilities sum to less than one."""
    sink = sink.astype(jnp.float32)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
    e = jnp.exp(s - m)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))


def window_chunk_attention(q, k, v, k_cache, v_cache, ring, pos, window: int, sm_scale: Optional[float] = None,
                           query_block: int = 256, sink=None):
    """A prefill chunk of a **window layer**: ``q (B, H, T, d)`` at
    positions ``pos[b] + t`` (``pos`` page-aligned) over the band ``p -
    window < j <= p``.  The chunk's own keys and values ``k, v (B, Hkv,
    T, d)`` are attended **where they are** (they are not in the ring
    yet: a ring shorter than the chunk could not hold them), in front of
    them the ``ceil((window - 1) / page_len)`` pages before the chunk's
    first, gathered once from the layer's ring ``k_cache, v_cache
    (pages, Hkv, page_len, d)`` under ``ring (B, pages_per_slot)``.  The
    walk is **banded**: query block ``i`` of ``query_block`` queries
    meets the ``earlier + query_block`` keys from its window's first
    page to its own last query — a static slice — under the band mask,
    and no block outside the band is computed, whatever the context.
    Grouped queries as :func:`paged_chunk_attention`.  ``sink (H,)``
    float32, where given, is a learned logit a head that stands as **one
    more column of every query's softmax** — it takes mass and carries no
    value (attention sinks) — entering the row's maximum and denominator
    and nothing else.  Returns ``(B, H, T, d_v)`` in ``q``'s dtype (``d_v``
    the values' width, the keys' unless a family's differ)."""
    B, H, T, d = q.shape
    _, Hkv, page_len, _ = k_cache.shape
    dv = v.shape[-1]
    G, n_prev = H // Hkv, ring_pages_for(window, page_len) - 1
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    E = n_prev * page_len  # earlier positions in front of the chunk's own
    with jax.named_scope("swa.chunk"):
        lp = pos[:, None] // page_len - n_prev + jnp.arange(n_prev, dtype=jnp.int32)[None, :]  # (B, n_prev), negative before the sequence
        pages = jnp.take_along_axis(ring, jnp.clip(lp, 0, ring.shape[1] - 1), axis=1)

        # (B, Hkv, E + T, d): the window's earlier pages out of the ring, then the chunk's own rows
        keys = jnp.concatenate([paged_gather(k_cache, pages).astype(q.dtype), k.astype(q.dtype)], axis=2)
        vals = jnp.concatenate([paged_gather(v_cache, pages).astype(q.dtype), v.astype(q.dtype)], axis=2)
        Tq = min(T, query_block)
        while T % Tq:
            Tq -= 1
        qg = q.reshape(B, Hkv, G, T, d)
        out = []
        for i in range(T // Tq):
            # the block's queries at chunk offsets i*Tq ... ; its keys at offsets i*Tq - E ... i*Tq + Tq - 1 (index i*Tq of ``keys``)
            ks, vs = keys[:, :, i * Tq: i * Tq + E + Tq], vals[:, :, i * Tq: i * Tq + E + Tq]
            s = jnp.einsum("bhgtd,bhsd->bhgts", qg[:, :, :, i * Tq:(i + 1) * Tq], ks, preferred_element_type=jnp.float32) * sm_scale
            q_off = i * Tq + jnp.arange(Tq, dtype=jnp.int32)[:, None]        # relative to the chunk's first position
            k_off = i * Tq - E + jnp.arange(E + Tq, dtype=jnp.int32)[None, :]
            band = (k_off <= q_off) & (k_off > q_off - window)               # (Tq, E + Tq)
            ok = band[None] & (pos[:, None, None] + k_off[None] >= 0)        # nothing lies before the sequence
            s = jnp.where(ok[:, None, None], s, -1e30)
            if sink is None:
                p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            else:
                p = sink_softmax(s, sink.reshape(1, Hkv, G, 1, 1)).astype(q.dtype)
            out.append(jnp.einsum("bhgts,bhsd->bhgtd", p, vs, preferred_element_type=jnp.float32))
        return jnp.concatenate(out, axis=3).reshape(B, H, T, dv).astype(q.dtype)


def window_cache_attention(q, k_cache, v_cache, ring, pos, window: int, sm_scale: Optional[float] = None,
                           use_kernel: Optional[bool] = None, work=None, trace_notes: Optional[dict] = None, sink=None):
    """One query a row against a window layer's ring: ``q (B, H, 1, d)``
    at ``pos (B,)`` (its own key already written) over positions ``pos -
    window < j <= pos``.  The paged decode kernel under its window form
    (``swa_decode_paged``: ``flash_decode_paged(..., window=)``, ``work``
    its list of the window's spans) where the suite is armed and the
    page geometry qualifies; else the ring's pages gathered and attended
    in ``jnp`` — the numerics ground truth.  ``sink (H,)`` float32 as
    :func:`window_chunk_attention` takes it; the output is as wide as the
    V ring's rows."""
    B, H, _, d = q.shape
    _, Hkv, page_len, _ = k_cache.shape
    dv = v_cache.shape[-1]
    said = "" if sink is None and dv == d else f"; keys {d} / values {dv} wide" + ("" if sink is None else ", a sink column a head")
    if use_kernel is None:
        from deepspeed_tpu.ops import kernels as _kernels

        use_kernel = _kernels.flash_decode_armed()
    if use_kernel:
        from deepspeed_tpu.ops.kernels.flash_decode import decode_paged_supported, flash_decode_paged, paged_tile

        if decode_paged_supported(B, H, ring.shape[1], page_len, d):
            if trace_notes is not None:
                heads, span = paged_tile(k_cache, ring.shape[1], v_cache)
                trace_notes["swa_decode_form"] = (f"swa_decode_paged: work list of the window's spans, {heads} heads x {span} "
                                                  f"page{'s' if span > 1 else ''}, {H // Hkv} query heads a KV head" + said)
            return flash_decode_paged(q, k_cache, v_cache, ring, pos, sm_scale=sm_scale, work=work, window=window, sink=sink)
    if trace_notes is not None:
        trace_notes["swa_decode_form"] = "jnp over the ring's pages (gather)" + said
    R = ring_pages_for(window, page_len)
    lp = pos[:, None] // page_len - (R - 1) + jnp.arange(R, dtype=jnp.int32)[None, :]  # (B, R): the pages the window lies in
    pages = jnp.take_along_axis(ring, jnp.clip(lp, 0, ring.shape[1] - 1), axis=1)

    k_pos = (lp[:, :, None] * page_len + jnp.arange(page_len, dtype=jnp.int32)[None, None, :]).reshape(B, R * page_len)
    ok = (k_pos <= pos[:, None]) & (k_pos > pos[:, None] - window) & (k_pos >= 0)
    keys, vals = (paged_gather(c, pages).astype(jnp.float32) for c in (k_cache, v_cache))  # (B, Hkv, R * page_len, d)
    s = jnp.einsum("bhgd,bhsd->bhgs", q.reshape(B, Hkv, H // Hkv, d).astype(jnp.float32), keys) * (sm_scale or 1.0 / (d ** 0.5))
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1) if sink is None else sink_softmax(s, sink.reshape(1, Hkv, H // Hkv, 1))
    return jnp.einsum("bhgs,bhsd->bhgd", p, vals).reshape(B, H, 1, dv).astype(q.dtype)


def cache_attention(q, k_cache, v_cache, pos, sm_scale: Optional[float] = None,
                    key_padding_mask=None, use_kernel: Optional[bool] = None):
    """Attend queries (B,H,T,d) against a static cache (B,H,S,d).

    Allowed keys for query i: cache index j <= pos + i (``pos`` = write
    offset of the first query).  Covers both prefill (pos=0 → causal) and
    decode (T=1, pos=n → full-prefix attention).  ``pos`` may also be a
    per-example (B,) vector — the slot-pool form where each batch row is
    an independent sequence at its own position (serving/).
    ``key_padding_mask`` (B, S) True=attendable additionally masks
    left-padded prompt slots.  Reference decode softmax:
    ``csrc/transformer/inference/csrc/softmax.cu``.

    Single-query steps (T=1 — pool decode, generate()'s token loop)
    dispatch to the fused Pallas flash-decode kernel when the kernel
    suite is armed (``ops/kernels``, docs/kernels.md): int8 KV codes
    stream compressed and dequantize in-register, eliminating the
    dequant→materialize→attend round-trip this lax path pays.  The lax
    path below stays the numerics ground truth and the CPU/tier-1
    fallback; ``use_kernel`` forces the choice (tests / the reference
    twin).  The decision is trace-time static, so a built executable
    never flips.
    """
    quant = isinstance(k_cache, dict)
    if use_kernel is None:
        from deepspeed_tpu.ops import kernels as _kernels

        use_kernel = _kernels.flash_decode_armed()
    if use_kernel and q.shape[2] == 1:
        from deepspeed_tpu.ops.kernels.flash_decode import decode_supported, flash_decode

        B, H, _, d = q.shape
        S = (k_cache["q"] if quant else k_cache).shape[2]
        if decode_supported(B, H, S, d):
            return flash_decode(
                q, k_cache, v_cache, pos, sm_scale=sm_scale,
                key_padding_mask=key_padding_mask,
            )
    if quant:
        # int8 cache: the CODES are the dot operands (a plain convert
        # fuses into the dot's operand read, so int8 is what streams
        # from HBM); the per-row scales apply OUTSIDE the dots — on the
        # (T,S) score matrix and folded into p before the value dot.
        # Dequantizing first (codes*scale as the operand) defeats
        # operand fusion and materializes an f32-sized cache per step.
        # The kv_dequant scope names this round-trip in a profiler
        # trace's op names — the cost the fused
        # decode kernel deletes, so the name is visible exactly when
        # this lax path runs.
        with jax.named_scope("kv_dequant"):
            k_scale = k_cache["s"][..., 0][:, :, None, :]  # (B,H,1,S)
            v_scale = v_cache["s"][..., 0][:, :, None, :]
        k_op, v_op = k_cache["q"], v_cache["q"]
    else:
        k_scale = v_scale = None
        k_op, v_op = k_cache, v_cache
    B, H, T, d = q.shape
    S = k_op.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32), k_op.astype(jnp.float32)) * sm_scale
    if quant:
        with jax.named_scope("kv_dequant"):
            s = s * k_scale
    key_idx = jnp.arange(S)[None, None, None, :]
    pos_off = pos[:, None, None, None] if _per_slot(pos) else pos
    q_idx = pos_off + jnp.arange(T)[None, None, :, None]
    allowed = key_idx <= q_idx
    if key_padding_mask is not None:
        allowed = allowed & key_padding_mask[:, None, None, :].astype(bool)
    s = jnp.where(allowed, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        with jax.named_scope("kv_dequant"):
            p = p * v_scale
    out = jnp.einsum("bhts,bhsd->bhtd", p, v_op.astype(jnp.float32))
    return out.astype(q.dtype)


def inference_block(
    cfg: DeepSpeedInferenceConfig,
    lp: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,
    key_padding_mask=None,
    page_table=None,
    write_mask=None,
    layer=None,
    trace_notes: Optional[dict] = None,
    work=None,
    write_plan=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One transformer layer with cache update.

    ``x``: (B, T, D).  Initial prefill = pass a *static* ``pos=0`` (a
    Python int) to get the flash/causal fast path over the prompt block;
    any traced or non-zero ``pos`` (single-token decode, chunked
    continuation, speculative multi-token steps) attends against the
    whole cache with the position mask.  A per-example (B,) ``pos``
    vector selects the slot-pool form: each row reads/writes its own
    position (continuous batching, serving/).  ``page_table`` (B,
    pages_per_slot) selects the PAGED form instead: the caches are the
    **stacked** page pools (layers, num_pages, H, page_len, d) (or the
    int8 code+scale pair) and ``layer`` — an int or a traced scalar —
    says which layer of them this block is.  The rows are written into
    the pool as slices (``write_mask`` redirecting masked rows to the
    garbage page) and attention reads the layer's pages where they lie:
    one query through the paged decode kernel (or the gather + lax
    form) — ``work`` its work list and ``write_plan`` the writes'
    targets, each built once for all layers
    (``flash_decode.paged_work_list``, :func:`decode_write_plan`) — a chunk block by block over the slot's pages
    — requires a per-slot ``pos`` and no ``key_padding_mask``.  Returns
    (y, new_k_cache, new_v_cache).  Mirrors the reference's fused
    attention+MLP inference module (``transformer_inference.py``
    DeepSpeedTransformerInference.forward).
    """
    B, T, D = x.shape
    H, hd = cfg.heads, cfg.head_dim

    h = _ln(x, lp["ln1_g"], lp["ln1_b"], cfg.layer_norm_eps)
    qkv = _wmm(h, lp["qkv_w"]) + lp["qkv_b"].astype(h.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(B, T, H, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    if page_table is not None:
        if key_padding_mask is not None:
            raise ValueError("paged caches do not support key_padding_mask")
        k_cache = paged_cache_write_slices(k_cache, layer, k, page_table, pos, write_mask, plan=write_plan)
        v_cache = paged_cache_write_slices(v_cache, layer, v, page_table, pos, write_mask, plan=write_plan)
        kc, vc, table = layer_pages(k_cache, v_cache, page_table, layer)
        if trace_notes is not None:
            trace_notes["kv_write_form"] = KV_WRITE_FORMS[decode_write_takes_kernel(k_cache)]
        if T == 1:
            attn = paged_cache_attention(q, kc, vc, table, pos, work=work, trace_notes=trace_notes)
        else:
            attn = paged_chunk_attention(q, kc, vc, table, pos, trace_notes=trace_notes)
            if trace_notes is not None:
                trace_notes["prefill_attend_form"] = chunk_attention_note(trace_notes, "blockwise (paged_chunk_attention)")
        attn = attn.transpose(0, 2, 1, 3).reshape(B, T, D)
        attn = _wmm(attn, lp["proj_w"]) + lp["proj_b"].astype(attn.dtype)
        return _block_mlp(cfg, lp, x + attn), k_cache, v_cache
    # in-place cache write at [.., pos:pos+T, ..] (per-row positions in
    # the slot-pool form)
    slotted = _per_slot(pos)
    if isinstance(k_cache, dict):
        def _write(cache, t):
            cq, cs = _kv_quant(t)
            if slotted:
                return {
                    "q": slot_cache_write(cache["q"], cq, pos),
                    "s": slot_cache_write(cache["s"], cs, pos),
                }
            return {
                "q": jax.lax.dynamic_update_slice(cache["q"], cq, (0, 0, pos, 0)),
                "s": jax.lax.dynamic_update_slice(cache["s"], cs, (0, 0, pos, 0)),
            }

        k_cache = _write(k_cache, k)
        v_cache = _write(v_cache, v)
    elif slotted:
        k_cache = slot_cache_write(k_cache, k.astype(k_cache.dtype), pos)
        v_cache = slot_cache_write(v_cache, v.astype(v_cache.dtype), pos)
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, 0, pos, 0))

    is_initial_prefill = isinstance(pos, int) and pos == 0
    if is_initial_prefill and T > 1 and key_padding_mask is None and cfg.use_flash_attention and T >= 128:
        # prefill fast path: pure causal attention over the prompt block
        attn = flash_attention(q, k, v, causal=True)
    elif is_initial_prefill and T > 1 and key_padding_mask is None:
        attn = mha_reference(q, k, v, causal=True)
    elif is_initial_prefill and T > 1:
        # masked prefill: keys beyond the prompt block are causally dead —
        # slice the cache so scores stay (T, T), not (T, T+N)
        kp = key_padding_mask[:, :T] if key_padding_mask is not None else None
        head = lambda c: (
            jax.tree.map(lambda a: a[:, :, :T], c) if isinstance(c, dict) else c[:, :, :T]
        )
        attn = cache_attention(q, head(k_cache), head(v_cache), 0, key_padding_mask=kp)
    else:
        # decode or mid-stream continuation: attend against the whole
        # cache with position + padding masks
        attn = cache_attention(q, k_cache, v_cache, pos, key_padding_mask=key_padding_mask)
    attn = attn.transpose(0, 2, 1, 3).reshape(B, T, D)
    attn = _wmm(attn, lp["proj_w"]) + lp["proj_b"].astype(attn.dtype)
    return _block_mlp(cfg, lp, x + attn), k_cache, v_cache


def _block_mlp(cfg: DeepSpeedInferenceConfig, lp: Dict[str, jnp.ndarray],
               x: jnp.ndarray) -> jnp.ndarray:
    """Post-attention half of the block: LN2 + (MoE | dense) MLP +
    residual — shared by the slot-pool and paged attention paths."""
    h = _ln(x, lp["ln2_g"], lp["ln2_b"], cfg.layer_norm_eps)
    if "gate_w" in lp:
        # MoE block: route through the expert layer (eval mode — no
        # jitter/aux; experts stay sharded over the `expert` axis).
        # NB: decode routes only the current step's tokens, so capacity
        # saturation can differ from a full teacher-forced forward when
        # the router is heavily skewed — eval_capacity_factor (2.0 by
        # default, matching the train model's eval path) keeps drops rare.
        from deepspeed_tpu.moe.layer import moe_ffn_from_block

        h, _ = moe_ffn_from_block(
            lp, h, top_k=cfg.moe_top_k, eval_capacity_factor=cfg.moe_eval_capacity_factor, training=False
        )
    else:
        h = _wmm(h, lp["fc_w"]) + lp["fc_b"].astype(h.dtype)
        h = jax.nn.gelu(h, approximate=True)  # fused bias+gelu (gelu.cu analog)
        h = _wmm(h, lp["fc_proj_w"]) + lp["fc_proj_b"].astype(h.dtype)
    return x + h


def forward_with_cache(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    k_cache,
    v_cache,
    pos,
    cfg: DeepSpeedInferenceConfig,
    key_padding_mask=None,
    position_ids=None,
    page_table=None,
    write_mask=None,
    trace_notes: Optional[dict] = None,
    pool_layout=None,
):
    """Full GPT-2-layout network step with cache: embeddings → scanned
    cached blocks → final LN → tied-embedding logits.

    ``tokens``: (B, T) int32 (T static).  ``pos``: scalar int32 write
    offset, or a per-example (B,) vector (slot-pool continuous batching:
    each row is an independent sequence at its own position).
    ``key_padding_mask`` (B, cache_len) True=attendable masks
    left-padded prompt slots; ``position_ids`` (B, T) overrides the
    default ``pos + arange(T)`` positions (per-example real positions
    under left padding).  ``page_table`` (B, pages_per_slot) +
    ``write_mask`` (B,) select the paged-cache form (see
    :func:`inference_block`): the stacked pools are the **carry** of
    the layer loop — every layer updates its own rows of them in place
    and reads its own pages — never its ``xs``/``ys``, which rebuilds
    the whole pool a step.  ``pool_layout``, a pytree of
    ``jax.experimental.layout.Layout`` like ``k_cache``, is the
    on-device layout the pools were allocated with (``array.format``):
    the carry is pinned to it.  ``trace_notes``, a dict, is told the
    forms the paged program took.  Returns (logits (B,T,V), new_k,
    new_v).
    """
    B, T = tokens.shape
    d = params["wte"].shape[1]
    if position_ids is None and _per_slot(pos):
        # per-slot positions: derive per-row ids, clipped so the garbage
        # rows a fixed-shape serving step carries (idle slots, padded
        # prefill tails) cannot gather out of range — real rows are kept
        # in range by admission control
        position_ids = jnp.clip(
            pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :],
            0, params["wpe"].shape[0] - 1,
        )
    if position_ids is not None:
        pos_emb = jnp.take(params["wpe"], position_ids, axis=0)  # (B, T, d)
    else:
        pos_emb = jax.lax.dynamic_slice(params["wpe"], (pos, 0), (T, d))[None]
    x = jnp.take(params["wte"], tokens, axis=0) + pos_emb
    x = x.astype(cfg.dtype)

    if page_table is not None:
        n_layer = jax.tree.leaves(k_cache)[0].shape[0]
        # a decode step's work list is the same for every layer (they
        # differ in the table's offset): the loop body closes over it
        from deepspeed_tpu.ops.kernels.flash_decode import paged_tile, paged_work_list

        P = page_table.shape[1]
        work = paged_work_list(pos, write_mask, jax.tree.leaves(k_cache)[0].shape[3], P, paged_tile(k_cache, P)[1]) if T == 1 else None
        write_plan = decode_write_plan(k_cache, page_table, pos, write_mask) if T == 1 else None  # and so are its writes' targets

        def pin(cache):
            if pool_layout is None:
                return cache
            return jax.tree.map(with_layout_constraint, cache, pool_layout)

        # The pools ride the layer loop's carry and the layer's index its
        # xs.  A loop body is free to pick the layout of its parameters,
        # and picks one to suit its own slices of the pool — another than
        # the pool was handed in with, so it is copied in and out, every
        # step — unless the carry is pinned to the layout the pool has.
        def layer(carry, xs):
            lp, i = xs
            x, k, v = carry
            x, k, v = inference_block(cfg, lp, x, pin(k), pin(v), pos, page_table=page_table, write_mask=write_mask,
                                      layer=i, trace_notes=trace_notes, work=work, write_plan=write_plan)
            return (x, pin(k), pin(v)), None

        (x, new_k, new_v), _ = jax.lax.scan(
            layer, (x, k_cache, v_cache), (params["blocks"], jnp.arange(n_layer, dtype=jnp.int32))
        )
    elif isinstance(k_cache, (tuple, list)):
        # PER-LAYER cache buffers (decode fast path): each of the L
        # python-unrolled layers reads/writes ITS OWN (B,H,S,d) array —
        # no slicing/reassembly of a stacked (L,...) buffer, which the
        # profiler showed materializing ~GBs of slice/bitcast copies per
        # token when the stacked cache flowed through an unrolled scan.
        # Weight slices a[i] are static reads that fuse into the matmuls.
        n_layer = len(k_cache)
        new_k, new_v = [], []
        for i in range(n_layer):
            lp = jax.tree.map(lambda a: a[i], params["blocks"])
            x, ck, cv = inference_block(
                cfg, lp, x, k_cache[i], v_cache[i], pos,
                key_padding_mask=key_padding_mask,
            )
            new_k.append(ck)
            new_v.append(cv)
        new_k, new_v = tuple(new_k), tuple(new_v)
    else:

        def body(carry, xs):
            lp, ck, cv = xs
            y, ck, cv = inference_block(
                cfg, lp, carry, ck, cv, pos,
                key_padding_mask=key_padding_mask,
            )
            return y, (ck, cv)

        n_layer = jax.tree.leaves(k_cache)[0].shape[0]
        # Single-token decode fully unrolls the layer loop (the scanned
        # form's per-iteration bookkeeping — dynamic slices of the
        # stacked cache/params — dominates when each layer's math is one
        # token; same fix as the training-side scan overhead).  The
        # engine's decode path goes further and uses the per-layer tuple
        # caches above.  Prefill (T>1) always scans: its per-layer
        # compute amortizes the loop and unrolling bloats compile time.
        unroll = n_layer if T == 1 else 1
        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["blocks"], k_cache, v_cache), unroll=unroll
        )
    x = _ln(x, params["lnf_g"], params["lnf_b"], cfg.layer_norm_eps)
    logits = x @ params["wte"].T.astype(x.dtype)
    return logits.astype(jnp.float32), new_k, new_v


# the serving engine's seam (docs/serving.md §Model families), reached through models/gpt2.py: this layout's step on the
# paged per-head pool, and on the slot-contiguous one

def _logit_rows(logits, take):
    """Row ``take[b]`` of each row's ``(T, V)`` logits — the last where
    ``take`` is None (a decode step) — as slices: ``(B, V)``."""
    if take is None:
        return logits[:, -1]
    return jnp.stack([logits[b, take[b]] for b in range(logits.shape[0])])


def serving_forward(mcfg):
    """``fwd(params, tokens, k, v, pos, page_table=, write_mask=, row_valid=, take=, state=, slot=) -> (logits (B, V), k, v,
    state, None)`` on the paged :class:`PerHeadKV` pool (bf16/f32 or the int8 pair): :func:`forward_with_cache` with the pools
    pinned to the layout they were allocated with, the chunk's position ids clipped (its default under a per-row ``pos``),
    and row ``take`` of the logits.  No per-slot state and no counters: ``state`` passes through, ``aux`` is None.
    ``fwd.bind(dtype=, mp_size=, pool=)`` is handed what a model config does not hold — the engine's compute dtype and the
    pool as allocated — before anything is traced; ``fwd.trace_notes`` holds ``kv_write_form``, ``prefill_attend_form``
    (from ``chunk_attention_kernel`` / ``_fallback``) and ``paged_decode_walk``."""
    notes: Dict[str, Any] = {}
    bound: Dict[str, Any] = {}

    def bind(dtype, mp_size: int, pool) -> None:
        from jax.experimental.layout import Layout

        bound["cfg"] = DeepSpeedInferenceConfig.for_model(mcfg, dtype, mp_size, pool.max_len)
        # the on-device layout the pool's K buffers were allocated with (V's is the same), leaf by leaf: both programs pin
        # the pool to it while they carry it through the layers, so that it is one layout from allocation to the kernel and
        # back (on the TPU a head narrower than the lanes lies with ``page_len`` in them; docs/serving.md §Paged KV)
        bound["layout"] = jax.tree.map(lambda a: Layout(major_to_minor=a.format.layout.major_to_minor), pool.k)

    def fwd(params, tokens, k, v, pos, page_table, write_mask=None, row_valid=None, take=None, state=None, slot=None):
        logits, k, v = forward_with_cache(params, tokens, k, v, pos, bound["cfg"], page_table=page_table,
                                          write_mask=write_mask, trace_notes=notes, pool_layout=bound["layout"])
        return _logit_rows(logits, take), k, v, state, None

    fwd.bind = bind
    fwd.trace_notes = notes
    return fwd


def _take_slot(c, slot):
    return jax.tree.map(lambda a: jax.lax.dynamic_slice(a, (0, slot, 0, 0, 0), (a.shape[0], 1) + a.shape[2:]), c)


def _put_slot(c, cs, slot):
    return jax.tree.map(lambda a, b: jax.lax.dynamic_update_slice(a, b, (0, slot, 0, 0, 0)), c, cs)


def slot_serving_forward(mcfg):
    """The same seam on the slot-contiguous pool (``serving/pool.py``),
    whose slot axis is the batch axis: a decode step's rows are the
    slots, each at its own ``pos``; a chunk (``slot`` given) takes its
    slot's rows out, runs them as a batch of one at the scalar ``pos``
    and puts them back.  No page table, no write mask."""
    bound: Dict[str, Any] = {}

    def bind(dtype, mp_size: int, pool) -> None:
        bound["cfg"] = DeepSpeedInferenceConfig.for_model(mcfg, dtype, mp_size, pool.max_len)

    def fwd(params, tokens, k, v, pos, page_table=None, write_mask=None, row_valid=None, take=None, state=None, slot=None):
        if slot is None:
            # per-slot pos: slot-indexed cache write + position mask, auto-clipped position ids
            logits, k, v = forward_with_cache(params, tokens, k, v, pos, bound["cfg"])
            return _logit_rows(logits, take), k, v, state, None
        slot, pos = slot[0], pos[0]
        ks, vs = _take_slot(k, slot), _take_slot(v, slot)
        # explicit clipped position ids: the zero-padded chunk tail must
        # not clamp the wpe slice and shift real rows
        position_ids = jnp.clip(pos + jnp.arange(tokens.shape[1], dtype=jnp.int32), 0, mcfg.n_positions - 1)[None, :]
        logits, ks, vs = forward_with_cache(params, tokens, ks, vs, pos, bound["cfg"], position_ids=position_ids)
        return _logit_rows(logits, take), _put_slot(k, ks, slot), _put_slot(v, vs, slot), state, None

    fwd.bind = bind
    return fwd


@register_op("transformer_inference", "xla", "KV-cache prefill/decode transformer (inference kernel analog)")
def _load_transformer_inference():
    return {
        "config": DeepSpeedInferenceConfig,
        "block": inference_block,
        "forward_with_cache": forward_with_cache,
        "cache_attention": cache_attention,
        "init_kv_cache": init_kv_cache,
        "slot_cache_write": slot_cache_write,
        "paged_gather": paged_gather,
        "paged_cache_write": paged_cache_write,
        "paged_cache_attention": paged_cache_attention,
    }
