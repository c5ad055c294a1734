"""Fused single-query flash-decode kernel over the slot-pool KV layout.

The decode hot path (one query token per live slot against an S-position
cache) was a chain of XLA fusions: for the int8 pool it **dequantized
the codes, materialized fp32-sized score/operand tensors, then
attended** — the ``kv_dequant`` scope of the lax path, which capped
long-context int8 decode (not measured on this code).  This kernel
collapses the round-trip: int8 codes + scales stream HBM→VMEM once,
dequantization happens **in-register inside the flash inner loop**
(codes are the dot operands; the per-row scales fold into the score row
and the probability row exactly like the lax path), and the online
softmax never materializes an (S,) tensor in HBM.  The bf16/f32 pool
runs the same kernel minus the dequant.

Contract (mirrors ``ops/transformer/inference.cache_attention``, which
remains the lax fallback and the numerics ground truth):

* ``q``: (B, H, 1, d) — exactly one query per slot (decode / one-token
  speculative step).  ``B`` is the slot axis of the serving pool or the
  batch axis of ``generate()``.
* caches: (B, H, S, d) arrays, or the int8 pair ``{"q": int8 codes,
  "s": (B, H, S, 1) fp32 scales}`` from ``init_kv_cache``.
* ``pos``: scalar or per-slot (B,) write offsets; key ``j`` is
  attendable iff ``j <= pos[b]`` (the overwrite-before-attend serving
  invariant rides on this mask).
* ``key_padding_mask``: optional (B, S), True = attendable (left-padded
  ``generate()`` prompts).
* Inference-only: no ``custom_vjp``, no lse output, no dropout — the
  decode step is never differentiated, so the kernel carries none of
  the training machinery.

Grid: ``(B // block_slots, H, S // block_k)`` with the kv axis
sequential ("arbitrary"); each program keeps (m, l, acc) for its
``block_slots`` rows in VMEM scratch across kv steps, so K/V blocks
double-buffer through VMEM while the previous block computes.
``block_k`` / ``block_slots`` come from the autotuner
(:mod:`deepspeed_tpu.ops.kernels.autotune`) — deterministic defaults
unless a measured tuning is cached.

Off-TPU the kernel runs under ``interpret=True`` (tests); the engines
only dispatch here when the kernel suite is armed
(:func:`deepspeed_tpu.ops.kernels.flash_decode_armed`), so CPU tier-1
stays on the lax path unless a test forces ``DS_KERNELS=1``.
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

# Same mask constant as cache_attention: fully-masked rows degrade to
# the same uniform softmax on both paths (parity over garbage rows the
# serving step deliberately carries).
NEG_INF = -1e30


def decode_supported(B: int, H: int, S: int, d: int) -> bool:
    """Shapes the kernel grid can serve: the kv axis must offer at least
    one >=128 block, head_dim must be lane-layout friendly.  Everything
    else falls back to the lax path (tiny unit-test caches)."""
    return S >= 128 and S % 128 == 0 and d >= 8 and B >= 1 and H >= 1


def _pick_block_k(S: int, pref: int) -> int:
    b = min(pref, S)
    while b > 128 and S % b:
        b //= 2
    return b if S % b == 0 else 128


def _pick_block_slots(B: int, pref: int) -> int:
    b = max(1, min(pref, B))
    while b > 1 and B % b:
        b //= 2
    return b


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------

def _flash_decode_kernel(
    pos_ref,          # SMEM (B, 1) int32 — per-slot query position (full array)
    q_ref,            # (block_slots, 1, 1, d)
    k_ref,            # (block_slots, 1, block_k, d)  codes or bf16/f32
    v_ref,            # (block_slots, 1, block_k, d)
    *rest,            # [ks_ref, vs_ref] int8 scales (block_slots,1,1,block_k); [kpm_ref (block_slots,1,S)]; o_ref; scratch: m, l, acc
    sm_scale: float,
    block_k: int,
    block_slots: int,
    quant: bool,
    masked: bool,
):
    refs = list(rest)
    ks_ref = refs.pop(0) if quant else None
    vs_ref = refs.pop(0) if quant else None
    kpm_ref = refs.pop(0) if masked else None
    o_ref, m_ref, l_ref, acc_ref = refs

    slot0 = pl.program_id(0) * block_slots
    kv_idx = pl.program_id(2)
    num_kv = pl.num_programs(2)
    col0 = kv_idx * block_k

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    key_idx = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    # static unroll over the slot rows of this program: each row is an
    # independent sequence (its own K/V and position), so the math is a
    # (1, d) x (d, block_k) matvec chain per row — decode is memory-
    # bound, the MXU shape hardly matters, the K/V stream does.
    for s in range(block_slots):
        row = pl.dslice(s, 1)
        q = q_ref[s, 0].astype(jnp.float32)                      # (1, d)
        k = k_ref[s, 0].astype(jnp.float32)                      # (block_k, d)
        scores = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                             # (1, block_k)
        if quant:
            # in-register dequant, scale OUTSIDE the dot (the codes are
            # the streamed operands — identical factoring to the lax
            # path, so parity is a tolerance not a rewrite)
            scores = scores * ks_ref[s, 0]                       # (1, block_k)
        allowed = key_idx <= pos_ref[slot0 + s, 0]
        if masked:
            allowed = jnp.logical_and(
                allowed, kpm_ref[s, :, pl.dslice(col0, block_k)] > 0
            )
        scores = jnp.where(allowed, scores, NEG_INF)

        m_prev = m_ref[row]                                      # (1, 1)
        l_prev = l_ref[row]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)                              # (1, block_k)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[row] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[row] = m_new
        if quant:
            p = p * vs_ref[s, 0]
        v = v_ref[s, 0].astype(jnp.float32)                      # (block_k, d)
        acc_ref[row] = acc_ref[row] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(kv_idx == num_kv - 1)
    def _emit():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])            # (bs, 1)
        o_ref[:] = (acc_ref[:] / l)[:, None, None, :].astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# host-graph wrapper
# ---------------------------------------------------------------------------

def flash_decode(
    q: jnp.ndarray,
    k_cache,
    v_cache,
    pos,
    sm_scale: Optional[float] = None,
    key_padding_mask=None,
    block_k: Optional[int] = None,
    block_slots: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Single-query attention against a slot cache; see module docs.
    Returns (B, H, 1, d) in ``q.dtype``.  Block sizes default to the
    autotuner's table (cached measured winners when present)."""
    from deepspeed_tpu.ops.kernels.autotune import get_autotuner

    quant = isinstance(k_cache, dict)
    k_op = k_cache["q"] if quant else k_cache
    v_op = v_cache["q"] if quant else v_cache
    B, H, T, d = q.shape
    S = k_op.shape[2]
    if T != 1:
        raise ValueError(f"flash_decode serves exactly one query per slot, got T={T}")
    if not decode_supported(B, H, S, d):
        raise ValueError(
            f"flash_decode grid cannot serve (B={B}, H={H}, S={S}, d={d}); "
            "callers must dispatch through decode_supported()"
        )
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = pallas_interpret_default()

    blocks = get_autotuner().blocks_for("flash_decode", B=B, H=H, S=S, d=d, int8=quant)
    bk = _pick_block_k(S, block_k or blocks["block_k"])
    bs = _pick_block_slots(B, block_slots or blocks["block_slots"])

    # per-slot position vector (scalar pos broadcasts: every generate()
    # row decodes at the same offset), shaped (B, 1) for SMEM blocks
    pos_vec = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (B,)
    ).reshape(B, 1)

    grid = (B // bs, H, S // bk)
    in_specs = [
        pl.BlockSpec((bs, 1, 1, d), lambda sb, h, kv: (sb, h, 0, 0)),
        pl.BlockSpec((bs, 1, bk, d), lambda sb, h, kv: (sb, h, kv, 0)),
        pl.BlockSpec((bs, 1, bk, d), lambda sb, h, kv: (sb, h, kv, 0)),
    ]
    args = [q, k_op, v_op]
    if quant:
        # (B, H, S, 1) scales -> (B, H, 1, S) row vectors (a contiguous
        # reshape) so in-kernel scale rows share the score layout
        ks = k_cache["s"].reshape(B, H, 1, S)
        vs = v_cache["s"].reshape(B, H, 1, S)
        spec = pl.BlockSpec((bs, 1, 1, bk), lambda sb, h, kv: (sb, h, 0, kv))
        in_specs += [spec, spec]
        args += [ks, vs]
    masked = key_padding_mask is not None
    if masked:
        # (B, S) -> (B, 1, S) f32: the trailing (1, S) block equals the
        # array dims, which Mosaic requires when B isn't sublane-aligned
        kpm = key_padding_mask.astype(jnp.float32).reshape(B, 1, S)
        in_specs.append(pl.BlockSpec((bs, 1, S), lambda sb, h, kv: (sb, 0, 0)))
        args.append(kpm)

    kern = functools.partial(
        _flash_decode_kernel,
        # static python scale (a traced sm_scale cannot close into the
        # kernel body; callers pass None or a host float)
        sm_scale=sm_scale,
        block_k=bk,
        block_slots=bs,
        quant=quant,
        masked=masked,
    )
    # pos rides SMEM un-blocked (the drop_seed pattern from the flash
    # fwd kernel): every program reads its absolute slot rows
    in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bs, 1, 1, d), lambda sb, h, kv: (sb, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bs, 1), jnp.float32),   # m
            pltpu.VMEM((bs, 1), jnp.float32),   # l
            pltpu.VMEM((bs, d), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode",
    )(pos_vec, *args)
    return out


def decode_paged_supported(B: int, H: int, P: int, page_len: int, d: int) -> bool:
    """Paged-grid shapes the kernel can serve: each page is one kv block,
    so ``page_len`` must be a lane-aligned >=128 run; head_dim must be
    layout friendly.  Small-page pools (unit tests) fall back to the
    gather + lax path, which is the numerics ground truth."""
    return page_len >= 128 and page_len % 128 == 0 and d >= 8 and B >= 1 and H >= 1 and P >= 1


# K + V bytes a grid step of the paged kernel may hold: what sets its tile
# (:func:`paged_tile`).  A Mosaic grid step costs ~0.4 us whatever it
# moves and a mebibyte is ~1.3 us of the v5e's HBM, so at the budget the
# step's own cost is under a quarter of its bytes' time; twice over for
# the pipeline's second buffer it is 2 MiB of the 16 MiB of VMEM a kernel
# may take.  Fixed by what the chip showed: ZAYA1's page reads 0.49 / 0.32 /
# 0.26 / 0.21 us at 128 KB ... 1 MiB a step, Solar-Open2's 0.86 / 0.75 /
# 0.74 at 0.5 / 1 / 2 MiB (docs/kernels.md; PERF.md, PR 46).
TILE_BYTES = 1 << 20


def paged_tile(cache, pages_per_slot: int, v_cache=None):
    """``(KV heads a program, pages an item)`` of a paged decode call:
    the tile a grid step holds, read off the pool's shape and nothing
    else.  Every KV head of a page whose K + V fit :data:`TILE_BYTES`
    (the largest divisor of ``Hkv`` that does), then a **span** of the
    largest of 8, 4, 2, 1 consecutive pages of a row that divides
    ``pages_per_slot`` and still fits.  ``cache`` is the K pool, its
    int8 pair, or a ``ShapeDtypeStruct``: ``(..., Hkv, page_len, d)``;
    ``v_cache`` the V pool where its rows are not as wide as K's."""
    leaf = cache["q"] if isinstance(cache, dict) else cache
    Hkv, page_len, d = leaf.shape[-3:]
    d_v = d if v_cache is None else (v_cache["q"] if isinstance(v_cache, dict) else v_cache).shape[-1]
    pair = page_len * (d + d_v) * jnp.dtype(leaf.dtype).itemsize  # K + V bytes of one KV head of one page
    heads = max(h for h in range(1, Hkv + 1) if Hkv % h == 0 and (h == 1 or h * pair <= TILE_BYTES))
    span = next(c for c in (8, 4, 2, 1) if pages_per_slot % c == 0 and (c == 1 or c * heads * pair <= TILE_BYTES))
    return heads, span


def paged_work_list(pos, live, page_len: int, pages_per_slot: int, span: int = 1, window: Optional[int] = None):
    """The work of one paged decode step as a list of (slot, span) items,
    a span being ``span`` consecutive logical pages of a row
    (:func:`paged_tile`; 1: an item is a page): for every row with
    ``live[b]`` (``None``: every row) the spans ``0 ... pos[b] //
    (page_len * span)``, in slot order, then span order — or, ``window``
    given (a layer that attends over its last ``window`` positions, the
    query's own among them), only the spans those positions lie in:
    from ``max(pos[b] - window + 1, 0) // (page_len * span)`` on.  Returns
    ``(slot, span_index, n, live)``: two int32 arrays of the static
    capacity ``B * pages_per_slot // span``, the count ``n (1,)`` — the
    items past ``n`` repeat the last one and are not walked
    (``compact_rows``' convention; with ``n == 0`` they name slot 0,
    span 0) — and the rows the list visits, ``(B,)`` bool.  It depends
    on nothing a layer has, so a decode program builds it once."""
    from deepspeed_tpu.ops.kernels.kda_decode import compact_rows

    if pages_per_slot % span:
        raise ValueError(f"paged_work_list: spans of {span} pages do not tile a slot of {pages_per_slot}")
    S = pages_per_slot // span
    pos = jnp.asarray(pos, jnp.int32)
    live = jnp.ones(pos.shape, bool) if live is None else live.astype(bool)
    spans = jnp.where(live, jnp.clip(pos // (page_len * span), 0, S - 1) + 1, 0)  # (B,) filled spans a row
    filled = jnp.arange(S, dtype=jnp.int32)[None, :] < spans[:, None]  # (B, S), row-major: slot order, then span order
    if window is not None:
        filled &= jnp.arange(S, dtype=jnp.int32)[None, :] >= (jnp.maximum(pos - (window - 1), 0) // (page_len * span))[:, None]
    items, n = compact_rows(filled.reshape(-1))
    return items // S, items % S, n, live


def _flash_decode_paged_kernel(
    pt_ref,           # SMEM (B, P) int32 — per-slot page table (scalar prefetch)
    pos_ref,          # SMEM (B,) int32 — per-slot query position (scalar prefetch)
    slot_ref,         # SMEM (B * P / span,) int32 — slot of each work item (scalar prefetch)
    span_ref,         # SMEM (B * P / span,) int32 — span of each work item (scalar prefetch)
    n_ref,            # SMEM (1,) int32 — items to walk: the traced bound of the grid's last axis
    q_ref,            # (1, block_heads, group, d): the query heads of block_heads KV heads
    *rest,            # ``span`` K pages, ``span`` V pages, each (1, block_heads, page_len, d) — THE page pt[slot, span * s + j],
                      # codes or bf16/f32, (1, block_heads, d, page_len) when ``lanes_hold_rows`` (an operand whose own width is not
                      # whole lanes: ``v_lanes_hold_rows`` where V's differs from K's);
                      # [``span`` K scales, ``span`` V scales (1, block_heads, 1, page_len)];
                      # [the item's strip of the row's selection (1, 1, 1, span * page_len) int8];
                      # [the heads' sink logits (block_heads * group, 1) float32]; o_ref; scratch m, l, acc, scores
    sm_scale: float,
    page_len: int,
    quant: bool,
    block_heads: int,
    group: int,
    span: int,
    lanes_hold_rows: bool,
    window: Optional[int] = None,
    v_lanes_hold_rows: bool = False,
    selected: bool = False,
    sunk: bool = False,
):
    k_refs, v_refs, *scales = (rest[g * span: (g + 1) * span] for g in range(4 if quant else 2))
    ks_refs, vs_refs = scales or (None, None)
    *extra, o_ref, m_ref, l_ref, acc_ref, s_ref = rest[(4 if quant else 2) * span:]
    # the call was given a selection, a sink: Python's branches, nothing of either is traced without one
    chosen_ref = extra.pop(0) if selected else None
    sink_ref = extra.pop(0) if sunk else None

    i = pl.program_id(1)
    b, s_idx = slot_ref[i], span_ref[i]
    heads = [(h, pl.dslice(h * group, group)) for h in range(block_heads)]   # a KV head and the rows of its query heads
    cols = [pl.dslice(j * page_len, page_len) for j in range(span)]          # a page's positions in the item's score rows
    # the row's first item: span 0, or — a window layer — the span the window's first position lies in
    first = 0 if window is None else jnp.maximum(pos_ref[b] - (window - 1), 0) // (page_len * span)

    @pl.when(n_ref[0] == 0)
    def _nothing_decodes():
        # the one step a grid of no items still takes
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_ref[0] > 0)
    def _item():
        @pl.when(s_idx == first)
        def _init():
            if sink_ref is None:
                m_ref[:] = jnp.full_like(m_ref, NEG_INF)
                l_ref[:] = jnp.zeros_like(l_ref)
            else:
                # the sink: one more column of the row's softmax, a logit a head that takes mass and carries no value — it
                # enters pass (2)'s running maximum and denominator once, here: exp(sink - m) is 1 with the maximum at the sink
                m_ref[:] = sink_ref[:]
                l_ref[:] = jnp.ones_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        # Three passes over the item, so that no product waits for another
        # head's softmax: (1) every (page, KV head) pair's scores — a KV
        # head's ``group`` query heads share its page: one fetch, ``group``
        # rows — into one (rows, span * page_len) buffer; the page's
        # positions are the rows of its tile, or — a head narrower than the
        # lanes — its columns: the contraction moves
        for j in range(span):
            for h, rows in heads:
                scores = jax.lax.dot_general(
                    q_ref[0, h].astype(jnp.float32),                     # (group, d)
                    k_refs[j][0, h].astype(jnp.float32),                 # (page_len, d) | (d, page_len)
                    dimension_numbers=(((1,), (0 if lanes_hold_rows else 1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                                    # (group, page_len)
                if quant:
                    scores = scores * ks_refs[j][0, h]               # in-register dequant
                s_ref[rows, cols[j]] = scores

        # (2) one online-softmax update for every row of the item.  The
        # page table indirection happened in the BlockSpec index_map (the
        # k/v blocks ARE pages pt[b, span * s + j]), so the mask math is
        # position-space; a row's last span may reach past its position:
        # those pages were fetched, and every position of theirs is masked
        key_idx = s_idx * (span * page_len) + jax.lax.broadcasted_iota(jnp.int32, (1, span * page_len), 1)
        seen = key_idx <= pos_ref[b]
        if window is not None:  # the lower bound: a span's head may lie before the window, and a ring's page may hold a later lap
            seen &= key_idx > pos_ref[b] - window
        if chosen_ref is not None:  # the row's selection: nothing else is attended
            seen &= chosen_ref[0, 0].astype(jnp.int32) != 0
        scores = jnp.where(seen, s_ref[:] * sm_scale, NEG_INF)
        m_prev = m_ref[:]                                            # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        if chosen_ref is not None:  # an item may hold nothing selected, a row's first too: with the maximum still at NEG_INF exp(0) is 1 a position
            p = jnp.where(seen, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        s_ref[:] = p
        acc_ref[:] = acc_ref[:] * alpha

        # (3) every pair's probabilities against its V page
        for h, rows in heads:
            acc_ref[rows] += sum(
                jax.lax.dot_general(
                    s_ref[rows, cols[j]] * vs_refs[j][0, h] if quant else s_ref[rows, cols[j]],
                    v_refs[j][0, h].astype(jnp.float32),
                    dimension_numbers=(((1,), (1 if v_lanes_hold_rows else 0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) for j in range(span))

        # the slot's last item: the span its position lies in
        @pl.when(s_idx == pos_ref[b] // (page_len * span))
        def _emit():
            l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
            o_ref[:] = (acc_ref[:] / l).reshape(o_ref.shape).astype(o_ref.dtype)


def flash_decode_paged(
    q: jnp.ndarray,
    k_cache,
    v_cache,
    page_table: jnp.ndarray,
    pos,
    sm_scale: Optional[float] = None,
    work=None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    mask=None,
    sink=None,
) -> jnp.ndarray:
    """Single-query attention against a PAGED pool (docs/serving.md
    §Paged KV & prefix caching): caches are ``(num_pages, Hkv, page_len,
    d)`` (or the int8 code+scale pair), ``page_table`` (B,
    pages_per_slot) maps each slot's logical positions onto pages.

    **Grouped queries**: ``q (B, H, 1, d)`` with ``H`` a multiple of the
    cache's ``Hkv``; query head ``i`` attends KV head ``i // (H / Hkv)``.
    The ``H / Hkv`` query heads of a KV head sit in one tile against its
    page, so a page is fetched once a KV head, not once a query head.

    **A grid step is sized by its bytes** (:func:`paged_tile`, from the
    pool's shape alone): every KV head of a page in one program —
    multi-head attention, the group of 1, included — up to
    :data:`TILE_BYTES` of K + V, and where a page is small a **span** of
    8, 4 or 2 consecutive pages of a row, each page an operand of its
    own under the index map ``pt[slot, span * s + j]``.  A step's fixed
    cost (~0.4 us) is then paid once for about a mebibyte.  A row's last
    span may reach past its position: those pages are read (the table's
    entries there name pages that exist) and add nothing.

    **The grid walks a work list**, ``(Hkv / heads a program, items)``:
    ``work = (slot, span, n, live)`` as :func:`paged_work_list` builds it
    under this call's span — the filled spans of the rows that decode,
    slot by slot — rides as prefetched scalars, and ``n``, a traced
    value, is the bound of the grid's last axis: a span past a slot's
    position and a row that does not decode cost no grid step, no DMA
    and no arithmetic.  A slot's items are consecutive, so its output
    block stays in VMEM over them and goes back when the slot changes.
    ``work=None`` builds the list from ``pos`` with every row live; a
    decode program builds it once and hands it to every layer's call
    (the layers differ in the *table*, not in the items).  Rows no item
    visits read 0.

    **A window layer** (``window`` given: the query attends over its last
    ``window`` positions, its own among them) is the same body under the
    kernel name ``swa_decode_paged``: the work list holds only the spans
    those positions lie in (``paged_work_list(..., window=)``), a row's
    softmax starts at the first of them, and the mask has the lower bound
    ``key > pos - window`` beside ``key <= pos``.  Its pages may be a
    **ring**: the table maps *logical* pages (``inference.ring_table``),
    so a page that holds a later lap of the ring than the span it is read
    for is masked like any position outside the window.

    **A selection** (``mask (B, P * page_len)`` bool given: learned sparse
    attention, the row attends the positions it selects and no other) is
    the same body again, under the kernel name ``dsa_sparse_decode``: the
    mask rides as one more operand, int8, an item's ``(1, span *
    page_len)`` strip under the index map ``(slot, span)``, and joins the
    position mask of pass (2).  The walk still reads every filled page —
    2,048 selected positions scattered over a long row touch nearly every
    one — so the selection saves arithmetic, not bytes.  An item with
    nothing selected adds nothing, and a row that selects nothing reads 0.
    The branch is Python's: with ``mask=None`` the call traces what it
    traced without the operand.

    **Values narrower than keys** (the V cache ``(num_pages, Hkv,
    page_len, d_v)`` with ``d_v != d``): the score pass contracts over
    ``d``, the value pass over pages ``d_v`` wide, and the output is ``(B,
    H, 1, d_v)``.  Each operand's tile takes the form its **own** width
    says (``lanes_hold_rows`` below): keys 192 wide lie with their
    positions in the lanes, values 128 wide row-major, in one call.

    **A sink** (``sink (H,)`` float32 given: a learned logit a head that
    stands as one more column of the row's softmax, taking mass and
    carrying no value) rides as one more operand, ``(H, 1)``, and enters
    pass (2)'s running maximum and denominator once a row — at the row's
    first item the maximum starts at the sink and the denominator at 1 —
    under either kernel name.  Python's branch again: with ``sink=None``
    and ``d_v == d`` the call traces what it traced before.

    The page table rides the grid as a **prefetched scalar** too
    (``PrefetchScalarGridSpec``), so each program's K/V pages stream
    HBM→VMEM directly — the gather the lax path materializes never
    exists.  The item axis is sequential; one page is one kv block
    (``decode_paged_supported`` demands page_len be lane-aligned), and
    the online softmax state lives in VMEM scratch exactly like
    :func:`flash_decode`, started at a slot's span 0 and emitted at the
    span its position lies in.  Scores are float32 and the int8 pool's
    scales fold in in-register whatever the tile.  An item's products
    run in three passes — every (page, KV head) pair's scores, one
    softmax update for all its rows, every pair's probabilities x V — so
    that no pair's products wait for another's softmax: taken pair by
    pair that chain, not the bytes, was the step's time
    (docs/kernels.md)."""
    quant = isinstance(k_cache, dict)
    k_op = k_cache["q"] if quant else k_cache
    v_op = v_cache["q"] if quant else v_cache
    B, H, T, d = q.shape
    NP, Hkv, page_len, _ = k_op.shape
    d_v = v_op.shape[-1]
    P = page_table.shape[1]
    if T != 1:
        raise ValueError(f"flash_decode_paged serves exactly one query per slot, got T={T}")
    if H % Hkv:
        raise ValueError(f"flash_decode_paged: {H} query heads are not whole groups over {Hkv} KV heads")
    if not decode_paged_supported(B, H, P, page_len, d):
        raise ValueError(
            f"flash_decode_paged grid cannot serve (B={B}, H={H}, P={P}, "
            f"page_len={page_len}, d={d}); callers must dispatch through "
            "decode_paged_supported()"
        )
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = pallas_interpret_default()
    group = H // Hkv
    bh, span = paged_tile(k_op, P, v_op)
    # A head narrower than the 128 lanes: the TPU stores ``(..., page_len,
    # d)`` with ``page_len`` in the lanes (``d`` there would pad every
    # row to 128), so the tile Mosaic is handed is ``(d, page_len)`` —
    # the pool's own bytes under another name, where a ``(page_len, d)``
    # tile is a relayout of the whole pool in front of every call
    # (docs/kernels.md).  A rule on the shape, for every caller — and on
    # each operand's own width where K's and V's differ.
    lanes_hold_rows, v_lanes_hold_rows = d % 128 != 0, d_v % 128 != 0
    if lanes_hold_rows:
        k_op = jnp.swapaxes(k_op, 2, 3)
    if v_lanes_hold_rows:
        v_op = jnp.swapaxes(v_op, 2, 3)
    page_block = (1, bh, d, page_len) if lanes_hold_rows else (1, bh, page_len, d)
    v_block = (1, bh, d_v, page_len) if v_lanes_hold_rows else (1, bh, page_len, d_v)

    table = jnp.asarray(page_table, jnp.int32)
    # a position is inside the slot: the page it lies in is an item of the list
    pos_vec = jnp.clip(jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,)), 0, P * page_len - 1)
    if work is None:
        work = paged_work_list(pos_vec, None, page_len, P, span, window)
    slot, span_idx, n, live = work
    if slot.shape[0] != B * (P // span):
        raise ValueError(f"flash_decode_paged: a work list of {slot.shape[0]} items is not one over spans of {span} pages "
                         f"({B} slots x {P} pages): build it with paged_tile()'s span")

    # index maps receive (*grid_ids, *scalar_prefetch_refs)
    row = lambda h, i, pt, pv, sl, sp, n: (sl[i], h, 0, 0)  # noqa: E731
    kv_page = lambda j: (lambda h, i, pt, pv, sl, sp, n: (pt[sl[i], sp[i] * span + j], h, 0, 0))  # noqa: E731
    pages = [pl.BlockSpec(page_block, kv_page(j)) for j in range(span)]
    v_pages = pages if v_block == page_block else [pl.BlockSpec(v_block, kv_page(j)) for j in range(span)]
    in_specs = [pl.BlockSpec((1, bh, group, d), row)] + pages + v_pages
    args = [q.reshape(B, Hkv, group, d)] + [k_op] * span + [v_op] * span
    if quant:
        # (NP, H, page_len, 1) scales -> (NP, H, 1, page_len) row
        # vectors (contiguous reshape) sharing the score-row layout
        ks = k_cache["s"].reshape(NP, Hkv, 1, page_len)
        vs = v_cache["s"].reshape(NP, Hkv, 1, page_len)
        in_specs += [pl.BlockSpec((1, bh, 1, page_len), kv_page(j)) for j in range(span)] * 2
        args += [ks] * span + [vs] * span
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, 1, 1, span * page_len), lambda h, i, pt, pv, sl, sp, n: (sl[i], sp[i], 0, 0)))
        args.append(mask.astype(jnp.int8).reshape(B, P // span, 1, span * page_len))
    if sink is not None:
        in_specs.append(pl.BlockSpec((bh * group, 1), lambda h, i, pt, pv, sl, sp, n: (h, 0)))
        args.append(jnp.asarray(sink, jnp.float32).reshape(H, 1))

    kern = functools.partial(
        _flash_decode_paged_kernel,
        sm_scale=sm_scale,
        page_len=page_len,
        quant=quant,
        block_heads=bh,
        group=group,
        span=span,
        lanes_hold_rows=lanes_hold_rows,
        **({} if window is None else {"window": int(window)}),
        v_lanes_hold_rows=v_lanes_hold_rows,
        selected=mask is not None,
        sunk=sink is not None,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # one step at least: a grid bound of zero is nothing a compiled program needs to meet
        grid=(Hkv // bh, jnp.maximum(n[0], 1)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, group, d_v), row),
        scratch_shapes=[
            pltpu.VMEM((bh * group, 1), jnp.float32),   # m
            pltpu.VMEM((bh * group, 1), jnp.float32),   # l
            pltpu.VMEM((bh * group, d_v), jnp.float32),   # acc
            pltpu.VMEM((bh * group, span * page_len), jnp.float32),   # an item's scores, then its probabilities
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="dsa_sparse_decode" if mask is not None else "flash_decode_paged" if window is None else "swa_decode_paged",
    )(table, pos_vec, slot, span_idx, n, *args)
    # rows no item visited hold whatever the output buffer held
    return jnp.where(live[:, None, None, None], out, 0).reshape(B, H, 1, d_v)


def flash_decode_reference(q, k_cache, v_cache, pos, sm_scale=None, key_padding_mask=None):
    """The lax ground truth — literally ``cache_attention`` (kept as an
    alias so the parity tests and the bench name one seam)."""
    from deepspeed_tpu.ops.transformer.inference import cache_attention

    return cache_attention(
        q, k_cache, v_cache, pos, sm_scale=sm_scale,
        key_padding_mask=key_padding_mask, use_kernel=False,
    )


def tune_decode_blocks(B: int, H: int, S: int, d: int, kv_dtype="bfloat16",
                       iters: int = 8) -> dict:
    """Measured block search for one decode shape (host-side; run BEFORE
    executables build — e.g. ``tools/bench_kernels.py`` or an explicit
    serving warmup).  Times the standalone kernel on synthetic buffers
    with a ``block_until_ready`` fence per candidate and persists the
    winner through the process autotuner.  Honors DS_KERNEL_AUTOTUNE:
    mode ``off``/``cache`` return without measuring (defaults / cached
    winner)."""
    import time

    import numpy as np

    from deepspeed_tpu.ops.kernels.autotune import get_autotuner
    from deepspeed_tpu.ops.transformer.inference import init_kv_cache

    tuner = get_autotuner()
    quant = kv_dtype == "int8" or kv_dtype == jnp.int8
    key = dict(B=B, H=H, S=S, d=d, int8=quant)
    if tuner.mode != "force":
        return tuner.blocks_for("flash_decode", **key)

    rng = np.random.default_rng(0)
    qd = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.bfloat16)
    k_cache, v_cache = init_kv_cache(1, B, H, S, d, "int8" if quant else jnp.bfloat16)
    squeeze = lambda c: jax.tree.map(lambda a: a[0], c)  # noqa: E731 — drop layer dim
    k_cache, v_cache = squeeze(k_cache), squeeze(v_cache)
    if quant:
        k_cache = dict(k_cache, q=jnp.asarray(rng.integers(-127, 127, k_cache["q"].shape), jnp.int8),
                       s=jnp.abs(jnp.asarray(rng.standard_normal(k_cache["s"].shape), jnp.float32)) + 0.01)
        v_cache = dict(v_cache, q=jnp.asarray(rng.integers(-127, 127, v_cache["q"].shape), jnp.int8),
                       s=jnp.abs(jnp.asarray(rng.standard_normal(v_cache["s"].shape), jnp.float32)) + 0.01)
    else:
        k_cache = jnp.asarray(rng.standard_normal(k_cache.shape), jnp.bfloat16)
        v_cache = jnp.asarray(rng.standard_normal(v_cache.shape), jnp.bfloat16)
    pos = jnp.full((B,), S - 1, jnp.int32)

    def timer(blocks):
        # host-side standalone tuning probe on synthetic replicated
        # buffers — no mesh layout to pin
        fn = jax.jit(  # ds-lint: disable=bare-jit
            lambda q_, k_, v_, p_: flash_decode(
                q_, k_, v_, p_, block_k=blocks["block_k"],
                block_slots=blocks["block_slots"],
            )
        )
        fn(qd, k_cache, v_cache, pos).block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(qd, k_cache, v_cache, pos)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    return tuner.tune("flash_decode", timer, **key)


@register_op(
    "flash_decode", "pallas",
    "Fused single-query flash decode over the slot KV pool; int8 codes dequantized in-register",
)
def _load_flash_decode():
    return flash_decode
