"""Block-sparse attention.

Reference: ``ops/sparse_attention/`` — Triton SDD/DSD/DDS block matmuls
(``matmul.py:16-615``), block softmax (``softmax.py:107-230``), the
``SparsityConfig`` layout family (``sparsity_config.py:9-662``:
Dense/Fixed/Variable/BigBird/BSLongformer) and ``SparseSelfAttention``
(``sparse_self_attention.py:14``).  The reference's long-sequence story
is exactly this stack (10-16× longer sequences, SURVEY.md §5.7).

TPU-native re-design (NOT a Triton port):

* Layouts stay: the ``SparsityConfig`` classes reproduce the reference's
  constructor surface and emit the same (heads, nb, nb) 0/1 block masks,
  so existing recipes keep working.
* Two interchangeable kernels (``backend=`` on
  ``block_sparse_attention``; auto prefers splash):

  - **splash** (default on MXU-worthy blocks): one Pallas grid step per
    (batch·head, q-row, edge), with the layout's kv-block index applied
    in the K/V BlockSpec index_map (scalar-prefetch) — the "gather" IS
    the pipeline's block fetch, so neither O(nnz) strips nor the
    O(nnz·block²) fp32 score tensors ever touch HBM.  Online-softmax
    state rides VMEM scratch across a row's sequential edge steps.  The
    backward is SPLIT: a q-major dq kernel plus a kv-major dkv kernel
    over a column-sorted edge list whose dk/dv accumulate conflict-free
    in VMEM (no strip outputs, no segment-sum).  Measured kernel-level
    fwd+bwd vs dense causal flash on v5e (block 256): 1.29× at 8k,
    21.5× at 16k; full-train-step 1.11× at 8k, 11.98× at 16k
    (round-5 crossover sweep, before the ledger; PERF.md).
  - **gather**: the XLA formulation (one ``take`` + dense masked
    block attention) — differentiable end-to-end; it is also the
    splash path's backward via recompute, and the numerics oracle.

  Both are O(nnz_blocks) compute, the asymptotics the Triton SDD/DSD
  kernels buy.
* Numerics are validated against dense attention under the equivalent
  element mask (tests/test_sparse_attention.py), mirroring the
  reference's ``test_sparse_attention.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import random as _random
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.attention.flash_attention import DEFAULT_MASK_VALUE
from deepspeed_tpu.ops.registry import register_op
from deepspeed_tpu.utils.device import on_tpu_backend, pallas_interpret_default

# ---------------------------------------------------------------------------
# Layout configs (reference sparsity_config.py; same constructor surface)
# ---------------------------------------------------------------------------


class SparsityConfig:
    """Abstract layout generator (reference ``SparsityConfig`` :9).

    ``block`` is the square block size in tokens; layouts are
    (num_heads, seq_blocks, seq_blocks) uint8 arrays."""

    def __init__(self, num_heads: int, block: int = 16, different_layout_per_head: bool = False):
        self.num_heads = num_heads
        self.block = block
        self.different_layout_per_head = different_layout_per_head
        self.num_layout_heads = num_heads if different_layout_per_head else 1

    def setup_layout(self, seq_len: int) -> np.ndarray:
        if seq_len % self.block != 0:
            raise ValueError(f"seq_len {seq_len} must be divisible by block {self.block}")
        nb = seq_len // self.block
        return np.zeros((self.num_heads, nb, nb), dtype=np.uint8)

    def check_and_propagate_first_head_layout(self, layout: np.ndarray) -> np.ndarray:
        if not self.different_layout_per_head:
            layout[1:] = layout[0]
        return layout

    def make_layout(self, seq_len: int) -> np.ndarray:
        raise NotImplementedError


class DenseSparsityConfig(SparsityConfig):
    """All blocks active (reference :63) — for correctness comparisons."""

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        layout[:] = 1
        return layout


class FixedSparsityConfig(SparsityConfig):
    """Fixed pattern à la Sparse Transformers (reference :94): local
    windows of ``num_local_blocks`` plus global attention to the last
    ``num_global_blocks`` of each window (vertical stripes; horizontal
    too when bidirectional)."""

    def __init__(
        self,
        num_heads: int,
        block: int = 16,
        different_layout_per_head: bool = False,
        num_local_blocks: int = 4,
        num_global_blocks: int = 1,
        attention: str = "bidirectional",
        horizontal_global_attention: bool = False,
        num_different_global_patterns: int = 1,
    ):
        super().__init__(num_heads, block, different_layout_per_head)
        if num_local_blocks % num_global_blocks != 0:
            raise ValueError("num_local_blocks must be divisible by num_global_blocks")
        if attention not in ("unidirectional", "bidirectional"):
            raise ValueError("attention must be uni/bidirectional")
        if horizontal_global_attention and attention != "bidirectional":
            raise ValueError("horizontal global attention requires bidirectional attention")
        if num_different_global_patterns > 1 and not different_layout_per_head:
            raise ValueError("num_different_global_patterns > 1 requires different_layout_per_head")
        if num_different_global_patterns > num_local_blocks // num_global_blocks:
            raise ValueError("num_different_global_patterns too large")
        self.num_local_blocks = num_local_blocks
        self.num_global_blocks = num_global_blocks
        self.attention = attention
        self.horizontal_global_attention = horizontal_global_attention
        self.num_different_global_patterns = num_different_global_patterns

    def _set_local(self, layout: np.ndarray, h: int) -> None:
        nb = layout.shape[1]
        for start in range(0, nb, self.num_local_blocks):
            end = min(start + self.num_local_blocks, nb)
            for r in range(start, end):
                hi = (r + 1) if self.attention == "unidirectional" else end
                layout[h, r, start:hi] = 1

    def _set_global(self, layout: np.ndarray, h: int) -> None:
        nb = layout.shape[1]
        # which block inside each window carries the global stripes —
        # rotates across heads when multiple patterns are requested
        pattern = h % self.num_different_global_patterns
        first = self.num_local_blocks - (1 + pattern) * self.num_global_blocks
        for wstart in range(0, nb, self.num_local_blocks):
            gstart = wstart + first
            gend = gstart + self.num_global_blocks
            if gstart >= nb:
                continue
            gend = min(gend, nb)
            # vertical stripes: rows at/after the global blocks attend to
            # them (all rows when bidirectional)
            if self.attention == "bidirectional":
                layout[h, :, gstart:gend] = 1
            else:
                layout[h, gstart:, gstart:gend] = 1
            if self.horizontal_global_attention:
                layout[h, gstart:gend, :] = 1

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        for h in range(self.num_layout_heads):
            self._set_local(layout, h)
            self._set_global(layout, h)
        layout = self.check_and_propagate_first_head_layout(layout)
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return layout


class VariableSparsityConfig(SparsityConfig):
    """Variable local window sizes + explicit global blocks + random
    blocks (reference :421)."""

    def __init__(
        self,
        num_heads: int,
        block: int = 16,
        different_layout_per_head: bool = False,
        num_random_blocks: int = 0,
        local_window_blocks: Optional[List[int]] = None,
        global_block_indices: Optional[List[int]] = None,
        global_block_end_indices: Optional[List[int]] = None,
        attention: str = "bidirectional",
        horizontal_global_attention: bool = False,
    ):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_random_blocks = num_random_blocks
        self.local_window_blocks = local_window_blocks or [4]
        self.global_block_indices = global_block_indices if global_block_indices is not None else [0]
        self.global_block_end_indices = global_block_end_indices
        if global_block_end_indices is not None and len(global_block_end_indices) != len(self.global_block_indices):
            raise ValueError("global_block_end_indices must pair with global_block_indices")
        self.attention = attention
        self.horizontal_global_attention = horizontal_global_attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        nb = layout.shape[1]
        rng = _random.Random(0)
        for h in range(self.num_layout_heads):
            # local variable-width windows, cycling the last width
            start = 0
            i = 0
            while start < nb:
                w = self.local_window_blocks[min(i, len(self.local_window_blocks) - 1)]
                end = min(start + w, nb)
                for r in range(start, end):
                    hi = (r + 1) if self.attention == "unidirectional" else end
                    layout[h, r, start:hi] = 1
                start, i = end, i + 1
            # global
            for gi, g in enumerate(self.global_block_indices):
                gend = (
                    self.global_block_end_indices[gi]
                    if self.global_block_end_indices is not None
                    else g + 1
                )
                g0, g1 = min(g, nb), min(gend, nb)
                layout[h, :, g0:g1] = 1
                if self.horizontal_global_attention:
                    layout[h, g0:g1, :] = 1
            # random
            for r in range(nb):
                for _ in range(self.num_random_blocks):
                    layout[h, r, rng.randrange(nb)] = 1
        layout = self.check_and_propagate_first_head_layout(layout)
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return layout


class BigBirdSparsityConfig(SparsityConfig):
    """BigBird ITC: random + sliding window + global first/last blocks
    (reference :243)."""

    def __init__(
        self,
        num_heads: int,
        block: int = 16,
        different_layout_per_head: bool = False,
        num_random_blocks: int = 1,
        num_sliding_window_blocks: int = 3,
        num_global_blocks: int = 1,
        attention: str = "bidirectional",
    ):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_random_blocks = num_random_blocks
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.num_global_blocks = num_global_blocks
        self.attention = attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        nb = layout.shape[1]
        if nb < self.num_sliding_window_blocks:
            raise ValueError(f"seq has {nb} blocks < sliding window {self.num_sliding_window_blocks}")
        rng = _random.Random(0)
        w = self.num_sliding_window_blocks // 2
        g = self.num_global_blocks
        for h in range(self.num_layout_heads):
            for r in range(nb):
                layout[h, r, max(0, r - w) : min(nb, r + w + 1)] = 1  # window
                for _ in range(self.num_random_blocks):  # random
                    layout[h, r, rng.randrange(nb)] = 1
            layout[h, :, :g] = 1  # global columns (first blocks)
            layout[h, :g, :] = 1  # global rows
            if self.attention == "bidirectional":
                layout[h, :, nb - g :] = 1
                layout[h, nb - g :, :] = 1
        layout = self.check_and_propagate_first_head_layout(layout)
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return layout


class BSLongformerSparsityConfig(SparsityConfig):
    """Block-sparse Longformer: sliding window + selected global blocks
    (reference :544)."""

    def __init__(
        self,
        num_heads: int,
        block: int = 16,
        different_layout_per_head: bool = False,
        num_sliding_window_blocks: int = 3,
        global_block_indices: Optional[List[int]] = None,
        global_block_end_indices: Optional[List[int]] = None,
        attention: str = "bidirectional",
    ):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.global_block_indices = global_block_indices if global_block_indices is not None else [0]
        self.global_block_end_indices = global_block_end_indices
        self.attention = attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        nb = layout.shape[1]
        w = self.num_sliding_window_blocks // 2
        for h in range(self.num_layout_heads):
            for r in range(nb):
                layout[h, r, max(0, r - w) : min(nb, r + w + 1)] = 1
            for gi, g in enumerate(self.global_block_indices):
                gend = (
                    self.global_block_end_indices[gi]
                    if self.global_block_end_indices is not None
                    else g + 1
                )
                g0, g1 = min(g, nb), min(gend, nb)
                layout[h, :, g0:g1] = 1
                layout[h, g0:g1, :] = 1
        layout = self.check_and_propagate_first_head_layout(layout)
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return layout


# ---------------------------------------------------------------------------
# Kernel: gather-based blockwise sparse attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _head_uniform(layout: np.ndarray) -> bool:
    """True when every head shares one layout (the default: configs
    propagate head 0 unless ``different_layout_per_head``)."""
    return layout.shape[0] == 1 or bool(np.all(layout == layout[:1]))


def _dense_row_mask(layout: np.ndarray, exempt_uniform_full: bool = False) -> np.ndarray:
    """(H, nb) bool: q-rows at FULL degree, routed to the dense bucket.
    Single definition shared by the row-major (`_layout_gather_indices`)
    and column-major (`_layout_dkv_edges`) enumerations — they must
    agree or dense rows' dk/dv would double-count or drop.

    ``exempt_uniform_full`` (the SPLASH path only): the bucket exists so
    a FEW full rows (BigBird/Longformer horizontal globals) don't pad
    every sparse row's degree up to nb.  When EVERY row of every head is
    full-degree (an all-ones layout — the flash_attention VMEM-fallback
    uses splash as a plain kv-blocked dense kernel), there is no padding
    penalty and no reason to materialize: no row goes to the bucket.
    The XLA *gather* formulation must NOT take this exemption — its
    per-row K/V gather at deg=nb would replicate full K/V nb-fold; the
    bucket is exactly its cheap path for full rows."""
    mask = layout.sum(-1) >= layout.shape[-1]
    if exempt_uniform_full and mask.all():
        return np.zeros_like(mask)
    return mask


def _layout_gather_indices(layout: np.ndarray, exempt_uniform_full: bool = False) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Row-bucketed layout prep — the analog of the reference's C++ LUT
    helper (``csrc/sparse_attention/utils.cpp``), plain numpy.

    Rows are split into two buckets so a few *fully dense* rows (the
    horizontal-global rows BigBird/Longformer emit) don't pad every
    sparse row up to full degree:

    * sparse rows → (idx (H, nb, deg), valid (H, nb, deg)): active
      kv-block ids padded to the max degree **among sparse rows only**;
      dense rows have valid=False everywhere (their gather output is 0
      and gets overwritten by the dense bucket).
    * dense rows → (dense_rows (H, M), dense_valid (H, M)): the q-block
      ids of full-degree rows, padded to the max count across heads.
    """
    H, nb, _ = layout.shape
    row_deg = layout.sum(-1)  # (H, nb)
    dense_mask = _dense_row_mask(layout, exempt_uniform_full)
    sparse_deg = int(np.where(dense_mask, 0, row_deg).max())
    deg = max(1, sparse_deg)
    idx = np.zeros((H, nb, deg), np.int32)
    valid = np.zeros((H, nb, deg), bool)
    for h in range(H):
        for r in range(nb):
            if dense_mask[h, r]:
                continue
            cols = np.nonzero(layout[h, r])[0]
            idx[h, r, : len(cols)] = cols
            valid[h, r, : len(cols)] = True
    M = int(dense_mask.sum(-1).max())
    dense_rows = np.zeros((H, max(M, 1)), np.int32)
    dense_valid = np.zeros((H, max(M, 1)), bool)
    for h in range(H):
        rows = np.nonzero(dense_mask[h])[0]
        dense_rows[h, : len(rows)] = rows
        dense_valid[h, : len(rows)] = True
    if M == 0:
        dense_rows = dense_rows[:, :0]
        dense_valid = dense_valid[:, :0]
    return idx, valid, dense_rows, dense_valid


def block_sparse_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    layout: np.ndarray,
    block: int,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    key_padding_mask: Optional[jnp.ndarray] = None,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """Attention restricted to the active blocks of ``layout``.

    ``q,k,v``: (B, H, T, hd); ``layout``: (H, T//block, T//block) 0/1
    numpy (static).  ``backend``:

    * ``"splash"`` — the streamed Pallas kernel (O(nnz) compute AND HBM
      traffic, one K/V block DMA per active pair); rows with no active
      block produce zeros (the kernel's l==0 guard);
    * ``"gather"`` — the XLA gather formulation below (O(nnz) compute,
      differentiable end-to-end; also the splash backward's recompute);
    * ``None`` — auto: splash when eligible (no key-padding mask,
      MXU-worthy ``block >= 64``, ``T % block == 0``, running on TPU),
      else gather.  NOTE the numerics difference: splash runs its score/
      value dots in the input dtype (bf16 on the MXU) with fp32
      accumulation, while gather runs fp32 dots — auto therefore changes
      dot precision when it switches backends on TPU.

    ``causal=True`` additionally applies the elementwise causal mask
    inside diagonal blocks (the layout itself should already be
    lower-triangular for unidirectional configs)."""
    B, H, T, hd = q.shape
    nb = T // block
    assert layout.shape == (H, nb, nb), f"layout {layout.shape} != {(H, nb, nb)}"
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    if backend not in (None, "gather", "splash"):
        raise ValueError(f"backend must be None|'gather'|'splash', got {backend!r}")
    if backend != "gather":
        eligible = key_padding_mask is None and block >= 64 and T % block == 0
        if backend == "splash":
            if not eligible:
                raise ValueError("splash backend needs block >= 64 and no key_padding_mask")
            return splash_attention(q, k, v, layout, block, causal=causal, sm_scale=sm_scale)
        # auto additionally requires a TPU: the interpret-mode kernel
        # exists as a numerics oracle; off TPU the compiled XLA gather
        # formulation is strictly faster
        if eligible and on_tpu_backend():
            return splash_attention(q, k, v, layout, block, causal=causal, sm_scale=sm_scale)
    idx_np, valid_np, drows_np, dvalid_np = _layout_gather_indices(layout)
    deg = idx_np.shape[-1]
    idx = jnp.asarray(idx_np)  # (H, nb, deg)
    valid = jnp.asarray(valid_np)

    qb = q.reshape(B, H, nb, block, hd)
    kb = k.reshape(B, H, nb, block, hd)
    vb = v.reshape(B, H, nb, block, hd)

    # ---- sparse bucket: gather active kv blocks per (h, q-block) --------
    gather = jax.vmap(  # over batch
        jax.vmap(  # over heads
            lambda blocks, ids: jnp.take(blocks, ids, axis=0), in_axes=(0, 0)
        ),
        in_axes=(0, None),
    )
    kg = gather(kb, idx)  # (B, H, nb, deg, block, hd)
    vg = gather(vb, idx)

    s = jnp.einsum("bhnqd,bhnekd->bhnqek", qb.astype(jnp.float32), kg.astype(jnp.float32)) * sm_scale
    mask = valid[None, :, :, None, :, None]  # (1,H,nb,1,deg,1)
    if causal:
        q_pos = jnp.arange(nb)[:, None] * block + jnp.arange(block)[None, :]  # (nb, block)
        k_pos = idx[..., None] * block + jnp.arange(block)[None, None, None, :]  # (H, nb, deg, block)
        causal_ok = q_pos[None, :, :, None, None] >= k_pos[:, :, None, :, :]  # (H,nb,block,deg,block)
        mask = mask & causal_ok[None]
    if key_padding_mask is not None:
        kp_blocks = key_padding_mask.reshape(B, nb, block)
        kpg = jnp.take(kp_blocks, idx, axis=1)  # (B, H, nb, deg, block)
        mask = mask & kpg[:, :, :, None, :, :]
    s = jnp.where(mask, s, NEG_INF)
    s = s.reshape(B, H, nb, block, deg * block)
    # explicit re-mask after softmax: a FULLY-masked row has uniform
    # exp(0)=1 everywhere (row_max == NEG_INF), so the denom>0 guard
    # alone would emit a junk average instead of zeros
    p = _masked_softmax(s).reshape(B, H, nb, block, deg, block) * mask.astype(jnp.float32)
    out = jnp.einsum("bhnqek,bhnekd->bhnqd", p, vg.astype(jnp.float32))

    # ---- dense bucket: the few full-degree (horizontal-global) rows -----
    out = out.reshape(B, H, T, hd).astype(q.dtype)
    if drows_np.shape[1] > 0:
        out = _apply_dense_rows(out, q, k, v, drows_np, dvalid_np, block, causal, sm_scale, key_padding_mask)
    return out


def _apply_dense_rows(out, q, k, v, drows_np, dvalid_np, block, causal, sm_scale, key_padding_mask):
    """Overwrite the full-degree (horizontal-global) q-rows of ``out``
    with dense full-T attention — shared by the gather and splash paths."""
    B, H, T, hd = q.shape
    nb = T // block
    qb = q.reshape(B, H, nb, block, hd)
    drows = jnp.asarray(drows_np)  # (H, M)
    dvalid = jnp.asarray(dvalid_np)
    qd = jnp.take_along_axis(qb, drows[None, :, :, None, None], axis=2)  # (B,H,M,block,hd)
    sd = jnp.einsum("bhmqd,bhtd->bhmqt", qd.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    dmask = jnp.ones((1, 1, 1, 1, T), bool)
    if causal:
        q_pos_d = drows[:, :, None] * block + jnp.arange(block)[None, None, :]  # (H,M,block)
        dmask = dmask & (q_pos_d[None, :, :, :, None] >= jnp.arange(T)[None, None, None, None, :])
    if key_padding_mask is not None:
        dmask = dmask & key_padding_mask[:, None, None, None, :]
    sd = jnp.where(dmask, sd, NEG_INF)
    pd = _masked_softmax(sd)
    od = jnp.einsum("bhmqt,bhtd->bhmqd", pd, v.astype(jnp.float32))  # (B,H,M,block,hd)
    # scatter dense-row outputs back over the sparse outputs
    onehot = jax.nn.one_hot(drows, nb, dtype=jnp.float32) * dvalid[..., None]  # (H,M,nb)
    od_full = jnp.einsum("hmn,bhmqd->bhnqd", onehot, od)
    is_dense_row = (jnp.sum(onehot, axis=1) > 0)[None, :, :, None, None]  # (1,H,nb,1,1)
    ob = out.reshape(B, H, nb, block, hd)
    ob = jnp.where(is_dense_row, od_full.astype(out.dtype), ob)
    return ob.reshape(B, H, T, hd)


def _masked_softmax(s):
    row_max = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - row_max)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    return jnp.where(denom > 0, p / jnp.maximum(denom, 1e-30), 0.0)


# ---------------------------------------------------------------------------
# Pallas splash kernel: fused block-sparse attention
# ---------------------------------------------------------------------------
#
# The Triton SDD/DSD/DDS stack (reference matmul.py:16-615 + trsrc/*.tr)
# becomes gather + ONE fused kernel: the static layout's active K/V
# blocks are gathered per (head, q-row) into a compact (…, deg, block,
# hd) buffer — O(nnz) bytes in the input dtype — and a Pallas program
# per (batch·head, q-row) runs the whole online softmax over its `deg`
# blocks in registers.  This kills the gather formulation's dominant
# cost: the O(nnz·block²) fp32 score/probability tensors never touch
# HBM.  Horizontal-global (fully dense) rows ride the existing dense
# bucket so they don't pad every row's degree to nb.


def _dot_rhs_t(a, bt):
    """a @ bt.T without materializing the transpose: contract a's last
    dim with bt's LAST dim — (M, K) × (N, K) → (M, N)."""
    return jax.lax.dot_general(
        a, bt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_lhs_t(at, b):
    """at.T @ b without materializing the transpose: contract FIRST
    dims — (K, M) × (K, N) → (M, N)."""
    return jax.lax.dot_general(
        at, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _edge_keep(ok, q_block, k_block, block: int, causal: bool):
    """(block, block) keep mask for one (q-block, kv-block) edge:
    edge validity broadcast, plus the elementwise causal constraint when
    the blocks' global positions demand it."""
    if causal:
        q_pos = q_block * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        k_pos = k_block * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        return jnp.logical_and(ok, q_pos >= k_pos)
    return jnp.broadcast_to(ok, (block, block))


def _bwd_p_ds(q, g, k, v, lse, delta, keep, sm_scale: float):
    """Shared P/dS rebuild for BOTH backward kernels (q-major dq and
    kv-major dkv): S from the saved-lse form, P = exp(S − lse) with the
    explicit keep re-mask (saved lse is +inf for zero-degree rows ⇒ p
    exactly 0), dP = g·vᵀ, dS = P∘(dP − delta)·scale.  One definition so
    a numerics change cannot diverge the two kernels' gradients."""
    s = _dot_rhs_t(q, k) * sm_scale
    s = jnp.where(keep, s, DEFAULT_MASK_VALUE)
    p = jnp.exp(s - lse) * keep.astype(jnp.float32)
    dp = _dot_rhs_t(g, v)  # g @ v^T
    ds = p * (dp - delta) * sm_scale
    return p, ds


def _splash_kernel(
    idx_ref, valid_ref, q_ref, k_ref, v_ref, o_ref, *rest,
    sm_scale: float, causal: bool, block: int, deg: int, heads: int,
):
    """One (q-row, edge) pair per grid step; the EDGE axis is the
    innermost grid dim and the layout's kv-block index is applied in the
    K/V BlockSpec index_map (scalar-prefetch) — the "gather" is the
    pipeline's own block fetch, so no O(nnz) strips ever materialize in
    HBM.  The r4 design gathered strips in XLA first; measured at 8k
    those gathers were most of the sparse step (9.7 ms of strips vs
    ~4.5 ms of kernels) and three in-kernel-DMA alternatives all hit
    Mosaic walls (2-D DMA of (block, 64) tiles: lane-dim < 128
    rejected; transposed/padded staging: 14-16 ms of XLA relayouts;
    1-D DMA + reshape: unsupported shape cast).

    Online-softmax state (m, l, acc) lives in VMEM scratch that
    persists across the sequential edge steps of one row; the output
    (and optional lse) is written at the row's last edge."""
    rest = list(rest)
    m_scr, l_scr, acc_scr = rest[-3], rest[-2], rest[-1]
    lse_ref = rest[0] if len(rest) == 4 else None
    bh = pl.program_id(0)
    h = bh % heads
    row = pl.program_id(1)
    e = pl.program_id(2)
    hd = q_ref.shape[-1]

    @pl.when(e == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = _dot_rhs_t(q, k) * sm_scale  # q @ k^T, contracting the hd dims
    ki = idx_ref[h, row * deg + e]
    ok = valid_ref[h, row * deg + e] == 1
    keep = _edge_keep(ok, row, ki, block, causal)
    s = jnp.where(keep, s, DEFAULT_MASK_VALUE)
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # p masked EXPLICITLY: if every entry of a row is masked,
    # m_new == MASK_VALUE and exp(s - m_new) would be 1, faking a
    # nonzero l — the zero-degree-row guard below depends on l==0
    p = jnp.exp(s - m_new) * keep.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )

    @pl.when(e == deg - 1)
    def _flush():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)
        if lse_ref is not None:
            # +inf for zero-degree rows ⇒ bwd's exp(s − lse) is exactly 0
            m = m_scr[...]
            lse = jnp.where(
                l[:, 0] == 0.0, jnp.inf, m[:, 0] + jnp.log(jnp.maximum(l[:, 0], 1e-37))
            )
            lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, block))


def _splash_prep(q, k, v, layout: np.ndarray, block: int):
    """Shared fwd/bwd staging: SMEM index arrays + (bh, nb, block, hd)
    block views of q/k/v — the kernels' K/V index_maps pick blocks
    straight from these (no strip gathers)."""
    B, H, T, hd = q.shape
    nb = T // block
    # Head-uniform layouts (the default: configs propagate head 0) keep
    # ONE row of prefetch indices instead of H — SMEM is ~1MB/core and
    # the (H, E) form bursts it at long sequences (32k dense-tril:
    # 12 heads × ~16k edges × 4B ≈ 780KB PER ARRAY)
    if _head_uniform(layout):
        layout = layout[:1]
    lh = layout.shape[0]
    idx_np, valid_np, drows_np, dvalid_np = _layout_gather_indices(layout, exempt_uniform_full=True)
    deg = idx_np.shape[-1]
    # prefetch arrays live in SMEM, where the LAST dim pads to 128
    # lanes — keep them 2-D (lh, nb·deg) or a (lh, nb, deg) layout costs
    # 32x its logical bytes and overflows SMEM at long sequences
    idx2 = jnp.asarray(idx_np.reshape(idx_np.shape[0], -1))
    valid2 = jnp.asarray(valid_np.astype(np.int32).reshape(valid_np.shape[0], -1))
    qr = q.reshape(B * H, nb, block, hd)
    kr = k.reshape(B * H, nb, block, hd)
    vr = v.reshape(B * H, nb, block, hd)
    return qr, kr, vr, idx2, valid2, deg, nb, lh, drows_np, dvalid_np


def _splash_fwd(q, k, v, layout: np.ndarray, block: int, causal: bool, sm_scale: float, interpret: bool, want_lse: bool = False):
    B, H, T, hd = q.shape
    qr, kr, vr, idx2, valid2, deg, nb, lh, _dr, _dv = _splash_prep(q, k, v, layout, block)
    H_ = lh

    q_spec = pl.BlockSpec((1, 1, block, hd), lambda b, r, e, idx, valid: (b, r, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block, hd),
        lambda b, r, e, idx, valid: (b, idx[b % H_, r * deg + e], 0, 0),
    )
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B * H, nb, block, hd), q.dtype)]
    if want_lse:
        out_specs.append(
            pl.BlockSpec((1, 1, 8, block), lambda b, r, e, idx, valid: (b, r, 0, 0))
        )
        out_shape.append(jax.ShapeDtypeStruct((B * H, nb, 8, block), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H, nb, deg),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, hd), jnp.float32),
        ],
    )
    kern = functools.partial(
        _splash_kernel, sm_scale=sm_scale, causal=causal, block=block, deg=deg, heads=lh
    )
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(idx2, valid2, qr, kr, vr)
    if want_lse:
        out, lse = outs
        return out.reshape(B, H, T, hd), lse[:, :, 0, :].reshape(B, H, T)
    return outs[0].reshape(B, H, T, hd)


def _splash_dq_kernel(
    idx_ref, valid_ref, q_ref, k_ref, v_ref, lse_ref, g_ref, dq_ref,
    dq_scr,
    *, sm_scale: float, causal: bool, block: int, deg: int, heads: int,
):
    """dq backward, one (q-row, edge) pair per grid step — the q-major
    half of the split backward.  P = exp(S − lse) rebuilds from the
    forward's SAVED logsumexp, then p → dp → ds accumulates dq in
    scratch, flushed at the row's last edge.  K/V blocks arrive through
    the same index_map "gather-in-the-pipeline" as the forward.
    ``delta`` comes in precomputed through the lse row buffer's sibling
    sublane.  dk/dv live in the kv-major sibling kernel
    (``_splash_dkv_kernel``) where their accumulation is conflict-free —
    the r5.0 design wrote per-edge dk/dv STRIPS here and segment-summed
    them outside, and that strip+scatter tail was most of the remaining
    sparse overhead at 8k (round 5, before the ledger)."""
    bh = pl.program_id(0)
    h = bh % heads
    row = pl.program_id(1)
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0]
    g = g_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    # (1, 8, block) layout: full-lane-dim reads (see fwd comment)
    lse = lse_ref[0, 0, 0, :][:, None]
    delta = lse_ref[0, 0, 1, :][:, None]
    ki = idx_ref[h, row * deg + e]
    ok = valid_ref[h, row * deg + e] == 1
    keep = _edge_keep(ok, row, ki, block, causal)
    _, ds = _bwd_p_ds(q, g, k, v, lse, delta, keep, sm_scale)
    dq_scr[...] = dq_scr[...] + jnp.dot(
        ds.astype(k.dtype), k, preferred_element_type=jnp.float32
    )

    @pl.when(e == deg - 1)
    def _flush():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _layout_dkv_edges(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Column-major (kv-block-major) edge enumeration for the dkv
    kernel: per head, the sparse-row edges sorted by kv column, so every
    kv block's contributions are CONSECUTIVE grid steps and dk/dv can
    accumulate in VMEM scratch with no write conflicts.  Every column
    appears at least once (untouched columns get one invalid edge) so
    the kernel writes every dk/dv output block exactly once — no
    outside scatter, and no garbage in never-visited blocks.  Dense
    (full-degree) rows are excluded, matching ``_layout_gather_indices``:
    their gradient flows through the XLA dense bucket's autodiff.

    Returns (qidx, kcol, flags), each (LH, E) int32 where LH = 1 for
    head-uniform layouts (SMEM: see `_splash_prep`) else H; flags bit0 =
    edge valid, bit1 = first edge of its column run, bit2 = last.

    Runs at every backward trace, so it is fully vectorized (nonzero on
    the transposed layout gives the column-major order directly) and
    cached per layout fingerprint — the r5 pure-Python enumeration was
    O(H·nb²) tuple churn (~65k allocations/head at 32k seq, block 128)."""
    if _head_uniform(layout):
        layout = layout[:1]  # before the key: fingerprint 1/H of the bytes
    return _layout_dkv_edges_cached(
        layout.shape, str(layout.dtype), np.ascontiguousarray(layout).tobytes()
    )


@functools.lru_cache(maxsize=64)
def _layout_dkv_edges_cached(
    shape: Tuple[int, ...], dtype: str, data: bytes
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    layout = np.frombuffer(data, dtype=dtype).reshape(shape)
    H, nb, _ = layout.shape
    dense_mask = _dense_row_mask(layout, exempt_uniform_full=True)
    per_head: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    for h in range(H):
        keep = (layout[h] != 0) & ~dense_mask[h][:, None]  # (row, col)
        # nonzero on the transpose enumerates sorted by (col, row) — the
        # exact column-major order the kernel's run detection needs
        cols, rows = np.nonzero(keep.T)
        empty = np.nonzero(~keep.any(axis=0))[0]  # columns with no edge
        c = np.concatenate([cols, empty])
        r = np.concatenate([rows, np.zeros(len(empty), np.intp)])
        ok = np.concatenate([np.ones(len(cols), np.int32), np.zeros(len(empty), np.int32)])
        # stable: preserves ascending-row order within each real column
        # (empty columns contribute exactly one edge, so order is total)
        order = np.argsort(c, kind="stable")
        c, r, ok = c[order], r[order], ok[order]
        boundary = np.diff(c) != 0  # column-run boundaries
        first = np.concatenate([[True], boundary])
        last = np.concatenate([boundary, [True]])
        per_head.append((r, c, ok | (first << 1) | (last << 2)))
    E = max(len(r) for r, _, _ in per_head)
    qidx = np.zeros((H, E), np.int32)
    # padding rides the FINAL column's run (flags 0): same output block
    # index as the last real edge, so the tail forces no extra writeback
    kcol = np.full((H, E), nb - 1, np.int32)
    flags = np.zeros((H, E), np.int32)
    for h, (r, c, fl) in enumerate(per_head):
        n = len(r)
        qidx[h, :n] = r
        kcol[h, :n] = c
        flags[h, :n] = fl
    return qidx, kcol, flags


def _splash_dkv_kernel(
    qidx_ref, kcol_ref, flags_ref, q_ref, k_ref, v_ref, lse_ref, g_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, sm_scale: float, causal: bool, block: int, heads: int,
):
    """dk/dv backward over the column-sorted edge list: one edge per
    grid step, K/V (and the dk/dv output blocks) held constant across a
    column's run — Pallas fetches them once per column and writes each
    output block once, at the run's last edge, from fp32 VMEM
    accumulators.  q/g/lse stream per edge through their index_maps.
    Same P = exp(S − lse) rebuild as the dq kernel; invalid (padding)
    edges contribute exact zeros."""
    bh = pl.program_id(0)
    h = bh % heads
    e = pl.program_id(1)
    flags = flags_ref[h, e]
    ok = (flags & 1) == 1
    isfirst = (flags & 2) != 0
    islast = (flags & 4) != 0

    @pl.when(isfirst)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0]
    g = g_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    lse = lse_ref[0, 0, 0, :][:, None]
    delta = lse_ref[0, 0, 1, :][:, None]
    qi = qidx_ref[h, e]
    ki = kcol_ref[h, e]
    keep = _edge_keep(ok, qi, ki, block, causal)
    p, ds = _bwd_p_ds(q, g, k, v, lse, delta, keep, sm_scale)
    dk_scr[...] = dk_scr[...] + _dot_lhs_t(ds.astype(q.dtype), q)  # ds^T @ q
    dv_scr[...] = dv_scr[...] + _dot_lhs_t(p.astype(g.dtype), g)  # p^T @ g

    @pl.when(islast)
    def _flush():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _splash_bwd(q, k, v, out, lse, g, layout: np.ndarray, block: int, causal: bool, sm_scale: float, interpret: bool):
    B, H, T, hd = q.shape
    qr, kr, vr, idx2, valid2, deg, nb, lh, _dr, _dv = _splash_prep(q, k, v, layout, block)
    H_ = lh
    gr = g.reshape(B * H, nb, block, hd)
    # per-row scalars ride ONE (bh, nb, 8, block) buffer: sublane 0 =
    # the fwd's saved lse, sublane 1 = delta = rowsum(dO ∘ O) (computed
    # here in XLA — one fused elementwise pass); the per-q-block trailing
    # dim keeps every in-kernel read full-lane (Mosaic 128-alignment)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1).reshape(B * H, nb, 1, block)
    rows = jnp.concatenate(
        [lse.reshape(B * H, nb, 1, block), delta, jnp.zeros((B * H, nb, 6, block), jnp.float32)],
        axis=2,
    )

    # ---- dq: q-major, same (bh, row, edge) walk as the forward --------
    q_spec = pl.BlockSpec((1, 1, block, hd), lambda b, r, e, idx, valid: (b, r, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block, hd),
        lambda b, r, e, idx, valid: (b, idx[b % H_, r * deg + e], 0, 0),
    )
    lse_spec = pl.BlockSpec((1, 1, 8, block), lambda b, r, e, idx, valid: (b, r, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H, nb, deg),
        in_specs=[q_spec, kv_spec, kv_spec, lse_spec, q_spec],
        out_specs=[q_spec],
        scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32)],
    )
    dq_kern = functools.partial(
        _splash_dq_kernel, sm_scale=sm_scale, causal=causal, block=block, deg=deg, heads=lh
    )
    (dq,) = pl.pallas_call(
        dq_kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B * H, nb, block, hd), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(idx2, valid2, qr, kr, vr, rows, gr)

    # ---- dk/dv: kv-major over the column-sorted edge list -------------
    # (accumulation per kv block is conflict-free inside the kernel; the
    # r5.0 strip-output + XLA segment-sum stage is gone)
    qidx_np, kcol_np, flags_np = _layout_dkv_edges(layout)
    qidx = jnp.asarray(qidx_np)
    kcol = jnp.asarray(kcol_np)
    flags = jnp.asarray(flags_np)
    E = qidx_np.shape[1]
    # head count of the dkv arrays themselves — 1 for head-uniform
    # layouts (must match the kernel's `heads` or h = bh % heads reads
    # SMEM out of bounds on hardware; interpret mode clamps and hides it)
    assert qidx_np.shape[0] == lh, (qidx_np.shape, lh)
    eq_spec = pl.BlockSpec(
        (1, 1, block, hd), lambda b, e, qidx, kcol, flags: (b, qidx[b % H_, e], 0, 0)
    )
    ekv_spec = pl.BlockSpec(
        (1, 1, block, hd), lambda b, e, qidx, kcol, flags: (b, kcol[b % H_, e], 0, 0)
    )
    else_spec = pl.BlockSpec(
        (1, 1, 8, block), lambda b, e, qidx, kcol, flags: (b, qidx[b % H_, e], 0, 0)
    )
    dkv_grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * H, E),
        in_specs=[eq_spec, ekv_spec, ekv_spec, else_spec, eq_spec],
        out_specs=[ekv_spec, ekv_spec],
        scratch_shapes=[
            pltpu.VMEM((block, hd), jnp.float32),
            pltpu.VMEM((block, hd), jnp.float32),
        ],
    )
    dkv_kern = functools.partial(
        _splash_dkv_kernel, sm_scale=sm_scale, causal=causal, block=block, heads=lh
    )
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid_spec=dkv_grid,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, nb, block, hd), k.dtype),
            jax.ShapeDtypeStruct((B * H, nb, block, hd), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qidx, kcol, flags, qr, kr, vr, rows, gr)

    return (
        dq.reshape(B, H, T, hd),
        dk.reshape(B, H, T, hd),
        dv.reshape(B, H, T, hd),
    )



class _LayoutKey:
    """Hashable static-layout wrapper for custom_vjp nondiff args: the
    key CARRIES the layout, so the backward can never lose it (a shared
    registry would need eviction and could KeyError a held-over vjp)."""

    __slots__ = ("layout", "_fp")

    def __init__(self, layout: np.ndarray):
        import hashlib

        self.layout = layout
        self._fp = (layout.shape, hashlib.sha1(np.ascontiguousarray(layout)).hexdigest())

    def __hash__(self):
        return hash(self._fp)

    def __eq__(self, other):
        return isinstance(other, _LayoutKey) and self._fp == other._fp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _splash_attention(q, k, v, layout_key, block, causal, sm_scale, interpret):
    return _splash_fwd(q, k, v, layout_key.layout, block, causal, sm_scale, interpret)


def _splash_fwd_rule(q, k, v, layout_key, block, causal, sm_scale, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _splash_fwd(
        q, k, v, layout_key.layout, block, causal, sm_scale, interpret, want_lse=True
    )
    # same residual names as the flash kernels: a remat policy saving
    # attn_o/attn_lse keeps these, so the backward never re-runs the
    # forward kernel under selective checkpointing either
    out = checkpoint_name(out, "attn_o")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _splash_bwd_rule(layout_key, block, causal, sm_scale, interpret, res, g):
    # dedicated Pallas backward (VERDICT r2 #7; r4: single pass from the
    # forward's saved lse; r5: split into a q-major dq kernel and a
    # kv-major dkv kernel over the column-sorted edge list — dk/dv
    # accumulate conflict-free in VMEM, so the strip outputs and the
    # XLA segment-sum scatter stage are gone)
    q, k, v, out, lse = res
    return _splash_bwd(q, k, v, out, lse, g, layout_key.layout, block, causal, sm_scale, interpret)


_splash_attention.defvjp(_splash_fwd_rule, _splash_bwd_rule)


def splash_attention(q, k, v, layout: np.ndarray, block: int, causal: bool = False, sm_scale: Optional[float] = None, interpret: Optional[bool] = None):
    """Streamed Pallas block-sparse attention (see section comment).

    The sparse rows run the custom-vjp Pallas kernels (fwd + dedicated
    bwd); the handful of horizontal-global (fully dense) rows are
    overwritten by the plain-XLA dense bucket OUTSIDE the custom vjp, so
    autodiff differentiates them natively and the kernels never pad
    every row's degree up to nb."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = pallas_interpret_default()
    out = _splash_attention(
        q, k, v, _LayoutKey(layout), int(block), bool(causal), float(sm_scale), bool(interpret)
    )
    _idx, _valid, drows_np, dvalid_np = _layout_gather_indices(layout, exempt_uniform_full=True)
    if drows_np.shape[1] > 0:
        out = _apply_dense_rows(out, q, k, v, drows_np, dvalid_np, block, causal, sm_scale, None)
    return out


# ---------------------------------------------------------------------------
# Module-level wrappers (reference sparse_self_attention.py /
# bert_sparse_self_attention.py / sparse_attention_utils.py)
# ---------------------------------------------------------------------------


class SparseSelfAttention:
    """Reference ``SparseSelfAttention`` (:14): holds a sparsity config,
    caches per-seq-len layouts, applies block-sparse attention to
    already-projected q/k/v in (B, H, T, hd) layout."""

    def __init__(self, sparsity_config: Optional[SparsityConfig] = None, key_padding_mask_mode: str = "add", attn_mask_mode: str = "mul"):
        self.sparsity_config = sparsity_config or FixedSparsityConfig(num_heads=4)
        self.key_padding_mask_mode = key_padding_mask_mode
        self.attn_mask_mode = attn_mask_mode
        self._layouts = {}

    def get_layout(self, seq_len: int) -> np.ndarray:
        if seq_len not in self._layouts:
            self._layouts[seq_len] = self.sparsity_config.make_layout(seq_len)
        return self._layouts[seq_len]

    def __call__(self, query, key, value, key_padding_mask=None, causal: Optional[bool] = None):
        T = query.shape[2]
        layout = self.get_layout(T)
        if causal is None:
            causal = getattr(self.sparsity_config, "attention", "bidirectional") == "unidirectional"
        return block_sparse_attention(
            query, key, value, layout, self.sparsity_config.block,
            causal=causal, key_padding_mask=key_padding_mask,
        )


class SparseAttentionUtils:
    """Helpers mirroring the reference's HF-patching utilities
    (``sparse_attention_utils.py``) at the functional level."""

    @staticmethod
    def extend_position_embedding(pos_emb: np.ndarray, new_len: int) -> np.ndarray:
        """Tile an existing position table to a longer sequence
        (reference extends HF models' embeddings the same way)."""
        cur = pos_emb.shape[0]
        reps = -(-new_len // cur)
        return np.concatenate([pos_emb] * reps, axis=0)[:new_len]

    @staticmethod
    def pad_to_block_size(block: int, tokens: np.ndarray, pad_token_id: int = 0):
        """Right-pad (B, T) token ids to a multiple of ``block``; returns
        (padded_tokens, attention_mask, pad_len)."""
        B, T = tokens.shape
        pad = (-T) % block
        if pad == 0:
            return tokens, np.ones((B, T), np.int32), 0
        padded = np.concatenate([tokens, np.full((B, pad), pad_token_id, tokens.dtype)], axis=1)
        mask = np.concatenate([np.ones((B, T), np.int32), np.zeros((B, pad), np.int32)], axis=1)
        return padded, mask, pad

    @staticmethod
    def unpad_sequence_output(pad_len: int, out):
        return out[:, : out.shape[1] - pad_len] if pad_len else out


@register_op("sparse_attn", "pallas", "fused splash block-sparse attention (+ XLA gather oracle) with the SparsityConfig layout family (Triton blocksparse analog)")
def _load_sparse_attn():
    return {
        "block_sparse_attention": block_sparse_attention,
        "SparseSelfAttention": SparseSelfAttention,
        "configs": {
            "dense": DenseSparsityConfig,
            "fixed": FixedSparsityConfig,
            "variable": VariableSparsityConfig,
            "bigbird": BigBirdSparsityConfig,
            "bslongformer": BSLongformerSparsityConfig,
        },
    }
