"""Flash attention — the TPU-native replacement for the reference's fused
attention CUDA kernels (``csrc/transformer/softmax_kernels.cu`` +
strided-batch GEMMs in ``csrc/transformer/ds_transformer_cuda.cpp``; and
the inference decode path in ``csrc/transformer/inference/csrc/softmax.cu``).

Design:
* **Forward**: Pallas TPU kernel, online-softmax over KV blocks held in
  VMEM, grid over (batch×heads, q-blocks).  Dots run in the input dtype
  (bf16 on the training path — the MXU's native rate; fp32 operands
  decompose into multiple MXU passes and measured ~4× slower) with fp32
  accumulation and fp32 softmax state.
* **Backward**: Pallas FA-2-style kernels (dq, then dk/dv) recomputing P
  from (Q, K, lse) — O(seq) memory; same bf16-dot/fp32-accumulate
  treatment.  ``_blockwise_xla`` remains as the interpretable
  long-sequence fallback used when shapes don't fit the kernel grid.
* On non-TPU backends the same kernel runs under ``interpret=True`` so
  unit tests execute on the CPU mesh.

Layout convention: ``(batch, heads, seq, head_dim)``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from deepspeed_tpu.ops.kernels.sharded import dim_spec, free_mesh_axes, shard_call, spec_axes
from deepspeed_tpu.ops.registry import register_op
from deepspeed_tpu.utils.device import pallas_interpret_default
from deepspeed_tpu.utils.logging import logger

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Reference implementation (tests + tiny shapes)
# ---------------------------------------------------------------------------

def mha_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    bias: Optional[jnp.ndarray] = None,
    dropout_mask: Optional[jnp.ndarray] = None,
    keep_prob: float = 1.0,
) -> jnp.ndarray:
    """Plain XLA attention; numerics ground truth for the Pallas kernel.
    ``dropout_mask``: (B, H, Tq, Tk) {0,1}, applied to the softmax output
    (softmax-then-dropout, matching the fused kernels)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool), k=klen - qlen)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_mask is not None:
        p = p * (dropout_mask.astype(jnp.float32) / keep_prob)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# Crossover measured on v5e (fwd+bwd, d=64, tokens held constant).
# r3 (two-pass bwd): T=128 dense 2.31ms vs kernel 2.82ms; T=256 dense
# 2.97ms vs kernel 2.64ms.  r4 re-measured with the fused single-pass
# backward: T=128 dense 2.13ms vs kernel 3.69ms (1.73x), T=256 ~parity.
# The bound is structural, not a missing optimization: at T=128 the
# grid runs one program per (batch·head) — B=64·H=16 ⇒ 1024 programs of
# a single 128-row block, so the per-program fixed cost (DMA prologue,
# pipeline fill) dominates a compute body that the dense path executes
# as a handful of large fused MXU launches with identical exp counts;
# shrinking blocks can't help (128 is the minimum useful q-block) and
# the O(T²) memory the kernel exists to avoid is only ~64MB here.
# Below ~128x128 scores the materializing bf16 path is simply the
# right program shape (BERT seq128 — the reference's own record shape).
SMALL_SEQ_DENSE_SCORES = 128 * 128


def mha_dense(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    bias: Optional[jnp.ndarray] = None,
    dropout_mask: Optional[jnp.ndarray] = None,
    keep_prob: float = 1.0,
) -> jnp.ndarray:
    """Materializing attention with input-dtype (MXU-rate) dots and fp32
    softmax — the fast path at short sequence, where the Pallas grid's
    per-program overhead exceeds the O(T^2) memory cost it avoids.  Same
    numerics class as the kernel (bf16 dots, fp32 accumulate/softmax);
    fp32 inputs stay fp32 end-to-end."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        qp = jnp.arange(qlen)[:, None] + (klen - qlen)
        s = jnp.where(qp >= jnp.arange(klen)[None, :], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_mask is not None:
        p = p * (dropout_mask.astype(jnp.float32) / keep_prob)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v, preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------------------
# In-kernel counter-based dropout PRNG
#
# Threefry-2x32 (20 rounds, the Random123 / jax.random schedule) written
# in pure uint32 add/xor/rotate — it lowers identically through Mosaic
# and the Pallas interpreter (pltpu.prng_random_bits is a TPU-only
# primitive and stubs to zeros in interpret mode), and the same pure
# function run host-side reproduces the exact keep-mask for the oracle
# and for the non-kernel fallback paths.  The counter is the score
# element's absolute (row·Tk + col, batch·head) position, so any block
# decomposition (fwd q-blocks, dkv kv-blocks) regenerates identical
# bits — the FA-2 backward never needs a stored mask.  Cost: ~80 VPU
# ops per score on the dropout path only — the same threefry work
# jax.random.bernoulli would do in XLA, minus the O(Tq·Tk) HBM
# round-trip the materialized mask paid.
# ---------------------------------------------------------------------------


def _rotl32(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _threefry2x32_bits(k0, k1, x0, x1):
    """First output word of 20-round Threefry-2x32 on counter (x0, x1)
    under key (k0, k1).  All inputs uint32 arrays/scalars."""
    ks0, ks1 = k0, k1
    ks2 = jnp.uint32(0x1BD11BDA) ^ k0 ^ k1
    x0 = x0 + ks0
    x1 = x1 + ks1
    rot_a = (13, 15, 26, 6)
    rot_b = (17, 29, 16, 24)

    def rounds4(x0, x1, rots):
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl32(x1, r)
            x1 = x1 ^ x0
        return x0, x1

    for i, (rots, ka, kb) in enumerate((
        (rot_a, ks1, ks2), (rot_b, ks2, ks0), (rot_a, ks0, ks1),
        (rot_b, ks1, ks2), (rot_a, ks2, ks0),
    )):
        x0, x1 = rounds4(x0, x1, rots)
        x0 = x0 + ka
        x1 = x1 + kb + jnp.uint32(i + 1)
    return x0


def _drop_threshold(keep_prob: float) -> int:
    """keep iff bits < threshold (uint32 compare) ⇒ P(keep) = keep_prob."""
    return min(int(keep_prob * 4294967296.0), 4294967295)


def _check_dropout_counter_bound(sq: int, sk: int) -> None:
    """The position-keyed Threefry counter packs ``row*sk + col`` into
    one uint32 word; beyond 2**32 score positions the stream would
    repeat.  64k × 64k scores is far outside any supported score-matrix
    size (long-context runs route through sparse/ring attention), so
    refuse loudly rather than degrade silently."""
    if sq * sk >= 2**32:
        raise ValueError(
            f"attention dropout PRNG counter would wrap: sq*sk = {sq}*{sk} "
            ">= 2**32; use sparse or ring attention for scores this large"
        )


def _drop_keep_tile(k0, k1, bh, row0, col0, bq, bk, sk, keep_prob):
    """(bq, bk) bool keep-tile for score rows [row0, row0+bq) × cols
    [col0, col0+bk) of batch·head ``bh`` — pure function of the absolute
    element position, identical across fwd/dq/dkv block decompositions.

    Counter bound: the x0 word is ``row*sk + col`` in uint32, so score
    grids with sq*sk >= 2**32 (64k × 64k) would silently repeat
    keep-bits across distant positions — entry points assert the bound
    (``_check_dropout_counter_bound``) before handing a seed down."""
    rows = jnp.uint32(row0) + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0)
    cols = jnp.uint32(col0) + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1)
    x0 = rows * jnp.uint32(sk) + cols
    x1 = jnp.full((bq, bk), 1, jnp.uint32) * jnp.uint32(bh)
    bits = _threefry2x32_bits(jnp.uint32(k0), jnp.uint32(k1), x0, x1)
    return bits < jnp.uint32(_drop_threshold(keep_prob))


def dropout_keep_mask_host(seed_pair, b, h, sq, sk, keep_prob):
    """The full (b·h, sq, sk) uint8 keep-mask the kernels generate —
    host-graph-side twin of ``_drop_keep_tile`` for the oracle and the
    materializing fallback paths (dense short-seq / reference)."""
    _check_dropout_counter_bound(sq, sk)
    k0 = seed_pair[0].astype(jnp.uint32)
    k1 = seed_pair[1].astype(jnp.uint32)
    rows = jax.lax.broadcasted_iota(jnp.uint32, (sq, sk), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (sq, sk), 1)
    x0 = rows * jnp.uint32(sk) + cols
    bhs = jnp.arange(b * h, dtype=jnp.uint32)
    bits = jax.vmap(lambda bh: _threefry2x32_bits(k0, k1, x0, jnp.full((sq, sk), 1, jnp.uint32) * bh))(bhs)
    return (bits < jnp.uint32(_drop_threshold(keep_prob))).astype(jnp.uint8)


def _seed_pair(rng) -> jnp.ndarray:
    """(2,) uint32 key words from either a new-style typed PRNG key or a
    raw uint32[2] key."""
    try:
        if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
            rng = jax.random.key_data(rng)
    except (TypeError, AttributeError):
        pass
    return jnp.asarray(rng).reshape(-1)[:2].astype(jnp.uint32)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, *rest, sm_scale: float, causal: bool, block_k: int,
    kbias: bool, fbias: bool, keep_prob: float, kdrop: bool = False,
):
    # optional trailing inputs: [bias], [drop-mask | prng-seed]; outputs:
    # o, [lse].  ``kdrop``: the dropout input is a (2,) uint32 SMEM seed
    # and the keep-mask is generated in-kernel (no O(Tq·Tk) HBM buffer).
    refs = list(rest)
    bias_ref = refs.pop(0) if (kbias or fbias) else None
    mask_ref = refs.pop(0) if keep_prob < 1.0 else None
    o_ref = refs.pop(0)
    maybe_lse_ref = refs

    block_q, d = q_ref.shape[1], q_ref.shape[2]
    seq_k = k_ref.shape[1]
    seq_q_total = pl.num_programs(1) * block_q
    q_idx = pl.program_id(1)
    bh_idx = pl.program_id(0)
    # End-aligned causal offset (queries are the LAST seq_q positions of
    # the kv sequence — decode convention, matches mha_reference's
    # tril(k=klen-qlen)).
    causal_offset = seq_k - seq_q_total

    # Keep q/k/v in the input dtype for the dots: the MXU multiplies
    # bf16×bf16 natively at full rate (fp32 operands decompose into
    # multiple passes — measured ~4× slower end-to-end); accumulation is
    # fp32 via preferred_element_type, and the softmax math stays fp32.
    q = q_ref[0]  # (block_q, d)

    num_kv = seq_k // block_k
    if causal:
        # Last KV block whose start can be <= this q block's end position.
        q_end = causal_offset + (q_idx + 1) * block_q
        hi = jax.lax.div(q_end + block_k - 1, block_k)
        hi = jnp.clip(hi, 0, num_kv)
    else:
        hi = num_kv

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale  # (block_q, block_k) fp32
        if kbias:
            s = s + bias_ref[0, 0, pl.dslice(i * block_k, block_k)].astype(jnp.float32)[None, :]
        elif fbias:
            s = s + bias_ref[0, :, pl.dslice(i * block_k, block_k)].astype(jnp.float32)
        if causal:
            q_pos = causal_offset + q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (block_q, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        # softmax statistics use the FULL p; dropout zeroes entries only
        # on the value path (reference softmax-then-dropout semantics,
        # csrc/transformer/dropout_kernels.cu)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if keep_prob < 1.0:
            if kdrop:
                keep = _drop_keep_tile(
                    mask_ref[0], mask_ref[1], bh_idx,
                    q_idx * block_q, i * block_k, block_q, block_k, seq_k, keep_prob,
                )
            else:
                keep = mask_ref[0, :, pl.dslice(i * block_k, block_k)]
            p = p * (keep.astype(jnp.float32) / keep_prob)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    init = (
        jnp.zeros((block_q, d), jnp.float32),
        jnp.full((block_q, 1), -jnp.inf, jnp.float32),
        jnp.zeros((block_q, 1), jnp.float32),
    )
    acc, m, l = jax.lax.fori_loop(0, hi, body, init)
    lse = jnp.where(l[:, 0] == 0.0, jnp.inf, m[:, 0] + jnp.log(jnp.maximum(l[:, 0], 1e-37)))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    if maybe_lse_ref:
        # per-row logsumexp of the SCALED scores (bwd input); stored with
        # an 8-sublane broadcast dim for TPU block-layout constraints.
        # Omitted on the inference-only path (no grad → no buffer).
        maybe_lse_ref[0][0] = jnp.broadcast_to(lse[None, :], (8, lse.shape[0]))


def _bias_mode(bias, b, h, sq, sk):
    """Classify/normalize an additive bias: (B,1,1,Tk) key-broadcast →
    ("kbias", (B, Tk)); anything broadcastable to (B,H,Tq,Tk) →
    ("fbias", (B*H, Tq, Tk))."""
    if bias is None:
        return None, None
    if bias.ndim != 4:
        raise ValueError(f"bias must be 4-D broadcastable to (B,H,Tq,Tk), got {bias.shape}")
    if bias.shape[1] == 1 and bias.shape[2] == 1 and bias.shape[3] == sk:
        # (B, 1, Tk): the middle singleton keeps the block's trailing two
        # dims equal to the array dims, which Mosaic requires when the
        # row count (B) isn't a multiple of 8
        return "kbias", bias.reshape(bias.shape[0], 1, sk)
    full = jnp.broadcast_to(bias, (b, h, sq, sk)).reshape(b * h, sq, sk)
    return "fbias", full


def _fwd_extra_specs(mode, bias2, mask, b, h, sq, sk, block_q, drop_seed=None):
    """in_specs + arrays for the optional bias/mask/seed inputs of the
    fwd/dq kernels (block over the q dim; the kv dim is sliced
    in-kernel).  ``drop_seed``: (2,) uint32 for in-kernel dropout —
    rides SMEM, mutually exclusive with ``mask``."""
    if drop_seed is not None:
        _check_dropout_counter_bound(sq, sk)
    specs, args = [], []
    if mode == "kbias":
        specs.append(pl.BlockSpec((1, 1, sk), lambda bh_, qi, h=h: (bh_ // h, 0, 0)))
        args.append(bias2)
    elif mode == "fbias":
        specs.append(pl.BlockSpec((1, block_q, sk), lambda bh_, qi: (bh_, qi, 0)))
        args.append(bias2)
    if mask is not None:
        specs.append(pl.BlockSpec((1, block_q, sk), lambda bh_, qi: (bh_, qi, 0)))
        args.append(mask)
    elif drop_seed is not None:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(drop_seed)
    return specs, args


def _flash_fwd_pallas(
    q, k, v, causal: bool, sm_scale: float, block_q: int, block_k: int, interpret: bool,
    want_lse: bool = True, bias=None, mask=None, keep_prob: float = 1.0, drop_seed=None,
):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    mode, bias2 = _bias_mode(bias, b, h, sq, sk)

    grid = (bh, sq // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0)),
        pl.BlockSpec((1, sk, d), lambda bh_, qi: (bh_, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda bh_, qi: (bh_, 0, 0)),
    ]
    extra_specs, extra_args = _fwd_extra_specs(mode, bias2, mask, b, h, sq, sk, block_q, drop_seed)
    in_specs += extra_specs
    o_spec = pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0))
    o_shape = jax.ShapeDtypeStruct((bh, sq, d), q.dtype)
    kern = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal, block_k=block_k,
        kbias=(mode == "kbias"), fbias=(mode == "fbias"), keep_prob=keep_prob,
        kdrop=(drop_seed is not None),
    )
    if not want_lse:
        # inference/eval path: skip the logsumexp output entirely
        out = pl.pallas_call(
            kern, grid=grid, in_specs=in_specs, out_specs=o_spec, out_shape=o_shape,
            interpret=interpret, name="flash_attention_fwd",
        )(qr, kr, vr, *extra_args)
        return out.reshape(b, h, sq, d), None
    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[o_spec, pl.BlockSpec((1, 8, block_q), lambda bh_, qi: (bh_, 0, qi))],
        out_shape=[o_shape, jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32)],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qr, kr, vr, *extra_args)
    return out.reshape(b, h, sq, d), lse[:, 0, :].reshape(b, h, sq)


# ---------------------------------------------------------------------------
# Blockwise XLA path (backward + long-sequence fallback): flash-style
# online softmax as a lax.scan over KV blocks, rematerialized.
# ---------------------------------------------------------------------------

def _blockwise_xla(q, k, v, causal: bool, sm_scale: float, block_k: int):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_k = min(block_k, sk)
    # Ragged sk: pad K/V up to a block multiple and mask the padded keys
    # (the l==0 guard below already handles fully-masked rows).
    pad = (-sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    num_kv = (sk + pad) // block_k
    qf = q.astype(jnp.float32) * sm_scale
    kf = k.astype(jnp.float32).reshape(b, h, num_kv, block_k, d)
    vf = v.astype(jnp.float32).reshape(b, h, num_kv, block_k, d)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def block(carry, inputs):
        acc, m_prev, l_prev = carry
        kb, vb, kv_i = inputs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb)
        # end-aligned causal positions (match mha_reference's
        # tril(k=klen-qlen)); generated in-body — a precomputed (sq, 1)
        # index constant was observed to land in SMEM and overflow it at
        # 16k sequences on TPU
        q_pos = (sk - sq) + jax.lax.broadcasted_iota(jnp.int32, (sq, 1), 0)
        k_pos = kv_i * block_k + jnp.arange(block_k)[None, :]
        if causal:
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        if pad:
            s = jnp.where(k_pos < sk, s, DEFAULT_MASK_VALUE)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (acc, m_new, l_new), None

    init = (
        jnp.zeros((b, h, sq, d), jnp.float32),
        jnp.full((b, h, sq, 1), -jnp.inf, jnp.float32),
        jnp.zeros((b, h, sq, 1), jnp.float32),
    )
    kb = jnp.moveaxis(kf, 2, 0)  # (num_kv, b, h, block_k, d)
    vb = jnp.moveaxis(vf, 2, 0)
    (acc, m, l), _ = jax.lax.scan(block, init, (kb, vb, jnp.arange(num_kv)))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style)
#
# With S = QKᵀ·sc, P = exp(S − lse), Δ = rowsum(dO ∘ O):
#   dV = Pᵀ dO
#   dS = P ∘ (dO Vᵀ − Δ)
#   dQ = dS K · sc          dK = dSᵀ Q · sc
# Both kernels recompute P from (Q, K, lse) — O(seq) memory like the
# forward; the fwd saves only O and the per-row logsumexp.
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    sm_scale, causal, block_k, kbias, fbias, keep_prob, kdrop=False,
):
    refs = list(rest)
    bias_ref = refs.pop(0) if (kbias or fbias) else None
    mask_ref = refs.pop(0) if keep_prob < 1.0 else None
    dq_ref = refs.pop(0)

    block_q, d = q_ref.shape[1], q_ref.shape[2]
    seq_k = k_ref.shape[1]
    seq_q_total = pl.num_programs(1) * block_q
    q_idx = pl.program_id(1)
    bh_idx = pl.program_id(0)
    causal_offset = seq_k - seq_q_total

    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0, :][:, None]
    delta = delta_ref[0, 0, :][:, None]

    num_kv = seq_k // block_k
    if causal:
        q_end = causal_offset + (q_idx + 1) * block_q
        hi = jnp.clip(jax.lax.div(q_end + block_k - 1, block_k), 0, num_kv)
    else:
        hi = num_kv

    def body(i, dq):
        k = k_ref[0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, pl.dslice(i * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if kbias:
            s = s + bias_ref[0, 0, pl.dslice(i * block_k, block_k)].astype(jnp.float32)[None, :]
        elif fbias:
            s = s + bias_ref[0, :, pl.dslice(i * block_k, block_k)].astype(jnp.float32)
        if causal:
            q_pos = causal_offset + q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if keep_prob < 1.0:
            if kdrop:
                keep = _drop_keep_tile(
                    mask_ref[0], mask_ref[1], bh_idx,
                    q_idx * block_q, i * block_k, block_q, block_k, seq_k, keep_prob,
                )
            else:
                keep = mask_ref[0, :, pl.dslice(i * block_k, block_k)]
            dp = dp * (keep.astype(jnp.float32) / keep_prob)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    sm_scale, causal, block_q, kbias, fbias, keep_prob, kdrop=False,
):
    refs = list(rest)
    bias_ref = refs.pop(0) if (kbias or fbias) else None
    mask_ref = refs.pop(0) if keep_prob < 1.0 else None
    dk_ref, dv_ref = refs

    block_k, d = k_ref.shape[1], k_ref.shape[2]
    seq_q = q_ref.shape[1]
    seq_k_total = pl.num_programs(1) * block_k
    kv_idx = pl.program_id(1)
    bh_idx = pl.program_id(0)
    causal_offset = seq_k_total - seq_q

    k = k_ref[0]
    v = v_ref[0]

    num_q = seq_q // block_q
    if causal:
        # first q block whose end position reaches this kv block's start
        k_start = kv_idx * block_k
        lo = jnp.clip(jax.lax.div(k_start - causal_offset, block_q), 0, num_q)
    else:
        lo = 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * block_q, block_q), :]
        do = do_ref[0, pl.dslice(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.dslice(i * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.dslice(i * block_q, block_q)][:, None]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if kbias:
            s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
        elif fbias:
            s = s + bias_ref[0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        if causal:
            q_pos = causal_offset + i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)
        if keep_prob < 1.0:
            if kdrop:
                keep = _drop_keep_tile(
                    mask_ref[0], mask_ref[1], bh_idx,
                    i * block_q, kv_idx * block_k, block_q, block_k, seq_k_total, keep_prob,
                )
            else:
                keep = mask_ref[0, pl.dslice(i * block_q, block_q), :]
            scaled_keep = keep.astype(jnp.float32) / keep_prob
            d_mat = p * scaled_keep  # post-dropout probabilities
        else:
            d_mat = p
        dv = dv + jnp.dot(d_mat.astype(do.dtype).T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if keep_prob < 1.0:
            dp = dp * scaled_keep
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk, dv

    init = (jnp.zeros((block_k, d), jnp.float32), jnp.zeros((block_k, d), jnp.float32))
    dk, dv = jax.lax.fori_loop(lo, num_q, body, init)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    sm_scale, causal, block_q, kbias, fbias, keep_prob, kdrop=False,
    q_row0=0, sq_full=None, sk_full=None,
):
    """Single-pass backward: one kernel computes dq, dk, dv together.

    Grid is (bh, kv-blocks) with the kv axis SEQUENTIAL ("arbitrary"
    semantics): every program loops the q blocks for its kv block,
    computing P = exp(S − lse) ONCE per score and feeding all three
    cotangents — the two-pass design (dq pass + dkv pass) pays that
    exp twice, and at d=64 the kernel is VPU-softmax-bound
    (round 3, before the ledger), so the second exp is pure waste.
    dq accumulates across kv blocks by revisiting its (full-seq) output
    block, which stays resident in VMEM between sequential grid steps —
    this bounds one CALL to seqs where sq·d fp32 fits VMEM (~8k at
    d=64); longer sequences run as q-CHUNKED calls of this same kernel
    (``_flash_bwd_fused_chunked``) with ``q_row0``/``sq_full``/
    ``sk_full`` carrying the chunk's global position so causal masking
    and the position-keyed dropout counter are chunking-invariant."""
    refs = list(rest)
    bias_ref = refs.pop(0) if (kbias or fbias) else None
    mask_ref = refs.pop(0) if keep_prob < 1.0 else None
    dq_ref, dk_ref, dv_ref = refs

    block_k, d = k_ref.shape[1], k_ref.shape[2]
    seq_q = q_ref.shape[1]
    seq_k_total = pl.num_programs(1) * block_k
    skf = seq_k_total if sk_full is None else sk_full
    sqf = seq_q if sq_full is None else sq_full
    kv_idx = pl.program_id(1)
    bh_idx = pl.program_id(0)
    # global q position of local row r is q_row0 + r; causal compares
    # (skf - sqf) + global_q >= global_k
    causal_offset = skf - sqf + q_row0

    @pl.when(kv_idx == 0)
    def _zero_dq():
        dq_ref[0] = jnp.zeros((seq_q, d), dq_ref.dtype)

    k = k_ref[0]
    v = v_ref[0]

    num_q = seq_q // block_q
    if causal:
        k_start = kv_idx * block_k
        lo = jnp.clip(jax.lax.div(k_start - causal_offset, block_q), 0, num_q)
    else:
        lo = 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * block_q, block_q), :]
        do = do_ref[0, pl.dslice(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.dslice(i * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.dslice(i * block_q, block_q)][:, None]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if kbias:
            s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
        elif fbias:
            s = s + bias_ref[0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        if causal:
            q_pos = causal_offset + i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if keep_prob < 1.0:
            if kdrop:
                keep = _drop_keep_tile(
                    mask_ref[0], mask_ref[1], bh_idx,
                    q_row0 + i * block_q, kv_idx * block_k, block_q, block_k, skf, keep_prob,
                )
            else:
                keep = mask_ref[0, pl.dslice(i * block_q, block_q), :]
            scaled_keep = keep.astype(jnp.float32) / keep_prob
            d_mat = p * scaled_keep
            dp = dp * scaled_keep
        else:
            d_mat = p
        dv = dv + jnp.dot(d_mat.astype(do.dtype).T, do, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        # dq accumulation: read-modify-write the resident dq block
        cur = dq_ref[0, pl.dslice(i * block_q, block_q), :]
        dq_ref[0, pl.dslice(i * block_q, block_q), :] = (
            cur + jnp.dot(ds, k, preferred_element_type=jnp.float32).astype(dq_ref.dtype)
        )
        return dk, dv

    init = (jnp.zeros((block_k, d), jnp.float32), jnp.zeros((block_k, d), jnp.float32))
    dk, dv = jax.lax.fori_loop(lo, num_q, body, init)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_fused_pallas(
    q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret,
    bias=None, mask=None, keep_prob: float = 1.0, drop_seed=None,
    q_row0: int = 0, sq_full=None, sk_full=None,
):
    """Single-kernel backward (see ``_flash_bwd_fused_kernel``).  dq is
    accumulated in fp32 and cast at the end."""
    b, h, sq, sk, bh, block_q, block_k, qr, kr, vr, dor, lser, delta, mode, bias2, flags = (
        _bwd_prologue(q, k, v, out, lse, g, bias, block_q, block_k, keep_prob, drop_seed)
    )
    d = q.shape[3]
    extra_specs, extra_args = _kv_grid_extra_specs(mode, bias2, mask, h, sq, block_k, drop_seed)

    dq32, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_fused_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
            q_row0=q_row0, sq_full=sq_full, sk_full=sk_full, **flags,
        ),
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, sq, d), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, 8, sq), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, 8, sq), lambda bh_, ki: (bh_, 0, 0)),
        ] + extra_specs,
        out_specs=[
            # dq: full-seq block revisited every kv step (accumulator)
            pl.BlockSpec((1, sq, d), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd",
    )(qr, kr, vr, dor, lser, delta, *extra_args)

    return (
        dq32.astype(q.dtype).reshape(q.shape),
        dk.reshape(k.shape),
        dv.reshape(v.shape),
    )


# VMEM bound for the fused backward's resident per-program state:
# q + do + dq(fp32) + k/v blocks, double-buffered — beyond this ONE
# call's worth; longer sequences run q-chunked calls of the same kernel
# (16k+ used to fall back to the two-pass kernels).
_FUSED_BWD_MAX_SQ_BYTES = 1 << 21  # sq * d * 4 (fp32 dq) per program


def _flash_bwd_fused_chunked(
    q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret,
    bias=None, mask=None, keep_prob: float = 1.0, drop_seed=None,
):
    """Fused single-pass backward for sequences whose fp32 dq exceeds a
    program's VMEM share: split the q axis into chunks that fit, run the
    fused kernel once per chunk (``q_row0``/``sq_full``/``sk_full`` keep
    causal masking and the dropout counter position-exact), sum the
    partial dk/dv in fp32.  Causal chunks slice their kv prefix — a
    chunk never visits kv blocks entirely above its diagonal — so total
    score work matches the monolithic kernel.  Explicit bias/mask
    tensors are not chunked (long-context runs are causal + in-kernel
    dropout); the dispatch sends those to the two-pass kernels."""
    assert bias is None and mask is None, "chunked fused bwd: bias/mask unsupported"
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    rows_max = _FUSED_BWD_MAX_SQ_BYTES // (d * 4)
    cs = max(bq, rows_max // bq * bq)
    dq_parts = []
    dk32 = jnp.zeros((b, h, sk, d), jnp.float32)
    dv32 = jnp.zeros((b, h, sk, d), jnp.float32)
    for c0 in range(0, sq, cs):
        ce = min(c0 + cs, sq)
        qs = slice(c0, ce)
        kv_hi = sk
        if causal:
            # highest k position this chunk can see: (sk - sq) + ce - 1
            kv_hi = min(sk, max(block_k, -((sk - sq + ce) // -block_k) * block_k))
        dq_c, dk_c, dv_c = _flash_bwd_fused_pallas(
            q[:, :, qs], k[:, :, :kv_hi], v[:, :, :kv_hi], out[:, :, qs],
            lse[:, :, qs], g[:, :, qs], causal, sm_scale, block_q, block_k,
            interpret, keep_prob=keep_prob, drop_seed=drop_seed,
            q_row0=c0, sq_full=sq, sk_full=sk,
        )
        dq_parts.append(dq_c)
        pad = sk - kv_hi
        dk_add = dk_c.astype(jnp.float32)
        dv_add = dv_c.astype(jnp.float32)
        if pad:
            dk32 = dk32.at[:, :, :kv_hi].add(dk_add)
            dv32 = dv32.at[:, :, :kv_hi].add(dv_add)
        else:
            dk32 = dk32 + dk_add
            dv32 = dv32 + dv_add
    return (
        jnp.concatenate(dq_parts, axis=2),
        dk32.astype(k.dtype),
        dv32.astype(v.dtype),
    )


def _bwd_prologue(q, k, v, out, lse, g, bias, block_q, block_k, keep_prob, drop_seed):
    """Shared backward-pass setup: (bh, seq, d) reshapes, 8-sublane
    lse/delta broadcasts (TPU block constraint: last two dims must be
    8/128-aligned or full), bias classification, kernel flags."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    qr, kr, vr = (t.reshape(bh, t.shape[2], d) for t in (q, k, v))
    dor = g.reshape(bh, sq, d)
    lser = jnp.broadcast_to(lse.reshape(bh, 1, sq), (bh, 8, sq))
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta.reshape(bh, 1, sq), (bh, 8, sq))
    mode, bias2 = _bias_mode(bias, b, h, sq, sk)
    flags = dict(
        kbias=(mode == "kbias"), fbias=(mode == "fbias"), keep_prob=keep_prob,
        kdrop=(drop_seed is not None),
    )
    return b, h, sq, sk, bh, block_q, block_k, qr, kr, vr, dor, lser, delta, mode, bias2, flags


def _kv_grid_extra_specs(mode, bias2, mask, h, sq, block_k, drop_seed):
    """in_specs + arrays for the optional bias/mask/seed inputs of the
    kv-gridded backward kernels (dkv pass + fused single-pass)."""
    specs, args = [], []
    if mode == "kbias":
        specs.append(pl.BlockSpec((1, 1, block_k), lambda bh_, ki, h=h: (bh_ // h, 0, ki)))
        args.append(bias2)
    elif mode == "fbias":
        specs.append(pl.BlockSpec((1, sq, block_k), lambda bh_, ki: (bh_, 0, ki)))
        args.append(bias2)
    if mask is not None:
        specs.append(pl.BlockSpec((1, sq, block_k), lambda bh_, ki: (bh_, 0, ki)))
        args.append(mask)
    elif drop_seed is not None:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(drop_seed)
    return specs, args


def _flash_bwd_pallas(
    q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret,
    bias=None, mask=None, keep_prob: float = 1.0, drop_seed=None,
):
    b, h, sq, sk, bh, block_q, block_k, qr, kr, vr, dor, lser, delta, mode, bias2, flags = (
        _bwd_prologue(q, k, v, out, lse, g, bias, block_q, block_k, keep_prob, drop_seed)
    )
    d = q.shape[3]

    dq_extra_specs, dq_extra_args = _fwd_extra_specs(mode, bias2, mask, b, h, sq, sk, block_q, drop_seed)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal, block_k=block_k, **flags),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0)),
            pl.BlockSpec((1, sk, d), lambda bh_, qi: (bh_, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda bh_, qi: (bh_, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0)),
            pl.BlockSpec((1, 8, block_q), lambda bh_, qi: (bh_, 0, qi)),
            pl.BlockSpec((1, 8, block_q), lambda bh_, qi: (bh_, 0, qi)),
        ] + dq_extra_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh_, qi: (bh_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qr, kr, vr, dor, lser, delta, *dq_extra_args)

    # kv-blocked layouts for the dk/dv pass
    kv_extra_specs, kv_extra_args = _kv_grid_extra_specs(mode, bias2, mask, h, sq, block_k, drop_seed)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q, **flags),
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, sq, d), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, 8, sq), lambda bh_, ki: (bh_, 0, 0)),
            pl.BlockSpec((1, 8, sq), lambda bh_, ki: (bh_, 0, 0)),
        ] + kv_extra_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ki: (bh_, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qr, kr, vr, dor, lser, delta, *kv_extra_args)

    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13))
def _flash_attention(q, k, v, bias, mask, drop_seed, causal, sm_scale, block_q, block_k, interpret, keep_prob, bwd_block_q=None, bwd_block_k=None):
    # non-differentiated primal (inference/eval): no lse buffer
    return _flash_fwd_pallas(
        q, k, v, causal, sm_scale, block_q, block_k, interpret,
        want_lse=False, bias=bias, mask=mask, keep_prob=keep_prob, drop_seed=drop_seed,
    )[0]


def _flash_fwd_rule(q, k, v, bias, mask, drop_seed, causal, sm_scale, block_q, block_k, interpret, keep_prob, bwd_block_q=None, bwd_block_k=None):
    out, lse = _flash_fwd_pallas(
        q, k, v, causal, sm_scale, block_q, block_k, interpret,
        bias=bias, mask=mask, keep_prob=keep_prob, drop_seed=drop_seed,
    )
    # Names for selective activation checkpointing: a remat policy that
    # saves "attn_o"/"attn_lse" keeps the kernel's residuals, so the
    # backward pass does NOT re-run the forward kernel to rebuild the
    # logsumexp (the policy-driven analog of the reference's fused
    # kernels persisting their softmax stats between fwd and bwd,
    # csrc/transformer/softmax_kernels.cu)
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "attn_o")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse, bias, mask, drop_seed)


def _bias_cotangent(q, k, v, out, lse, g, bias, mask, causal, sm_scale, keep_prob, drop_seed=None):
    """Exact dL/dbias = dS (pre-scale scores' cotangent) reduced over the
    bias' broadcast dims.  Deliberately a SEPARATE computation from the
    Pallas backward: when the caller's bias is a constant (padding mask —
    the common case) the returned cotangent is unused and XLA's DCE
    removes this entire block; a trainable bias (learned relative
    position / ALiBi) pays O(Tq·Tk) here, the same order as the bias
    tensor it owns."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    s = s + jnp.broadcast_to(bias, (b, h, sq, sk)).astype(jnp.float32)
    if causal:
        qp = jnp.arange(sq)[:, None] + (sk - sq)
        s = jnp.where(qp >= jnp.arange(sk)[None, :], s, DEFAULT_MASK_VALUE)
    p = jnp.exp(s - lse[..., None])
    dp = jnp.einsum("bhqd,bhkd->bhqk", g.astype(jnp.float32), v.astype(jnp.float32))
    if mask is None and drop_seed is not None:
        # regenerate the kernels' keep-mask (host twin of the in-kernel
        # counter PRNG); only reached for a TRAINABLE bias under dropout
        mask = dropout_keep_mask_host(drop_seed, b, h, sq, sk, keep_prob)
    if mask is not None:
        dp = dp * (mask.reshape(b, h, sq, sk).astype(jnp.float32) / keep_prob)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    ds = p * (dp - delta[..., None])  # no sm_scale: bias enters post-scale
    # reduce over the dims the bias broadcast along
    reduce_axes = tuple(i for i in range(4) if bias.shape[i] == 1)
    db = jnp.sum(ds, axis=reduce_axes, keepdims=True) if reduce_axes else ds
    return db.astype(bias.dtype)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret, keep_prob, bwd_block_q, bwd_block_k, res, g):
    q, k, v, out, lse, bias, mask, drop_seed = res
    # single-pass backward: one exp per score instead of two (the d=64
    # kernel is VPU-softmax-bound; measured ~20% faster bwd at GPT-2
    # shapes).  Sequences whose fp32 dq exceeds a program's VMEM share
    # run the same kernel q-CHUNKED (r5); only explicit bias/mask
    # tensors still take the two-pass FA-2 kernels at those sizes
    if q.shape[2] * q.shape[3] * 4 <= _FUSED_BWD_MAX_SQ_BYTES:
        bwd = _flash_bwd_fused_pallas
    elif bias is None and mask is None:
        bwd = _flash_bwd_fused_chunked
    else:
        bwd = _flash_bwd_pallas
    dq, dk, dv = bwd(
        q, k, v, out, lse, g, causal, sm_scale,
        bwd_block_q or block_q, bwd_block_k or block_k, interpret,
        bias=bias, mask=mask, keep_prob=keep_prob, drop_seed=drop_seed,
    )
    dbias = None if bias is None else _bias_cotangent(
        q, k, v, out, lse, g, bias, mask, causal, sm_scale, keep_prob,
        drop_seed=drop_seed,
    )
    dmask = None if mask is None else jnp.zeros_like(mask)
    dseed = None if drop_seed is None else jnp.zeros_like(drop_seed)
    return dq, dk, dv, dbias, dmask, dseed


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    bias: Optional[jnp.ndarray] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    # (512, 512) measured fastest for the FULL 774M train step on v5e
    # (47.6% MFU vs 44.4% at the isolated-microbench winner (1024, 256)
    # — the micro sweep's 4.18ms/layer did not survive composition with
    # remat + the rest of the step's VMEM pressure); pick() clamps to
    # sequence divisors
    block_q: int = 512,
    block_k: int = 512,
    # backward-pass blocks (None ⇒ same as forward); the fused bwd and
    # the fwd kernel prefer different shapes at some sizes
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention over ``(batch, heads, seq, head_dim)`` inputs.

    Differentiable; forward and backward both run Pallas kernels (FA-2
    style dq/dkv backward with P recomputed from Q, K, lse).  Shapes the
    kernel grid can't serve fall back to the blockwise-rematerialized
    XLA path (large) or ``mha_reference`` (small).  ``interpret``
    defaults to True off-TPU.

    ``bias``: additive score bias broadcastable to (B, H, Tq, Tk) — e.g.
    a (B, 1, 1, Tk) padding mask.  Fully differentiable: a trainable
    bias (learned relative position) gets its exact cotangent from a
    separable O(Tq·Tk) recompute that XLA dead-code-eliminates when the
    gradient is unused (constant masks — the common case).
    ``dropout_rate`` applies attention-probability dropout
    (softmax-then-dropout, the reference's stochastic-transformer mode,
    csrc/transformer/dropout_kernels.cu).  On the kernel path the
    keep-mask is generated IN-KERNEL by a counter-based Threefry-2x32
    keyed on ``dropout_rng`` and the score element's absolute position
    — no O(Tq·Tk) HBM buffer, so long-context training keeps flash
    attention's O(T) memory with dropout on.  Non-kernel fallback paths
    materialize the identical mask host-graph-side (warned above 4k²).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    # An explicitly-passed ``interpret`` signals "exercise the kernel"
    # (the parity tests) — only the default dispatch may take the
    # short-sequence dense shortcut below.
    explicit_interpret = interpret is not None
    if interpret is None:
        interpret = pallas_interpret_default()
    b, h, sq, d = q.shape
    sk = k.shape[2]
    keep_prob = 1.0 - float(dropout_rate)
    drop_seed = None  # (2,) uint32 — the kernels generate keep-bits in-kernel
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        drop_seed = _seed_pair(dropout_rng)

    def host_mask4():
        """Materialized keep-mask for the non-kernel paths — the SAME
        bits the kernels would generate (one dropout stream per seed,
        whatever the dispatch)."""
        if drop_seed is None:
            return None
        if sq * sk > 4096 * 4096:
            logger.warning(
                f"attention dropout at seq {sq}x{sk} fell off the Pallas "
                f"kernel path and materializes a {b*h*sq*sk/2**30:.1f}GiB "
                "keep-mask in HBM (the kernel path generates it in-kernel "
                "at O(T) memory)"
            )
        return dropout_keep_mask_host(drop_seed, b, h, sq, sk, keep_prob).reshape(b, h, sq, sk)

    if not explicit_interpret and sq * sk <= SMALL_SEQ_DENSE_SCORES:
        return mha_dense(
            q, k, v, causal=causal, sm_scale=sm_scale, bias=bias,
            dropout_mask=host_mask4(), keep_prob=keep_prob,
        )

    def reference():
        return mha_reference(
            q, k, v, causal=causal, sm_scale=sm_scale, bias=bias,
            dropout_mask=host_mask4(), keep_prob=keep_prob,
        )

    # Caller-supplied blocks are honored when they divide the sequence;
    # otherwise halve down to 128 looking for a divisor (so e.g. seq 384
    # runs the kernel at block 128 instead of silently falling back to
    # the materializing reference path).
    def pick(n, pref):
        b_ = min(pref, n)
        if n % b_ == 0:
            return b_
        while b_ > 128:
            b_ //= 2
            if n % b_ == 0:
                return b_
        return None

    bq, bk = pick(sq, block_q), pick(sk, block_k)
    if bq is not None and bk is not None and bias is not None:
        # the full-bias BlockSpecs are (1, block_q, sk) fwd and
        # (1, sq, block_k) in the dkv pass — clamp the block sizes so
        # those auxiliary buffers stay ~2MB (VMEM is ~16MB/core and the
        # pipeline double-buffers); in-kernel dropout carries only a
        # (2,) SMEM seed, no clamp needed
        aux_bytes = 4
        while bq > 128 and bq * sk * aux_bytes > 2**21:
            bq = pick(sq, bq // 2) or 128
        while bk > 128 and bk * sq * aux_bytes > 2**21:
            bk = pick(sk, bk // 2) or 128
    if bq is None or bk is None or sq < 8 or sk < 8:
        if sq >= 8 and sk >= 8 and b * h * sq * sk * 4 > 2**28 and bias is None and drop_seed is None:
            # No kernel-compatible blocking but the (b,h,sq,sk) fp32
            # score tensor would exceed ~256MB: blockwise-rematerialized
            # XLA path (handles ragged sk by pad+mask).
            return _blockwise_xla(q, k, v, causal=causal, sm_scale=sm_scale, block_k=min(block_k, sk))
        # bias/dropout on ragged shapes: materializing scores is the only
        # correct path (the pre-kernel behavior of every caller)
        return reference()
    # VMEM guard (bytes): the fwd kernel keeps full K/V per
    # (batch,head) program resident, and the dkv backward keeps full
    # Q/dO — two operands, each DOUBLE-buffered by the pallas pipeline
    # (measured: 16k×64 bf16 wants 16.5M scoped vmem), so budget 4×
    # against the ~16MB/core limit.
    itemsize = jnp.dtype(q.dtype).itemsize
    if max(sq, sk) * d * itemsize * 4 >= 2**23:
        if bias is not None or drop_seed is not None:
            # scores must materialize beyond the kernel's VMEM envelope
            return reference()
        if sq == sk and sq % 128 == 0:
            # VMEM-bound self-attention: the splash kernel with a dense
            # layout (lower-triangular when causal, all-ones otherwise)
            # IS a kv-blocked flash — K/V stream per block through the
            # grid instead of sitting fully resident, so the VMEM bound
            # disappears.  Measured at 16k causal (B1 H12 d64, v5e):
            # fwd 56.8ms vs 63.6ms blockwise-XLA, fwd+bwd 112.3ms vs
            # 208.4ms (1.86×).  An all-ones layout carries no padding
            # penalty (every row has uniform full degree), so the
            # dense-row bucket exemption in `_dense_row_mask` keeps all
            # rows on the streaming kernel.
            from deepspeed_tpu.ops.attention.sparse import splash_attention

            blk = 256 if sq % 256 == 0 else 128
            nbq = sq // blk
            full = np.ones((h, nbq, nbq), np.uint8)
            layout = np.tril(full) if causal else full
            return splash_attention(
                q, k, v, layout, blk, causal=causal, sm_scale=sm_scale, interpret=interpret
            )
        return _blockwise_xla(q, k, v, causal=causal, sm_scale=sm_scale, block_k=bk)
    bbq = pick(sq, bwd_block_q) if bwd_block_q else None
    bbk = pick(sk, bwd_block_k) if bwd_block_k else None

    scale = float(sm_scale)

    def kernel(q, k, v, bias, drop_seed):
        return _flash_attention(
            q, k, v, bias, None, drop_seed, causal, scale, bq, bk,
            interpret, keep_prob, bbq, bbk,
        )

    sizes = {} if interpret else free_mesh_axes()
    if not sizes:
        return kernel(q, k, v, bias, drop_seed)
    return _over_mesh(kernel, q, k, v, bias, drop_seed, sizes)


def _over_mesh(kernel, q, k, v, bias, drop_seed, sizes):
    """The compiled kernel on a multi-device mesh (ops/kernels/sharded):
    batch over the dp grid, heads over tp, each device running the
    kernel on its rows.  In-kernel dropout keys its counter on the LOCAL
    batch·head index, so each shard folds its mesh position into the
    seed — shards draw different masks, one stream per (seed, layout)."""
    from deepspeed_tpu.sharding.layout import DEFAULT_LAYOUT

    spec = dim_spec(q.shape, {0: DEFAULT_LAYOUT.dp_axes, 1: DEFAULT_LAYOUT.tp_axis}, sizes)
    bias_spec = None
    if bias is not None:
        # a broadcast (size-1) bias dim stays whole
        bias_spec = PartitionSpec(
            *(ax if bias.shape[i] == q.shape[i] else None for i, ax in enumerate(spec))
        )
    axes = spec_axes(spec)

    def body(q, k, v, bias, drop_seed):
        if drop_seed is not None and axes:
            drop_seed = drop_seed.at[1].add(jax.lax.axis_index(axes).astype(drop_seed.dtype))
        return kernel(q, k, v, bias, drop_seed)

    return shard_call(
        body, (q, k, v, bias, drop_seed),
        (spec, spec, spec, bias_spec, PartitionSpec()), spec, sizes,
    )


@register_op("flash_attention", "pallas", "Online-softmax fused attention, Pallas fwd + FA-2 dq/dkv bwd, bias + attention dropout")
def _load_flash_attention():
    return flash_attention
