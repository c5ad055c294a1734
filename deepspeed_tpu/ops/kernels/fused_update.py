"""Fused optimizer update: one HBM pass per leaf.

The XLA path (``ops/adam/fused_adam.py`` driven by the engine's
``_apply_update_unscaled``) is correct but multi-fusion: the fp32
moment updates, the update-direction math, and the final
``(p + u).astype(p.dtype)`` parameter cast land in separate producer
passes, each re-streaming param-sized tensors through HBM — the
optimizer phase is purely memory-bound (attribution verdict:
``optimizer-update`` = memory), so every extra pass is wall-clock.
This module is the Pallas equivalent of the reference's
``multi_tensor_adam.cu`` / ``fused_lamb_cuda_kernel.cu``: **one kernel
per leaf** reads (p, g, m, v) once and writes (p', m', v') once — the
master-weight read, Adam/LAMB moment update, and the param-dtype cast
happen in-register between the two.

Three executors share one update body:

* **Pallas** (:func:`_adam_pallas_leaf`) — leaves whose last two
  dimensions fill whole tiles (:func:`_leaf_view`: the transformer
  weight matrices that carry ~all the bytes), each read **in the
  layout its buffers already have**;
* **XLA** (:func:`_adam_math`) — ragged/tiny leaves (vectors, stacked
  biases, an odd-row embedding) where a padding copy would cost more
  than it saves;
* **host numpy** — ``ops/adam/cpu_adam.py``'s fallback calls
  :func:`adam_update_reference` with ``xp=numpy``, so the
  ZeRO-Offload/Infinity drain steps the exact same formulas (the
  1-bit-Adam line, arXiv:2102.02888, is the precedent for keeping the
  memory-bound optimizer passes fused).

Overflow ("skip") semantics match the engine's in-producer contract:
``keep = 1 - overflow`` folds into the same pass — a skipped step
writes back the old state and a zero update without re-reading
anything.

LAMB needs the whole-leaf trust ratio (norms over p and the update
direction) before any param byte can be written, so it is structurally
two passes: kernel 1 fuses moments + direction + per-block norm
partials, the scalar trust resolves in-graph, kernel 2 applies
``p - lr·trust·dir`` with the dtype cast.  Still two passes instead of
the XLA path's four-plus.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import register_op
from deepspeed_tpu.utils.device import pallas_interpret_default

_LANES = 128
_PART_TILE = (8, _LANES)  # fp32 VMEM tile: the smallest block Mosaic writes
# most elements of one block.  Seven leaf-sized operands, each double-
# buffered, stand in VMEM at once: 56 B an element, 5.5 MB of the 16 MB
# a v5e kernel may use.
_BLOCK_ELEMS = 96 * 1024


# ---------------------------------------------------------------------------
# the ONE update body (dtype-agnostic; xp = jnp inside kernels/XLA, numpy
# on the ZeRO-Offload host path)
# ---------------------------------------------------------------------------

def adam_update_reference(xp, p32, g32, m, v, lr, b1, b2, eps, weight_decay,
                          adam_w_mode, c1, c2, inplace=False):
    """Adam/AdamW on fp32 values: returns (p_new, m_new, v_new).
    ``c1``/``c2`` are the bias corrections (pass 1.0 to disable).  The
    Pallas kernel, the XLA leaf path, and cpu_adam's numpy fallback all
    execute these lines (the keep-folded jnp twin below is the same
    algebra at keep=1).  ``inplace`` (numpy only — jnp arrays are
    immutable): mutate m/v/p32 buffers instead of allocating fresh
    leaf-sized arrays — the ZeRO-Offload drain exists because host
    memory is scarce."""
    if not adam_w_mode:
        g32 = g32 + weight_decay * p32
    if inplace:
        m *= b1
        m += (1.0 - b1) * g32
        v *= b2
        v += (1.0 - b2) * xp.square(g32)
        m_new, v_new = m, v
    else:
        m_new = b1 * m + (1.0 - b1) * g32
        v_new = b2 * v + (1.0 - b2) * g32 * g32
    denom = xp.sqrt(v_new / c2) + eps
    upd = -(lr * (m_new / c1) / denom)
    if adam_w_mode and weight_decay > 0.0:
        upd = upd - lr * weight_decay * p32
    if inplace:
        p32 += upd
        return p32, m_new, v_new
    return p32 + upd, m_new, v_new


def _adam_keep_body(p32, g32, m, v, lr, keep, c1, c2, *, b1, b2, eps,
                    weight_decay, adam_w_mode):
    """The ONE keep-folded Adam body: fp32 values in, (p32_new, m_new,
    v_new) out.  Executed verbatim by the Pallas kernel (on ref reads)
    and the XLA leaf path — keep = 1-overflow selects old-state/zero-
    update INSIDE the producer pass; algebraically equal to
    ``adam_update_reference`` at keep=1."""
    g32 = jnp.where(keep > 0, g32, 0.0)  # 0*inf would poison the fold
    if not adam_w_mode and weight_decay > 0.0:
        g32 = g32 + weight_decay * p32
    m_new = m + keep * ((b1 - 1.0) * m + (1.0 - b1) * g32)
    v_new = v + keep * ((b2 - 1.0) * v + (1.0 - b2) * g32 * g32)
    denom = jnp.sqrt(v_new / c2) + eps
    upd = -(lr * (m_new / c1) / denom)
    if adam_w_mode and weight_decay > 0.0:
        upd = upd - lr * weight_decay * p32
    return p32 + keep * upd, m_new, v_new


def _adam_math(p, g, m, v, lr, keep, c1, c2, **hyper):
    """XLA leaf path: the shared body on astype'd leaves."""
    p_new, m_new, v_new = _adam_keep_body(
        p.astype(jnp.float32), g.astype(jnp.float32), m, v, lr, keep, c1, c2,
        **hyper,
    )
    return p_new.astype(p.dtype), m_new, v_new


# ---------------------------------------------------------------------------
# Pallas Adam kernel
# ---------------------------------------------------------------------------

def _adam_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
                 *, b1, b2, eps, weight_decay, adam_w_mode):
    # scal: [lr, keep, c1, c2] fp32 in SMEM — traced scalars (schedule,
    # overflow flag, bias corrections) that must not bake into the
    # executable; the math is the ONE shared keep-folded body
    p_new, m_new, v_new = _adam_keep_body(
        p_ref[:].astype(jnp.float32), g_ref[:].astype(jnp.float32),
        m_ref[:], v_ref[:],
        scal_ref[0], scal_ref[1], scal_ref[2], scal_ref[3],
        b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
        adam_w_mode=adam_w_mode,
    )
    po_ref[:] = p_new.astype(po_ref.dtype)
    mo_ref[:] = m_new
    vo_ref[:] = v_new


def _leaf_view(shape, dtypes):
    """The view the kernels take of a leaf, from its shape and dtypes:
    ``((lead, rows, cols), (block_rows, block_cols))``, or None when the
    leaf is ragged/tiny (XLA path; a pad would cost a full extra
    read+write — exactly the traffic this kernel removes).

    The TPU stores an array in (sublanes, 128) tiles over its last two
    dimensions — 8 rows of float32, 16 of bf16 — so a view is free only
    while it leaves those two alone: ``rows`` and ``cols`` are the
    leaf's own, the dimensions before them fold into ``lead``, which the
    grid walks one entry at a time.  (A ``(size // 256, 256)`` view of a
    ``(…, 5120)`` leaf is a relayout: one more read and write of every
    operand and result, around a kernel whose point is one pass.)
    A block is whole tiles, as many rows of the whole last dimension as
    ``_BLOCK_ELEMS`` holds — ``(16, 5120)``, ``(64, 1280)``; a last
    dimension too wide for even one tile-row of that is split on a
    128-multiple, hence the grid's third axis."""
    if len(shape) < 2:
        return None
    rows, cols = shape[-2:]
    sublanes = max(32 // jnp.dtype(d).itemsize for d in dtypes)
    if cols % _LANES or rows % sublanes or not rows * cols:
        return None

    def largest_block(n, unit, most):
        # unit divides n and most >= unit, so there is always one
        return max(b for b in range(unit, min(n, most) + 1, unit) if n % b == 0)

    bc = largest_block(cols, _LANES, _BLOCK_ELEMS // sublanes)
    br = largest_block(rows, sublanes, _BLOCK_ELEMS // bc)
    return (math.prod(shape[:-2]), rows, cols), (br, bc)


def _leaf_specs(view):
    """(grid, leaf block spec, norm-partial block spec) of a
    :func:`_leaf_view`: the leading dimension is squeezed out of the
    kernel's refs, so the bodies see ``(block_rows, block_cols)``; each
    block has one norm-partial tile, in the grid's order."""
    (lead, rows, cols), (br, bc) = view
    nr, nc = rows // br, cols // bc
    blk = pl.BlockSpec((None, br, bc), lambda l, i, j: (l, i, j))
    part = pl.BlockSpec(_PART_TILE, lambda l, i, j: ((l * nr + i) * nc + j, 0))
    return (lead, nr, nc), blk, part


def _adam_pallas_leaf(p, g, m, v, scal, *, view, b1, b2, eps,
                      weight_decay, adam_w_mode, interpret):
    shape3 = view[0]
    p3, g3, m3, v3 = (t.reshape(shape3) for t in (p, g, m, v))
    grid, blk, _ = _leaf_specs(view)
    po, mo, vo = pl.pallas_call(
        functools.partial(
            _adam_kernel, b1=b1, b2=b2, eps=eps,
            weight_decay=weight_decay, adam_w_mode=adam_w_mode,
        ),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk, blk, blk, blk],
        out_specs=[blk, blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct(shape3, p.dtype),
            jax.ShapeDtypeStruct(shape3, jnp.float32),
            jax.ShapeDtypeStruct(shape3, jnp.float32),
        ],
        # true in-place: p/m/v buffers are consumed by their updates
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name="fused_adam",
    )(scal, p3, g3, m3, v3)
    return po.reshape(p.shape), mo.reshape(p.shape), vo.reshape(p.shape)


# ---------------------------------------------------------------------------
# Pallas LAMB kernels (two passes; see module docs)
# ---------------------------------------------------------------------------

def _lamb_dir_body(p32, g32, m, v, keep, c1, c2, *, b1, b2, eps, weight_decay):
    """The ONE keep-folded LAMB direction body (moments + update
    direction incl. decay term), shared by the Pallas pass-1 kernel and
    the XLA leaf path."""
    g32 = jnp.where(keep > 0, g32, 0.0)
    m_new = m + keep * ((b1 - 1.0) * m + (1.0 - b1) * g32)
    v_new = v + keep * ((b2 - 1.0) * v + (1.0 - b2) * g32 * g32)
    d = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
    if weight_decay > 0.0:
        d = d + weight_decay * p32
    return d, m_new, v_new


def _lamb_trust(w_norm, u_norm, min_coeff, max_coeff):
    return jnp.where(
        (w_norm > 0) & (u_norm > 0),
        jnp.clip(w_norm / u_norm, min_coeff, max_coeff),
        jnp.float32(1.0),
    )


def _lamb_dir_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref,
                     dir_ref, mo_ref, vo_ref, wsq_ref, dsq_ref,
                     *, b1, b2, eps, weight_decay):
    p32 = p_ref[:].astype(jnp.float32)
    d, m_new, v_new = _lamb_dir_body(
        p32, g_ref[:].astype(jnp.float32), m_ref[:], v_ref[:],
        scal_ref[1], scal_ref[2], scal_ref[3],
        b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
    )
    dir_ref[:] = d
    mo_ref[:] = m_new
    vo_ref[:] = v_new
    # per-block norm partials for the whole-leaf trust ratio, splat over
    # one (8, 128) tile each: Mosaic has no smaller VMEM output block
    wsq_ref[:] = jnp.full(wsq_ref.shape, jnp.sum(p32 * p32))
    dsq_ref[:] = jnp.full(dsq_ref.shape, jnp.sum(d * d))


def _lamb_apply_kernel(scal_ref, p_ref, dir_ref, trust_ref, po_ref):
    lr = scal_ref[0]
    keep = scal_ref[1]
    p32 = p_ref[:].astype(jnp.float32)
    upd = -(lr * trust_ref[0] * dir_ref[:]) * keep
    po_ref[:] = (p32 + upd).astype(po_ref.dtype)


def _lamb_pallas_leaf(p, g, m, v, scal, *, view, b1, b2, eps,
                      weight_decay, min_coeff, max_coeff, interpret):
    shape3 = view[0]
    p3, g3, m3, v3 = (t.reshape(shape3) for t in (p, g, m, v))
    grid, blk, part = _leaf_specs(view)
    part_shape = jax.ShapeDtypeStruct((math.prod(grid) * _PART_TILE[0], _PART_TILE[1]), jnp.float32)
    d3, mo, vo, wsq, dsq = pl.pallas_call(
        functools.partial(
            _lamb_dir_kernel, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
        ),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk, blk, blk, blk],
        out_specs=[blk, blk, blk, part, part],
        out_shape=[
            jax.ShapeDtypeStruct(shape3, jnp.float32),
            jax.ShapeDtypeStruct(shape3, jnp.float32),
            jax.ShapeDtypeStruct(shape3, jnp.float32),
            part_shape,
            part_shape,
        ],
        input_output_aliases={3: 1, 4: 2},
        interpret=interpret,
        name="fused_lamb_dir",
    )(scal, p3, g3, m3, v3)
    # one representative element per block tile
    wsq, dsq = (t[:: _PART_TILE[0], 0] for t in (wsq, dsq))
    trust = _lamb_trust(
        jnp.sqrt(jnp.sum(wsq)), jnp.sqrt(jnp.sum(dsq)), min_coeff, max_coeff
    ).reshape(1)
    po = pl.pallas_call(
        _lamb_apply_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM), blk, blk,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(shape3, p.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="fused_lamb_apply",
    )(scal, p3, d3, trust)
    return po.reshape(p.shape), mo.reshape(p.shape), vo.reshape(p.shape)


def _lamb_math(p, g, m, v, lr, keep, c1, c2, *, b1, b2, eps, weight_decay,
               min_coeff, max_coeff):
    """XLA leaf path: the shared direction body + trust + apply."""
    p32 = p.astype(jnp.float32)
    d, m_new, v_new = _lamb_dir_body(
        p32, g.astype(jnp.float32), m, v, keep, c1, c2,
        b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
    )
    trust = _lamb_trust(
        jnp.linalg.norm(p32.reshape(-1)), jnp.linalg.norm(d.reshape(-1)),
        min_coeff, max_coeff,
    )
    return (p32 - keep * lr * trust * d).astype(p.dtype), m_new, v_new


# ---------------------------------------------------------------------------
# engine entry point
# ---------------------------------------------------------------------------

def engine_update(optimizer, grads, opt_state, params, lr, overflow,
                  interpret: Optional[bool] = None,
                  split: Optional[Dict[str, int]] = None):
    """The ``_apply_update_unscaled`` seam: returns
    ``(new_params, new_opt_state)`` with the fused-kernel treatment, or
    None when this optimizer/state isn't kernel-eligible (the caller
    falls back to the XLA path unchanged).  Eligible today: FusedAdam /
    FusedAdamW with fp32 state (8-bit/bf16 states keep their SR
    machinery on XLA), and FusedLamb.  Overflow folds in-producer:
    skipped steps write back old state + unchanged params in the same
    single pass.  ``split``, when given, is handed back the elements
    that went each way (``pallas_elems`` / ``xla_elems``, counted while
    tracing): whether a model's state takes the one-pass path."""
    from deepspeed_tpu.ops.adam.fused_adam import AdamState, FusedAdam
    from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb, LambState

    if interpret is None:
        interpret = pallas_interpret_default()
    is_adam = isinstance(optimizer, FusedAdam) and isinstance(opt_state, AdamState)
    is_lamb = isinstance(optimizer, FusedLamb) and isinstance(opt_state, LambState)
    if is_adam and getattr(optimizer, "state_precision", "fp32") != "fp32":
        return None
    if not (is_adam or is_lamb):
        return None

    b1, b2 = optimizer.b1, optimizer.b2
    keep = (
        jnp.float32(1.0) if overflow is None
        else 1.0 - overflow.astype(jnp.float32)
    )
    step = opt_state.step
    if optimizer.bias_correction:
        # unconditional count — same skip-safe rule as FusedAdam.update
        bstep = (step + 1).astype(jnp.float32)
        c1 = 1.0 - b1 ** bstep
        c2 = 1.0 - b2 ** bstep
    else:
        c1 = c2 = jnp.float32(1.0)
    lr = jnp.asarray(lr, jnp.float32)
    scal = jnp.stack([
        lr, jnp.asarray(keep, jnp.float32),
        jnp.asarray(c1, jnp.float32), jnp.asarray(c2, jnp.float32),
    ])

    if split is None:
        split = {}
    split.update(pallas_elems=0, xla_elems=0)

    def one(g, m, v, p):
        common = dict(b1=b1, b2=b2, eps=optimizer.eps,
                      weight_decay=optimizer.weight_decay)
        if is_adam:
            common["adam_w_mode"] = optimizer.adam_w_mode
            pallas_leaf, xla_leaf = _adam_pallas_leaf, _adam_math
        else:
            common["min_coeff"] = optimizer.min_coeff
            common["max_coeff"] = optimizer.max_coeff
            pallas_leaf, xla_leaf = _lamb_pallas_leaf, _lamb_math
        view = _leaf_view(p.shape, (p.dtype, g.dtype))
        if view is None:
            split["xla_elems"] += p.size
            return xla_leaf(p, g, m, v, lr, keep, c1, c2, **common)
        split["pallas_elems"] += p.size
        return pallas_leaf(p, g, m, v, scal, view=view, interpret=interpret, **common)

    from deepspeed_tpu.ops.adam.fused_adam import _map_multi

    new_p, new_m, new_v = _map_multi(
        one, 3, grads, opt_state.exp_avg, opt_state.exp_avg_sq, params
    )
    new_step = step + (1 if overflow is None else jnp.where(overflow, 0, 1))
    state_cls = AdamState if is_adam else LambState
    return new_p, state_cls(step=new_step, exp_avg=new_m, exp_avg_sq=new_v)


@register_op(
    "fused_update", "pallas",
    "One-HBM-pass Adam/LAMB update: master read + moments + param cast per leaf",
)
def _load_fused_update():
    return engine_update
