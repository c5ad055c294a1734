"""The recurrent step of Kimi Delta Attention (KDA) for a decode step,
in place on the per-slot recurrent state.

Per head, with state ``S (dk, dv)`` float32, a row's ``q, k (dk)``,
``v (dv)``, per-channel decay ``a = exp(g) (dk)`` and ``b = beta``::

    S' = diag(a) S
    u  = v - k^T S'                      (dv)
    S  = S' + (b k) u^T                  the delta rule, decayed first
    o  = S^T q                           (dv)

One decode step moves every decoding row's whole state in and out —
``2 x heads x dk x dv x 4`` bytes a row and layer, 8.4 MB at 64 heads of
128 x 128 — and does ~6 operations a state element: nothing but the
state's way through HBM.  The state buffer ``(layers, slots, heads, dk,
dv)`` is aliased to the output (``input_output_aliases``): a block that
no grid step visits keeps what it held, so the other layers, the slots
that are not decoding and nothing else is read or written.

Grid ``(decoding rows, heads / 16)``; the first bound is the **traced
count** of decoding rows, and the rows' slot ids ride as prefetched
scalars (compacted from ``write_mask`` by the caller), so a slot that is
empty or prefilling costs no step and no DMA.  A step holds 16 heads'
states (1 MB in, 1 MB out, both double-buffered).

Layout: the arithmetic is on the VPU in float32 (a matvec against a
float32 state on the MXU would round it to bfloat16 passes).  ``S`` has
``dk`` down the sublanes and ``dv`` along the lanes, so the two
reductions (over ``dk``) are sublane adds, ``v``, ``u`` and ``o`` are
lane rows, and ``q``, ``k``, ``a``, ``b k`` are needed as **columns**.
The caller hands those over already transposed: ``cols (rows, heads /
16, dk, 128)`` holds, for the 16 heads of a block, 8 columns a head
(``q, k, a, b k`` and four unused) — the same bytes as 8 rows a head,
and no transpose in the kernel.  Inference only.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import register_op
from deepspeed_tpu.utils.device import pallas_interpret_default

HEAD_BLOCK = 16      # heads a grid step holds
COLS_PER_HEAD = 8    # columns a head has in the ``cols`` tile: q, k, a, b k, four unused; 16 x 8 = the 128 lanes of a tile
_Q, _K, _A, _BK = 0, 1, 2, 3


def kda_decode_supported(H: int, dk: int, dv: int) -> bool:
    """Shapes the compiled kernel serves: whole blocks of 16 heads and a
    state of whole (8, 128) tiles.  Anything else takes the ``jnp`` form
    (``ops/transformer/linear_attention.py``)."""
    return H % HEAD_BLOCK == 0 and dk % 8 == 0 and dv % 128 == 0


def pack_columns(q, k, a, bk) -> jnp.ndarray:
    """``q, k, a, bk (B, H, dk)`` float32 → ``(B, H / 16, dk, 128)``: lane
    ``8 j + c`` of tile ``i`` is column ``c`` of head ``16 i + j``."""
    B, H, dk = q.shape
    zero = jnp.zeros_like(q)
    cols = jnp.stack([q, k, a, bk, zero, zero, zero, zero], axis=-1).astype(jnp.float32)  # (B, H, dk, 8)
    cols = cols.reshape(B, H // HEAD_BLOCK, HEAD_BLOCK, dk, COLS_PER_HEAD).transpose(0, 1, 3, 2, 4)
    return cols.reshape(B, H // HEAD_BLOCK, dk, HEAD_BLOCK * COLS_PER_HEAD)


def compact_rows(write_mask) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(rows (B,) int32, count (1,) int32)``: the indices where
    ``write_mask`` is True, in order, first; the rest repeat the last of
    them and are not walked."""
    B = write_mask.shape[0]
    mask = write_mask.astype(bool)
    order = jnp.argsort(jnp.logical_not(mask), stable=True).astype(jnp.int32)
    n = jnp.sum(mask).astype(jnp.int32)
    rows = jnp.where(jnp.arange(B) < n, order, order[jnp.maximum(n - 1, 0)])
    return rows, n.reshape(1)


def _kda_decode_kernel(rows_ref, n_ref, cols_ref, v_ref, s_ref, o_ref, s_out_ref):
    @pl.when(n_ref[0] == 0)
    def _nothing_decodes():
        # the one step a grid of no rows still takes: hand the block back as it came
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_ref[0] > 0)
    def _rows():
        cols = cols_ref[0, 0]  # (dk, 128)
        for j in range(HEAD_BLOCK):
            col = lambda c: cols[:, COLS_PER_HEAD * j + c: COLS_PER_HEAD * j + c + 1]  # noqa: E731  (dk, 1)
            s = s_ref[0, 0, j] * col(_A)                                   # (dk, dv)
            u = v_ref[0, pl.ds(j, 1), :] - jnp.sum(s * col(_K), axis=0, keepdims=True)  # (1, dv)
            s = s + col(_BK) * u
            s_out_ref[0, 0, j] = s
            o_ref[0, pl.ds(j, 1), :] = jnp.sum(s * col(_Q), axis=0, keepdims=True)


def _delta_rule_call(name: str, state, layer: int, q, k, v, a, beta, write_mask, interpret: Optional[bool]):
    """The one ``pallas_call`` under both names: ``q, k, a (B, H, dk)``
    a state's query, key and decay columns (``a = exp(g)``), ``v (B, H,
    dv)``, ``beta (B, H)``."""
    L, B, H, dk, dv = state.shape
    if not kda_decode_supported(H, dk, dv) or state.dtype != jnp.float32:
        raise ValueError(f"{name}: unsupported call (state {state.shape} {state.dtype}); "
                         "callers must dispatch through kda_decode_supported()")
    if interpret is None:
        interpret = pallas_interpret_default()
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    q, k, v, a, beta = f32(q), f32(k), f32(v), f32(a), f32(beta)
    cols = pack_columns(q, k, a, beta[..., None] * k)
    rows, n = compact_rows(write_mask)
    nh = H // HEAD_BLOCK
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # one step at least: a grid bound of zero is nothing a compiled program needs to meet
        grid=(jnp.maximum(n[0], 1), nh),
        in_specs=[
            pl.BlockSpec((1, 1, dk, HEAD_BLOCK * COLS_PER_HEAD), lambda i, h, rows, n: (rows[i], h, 0, 0)),
            pl.BlockSpec((1, HEAD_BLOCK, dv), lambda i, h, rows, n: (rows[i], h, 0)),
            pl.BlockSpec((1, 1, HEAD_BLOCK, dk, dv), lambda i, h, rows, n: (layer, rows[i], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, HEAD_BLOCK, dv), lambda i, h, rows, n: (rows[i], h, 0)),
            pl.BlockSpec((1, 1, HEAD_BLOCK, dk, dv), lambda i, h, rows, n: (layer, rows[i], h, 0, 0)),
        ],
    )
    o, state = pl.pallas_call(
        _kda_decode_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the prefetched scalars: rows, n, cols, v, state -> the state is the fifth
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(rows, n, cols, v, state)
    # rows no step visited hold whatever the output buffer held
    return jnp.where(write_mask.astype(bool)[:, None, None], o, 0.0), state


def kda_decode(state, layer: int, q, k, v, g, beta, write_mask, interpret: Optional[bool] = None):
    """One recurrent step of the rows where ``write_mask`` holds.

    ``state (layers, B, H, dk, dv)`` float32, updated in place at the
    static ``layer``; ``q, k, g (B, H, dk)`` (``q`` already scaled, both
    normalised, ``g`` the log-decay), ``v (B, H, dv)``, ``beta (B, H)``,
    ``write_mask (B,)``.  Returns ``(o (B, H, dv) float32, state)``; the
    rows that did not decode read 0 and their states are untouched."""
    return _delta_rule_call("kda_decode", state, layer, q, k, v, jnp.exp(g.astype(jnp.float32)), beta, write_mask, interpret)


def gdn_decode(state, layer: int, q, k, v, g, beta, write_mask, interpret: Optional[bool] = None):
    """The **gated delta rule**'s decode step (Gated DeltaNet,
    arXiv:2412.06464): KDA's recurrence with the decay **one scalar a
    head** (``diag(exp g)`` is ``exp(g) I``) and a query / key head
    shared by ``H / Hk`` value heads.

    ``state (layers, B, H, dk, dv)`` float32 as :func:`kda_decode`'s;
    ``q, k (B, Hk, dk)`` with ``H % Hk == 0`` — value head ``h`` reads
    query / key head ``h // (H / Hk)`` —, ``v (B, H, dv)``, ``g, beta (B,
    H)``.  The body is :func:`kda_decode`'s under the program name
    ``gdn_decode``: the columns tile is packed a **value** head, so a
    shared query / key rides twice and the scalar decay as a column of
    one number — 4 KB a head beside the 128 KB of state a head moves in
    and out, 3 % the recurrence need not move
    (``benchmark/kernels/gdn_decode.py`` counts the work without them)."""
    H, dk = state.shape[2], state.shape[3]
    rep = H // q.shape[1]
    if rep * q.shape[1] != H:
        raise ValueError(f"gdn_decode: {H} value heads are not whole groups of the {q.shape[1]} query / key heads")
    share = lambda t: jnp.repeat(t, rep, axis=1) if rep > 1 else t  # noqa: E731
    a = jnp.broadcast_to(jnp.exp(g.astype(jnp.float32))[..., None], g.shape + (dk,))
    return _delta_rule_call("gdn_decode", state, layer, share(q), share(k), v, a, beta, write_mask, interpret)


@register_op("kda_decode", "pallas", "KDA recurrent decode step, in place on the per-slot recurrent state")
def _load_kda_decode():
    return kda_decode


@register_op("gdn_decode", "pallas", "gated-delta-rule recurrent decode step (scalar decay a head, shared query / key heads), in place")
def _load_gdn_decode():
    return gdn_decode
