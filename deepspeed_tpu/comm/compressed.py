"""Error-feedback 1-bit compressed collectives.

TPU-native port of the reference's compressed allreduce algorithm
(``runtime/comm/nccl.py:47-186``; same algorithm over MPI in
``comm/mpi.py``): each rank adds its error-feedback residual, compresses
to sign bits + an L1 scale, exchanges chunks (all_to_all), every rank
averages the signs it "serves", re-compresses with a server-side
residual, and all-gathers the result.  cupy bit-packing + NCCL
primitives become pure XLA ops inside ``shard_map`` over a named mesh
axis — on TPU the sign tensors ride ICI as int8 (XLA has no bit-packed
dtype; volume saving is 4× vs fp32 rather than the reference's ~32×,
but the error-feedback math and convergence behavior are identical,
and int8 is the densest ICI-native exchange format).

State (worker_error, server_error) lives in the optimizer state.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _sign_compress(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compress to {-1,+1} int8 signs + scalar L1 scale (reference
    nccl.py:76-86: scale = |x|.mean(); sign with 0→+1)."""
    scale = jnp.mean(jnp.abs(x))
    signs = jnp.where(x >= 0, jnp.int8(1), jnp.int8(-1))
    return signs, scale


def _decompress(signs: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return signs.astype(jnp.float32) * scale


def _body(x, worker_error, server_error, *, axis_name: str):
    """Per-rank body under shard_map.  Shapes (leading mapped dim of 1):
    x, worker_error: (1, M); server_error: (1, M//n).  Returns the
    averaged tensor (1, M) (identical on every rank) + new errors."""
    n = jax.lax.psum(1, axis_name)
    x = x[0]
    werr = worker_error[0]
    serr = server_error[0]
    m = x.shape[0]
    chunk = m // n

    corrected = x + werr
    signs, scale = _sign_compress(corrected)
    new_werr = corrected - _decompress(signs, scale)

    # Phase 1 — scatter: rank j receives chunk j from every rank
    # (reference's all_to_all_single, nccl.py:99) + scales via all_gather.
    served = jax.lax.all_to_all(signs.reshape(n, chunk), axis_name, split_axis=0, concat_axis=0, tiled=False)
    scales = jax.lax.all_gather(scale, axis_name)  # (n,)
    avg = jnp.mean(served.astype(jnp.float32) * scales[:, None], axis=0)  # (chunk,)

    # Phase 2 — server-side re-compress with server error feedback
    # (nccl.py:120-150).
    corrected_srv = avg + serr
    srv_signs, srv_scale = _sign_compress(corrected_srv)
    new_serr = corrected_srv - _decompress(srv_signs, srv_scale)

    # Phase 3 — allgather the served slices back (nccl.py:152-170).
    all_signs = jax.lax.all_gather(srv_signs, axis_name)  # (n, chunk)
    all_scales = jax.lax.all_gather(srv_scale, axis_name)  # (n,)
    out = (all_signs.astype(jnp.float32) * all_scales[:, None]).reshape(-1)
    return out[None], new_werr[None], new_serr[None]


def _exchange(x_per_rank, worker_error, server_error, mesh, axis_name, replicated_out: bool):
    # per-rank exchange rows resolve through the partition-rule engine's
    # layout helpers (one row per rank of the exchange grid)
    from deepspeed_tpu.sharding.layout import dp_rows_spec, replicated_pspec

    n, m = x_per_rank.shape
    if m % n:
        raise ValueError(f"tensor length {m} not divisible by axis size {n}")

    rows = dp_rows_spec(axis_name)

    def body(x, werr, serr):
        out, new_werr, new_serr = _body(x, werr, serr, axis_name=axis_name)
        return (out[0] if replicated_out else out), new_werr, new_serr

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rows, rows, rows),
        out_specs=(replicated_pspec() if replicated_out else rows, rows, rows),
        check_vma=False,
    )
    return mapped(x_per_rank, worker_error, server_error)


def compressed_allreduce(x_per_rank, worker_error, server_error, mesh, axis_name="data"):
    """1-bit error-feedback averaged allreduce.

    ``x_per_rank``: (n, M) — row i is rank i's local tensor (M divisible
    by n).  ``worker_error``: (n, M).  ``server_error``: (n, M // n).
    Returns (avg (n, M) — every row identical, new_worker_error,
    new_server_error), all sharded over ``axis_name``.

    ``axis_name`` may be one mesh axis name or a TUPLE of axis names —
    e.g. ``("data", "fsdp")`` runs the exchange flat across the whole
    data-parallel grid, the ZeRO-composed form (n = product of the axis
    sizes; rank order is mesh-major).  The reference's 1-bit Adam never
    composes with ZeRO (onebit/adam.py:110 under FP16_UnfusedOptimizer
    only); here it is just a bigger ring.
    """
    return _exchange(x_per_rank, worker_error, server_error, mesh, axis_name, replicated_out=False)


def compressed_allreduce_replicated(x_per_rank, worker_error, server_error, mesh, axis_name="data"):
    """Like :func:`compressed_allreduce` but returns the averaged vector
    as a single replicated ``(M,)`` array — free, because phase 3's
    all-gather already leaves the full result on every rank; declaring
    the output replicated avoids a redundant broadcast at the engine
    boundary (this is the training-path entry point)."""
    return _exchange(x_per_rank, worker_error, server_error, mesh, axis_name, replicated_out=True)


def compressed_allreduce_compressed_out(
    x_per_rank, worker_error, server_error, mesh, axis_name="data"
):
    """Like :func:`compressed_allreduce_replicated` but returns the
    averaged vector in its COMPRESSED form — ``(signs (M,) int8,
    scales (n,) fp32)`` with ``out = decompress_chunks(signs, scales)``
    — instead of the decompressed fp32 vector.  Phase 3's all-gather
    already moves exactly these bytes; exposing them lets the caller
    STORE the synced momentum at 1 byte/param (it is exactly
    sign×chunk-scale by construction) and decompress transiently."""
    from deepspeed_tpu.sharding.layout import dp_rows_spec, replicated_pspec

    n, m = x_per_rank.shape
    if m % n:
        raise ValueError(f"tensor length {m} not divisible by axis size {n}")
    rows = dp_rows_spec(axis_name)

    def body(x, werr, serr):
        n_ = jax.lax.psum(1, axis_name)
        xv, we, se = x[0], werr[0], serr[0]
        chunk = xv.shape[0] // n_

        corrected = xv + we
        signs, scale = _sign_compress(corrected)
        new_werr = corrected - _decompress(signs, scale)

        served = jax.lax.all_to_all(
            signs.reshape(n_, chunk), axis_name, split_axis=0, concat_axis=0, tiled=False
        )
        scales = jax.lax.all_gather(scale, axis_name)
        avg = jnp.mean(served.astype(jnp.float32) * scales[:, None], axis=0)

        corrected_srv = avg + se
        srv_signs, srv_scale = _sign_compress(corrected_srv)
        new_serr = corrected_srv - _decompress(srv_signs, srv_scale)

        all_signs = jax.lax.all_gather(srv_signs, axis_name).reshape(-1)  # (M,)
        all_scales = jax.lax.all_gather(srv_scale, axis_name)  # (n,)
        return all_signs, all_scales, new_werr[None], new_serr[None]

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rows, rows, rows),
        out_specs=(replicated_pspec(), replicated_pspec(), rows, rows),
        check_vma=False,
    )
    return mapped(x_per_rank, worker_error, server_error)


def decompress_chunks(signs: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """Rebuild the fp32 vector from per-chunk sign compression:
    ``signs`` (M,) int8, ``scales`` (n,) — chunk i spans
    ``[i*M/n, (i+1)*M/n)`` (the all-to-all chunking)."""
    n = scales.shape[0]
    return (signs.reshape(n, -1).astype(jnp.float32) * scales[:, None]).reshape(-1)


def compress_chunks(x: jnp.ndarray, n: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-chunk sign compression of a flat vector (the server-side
    granularity): returns (signs (M,) int8, scales (n,))."""
    xc = x.reshape(n, -1)
    scales = jnp.mean(jnp.abs(xc), axis=1)
    signs = jnp.where(xc >= 0, jnp.int8(1), jnp.int8(-1)).reshape(-1)
    return signs, scales
