"""Sequence / context parallelism — first-class long-context support.

The reference (v0.4.5) has **no** sequence parallelism; its long-sequence
story is block-sparse attention + activation checkpointing (SURVEY.md
§5.7).  This module provides the modern successors as first-class mesh
citizens over the ``seq`` axis:

* **Ring attention** (`ring_attention`): K/V shards rotate around the
  ring via ``lax.ppermute`` (XLA ``collective-permute`` riding ICI)
  while each device's Q shard accumulates an online softmax — exact
  attention with O(T/P) activation memory per device, comm overlapped
  with the block matmuls by XLA's async collectives.
* **Ulysses-style attention** (`ulysses_attention`): two
  ``lax.all_to_all``s swap sequence-sharding for head-sharding, run the
  (flash) attention kernel on full-length sequences for H/P heads, and
  swap back — cheaper comm than ring for moderate P (2 all-to-alls of
  the activations) but requires ``heads % P == 0``.

Both run inside ``jax.shard_map`` with *only* the ``seq`` axis manual
(``axis_names={'seq'}``) so batch / tensor-parallel sharding on the same
arrays stays GSPMD-automatic and composes with ZeRO and TP untouched.

Layout convention matches ops.attention: ``(batch, heads, seq, head_dim)``
with the **seq dim sharded** over the ``seq`` mesh axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention.flash_attention import DEFAULT_MASK_VALUE, flash_attention, mha_reference
from deepspeed_tpu.ops.registry import register_op

SEQ_AXIS = "seq"


def _axis_size(mesh, axis_name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis_name, 1)


# ---------------------------------------------------------------------------
# Ring attention (per-shard body; runs under shard_map)
# ---------------------------------------------------------------------------

def _ring_attention_sharded(q, k, v, *, axis_name: str, causal: bool, sm_scale: float):
    """Exact attention with K/V rotating around the ``axis_name`` ring.

    ``q, k, v``: local shards ``(B, H, T/P, D)``; sequence is sharded
    contiguously (shard ``r`` holds positions ``[r*T/P, (r+1)*T/P)``).
    """
    ring = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, t_local, d = q.shape
    qf = q.astype(jnp.float32) * sm_scale
    q_pos = my * t_local + jnp.arange(t_local)  # global query positions
    perm = [(j, (j + 1) % ring) for j in range(ring)]

    def step(carry, i):
        k_cur, v_cur, acc, m_prev, l_prev = carry
        # Kick off the rotation *before* the block math so XLA overlaps the
        # collective-permute with the matmuls (no data dependency).
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)

        src = jnp.mod(my - i, ring)  # rank whose K/V chunk we hold at step i
        k_pos = src * t_local + jnp.arange(t_local)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32))
        if causal:
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, DEFAULT_MASK_VALUE)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        return (k_nxt, v_nxt, acc, m_new, l_new), None

    init = (
        k,
        v,
        jnp.zeros((b, h, t_local, d), jnp.float32),
        jnp.full((b, h, t_local, 1), -jnp.inf, jnp.float32),
        jnp.zeros((b, h, t_local, 1), jnp.float32),
    )
    # remat each ring step: backward re-runs the block math instead of
    # saving (t_local × t_local) score blocks.
    stepr = jax.checkpoint(step, prevent_cse=False)
    (k_f, v_f, acc, m, l), _ = jax.lax.scan(stepr, init, jnp.arange(ring))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (causal, early shards)
    return (acc / l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ulysses (DeepSpeed-Ulysses-style all-to-all attention)
# ---------------------------------------------------------------------------

def _ulysses_sharded(q, k, v, *, axis_name: str, causal: bool, sm_scale: float, use_flash: bool):
    """seq-sharded → head-sharded via all_to_all, full-seq attention, back."""

    def scatter_heads(x):  # (B, H, T/P, D) -> (B, H/P, T, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def gather_heads(x):  # (B, H/P, T, D) -> (B, H, T/P, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    q, k, v = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    if use_flash:
        o = flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    else:
        o = mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    return gather_heads(o)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    mesh=None,
    axis_name: str = SEQ_AXIS,
) -> jnp.ndarray:
    """Ring attention over the ``seq`` mesh axis.

    Inputs are **global** arrays ``(B, H, T, D)`` (sharded or not — GSPMD
    handles movement to the required seq-sharding); output matches
    ``mha_reference`` numerics exactly.
    """
    return _seq_parallel_call(_ring_attention_sharded, q, k, v, causal, sm_scale, mesh, axis_name)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    mesh=None,
    axis_name: str = SEQ_AXIS,
    use_flash: bool = True,
) -> jnp.ndarray:
    """All-to-all (Ulysses) sequence-parallel attention over ``seq``.

    Requires ``H % seq_parallel_size == 0``.
    """
    return _seq_parallel_call(
        _ulysses_sharded, q, k, v, causal, sm_scale, mesh, axis_name, use_flash=use_flash
    )


def _seq_parallel_call(body_fn, q, k, v, causal, sm_scale, mesh, axis_name, **body_kwargs):
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    mesh = _resolve_mesh(mesh)
    ring = _axis_size(mesh, axis_name)
    use_flash = body_kwargs.get("use_flash", True)
    if ring == 1:
        if use_flash:
            return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    if q.shape[2] % ring:
        raise ValueError(f"seq len {q.shape[2]} not divisible by seq axis size {ring}")
    if body_fn is _ulysses_sharded and q.shape[1] % ring:
        raise ValueError(f"ulysses needs heads ({q.shape[1]}) divisible by seq axis ({ring})")
    body = functools.partial(
        body_fn, axis_name=axis_name, causal=causal, sm_scale=float(sm_scale), **body_kwargs
    )
    spec = P(None, None, axis_name, None)
    # version-compat shard_map (axis_names/check_vma vs auto/check_rep
    # keyword drift across the jax 0.4.x line) — same shim the pipeline
    # engine's per-stage bodies use
    from deepspeed_tpu.comm.collectives import shard_map_manual

    fn = shard_map_manual(body, mesh, in_specs=(spec, spec, spec), out_specs=spec, manual_axes={axis_name})
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ambient mesh — models are built before the engine/mesh exists, so
# sequence-parallel attention resolves the mesh lazily at trace time.
# Each engine activates its own mesh (``ambient_mesh``) around every
# trace, so multiple engines with different meshes co-exist in one
# process (train + eval, train + inference) with no global cross-talk;
# ``set_global_mesh`` remains as a *process default* for code running
# outside any engine (tests, notebooks) and sits below the ambient mesh
# in the resolution order: explicit arg > ambient (tracing engine) >
# process default.
# ---------------------------------------------------------------------------

import contextlib
from contextvars import ContextVar

_AMBIENT_MESH: ContextVar = ContextVar("ds_tpu_ambient_mesh", default=None)
_DEFAULT_MESH = None


@contextlib.contextmanager
def ambient_mesh(mesh):
    """Activate ``mesh`` for the duration of a trace (engine-scoped)."""
    token = _AMBIENT_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _AMBIENT_MESH.reset(token)


def scoped_to(mesh, fn):
    """Wrap a to-be-traced function so lazily-resolved parallel ops
    (ring/ulysses attention, MoE expert sharding) see ``mesh`` at trace
    time.  Engine-scoped (contextvar), so engines over different meshes
    co-exist in one process — no global singleton."""
    import functools

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with ambient_mesh(mesh):
            return fn(*args, **kwargs)

    return wrapped


def set_global_mesh(mesh) -> None:
    """Set the process-default mesh (fallback for code outside engines)."""
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def get_global_mesh():
    """The mesh lazily-resolved ops would use here: the tracing engine's
    ambient mesh if inside one, else the process default."""
    amb = _AMBIENT_MESH.get()
    return amb if amb is not None else _DEFAULT_MESH


def _resolve_mesh(mesh):
    if mesh is not None:
        return mesh
    resolved = get_global_mesh()
    if resolved is None:
        raise ValueError(
            "sequence-parallel attention needs a mesh: pass mesh=..., run "
            "under an engine (it scopes its mesh around every trace), or "
            "set_global_mesh(...) for standalone use"
        )
    return resolved


@register_op("ring_attention", "xla+shard_map", "Exact ring attention over the seq axis (ppermute K/V rotation)")
def _load_ring_attention():
    return ring_attention


@register_op("ulysses_attention", "xla+shard_map", "All-to-all head<->seq parallel attention")
def _load_ulysses_attention():
    return ulysses_attention
