"""Compressed convolutional attention (CCA): softmax attention computed
**in a compressed latent**, its queries and keys mixed along the
sequence by two short causal convolutions before they meet.

For position ``t`` with ``h_t`` the layer's normed input (``H`` query
heads, ``Hkv`` KV heads of ``d`` each, ``C = (H + Hkv) d`` channels)::

    q~_t, k~_t   = h_t W_q, h_t W_k                      down-projections: the latent
    v_t          = [h_t W_v1 ; h_{t-1} W_v2]             half the KV heads from this token, half from the one before
    [q^ ; k^]_t  = conv1(conv0([q~ ; k~]))_t             conv0 depthwise, 2 taps; conv1 grouped (a group a head, d -> d), 2 taps
    q_t, k_t     = q^_t + m_q, k^_t + m_k                the q-k mean, a skip from before the convolutions:
                                                         m_q[h] = (q~[h] + k~[h // G]) / 2, m_k[g] = (mean_{h in g} q~[h] + k~[g]) / 2
    q_t, k_t     = rope(sqrt(d) q / |q|), rope(tau[g] sqrt(d) k / |k|)    per head; rotary on the first ``rot`` dims
    o_t          = softmax_s<=t(q_t k_s / sqrt(d)) v_s   grouped queries: head h on KV head h // G

Position ``t`` sees ``[q~ ; k~]`` of ``t``, ``t - 1`` and ``t - 2``, and
``h W_v2`` of ``t - 1``: zeros before the sequence starts.

What a serving cache keeps is therefore **two things a layer**: on K/V
pages the *mixed, normalised, rotated* ``k`` and the shifted ``v`` of
every position, and **per slot a tail** that the next position's mixing
needs and a page cannot give back — the last two ``[q~ ; k~]`` rows
(``conv (layers, slots, 2, C)``) and the last ``h W_v2`` (``vshift
(layers, slots, Hkv / 2 * d)``).

Two forms of the one function (:func:`attention`):

* a **prefill chunk** (``slot`` given): the convolutions and the value
  shift carried in from the slot's tail — **zero where the chunk starts
  at position 0**, so a slot needs no reset between requests — the tail
  left at the chunk's last *real* token, attention block by block over
  the slot's pages (``inference.paged_chunk_attention``);
* a **decode step** (``slot`` None: row ``b`` is slot ``b``): one
  position, the tail shifted by one row, the grouped
  ``flash_decode_paged`` over the slot's pages.

Both reach the stacked pools ``(layers, pages, Hkv, page_len, d)`` **in
place**: the layer's K/V rows are written as slices
(``inference.paged_cache_write_slices``), and attention is handed the
pool with its two leading dims merged — a bitcast — and a page table
offset by ``layer * pages``; a static slice ``pool[layer]`` in front of a
kernel is a copy of the layer's pages, once a layer a step.

Device ops carry two named scopes: ``cca.mix`` (projections, tail read
and write, both convolutions, the q-k mean, norms, rotary) and
``cca.attend`` (page writes and attention).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.transformer.linear_attention import l2norm

TAPS = 2  # of each convolution: the tail is TAPS rows deep (conv0's one row back + conv1's)


class Sizes(NamedTuple):
    """What :func:`attention` needs of a model's configuration."""

    heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads

    @property
    def channels(self) -> int:
        """``[q~ ; k~]``: what the convolutions mix and the tail holds."""
        return (self.heads + self.kv_heads) * self.head_dim

    @property
    def shift_width(self) -> int:
        """``h W_v2``: the values of the KV heads that read the token before."""
        return self.kv_heads // 2 * self.head_dim


def rope(x, positions, rotary_dim: int, theta: float):
    """Rotate the first ``rotary_dim`` dims of each head of ``x (B, T,
    heads, d)`` (half layout) by ``positions (B, T)``; the rest pass."""
    half = rotary_dim // 2
    inv_freq = jnp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq  # (B, T, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], axis=-1)


def mix(z, w0, w1, tail, n_valid=None):
    """Both convolutions over ``z (B, T, C)``, the rows ``[q~ ; k~]``:
    ``w0 (2, C)`` the depthwise taps and ``w1 (2, heads, d, d)`` the
    grouped ones (index 1 multiplies the current input, 0 the one
    before), ``tail (B, 2, C)`` the two rows before ``z``.  Returns ``(y
    (B, T, C) float32, new tail)``: the last two rows, or — ``n_valid
    (B,)`` given — the two that end at row ``b``'s ``n_valid[b]``-th
    token (a chunk's padded tail is not remembered)."""
    B, T, C = z.shape
    G, d = w1.shape[1], w1.shape[2]
    f32 = jnp.float32
    ext = jnp.concatenate([tail.astype(z.dtype), z], axis=1)  # row t of the chunk sits at t + 2
    e32 = ext.astype(f32)
    y0 = e32[:, 1:] * w0[1].astype(f32) + e32[:, :-1] * w0[0].astype(f32)  # (B, T + 1, C): conv0 at t - 1 .. T - 1
    y0 = y0.astype(z.dtype).reshape(B, T + 1, G, d)
    # "btgi,gio->btgo" as the dot_general it is (the group a batch dim): jnp.einsum's own transposes in front of
    # it leave XLA:CPU a bf16 x bf16 -> f32 dot it has no thunk for
    tap = lambda rows, w: jnp.moveaxis(jax.lax.dot_general(  # noqa: E731
        rows, w.astype(z.dtype), (((3,), (1,)), ((2,), (0,))), preferred_element_type=f32), 0, 2)
    y = (tap(y0[:, 1:], w1[1]) + tap(y0[:, :-1], w1[0])).reshape(B, T, C)
    if n_valid is None:
        new = ext[:, T:]
    else:
        new = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, TAPS, axis=0))(ext, n_valid)
    return y, new.astype(tail.dtype)


def qk_heads(z, y, tau, positions, sz: Sizes):
    """From ``z = [q~ ; k~]`` and its mixed form ``y`` (both ``(B, T,
    C)``) to the ``q (B, H, T, d)`` and ``k (B, Hkv, T, d)`` that meet in
    attention: the q-k mean added, each head L2-normalised to ``sqrt(d)``
    (``k`` times its temperature ``tau (Hkv,)``), rotary."""
    B, T, _ = z.shape
    H, Hkv, d, G = sz.heads, sz.kv_heads, sz.head_dim, sz.group
    f32 = jnp.float32
    z = z.astype(f32)
    zq, zk = z[..., : H * d].reshape(B, T, Hkv, G, d), z[..., H * d:].reshape(B, T, Hkv, d)
    q = y[..., : H * d].reshape(B, T, Hkv, G, d) + 0.5 * (zq + zk[:, :, :, None])
    k = y[..., H * d:].reshape(B, T, Hkv, d) + 0.5 * (jnp.mean(zq, axis=3) + zk)
    q = l2norm(q.reshape(B, T, H, d)) * d ** 0.5
    k = l2norm(k) * (d ** 0.5 * tau.astype(f32))[:, None]
    q, k = rope(q, positions, sz.rotary_dim, sz.rope_theta), rope(k, positions, sz.rotary_dim, sz.rope_theta)
    return q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)


def shifted_values(v_now, v_back, vshift, sz: Sizes, n_valid=None):
    """``v (B, Hkv, T, d)``: the first half of the KV heads from ``v_now
    (B, T, Hkv / 2 * d)`` (this token), the second from ``v_back`` **one
    position earlier** — ``vshift (B, Hkv / 2 * d)`` in front of the
    chunk.  Also the new ``vshift``: ``v_back`` of the last token, or of
    row ``b``'s ``n_valid[b]``-th."""
    B, T, _ = v_now.shape
    ext = jnp.concatenate([vshift.astype(v_back.dtype)[:, None], v_back], axis=1)  # v_back of t - 1 sits at t
    v = jnp.concatenate([v_now, ext[:, :T]], axis=-1).reshape(B, T, sz.kv_heads, sz.head_dim)
    last = ext[:, T] if n_valid is None else jnp.take_along_axis(ext, n_valid[:, None, None], axis=1)[:, 0]
    return v.transpose(0, 2, 1, 3), last.astype(vshift.dtype)


def attention(sz: Sizes, w_qkv, w_conv0, w_conv1, tau, h, k_pool, v_pool, state: Dict[str, Any], layer: int, pos,
              page_table, slot=None, write_mask=None, row_valid=None, use_kernel: Optional[bool] = None,
              trace_notes: Optional[dict] = None, work=None) -> Tuple[Any, Any, Any, Dict[str, Any]]:
    """CCA of ``h (B, T, D)``, the layer's normed input, on layer
    ``layer`` of the stacked pools and of the per-slot ``state``
    (``{"conv", "vshift"}``), at per-row write offsets ``pos (B,)``.
    ``w_qkv (D, C + 2 * shift_width)`` holds ``W_q | W_k | W_v1 | W_v2``.

    ``slot (B,)`` given: a **prefill chunk** of the slots named (the tail
    read as zero where ``pos == 0``, left at the last token whose
    ``row_valid`` is True).  ``slot`` None: a **decode step**, row ``b``
    is slot ``b`` and rows with ``write_mask`` False keep their tail and
    write their K/V to the garbage page; ``work`` is the step's work
    list for the paged decode kernel (``flash_decode.paged_work_list``, built
    once for all layers).  Returns ``(o (B, T, H * d) in
    h's dtype, k_pool, v_pool, state)``."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.kernels.flash_decode import decode_paged_supported
    from deepspeed_tpu.ops.transformer import inference as inf

    B, T, _ = h.shape
    H, d, C, S = sz.heads, sz.head_dim, sz.channels, sz.shift_width
    decode = slot is None
    with jax.named_scope("cca.mix"):
        proj = h @ w_qkv
        z, v_now, v_back = proj[..., :C], proj[..., C: C + S], proj[..., C + S:]
        if decode:
            tail0, shift0, n_valid = state["conv"][layer], state["vshift"][layer], None
        else:
            fresh = (pos == 0)
            tail0 = jnp.where(fresh[:, None, None], 0, inf.state_rows(state["conv"], layer, slot))
            shift0 = jnp.where(fresh[:, None], 0, inf.state_rows(state["vshift"], layer, slot))
            n_valid = None if row_valid is None else jnp.sum(row_valid.astype(jnp.int32), axis=1)
        y, tail1 = mix(z, w_conv0, w_conv1, tail0, n_valid)
        v, shift1 = shifted_values(v_now, v_back, shift0, sz, n_valid)
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        q, k = qk_heads(z, y, tau, positions, sz)
        q, k = q.astype(h.dtype), k.astype(h.dtype)
        if decode:
            mask = jnp.ones((B,), bool) if write_mask is None else write_mask.astype(bool)
            state = {"conv": state["conv"].at[layer].set(jnp.where(mask[:, None, None], tail1, tail0)),
                     "vshift": state["vshift"].at[layer].set(jnp.where(mask[:, None], shift1, shift0))}
        else:
            state = {"conv": inf.state_rows_write(state["conv"], layer, slot, tail1),
                     "vshift": inf.state_rows_write(state["vshift"], layer, slot, shift1)}
    with jax.named_scope("cca.attend"):
        k_pool = inf.paged_cache_write_slices(k_pool, layer, k, page_table, pos, write_mask)
        v_pool = inf.paged_cache_write_slices(v_pool, layer, v, page_table, pos, write_mask)
        kc, vc, table = inf.layer_pages(k_pool, v_pool, page_table, layer)  # the layer's pages, where they lie
        page_len = k_pool.shape[3]
        if T == 1:
            armed = _kernels.flash_decode_armed() if use_kernel is None else use_kernel
            fits = decode_paged_supported(B, H, page_table.shape[1], page_len, d)
            if trace_notes is not None:
                why_not = "" if armed and fits else ("kernel suite not armed" if not armed
                                                    else f"unsupported page geometry (page_len {page_len})")
                trace_notes.update(cca_decode_kernel=not why_not, cca_decode_fallback=why_not)
            o = inf.paged_cache_attention(q, kc, vc, table, pos, use_kernel=armed, work=work, trace_notes=trace_notes)
        else:
            o = inf.paged_chunk_attention(q, kc, vc, table, pos, use_kernel=use_kernel, trace_notes=trace_notes)
            if trace_notes is not None:
                trace_notes["cca_prefill_form"] = inf.chunk_attention_note(trace_notes)
    return o.transpose(0, 2, 1, 3).reshape(B, T, H * d), k_pool, v_pool, state
