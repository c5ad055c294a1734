"""Kimi Delta Attention (KDA, arXiv:2510.26692): a linear-attention
layer whose cache is a **fixed-size recurrent state per sequence**, not
keys and values per position.

Per head, with ``S (dk, dv)`` float32, normalised ``q, k (dk)``,
``v (dv)``, per-channel log-decay ``g <= 0 (dk)`` and ``beta`` in
``[0, 2]``::

    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The **gated delta rule** (Gated DeltaNet, arXiv:2412.06464) is the same
recurrence with the decay **one scalar a head** — ``diag(exp g)`` is
``exp(g) I`` — and all three forms below serve it with ``g`` given one
number wide (``g (..., H, 1)``: it broadcasts over ``dk``);
:func:`chunked` then takes the decay out of the key products (``k k^T``
and ``q k^T`` are matrix products, scaled by ``exp(G_t - G_s)``
afterwards) in place of a ``(C, C, dk)`` tensor a head.  Where fewer
query / key heads feed more value heads (``Hk < H``: value head ``h``
reads query / key head ``h // (H / Hk)``), the caller repeats them
(:func:`share_heads`); :func:`decode_step` takes them as they are.

Three forms of the one function:

* :func:`recurrent_step` — the recurrence as written, in ``jnp``: the
  numerics ground truth, and what a decode step runs off the chip;
* :func:`decode_step` — a decode step on the serving pool's per-slot
  state ``(layers, slots, H, dk, dv)``: the Mosaic kernel
  ``ops/kernels/kda_decode.py`` in place where the suite is armed and
  the shapes fit, :func:`recurrent_step` otherwise; rows whose
  ``write_mask`` is False are neither read nor written;
* :func:`chunked` — a prefill chunk of ``T`` tokens from a carried state
  (the WY / UT transform within chunks of 64, the state carried across):
  with ``G_t`` the running sum of ``g`` inside a chunk and
  ``A_ts = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)`` (``s < t``),
  ``U = (I + A)^-1 (beta V - beta (K exp G) S_0)``,
  ``O = (Q exp G) S_0 + B U`` with ``B_ts = sum_c q_tc k_sc exp(G_tc -
  G_sc)`` (``s <= t``), ``S_C = diag(exp G_C) S_0 + (K exp(G_C - G))^T U``.
  Every exponent is a difference ``G_t - G_s`` with ``s <= t``, or a ``G``
  itself: none is positive.  Plain ``jnp`` in float32 at ``highest``
  matmul precision — next to the layer's projections these products are
  small.  A token with ``beta = 0`` and ``g = 0`` (a chunk's padded
  tail) leaves the state as it was.

:func:`short_conv` is the causal depthwise convolution (+ SiLU) in front
of ``q``, ``k`` and ``v``, with its own carried state: the last
``kernel - 1`` inputs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
CHUNK = 64


def l2norm(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def short_conv(x, w, state, n_valid=None):
    """Causal depthwise convolution then SiLU: ``x (B, T, C)``, ``w (K,
    C)`` (``w[K - 1]`` multiplies the current input), ``state (B, K - 1,
    C)`` the inputs before ``x``.  Returns ``(y (B, T, C) float32, new
    state)``: the last ``K - 1`` inputs, or — ``n_valid (B,)`` given —
    the ``K - 1`` inputs that end at row ``b``'s ``n_valid[b]``-th token
    (a chunk's padded tail is not remembered)."""
    K, T = w.shape[0], x.shape[1]
    ext = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # input t of the chunk sits at t + K - 1
    y = sum(ext[:, j:j + T].astype(jnp.float32) * w[j].astype(jnp.float32) for j in range(K))
    if n_valid is None:
        new = ext[:, T:]
    else:
        new = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, K - 1, axis=0))(ext, n_valid)
    return jax.nn.silu(y), new.astype(state.dtype)


def share_heads(t, H: int, axis: int):
    """``t`` with ``Hk`` query / key heads along ``axis`` as ``H`` of
    them: head ``h`` of the result is head ``h // (H / Hk)`` of ``t``."""
    Hk = t.shape[axis]
    if H % Hk:
        raise ValueError(f"{H} value heads are not whole groups of the {Hk} query / key heads")
    return t if Hk == H else jnp.repeat(t, H // Hk, axis=axis)


def recurrent_step(S, q, k, v, g, beta):
    """One token: ``S (..., H, dk, dv)``, ``q, k (..., H, dk)``, ``g
    (..., H, dk)`` or ``(..., H, 1)`` (a scalar decay a head), ``v (...,
    H, dv)``, ``beta (..., H)``, all float32.  Returns ``(o (..., H,
    dv), S)``."""
    S = S * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.einsum("...hk,...hkv->...hv", k, S, precision=_HI))
    S = S + k[..., None] * u[..., None, :]
    return jnp.einsum("...hk,...hkv->...hv", q, S, precision=_HI), S


def decode_form(use_kernel: Optional[bool], H: int, dk: int, dv: int, dtype=jnp.float32,
                kernel_name: str = "kda_decode") -> Tuple[bool, str]:
    """Which form a decode step on a state of these shapes and ``dtype``
    takes: ``(kernel, why_not)``; one line of the log for each distinct
    answer.  ``kernel_name`` is the program the kernel form would run
    (``kda_decode``, or ``gdn_decode`` for a scalar decay a head)."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.kernels.kda_decode import kda_decode_supported
    from deepspeed_tpu.ops.kernels.sharded import free_mesh_axes
    from deepspeed_tpu.utils.device import pallas_interpret_default

    if use_kernel is None:
        use_kernel = _kernels.flash_decode_armed()
    if not use_kernel:
        why_not = "kernel suite not armed"
    elif not pallas_interpret_default() and any(n > 1 for n in free_mesh_axes().values()):
        why_not = "traced for a multi-device mesh"
    elif not kda_decode_supported(H, dk, dv):
        why_not = f"unsupported shape (H {H}, dk {dk}, dv {dv})"
    elif jnp.dtype(dtype) != jnp.float32:
        why_not = f"the state is {jnp.dtype(dtype).name}, the kernel's is float32"
    else:
        why_not = ""
    what = "KDA" if kernel_name == "kda_decode" else "gated-delta-rule"
    _kernels.warn_once((kernel_name, H, dk, dv, why_not),
                       f"kernels: a {what} decode step (H {H}, state {dk} x {dv}) takes "
                       + (f"the jnp recurrence: {why_not}" if why_not else kernel_name), level="info")
    return not why_not, why_not


def decode_step(state, layer: int, q, k, v, g, beta, write_mask=None, use_kernel: Optional[bool] = None,
                trace_notes: Optional[dict] = None):
    """A decode step on the pool's state ``(layers, B, H, dk, dv)`` at
    the static ``layer``: ``q, k, g (B, H, dk)``, ``v (B, H, dv)``,
    ``beta (B, H)`` — or, the gated delta rule, ``g (B, H)`` **one
    scalar a head** and ``q, k (B, Hk, dk)`` shared by ``H / Hk`` value
    heads.  Returns ``(o (B, H, dv) float32, state)``; rows with
    ``write_mask`` False read 0 and keep their state.  ``trace_notes`` is
    told which form (``kda_decode_kernel``, ``kda_decode_fallback``;
    ``gdn_*`` for the scalar decay)."""
    _, B, H, dk, dv = state.shape
    scalar = g.ndim == 2
    name = "gdn" if scalar else "kda"
    mask = jnp.ones((B,), bool) if write_mask is None else write_mask.astype(bool)
    kernel, why_not = decode_form(use_kernel, H, dk, dv, state.dtype, f"{name}_decode")
    if trace_notes is not None:
        trace_notes.update({f"{name}_decode_kernel": kernel, f"{name}_decode_fallback": why_not})
    if kernel:
        from deepspeed_tpu.ops.kernels.kda_decode import gdn_decode, kda_decode

        return (gdn_decode if scalar else kda_decode)(state, layer, q, k, v, g, beta, mask)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    if scalar:
        q, k, g = share_heads(q, H, 1), share_heads(k, H, 1), g[..., None]
    o, S = recurrent_step(state[layer], f32(q), f32(k), f32(v), f32(g), f32(beta))
    S = jnp.where(mask[:, None, None, None], S, state[layer]).astype(state.dtype)
    return jnp.where(mask[:, None, None], o, 0.0), state.at[layer].set(S)


def chunked(S0, q, k, v, g, beta, chunk: int = CHUNK):
    """``T`` tokens from the carried state: ``S0 (B, H, dk, dv)``,
    ``q, k (B, T, H, dk)``, ``g (B, T, H, dk)`` or ``(B, T, H, 1)`` (a
    scalar decay a head), ``v (B, T, H, dv)``, ``beta (B, T, H)``.
    Returns ``(o (B, T, H, dv) float32, S_T)``.  ``T`` is cut into
    chunks of ``chunk`` (a shorter ``T`` is one chunk)."""
    B, T, H, dk = q.shape
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"chunked KDA: {T} tokens are not whole chunks of {C}")
    N = T // C
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    # (N, B, H, C, d): the scan walks the chunks, a head's chunk is a matrix
    split = lambda t: f32(t).reshape(B, N, C, H, -1).transpose(1, 0, 3, 2, 4)  # noqa: E731
    qs, ks, vs, gs = split(q), split(k), split(v), split(g)
    bs = f32(beta).reshape(B, N, C, H).transpose(1, 0, 3, 2)  # (N, B, H, C)
    t_idx = jnp.arange(C)
    lower = t_idx[:, None] >= t_idx[None, :]   # s <= t
    strict = t_idx[:, None] > t_idx[None, :]
    eye = jnp.eye(C, dtype=jnp.float32)

    def one_chunk(S, xs):
        qc, kc, vc, gc, bc = xs
        G = jnp.cumsum(gc, axis=-2)                                       # (B, H, C, dk) or (B, H, C, 1), <= 0
        if G.shape[-1] == 1 and dk > 1:
            # a scalar decay a head leaves the channel sum: two matrix products, scaled afterwards
            E = jnp.exp(jnp.where(lower, G[..., :, None, 0] - G[..., None, :, 0], -jnp.inf))  # (B, H, C, C)
            kk = jnp.einsum("bhtc,bhsc->bhts", kc, kc, precision=_HI) * E
            qk = jnp.einsum("bhtc,bhsc->bhts", qc, kc, precision=_HI) * E
        else:
            diff = G[..., :, None, :] - G[..., None, :, :]                # (B, H, C, C, dk): G_t - G_s
            E = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))      # 0 above the diagonal
            kk = jnp.einsum("bhtc,bhtsc->bhts", kc, kc[..., None, :, :] * E, precision=_HI)
            qk = jnp.einsum("bhtc,bhtsc->bhts", qc, kc[..., None, :, :] * E, precision=_HI)
        A = jnp.where(strict, bc[..., None] * kk, 0.0)
        expG = jnp.exp(G)
        rhs = jnp.concatenate([bc[..., None] * vc, bc[..., None] * kc * expG], axis=-1)  # (B, H, C, dv + dk)
        sol = jax.scipy.linalg.solve_triangular(eye + A, rhs, lower=True, unit_diagonal=True)
        dv = vc.shape[-1]
        U = sol[..., :dv] - jnp.einsum("bhtk,bhkv->bhtv", sol[..., dv:], S, precision=_HI)
        o = (jnp.einsum("bhtk,bhkv->bhtv", qc * expG, S, precision=_HI)
             + jnp.einsum("bhts,bhsv->bhtv", jnp.where(lower, qk, 0.0), U, precision=_HI))
        to_end = jnp.exp(G[..., -1:, :] - G)                              # (B, H, C, dk), <= 1
        S = expG[..., -1, :, None] * S + jnp.einsum("bhsk,bhsv->bhkv", kc * to_end, U, precision=_HI)
        return S, o

    S, o = jax.lax.scan(one_chunk, f32(S0), (qs, ks, vs, gs, bs))
    return o.transpose(1, 0, 3, 2, 4).reshape(B, T, H, -1), S
