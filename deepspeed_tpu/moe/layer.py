"""Mixture-of-experts with expert parallelism over the ``expert`` axis.

The reference snapshot (v0.4.5) predates DeepSpeed-MoE (landed v0.5,
``deepspeed/moe/layer.py`` upstream); this framework ships MoE
TPU-first from the start:

* **Static-shape capacity dispatch** (GShard-style): top-k gating
  produces dense ``(tokens, experts, capacity)`` dispatch/combine
  tensors; dispatch and combine are einsums that XLA lowers onto the
  MXU, and token→expert movement over the ``expert`` mesh axis becomes
  an XLA all-to-all inserted by GSPMD from the sharding constraints —
  no Python-side routing, no dynamic shapes.
* **Experts stacked on a leading dim** ``(E, ...)`` sharded
  ``P("expert", ...)`` so each expert-parallel rank owns ``E/ep``
  experts; compute is a single batched matmul over the local experts.
* **Load-balancing aux loss** (Switch/GShard): ``E * Σ_e mean_prob_e *
  frac_tokens_e``, returned to the caller to add to the task loss.

Functional API (params are plain pytrees, like the rest of the
framework): ``init_moe_params`` → ``moe_ffn(params, x)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.registry import register_op

EXPERT_AXIS = "expert"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    d_model: int
    d_ff: int
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    # router jitter noise (training only), as in Switch Transformer.
    # NB: the aux-loss *weight* is applied by the caller (moe_ffn returns
    # the unweighted load-balancing loss).
    router_jitter: float = 0.0


def init_moe_params(cfg: MoEConfig, rng: np.random.Generator, std: float = 0.02, proj_std: Optional[float] = None) -> Dict[str, Any]:
    """Expert FFN + router weights, experts stacked on dim 0."""
    if proj_std is None:
        proj_std = std
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "gate_w": (rng.standard_normal((D, E)) * std).astype(np.float32),
        "w1": (rng.standard_normal((E, D, F)) * std).astype(np.float32),
        "b1": np.zeros((E, F), np.float32),
        "w2": (rng.standard_normal((E, F, D)) * proj_std).astype(np.float32),
        "b2": np.zeros((E, D), np.float32),
    }


def moe_param_specs(layer_dim: bool = False, tp_axis: Optional[str] = None) -> Dict[str, P]:
    """PartitionSpecs for MoE weights: experts over ``expert``, and
    (optionally) the expert-FFN hidden dim over ``tp_axis`` (EP × TP).

    Back-compat re-export: the layout now lives in the partition-rule
    engine (:func:`deepspeed_tpu.sharding.rules.moe_param_specs`), which
    every engine resolves through."""
    from deepspeed_tpu.sharding.rules import moe_param_specs as _specs

    return _specs(layer_dim=layer_dim, tp_axis=tp_axis)


def _capacity(tokens: int, num_experts: int, factor: float, min_capacity: int) -> int:
    cap = int(np.ceil(tokens / num_experts * factor))
    return max(cap, min_capacity)


def top_k_gating(
    logits: jnp.ndarray,
    top_k: int,
    capacity: int,
    rng: Optional[jax.Array] = None,
    jitter: float = 0.0,
    token_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """GShard-style top-k gating with static capacity.

    ``logits``: (T, E) router scores for T tokens.  ``token_mask`` (T,)
    in {0,1} excludes padding tokens from dispatch, capacity, and the
    aux loss.
    Returns ``(dispatch (T,E,C) bool-ish, combine (T,E,C) float, aux_loss)``.
    """
    T, E = logits.shape
    if rng is not None and jitter > 0.0:
        logits = logits * jax.random.uniform(rng, logits.shape, minval=1.0 - jitter, maxval=1.0 + jitter)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    if token_mask is None:
        tmask = jnp.ones((T,), jnp.float32)
        n_real = float(T)
    else:
        tmask = token_mask.astype(jnp.float32)
        n_real = jnp.maximum(jnp.sum(tmask), 1.0)

    # Iteratively pick top-k choices per token, masking previous picks.
    masked = probs
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    # Track per-expert fill across the k rounds so capacity is shared.
    fill = jnp.zeros((E,), jnp.int32)
    frac_tokens = jnp.zeros((E,), jnp.float32)  # for aux loss (top-1 only per Switch)

    for r in range(top_k):
        idx = jnp.argmax(masked, axis=-1)  # (T,)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32) * tmask[:, None]  # (T, E); pads route nowhere
        gate = jnp.sum(probs * onehot, axis=-1)  # (T,)
        # position of each token within its chosen expert's buffer
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot) * onehot  # (T, E)
        pos = jnp.sum(pos_in_expert, axis=-1).astype(jnp.int32) + jnp.sum(onehot * fill[None, :], axis=-1).astype(jnp.int32)
        keep = pos < capacity
        gate = gate * keep
        pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity, dtype=jnp.float32)  # (T, C)
        sel = onehot * keep[:, None]  # (T, E)
        dispatch = dispatch + sel[:, :, None] * pos_oh[:, None, :]
        combine = combine + (gate[:, None] * sel)[:, :, None] * pos_oh[:, None, :]
        fill = fill + jnp.sum(sel, axis=0).astype(jnp.int32)
        if r == 0:
            frac_tokens = jnp.sum(onehot, axis=0) / n_real
        masked = masked * (1.0 - onehot)  # mask picked expert for next round

    mean_prob = jnp.sum(probs * tmask[:, None], axis=0) / n_real  # (E,)
    aux_loss = E * jnp.sum(mean_prob * frac_tokens)
    return dispatch, combine, aux_loss


def _expert_sharding(spec: P):
    """Best-effort NamedSharding from the engine's global mesh (None if
    no engine/mesh yet — then GSPMD is unconstrained, still correct)."""
    from deepspeed_tpu.parallel.sequence import get_global_mesh

    mesh = get_global_mesh()
    if mesh is None or EXPERT_AXIS not in mesh.axis_names:
        return None
    return NamedSharding(mesh, spec)


def moe_ffn(
    params: Dict[str, Any],
    x: jnp.ndarray,
    cfg: MoEConfig,
    rng: Optional[jax.Array] = None,
    training: bool = False,
    token_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE feed-forward over ``x (B, T, D)`` → ``(out (B, T, D), aux_loss)``.

    Expert weights ``params['w1'] (E, D, F)`` etc. may be sharded over
    the ``expert`` axis; dispatch/combine einsums trigger GSPMD
    all-to-alls between the token sharding (batch axes) and the expert
    sharding.  ``training`` selects capacity_factor (vs the laxer
    eval_capacity_factor) and enables router jitter; ``token_mask``
    (B, T) excludes padding from routing/capacity/aux.
    """
    B, T, D = x.shape
    tokens = B * T
    E = cfg.num_experts
    factor = cfg.capacity_factor if training else cfg.eval_capacity_factor
    C = _capacity(tokens, E, factor, cfg.min_capacity)

    xt = x.reshape(tokens, D)
    logits = xt.astype(jnp.float32) @ params["gate_w"].astype(jnp.float32)
    dispatch, combine, aux = top_k_gating(
        logits,
        cfg.top_k,
        C,
        rng=rng,
        jitter=cfg.router_jitter if training else 0.0,
        token_mask=token_mask.reshape(tokens) if token_mask is not None else None,
    )
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(jnp.float32)

    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)  # (E, C, D)
    sh = _expert_sharding(P(EXPERT_AXIS, None, None))
    if sh is not None:
        expert_in = jax.lax.with_sharding_constraint(expert_in, sh)
    h = jnp.einsum("ecd,edf->ecf", expert_in, params["w1"].astype(x.dtype)) + params["b1"][:, None, :].astype(x.dtype)
    h = jax.nn.gelu(h, approximate=True)
    out = jnp.einsum("ecf,efd->ecd", h, params["w2"].astype(x.dtype)) + params["b2"][:, None, :].astype(x.dtype)
    if sh is not None:
        out = jax.lax.with_sharding_constraint(out, sh)
    y = jnp.einsum("tec,ecd->td", combine, out.astype(jnp.float32))
    return y.reshape(B, T, D).astype(x.dtype), aux.astype(jnp.float32)


@register_op("moe", "xla", "GShard-style top-k MoE dispatch/combine (GSPMD all-to-all over expert axis)")
def _load_moe():
    return moe_ffn


MOE_PARAM_KEYS = ("gate_w", "w1", "b1", "w2", "b2")


def moe_ffn_from_block(lp: Dict[str, Any], h: jnp.ndarray, *, top_k: int = 2,
                       capacity_factor: float = 1.25, eval_capacity_factor: float = 2.0,
                       rng: Optional[jax.Array] = None, training: bool = False,
                       token_mask: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply a block's MoE FFN from its stacked layer params ``lp``
    (shapes determine num_experts/d_ff) — the ONE place the train block
    (models/gpt2.py) and the inference block (ops/transformer/inference)
    build their MoEConfig, so capacity semantics can't drift."""
    cfg = MoEConfig(
        num_experts=lp["gate_w"].shape[-1],
        d_model=h.shape[-1],
        d_ff=lp["w1"].shape[-1],
        top_k=top_k,
        capacity_factor=capacity_factor,
        eval_capacity_factor=eval_capacity_factor,
    )
    params = {k: lp[k] for k in MOE_PARAM_KEYS}
    return moe_ffn(params, h, cfg, rng=rng, training=training, token_mask=token_mask)


# ---------------------------------------------------------------------------
# dropless routing over the experts held here (DeepSeek-V2 style)
# ---------------------------------------------------------------------------

def group_limited_topk(probs: jnp.ndarray, n_group: int, topk_group: int, top_k: int,
                       scale: float = 1.0, renormalize: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Group-limited greedy routing: ``probs (N, E)`` are the router's
    softmax scores over **all** experts.  A group's score is its largest
    ``p``; the ``topk_group`` best groups are kept, the rest zeroed, and
    the ``top_k`` best experts of what is left are chosen.  Returns
    ``(idx (N, top_k) int32, weight (N, top_k) float32)`` with
    ``weight = scale * p`` (``renormalize`` divides by the chosen sum
    first)."""
    N, E = probs.shape
    group_score = probs.reshape(N, n_group, E // n_group).max(axis=-1)
    _, best = jax.lax.top_k(group_score, topk_group)
    keep = jnp.sum(jax.nn.one_hot(best, n_group, dtype=probs.dtype), axis=1) > 0  # (N, n_group)
    masked = jnp.where(jnp.repeat(keep, E // n_group, axis=1), probs, 0.0)
    w, idx = jax.lax.top_k(masked, top_k)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), (w * scale).astype(jnp.float32)


def softmax_topk(logits: jnp.ndarray, top_k: int, renormalize: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Softmax routing, one group (the Qwen3-MoE convention): ``logits (N,
    E)`` over **all** experts; ``p = softmax(logits)`` in float32, the
    ``top_k`` largest chosen, their weights divided by their sum
    (``renormalize``: ``norm_topk_prob``).  The same function as
    :func:`group_limited_topk` at one group with ``renormalize`` (the
    tests hold them equal), without the group bookkeeping.  Returns
    ``(idx (N, top_k) int32, weight (N, top_k) float32)``."""
    w, idx = jax.lax.top_k(jax.nn.softmax(logits.astype(jnp.float32), axis=-1), top_k)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w.astype(jnp.float32)


def sigmoid_topk(logits: jnp.ndarray, bias: Optional[jnp.ndarray], top_k: int, scale: float = 1.0,
                 renormalize: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sigmoid-scored routing (the DeepSeek-V3 / GLM-4-MoE convention,
    one group): ``logits (N, E)`` float32 over **all** experts; the score
    is ``s = sigmoid(logits)``, the ``top_k`` experts are chosen by ``s +
    bias`` (``bias (E,)``, a load-balancing correction that selects and
    never weighs; None = 0), and the weights are ``s`` at the chosen,
    divided by their sum (``renormalize``), times ``scale``.  Returns
    ``(idx (N, top_k) int32, weight (N, top_k) float32)``."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s if bias is None else s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), (w * scale).astype(jnp.float32)


def mlp_top1(h: jnp.ndarray, r_prev: jnp.ndarray, lp: Dict[str, Any], eps: float) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-1 routing by a small MLP on a **down-projection that is
    carried across layers** (the ZAYA router): ``h (N, D)`` the expert
    layer's normed input, ``r_prev (N, R)`` float32 the previous layer's
    ``r`` (zeros at the first)::

        r = h W_d + gamma * r_prev                        W_d: D -> R, gamma one learned scalar a layer
        s = softmax(gelu(gelu(RMS(r) W_1) W_2) W_3)       over **all** experts, float32
        e = argmax(s + bias),  weight = s_e               the bias selects and never weighs

    ``lp`` holds ``router_down (D, R)``, ``router_gamma ()``,
    ``router_norm (R,)``, ``router_w1``, ``router_w2 (R, R)``,
    ``router_w3 (R, E)`` and ``router_bias (E,)``.  Everything after
    ``h`` is float32 at ``highest`` precision: next to the experts these
    products are small, and a top-1 choice has no second expert to soften
    a flipped one.  Returns ``(idx (N, 1) int32, weight (N, 1) float32,
    r (N, R) float32)``; ``r`` goes to the next layer."""
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    w = lambda name: lp[name].astype(f32)  # noqa: E731
    dot = lambda a, b: jnp.dot(a, b, precision=hi)  # noqa: E731
    r = dot(h.astype(f32), w("router_down")) + w("router_gamma") * r_prev.astype(f32)
    x = r * jax.lax.rsqrt(jnp.mean(jnp.square(r), -1, keepdims=True) + eps) * w("router_norm")
    x = jax.nn.gelu(dot(x, w("router_w1")), approximate=False)
    x = jax.nn.gelu(dot(x, w("router_w2")), approximate=False)
    s = jax.nn.softmax(dot(x, w("router_w3")), axis=-1)
    idx = jnp.argmax(s + w("router_bias"), axis=-1)[:, None]
    return idx.astype(jnp.int32), jnp.take_along_axis(s, idx, axis=-1), r


def grouped_form(rows: int, D: int, F: int, dtype) -> Tuple[bool, str]:
    """Which form the two grouped matmuls of a held-expert call of
    ``rows`` assignment rows take: ``(kernel, why_not)`` — the Mosaic
    kernel ``moe_grouped_matmul`` when the suite is armed, the trace
    targets one device and the kernel serves both shapes
    (``(rows, D) x (D, 2F)`` and ``(rows, F) x (F, D)``), else
    ``jax.lax.ragged_dot`` and the reason; one line of the log for each
    distinct answer.  No row threshold: on the chip the kernel is ahead
    at a decode step's 192 rows as at a chunk's 3,072 (docs/kernels.md)."""
    from deepspeed_tpu.ops import kernels as _kernels
    from deepspeed_tpu.ops.kernels.grouped_matmul import grouped_matmul_supported
    from deepspeed_tpu.ops.kernels.sharded import free_mesh_axes
    from deepspeed_tpu.utils.device import pallas_interpret_default

    if not _kernels.grouped_matmul_armed():
        why_not = "kernel suite not armed"
    elif not pallas_interpret_default() and any(n > 1 for n in free_mesh_axes().values()):
        why_not = "traced for a multi-device mesh"
    elif not (grouped_matmul_supported(rows, D, 2 * F, dtype) and grouped_matmul_supported(rows, F, D, dtype)):
        why_not = f"unsupported shape ({rows} rows, widths {D} / {F}, {jnp.dtype(dtype).name})"
    else:
        why_not = ""
    _kernels.warn_once(("moe_grouped_matmul", rows, D, F, why_not),
                       f"kernels: a held-expert call of {rows} assignment rows (widths {D} / {F}) takes "
                       + (f"jax.lax.ragged_dot: {why_not}" if why_not else "moe_grouped_matmul"), level="info")
    return not why_not, why_not


def note_grouped_form(trace_notes: dict, rows: int, why_not: str) -> None:
    """Leave in a family's ``trace_notes`` which form a held-expert call
    of ``rows`` assignment rows took.  Both keys are strings (a serve
    record keeps scalars and strings of ``stats()``):
    ``moe_grouped_kernel`` the row counts on the kernel, ascending,
    comma-separated; ``moe_grouped_fallback`` ``"<rows>: <reason>"`` for
    the others, ``"; "`` between."""
    on = {int(r) for r in trace_notes.get("moe_grouped_kernel", "").split(",") if r}
    off = dict(e.split(": ", 1) for e in trace_notes.get("moe_grouped_fallback", "").split("; ") if e)
    on.discard(rows)
    off.pop(str(rows), None)
    if why_not:
        off[str(rows)] = why_not
    else:
        on.add(rows)
    trace_notes["moe_grouped_kernel"] = ",".join(str(r) for r in sorted(on))
    trace_notes["moe_grouped_fallback"] = "; ".join(f"{r}: {off[r]}" for r in sorted(off, key=int))


def swiglu_gate(g: jnp.ndarray, u: jnp.ndarray, limit: Optional[float] = None) -> jnp.ndarray:
    """``silu(g) * u``, or — ``limit`` given, the **clamped** SwiGLU —
    ``silu(min(g, limit)) * clip(u, -limit, limit)``: the gate bounded
    above (``silu`` bounds it below by itself), the linear half on both
    sides."""
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return jax.nn.silu(g) * u


def dropless_held_experts(x: jnp.ndarray, idx: jnp.ndarray, weight: jnp.ndarray, w_gu: jnp.ndarray,
                          w_down: jnp.ndarray, held: Tuple[int, int], valid: Optional[jnp.ndarray] = None,
                          trace_notes: Optional[dict] = None,
                          swiglu_limit: Optional[float] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The part of a routed-expert layer that the experts **held here**
    give: ``sum_{e in chosen, first <= e < first + count} w_e E_e(x)``
    with ``E_e(x) = (silu(x W_gate,e) * x W_up,e) W_down,e``
    (``swiglu_limit``: the clamped form, :func:`swiglu_gate`).

    No capacity and no dropped assignment: every (token, expert) pair
    whose expert is held is computed.  Static shapes: the ``N * top_k``
    assignments are sorted by expert (those of absent experts last), the
    held ones go through two grouped matmuls (group sizes traced), and
    each token sums its own rows back.  On one chip there is no
    exchange; over an ``expert`` mesh axis this is what each rank
    computes between the two all-to-alls.

    The grouped matmuls have two forms, chosen by :func:`grouped_form`
    from what the trace can see: the Mosaic kernel
    ``ops/kernels/grouped_matmul.py`` (each touched expert's weights
    streamed once; the chip) and ``jax.lax.ragged_dot`` (the CPU,
    unsupported shapes, a multi-device trace; the kernel's reference).
    Same rows, same arithmetic: operands in their dtype, float32
    products.  ``trace_notes``, a dict, is told while tracing which row
    counts took which (``moe_grouped_kernel``, ``moe_grouped_fallback``:
    ``ServingEngine.stats()``, docs/telemetry.md).

    ``x (N, D)``; ``idx``/``weight (N, K)`` from the router over all
    experts; ``w_gu (count, D, 2F)`` (gate columns first), ``w_down
    (count, F, D)``.  ``valid (N,)`` marks the real tokens for the
    counters only.  Returns ``(out (N, D), counts (count + 1,) int32)``:
    per held expert the real tokens computed for it, and last the real
    assignments the router sent to held experts — equal to their sum
    unless something drops.
    """
    first, count = held
    N, K = idx.shape
    local = idx - first
    is_held = (local >= 0) & (local < count)
    key = jnp.where(is_held, local, count).reshape(N * K)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
    xs = jnp.take(x, order // K, axis=0)
    from deepspeed_tpu.ops.kernels.grouped_matmul import WINDOW, grouped_matmul

    # fewer assignment rows than one MXU window (top-1 over a small batch): the kernel is asked for a whole
    # window, the rows past the real ones belonging to no group, as those of absent experts do
    rows = max(N * K, WINDOW)
    kernel, why_not = grouped_form(rows, x.shape[1], w_down.shape[1], x.dtype)
    if trace_notes is not None:
        note_grouped_form(trace_notes, N * K, why_not)
    if kernel:
        grouped, xs = grouped_matmul, jnp.pad(xs, ((0, rows - N * K), (0, 0)))
    else:
        grouped = jax.lax.ragged_dot
    gu = grouped(xs, w_gu.astype(x.dtype), sizes)
    g, u = jnp.split(gu, 2, axis=-1)
    ys = grouped(swiglu_gate(g, u, swiglu_limit), w_down.astype(x.dtype), sizes)[: N * K]
    # rows past the held groups belong to absent experts: nothing was computed for them
    computed = jnp.arange(N * K) < jnp.sum(sizes)
    ws = jnp.take(jnp.where(is_held, weight, 0.0).reshape(N * K), order)
    ys = jnp.where(computed[:, None], ys.astype(jnp.float32) * ws[:, None], 0.0)
    out = jnp.take(ys, jnp.argsort(order), axis=0).reshape(N, K, -1).sum(axis=1)
    # counters, over the real tokens: the rows the grouped matmuls computed per expert (read off the
    # group keys), and what the router sent to held experts (read off its own choice) — equal unless a
    # later change drops assignments between the two
    real = jnp.ones((N,), bool) if valid is None else valid.astype(bool)
    per_expert = jnp.bincount(key, weights=jnp.repeat(real, K).astype(jnp.int32), length=count + 1)[:count].astype(jnp.int32)
    routed = jnp.sum((is_held & real[:, None]).astype(jnp.int32))
    counts = jnp.concatenate([per_expert, routed[None]])
    return out.astype(x.dtype), counts
