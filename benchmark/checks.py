"""The comparisons that decide ``correct``: the program's outputs
against :mod:`reference_gpt2`, outside the measured window.

Each function returns the numbers compared; the runner sets them beside
the limits its configuration file states (``checks`` block — read from
the chip as PERF.md §2 records: the largest value sound runs gave, the
smallest the lower-precision control gave, the limit between them).

Training (three numbers):

* ``loss_abs_err`` — the loss ``train_batch`` returns for its first
  step (the engine's own path: bf16, kernels, remat, chunked
  cross-entropy) against the reference's mean next-token NLL on the
  same sequences at the same seeded weights;
* ``logprob_rms_err`` — per-position NLL out of the same loss
  function the engine is handed (``make_model``'s: the engine's compute
  type, flash attention, remat, chunked cross-entropy; each position's
  term is read off the scalar loss through its gradient in the mask, see
  :func:`program_nll`) against the reference's, root-mean-square over
  all positions: the number a lower-precision forward moves;
* ``update_disagreement`` — backward, update and ZeRO partitioning:
  Adam's first step moves every weight by ``lr`` against the sign of
  its gradient, so with ``g`` the *reference's* float32 gradient and
  ``dp`` what the engine's step did to the seeded weights,
  ``1 - sum(-dp * g) / (lr * sum|g|)`` over every block weight matrix
  of every layer is 0 for a faultless backward and 1 for a random one.

Serving (one number, and one shown):

* ``token_gap_mean`` — for a seeded sample of served requests, one
  reference forward over prompt + generated tokens; at each generated
  position the reference's largest logit minus its logit of the token
  the engine emitted (0 when they agree; logits, not token equality,
  because with random weights the top two logits are a rounding error
  apart), averaged over the sample's tokens.  ``token_gap_max`` is
  printed beside it.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .reference_gpt2 import BLOCK_MATRICES, Reference


def check_sequences(seed: int, vocab: int, seq: int, n: int = 2) -> np.ndarray:
    return np.random.default_rng([int(seed), 4]).integers(0, vocab, (n, seq), dtype=np.int32)


def program_nll(loss_fn, params, tokens) -> np.ndarray:
    """Per-position NLL ``(B, T-1)`` out of the program's own **loss
    path** — ``loss_fn(params, batch, rng)`` is the function
    ``deepspeed_tpu.initialize`` is handed (bf16, kernels, remat, chunked
    cross-entropy) and returns one scalar, the masked mean
    ``L = sum(nll * m) / sum(m)``.  Its gradient in the mask at ``m = 1``
    is ``(nll_i - L) / N``, so ``nll_i = N * dL/dm_i + L``: every
    position's term, read without touching the program."""
    tokens = jnp.asarray(tokens, jnp.int32)

    @jax.jit
    def f(p, t):
        ones = jnp.ones(t.shape, jnp.float32)
        loss, dm = jax.value_and_grad(lambda m: loss_fn(p, {"input_ids": t, "attention_mask": m}, None))(ones)
        return (t.shape[0] * (t.shape[1] - 1)) * dm[:, 1:] + loss  # the mask indexes the label position

    return np.asarray(f(params, tokens))


def natural(leaf, shape):
    """An engine state leaf in its natural shape (ZeRO's flat fallback
    pads and flattens leaves whose dims the mesh does not divide)."""
    if tuple(leaf.shape) == tuple(shape):
        return leaf
    return leaf.reshape(-1)[: int(np.prod(shape))].reshape(shape)


@jax.jit
def _agreement_terms(after: Dict[str, Any], init: Dict[str, Any], grads: Dict[str, Any]):
    num = sum(jnp.sum((init[n] - after[n].astype(jnp.float32)) * grads[n]) for n in BLOCK_MATRICES)
    den = sum(jnp.sum(jnp.abs(grads[n])) for n in BLOCK_MATRICES)
    return num, den


def update_disagreement(ref: Reference, grad_sweep, after_blocks: Dict[str, Any], lr: float) -> float:
    """``after_blocks[name]`` is the engine's stacked ``(L, …)`` leaf after
    its first step (a host copy will do: one layer at a time goes to the
    reference's device); ``grad_sweep`` yields the reference's gradients."""
    num = den = 0.0
    for l, grads in grad_sweep:
        init = ref.layer_init(l)
        after = {n: jnp.asarray(natural(after_blocks[n], (ref.dims["n_layer"],) + init[n].shape)[l])
                 for n in BLOCK_MATRICES}
        a, b = _agreement_terms(after, {n: init[n] for n in BLOCK_MATRICES}, grads)
        num += float(a)
        den += float(b)
    return 1.0 - num / (lr * den)


def position_gaps(ref: Reference, context: np.ndarray, n_prompt: int, chosen: np.ndarray, pad_to: int) -> List[float]:
    """For the ``len(context) - n_prompt`` generated positions of one
    sequence: the reference's largest logit minus its logit of the token
    ``chosen`` there.  The sequence is padded to ``pad_to`` (causal
    attention: the padding cannot reach an earlier position), so one
    program serves every length."""
    n = len(context)
    padded = np.zeros((1, pad_to), np.int32)
    padded[0, :n] = context
    rows = ref.logits(padded)[0, n_prompt - 1 : n - 1]  # the positions that predicted each generated token
    picked = jnp.take_along_axis(rows, jnp.asarray(chosen, jnp.int32)[:, None], axis=-1)[:, 0]
    return [float(g) for g in np.asarray(jnp.max(rows, axis=-1) - picked)]


def gap_summary(gaps: List[float]) -> Dict[str, Any]:
    return {"token_gap_mean": float(np.mean(gaps)), "token_gap_max": float(np.max(gaps)), "tokens": len(gaps)}


def token_gaps(ref: Reference, served: Sequence[Dict[str, Any]], pad_to: int) -> Dict[str, Any]:
    """``served``: ``{"prompt": ids, "generated": ids}`` each; the chosen
    tokens are the ones the engine emitted."""
    gaps: List[float] = []
    for r in served:
        context = np.concatenate([np.asarray(r["prompt"], np.int32), np.asarray(r["generated"], np.int32)])
        gaps += position_gaps(ref, context, len(r["prompt"]), context[len(r["prompt"]):], pad_to)
    return gap_summary(gaps)
