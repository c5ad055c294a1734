#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that deepspeed_tpu still starts on the chip.

One process that owns every chip JAX finds drives the two main paths
through the entry points a user calls, at published widths with random
weights made from a seed:

* trainer — ``deepspeed_tpu.initialize`` → ``engine.train_batch``:
  GPT-2 Medium, sequence 1024, bf16, ZeRO stage 3 over
  ``{"fsdp": -1, "data": 1}``, 16 sequences a step (4 × gas 4 on one
  chip, 4 × gas 1 on four) under the repo's ``774M-zero3`` remat recipe;
* a second family on the same server — DeepSeek-V2 at a tiny size
  (latent attention on the paged latent pool, dropless routing over the
  experts held here) against ``benchmark/reference_deepseek_v2.py``;
* a third family — Solar-Open2 at a small size on the hybrid cache (K/V
  pages + per-slot recurrent state; ``kda_decode`` and the grouped
  ``flash_decode_paged`` in its decode program)
  against ``benchmark/reference_solar_open2.py``;
* a fifth family — Keye's language model at a small size on the cache
  kind with a third leaf (K, V and an indexer key a position;
  ``dsa_index_scores_paged`` and ``dsa_sparse_decode`` in its decode
  program: attention over the positions the indexer selects), the
  masked decode kernel first held against the lax form at Keye's tile
  (32 / 4 heads, four pages a grid step),
  against ``benchmark/reference_keye.py``;
* a sixth family — GigaChat3.5 at a small size on the hybrid cache over
  **latent** pages (a latent buffer + per-slot recurrent state;
  ``gdn_decode`` and ``mla_decode_paged`` in its decode program,
  ``mla_prefill`` in its prefill program)
  against ``benchmark/reference_gigachat35.py``;
* a seventh family — Laguna at a small size on two page groups in one
  pool (pages by length for the full-attention layers, a ring of pages a
  slot for the window layers; ``flash_decode_paged`` and, for the window
  layers, ``swa_decode_paged`` in its decode program), the window kernel
  first held against its ``jnp`` form on a lapped ring at 72 / 8 heads,
  against ``benchmark/reference_laguna.py``;
* server — ``deepspeed_tpu.init_inference("gpt2-xl")`` → ``ServingEngine``
  on the paged pool (8 slots, ``page_len`` 128), a bf16 then an int8 KV
  pool, eight seeded greedy requests each;
* every Pallas kernel those paths arm, once, against the repo's own
  lax/XLA ground truth at the same shapes — ``flash_chunk_paged``, a
  prefill chunk's attention over its pages, at Keye's chunk (32 / 4
  heads, 2,048 queries, under a top-2,048 selection and without) and
  Laguna's (48 / 8, 1,024); it must stand in the prefill programs of the
  Solar-Open2, Keye and Laguna cases and be absent from GPT-2's, whose
  heads are narrower than the lanes (``stats()`` say why).

It fails (non-zero exit, no result line) when JAX finds no TPU, when a
phase raises, or when a check does not hold; no phase is wrapped in
``try``.  Wall times it prints are smoke observations, not measurements.
The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Run it from the root of a checkout: ``python3 chip_smoke.py`` (or
``bin/deepspeed chip_smoke.py``).  The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import math
import re
import sys
import time
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.utils.device import setup_compile_cache


@dataclasses.dataclass(frozen=True)
class Smoke:
    """The sizes of one run.  ``FULL`` is what the chip runs; the CPU test
    drives the same phases at toy sizes with ``mosaic=False``."""

    train_cfg: gpt2.GPT2Config
    seq: int
    micro: int              # sequences per device per micro-batch
    global_batch: int       # sequences per optimizer step, whatever the device count
    steps: int
    serve_model: str        # an init_inference preset name
    slots: int
    max_len: int            # positions per slot (prompt + generated)
    page_len: int
    prefill_chunk: int
    prompt_lens: Tuple[int, int]
    new_tokens: int
    requests: int
    # one more leaf for the fused update's check, of the shape class the
    # benchmark's one-chip train cell holds (GPT-2 Large's fc_w, two layers)
    update_leaf: Tuple[int, ...] = (2, 1280, 5120)
    # True: the Pallas kernels are compiled by Mosaic and must show in
    # the optimized HLO.  False (CPU control-flow test): the kernel suite
    # is not armed and no executable may hold a Mosaic call.
    mosaic: bool = True
    seed: int = 0


# GPT-2 Large with the repo's 774M-zero3 recipe does not leave
# room for the fp32 gradient accumulator that gas > 1 adds on one chip:
# compiled for a v5e it wants 17.86 GB of 15.75 GB (PERF.md, "what stopped
# the program").  The next preset down keeps every published width.
_TRAIN_CFG = dataclasses.replace(
    gpt2.GPT2_MEDIUM, remat=True, xent_chunk_size=512,
    remat_save_names=("qkv", "ffn_pre", "attn_o", "attn_lse"),
)
FULL = Smoke(
    train_cfg=_TRAIN_CFG, seq=1024, micro=4, global_batch=16, steps=3,
    serve_model="gpt2-xl", slots=8, max_len=512, page_len=128, prefill_chunk=64,
    prompt_lens=(32, 384), new_tokens=32, requests=8,
)

# bf16 operands; the kernel rounds the softmax weights to bf16 before
# the PV dot (the MXU's native rate) where the f32 reference does not —
# the bound tests/test_flash_attention.py::test_bf16_forward_close uses.
TOL_BF16 = 3e-2
# f32 math on both sides, but the dots accumulate in another order and
# the int8 scales fold in at another point.
TOL_F32 = 2e-3
# elementwise f32 optimizer math, reassociated (m + keep·(…) vs b1·m + …)
# under two compilers whose divide and sqrt are not correctly rounded,
# measured on what the steps moved the parameters by: the parameters'
# own ulp (7e-9 at |p| 0.1) is 2e-5 of LAMB's two-step move.  Relative
# to each tensor's largest entry.
TOL_UPDATE = 1e-4
# one chip vs all of them: the same 16 sequences, summed in another
# order (per-device partial sums, bf16 matmuls split over the mesh).
# Four v5e chips differed from one by 1e-5 over three steps (PERF.md);
# a hundred times that.
TOL_LOSS_ACROSS_MESHES = 1e-3


_T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:6.1f}s] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


# ---------------------------------------------------------------------------
# optimized-HLO reading
# ---------------------------------------------------------------------------

_MOSAIC_RE = re.compile(r'%([A-Za-z_]\w*?)(?:\.\d+)* = (\S+)[^\n]*custom_call_target="tpu_custom_call"')
_GATHER_RE = re.compile(r"= (.*?) all-gather(?:-start)?\(")
_SHAPE_RE = re.compile(r"(bf16|f16|f32)\[([\d,]+)\]")


def mosaic_kernels(hlo: str) -> Dict[str, int]:
    """Kernel name (the ``pallas_call``'s ``name=``) → number of Mosaic
    custom calls in an executable's optimized HLO."""
    return dict(collections.Counter(m.group(1) for m in _MOSAIC_RE.finditer(hlo)))


def first_output_dims(hlo: str, kernel: str) -> Tuple[int, ...]:
    for m in _MOSAIC_RE.finditer(hlo):
        if m.group(1) == kernel:
            return tuple(int(d) for d in _SHAPE_RE.search(m.group(2)).group(2).split(","))
    raise AssertionError(f"chip_smoke: no Mosaic call named {kernel!r} in the executable")


def gathered_float_shapes(hlo: str) -> List[Tuple[int, ...]]:
    out = []
    for m in _GATHER_RE.finditer(hlo):
        out += [tuple(int(d) for d in dims.split(",")) for _, dims in _SHAPE_RE.findall(m.group(1))]
    return out


_INSTR_RE = re.compile(r"^\s*(?:ROOT )?%?\S+ = (.*?)\s([a-z][a-z\-]*)\(", re.M)
_LEAF_RE = re.compile(r"(bf16|f16|f32|s8)\[([\d,]+)\]")  # a leaf may be int8 codes
# what may hold an array without moving it
_NO_MOVE = ("parameter", "get-tuple-element", "bitcast", "tuple")


def leaf_sized_moves(hlo: str, elems: int) -> List[str]:
    """Opcodes of the instructions, anywhere in an optimized HLO module,
    that produce an array of ``elems`` elements and are neither a Mosaic
    call nor free: each is one more pass over a leaf of that size (a
    ``copy``, ``reshape``, ``transpose`` or fusion round a kernel that
    was handed a view its buffers do not have)."""
    out = []
    for m in _INSTR_RE.finditer(hlo):
        sizes = [math.prod(int(d) for d in dims.split(",")) for _, dims in _LEAF_RE.findall(m.group(1))]
        line = hlo[m.start():hlo.find("\n", m.end())]
        if elems in sizes and m.group(2) not in _NO_MOVE and "tpu_custom_call" not in line:
            out.append(m.group(2))
    return out


# the Mosaic calls an executable of the fused update must hold, by optimizer
UPDATE_KERNELS = {"adam": ["fused_adam"], "lamb": ["fused_lamb_dir", "fused_lamb_apply"]}


def expect_kernels(found: Dict[str, int], expected: Sequence[str], where: str) -> None:
    """A shape-based dispatch to a lax path must be visible, not silent:
    the executable holds exactly the kernels this path is known to arm."""
    say(f"{where}: Mosaic kernels {found or '{}'}")
    check(sorted(found) == sorted(expected),
          f"{where}: expected Mosaic kernels {sorted(expected)}, executable holds {sorted(found)}")


# ---------------------------------------------------------------------------
# phase 1: each kernel against its ground truth
# ---------------------------------------------------------------------------

def _max_err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(1.0, float(np.max(np.abs(b)))))


def check_flash_attention(s: Smoke) -> Dict[str, float]:
    """flash fwd + bwd vs ``mha_reference`` on a sample of heads at the
    trainer's per-device shape."""
    from deepspeed_tpu.ops.attention.flash_attention import flash_attention, mha_reference

    heads = min(4, s.train_cfg.n_head)
    shape = (s.micro, heads, s.seq, s.train_cfg.head_dim)
    keys = jax.random.split(jax.random.PRNGKey(s.seed), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16) for kk in keys)

    def run(attn):
        def fwd_bwd(q, k, v, g):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, causal=True), q, k, v)
            return (out, *vjp(g))

        return jax.jit(fwd_bwd)(q, k, v, g)

    got = run(flash_attention)
    with jax.default_matmul_precision("highest"):
        want = run(mha_reference)
    errs = {n: _max_err(a, b) for n, a, b in zip(("out", "dq", "dk", "dv"), got, want)}
    say(f"flash_attention vs mha_reference {shape}: " + " ".join(f"{n}={e:.2e}" for n, e in errs.items()))
    check(max(errs.values()) < TOL_BF16, f"flash attention off its reference by {errs} (tolerance {TOL_BF16})")
    return errs


def check_flash_decode_paged(s: Smoke, mcfg, kv_dtype, kv_heads=None, head_dim=None, pages=None) -> float:
    """``flash_decode_paged`` vs gather + ``cache_attention(use_kernel=False)``
    on a pool written through the real paged write, at the server's
    shapes: scattered pages, a different fill per slot, and one slot
    that does not decode — the kernel walks the work list of the others'
    filled spans, and that slot reads 0.  The server's own shapes are
    multi-head attention with every head of a page in one program (the
    int8 pool: two pages an item); ``kv_heads``, ``head_dim`` and
    ``pages`` a slot ask for a grouped call under another tile."""
    from deepspeed_tpu.ops.kernels.flash_decode import decode_paged_supported, paged_tile, paged_work_list
    from deepspeed_tpu.ops.transformer.inference import (
        init_kv_cache, paged_cache_attention, paged_cache_write,
    )

    B, H, d = s.slots, mcfg.n_head, head_dim or mcfg.head_dim
    Hkv = kv_heads or H
    P = pages or s.max_len // s.page_len
    rng = np.random.default_rng(s.seed)
    table = (1 + rng.permutation(B * P)).reshape(B, P).astype(np.int32)  # page 0 is the garbage page
    fill = rng.integers(1, P * s.page_len, (B,)).astype(np.int32)
    fill[0] = P * s.page_len - 1  # one slot full to the last row
    live = np.ones((B,), bool)
    live[-1] = B == 1             # and one, where there are several, that does not decode
    k_pool, v_pool = (jax.tree.map(lambda a: a[0], c)
                      for c in init_kv_cache(1, 1 + B * P, Hkv, s.page_len, d, kv_dtype))
    kk, kv_, kq = jax.random.split(jax.random.PRNGKey(s.seed + 1), 3)
    rows = (B, Hkv, P * s.page_len, d)
    zero = jnp.zeros((B,), jnp.int32)
    k_pool = paged_cache_write(k_pool, jax.random.normal(kk, rows, jnp.float32).astype(jnp.bfloat16), table, zero)
    v_pool = paged_cache_write(v_pool, jax.random.normal(kv_, rows, jnp.float32).astype(jnp.bfloat16), table, zero)
    q = jax.random.normal(kq, (B, H, 1, d), jnp.float32).astype(jnp.bfloat16)
    heads, span = paged_tile(k_pool, P)

    def attend(use_kernel):
        def f(q, k, v, t, p, m):
            return paged_cache_attention(q, k, v, t, p, use_kernel=use_kernel, work=paged_work_list(p, m, s.page_len, P, span))
        return jax.jit(f)(q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(fill), jnp.asarray(live))

    got = attend(True)
    with jax.default_matmul_precision("highest"):
        want = attend(False)
    err = _max_err(got[live], want[live])
    if decode_paged_supported(B, H, P, s.page_len, d):  # the gather + lax form attends every row
        check(not np.asarray(got[~live], np.float32).any(), "flash_decode_paged: a row its work list does not visit reads other than 0")
    name = "int8" if kv_dtype == "int8" else jnp.dtype(kv_dtype).name
    say(f"flash_decode_paged[{name}] vs cache_attention (B={B} H={H} over {Hkv} KV heads, pages={P}x{s.page_len} d={d}; "
        f"{heads} heads x {span} pages a grid step): {err:.2e}")
    # the output is cast to bf16 on both paths: one bf16 ulp on top of TOL_F32
    check(err < TOL_F32 + 2 ** -8, f"flash_decode_paged[{name}] off its reference by {err}")
    return err


def check_paged_kv_write(s: Smoke, mcfg, kv_dtype) -> None:
    """A decode step's K/V write into the stacked pool — on the chip the
    one aliased ``paged_kv_write`` call a leaf, at the toy size the
    slices — against the scatter ``paged_cache_write`` on the layer's
    slice, **bit for bit on every page but the garbage page**: a layer
    that is not the first, offsets 0 and ``page_len - 1``, a row at its
    slot's last position, masked rows between writing ones, and the
    other layers untouched.  The pool is donated and comes back."""
    from deepspeed_tpu.ops.transformer import inference as inf

    B, H, d, P, L, layer = s.slots, mcfg.n_head, mcfg.head_dim, s.max_len // s.page_len, 3, 1
    rng = np.random.default_rng(s.seed + 2)
    table = (1 + rng.permutation(B * P)).reshape(B, P).astype(np.int32)
    pos = rng.integers(0, P * s.page_len, (B,)).astype(np.int32)
    pos[:3] = (0, s.page_len - 1, P * s.page_len - 1)[:B]
    mask = np.ones((B,), bool)
    mask[1::3] = B < 3  # rows 1, 4, 7 of the chip's eight write nothing
    k_pool, _ = inf.init_kv_cache(L, 1 + B * P, H, s.page_len, d, kv_dtype)
    fill = lambda a, key: (jax.random.randint(key, a.shape, -127, 128, jnp.int32) if a.dtype == jnp.int8  # noqa: E731
                           else jax.random.normal(key, a.shape, jnp.float32)).astype(a.dtype)
    leaves, tree = jax.tree.flatten(k_pool)
    k_pool = jax.tree.unflatten(tree, [fill(a, jax.random.PRNGKey(s.seed + 3 + i)) for i, a in enumerate(leaves)])
    t = jax.random.normal(jax.random.PRNGKey(s.seed + 9), (B, H, 1, d), jnp.float32).astype(jnp.bfloat16)
    args = (jnp.asarray(table), jnp.asarray(pos), jnp.asarray(mask))
    want = jax.tree.map(lambda a, w: np.asarray(a.at[layer].set(w)), k_pool,
                        inf.paged_cache_write(jax.tree.map(lambda a: a[layer], k_pool), t, *args))
    takes_kernel = inf.decode_write_takes_kernel(k_pool, s.mosaic)
    write = jax.jit(lambda c, t, table, pos, m: inf.paged_cache_write_slices(c, layer, t, table, pos, m, use_kernel=s.mosaic),
                    donate_argnums=0)
    write = write.lower(k_pool, t, *args).compile()
    kernels = mosaic_kernels(write.as_text())
    got = jax.tree.map(np.asarray, write(k_pool, t, *args))
    name = "int8" if kv_dtype == "int8" else jnp.dtype(kv_dtype).name
    check(kernels == ({"paged_kv_write": len(leaves)} if takes_kernel else {}), f"paged K/V write[{name}]: Mosaic calls {kernels}")
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        check(g.dtype == w.dtype and np.array_equal(g[:, 1:], w[:, 1:]),
              f"paged K/V write[{name}]: {int((g[:, 1:] != w[:, 1:]).sum())} values of a {g.dtype} leaf differ from the scatter's")
    say(f"paged K/V write[{name}] ({inf.KV_WRITE_FORMS[takes_kernel]}; B={B}, {int(mask.sum())} rows writing, "
        f"pages of {H}x{s.page_len}x{d}): the scatter's, bit for bit")


def check_fused_update(s: Smoke) -> Dict[str, float]:
    """The fused Adam and LAMB kernels vs the XLA update the engine runs
    without them, on leaves of the trainer's shapes — one stacked weight
    (the Pallas path) and one ragged bias (the XLA leaf path) — and one
    of the benchmark's train cell.  The executable, compiled as the
    engine compiles it (state and parameters donated), must hold the
    kernels and nothing else that touches a weight leaf."""
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
    from deepspeed_tpu.ops.kernels.fused_update import engine_update
    from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb

    c = s.train_cfg
    shapes = {"qkv_w": (c.n_layer, c.n_embd, 3 * c.n_embd), "qkv_b": (c.n_layer, 3 * c.n_embd - 1),
              "cell_fc_w": s.update_leaf}
    keys = iter(jax.random.split(jax.random.PRNGKey(s.seed + 2), 2 * len(shapes)))
    params = {n: 0.02 * jax.random.normal(next(keys), sh, jnp.float32) for n, sh in shapes.items()}
    grads = {n: 1e-3 * jax.random.normal(next(keys), sh, jnp.float32) for n, sh in shapes.items()}
    # a learning rate large enough that what the steps move the
    # parameters by stands clear of the parameters' own ulp (LAMB's trust
    # ratio is ~0.02 here, so at 1e-4 two steps move |p| ~ 0.1 by 4e-6,
    # 500 ulp)
    lr = jnp.float32(1e-2)
    errs = {}
    for name, opt in (("adam", FusedAdam(lr=1e-2, weight_decay=0.01)), ("lamb", FusedLamb(lr=1e-2))):
        state = opt.init(params)

        def fused(g, st, p, opt=opt):
            return engine_update(opt, g, st, p, lr, None)

        def xla(g, st, p, opt=opt):
            upd, new = opt.update(g, st, p, lr=lr)
            return jax.tree.map(lambda a, u: a + u, p, upd), new

        # two steps, so the second reads moments the first one wrote
        got = want = (params, state)
        for _ in range(2):
            got = jax.jit(fused)(grads, got[1], got[0])
            want = jax.jit(xla)(grads, want[1], want[0])
        # what the two steps moved the parameters by, and the moments
        parts = lambda out: jax.tree.leaves((  # noqa: E731
            jax.tree.map(jnp.subtract, out[0], params), out[1].exp_avg, out[1].exp_avg_sq))
        errs[name] = max(
            float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) for a, b in zip(parts(got), parts(want))
        )
        if s.mosaic:
            # outputs in the order of the donated inputs, as the engine's
            # state is: jit pairs donated buffers with results by position
            hlo = (jax.jit(lambda g, st, p: fused(g, st, p)[::-1], donate_argnums=(1, 2))
                   .lower(grads, state, params).compile().as_text())
            expect_kernels(mosaic_kernels(hlo), UPDATE_KERNELS[name], f"fused_update[{name}]")
            for leaf in ("qkv_w", "cell_fc_w"):
                moves = leaf_sized_moves(hlo, math.prod(shapes[leaf]))
                check(not moves, f"fused_update[{name}]: {moves} of {leaf}'s size {shapes[leaf]} beside the kernels")
    say("fused_update vs XLA update: " + " ".join(f"{n}={e:.2e}" for n, e in errs.items()))
    check(max(errs.values()) < TOL_UPDATE, f"fused update off the XLA update by {errs} (tolerance {TOL_UPDATE})")
    return errs


# ---------------------------------------------------------------------------
# phase 2: the trainer
# ---------------------------------------------------------------------------

def _batches(s: Smoke):
    rng = np.random.default_rng(s.seed)
    for _ in range(s.steps):
        yield {"input_ids": rng.integers(0, s.train_cfg.vocab_size, (s.global_batch, s.seq), dtype=np.int32)}


def train(s: Smoke, devices: Sequence) -> Dict[str, Any]:
    """``initialize`` → ``train_batch`` × steps over ``devices``; returns
    the losses and what the compiled step and the state layout show."""
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig

    n = len(devices)
    check(s.global_batch % (s.micro * n) == 0, f"{s.global_batch} sequences do not split over {n} devices x micro {s.micro}")
    gas = s.global_batch // (s.micro * n)
    mesh_block = {"fsdp": -1, "data": 1}
    config = {
        "train_micro_batch_size_per_gpu": s.micro,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "mesh": mesh_block,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "steps_per_print": 10_000,
    }
    model_fn, _, tp_fn = gpt2.make_model(s.train_cfg)
    t0 = time.perf_counter()
    params = gpt2.init_params_device(s.train_cfg, seed=s.seed)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=params, config=config, tp_spec_fn=tp_fn,
        mesh=make_mesh(MeshConfig.from_dict(mesh_block), devices=list(devices)),
    )
    del params
    say(f"train[{n} dev]: engine ready in {time.perf_counter() - t0:.1f}s (micro {s.micro} x gas {gas} x dp {n})")

    losses, walls = [], []
    for batch in _batches(s):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch)))  # float() waits for the step
        walls.append(time.perf_counter() - t0)
    say(f"train[{n} dev]: losses {[round(x, 4) for x in losses]}; smoke wall first step (compile + run) "
        f"{walls[0]:.1f}s, later steps {[round(w, 2) for w in walls[1:]]}s")

    check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    # untrained, the tied head's logits are Gaussian with variance
    # n_embd · 0.02² (unit-variance LayerNorm output times the init's
    # embedding std), so the expected cross-entropy is ln(vocab) plus
    # half of that: 11.03 at GPT-2 Medium, not the 10.82 of uniform logits
    want = math.log(s.train_cfg.vocab_size) + s.train_cfg.n_embd * 0.02 ** 2 / 2
    check(abs(losses[0] - want) < 0.01 * want, f"first loss {losses[0]:.4f} not within 1% of {want:.4f}")
    check(engine.global_steps == s.steps, f"global_steps {engine.global_steps} after {s.steps} steps")
    check(engine.compilation_count == 1, f"{engine.compilation_count} train executables for one batch shape")

    # ZeRO-3: every device holds its 1/n of the parameters and Adam
    # state, leaves under the persistence threshold (biases, LayerNorms)
    # whole
    small = engine.config.zero_config.param_persistence_threshold
    held = collections.Counter()
    total = promised = 0
    for leaf in jax.tree.leaves((engine.state["params"], engine.state["opt_state"])):
        total += leaf.nbytes
        promised += leaf.nbytes if leaf.size < small else leaf.nbytes / n
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    share = max(held.values()) / total
    say(f"train[{n} dev]: params + Adam state {total / 2**30:.2f} GiB, largest per-device share {share:.3f}")
    check(share <= 1.05 * promised / total,
          f"a device holds {share:.3f} of params + optimizer state, ZeRO-3 over {n} promises {promised / total:.3f}")

    hlo = engine.train_step_executable().as_text()
    if s.mosaic:
        # fused_update runs on one device only (runtime/engine.py: a Mosaic
        # call cannot be partitioned over the sharded optimizer state)
        expect_kernels(mosaic_kernels(hlo),
                       ["flash_attention_fwd", "flash_attention_bwd"] + (["fused_adam"] if n == 1 else []),
                       f"train[{n} dev]")
        c = s.train_cfg
        rows = first_output_dims(hlo, "flash_attention_fwd")[0]
        check(rows == s.micro * c.n_head,
              f"attention kernel runs on {rows} batch·head rows a device, expected micro {s.micro} x {c.n_head} heads")
        whole = {tuple(sorted(t)) for t in (
            (s.micro * n, c.n_head, s.seq, c.head_dim), (s.micro * n * c.n_head, s.seq, c.head_dim),
            (s.micro * n, s.seq, 3 * c.n_embd), (s.micro * n, s.seq, c.n_embd),
        )}
        gathered = [g for g in gathered_float_shapes(hlo) if tuple(sorted(g)) in whole]
        check(n == 1 or not gathered, f"the step all-gathers whole-batch activations {gathered} in front of attention")
    else:
        expect_kernels(mosaic_kernels(hlo), [], f"train[{n} dev]")
    del engine
    gc.collect()
    return {"losses": losses, "share": share, "first_step_wall": walls[0]}


# ---------------------------------------------------------------------------
# phase 3: the server
# ---------------------------------------------------------------------------

def serve(s: Smoke, device) -> Dict[str, Any]:
    """``init_inference`` → one ``ServingEngine`` per KV dtype on the
    paged pool; seeded greedy requests submitted and drained.  The
    engine gets an explicit one-device mesh: the default spreads ``data``
    over every device and replicates the pool, so four chips would
    compute the same tokens four times."""
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.ops.transformer import inference
    from deepspeed_tpu.serving import ServingEngine

    t0 = time.perf_counter()
    inf = deepspeed_tpu.init_inference(
        model=s.serve_model, max_out_tokens=s.max_len, init_on_device=True, seed=s.seed,
        mesh=make_mesh(MeshConfig(), devices=[device]),
    )
    mcfg = inf.model_config
    say(f"serve: {s.serve_model} ready in {time.perf_counter() - t0:.1f}s")
    rng = np.random.default_rng(s.seed)
    lo, hi = s.prompt_lens
    prompts = [rng.integers(0, mcfg.vocab_size, (int(n),), dtype=np.int32)
               for n in rng.integers(lo, hi + 1, (s.requests,))]
    out = {}
    for kv in ("model", "int8"):
        if s.mosaic:
            check_flash_decode_paged(s, mcfg, "int8" if kv == "int8" else inf.dtype)
            if kv == "model":  # grouped queries over one KV head of whole lane rows, eight pages a slot: a span of eight
                check_flash_decode_paged(s, mcfg, inf.dtype, kv_heads=1, head_dim=128, pages=8)
        check_paged_kv_write(s, mcfg, "int8" if kv == "int8" else inf.dtype)
        srv = ServingEngine(inf, config={
            "num_slots": s.slots, "max_len": s.max_len, "kv_cache_dtype": kv,
            "prefill_chunk": s.prefill_chunk,
            # pages for every slot's full length plus the garbage page, and
            # none of the default 2x prefix-cache headroom nothing here uses
            "kvcache": {"enabled": True, "page_len": s.page_len,
                        "num_pages": 1 + s.slots * (s.max_len // s.page_len)},
        })
        t0 = time.perf_counter()
        ids = [srv.submit(p, max_new_tokens=s.new_tokens) for p in prompts]
        done = srv.drain()
        wall = time.perf_counter() - t0
        check(sorted(done) == sorted(ids), f"serve[{kv}]: submitted {sorted(ids)}, drained {sorted(done)}")
        for rid in ids:
            r = done[rid]
            check(r.status == "done" and r.finish_reason == "length" and len(r.generated) == s.new_tokens,
                  f"serve[{kv}]: request {rid} ended {r.status}/{r.finish_reason} with {len(r.generated)} tokens")
            check(all(0 <= t < mcfg.vocab_size for t in r.generated), f"serve[{kv}]: request {rid} token id out of range")
        check((srv.prefill_compiles, srv.decode_compiles) == (1, 1),
              f"serve[{kv}]: {srv.prefill_compiles} prefill / {srv.decode_compiles} decode executables for one pool")
        say(f"serve[{kv}]: {len(ids)} requests x {s.new_tokens} tokens done, smoke wall {wall:.1f}s "
            f"(compiles included), pool {srv.pool.cache_bytes() / 2**30:.2f} GiB")
        # the paged prefill attends block by block in jnp and writes its
        # chunk as slices (T > 1); only the decode step arms kernels
        decode = srv.compiled_step("decode")
        decode_hlo = decode.as_text()
        expect_kernels(mosaic_kernels(decode_hlo), ["flash_decode_paged", "paged_kv_write"] if s.mosaic else [], f"serve[{kv}] decode")
        stats = srv.stats()
        form = stats["kv_write_form"]
        check(form == inference.KV_WRITE_FORMS[s.mosaic], f"serve[{kv}]: kv_write_form reads {form!r}")
        # a head of 64 lies with its positions in the lanes: flash_chunk_paged leaves GPT-2's chunk to the jnp form, and says so
        expect_kernels(mosaic_kernels(srv.compiled_step("prefill").as_text()), [], f"serve[{kv}] prefill")
        check(stats["chunk_attention_kernel"] is False
              and stats["chunk_attention_fallback"].startswith(("int8 pool" if kv == "int8" else "head dim") if s.mosaic else "kernel suite not armed"),
              f"serve[{kv}]: stats() say of the chunk's attention: kernel {stats['chunk_attention_kernel']}, {stats['chunk_attention_fallback']!r}")
        # the pool is written in place in the one layout the kernel
        # reads: the decode program hands all of it back aliased and
        # keeps less than one layer's K+V (and the tied head's weights,
        # which XLA transposes) beside it — a relayout in front of the
        # kernel or a scan over the pool is a pool-sized temporary
        m = decode.memory_analysis()
        pool, layer_kv = srv.pool.cache_bytes(), srv.pool.cache_bytes() // mcfg.n_layer
        head = mcfg.vocab_size * mcfg.n_embd * jnp.dtype(inf.dtype).itemsize
        say(f"serve[{kv}] decode: {m.alias_size_in_bytes / 2**20:.0f} MiB aliased of a pool of {pool / 2**20:.0f}, "
            f"temporaries {m.temp_size_in_bytes / 2**20:.0f} MiB (one layer's K+V {layer_kv / 2**20:.0f}, the head {head / 2**20:.0f})")
        check(m.alias_size_in_bytes >= pool, f"serve[{kv}] decode: {m.alias_size_in_bytes} B aliased, the pool holds {pool}")
        check(m.temp_size_in_bytes < layer_kv + head + (32 << 20),
              f"serve[{kv}] decode: {m.temp_size_in_bytes} B of temporaries beside a layer slice of {layer_kv} B")
        if s.mosaic:
            # and nothing but the layer loop produces an array the size of a K or V leaf (int8: of its codes):
            # the Mosaic calls write through bitcasts of the carry, no copy, relayout or slice update of it is left
            moves = leaf_sized_moves(decode_hlo, max(a.size for a in jax.tree.leaves(srv.pool.k)))
            check(set(moves) <= {"while"}, f"serve[{kv}] decode: pool-sized {sorted(set(moves) - {'while'})} beside the layer loop")
        out[kv] = [done[rid].generated for rid in ids]
        # the default order of a step: a chunk that is not its prompt's last is left unread a step, a last one waited for
        st = srv.stats()
        chunks = sum(-(-len(p) // s.prefill_chunk) for p in prompts)
        check(srv.config.overlap_chunks and (st["chunks_awaited"], st["chunks_deferred"]) == (len(prompts), chunks - len(prompts)),
              f"serve[{kv}]: {st['chunks_awaited']} chunks awaited, {st['chunks_deferred']} deferred of {chunks} in {len(prompts)} prompts")
        del srv, done
        gc.collect()
    return out


# ---------------------------------------------------------------------------
# phase 4: a second family on the same server — DeepSeek-V2 at a tiny size
# ---------------------------------------------------------------------------

# every mechanism present (latent attention on the paged latent pool, YaRN,
# one dense layer then expert layers, group-limited routing over all experts
# with half of them held here, shared experts, an untied head over half the
# vocabulary), at sizes the compiled ``mla_decode_paged`` and ``mla_prefill``
# and ``moe_grouped_matmul`` accept: pages of 128 positions, a latent of
# 128 + 64, chunks of 128 (x top-4 = 512 assignment rows), expert widths of
# whole lane tiles; a decode step's 2 x 4 = 8 rows stay on ``ragged_dot``
DSV2_SMOKE = {
    "vocab_size": 512, "hidden_size": 256, "intermediate_size": 512, "moe_intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 8, "q_lora_rank": 96, "kv_lora_rank": 128,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 64, "v_head_dim": 32, "n_routed_experts": 16, "n_shared_experts": 2,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.0, "norm_topk_prob": False,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000, "max_position_embeddings": 4096,
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707},
    "experts_held": [8, 8], "vocab_held": 256,
}
TOL_DSV2_TOKEN_GAP = 0.05  # logits of +-0.5 here; a bf16 program strays ~1e-2 from the float32 reference


def serve_deepseek_v2(s: Smoke, device) -> Dict[str, float]:
    """``init_inference(model_config=DeepseekV2Config)`` → the same
    ``ServingEngine``: compiles both programs, serves two requests
    (chunked prefill, then decode through the latent pool) and holds
    every emitted token against the plain reference's logits
    (``benchmark/reference_deepseek_v2.py``) — so that a bring-up fault
    shows here, in seconds, before the 10 GB cell runs."""
    from benchmark import weights_deepseek_v2 as weights
    from benchmark.reference_deepseek_v2 import Reference
    from benchmark.runners.serve_dsv2 import served_gaps
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models import deepseek_v2
    from deepspeed_tpu.serving import ServingEngine

    dims = DSV2_SMOKE
    mcfg = deepseek_v2.DeepseekV2Config.from_hf(dims, experts_held=dims["experts_held"], vocab_held=dims["vocab_held"])
    inf = deepspeed_tpu.init_inference(
        model_config=mcfg, params=weights.program_params(s.seed, dims, jnp.bfloat16), dtype=jnp.bfloat16,
        max_out_tokens=512, mesh=make_mesh(MeshConfig(), devices=[device]), donate_params=True,
    )
    srv = ServingEngine(inf, config={"num_slots": 2, "max_len": 512, "prefill_chunk": 128, "max_new_tokens": 16,
                                     "kvcache": {"enabled": True, "page_len": 128, "num_pages": 9}})
    rng = np.random.default_rng(s.seed)
    prompts = [rng.integers(1, dims["vocab_held"], n, dtype=np.int32) for n in (200, 131)]
    ids = [srv.submit(p, max_new_tokens=8) for p in prompts]
    done = srv.drain()
    check((srv.prefill_compiles, srv.decode_compiles) == (1, 1),
          f"serve[dsv2]: {srv.prefill_compiles} prefill / {srv.decode_compiles} decode executables")
    moe = srv.stats()["moe"]
    check(moe["dropped_assignments"] == 0 and moe["assignments_computed"] > 0, f"serve[dsv2]: expert counters {moe}")
    gaps = served_gaps(Reference(dims, s.seed), [{"prompt": p, "generated": list(done[i].generated)}
                                                 for p, i in zip(prompts, ids)], 256)
    check(gaps["token_gap_max"] <= TOL_DSV2_TOKEN_GAP,
          f"serve[dsv2]: an emitted token lies {gaps['token_gap_max']:.4f} under the reference's best logit "
          f"(tolerance {TOL_DSV2_TOKEN_GAP})")
    expect_kernels(mosaic_kernels(srv.compiled_step("decode").as_text()),
                   ["mla_decode_paged", "moe_grouped_matmul"] if s.mosaic else [], "serve[dsv2] decode")  # a step's few rows: one padded window
    expect_kernels(mosaic_kernels(srv.compiled_step("prefill").as_text()),
                   ["mla_prefill", "moe_grouped_matmul"] if s.mosaic else [], "serve[dsv2] prefill")
    stats = srv.stats()
    check(stats["mla_prefill_kernel"] is bool(s.mosaic),
          f"serve[dsv2]: stats() say of the prefill program: mla_prefill_kernel {stats['mla_prefill_kernel']}, "
          f"fallback {stats['mla_prefill_fallback']!r}")
    check((stats["moe_grouped_fallback"] == "" and stats["moe_grouped_kernel"].endswith("512")) if s.mosaic else stats["moe_grouped_kernel"] == "",
          f"serve[dsv2]: stats() say of the held experts: moe_grouped_kernel {stats['moe_grouped_kernel']!r}, "
          f"fallback {stats['moe_grouped_fallback']!r}")
    say(f"serve[dsv2]: 2 requests x 8 tokens through the latent pool, token gap mean {gaps['token_gap_mean']:.5f} "
        f"max {gaps['token_gap_max']:.5f} over {gaps['tokens']} tokens")
    return gaps


# Solar-Open2 at a small size: the published kinds of layer (one period twice: GQA, KDA, KDA, KDA), head_dim 128 and
# 16 KDA heads so that the kernels' tiles are the real ones, everything else narrow.  Experts 8-15 of 16 are held.
SOLAR2_SMOKE = {
    "hidden_size": 256, "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "vocab_size": 512, "intermediate_size": 512, "moe_intermediate_size": 128, "rms_norm_eps": 1e-5, "gqa_layers": [0, 4],
    "use_gqa_gate": True, "use_rope": False, "first_k_dense_replace": 0, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "tie_word_embeddings": False, "n_routed_experts": 16, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 4, "max_position_embeddings": 4096,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 16, "num_kv_heads": None},
    "experts_held": [8, 8], "vocab_held": 256,
}
TOL_SOLAR2_TOKEN_GAP = 0.05  # as TOL_DSV2_TOKEN_GAP: a bf16 program against the float32 reference


def serve_solar_open2(s: Smoke, device) -> Dict[str, float]:
    """``init_inference(model_config=SolarOpen2Config)`` → the same
    ``ServingEngine`` on the hybrid cache: compiles both programs, serves
    three requests over two slots (chunked prefill; decode through the
    per-slot recurrent state and the grouped ``flash_decode_paged``; a
    slot reused) and holds every emitted token against the plain
    reference's logits (``benchmark/reference_solar_open2.py``)."""
    from benchmark import weights_solar_open2 as weights
    from benchmark.reference_solar_open2 import Reference
    from benchmark.runners.serve_solar2 import served_gaps
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models import solar_open2
    from deepspeed_tpu.serving import ServingEngine

    dims = SOLAR2_SMOKE
    mcfg = solar_open2.SolarOpen2Config.from_hf(dims, experts_held=dims["experts_held"], vocab_held=dims["vocab_held"])
    inf = deepspeed_tpu.init_inference(
        model_config=mcfg, params=weights.program_params(s.seed, dims, jnp.bfloat16), dtype=jnp.bfloat16,
        max_out_tokens=512, mesh=make_mesh(MeshConfig(), devices=[device]), donate_params=True,
    )
    srv = ServingEngine(inf, config={"num_slots": 2, "max_len": 512, "prefill_chunk": 128, "max_new_tokens": 16,
                                     "kvcache": {"enabled": True, "page_len": 128, "num_pages": 9}})
    rng = np.random.default_rng(s.seed)
    prompts = [rng.integers(1, dims["vocab_held"], n, dtype=np.int32) for n in (200, 131, 70)]
    ids = [srv.submit(p, max_new_tokens=8) for p in prompts]
    done = srv.drain()
    check((srv.prefill_compiles, srv.decode_compiles) == (1, 1),
          f"serve[solar2]: {srv.prefill_compiles} prefill / {srv.decode_compiles} decode executables")
    stats = srv.stats()
    moe, hybrid = stats["moe"], stats["hybrid"]
    check(moe["dropped_assignments"] == 0 and moe["assignments_computed"] > 0, f"serve[solar2]: expert counters {moe}")
    check(hybrid["state_resets_in_program"] == 3 and hybrid["state_bytes"] > 0, f"serve[solar2]: hybrid cache {hybrid}")
    gaps = served_gaps(Reference(dims, s.seed), [{"prompt": p, "generated": list(done[i].generated)}
                                                 for p, i in zip(prompts, ids)], 256)
    check(gaps["token_gap_max"] <= TOL_SOLAR2_TOKEN_GAP,
          f"serve[solar2]: an emitted token lies {gaps['token_gap_max']:.4f} under the reference's best logit "
          f"(tolerance {TOL_SOLAR2_TOKEN_GAP})")
    expect_kernels(mosaic_kernels(srv.compiled_step("decode").as_text()),
                   ["kda_decode", "flash_decode_paged", "moe_grouped_matmul"] if s.mosaic else [], "serve[solar2] decode")
    expect_kernels(mosaic_kernels(srv.compiled_step("prefill").as_text()),
                   ["flash_chunk_paged", "moe_grouped_matmul"] if s.mosaic else [], "serve[solar2] prefill")
    check(stats["chunk_attention_kernel"] is bool(s.mosaic) and stats["gqa_prefill_form"].startswith("flash_chunk_paged" if s.mosaic else "blockwise jnp"),
          f"serve[solar2]: stats() say of the chunk's attention: {stats['gqa_prefill_form']!r}")
    check(stats["kda_decode_kernel"] is bool(s.mosaic) and stats["gqa_decode_kernel"] is bool(s.mosaic),
          f"serve[solar2]: stats() say of the decode program: kda_decode_kernel {stats['kda_decode_kernel']} "
          f"({stats['kda_decode_fallback']!r}), gqa_decode_kernel {stats['gqa_decode_kernel']} ({stats['gqa_decode_fallback']!r})")
    say(f"serve[solar2]: 3 requests x 8 tokens through the hybrid cache, token gap mean {gaps['token_gap_mean']:.5f} "
        f"max {gaps['token_gap_max']:.5f} over {gaps['tokens']} tokens")
    return gaps


KEYE_SMOKE = {
    "hidden_size": 256, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "vocab_size": 512, "moe_intermediate_size": 128, "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "rope_theta": 10000000, "rope_scaling": {"mrope_section": [16, 24, 24]},
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 4, "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 128},
    "decoder_sparse_step": 1, "mlp_only_layers": [], "tie_word_embeddings": False, "max_position_embeddings": 4096,
    "experts_held": [8, 8], "vocab_held": 256,
}
TOL_KEYE_TOKEN_GAP = 0.05  # as TOL_DSV2_TOKEN_GAP: a bf16 program against the float32 reference


def serve_keye(s: Smoke, device) -> Dict[str, float]:
    """``init_inference(model_config=KeyeConfig)`` → the same
    ``ServingEngine`` on the cache kind whose pages carry a third leaf:
    compiles both programs, serves three requests over eight slots and nine
    pages (chunked prefill under the selection mask; decode through
    ``dsa_index_scores_paged`` and ``dsa_sparse_decode`` over the 128
    positions each row's indexer selects of up to 308; pages reused) and
    holds every emitted token against the plain reference's logits
    (``benchmark/reference_keye.py``)."""
    from benchmark import weights_keye as weights
    from benchmark.reference_keye import Reference
    from benchmark.runners.serve_keye import served_gaps
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models import keye
    from deepspeed_tpu.serving import ServingEngine

    dims = {**KEYE_SMOKE, "vocab_size": KEYE_SMOKE["vocab_held"]}  # a sliced vocabulary is a smaller one
    mcfg = keye.KeyeConfig.from_hf(dims, experts_held=dims["experts_held"])
    inf = deepspeed_tpu.init_inference(
        model_config=mcfg, params=weights.program_params(s.seed, dims, jnp.bfloat16), dtype=jnp.bfloat16,
        max_out_tokens=512, mesh=make_mesh(MeshConfig(), devices=[device]), donate_params=True,
    )
    srv = ServingEngine(inf, config={"num_slots": 8, "max_len": 512, "prefill_chunk": 128, "max_new_tokens": 16,
                                     "kvcache": {"enabled": True, "page_len": 128, "num_pages": 9}})  # 8 slots: a block of rows of the selection's kernel
    rng = np.random.default_rng(s.seed)
    prompts = [rng.integers(1, dims["vocab_size"], n, dtype=np.int32) for n in (200, 300, 70)]
    ids = [srv.submit(p, max_new_tokens=8) for p in prompts]
    done = srv.drain()
    check((srv.prefill_compiles, srv.decode_compiles) == (1, 1),
          f"serve[keye]: {srv.prefill_compiles} prefill / {srv.decode_compiles} decode executables")
    stats = srv.stats()
    moe, leaves = stats["moe"], stats["kvcache"]["page_leaves"]
    check(moe["dropped_assignments"] == 0 and moe["assignments_computed"] > 0, f"serve[keye]: expert counters {moe}")
    check(set(leaves) == {"k", "v", "idx"} and leaves["idx"] == 2 * 9 * 128 * 64 * 2, f"serve[keye]: the pool's leaves {leaves}")
    check(0 < stats["dsa_positions_selected"] < stats["dsa_positions_attendable"],
          f"serve[keye]: selected {stats['dsa_positions_selected']} of {stats['dsa_positions_attendable']} attendable positions")
    gaps = served_gaps(Reference(dims, s.seed), [{"prompt": p, "generated": list(done[i].generated)}
                                                 for p, i in zip(prompts, ids)], 128)
    check(gaps["token_gap_max"] <= TOL_KEYE_TOKEN_GAP,
          f"serve[keye]: an emitted token lies {gaps['token_gap_max']:.4f} under the reference's best logit "
          f"(tolerance {TOL_KEYE_TOKEN_GAP})")
    expect_kernels(mosaic_kernels(srv.compiled_step("decode").as_text()),
                   ["dsa_index_scores_paged", "dsa_select_threshold", "dsa_sparse_decode", "moe_grouped_matmul"] if s.mosaic else [], "serve[keye] decode")
    expect_kernels(mosaic_kernels(srv.compiled_step("prefill").as_text()),
                   ["dsa_select_threshold", "flash_chunk_paged", "moe_grouped_matmul"] if s.mosaic else [], "serve[keye] prefill")
    check(stats["chunk_attention_kernel"] is bool(s.mosaic) and ("flash_chunk_paged" in stats["dsa_prefill_form"]) is bool(s.mosaic),
          f"serve[keye]: stats() say of the chunk's attention: {stats['dsa_prefill_form']!r}")
    check(stats["dsa_decode_kernel"].startswith("dsa_sparse_decode" if s.mosaic else "lax"),
          f"serve[keye]: stats() say of the decode program: dsa_decode_kernel {stats['dsa_decode_kernel']!r}, "
          f"dsa_index_form {stats['dsa_index_form']!r}")
    say(f"serve[keye]: 3 requests x 8 tokens through the three-leaf cache, token gap mean {gaps['token_gap_mean']:.5f} "
        f"max {gaps['token_gap_max']:.5f} over {gaps['tokens']} tokens")
    return gaps


GIGACHAT35_SMOKE = {
    "vocab_size": 512, "max_position_embeddings": 4096, "hidden_size": 256, "intermediate_size": 512, "moe_intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 8, "n_shared_experts": 1, "n_routed_experts": 16, "routed_scaling_factor": 2.5,
    "kv_lora_rank": 128, "q_lora_rank": 96, "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128, "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 4, "first_k_dense_replace": 1, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 100000, "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                                           "original_max_position_embeddings": 64, "type": "yarn"},
    "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post", "layernorm_gating_weight": 2, "gated_attention": True,
    "use_mla_scaling_factor": True, "linear_attention_type": "GigaChat35GatedDeltaNet", "full_attention_layers": [2],
    "linear_key_head_dim": 128, "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4, "linear_num_key_heads": 8,
    "linear_num_value_heads": 16, "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered", "linear_sigmoid_gate_scale": 2,
    "linear_attn_o_norm_eps": 1e-6, "swiglu_limit": 10, "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
    "experts_held": [8, 8], "vocab_held": 256,
}
# a bf16 program against the float32 reference: the mean as TOL_DSV2_TOKEN_GAP; the largest of 24 gaps is wider under four norms a
# layer (0.19 at this size on the CPU mesh, 0.58-0.78 in the cell's runs: PERF.md section 2) and a wrong forward reads several units
TOL_GIGACHAT35_TOKEN_GAP, TOL_GIGACHAT35_TOKEN_GAP_MAX = 0.05, 0.5


def serve_gigachat35(s: Smoke, device) -> Dict[str, float]:
    """``init_inference(model_config=GigaChat35Config)`` → the same
    ``ServingEngine`` on the hybrid cache over **latent** pages: compiles
    both programs, serves three requests over two slots (chunked prefill:
    the chunked delta rule at a scalar decay and expanded latent
    attention; decode through ``gdn_decode`` on the per-slot recurrent
    state — 16 value heads on 8 query / key heads — and
    ``mla_decode_paged`` on the latent pages; a slot reused) and holds
    every emitted token against the plain reference's logits
    (``benchmark/reference_gigachat35.py``)."""
    from benchmark import weights_gigachat35 as weights
    from benchmark.reference_gigachat35 import Reference
    from benchmark.runners.serve_gigachat35 import served_gaps
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models import gigachat35
    from deepspeed_tpu.serving import ServingEngine

    dims = GIGACHAT35_SMOKE
    mcfg = gigachat35.GigaChat35Config.from_hf(dims, experts_held=dims["experts_held"], vocab_held=dims["vocab_held"])
    inf = deepspeed_tpu.init_inference(
        model_config=mcfg, params=weights.program_params(s.seed, dims, jnp.bfloat16), dtype=jnp.bfloat16,
        max_out_tokens=512, mesh=make_mesh(MeshConfig(), devices=[device]), donate_params=True,
    )
    srv = ServingEngine(inf, config={"num_slots": 2, "max_len": 512, "prefill_chunk": 128, "max_new_tokens": 16,
                                     "overlap_chunks": True,  # as the cell serves it: programs dispatched ahead of the host's reads
                                     "kvcache": {"enabled": True, "page_len": 128, "num_pages": 9}})
    rng = np.random.default_rng(s.seed)
    prompts = [rng.integers(1, dims["vocab_held"], n, dtype=np.int32) for n in (200, 131, 70)]
    ids = [srv.submit(p, max_new_tokens=8) for p in prompts]
    done = srv.drain()
    check((srv.prefill_compiles, srv.decode_compiles) == (1, 1),
          f"serve[gigachat35]: {srv.prefill_compiles} prefill / {srv.decode_compiles} decode executables")
    stats = srv.stats()
    moe, hybrid, kv = stats["moe"], stats["hybrid"], stats["kvcache"]
    check(moe["dropped_assignments"] == 0 and moe["assignments_computed"] > 0, f"serve[gigachat35]: expert counters {moe}")
    check(hybrid["state_resets_in_program"] == 3 and hybrid["state_bytes"] > 0, f"serve[gigachat35]: hybrid cache {hybrid}")
    check(kv["page_kind"] == "LatentKV" and kv["page_leaves"] == {"k": 1 * 9 * 192 * 128 * 2} and srv.pool.v is None,
          f"serve[gigachat35]: the pool's pages {kv.get('page_kind')} {kv.get('page_leaves')}")
    gaps = served_gaps(Reference(dims, s.seed), [{"prompt": p, "generated": list(done[i].generated)}
                                                 for p, i in zip(prompts, ids)], 256)
    check(gaps["token_gap_mean"] <= TOL_GIGACHAT35_TOKEN_GAP and gaps["token_gap_max"] <= TOL_GIGACHAT35_TOKEN_GAP_MAX,
          f"serve[gigachat35]: the emitted tokens lie {gaps['token_gap_mean']:.4f} on the mean, {gaps['token_gap_max']:.4f} at most "
          f"under the reference's best logit (tolerances {TOL_GIGACHAT35_TOKEN_GAP}, {TOL_GIGACHAT35_TOKEN_GAP_MAX})")
    expect_kernels(mosaic_kernels(srv.compiled_step("decode").as_text()),
                   ["gdn_decode", "mla_decode_paged", "moe_grouped_matmul"] if s.mosaic else [], "serve[gigachat35] decode")
    expect_kernels(mosaic_kernels(srv.compiled_step("prefill").as_text()),
                   ["mla_prefill", "moe_grouped_matmul"] if s.mosaic else [], "serve[gigachat35] prefill")
    check(stats["gdn_decode_kernel"] is bool(s.mosaic) and stats["mla_decode_kernel"] is bool(s.mosaic),
          f"serve[gigachat35]: stats() say of the decode program: gdn_decode_kernel {stats['gdn_decode_kernel']} "
          f"({stats['gdn_decode_fallback']!r}), mla_decode_kernel {stats['mla_decode_kernel']} ({stats['mla_decode_fallback']!r})")
    say(f"serve[gigachat35]: 3 requests x 8 tokens through the hybrid cache over latent pages, token gap mean "
        f"{gaps['token_gap_mean']:.5f} max {gaps['token_gap_max']:.5f} over {gaps['tokens']} tokens")
    return gaps


LAGUNA_SMOKE = {
    "model_type": "laguna", "vocab_size": 512, "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 5,
    "num_attention_heads": 12, "num_key_value_heads": 2, "head_dim": 128, "max_position_embeddings": 4096, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 128,
    "shared_expert_intermediate_size": 128, "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 160,
    "rope_parameters": {"full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 8, "original_max_position_embeddings": 128,
                                           "beta_slow": 1, "beta_fast": 32, "attention_factor": 1.2, "partial_rotary_factor": 0.5},
                        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"], "gating_types": ["per_head"] * 5,
    "num_attention_heads_per_layer": [12, 18, 18, 18, 12],  # 6 and 9 query heads a KV head: the published groups
    "moe_routed_scaling_factor": 2.5, "moe_apply_router_weight_on_input": False, "moe_router_logit_softcapping": 0,
    "experts_held": [8, 8], "vocab_held": 256,
}
TOL_LAGUNA_TOKEN_GAP = 0.05  # as TOL_DSV2_TOKEN_GAP: a bf16 program against the float32 reference


def check_swa_decode_paged(s: Smoke) -> float:
    """``swa_decode_paged`` — the paged decode kernel under its window
    form — against its ``jnp`` form on a **lapped ring** at the cell's
    heads (72 query heads on 8 KV heads of 128, 9 a KV head), pages of
    128, window 512: rows before a window is full, rows whose ring has
    lapped several times, a row that does not decode."""
    from deepspeed_tpu.ops.kernels.flash_decode import paged_tile, paged_work_list
    from deepspeed_tpu.ops.transformer import inference as inf

    B, H, Hkv, d, page_len, window, P = 6, 72, 8, 128, 128, 512, 64
    R = inf.ring_pages_for(window, page_len)
    rng = np.random.default_rng(s.seed)
    wk, wv = (jnp.asarray(rng.standard_normal((1 + B * R, Hkv, page_len, d)), jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.bfloat16)
    ring = inf.ring_table(jnp.arange(B), R, P)
    pos = jnp.asarray([7, 511, 512, 3000, 8191, 1234], jnp.int32)
    live = jnp.asarray([True, True, True, True, True, False])
    work = paged_work_list(pos, live, page_len, P, paged_tile(wk, P)[1], window)
    got = jax.jit(lambda *a: inf.window_cache_attention(*a, window, use_kernel=True, work=work))(q, wk, wv, ring, pos)
    want = jax.jit(lambda *a: inf.window_cache_attention(*a, window, use_kernel=False))(q, wk, wv, ring, pos)
    err = _max_err(got[:5], want[:5])
    check(err <= TOL_BF16, f"swa_decode_paged on a lapped ring of {R} pages, {H} / {Hkv} heads: max error {err:.4f} against the jnp form (tolerance {TOL_BF16})")
    check(float(jnp.abs(got[5].astype(jnp.float32)).max()) == 0.0, "swa_decode_paged: the row that does not decode reads 0")
    check(int(work[2][0]) == sum(int(p) // 256 - max(int(p) - 511, 0) // 256 + 1 for p in np.asarray(pos)[:5]),
          f"swa_decode_paged: the work list holds {int(work[2][0])} items, the window's spans of the five live rows")
    say(f"kernel swa_decode_paged: max error {err:.4f}, {int(work[2][0])} items for 5 live rows")
    return err


def check_mimo_decode_paged(s: Smoke) -> Dict[str, float]:
    """The paged decode kernel at MiMo-V2-Flash's two geometries — keys
    192 wide (positions in the lanes), values 128 (row-major), in one
    call — against the ``jnp`` forms: ``swa_decode_paged`` with **a sink
    a head** on a lapped ring of 2 pages (64 query heads on 8 KV heads,
    window 128: rows before the window is full, rows whose ring has
    lapped many times, a row at a page's last position, one that does not
    decode), and ``flash_decode_paged`` at 64 / 4 heads over pages by
    length."""
    from deepspeed_tpu.ops.kernels.flash_decode import paged_tile, paged_work_list
    from deepspeed_tpu.ops.transformer import inference as inf

    B, H, d, dv, page_len, window, P = 6, 64, 192, 128, 128, 128, 64
    R = inf.ring_pages_for(window, page_len)
    rng = np.random.default_rng(s.seed + 1)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)  # noqa: E731
    wk, wv, q = draw(1 + B * R, 8, page_len, d), draw(1 + B * R, 8, page_len, dv), draw(B, H, 1, d)
    sink = jnp.asarray(rng.standard_normal((H,)) + 4.0, jnp.float32)
    ring = inf.ring_table(jnp.arange(B), R, P)
    pos = jnp.asarray([7, 127, 128, 3000, 8191, 1234], jnp.int32)
    live = jnp.asarray([True, True, True, True, True, False])
    work = paged_work_list(pos, live, page_len, P, paged_tile(wk, P, wv)[1], window)
    got = jax.jit(lambda *a: inf.window_cache_attention(*a[:5], window, use_kernel=True, work=work, sink=a[5]))(q, wk, wv, ring, pos, sink)
    want = jax.jit(lambda *a: inf.window_cache_attention(*a[:5], window, use_kernel=False, sink=a[5]))(q, wk, wv, ring, pos, sink)
    bare = jax.jit(lambda *a: inf.window_cache_attention(*a, window, use_kernel=False))(q, wk, wv, ring, pos)
    swa = _max_err(got[:5], want[:5])
    check(got.shape == (B, H, 1, dv) and swa <= TOL_BF16,
          f"swa_decode_paged with sinks on a lapped ring of {R} pages, {H} / 8 heads, keys {d} / values {dv}: max error {swa:.4f} "
          f"against the jnp form (tolerance {TOL_BF16})")
    check(float(jnp.abs(got[5].astype(jnp.float32)).max()) == 0.0, "swa_decode_paged with sinks: the row that does not decode reads 0")
    check(_max_err(bare[:5], want[:5]) > 10 * TOL_BF16, "swa_decode_paged: the sink column takes mass (without it the rows read differently)")
    k, v, q4 = draw(1 + 3 * 8, 4, page_len, d), draw(1 + 3 * 8, 4, page_len, dv), draw(3, H, 1, d)
    table = jnp.asarray(1 + np.arange(24, dtype=np.int32).reshape(3, 8))
    at = jnp.asarray([5, 600, 1023], jnp.int32)
    full = _max_err(jax.jit(lambda *a: inf.paged_cache_attention(*a, use_kernel=True))(q4, k, v, table, at),
                    jax.jit(lambda *a: inf.paged_cache_attention(*a, use_kernel=False))(q4, k, v, table, at))
    check(full <= TOL_BF16, f"flash_decode_paged at {H} / 4 heads, keys {d} / values {dv}: max error {full:.4f} against the lax form (tolerance {TOL_BF16})")
    say(f"kernel swa_decode_paged (sinks, {d} / {dv}): max error {swa:.4f}; flash_decode_paged ({d} / {dv}): {full:.4f}")
    return {"swa": swa, "full": full}


def check_dsa_sparse_decode(s: Smoke) -> float:
    """``dsa_sparse_decode`` — the paged decode kernel under a selection —
    against the gathered rows under the mask (the lax form) at Keye's tile
    (32 query heads on 4 KV heads of 128, pages of 128: 4 heads x 4 pages
    a grid step): a row whose first item holds nothing selected, a row
    that selects nothing, a row that does not decode, a row whose last
    span reaches past its position, a full one."""
    from deepspeed_tpu.ops.kernels import sparse_decode as kern
    from deepspeed_tpu.ops.transformer.sparse_attention import selected_decode_reference

    B, H, Hkv, d, page_len, P = 6, 32, 4, 128, 128, 16
    rng = np.random.default_rng(s.seed)
    kc, vc = (jnp.asarray(rng.standard_normal((1 + B * P, Hkv, page_len, d)), jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.bfloat16)
    table = jnp.asarray((1 + rng.permutation(B * P)).reshape(B, P).astype(np.int32))
    pos = np.asarray([5, 600, 2047, 1500, 700, 1234], np.int32)
    live = np.asarray([True, True, True, True, True, False])
    mask = (np.arange(P * page_len)[None, :] <= pos[:, None]) & (rng.random((B, P * page_len)) < 0.115)
    mask[0, 2] = True
    mask[1, :512], mask[1, 513] = False, True   # the first item (4 pages) holds nothing selected
    mask[3] = False                             # a row that selects nothing
    span = kern.span_of(kc, P)
    work = kern.work_list(jnp.asarray(pos), jnp.asarray(live), kc, P)
    got = jax.jit(lambda *a: kern.dsa_sparse_decode(*a, None, work))(q, kc, vc, table, jnp.asarray(pos), jnp.asarray(mask))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: selected_decode_reference(*a, d ** -0.5))(q, kc, vc, table, jnp.asarray(mask & live[:, None]))
    err = _max_err(got, want)
    check(span == 4, f"dsa_sparse_decode: {span} pages a grid step at Keye's pages, not 4")
    check(err <= TOL_BF16, f"dsa_sparse_decode at {H} / {Hkv} heads, spans of {span} pages: max error {err:.4f} against the lax form (tolerance {TOL_BF16})")
    quiet = np.abs(np.asarray(got, np.float32)).reshape(B, -1).max(1)
    check(quiet[3] == 0.0 and quiet[5] == 0.0 and quiet[[0, 1, 2, 4]].min() > 0.0,
          f"dsa_sparse_decode: the row that selects nothing and the row that does not decode read 0, the others do not ({quiet})")
    say(f"kernel dsa_sparse_decode: max error {err:.4f}, {int(work[2][0])} items for 5 live rows")
    return err


def check_flash_chunk_paged(s: Smoke) -> Dict[str, float]:
    """``flash_chunk_paged`` against the ``jnp`` form of
    ``paged_chunk_attention`` at the two cells it leads: Keye's chunk (32
    query heads on 4 KV heads of 128, 2,048 queries ending a context of
    16,384 under a top-2,048 selection, and without it) and Laguna's full
    layers' (48 on 8, 1,024 queries ending 6,144), pages of 128 in
    shuffled order."""
    from deepspeed_tpu.ops.transformer import inference as inf

    errs = {}
    for name, H, Hkv, T, P, context, topk in (("keye", 32, 4, 2048, 264, 16384, 2048), ("keye, no selection", 32, 4, 2048, 264, 16384, 0),
                                              ("laguna", 48, 8, 1024, 168, 6144, 0)):
        rng = np.random.default_rng(s.seed)
        k, v = (jnp.asarray(rng.standard_normal((1 + P, Hkv, 128, 128)), jnp.bfloat16) for _ in range(2))
        q = jnp.asarray(rng.standard_normal((1, H, T, 128)), jnp.bfloat16)
        table = jnp.asarray(1 + rng.permutation(P)[None], jnp.int32)
        pos = jnp.asarray([context - T], jnp.int32)
        mask = None
        if topk:  # each query's top-k of random scores over what it reaches, as the indexer's selection leaves it
            reach = jnp.arange(P * 128)[None, None, :] <= (pos[:, None] + jnp.arange(T)[None, :])[:, :, None]
            scores = jnp.where(reach, jnp.asarray(rng.standard_normal((1, T, P * 128)), jnp.float32), -jnp.inf)
            mask = (scores >= jax.lax.top_k(scores, topk)[0][..., -1:]) & reach
        got, want = (jax.jit(lambda *a, use=use: inf.paged_chunk_attention(*a, extra_mask=mask, use_kernel=use))(q, k, v, table, pos)
                     for use in (True, False))
        errs[name] = _max_err(got, want)
        check(errs[name] <= TOL_BF16, f"flash_chunk_paged at {H} / {Hkv} heads, {T} queries ending {context}"
              f"{f', top-{topk} selected' if topk else ''}: max error {errs[name]:.4f} against the jnp form (tolerance {TOL_BF16})")
    say("kernel flash_chunk_paged: max error " + ", ".join(f"{e:.4f} ({name})" for name, e in errs.items()))
    return errs


def serve_laguna(s: Smoke, device) -> Dict[str, float]:
    """``init_inference(model_config=LagunaConfig)`` → the same
    ``ServingEngine`` on **two page groups in one pool**: compiles both
    programs, serves three requests over two slots (chunked prefill:
    block by block over pages by length in the full layers, a banded walk
    and the ring's write in the window layers — window 160 on pages of
    128 is a ring of 3 pages, so a prompt of 600 laps it; decode through
    ``flash_decode_paged`` at 6 query heads a KV head and
    ``swa_decode_paged`` at 9; a slot reused) and holds every emitted
    token against the plain reference's logits
    (``benchmark/reference_laguna.py``)."""
    from benchmark import weights_laguna as weights
    from benchmark.reference_laguna import Reference
    from benchmark.runners.serve_laguna import served_gaps
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models import laguna
    from deepspeed_tpu.serving import ServingEngine

    dims = LAGUNA_SMOKE
    mcfg = laguna.LagunaConfig.from_hf(dims, experts_held=dims["experts_held"], vocab_held=dims["vocab_held"])
    inf = deepspeed_tpu.init_inference(
        model_config=mcfg, params=weights.program_params(s.seed, dims, jnp.bfloat16), dtype=jnp.bfloat16,
        max_out_tokens=1024, mesh=make_mesh(MeshConfig(), devices=[device]), donate_params=True,
    )
    srv = ServingEngine(inf, config={"num_slots": 2, "max_len": 1024, "prefill_chunk": 256, "max_new_tokens": 16,
                                     "kvcache": {"enabled": True, "page_len": 128, "num_pages": 17}})
    rng = np.random.default_rng(s.seed)
    prompts = [rng.integers(1, dims["vocab_held"], n, dtype=np.int32) for n in (600, 131, 300)]
    ids = [srv.submit(p, max_new_tokens=8) for p in prompts]
    done = srv.drain()
    check((srv.prefill_compiles, srv.decode_compiles) == (1, 1),
          f"serve[laguna]: {srv.prefill_compiles} prefill / {srv.decode_compiles} decode executables")
    stats = srv.stats()
    moe, groups = stats["moe"], stats["kvcache"]["groups"]
    check(moe["dropped_assignments"] == 0 and moe["assignments_computed"] > 0, f"serve[laguna]: expert counters {moe}")
    check((groups["full"]["layers"], groups["window"]["layers"], groups["window"]["pages_per_slot"], groups["window"]["positions_per_slot"]) == (2, 3, 3, 384)
          and groups["window"]["bytes"] == 2 * 3 * (1 + 2 * 3) * 2 * 128 * 128 * 2 and stats["swa_ring_positions"] == 384,
          f"serve[laguna]: the pool's two groups {groups}")
    check(stats["kvcache"]["reuse"].startswith("off:"), "serve[laguna]: prefix reuse is off and says so")
    gaps = served_gaps(Reference(dims, s.seed), [{"prompt": p, "generated": list(done[i].generated)}
                                                 for p, i in zip(prompts, ids)], 256)
    check(gaps["token_gap_max"] <= TOL_LAGUNA_TOKEN_GAP,
          f"serve[laguna]: an emitted token lies {gaps['token_gap_max']:.4f} under the reference's best logit (tolerance {TOL_LAGUNA_TOKEN_GAP})")
    expect_kernels(mosaic_kernels(srv.compiled_step("decode").as_text()),
                   ["flash_decode_paged", "swa_decode_paged", "moe_grouped_matmul"] if s.mosaic else [], "serve[laguna] decode")
    expect_kernels(mosaic_kernels(srv.compiled_step("prefill").as_text()),
                   ["flash_chunk_paged", "moe_grouped_matmul"] if s.mosaic else [], "serve[laguna] prefill")
    check(stats["chunk_attention_kernel"] is bool(s.mosaic) and stats["gqa_prefill_form"].startswith("flash_chunk_paged" if s.mosaic else "blockwise jnp"),
          f"serve[laguna]: stats() say of the full layers' chunk: {stats['gqa_prefill_form']!r}")
    check(stats["swa_decode_form"].startswith("swa_decode_paged" if s.mosaic else "jnp over the ring") and stats["swa_chunk_form"].startswith("banded jnp"),
          f"serve[laguna]: stats() say of the window layers: decode {stats['swa_decode_form']!r}, chunk {stats['swa_chunk_form']!r}")
    say(f"serve[laguna]: 3 requests x 8 tokens through both page groups, token gap mean {gaps['token_gap_mean']:.5f} "
        f"max {gaps['token_gap_max']:.5f} over {gaps['tokens']} tokens")
    return gaps


def run(s: Smoke, devices: Sequence) -> None:
    """Every phase, in order; raises on the first check that fails."""
    if s.mosaic:
        check_flash_attention(s)
        check_fused_update(s)
        check_swa_decode_paged(s)
        check_mimo_decode_paged(s)
        check_dsa_sparse_decode(s)
        check_flash_chunk_paged(s)
    result = train(s, devices[:1])
    if len(devices) > 1:
        # the same 16 sequences on one chip (above) and on all of them
        many = train(s, devices)
        for i, (a, b) in enumerate(zip(result["losses"], many["losses"])):
            check(abs(a - b) <= TOL_LOSS_ACROSS_MESHES * abs(a),
                  f"step {i + 1} loss {b:.5f} on {len(devices)} devices vs {a:.5f} on one (tolerance {TOL_LOSS_ACROSS_MESHES})")
        say(f"train: losses on 1 and {len(devices)} devices agree within {TOL_LOSS_ACROSS_MESHES}")
    serve(s, devices[0])
    serve_deepseek_v2(s, devices[0])
    serve_solar_open2(s, devices[0])
    serve_keye(s, devices[0])
    serve_gigachat35(s, devices[0])
    serve_laguna(s, devices[0])


def main() -> int:
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {device['platform']!r} "
              f"({device['kind']} x {device['count']})", file=sys.stderr)
        return 2
    say(f"device: {device}")
    cache_hits = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_hits.update([event.rsplit("/", 1)[-1]])
        if event.startswith("/jax/compilation_cache/cache_") else None
    )
    say(f"compile cache: {setup_compile_cache()}")
    run(FULL, devices)
    say(f"compile cache: {cache_hits['cache_hits']} hits, {cache_hits['cache_misses']} misses")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    say(f"peak HBM in use on a device: {peak / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
