"""Pallas kernel suite tests (docs/kernels.md, ISSUE 12).

Coverage: flash-decode bit/tolerance parity vs the lax ``cache_attention``
ground truth over the (dtype, context, block) grid — int8 codes
dequantized in-register, per-slot positions, padding masks, scalar pos;
fused Adam/LAMB update parity incl. the in-producer overflow skip and
the ragged-leaf XLA fallback; the engine-level fused-update seam
(trajectory parity against the stock XLA path); autotuner cache
round-trip, corrupt-cache fallback-to-defaults, mode semantics, and the
LRU; serving churn parity with the kernel armed (decode_compiles still
== 1 under armed ds_san).

Off-TPU every kernel runs under ``interpret=True`` — the same kernel
body, so the parity statements carry to hardware modulo MXU rounding.
"""
import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.kernels import autotune as at
from deepspeed_tpu.ops.kernels import flash_decode as fd
from deepspeed_tpu.ops.kernels import fused_update as fu
from deepspeed_tpu.ops.transformer.inference import _kv_quant, cache_attention

pytestmark = pytest.mark.kernels


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), dtype)


def _int8_cache(k, v):
    kq, ks = _kv_quant(k)
    vq, vs = _kv_quant(v)
    return {"q": kq, "s": ks}, {"q": vq, "s": vs}


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# flash decode: parity vs the lax reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("S", [128, 256])
@pytest.mark.parametrize("block_k", [128, 256])
@pytest.mark.parametrize("block_slots", [1, 2])
def test_flash_decode_parity_cells(kv, S, block_k, block_slots):
    B, H, d = 4, 3, 64
    q = _rand((B, H, 1, d), jnp.float32, seed=1)
    k = _rand((B, H, S, d), jnp.float32, seed=2)
    v = _rand((B, H, S, d), jnp.float32, seed=3)
    if kv == "bf16":
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        kc, vc = k, v
    elif kv == "int8":
        kc, vc = _int8_cache(k, v)
    else:
        kc, vc = k, v
    # per-slot positions incl. the edges (fresh slot at 0, full cache)
    pos = jnp.asarray([0, S // 3, S - 1, 7], jnp.int32)
    ref = cache_attention(q, kc, vc, pos, use_kernel=False)
    out = fd.flash_decode(
        q, kc, vc, pos, block_k=block_k, block_slots=block_slots, interpret=True
    )
    assert out.shape == ref.shape and out.dtype == ref.dtype
    assert _max_err(ref, out) < 2e-5, (kv, S, block_k, block_slots)


def test_flash_decode_scalar_pos_and_padding_mask():
    B, H, S, d = 2, 4, 128, 16
    q = _rand((B, H, 1, d), jnp.float32, seed=4)
    k = _rand((B, H, S, d), jnp.float32, seed=5)
    v = _rand((B, H, S, d), jnp.float32, seed=6)
    mask = jnp.asarray(
        np.random.default_rng(7).integers(0, 2, (B, S)), bool
    ).at[:, 0].set(True)
    ref = cache_attention(q, k, v, 64, key_padding_mask=mask, use_kernel=False)
    out = fd.flash_decode(q, k, v, 64, key_padding_mask=mask, interpret=True)
    assert _max_err(ref, out) < 2e-5
    # and through a jit with a traced scalar pos (generate()'s form)
    f = jax.jit(lambda q, k, v, p: fd.flash_decode(q, k, v, p, interpret=True))
    out2 = f(q, k, v, jnp.int32(64))
    assert _max_err(cache_attention(q, k, v, jnp.int32(64), use_kernel=False), out2) < 2e-5


def test_flash_decode_contract_errors():
    q = _rand((2, 2, 1, 16))
    k = _rand((2, 2, 128, 16))
    with pytest.raises(ValueError, match="one query"):
        fd.flash_decode(_rand((2, 2, 2, 16)), k, k, 0, interpret=True)
    with pytest.raises(ValueError, match="decode_supported"):
        fd.flash_decode(q, _rand((2, 2, 96, 16)), _rand((2, 2, 96, 16)), 0, interpret=True)
    assert not fd.decode_supported(2, 2, 96, 16)   # ragged S
    assert not fd.decode_supported(2, 2, 64, 16)   # S < 128
    assert fd.decode_supported(8, 12, 2048, 64)


def test_cache_attention_dispatch_honors_env(monkeypatch):
    """DS_KERNELS=1 routes T=1 cache_attention through the kernel; tiny
    caches (S<128) and prefill (T>1) stay on the lax path."""
    from deepspeed_tpu.ops.kernels import flash_decode as fd_mod

    calls = []
    real = fd_mod.flash_decode
    monkeypatch.setattr(
        fd_mod, "flash_decode",
        lambda *a, **kw: calls.append(1) or real(*a, **kw),
    )
    monkeypatch.setenv("DS_KERNELS", "1")
    B, H, S, d = 2, 2, 128, 16
    q, k, v = _rand((B, H, 1, d)), _rand((B, H, S, d)), _rand((B, H, S, d))
    ref = cache_attention(q, k, v, jnp.asarray([3, 50], jnp.int32), use_kernel=False)
    out = cache_attention(q, k, v, jnp.asarray([3, 50], jnp.int32))
    assert calls == [1]
    assert _max_err(ref, out) < 2e-5
    # prefill shape: no kernel call
    cache_attention(_rand((B, H, 4, d)), k, v, 0)
    assert calls == [1]
    # too-small cache: lax fallback
    cache_attention(q, _rand((B, H, 64, d)), _rand((B, H, 64, d)), 0)
    assert calls == [1]
    monkeypatch.setenv("DS_KERNELS", "0")
    cache_attention(q, k, v, jnp.asarray([3, 50], jnp.int32))
    assert calls == [1]


# ---------------------------------------------------------------------------
# fused optimizer update
# ---------------------------------------------------------------------------

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        # kernel-eligible bf16 leaf (lane-aligned), ragged fp32 leaf
        "w": jnp.asarray(rng.standard_normal((64, 256)), jnp.bfloat16),
        "b": jnp.asarray(rng.standard_normal((100,)), jnp.float32),
    }


def _grads_like(params, seed=1):
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), p.dtype), params
    )


def _update_opt(opt_kind):
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
    from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb

    if opt_kind == "lamb":
        return FusedLamb(lr=1e-2, weight_decay=0.01)
    # "adamw" decouples the decay; any other name ("adam", "adam_l2") adds it to the gradient
    return FusedAdam(lr=1e-2, weight_decay=0.01, adam_w_mode=(opt_kind == "adamw"))


@pytest.mark.parametrize("opt_kind", ["adamw", "adam_l2", "lamb"])
def test_fused_update_trajectory_parity(opt_kind):
    opt = _update_opt(opt_kind)
    params = _tree()
    grads = _grads_like(params)
    st_ref, p_ref = opt.init(params), params
    st_k, p_k = opt.init(params), params
    for _ in range(3):
        upd, st_ref = opt.update(grads, st_ref, p_ref, lr=jnp.float32(1e-2))
        p_ref = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype), p_ref, upd
        )
        res = fu.engine_update(opt, grads, st_k, p_k, jnp.float32(1e-2), None, interpret=True)
        assert res is not None
        p_k, st_k = res
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_k)):
        assert _max_err(a, b) < 1e-5
    for a, b in zip(jax.tree.leaves(st_ref.exp_avg), jax.tree.leaves(st_k.exp_avg)):
        assert _max_err(a, b) < 1e-6
    assert int(st_k.step) == 3


def test_fused_update_overflow_skip_preserves_state():
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam

    opt = FusedAdam(lr=1e-2)
    params = _tree()
    st = opt.init(params)
    bad = jax.tree.map(lambda g: g.at[(0,) * g.ndim].set(jnp.inf), _grads_like(params))
    p_k, st_k = fu.engine_update(
        opt, bad, st, params, jnp.float32(1e-2), jnp.bool_(True), interpret=True
    )
    for a, b in zip(jax.tree.leaves(p_k), jax.tree.leaves(params)):
        assert bool(jnp.all(a == b))
    for a, b in zip(jax.tree.leaves(st_k.exp_avg), jax.tree.leaves(st.exp_avg)):
        assert bool(jnp.all(a == b))
    assert int(st_k.step) == 0  # skipped steps don't count


def test_fused_update_ineligible_optimizers_return_none():
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam, SGD

    params = _tree()
    grads = _grads_like(params)
    sgd = SGD(lr=1e-2)
    assert fu.engine_update(sgd, grads, sgd.init(params), params, 1e-2, None) is None
    a8 = FusedAdam(lr=1e-2, state_precision="8bit")
    assert fu.engine_update(a8, grads, a8.init(params), params, 1e-2, None) is None


# the leaf shapes the two train configurations hold (GPT-2 Large and XL:
# fc_w, fc_proj_w, qkv_w, proj_w, a stacked bias, the odd-row
# embedding), scaled down with the ratios kept; True where the rule of
# docs/kernels.md gives the leaf to the Pallas kernels
_L, _D, _V = 2, 128, 257
_VIEW_LEAVES = {
    "L_D_4D": ((_L, _D, 4 * _D), True),
    "L_4D_D": ((_L, 4 * _D, _D), True),
    "L_D_3D": ((_L, _D, 3 * _D), True),
    "L_D_D": ((_L, _D, _D), True),
    "L_3D_bias": ((_L, 3 * _D), False),
    "V_D_odd_rows": ((_V, _D), False),
}


@pytest.mark.parametrize("overflow", [False, True], ids=["step", "overflow"])
@pytest.mark.parametrize("opt_kind", ["adam", "adamw", "lamb"])
@pytest.mark.parametrize("leaf", list(_VIEW_LEAVES))
def test_fused_update_leaf_view_parity(leaf, opt_kind, overflow, monkeypatch):
    """engine_update against the XLA update, leaf shape by leaf shape,
    with a block budget small enough that the grid has all three axes
    (lead, row blocks, split columns)."""
    monkeypatch.setattr(fu, "_BLOCK_ELEMS", 8 * 256)
    shape, on_kernel = _VIEW_LEAVES[leaf]
    opt = _update_opt(opt_kind)
    params = {"x": _rand(shape, seed=4)}
    grads = {"x": _rand(shape, seed=5)}
    # moments of a step already taken, so the kernels read non-zero state
    _, st = opt.update(grads, opt.init(params), params, lr=jnp.float32(1e-2))
    split = {}
    if overflow:
        bad = {"x": grads["x"].at[(0,) * len(shape)].set(jnp.inf)}
        p_k, st_k = fu.engine_update(
            opt, bad, st, params, jnp.float32(1e-2), jnp.bool_(True), interpret=True, split=split
        )
        assert bool(jnp.all(p_k["x"] == params["x"]))
        assert bool(jnp.all(st_k.exp_avg["x"] == st.exp_avg["x"]))
        assert bool(jnp.all(st_k.exp_avg_sq["x"] == st.exp_avg_sq["x"]))
        assert int(st_k.step) == int(st.step)
    else:
        upd, st_ref = opt.update(grads, st, params, lr=jnp.float32(1e-2))
        p_k, st_k = fu.engine_update(
            opt, grads, st, params, jnp.float32(1e-2), None, interpret=True, split=split
        )
        assert _max_err(p_k["x"], params["x"] + upd["x"]) < 1e-5
        assert _max_err(st_k.exp_avg["x"], st_ref.exp_avg["x"]) < 1e-6
        assert _max_err(st_k.exp_avg_sq["x"], st_ref.exp_avg_sq["x"]) < 1e-6
        assert int(st_k.step) == int(st_ref.step)
    n = math.prod(shape)
    assert split == ({"pallas_elems": n, "xla_elems": 0} if on_kernel else {"pallas_elems": 0, "xla_elems": n})


@pytest.mark.parametrize("opt_kind", ["adam", "lamb"])
@pytest.mark.parametrize("shape", [(_L, _D, 4 * _D), (4 * _D, _D), (2, _L, _D, 3 * _D)], ids=str)
def test_fused_update_takes_the_leaf_in_its_own_layout(shape, opt_kind):
    """No relayout round the kernels: every leaf-sized operand and result
    of each pallas_call keeps the leaf's last two dimensions, and no
    reshape of the leaf touches them (on the TPU they are the tiled ones;
    a (rows, 256) view cost a pass over every operand — PERF.md, PR 27)."""
    opt = _update_opt(opt_kind)
    params = {"x": _rand(shape)}
    st = opt.init(params)
    jaxpr = jax.make_jaxpr(
        lambda g, st, p: fu.engine_update(opt, g, st, p, jnp.float32(1e-2), jnp.bool_(False), interpret=False)
    )(params, st, params)
    n, minor = math.prod(shape), shape[-2:]
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == (1 if opt_kind == "adam" else 2)
    for e in calls:
        leaf_sized = [v.aval for v in (*e.invars, *e.outvars) if v.aval.size == n]
        assert len(leaf_sized) >= 3  # fused_lamb_apply: p, the direction, the new p
        assert all(a.shape[-2:] == minor for a in leaf_sized), [a.shape for a in leaf_sized]
    for e in jaxpr.eqns:
        if e.primitive.name in ("reshape", "transpose", "copy") and e.invars[0].aval.size == n:
            assert e.primitive.name == "reshape"
            assert e.invars[0].aval.shape[-2:] == e.outvars[0].aval.shape[-2:] == minor


@pytest.mark.parametrize("shape,dtype,want", [
    # GPT-2 Large (benchmark/configs/gpt2-large-train-1chip.json)
    ((36, 1280, 5120), "float32", ((36, 1280, 5120), (16, 5120))),
    ((36, 5120, 1280), "float32", ((36, 5120, 1280), (64, 1280))),
    ((36, 1280, 3840), "float32", ((36, 1280, 3840), (16, 3840))),
    ((36, 1280, 1280), "float32", ((36, 1280, 1280), (64, 1280))),
    ((1024, 1280), "float32", ((1, 1024, 1280), (64, 1280))),
    ((50257, 1280), "float32", None),    # wte: odd rows
    ((36, 5120), "float32", None),       # stacked bias: 36 rows are not whole tiles
    ((1280,), "float32", None),
    # GPT-2 XL
    ((48, 1600, 6400), "float32", ((48, 1600, 6400), (8, 6400))),
    ((48, 6400, 1600), "float32", None),  # 1600 is 12.5 lanes: XL keeps only fc_w on the kernel
    # bf16 tiles are 16 rows deep
    ((4, 64, 256), "bfloat16", ((4, 64, 256), (64, 256))),
    ((4, 24, 256), "bfloat16", None),
    # a last dimension wider than a tile-row of the budget is split
    ((16, 8 * 12288), "float32", ((1, 16, 8 * 12288), (8, 12288))),
    ((2, 3, 8, 128), "float32", ((6, 8, 128), (8, 128))),
    ((8, 100), "float32", None),
    ((0, 128), "float32", None),
], ids=str)
def test_fused_update_leaf_view_rule(shape, dtype, want):
    assert fu._leaf_view(shape, (jnp.dtype(dtype), jnp.float32)) == want
    if want is not None:
        (_, rows, cols), (br, bc) = want
        assert rows % br == 0 and cols % bc == 0 and br * bc <= fu._BLOCK_ELEMS


def test_shared_update_body_numpy_matches_jax():
    """ONE update body, three executors: the numpy execution (the
    ZeRO-Offload drain's cpu_adam fallback) must match the jnp one."""
    rng = np.random.default_rng(3)
    p = rng.standard_normal((32, 256)).astype(np.float32)
    g = rng.standard_normal((32, 256)).astype(np.float32)
    m = np.zeros_like(p)
    v = np.zeros_like(p)
    args = (0.01, 0.9, 0.999, 1e-8, 0.01, True, 1 - 0.9, 1 - 0.999)
    pn_np, mn_np, vn_np = fu.adam_update_reference(np, p, g, m, v, *args)
    pn_j, mn_j, vn_j = fu.adam_update_reference(
        jnp, jnp.asarray(p), jnp.asarray(g), jnp.asarray(m), jnp.asarray(v), *args
    )
    np.testing.assert_allclose(pn_np, np.asarray(pn_j), rtol=1e-6)
    np.testing.assert_allclose(vn_np, np.asarray(vn_j), rtol=1e-6)


def test_engine_train_parity_with_fused_update(monkeypatch):
    """The _apply_update seam end-to-end: a tiny engine trained with the
    fused-update kernel armed matches the stock XLA path's loss
    trajectory (and the overflow machinery still composes)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    cfg = dataclasses.replace(gpt2.GPT2_TINY, remat=False)
    model_fn, init_fn, tp_fn = gpt2.make_model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "steps_per_print": 1000,
    }
    # conftest's 8 virtual devices: batch = gas(1) x micro_bs(2) x dp(8)
    batch = {
        "input_ids": np.random.default_rng(0).integers(
            0, cfg.vocab_size, (16, 32), dtype=np.int32
        )
    }

    def run(env):
        monkeypatch.setenv("DS_KERNELS", env)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model_fn, model_parameters=init_fn(seed=11), config=config,
            tp_spec_fn=tp_fn,
        )
        return [float(eng.train_batch(batch)) for _ in range(3)]

    ref = run("0")
    fused = run("1")
    np.testing.assert_allclose(ref, fused, rtol=2e-4)


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def test_autotune_defaults_are_deterministic():
    a = at.default_blocks("flash_decode", S=16384, int8=True, B=4)
    b = at.default_blocks("flash_decode", S=16384, int8=True, B=4)
    assert a == b
    assert a["block_k"] >= 512  # long context takes the big block
    assert at.default_blocks("flash_decode", S=128, B=1)["block_k"] == 128
    assert at.default_blocks("flash_attention", sq=1024)["block_q"] == 512
    # the fused update derives its block from each leaf's shape: no table
    for kind in ("nope", "fused_update"):
        with pytest.raises(KeyError):
            at.default_blocks(kind)


def test_autotune_cache_roundtrip(tmp_path):
    path = str(tmp_path / "kernel_autotune.json")
    tuner = at.Autotuner(path=path, mode="force")
    timings = {128: 0.004, 256: 0.002, 512: 0.009}
    picked = tuner.tune(
        "flash_decode",
        lambda blocks: timings[blocks["block_k"]],
        candidates=[{"block_k": k, "block_slots": 1} for k in timings],
        S=256, int8=False, B=4,
    )
    assert picked == {"block_k": 256, "block_slots": 1}
    # a FRESH tuner over the same file (new process twin) hits the cache
    tuner2 = at.Autotuner(path=path, mode="cache")
    assert tuner2.blocks_for("flash_decode", S=256, int8=False, B=4) == picked
    assert tuner2.stats()["entries"] == 1 and tuner2.stats()["hits"] == 1
    # cache mode returns the cached winner without calling the timer
    assert tuner2.tune(
        "flash_decode", lambda b: (_ for _ in ()).throw(AssertionError("measured")),
        S=256, int8=False, B=4,
    ) == picked
    # LRU hit path (second lookup never re-reads disk)
    assert tuner2.blocks_for("flash_decode", S=256, int8=False, B=4) == picked
    assert tuner2.stats()["hits"] == 3


def test_autotune_corrupt_cache_falls_back_to_defaults(tmp_path):
    path = str(tmp_path / "kernel_autotune.json")
    with open(path, "w") as f:
        f.write("{ this is not json")
    tuner = at.Autotuner(path=path, mode="cache")
    blocks = tuner.blocks_for("flash_decode", S=256, int8=False, B=4)
    assert blocks == at.default_blocks("flash_decode", S=256, int8=False, B=4)
    assert tuner.stats()["cache_ok"] is False
    # a tune over a corrupt cache never overwrites the unreadable file
    tuner.record("fp", {"block_k": 128}, 1.0)
    with open(path) as f:
        assert f.read().startswith("{ this is not json")
    # structurally-invalid JSON degrades the same way
    path2 = str(tmp_path / "k2.json")
    with open(path2, "w") as f:
        json.dump({"entries": {"fp": {"no_blocks": 1}}}, f)
    t2 = at.Autotuner(path=path2, mode="cache")
    assert t2.blocks_for("flash_attention") == at.default_blocks("flash_attention")
    assert t2.stats()["cache_ok"] is False


def test_autotune_off_mode_ignores_cache(tmp_path):
    path = str(tmp_path / "kernel_autotune.json")
    force = at.Autotuner(path=path, mode="force")
    force.record(at.fingerprint("flash_attention"), {"block_q": 1024, "block_k": 1024}, 1.0)
    off = at.Autotuner(path=path, mode="off")
    assert off.blocks_for("flash_attention") == at.default_blocks("flash_attention")
    assert off.tune("flash_attention", lambda b: 0.0) == at.default_blocks("flash_attention")


def test_autotune_failed_candidates_degrade(tmp_path):
    tuner = at.Autotuner(path=str(tmp_path / "k.json"), mode="force")

    def bad_timer(blocks):
        raise RuntimeError("grid refused")

    assert tuner.tune("flash_attention", bad_timer) == at.default_blocks("flash_attention")


def test_autotune_env_mode_escape_hatch(monkeypatch):
    monkeypatch.setenv("DS_KERNEL_AUTOTUNE", "off")
    assert at.autotune_mode() == "off"
    monkeypatch.setenv("DS_KERNEL_AUTOTUNE", "bogus")
    assert at.autotune_mode() == "cache"  # typo never flips CI to tuning
    monkeypatch.delenv("DS_KERNEL_AUTOTUNE")
    assert at.autotune_mode() == "cache"


def test_fingerprint_keys_on_jaxlib_and_topology():
    fp = at.fingerprint("flash_decode", S=256, int8=True)
    assert "jaxlib=" in fp and "topo=" in fp and "S=256" in fp
    assert fp != at.fingerprint("flash_decode", S=512, int8=True)


# ---------------------------------------------------------------------------
# serving churn with the kernel armed (compile stability + parity)
# ---------------------------------------------------------------------------

def test_serving_churn_parity_with_kernel_armed(monkeypatch):
    """The serving acceptance proof with DS_KERNELS=1: a churning live
    set still runs against exactly ONE decode executable under an armed
    ds_san (the kernel is inside the trace, not a new signature), and
    greedy outputs bit-match the engine's solo generate() — which runs
    the SAME armed kernel path."""
    import deepspeed_tpu
    from deepspeed_tpu.analysis.sanitizer import core as san_core
    from deepspeed_tpu.analysis.sanitizer.core import Sanitizer
    from deepspeed_tpu.config.config import SanitizerConfig
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.serving import ServingEngine

    monkeypatch.setenv("DS_KERNELS", "1")
    cfg = dataclasses.replace(gpt2.GPT2_TINY, remat=False)
    params = gpt2.init_params(cfg, seed=7)
    params["wpe"] = params["wpe"] * 40.0  # position-sensitive
    eng = deepspeed_tpu.init_inference(
        model_config=cfg, params=params, dtype=jnp.float32,
        max_out_tokens=cfg.n_positions,
    )
    san = san_core.install(Sanitizer(SanitizerConfig.from_dict(
        {"enabled": True, "checkers": ["recompile", "transfer"], "compile_budget": 2}
    )))
    try:
        srv = ServingEngine(eng, num_slots=2, prefill_chunk=32, max_len=128,
                            max_new_tokens=4)
        rng = np.random.default_rng(8)
        prompts = [
            rng.integers(1, cfg.vocab_size, n, dtype=np.int32)
            for n in (40, 9, 17, 5)
        ]
        rids = [srv.submit(prompts[0], max_new_tokens=4),
                srv.submit(prompts[1], max_new_tokens=3)]
        srv.step()
        rids += [srv.submit(p, max_new_tokens=3) for p in prompts[2:]]
        res = srv.drain(max_steps=200)
        assert sorted(res) == sorted(rids)
        assert srv.decode_compiles == 1 and srv.prefill_compiles == 1
        counts = san.recompile.compile_counts()
        assert counts.get("serving.decode") == 1, counts
        assert san.findings == [], [f.format() for f in san.findings]
    finally:
        san_core.uninstall()
    for rid, prompt in zip(rids, prompts):
        n_new = 4 if rid == rids[0] else 3
        solo = np.asarray(eng.generate(prompt[None, :], max_new_tokens=n_new))[0]
        np.testing.assert_array_equal(res[rid].tokens(), solo)


def test_kernels_report_shape(monkeypatch):
    from deepspeed_tpu.ops import kernels as k

    monkeypatch.setenv("DS_KERNELS", "1")
    rep = k.kernels_report()
    assert rep["suite_armed"] is True and rep["flash_decode"] is True
    assert {"mode", "path", "entries", "hits"} <= set(rep["autotune"])
    monkeypatch.setenv("DS_KERNELS", "0")
    assert k.kernels_report()["suite_armed"] is False
