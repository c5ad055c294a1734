"""FLOPs profiler tests (reference tests/unit/test_flops_profiler.py —
but against XLA cost analysis instead of functional patching)."""
import dataclasses

import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.profiling import analyze_fn, get_model_profile, see_memory_usage


def test_analyze_matmul_flops():
    a = jnp.zeros((128, 256), jnp.float32)
    b = jnp.zeros((256, 512), jnp.float32)
    cost = analyze_fn(lambda x, y: x @ y, a, b)
    expect = 2 * 128 * 256 * 512  # mul + add
    assert abs(cost["flops"] - expect) / expect < 0.1, cost


def test_get_model_profile_gpt2():
    cfg = dataclasses.replace(gpt2.GPT2_TINY, remat=False)
    params = gpt2.init_params(cfg)
    toks = np.zeros((2, 32), np.int32)
    flops, macs, n_params = get_model_profile(
        lambda p, t: gpt2.apply(p, jnp.asarray(t), cfg, deterministic=True),
        args=(params, toks),
        params=params,
        print_profile=False,
    )
    assert flops > 0 and macs == flops / 2
    assert n_params == sum(int(np.prod(v.shape)) for v in __import__("jax").tree.leaves(params))
    # transformer fwd flops should be within 3x of the 2*params*tokens rule
    # of thumb (tiny models are embedding/logit-dominated, hence the slack)
    rough = 2 * n_params * 2 * 32
    assert flops > rough / 3


def test_engine_profile_step(capsys, cpu_peak):
    cfg = dataclasses.replace(gpt2.GPT2_TINY, remat=False)
    model_fn, init_fn, tp_fn = gpt2.make_model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "flops_profiler": {"enabled": True, "profile_step": 2},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=init_fn(), config=config, tp_spec_fn=tp_fn
    )
    batch = {"input_ids": np.zeros((16, 16), np.int32)}
    for _ in range(3):
        engine.train_batch(batch)
    res = engine.flops_profiler.results
    assert res.get("step") == 2
    assert res["flops_per_step"] > 0
    assert res["latency_s"] > 0
    assert 0 <= res["mfu"] < 10  # sane range (CPU peak is a rough constant)


def test_get_model_profile_as_string_and_bytes():
    cfg = dataclasses.replace(gpt2.GPT2_TINY, remat=False)
    params = gpt2.init_params(cfg)
    toks = np.zeros((1, 16), np.int32)
    f_s, m_s, p_s = get_model_profile(
        lambda p, t: gpt2.apply(p, jnp.asarray(t), cfg, deterministic=True),
        args=(params, toks), params=params, print_profile=False, as_string=True,
    )
    assert f_s.endswith("FLOPs") and m_s.endswith("MACs")
    cost = analyze_fn(
        lambda p, t: gpt2.apply(p, jnp.asarray(t), cfg, deterministic=True),
        params, toks,
    )
    assert cost["bytes_accessed"] > 0  # HBM side of the profile is real too


def test_see_memory_usage_reports_nonzero_on_cpu():
    # keep a live device buffer so the CPU fallback (live-array shard
    # accounting — PJRT:CPU has no memory_stats) has something to count
    keep = jnp.ones((128, 128), jnp.float32)
    out = see_memory_usage("test")
    assert isinstance(out, dict)
    dev = sum(v for k, v in out.items() if k.endswith("/bytes_in_use"))
    assert dev >= keep.nbytes  # real per-device stats, not silent zeros
