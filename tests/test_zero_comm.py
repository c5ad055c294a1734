"""ZeRO collective-byte regression tests (VERDICT r2 #2: the BASELINE
'ZeRO allgather BW' metric needs HLO-grounded byte accounting).

The analytic model (zero_step_comm_model) feeds the comm summary's
per-step byte model; these tests pin it against compiled-HLO byte counts
so the model can't drift from reality.  Caveats encoded here:

* XLA:CPU decomposes all-gather/reduce-scatter into all-reduce for some
  shapes, so per-op taxonomy is asserted loosely and TOTALS tightly;
* collectives inside ``lax.scan`` bodies appear once in HLO text but
  run per iteration — the test model unrolls its layer scan so every
  collective is visible to the text parser.
"""
import dataclasses

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime.zero.stages import zero_step_comm_model
from deepspeed_tpu.utils.hlo import collective_bytes, collective_bytes_by_op

FSDP = 8

TINY8 = dataclasses.replace(
    gpt2.GPT2_TINY, n_layer=8, n_embd=64, n_head=4, vocab_size=256,
    n_positions=64, scan_unroll=8, remat=True, use_flash_attention=False,
)


def _step_hlo_and_nparams(stage, gas=1):
    model_fn, init_fn, tp_fn = gpt2.make_model(TINY8)
    params = init_fn()
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage},
        "mesh": {"fsdp": FSDP},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "steps_per_print": 100000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=init_fn(), config=config, tp_spec_fn=tp_fn
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, TINY8.vocab_size, (gas * engine.mesh_info.dp_world_size, 32), dtype=np.int32
    )}
    engine.train_batch(batch)
    key = next(k for k in engine._compiled if isinstance(k, tuple) and k[0] == "train_batch")
    return engine._compiled[key].as_text(), n


def test_zero3_gather_traffic_is_param_sized():
    """Stage-3 per-step gather traffic is a small multiple of the bf16
    param bytes (fwd gather + remat-bwd regather + grad path) — the
    analytic model's regime.  Catches the two real failure modes:
    silently replicated params (traffic collapses to ~0) and a gather
    explosion (traffic ≫ a few × params)."""
    hlo, n = _step_hlo_and_nparams(stage=3)
    n_bf16 = 2 * n
    by = collective_bytes_by_op(hlo)
    ag = by.get("all-gather", 0) + by.get("all-reduce", 0)  # CPU may decompose
    model = zero_step_comm_model(n, FSDP, stage=3)
    assert model["all-gather"] == 2 * n_bf16
    # gather+grad traffic: at least the model's 2 passes, at most ~8
    # param-sized transfers (remat + fp32 grads + decomposition weights)
    assert 2 * n_bf16 <= ag <= 16 * n_bf16, (ag, n_bf16, by)


@pytest.mark.slow  # ~44s HLO compile; the sharding CI job runs test_zero_comm.py in full
def test_zero3_gas2_repeats_gathers_per_micro():
    """gas=2 runs the gather/reduce machinery per micro batch (the
    reference pays the same per-micro gathers, stage3.py:1394-1599).
    The micro loop is a ``lax.scan``, so its collectives appear ONCE in
    HLO text but execute per iteration — the static text must therefore
    still show the full per-micro traffic (i.e. the machinery was not
    hoisted out of the loop), not 2x of it."""
    hlo1, _ = _step_hlo_and_nparams(stage=3, gas=1)
    hlo2, _ = _step_hlo_and_nparams(stage=3, gas=2)
    t1, t2 = collective_bytes(hlo1), collective_bytes(hlo2)
    assert t2 >= 0.7 * t1, (t1, t2)
    assert "while" in hlo2  # the micro scan exists


def test_zero0_has_no_gather_bulk():
    """Stage 0 keeps params replicated: its collective traffic (grad
    all-reduce only) sits well below stage 3's gather+reduce total."""
    hlo3, n = _step_hlo_and_nparams(stage=3)
    hlo0, _ = _step_hlo_and_nparams(stage=0)
    t3, t0 = collective_bytes(hlo3), collective_bytes(hlo0)
    assert t0 < t3, (t0, t3)
    # stage-0 traffic ≈ one fp32 grad all-reduce (weight 2): ~8N bytes
    assert t0 <= 10 * n, (t0, n)


def _step_memory(stage):
    """Per-device memory analysis of the compiled train step."""
    model_fn, init_fn, tp_fn = gpt2.make_model(TINY8)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage},
        "mesh": {"fsdp": FSDP},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "steps_per_print": 100000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=init_fn(), config=config, tp_spec_fn=tp_fn
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, TINY8.vocab_size, (engine.mesh_info.dp_world_size, 32), dtype=np.int32
    )}
    engine.train_batch(batch)
    key = next(k for k in engine._compiled if isinstance(k, tuple) and k[0] == "train_batch")
    return engine._compiled[key].memory_analysis()


@pytest.mark.slow  # ~37s fsdp8 compile + live-range analysis; the sharding CI job runs test_zero_comm.py in full
def test_zero3_compiled_memory_is_sharded_at_fsdp8():
    """The regression this pins: GSPMD silently re-materializing the
    full param/opt tree under stage 3 (a bad sharding annotation makes
    the compiled step's per-device live ranges ≈ the replicated
    engine's, and single-chip benches would never notice).  Per-device
    ARGUMENT bytes (params + opt state + grads live ranges) must be a
    small fraction of stage 0's, and temps must not quietly re-create
    the difference."""
    m3 = _step_memory(3)
    m0 = _step_memory(0)
    a3, a0 = m3.argument_size_in_bytes, m0.argument_size_in_bytes
    t3, t0 = m3.temp_size_in_bytes, m0.temp_size_in_bytes
    # big leaves are 1/8 per device at stage 3; small leaves stay
    # replicated by design (stage3_param_persistence_threshold), so the
    # tiny test model only reaches ~0.45 — the regression this guards
    # is the ratio creeping to ~1.0
    assert a3 < 0.55 * a0, (a3, a0)
    # temps: stage-3 gathers are per-layer transients, so temp growth
    # over stage 0 must stay far below one full bf16 param tree — if
    # GSPMD ever re-materializes the whole gathered tree for the step's
    # duration, t3 jumps by ~full-params and this fires
    model_fn, init_fn, _ = gpt2.make_model(TINY8)
    full_param_bf16 = 2 * sum(int(np.prod(p.shape)) for p in jax.tree.leaves(init_fn()))
    assert t3 - t0 < 0.5 * full_param_bf16, (t3, t0, full_param_bf16)
