"""Test harness: single-process SPMD over 8 virtual CPU devices.

This replaces the reference's ``@distributed_test`` fork-per-rank
machinery (``tests/unit/common.py:16-104``): instead of N OS processes
over NCCL, tests run one process whose XLA "host platform" exposes 8
devices, and every collective/sharding path exercises the same GSPMD
code that runs on a real TPU slice (SURVEY.md §4 "what to replicate").
"""
import os

# Must be set before the CPU backend initializes (first jax array op).
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_TRACEBACK_FILTERING", "off")

# NOTE: the XLA persistent compilation cache is deliberately NOT enabled
# here.  On this class of virtualized CPU, machine-feature detection is
# unstable across processes, and XLA:CPU loads cached AOT executables
# compiled for a different feature set ("Machine type used for XLA:CPU
# compilation doesn't match ... could lead to execution errors such as
# SIGILL") — observed to silently corrupt optimizer numerics by ~1e-3.

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def cpu_peak(monkeypatch):
    """MFU and roofline tests bring a peak of their own: the CPU has no
    published one, so the peaks table (flops_profiler.DEVICE_PEAKS) has
    no row for it and production code reports no MFU here.  0.5 TFLOP/s
    over 100 GB/s puts the machine balance at 5 flops/byte, far enough
    from both the dryrun train matmuls (AI ~10) and the decode matvecs
    (AI ~1) that the pinned roofline verdicts are stable."""
    from deepspeed_tpu.profiling import flops_profiler

    monkeypatch.setitem(
        flops_profiler.DEVICE_PEAKS, "cpu",
        flops_profiler.DevicePeak(bf16_tflops=0.5, hbm_gbps=100.0, source="tests/conftest.py"),
    )


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


# Long-running tests (>~2.5s call time on the CI CPU mesh, measured with
# --durations=0), centrally marked so `pytest -m "not slow"` gives a
# fast sanity pass and the full suite stays the merge gate.  Regenerate
# by re-measuring when the set drifts.
_SLOW_TESTS = (
    "test_int8_weight_quantization_close",
    "test_onebit_checkpoint_at_freeze_boundary_and_rollback",
    "test_backward_matches_reference",
    "test_onebit_frozen_checkpoint_roundtrip",
    "test_flat_stages_match_stage0_numerics",
    "test_attention_mask_blocks_padding",
    "test_gpt2_tiny_trains",
    "test_flax_adapter_trains",
    "test_haiku_adapter_trains",
    "test_backward_rectangular_causal",
    "test_zero_infinity_nvme_moments",
    "test_true_int8_serving_close_and_packed",
    "test_zero_stages_agree",
    "test_train_batch_matches_micro_steps",
    "test_flat_plan_covers_awkward_leaves",
    "test_compressed_allreduce_approximates_mean",
    "test_lamb_optimizer",
    "test_pipeline_data_iterator_api",
    "test_forward_rectangular_blocks",
    "test_onebit_frozen_collective_bytes_drop_4x",
    "test_zero_stage_trains",
    "test_pipeline_convergence",
    "test_forward_matches_bert_block",
    "test_forward_matches_reference",
    "test_dropout_rng_determinism",
    "test_pld_drop_actually_skips_layers",
    "test_block_sparse_matches_masked_dense",
    "test_checkpoint_sequential_matches_plain_scan",
    "test_onebit_optimizers_train",
    "test_pipeline_train_matches_sequential_train",
    "test_onebit_engine_enters_frozen_phase_and_trains",
    "test_layer_wrapper_with_packed_weights",
    "test_int8_tp_serving",
    "test_1f1b_activation_memory_bounded_in_micro_batches",
    "test_1f1b_matches_gpipe_step",
    "test_flat_checkpoint_roundtrip_and_resize",
    "test_bias_matches_reference_fwd_and_grads",
    "test_dropout_matches_reference_with_same_mask",
    "test_bert_attention_dropout_trains",
    "test_roundtrip_across_optimizer_wrappers",
    "test_elastic_dp_resize",
    "test_tp_resize",
    "test_cifar",
    "test_3d_pipeline_with_onebit_adam",
    "test_moe_expert_parallel_matches_single_device",
    "test_cpu_adam_matches_fused_device_adam",
    "test_fp16_dynamic_loss_scale_overflow",
    "test_eigenvalue_power_iteration_quadratic",
    "test_tiny_shapes_fallback",
    "test_hf_bert_injection_matches_hf_encoder",
    "test_hf_gptneo_injection_matches_hf_forward",
    # 38 s of tier-1's 870 (re-measured 2026-09, PR 21, which added ~20 s
    # of chip-path tests); its BERT and GPT-Neo siblings are here already
    "test_hf_gpt2_injection_matches_hf_forward",
    "test_blockwise_xla_matches_reference",
    "test_scheduler_in_engine",
    "test_gradient_accumulation",
    "test_gating_dispatch_properties",
    "test_checkpoint_same_value_and_grad",
    "test_ring_attention_matches_dense",
    "test_get_model_profile_gpt2",
    "test_bf16_forward_close",
    "test_right_padded_mask_rejected_and_all_ones_fast_path",
    "test_seq_axis_one_falls_back",
    "test_dropout_zero_rate_is_exact_and_public_api_runs",
    "test_bias_dropout_causal_combined",
    "test_generation_left_padded_matches_unpadded",
    "test_moe_decode",
    "test_ulysses",
    "test_megatron_injection",
    "test_kv_cache",
    # multi-seed stress sweeps, re-run in full by the CI ds-race job
    "test_fixed_runtime_scenarios_green",
    "test_kv_scenario_green",
    # serving/fleet/kvcache/overlap integration tests >2.5s (re-measured
    # 2026-08; each file has a dedicated unfiltered CI job)
    "test_kill_one_of_three_zero_acknowledged_loss_bit_identical",
    "test_fleet_results_bit_match_solo_generate",
    "test_churn_parity_vs_solo_generate",
    "test_background_restart_overlaps_serving",
    "test_kill_mid_decode_restart_replays_bit_identical",
    "test_fault_site_replica_death_via_env_plan",
    "test_unrestartable_replica_refires_elsewhere",
    "test_routing_spreads_load_least_ttft",
    "test_fleet_session_stickiness_three_turns",
    "test_prefetched_losses_match_unprefetched",
    "test_hedge_fires_after_p99_delay_and_cancels_loser",
    "test_int8_kv_slot_pool",
    "test_train_step_compiles_exactly_once_across_varying_batches",
    "test_chunked_prefill_parity",
    "test_sampling_reproducible_across_slot_churn",
    "test_hung_drain_exits_1_not_43",
    "test_fault_site_router_route_recurring_latency",
    "test_hedge_disarmed_below_min_observations",
    "test_unfenced_default_omits_compute_but_keeps_host_phases",
    "test_compile_stability_churn_ds_san_clean",
    "test_client_key_dedup_survives_replica_crash",
    "test_kill_mid_async_commit_never_publishes_corrupt_tag",
    "test_sigterm_drains_inflight_save_before_emergency_exit_43",
    "test_mixed_pool_greedy_still_bit_matches_solo",
    "test_fault_site_router_hedge_blocks_hedging",
    "test_top_k_one_equals_greedy",
    "test_paged_engine_pinned_prefix_hits_first_traffic",
    "test_load_checkpoint_drains_inflight_save",
    "test_hedge_skipped_once_first_token_seen",
    "test_rebind_preserves_original_request_ids",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(name in item.nodeid for name in _SLOW_TESTS):
            item.add_marker(pytest.mark.slow)
