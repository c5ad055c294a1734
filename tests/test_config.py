"""Config-system tests (reference: tests/unit/test_config.py,
test_ds_config.py)."""
import pytest

from deepspeed_tpu.config.config import DeepSpeedConfig, DeepSpeedConfigError


def base_config(**overrides):
    d = {
        "train_batch_size": 16,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }
    d.update(overrides)
    return d


class TestBatchTriad:
    def test_full_triad(self):
        c = DeepSpeedConfig(
            {"train_batch_size": 32, "train_micro_batch_size_per_gpu": 4, "gradient_accumulation_steps": 2},
            world_size=4,
        )
        assert c.train_batch_size == 32
        assert c.train_micro_batch_size_per_gpu == 4
        assert c.gradient_accumulation_steps == 2

    def test_infer_gas(self):
        c = DeepSpeedConfig({"train_batch_size": 32, "train_micro_batch_size_per_gpu": 4}, world_size=2)
        assert c.gradient_accumulation_steps == 4

    def test_infer_micro(self):
        c = DeepSpeedConfig({"train_batch_size": 32, "gradient_accumulation_steps": 2}, world_size=2)
        assert c.train_micro_batch_size_per_gpu == 8

    def test_infer_train(self):
        c = DeepSpeedConfig(
            {"train_micro_batch_size_per_gpu": 4, "gradient_accumulation_steps": 2}, world_size=4
        )
        assert c.train_batch_size == 32

    def test_only_train(self):
        c = DeepSpeedConfig({"train_batch_size": 32}, world_size=4)
        assert c.train_micro_batch_size_per_gpu == 8
        assert c.gradient_accumulation_steps == 1

    def test_invalid_triad(self):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig(
                {"train_batch_size": 30, "train_micro_batch_size_per_gpu": 4, "gradient_accumulation_steps": 2},
                world_size=4,
            )

    def test_nothing_set(self):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig({"optimizer": {"type": "Adam"}}, world_size=1)


class TestUnknownKeys:
    def test_unknown_top_level(self):
        with pytest.raises(DeepSpeedConfigError, match="Unknown top-level"):
            DeepSpeedConfig(base_config(definitely_not_a_key=1))

    def test_unknown_zero_key(self):
        with pytest.raises(DeepSpeedConfigError, match="zero_optimization"):
            DeepSpeedConfig(base_config(zero_optimization={"stage": 2, "typo_key": True}))


class TestZeroConfig:
    def test_defaults(self):
        c = DeepSpeedConfig(base_config())
        assert c.zero_config.stage == 0
        assert not c.zero_enabled

    def test_stage3_with_offload(self):
        c = DeepSpeedConfig(
            base_config(
                zero_optimization={
                    "stage": 3,
                    "offload_optimizer": {"device": "cpu", "pin_memory": True},
                    "offload_param": {"device": "nvme", "nvme_path": "/tmp/nvme"},
                    "stage3_param_persistence_threshold": 1000,
                }
            )
        )
        assert c.zero_config.stage == 3
        assert c.zero_config.offload_optimizer.device == "cpu"
        assert c.zero_config.offload_param.device == "nvme"
        assert c.zero_config.param_persistence_threshold == 1000

    def test_legacy_cpu_offload(self):
        c = DeepSpeedConfig(base_config(zero_optimization={"stage": 2, "cpu_offload": True}))
        assert c.zero_config.offload_optimizer.device == "cpu"

    def test_bad_stage(self):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig(base_config(zero_optimization={"stage": 5}))


class TestPrecision:
    def test_bf16(self):
        c = DeepSpeedConfig(base_config(bf16={"enabled": True}))
        assert c.compute_dtype == "bfloat16"

    def test_fp16_dynamic(self):
        c = DeepSpeedConfig(base_config(fp16={"enabled": True}))
        assert c.fp16.dynamic_loss_scale

    def test_fp16_static(self):
        c = DeepSpeedConfig(base_config(fp16={"enabled": True, "loss_scale": 128}))
        assert not c.fp16.dynamic_loss_scale
        assert c.fp16.loss_scale == 128

    def test_both_fails(self):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig(base_config(fp16={"enabled": True}, bf16={"enabled": True}))


class TestMeshConfig:
    def test_default(self):
        c = DeepSpeedConfig(base_config())
        assert c.mesh.data == -1
        assert c.mesh.fsdp == 1

    def test_explicit(self):
        c = DeepSpeedConfig(base_config(mesh={"fsdp": 4, "model": 2}))
        assert c.mesh.fsdp == 4
        assert c.mesh.model == 2


class TestBatchTriadCompletion:
    """Every auto-completion arm of the triad resolver, plus the exact
    failure messages (reference runtime/config.py:736-898 semantics)."""

    def test_micro_and_gas_completes_train(self):
        c = DeepSpeedConfig(
            {"train_micro_batch_size_per_gpu": 3, "gradient_accumulation_steps": 5}, world_size=2
        )
        assert (c.train_batch_size, c.train_micro_batch_size_per_gpu, c.gradient_accumulation_steps) == (30, 3, 5)

    def test_only_micro_completes_train_and_gas(self):
        c = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 4}, world_size=8)
        assert (c.train_batch_size, c.gradient_accumulation_steps) == (32, 1)

    def test_train_and_gas_completes_micro(self):
        c = DeepSpeedConfig(
            {"train_batch_size": 24, "gradient_accumulation_steps": 3}, world_size=4
        )
        assert c.train_micro_batch_size_per_gpu == 2

    def test_train_and_micro_completes_gas(self):
        c = DeepSpeedConfig(
            {"train_batch_size": 24, "train_micro_batch_size_per_gpu": 2}, world_size=4
        )
        assert c.gradient_accumulation_steps == 3

    def test_inconsistent_full_triad_exact_error(self):
        with pytest.raises(DeepSpeedConfigError, match=r"Batch triad check failed: 32 != 4 \* 2 \* 2"):
            DeepSpeedConfig(
                {
                    "train_batch_size": 32,
                    "train_micro_batch_size_per_gpu": 4,
                    "gradient_accumulation_steps": 2,
                },
                world_size=2,
            )

    def test_train_not_divisible_by_micro_exact_error(self):
        with pytest.raises(
            DeepSpeedConfigError, match=r"train_batch_size \(30\) not divisible by micro_batch\*world_size \(4\*2\)"
        ):
            DeepSpeedConfig({"train_batch_size": 30, "train_micro_batch_size_per_gpu": 4}, world_size=2)

    def test_train_not_divisible_by_gas_exact_error(self):
        with pytest.raises(
            DeepSpeedConfigError, match=r"train_batch_size \(30\) not divisible by grad_accum\*world_size \(4\*2\)"
        ):
            DeepSpeedConfig({"train_batch_size": 30, "gradient_accumulation_steps": 4}, world_size=2)

    def test_train_not_divisible_by_world_size_exact_error(self):
        with pytest.raises(DeepSpeedConfigError, match=r"train_batch_size \(9\) not divisible by world_size \(4\)"):
            DeepSpeedConfig({"train_batch_size": 9}, world_size=4)

    def test_nothing_set_exact_error(self):
        with pytest.raises(DeepSpeedConfigError, match="At least one of train_batch_size"):
            DeepSpeedConfig({"optimizer": {"type": "Adam"}}, world_size=1)


class TestUnknownKeyNesting:
    """Unknown keys rejected at every nesting level, reported with the
    full dotted path and a nearest-key suggestion."""

    def test_top_level_with_suggestion(self):
        with pytest.raises(
            DeepSpeedConfigError, match=r"'gradient_cliping' \(did you mean 'gradient_clipping'\?\)"
        ):
            DeepSpeedConfig(base_config(gradient_cliping=1.0))

    def test_zero_block_path_and_suggestion(self):
        with pytest.raises(
            DeepSpeedConfigError,
            match=r"'zero_optimization\.reduce_buckett_size' \(did you mean 'reduce_bucket_size'\?\)",
        ):
            DeepSpeedConfig(base_config(zero_optimization={"stage": 2, "reduce_buckett_size": 1}))

    def test_doubly_nested_offload_path(self):
        with pytest.raises(
            DeepSpeedConfigError,
            match=r"'zero_optimization\.offload_param\.buffer_sz' \(did you mean 'buffer_size'\?\)",
        ):
            DeepSpeedConfig(
                base_config(
                    zero_optimization={"stage": 3, "offload_param": {"device": "cpu", "buffer_sz": 2}}
                )
            )

    def test_offload_optimizer_path(self):
        with pytest.raises(DeepSpeedConfigError, match=r"'zero_optimization\.offload_optimizer\.pinned'"):
            DeepSpeedConfig(
                base_config(
                    zero_optimization={"stage": 2, "offload_optimizer": {"device": "cpu", "pinned": True}}
                )
            )

    @pytest.mark.parametrize(
        "block,payload,expect",
        [
            ("fp16", {"enabled": True, "loss_scal": 0}, r"'fp16\.loss_scal' \(did you mean 'loss_scale'\?\)"),
            ("bf16", {"enable": True}, r"'bf16\.enable' \(did you mean 'enabled'\?\)"),
            ("optimizer", {"type": "Adam", "parms": {}}, r"'optimizer\.parms' \(did you mean 'params'\?\)"),
            ("scheduler", {"type": "WarmupLR", "prams": {}}, r"'scheduler\.prams' \(did you mean 'params'\?\)"),
            ("mesh", {"dta": 2}, r"'mesh\.dta' \(did you mean 'data'\?\)"),
            ("pipeline", {"stagess": 2}, r"'pipeline\.stagess' \(did you mean 'stages'\?\)"),
            ("aio", {"block_sz": 1}, r"'aio\.block_sz' \(did you mean 'block_size'\?\)"),
            (
                "activation_checkpointing",
                {"partition_activation": True},
                r"'activation_checkpointing\.partition_activation' \(did you mean 'partition_activations'\?\)",
            ),
            (
                "flops_profiler",
                {"profile_steps": 2},
                r"'flops_profiler\.profile_steps' \(did you mean 'profile_step'\?\)",
            ),
            ("tensorboard", {"output_pth": "x"}, r"'tensorboard\.output_pth' \(did you mean 'output_path'\?\)"),
            # the HLO-text attribution went with its fields (PR 30): an old config says so by name
            ("telemetry", {"attribution": False}, r"'telemetry\.attribution'"),
        ],
    )
    def test_every_block_reports_full_path(self, block, payload, expect):
        with pytest.raises(DeepSpeedConfigError, match=expect):
            DeepSpeedConfig(base_config(**{block: payload}))

    def test_stage3_aliases_still_accepted(self):
        c = DeepSpeedConfig(
            base_config(zero_optimization={"stage": 3, "stage3_max_live_parameters": 7})
        )
        assert c.zero_config.max_live_parameters == 7

    def test_quantize_training_bit_aliases_accepted(self):
        c = DeepSpeedConfig(base_config(quantize_training={"enabled": True, "start_bits": 8}))
        assert c.quantize_training.quantize_bits_start == 8

    def test_conflicting_alias_pair_raises(self):
        with pytest.raises(
            DeepSpeedConfigError,
            match=r"'zero_optimization\.stage3_max_live_parameters' and its alias "
            r"'zero_optimization\.max_live_parameters' are both set",
        ):
            DeepSpeedConfig(
                base_config(
                    zero_optimization={
                        "stage": 3,
                        "stage3_max_live_parameters": 7,
                        "max_live_parameters": 9,
                    }
                )
            )

    def test_conflicting_quantize_bits_alias_raises(self):
        with pytest.raises(DeepSpeedConfigError, match=r"'quantize_training\.quantize_bits_start' and its alias"):
            DeepSpeedConfig(
                base_config(quantize_training={"enabled": True, "quantize_bits_start": 8, "start_bits": 8})
            )
