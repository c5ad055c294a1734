"""Typed config system.

Parses the reference's JSON config surface (``runtime/config.py:655``
``DeepSpeedConfig`` and its ~80 ``get_*`` accessors, defaults in
``runtime/constants.py``) into typed dataclasses.  Differences from the
reference, per the TPU design stance (SURVEY.md §5.6):

* unknown keys raise instead of being silently ignored;
* the batch-size triad invariant (``train_batch_size = micro_batch ×
  grad_accum × dp_world_size``, reference ``config.py:736-898``) is
  auto-completed and validated identically;
* a ``mesh`` block (TPU-native extension) declares named SPMD axis sizes,
  replacing the reference's mpu/process-group plumbing.
"""
from __future__ import annotations

import dataclasses
import difflib
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

from deepspeed_tpu.config import constants as C


class DeepSpeedConfigError(Exception):
    pass


def _pop(d: Dict[str, Any], key: str, default: Any = None) -> Any:
    return d.pop(key, default)


def _pop_alias(d: Dict[str, Any], key: str, alias: str, default: Any, block: str) -> Any:
    """Pop a key that also has a reference-compat alias.  Supplying both
    spellings raises instead of silently dropping one (the module's
    unknown-keys-raise stance applies to conflicts too)."""
    if key in d and alias in d:
        raise DeepSpeedConfigError(
            f"'{block}.{key}' and its alias '{block}.{alias}' are both set; use one"
        )
    return d.pop(key, d.pop(alias, default))


def _describe_unknown(keys: Iterable[str], block: str, valid: Iterable[str]) -> str:
    """'zero_optimization.offload_param.buffer_sz' (did you mean
    'buffer_size'?), ... — full nested paths plus nearest-key hints."""
    valid = sorted(str(v) for v in valid)
    parts = []
    for key in sorted(str(k) for k in keys):
        path = f"{block}.{key}" if block else key
        close = difflib.get_close_matches(key, valid, n=1, cutoff=0.6)
        hint = f" (did you mean '{close[0]}'?)" if close else ""
        parts.append(f"'{path}'{hint}")
    return ", ".join(parts)


def _check_empty(d: Dict[str, Any], block: str, valid: Iterable[str] = ()) -> None:
    if d:
        raise DeepSpeedConfigError(
            f"Unknown config key(s): {_describe_unknown(d.keys(), block, valid)}"
        )


def _known_keys(cls, *aliases: str) -> Iterable[str]:
    """A block's accepted keys: its dataclass field names plus any
    reference-compat aliases the parser also pops."""
    return tuple(f.name for f in dataclasses.fields(cls)) + aliases


@dataclass
class OffloadDeviceConfig:
    """``zero_optimization.offload_param`` / ``offload_optimizer``
    (reference ``runtime/zero/offload_config.py``).  On TPU, ``device:
    'cpu'`` means host-resident shards (SIMD host optimizer path) and
    ``device: 'nvme'`` means the aio swapper."""

    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    max_in_cpu: int = 1_000_000_000
    ratio: float = 1.0

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], block: str) -> "OffloadDeviceConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            device=_pop(d, "device", "none"),
            nvme_path=_pop(d, "nvme_path", None),
            buffer_count=int(_pop(d, "buffer_count", 5)),
            buffer_size=int(_pop(d, "buffer_size", 100_000_000)),
            pin_memory=bool(_pop(d, "pin_memory", False)),
            pipeline_read=bool(_pop(d, "pipeline_read", False)),
            pipeline_write=bool(_pop(d, "pipeline_write", False)),
            fast_init=bool(_pop(d, "fast_init", False)),
            max_in_cpu=int(_pop(d, "max_in_cpu", 1_000_000_000)),
            ratio=float(_pop(d, "ratio", 1.0)),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.device not in ("none", "cpu", "nvme"):
            raise DeepSpeedConfigError(f"{block}.device must be none|cpu|nvme, got {out.device}")
        return out

    @property
    def enabled(self) -> bool:
        return self.device != "none"


@dataclass
class ZeroConfig:
    """``zero_optimization`` block (reference ``runtime/zero/config.py:14``)."""

    stage: int = C.ZERO_STAGE_DEFAULT
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = True
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = True
    offload_param: OffloadDeviceConfig = field(default_factory=OffloadDeviceConfig)
    offload_optimizer: OffloadDeviceConfig = field(default_factory=OffloadDeviceConfig)
    sub_group_size: int = 1_000_000_000
    prefetch_bucket_size: int = 50_000_000
    param_persistence_threshold: int = 100_000
    max_live_parameters: int = 1_000_000_000
    max_reuse_distance: int = 1_000_000_000
    gather_fp16_weights_on_model_save: bool = False
    round_robin_gradients: bool = False
    ignore_unused_parameters: bool = True
    legacy_stage1: bool = False
    cpu_offload: bool = False  # legacy alias for offload_optimizer.device=cpu
    # cross-replica weight-update sharding (arXiv:2004.13336): at stage
    # >= 1 the optimizer state/update also shards across the pure
    # ``data`` axis — ~dp× less update FLOPs + opt-state bytes per
    # replica for one updated-params all-gather (docs/sharding.md)
    cross_replica_weight_update: bool = True

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ZeroConfig":
        if d is None:
            return cls()
        d = dict(d)
        cpu_offload = bool(_pop(d, "cpu_offload", False))
        offload_param = OffloadDeviceConfig.from_dict(_pop(d, "offload_param", None), "zero_optimization.offload_param")
        offload_optimizer = OffloadDeviceConfig.from_dict(
            _pop(d, "offload_optimizer", None), "zero_optimization.offload_optimizer"
        )
        if cpu_offload and not offload_optimizer.enabled:
            offload_optimizer = dataclasses.replace(offload_optimizer, device="cpu")
        out = cls(
            stage=int(_pop(d, C.ZERO_STAGE, C.ZERO_STAGE_DEFAULT)),
            contiguous_gradients=bool(_pop(d, "contiguous_gradients", True)),
            reduce_scatter=bool(_pop(d, "reduce_scatter", True)),
            reduce_bucket_size=int(_pop(d, "reduce_bucket_size", 500_000_000)),
            allgather_partitions=bool(_pop(d, "allgather_partitions", True)),
            allgather_bucket_size=int(_pop(d, "allgather_bucket_size", 500_000_000)),
            overlap_comm=bool(_pop(d, "overlap_comm", True)),
            load_from_fp32_weights=bool(_pop(d, "load_from_fp32_weights", True)),
            elastic_checkpoint=bool(_pop(d, "elastic_checkpoint", True)),
            offload_param=offload_param,
            offload_optimizer=offload_optimizer,
            sub_group_size=int(_pop(d, "sub_group_size", 1_000_000_000)),
            prefetch_bucket_size=int(_pop_alias(d, "stage3_prefetch_bucket_size", "prefetch_bucket_size", 50_000_000, C.ZERO_OPTIMIZATION)),
            param_persistence_threshold=int(
                _pop_alias(d, "stage3_param_persistence_threshold", "param_persistence_threshold", 100_000, C.ZERO_OPTIMIZATION)
            ),
            max_live_parameters=int(_pop_alias(d, "stage3_max_live_parameters", "max_live_parameters", 1_000_000_000, C.ZERO_OPTIMIZATION)),
            max_reuse_distance=int(_pop_alias(d, "stage3_max_reuse_distance", "max_reuse_distance", 1_000_000_000, C.ZERO_OPTIMIZATION)),
            gather_fp16_weights_on_model_save=bool(
                _pop_alias(d, "stage3_gather_fp16_weights_on_model_save", "gather_fp16_weights_on_model_save", False, C.ZERO_OPTIMIZATION)
            ),
            round_robin_gradients=bool(_pop(d, "round_robin_gradients", False)),
            ignore_unused_parameters=bool(_pop(d, "ignore_unused_parameters", True)),
            legacy_stage1=bool(_pop(d, "legacy_stage1", False)),
            cpu_offload=cpu_offload,
            cross_replica_weight_update=bool(_pop(d, "cross_replica_weight_update", True)),
        )
        _check_empty(
            d, C.ZERO_OPTIMIZATION,
            _known_keys(
                cls,
                "stage3_prefetch_bucket_size",
                "stage3_param_persistence_threshold",
                "stage3_max_live_parameters",
                "stage3_max_reuse_distance",
                "stage3_gather_fp16_weights_on_model_save",
            ),
        )
        if not (0 <= out.stage <= C.MAX_STAGE_ZERO_OPTIMIZATION):
            raise DeepSpeedConfigError(f"zero_optimization.stage must be in [0,3], got {out.stage}")
        return out


@dataclass
class Fp16Config:
    enabled: bool = C.FP16_ENABLED_DEFAULT
    loss_scale: float = C.FP16_LOSS_SCALE_DEFAULT  # 0 => dynamic
    initial_scale_power: int = C.FP16_INITIAL_SCALE_POWER_DEFAULT
    loss_scale_window: int = C.FP16_LOSS_SCALE_WINDOW_DEFAULT
    hysteresis: int = C.FP16_HYSTERESIS_DEFAULT
    min_loss_scale: float = C.FP16_MIN_LOSS_SCALE_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "Fp16Config":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, C.FP16_ENABLED, C.FP16_ENABLED_DEFAULT)),
            loss_scale=float(_pop(d, C.FP16_LOSS_SCALE, C.FP16_LOSS_SCALE_DEFAULT)),
            initial_scale_power=int(_pop(d, C.FP16_INITIAL_SCALE_POWER, C.FP16_INITIAL_SCALE_POWER_DEFAULT)),
            loss_scale_window=int(_pop(d, C.FP16_LOSS_SCALE_WINDOW, C.FP16_LOSS_SCALE_WINDOW_DEFAULT)),
            hysteresis=int(_pop(d, C.FP16_HYSTERESIS, C.FP16_HYSTERESIS_DEFAULT)),
            min_loss_scale=float(_pop(d, C.FP16_MIN_LOSS_SCALE, C.FP16_MIN_LOSS_SCALE_DEFAULT)),
        )
        _check_empty(d, C.FP16, _known_keys(cls))
        return out

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.loss_scale == 0


@dataclass
class Bf16Config:
    enabled: bool = C.BF16_ENABLED_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "Bf16Config":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(enabled=bool(_pop(d, C.BF16_ENABLED, C.BF16_ENABLED_DEFAULT)))
        _check_empty(d, C.BF16, _known_keys(cls))
        return out


@dataclass
class OptimizerConfig:
    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)
    legacy_fusion: bool = False

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "OptimizerConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            type=_pop(d, C.TYPE, None),
            params=dict(_pop(d, C.OPTIMIZER_PARAMS, {}) or {}),
            legacy_fusion=bool(_pop(d, C.LEGACY_FUSION, C.LEGACY_FUSION_DEFAULT)),
        )
        _check_empty(d, C.OPTIMIZER, _known_keys(cls))
        if out.type is not None and not isinstance(out.type, str):
            raise DeepSpeedConfigError("optimizer.type must be a string")
        return out

    @property
    def name(self) -> Optional[str]:
        return self.type.lower() if self.type else None


@dataclass
class SchedulerConfig:
    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "SchedulerConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(type=_pop(d, C.TYPE, None), params=dict(_pop(d, C.SCHEDULER_PARAMS, {}) or {}))
        _check_empty(d, C.SCHEDULER, _known_keys(cls))
        return out


@dataclass
class MeshConfig:
    """TPU-native named SPMD mesh axes (SURVEY.md §2.6 TPU equivalent).

    Axis sizes; ``data`` defaults to "whatever is left" (-1).  The full
    mesh device count must equal ``jax.device_count()`` at engine init.
    """

    data: int = -1
    fsdp: int = 1
    model: int = 1  # tensor parallel (the reference's "slice parallel")
    pipe: int = 1
    seq: int = 1  # sequence/context parallel (ring attention axis)
    expert: int = 1

    AXES = ("pipe", "data", "fsdp", "seq", "model", "expert")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "MeshConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            data=int(_pop(d, "data", -1)),
            fsdp=int(_pop(d, "fsdp", 1)),
            # the mesh AXIS named "model" (tensor parallel), unrelated to
            # serving's kv_cache_dtype="model" sentinel that shares the
            # spelling  # ds-lint: disable=config-key-drift
            model=int(_pop(d, "model", 1)),
            pipe=int(_pop(d, "pipe", 1)),
            seq=int(_pop(d, "seq", 1)),
            expert=int(_pop(d, "expert", 1)),
        )
        _check_empty(d, C.MESH, _known_keys(cls))
        return out


@dataclass
class ResilienceCheckpointConfig:
    """``resilience.checkpoint`` — durability of the checkpoint tree."""

    atomic: bool = C.CHECKPOINT_ATOMIC_DEFAULT
    verify_on_load: bool = C.CHECKPOINT_VERIFY_ON_LOAD_DEFAULT
    checksum: str = C.CHECKPOINT_CHECKSUM_DEFAULT
    keep_last_n: int = C.CHECKPOINT_KEEP_LAST_N_DEFAULT  # 0 = keep all
    keep_every: int = C.CHECKPOINT_KEEP_EVERY_DEFAULT  # pin step multiples
    fail_on_missing: bool = C.CHECKPOINT_FAIL_ON_MISSING_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], block: str) -> "ResilienceCheckpointConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            atomic=bool(_pop(d, "atomic", C.CHECKPOINT_ATOMIC_DEFAULT)),
            verify_on_load=bool(_pop(d, "verify_on_load", C.CHECKPOINT_VERIFY_ON_LOAD_DEFAULT)),
            checksum=str(_pop(d, "checksum", C.CHECKPOINT_CHECKSUM_DEFAULT)).lower(),
            keep_last_n=int(_pop(d, "keep_last_n", C.CHECKPOINT_KEEP_LAST_N_DEFAULT)),
            keep_every=int(_pop(d, "keep_every", C.CHECKPOINT_KEEP_EVERY_DEFAULT)),
            fail_on_missing=bool(_pop(d, C.CHECKPOINT_FAIL_ON_MISSING, C.CHECKPOINT_FAIL_ON_MISSING_DEFAULT)),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.checksum not in C.CHECKPOINT_CHECKSUM_ALGORITHMS:
            raise DeepSpeedConfigError(
                f"'{block}.checksum' must be one of {C.CHECKPOINT_CHECKSUM_ALGORITHMS}, got '{out.checksum}'"
            )
        return out


@dataclass
class WatchdogConfig:
    """``resilience.watchdog`` — SIGTERM/SIGINT → emergency checkpoint at
    the next step boundary, then exit with a scheduler-readable code."""

    enabled: bool = C.WATCHDOG_ENABLED_DEFAULT
    grace_seconds: float = C.WATCHDOG_GRACE_SECONDS_DEFAULT
    exit_code: int = C.WATCHDOG_EXIT_CODE_DEFAULT
    save_dir: Optional[str] = None  # default: the engine's last ckpt dir

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], block: str) -> "WatchdogConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, "enabled", C.WATCHDOG_ENABLED_DEFAULT)),
            grace_seconds=float(_pop(d, "grace_seconds", C.WATCHDOG_GRACE_SECONDS_DEFAULT)),
            exit_code=int(_pop(d, "exit_code", C.WATCHDOG_EXIT_CODE_DEFAULT)),
            save_dir=_pop(d, "save_dir", None),
        )
        _check_empty(d, block, _known_keys(cls))
        if not (0 <= out.exit_code <= 255):
            raise DeepSpeedConfigError(f"'{block}.exit_code' must be in [0, 255], got {out.exit_code}")
        if out.grace_seconds < 0:
            raise DeepSpeedConfigError(f"'{block}.grace_seconds' must be >= 0, got {out.grace_seconds}")
        return out


@dataclass
class RetryConfig:
    """``resilience.retry`` — the shared bounded-retry policy applied to
    checkpoint I/O and distributed init."""

    max_attempts: int = C.RETRY_MAX_ATTEMPTS_DEFAULT
    backoff_seconds: float = C.RETRY_BACKOFF_SECONDS_DEFAULT
    backoff_max_seconds: float = C.RETRY_BACKOFF_MAX_SECONDS_DEFAULT
    jitter: float = C.RETRY_JITTER_DEFAULT
    timeout_seconds: Optional[float] = None

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], block: str) -> "RetryConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            max_attempts=int(_pop(d, "max_attempts", C.RETRY_MAX_ATTEMPTS_DEFAULT)),
            backoff_seconds=float(_pop(d, "backoff_seconds", C.RETRY_BACKOFF_SECONDS_DEFAULT)),
            backoff_max_seconds=float(_pop(d, "backoff_max_seconds", C.RETRY_BACKOFF_MAX_SECONDS_DEFAULT)),
            jitter=float(_pop(d, "jitter", C.RETRY_JITTER_DEFAULT)),
            timeout_seconds=_pop(d, "timeout_seconds", None),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.max_attempts < 1:
            raise DeepSpeedConfigError(f"'{block}.max_attempts' must be >= 1, got {out.max_attempts}")
        return out

    def policy(self):
        """Materialize as a runtime RetryPolicy (lazy import keeps config
        parsing free of the resilience package)."""
        from deepspeed_tpu.resilience.policy import RetryPolicy

        return RetryPolicy(
            max_attempts=self.max_attempts,
            backoff_seconds=self.backoff_seconds,
            backoff_max_seconds=self.backoff_max_seconds,
            jitter=self.jitter,
            timeout_seconds=self.timeout_seconds,
        )


@dataclass
class DivergenceConfig:
    """``resilience.divergence`` — N consecutive NaN/overflow-skipped
    steps trip a configurable action (warn / lower the loss-scale floor /
    auto-rollback to the last verified checkpoint)."""

    enabled: bool = C.DIVERGENCE_ENABLED_DEFAULT
    threshold: int = C.DIVERGENCE_THRESHOLD_DEFAULT
    action: str = C.DIVERGENCE_ACTION_WARN
    # Opt-in host sync: without dynamic loss scaling (bf16 default) there
    # is no overflow flag, so NaN detection must read the loss each step.
    check_loss: bool = False

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], block: str) -> "DivergenceConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, "enabled", C.DIVERGENCE_ENABLED_DEFAULT)),
            threshold=int(_pop(d, "threshold", C.DIVERGENCE_THRESHOLD_DEFAULT)),
            action=str(_pop(d, "action", C.DIVERGENCE_ACTION_WARN)).lower(),
            check_loss=bool(_pop(d, "check_loss", False)),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.action not in C.DIVERGENCE_ACTIONS:
            raise DeepSpeedConfigError(
                f"'{block}.action' must be one of {C.DIVERGENCE_ACTIONS}, got '{out.action}'"
            )
        if out.threshold < 1:
            raise DeepSpeedConfigError(f"'{block}.threshold' must be >= 1, got {out.threshold}")
        return out


@dataclass
class SupervisionConfig:
    """``resilience.supervision`` — the distributed failure domain:
    heartbeat liveness plane, hung-collective watchdog and the exit-44
    "peer-failed-and-saved" rescue contract (docs/resilience.md)."""

    enabled: bool = C.SUPERVISION_ENABLED_DEFAULT
    channel: str = C.SUPERVISION_CHANNEL_DEFAULT  # auto | tcp | file
    beat_dir: Optional[str] = None  # file-channel directory
    beat_interval_seconds: float = C.SUPERVISION_BEAT_INTERVAL_DEFAULT
    beat_timeout_seconds: float = C.SUPERVISION_BEAT_TIMEOUT_DEFAULT
    sync_timeout_seconds: float = C.SUPERVISION_SYNC_TIMEOUT_DEFAULT
    rescue_grace_seconds: float = C.SUPERVISION_RESCUE_GRACE_DEFAULT
    connect_grace_seconds: float = C.SUPERVISION_CONNECT_GRACE_DEFAULT
    snapshot_interval_steps: int = C.SUPERVISION_SNAPSHOT_INTERVAL_DEFAULT
    exit_code: int = C.SUPERVISION_EXIT_CODE_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], block: str) -> "SupervisionConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, "enabled", C.SUPERVISION_ENABLED_DEFAULT)),
            channel=str(_pop(d, "channel", C.SUPERVISION_CHANNEL_DEFAULT)).lower(),
            beat_dir=_pop(d, "beat_dir", None),
            beat_interval_seconds=float(
                _pop(d, "beat_interval_seconds", C.SUPERVISION_BEAT_INTERVAL_DEFAULT)
            ),
            beat_timeout_seconds=float(
                _pop(d, "beat_timeout_seconds", C.SUPERVISION_BEAT_TIMEOUT_DEFAULT)
            ),
            sync_timeout_seconds=float(
                _pop(d, "sync_timeout_seconds", C.SUPERVISION_SYNC_TIMEOUT_DEFAULT)
            ),
            rescue_grace_seconds=float(
                _pop(d, "rescue_grace_seconds", C.SUPERVISION_RESCUE_GRACE_DEFAULT)
            ),
            connect_grace_seconds=float(
                _pop(d, "connect_grace_seconds", C.SUPERVISION_CONNECT_GRACE_DEFAULT)
            ),
            snapshot_interval_steps=int(
                _pop(d, "snapshot_interval_steps", C.SUPERVISION_SNAPSHOT_INTERVAL_DEFAULT)
            ),
            exit_code=int(_pop(d, "exit_code", C.SUPERVISION_EXIT_CODE_DEFAULT)),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.channel not in C.SUPERVISION_CHANNELS:
            raise DeepSpeedConfigError(
                f"'{block}.channel' must be one of {C.SUPERVISION_CHANNELS}, got '{out.channel}'"
            )
        if not (0 <= out.exit_code <= 255):
            raise DeepSpeedConfigError(f"'{block}.exit_code' must be in [0, 255], got {out.exit_code}")
        for name in ("beat_interval_seconds", "beat_timeout_seconds", "sync_timeout_seconds"):
            if getattr(out, name) <= 0:
                raise DeepSpeedConfigError(f"'{block}.{name}' must be > 0, got {getattr(out, name)}")
        if out.beat_timeout_seconds <= out.beat_interval_seconds:
            raise DeepSpeedConfigError(
                f"'{block}.beat_timeout_seconds' ({out.beat_timeout_seconds}) must exceed "
                f"beat_interval_seconds ({out.beat_interval_seconds}) or every beat gap reads as a death"
            )
        if out.snapshot_interval_steps < 1:
            raise DeepSpeedConfigError(
                f"'{block}.snapshot_interval_steps' must be >= 1, got {out.snapshot_interval_steps}"
            )
        return out


@dataclass
class ResilienceConfig:
    """``resilience`` block (TPU-native extension; docs/resilience.md)."""

    checkpoint: ResilienceCheckpointConfig = field(default_factory=ResilienceCheckpointConfig)
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)
    retry: RetryConfig = field(default_factory=RetryConfig)
    divergence: DivergenceConfig = field(default_factory=DivergenceConfig)
    supervision: SupervisionConfig = field(default_factory=SupervisionConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ResilienceConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            checkpoint=ResilienceCheckpointConfig.from_dict(
                _pop(d, C.RESILIENCE_CHECKPOINT, None), f"{C.RESILIENCE}.{C.RESILIENCE_CHECKPOINT}"
            ),
            watchdog=WatchdogConfig.from_dict(
                _pop(d, C.RESILIENCE_WATCHDOG, None), f"{C.RESILIENCE}.{C.RESILIENCE_WATCHDOG}"
            ),
            retry=RetryConfig.from_dict(
                _pop(d, C.RESILIENCE_RETRY, None), f"{C.RESILIENCE}.{C.RESILIENCE_RETRY}"
            ),
            divergence=DivergenceConfig.from_dict(
                _pop(d, C.RESILIENCE_DIVERGENCE, None), f"{C.RESILIENCE}.{C.RESILIENCE_DIVERGENCE}"
            ),
            supervision=SupervisionConfig.from_dict(
                _pop(d, C.RESILIENCE_SUPERVISION, None), f"{C.RESILIENCE}.{C.RESILIENCE_SUPERVISION}"
            ),
        )
        _check_empty(d, C.RESILIENCE, _known_keys(cls))
        return out


@dataclass
class PrefetchOverlapConfig:
    """``overlap.prefetch`` — pipelined load + sharded ``device_put`` of
    input batches ahead of the compiled step (``engine.prefetch_loader``)."""

    enabled: bool = C.PREFETCH_ENABLED_DEFAULT
    depth: int = C.PREFETCH_DEPTH_DEFAULT  # batches in flight per stage

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], block: str) -> "PrefetchOverlapConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, "enabled", C.PREFETCH_ENABLED_DEFAULT)),
            depth=int(_pop(d, "depth", C.PREFETCH_DEPTH_DEFAULT)),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.depth < 1:
            raise DeepSpeedConfigError(f"'{block}.depth' must be >= 1, got {out.depth}")
        return out


@dataclass
class AsyncCheckpointConfig:
    """``overlap.async_checkpoint`` — snapshot device state at the step
    boundary, run the stage->manifest->rename commit on a background
    thread (docs/performance.md; durability contract per
    docs/resilience.md is unchanged)."""

    enabled: bool = C.ASYNC_CHECKPOINT_ENABLED_DEFAULT
    drain_timeout_seconds: float = C.ASYNC_CHECKPOINT_DRAIN_TIMEOUT_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], block: str) -> "AsyncCheckpointConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, "enabled", C.ASYNC_CHECKPOINT_ENABLED_DEFAULT)),
            drain_timeout_seconds=float(
                _pop(d, "drain_timeout_seconds", C.ASYNC_CHECKPOINT_DRAIN_TIMEOUT_DEFAULT)
            ),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.drain_timeout_seconds <= 0:
            raise DeepSpeedConfigError(
                f"'{block}.drain_timeout_seconds' must be > 0, got {out.drain_timeout_seconds}"
            )
        return out


@dataclass
class TimelineConfig:
    """``overlap.timeline`` — per-step wall-time attribution
    (data_wait / compute / ckpt_stall / compile / other).

    ``fence``: per-step ``block_until_ready`` before the compute note.
    Honest per-step compute attribution requires it, but it costs a full
    host<->device round trip per step (exactly what ThroughputTimer
    avoids off report steps).  ``null`` (default) follows
    ``wall_clock_breakdown``; without the fence the timeline still
    attributes the host-measurable phases (data_wait / ckpt_stall /
    compile) and omits ``compute`` rather than record an unfenced lie."""

    enabled: bool = C.TIMELINE_ENABLED_DEFAULT
    window: int = C.TIMELINE_WINDOW_DEFAULT
    fence: Optional[bool] = None  # None = follow wall_clock_breakdown

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], block: str) -> "TimelineConfig":
        if d is None:
            return cls()
        d = dict(d)
        fence = _pop(d, "fence", None)
        out = cls(
            enabled=bool(_pop(d, "enabled", C.TIMELINE_ENABLED_DEFAULT)),
            window=int(_pop(d, "window", C.TIMELINE_WINDOW_DEFAULT)),
            fence=None if fence is None else bool(fence),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.window < 1:
            raise DeepSpeedConfigError(f"'{block}.window' must be >= 1, got {out.window}")
        return out


@dataclass
class OverlapConfig:
    """``overlap`` block (TPU-native extension; docs/performance.md)."""

    prefetch: PrefetchOverlapConfig = field(default_factory=PrefetchOverlapConfig)
    async_checkpoint: AsyncCheckpointConfig = field(default_factory=AsyncCheckpointConfig)
    timeline: TimelineConfig = field(default_factory=TimelineConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "OverlapConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            prefetch=PrefetchOverlapConfig.from_dict(
                _pop(d, C.OVERLAP_PREFETCH, None), f"{C.OVERLAP}.{C.OVERLAP_PREFETCH}"
            ),
            async_checkpoint=AsyncCheckpointConfig.from_dict(
                _pop(d, C.OVERLAP_ASYNC_CHECKPOINT, None),
                f"{C.OVERLAP}.{C.OVERLAP_ASYNC_CHECKPOINT}",
            ),
            timeline=TimelineConfig.from_dict(
                _pop(d, C.OVERLAP_TIMELINE, None), f"{C.OVERLAP}.{C.OVERLAP_TIMELINE}"
            ),
        )
        _check_empty(d, C.OVERLAP, _known_keys(cls))
        return out


@dataclass
class CommConfig:
    """``comm`` block (TPU-native extension; docs/comm.md): the wire
    strategy for gradient exchange — ``dense`` (full precision, the
    default), ``int8`` (EQuARX-style quantized allreduce: per-chunk
    scale + stochastic rounding), ``onebit`` (error-feedback sign +
    L1-scale compression, generalized from 1-bit Adam's exchange), or
    ``auto`` (policy-selected per tensor size/dtype/topology)."""

    strategy: str = C.COMM_STRATEGY_DEFAULT
    threshold_bytes: int = C.COMM_THRESHOLD_BYTES_DEFAULT
    dcn_threshold_bytes: int = C.COMM_DCN_THRESHOLD_BYTES_DEFAULT
    quantize_bits: int = C.COMM_QUANTIZE_BITS_DEFAULT
    error_feedback: bool = C.COMM_ERROR_FEEDBACK_DEFAULT
    stochastic_rounding: bool = C.COMM_STOCHASTIC_ROUNDING_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "CommConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            strategy=str(_pop(d, "strategy", C.COMM_STRATEGY_DEFAULT)).lower(),
            threshold_bytes=int(_pop(d, "threshold_bytes", C.COMM_THRESHOLD_BYTES_DEFAULT)),
            dcn_threshold_bytes=int(
                _pop(d, "dcn_threshold_bytes", C.COMM_DCN_THRESHOLD_BYTES_DEFAULT)
            ),
            quantize_bits=int(_pop(d, "quantize_bits", C.COMM_QUANTIZE_BITS_DEFAULT)),
            error_feedback=bool(_pop(d, "error_feedback", C.COMM_ERROR_FEEDBACK_DEFAULT)),
            stochastic_rounding=bool(
                _pop(d, "stochastic_rounding", C.COMM_STOCHASTIC_ROUNDING_DEFAULT)
            ),
        )
        _check_empty(d, C.COMM, _known_keys(cls))
        if out.strategy not in C.COMM_STRATEGIES:
            raise DeepSpeedConfigError(
                f"'{C.COMM}.strategy' must be one of {C.COMM_STRATEGIES}, got '{out.strategy}'"
            )
        if out.threshold_bytes < 0:
            raise DeepSpeedConfigError(
                f"'{C.COMM}.threshold_bytes' must be >= 0, got {out.threshold_bytes}"
            )
        if out.dcn_threshold_bytes < 0:
            raise DeepSpeedConfigError(
                f"'{C.COMM}.dcn_threshold_bytes' must be >= 0, got {out.dcn_threshold_bytes}"
            )
        if out.quantize_bits != C.COMM_QUANTIZE_BITS_DEFAULT:
            # XLA has no bit-packed dtype: int8 is the densest exchange
            # format ICI moves natively (comm/compressed.py module note);
            # the 1-bit TIER is the `onebit` strategy, whose signs also
            # ride as int8
            raise DeepSpeedConfigError(
                f"'{C.COMM}.quantize_bits' supports only {C.COMM_QUANTIZE_BITS_DEFAULT} "
                f"(int8 is the densest ICI-native exchange format; use strategy "
                f"'{C.COMM_STRATEGY_ONEBIT}' for the sign+scale tier), got {out.quantize_bits}"
            )
        return out


@dataclass
class ElasticConfig:
    """``serving.fleet.elastic`` block (docs/serving.md §Elastic
    fleet): load-driven autoscaling — hot/cold tick hysteresis over the
    router's own signals (queue depth, admitted-TTFT estimate, shed),
    warm-pool scale-up, and drain-based scale-down with live KV session
    migration to the survivors over the spill-manifest wire format."""

    enabled: bool = C.SERVING_FLEET_ELASTIC_ENABLED_DEFAULT
    min_replicas: int = C.SERVING_FLEET_ELASTIC_MIN_REPLICAS_DEFAULT
    max_replicas: int = C.SERVING_FLEET_ELASTIC_MAX_REPLICAS_DEFAULT
    scale_up_queue_depth: int = C.SERVING_FLEET_ELASTIC_SCALE_UP_QUEUE_DEPTH_DEFAULT
    scale_up_ttft_seconds: float = C.SERVING_FLEET_ELASTIC_SCALE_UP_TTFT_SECONDS_DEFAULT
    scale_down_queue_depth: int = (
        C.SERVING_FLEET_ELASTIC_SCALE_DOWN_QUEUE_DEPTH_DEFAULT
    )
    engage_ticks: int = C.SERVING_FLEET_ELASTIC_ENGAGE_TICKS_DEFAULT
    disengage_ticks: int = C.SERVING_FLEET_ELASTIC_DISENGAGE_TICKS_DEFAULT
    scale_up_cooldown_seconds: float = (
        C.SERVING_FLEET_ELASTIC_SCALE_UP_COOLDOWN_SECONDS_DEFAULT
    )
    scale_down_cooldown_seconds: float = (
        C.SERVING_FLEET_ELASTIC_SCALE_DOWN_COOLDOWN_SECONDS_DEFAULT
    )
    warm_pool_size: int = C.SERVING_FLEET_ELASTIC_WARM_POOL_SIZE_DEFAULT
    migration_deadline_seconds: float = (
        C.SERVING_FLEET_ELASTIC_MIGRATION_DEADLINE_SECONDS_DEFAULT
    )
    migration_retries: int = C.SERVING_FLEET_ELASTIC_MIGRATION_RETRIES_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ElasticConfig":
        if d is None:
            return cls()
        if isinstance(d, ElasticConfig):
            d = dataclasses.asdict(d)
        d = dict(d)
        block = f"{C.SERVING}.{C.SERVING_FLEET}.{C.SERVING_FLEET_ELASTIC}"
        out = cls(
            enabled=bool(_pop(d, "enabled", C.SERVING_FLEET_ELASTIC_ENABLED_DEFAULT)),
            min_replicas=int(
                _pop(d, "min_replicas", C.SERVING_FLEET_ELASTIC_MIN_REPLICAS_DEFAULT)
            ),
            max_replicas=int(
                _pop(d, "max_replicas", C.SERVING_FLEET_ELASTIC_MAX_REPLICAS_DEFAULT)
            ),
            scale_up_queue_depth=int(
                _pop(d, "scale_up_queue_depth",
                     C.SERVING_FLEET_ELASTIC_SCALE_UP_QUEUE_DEPTH_DEFAULT)
            ),
            scale_up_ttft_seconds=float(
                _pop(d, "scale_up_ttft_seconds",
                     C.SERVING_FLEET_ELASTIC_SCALE_UP_TTFT_SECONDS_DEFAULT)
            ),
            scale_down_queue_depth=int(
                _pop(d, "scale_down_queue_depth",
                     C.SERVING_FLEET_ELASTIC_SCALE_DOWN_QUEUE_DEPTH_DEFAULT)
            ),
            engage_ticks=int(
                _pop(d, "engage_ticks", C.SERVING_FLEET_ELASTIC_ENGAGE_TICKS_DEFAULT)
            ),
            disengage_ticks=int(
                _pop(d, "disengage_ticks",
                     C.SERVING_FLEET_ELASTIC_DISENGAGE_TICKS_DEFAULT)
            ),
            scale_up_cooldown_seconds=float(
                _pop(d, "scale_up_cooldown_seconds",
                     C.SERVING_FLEET_ELASTIC_SCALE_UP_COOLDOWN_SECONDS_DEFAULT)
            ),
            scale_down_cooldown_seconds=float(
                _pop(d, "scale_down_cooldown_seconds",
                     C.SERVING_FLEET_ELASTIC_SCALE_DOWN_COOLDOWN_SECONDS_DEFAULT)
            ),
            warm_pool_size=int(
                _pop(d, "warm_pool_size",
                     C.SERVING_FLEET_ELASTIC_WARM_POOL_SIZE_DEFAULT)
            ),
            migration_deadline_seconds=float(
                _pop(d, "migration_deadline_seconds",
                     C.SERVING_FLEET_ELASTIC_MIGRATION_DEADLINE_SECONDS_DEFAULT)
            ),
            migration_retries=int(
                _pop(d, "migration_retries",
                     C.SERVING_FLEET_ELASTIC_MIGRATION_RETRIES_DEFAULT)
            ),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.min_replicas < 1:
            raise DeepSpeedConfigError(
                f"'{block}.min_replicas' must be >= 1, got {out.min_replicas}"
            )
        if out.max_replicas < out.min_replicas:
            raise DeepSpeedConfigError(
                f"'{block}.max_replicas' ({out.max_replicas}) must be >= "
                f"min_replicas ({out.min_replicas})"
            )
        if out.scale_up_queue_depth < 1:
            raise DeepSpeedConfigError(
                f"'{block}.scale_up_queue_depth' must be >= 1, "
                f"got {out.scale_up_queue_depth}"
            )
        if out.scale_up_ttft_seconds <= 0:
            raise DeepSpeedConfigError(
                f"'{block}.scale_up_ttft_seconds' must be > 0, "
                f"got {out.scale_up_ttft_seconds}"
            )
        if out.scale_down_queue_depth < 0:
            raise DeepSpeedConfigError(
                f"'{block}.scale_down_queue_depth' must be >= 0, "
                f"got {out.scale_down_queue_depth}"
            )
        if out.scale_down_queue_depth >= out.scale_up_queue_depth:
            raise DeepSpeedConfigError(
                f"'{block}.scale_down_queue_depth' "
                f"({out.scale_down_queue_depth}) must be < "
                f"scale_up_queue_depth ({out.scale_up_queue_depth}) — "
                f"overlapping thresholds would flap"
            )
        if out.engage_ticks < 1:
            raise DeepSpeedConfigError(
                f"'{block}.engage_ticks' must be >= 1, got {out.engage_ticks}"
            )
        if out.disengage_ticks < 1:
            raise DeepSpeedConfigError(
                f"'{block}.disengage_ticks' must be >= 1, "
                f"got {out.disengage_ticks}"
            )
        if out.scale_up_cooldown_seconds < 0:
            raise DeepSpeedConfigError(
                f"'{block}.scale_up_cooldown_seconds' must be >= 0, "
                f"got {out.scale_up_cooldown_seconds}"
            )
        if out.scale_down_cooldown_seconds < 0:
            raise DeepSpeedConfigError(
                f"'{block}.scale_down_cooldown_seconds' must be >= 0, "
                f"got {out.scale_down_cooldown_seconds}"
            )
        if out.warm_pool_size < 0:
            raise DeepSpeedConfigError(
                f"'{block}.warm_pool_size' must be >= 0, "
                f"got {out.warm_pool_size}"
            )
        if out.migration_deadline_seconds <= 0:
            raise DeepSpeedConfigError(
                f"'{block}.migration_deadline_seconds' must be > 0, "
                f"got {out.migration_deadline_seconds}"
            )
        if out.migration_retries < 0:
            raise DeepSpeedConfigError(
                f"'{block}.migration_retries' must be >= 0, "
                f"got {out.migration_retries}"
            )
        return out


@dataclass
class FleetConfig:
    """``serving.fleet`` block (docs/serving.md §Fleet): the front-door
    router over N engine replicas — least-estimated-TTFT placement, a
    per-replica circuit breaker with seeded-jitter exponential backoff,
    optional tail-latency hedging, and supervised lossless replica
    restart (journal replay under original ids)."""

    replicas: int = C.SERVING_FLEET_REPLICAS_DEFAULT
    route_retries: int = C.SERVING_FLEET_ROUTE_RETRIES_DEFAULT
    breaker_failures: int = C.SERVING_FLEET_BREAKER_FAILURES_DEFAULT
    breaker_backoff_seconds: float = C.SERVING_FLEET_BREAKER_BACKOFF_SECONDS_DEFAULT
    breaker_backoff_max_seconds: float = (
        C.SERVING_FLEET_BREAKER_BACKOFF_MAX_SECONDS_DEFAULT
    )
    breaker_halfopen_probes: int = C.SERVING_FLEET_BREAKER_HALFOPEN_PROBES_DEFAULT
    hedge: bool = C.SERVING_FLEET_HEDGE_DEFAULT
    hedge_factor: float = C.SERVING_FLEET_HEDGE_FACTOR_DEFAULT
    hedge_min_observations: int = C.SERVING_FLEET_HEDGE_MIN_OBSERVATIONS_DEFAULT
    max_restarts: int = C.SERVING_FLEET_MAX_RESTARTS_DEFAULT
    restart_backoff_seconds: float = C.SERVING_FLEET_RESTART_BACKOFF_SECONDS_DEFAULT
    restart_budget_reset_seconds: float = (
        C.SERVING_FLEET_RESTART_BUDGET_RESET_SECONDS_DEFAULT
    )
    elastic: ElasticConfig = dataclasses.field(default_factory=ElasticConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "FleetConfig":
        if d is None:
            return cls()
        if isinstance(d, FleetConfig):
            d = dataclasses.asdict(d)
        d = dict(d)
        block = f"{C.SERVING}.{C.SERVING_FLEET}"
        elastic = ElasticConfig.from_dict(_pop(d, C.SERVING_FLEET_ELASTIC, None))
        out = cls(
            replicas=int(_pop(d, "replicas", C.SERVING_FLEET_REPLICAS_DEFAULT)),
            route_retries=int(
                _pop(d, "route_retries", C.SERVING_FLEET_ROUTE_RETRIES_DEFAULT)
            ),
            breaker_failures=int(
                _pop(d, "breaker_failures", C.SERVING_FLEET_BREAKER_FAILURES_DEFAULT)
            ),
            breaker_backoff_seconds=float(
                _pop(d, "breaker_backoff_seconds",
                     C.SERVING_FLEET_BREAKER_BACKOFF_SECONDS_DEFAULT)
            ),
            breaker_backoff_max_seconds=float(
                _pop(d, "breaker_backoff_max_seconds",
                     C.SERVING_FLEET_BREAKER_BACKOFF_MAX_SECONDS_DEFAULT)
            ),
            breaker_halfopen_probes=int(
                _pop(d, "breaker_halfopen_probes",
                     C.SERVING_FLEET_BREAKER_HALFOPEN_PROBES_DEFAULT)
            ),
            hedge=bool(_pop(d, "hedge", C.SERVING_FLEET_HEDGE_DEFAULT)),
            hedge_factor=float(
                _pop(d, "hedge_factor", C.SERVING_FLEET_HEDGE_FACTOR_DEFAULT)
            ),
            hedge_min_observations=int(
                _pop(d, "hedge_min_observations",
                     C.SERVING_FLEET_HEDGE_MIN_OBSERVATIONS_DEFAULT)
            ),
            max_restarts=int(
                _pop(d, "max_restarts", C.SERVING_FLEET_MAX_RESTARTS_DEFAULT)
            ),
            restart_backoff_seconds=float(
                _pop(d, "restart_backoff_seconds",
                     C.SERVING_FLEET_RESTART_BACKOFF_SECONDS_DEFAULT)
            ),
            restart_budget_reset_seconds=float(
                _pop(d, "restart_budget_reset_seconds",
                     C.SERVING_FLEET_RESTART_BUDGET_RESET_SECONDS_DEFAULT)
            ),
            elastic=elastic,
        )
        _check_empty(d, block, _known_keys(cls))
        if out.replicas < 1:
            raise DeepSpeedConfigError(
                f"'{block}.replicas' must be >= 1, got {out.replicas}"
            )
        if out.route_retries < 0:
            raise DeepSpeedConfigError(
                f"'{block}.route_retries' must be >= 0, got {out.route_retries}"
            )
        if out.breaker_failures < 1:
            raise DeepSpeedConfigError(
                f"'{block}.breaker_failures' must be >= 1, got {out.breaker_failures}"
            )
        if out.breaker_backoff_seconds < 0:
            raise DeepSpeedConfigError(
                f"'{block}.breaker_backoff_seconds' must be >= 0, "
                f"got {out.breaker_backoff_seconds}"
            )
        if out.breaker_backoff_max_seconds < out.breaker_backoff_seconds:
            raise DeepSpeedConfigError(
                f"'{block}.breaker_backoff_max_seconds' "
                f"({out.breaker_backoff_max_seconds}) must be >= "
                f"breaker_backoff_seconds ({out.breaker_backoff_seconds})"
            )
        if out.breaker_halfopen_probes < 1:
            raise DeepSpeedConfigError(
                f"'{block}.breaker_halfopen_probes' must be >= 1, "
                f"got {out.breaker_halfopen_probes}"
            )
        if out.hedge_factor <= 0:
            raise DeepSpeedConfigError(
                f"'{block}.hedge_factor' must be > 0, got {out.hedge_factor}"
            )
        if out.hedge_min_observations < 1:
            raise DeepSpeedConfigError(
                f"'{block}.hedge_min_observations' must be >= 1, "
                f"got {out.hedge_min_observations}"
            )
        if out.max_restarts < 0:
            raise DeepSpeedConfigError(
                f"'{block}.max_restarts' must be >= 0 (0 = never restart), "
                f"got {out.max_restarts}"
            )
        if out.restart_backoff_seconds < 0:
            raise DeepSpeedConfigError(
                f"'{block}.restart_backoff_seconds' must be >= 0, "
                f"got {out.restart_backoff_seconds}"
            )
        if out.restart_budget_reset_seconds < 0:
            raise DeepSpeedConfigError(
                f"'{block}.restart_budget_reset_seconds' must be >= 0 "
                f"(0 = budget never decays), "
                f"got {out.restart_budget_reset_seconds}"
            )
        return out


@dataclass
class KVTiersConfig:
    """``serving.kvcache.tiers`` block (docs/serving.md §KV tiering):
    hierarchical page residency HBM (T0) → pinned host memory (T1) →
    disk (T2).  Cold pages demote asynchronously past the watermark;
    promotion is demand-driven plus scheduler-hinted prefetch."""

    enabled: bool = C.SERVING_KVCACHE_TIERS_ENABLED_DEFAULT
    host_pages: int = C.SERVING_KVCACHE_TIERS_HOST_PAGES_DEFAULT  # 0 = unbounded
    disk_dir: str = C.SERVING_KVCACHE_TIERS_DISK_DIR_DEFAULT  # "" = no T2
    # tokens of a parked session kept T0-resident; tail pages beyond
    # this demote (0 keeps whole sessions resident until cold)
    residency_window: int = C.SERVING_KVCACHE_TIERS_RESIDENCY_WINDOW_DEFAULT
    demote_watermark: float = C.SERVING_KVCACHE_TIERS_DEMOTE_WATERMARK_DEFAULT
    prefetch_ahead: int = C.SERVING_KVCACHE_TIERS_PREFETCH_AHEAD_DEFAULT
    demote_batch: int = C.SERVING_KVCACHE_TIERS_DEMOTE_BATCH_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "KVTiersConfig":
        if d is None:
            return cls()
        if isinstance(d, KVTiersConfig):
            d = dataclasses.asdict(d)
        d = dict(d)
        block = (f"{C.SERVING}.{C.SERVING_KVCACHE}"
                 f".{C.SERVING_KVCACHE_TIERS}")
        out = cls(
            enabled=bool(_pop(d, "enabled",
                              C.SERVING_KVCACHE_TIERS_ENABLED_DEFAULT)),
            host_pages=int(_pop(d, "host_pages",
                                C.SERVING_KVCACHE_TIERS_HOST_PAGES_DEFAULT)),
            disk_dir=str(_pop(d, "disk_dir",
                              C.SERVING_KVCACHE_TIERS_DISK_DIR_DEFAULT) or ""),
            residency_window=int(_pop(
                d, "residency_window",
                C.SERVING_KVCACHE_TIERS_RESIDENCY_WINDOW_DEFAULT)),
            demote_watermark=float(_pop(
                d, "demote_watermark",
                C.SERVING_KVCACHE_TIERS_DEMOTE_WATERMARK_DEFAULT)),
            prefetch_ahead=int(_pop(
                d, "prefetch_ahead",
                C.SERVING_KVCACHE_TIERS_PREFETCH_AHEAD_DEFAULT)),
            demote_batch=int(_pop(
                d, "demote_batch",
                C.SERVING_KVCACHE_TIERS_DEMOTE_BATCH_DEFAULT)),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.host_pages < 0:
            raise DeepSpeedConfigError(
                f"'{block}.host_pages' must be >= 0 (0 = unbounded), "
                f"got {out.host_pages}"
            )
        if out.residency_window < 0:
            raise DeepSpeedConfigError(
                f"'{block}.residency_window' must be >= 0 (0 keeps whole "
                f"sessions resident), got {out.residency_window}"
            )
        if not (0.0 < out.demote_watermark <= 1.0):
            raise DeepSpeedConfigError(
                f"'{block}.demote_watermark' must be in (0, 1], "
                f"got {out.demote_watermark}"
            )
        if out.prefetch_ahead < 0:
            raise DeepSpeedConfigError(
                f"'{block}.prefetch_ahead' must be >= 0, "
                f"got {out.prefetch_ahead}"
            )
        if out.demote_batch < 1:
            raise DeepSpeedConfigError(
                f"'{block}.demote_batch' must be >= 1, got {out.demote_batch}"
            )
        return out


@dataclass
class KVCacheConfig:
    """``serving.kvcache`` block (docs/serving.md §Paged KV & prefix
    caching): the paged KV pool — fixed-shape page buffers with a host
    page allocator, shared-prefix dedup via a radix index, copy-on-write
    for partially filled shared pages, and durable per-``session_id`` KV
    reuse (warm in-pool, spilled to ``spill_dir`` when cold / at drain)."""

    enabled: bool = C.SERVING_KVCACHE_ENABLED_DEFAULT
    page_len: int = C.SERVING_KVCACHE_PAGE_LEN_DEFAULT
    num_pages: int = C.SERVING_KVCACHE_NUM_PAGES_DEFAULT  # 0 = derive
    # prompt prefixes (token-id lists) pre-registered in the radix index
    # at engine start; pinned entries are never evicted under pressure
    pinned_prefixes: Tuple[Tuple[int, ...], ...] = ()
    session_ttl_seconds: float = C.SERVING_KVCACHE_SESSION_TTL_SECONDS_DEFAULT
    spill_dir: str = C.SERVING_KVCACHE_SPILL_DIR_DEFAULT
    # hierarchical HBM -> host -> disk page tiering (docs/serving.md
    # §KV tiering)
    tiers: KVTiersConfig = field(default_factory=KVTiersConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "KVCacheConfig":
        if d is None:
            return cls()
        if isinstance(d, KVCacheConfig):
            d = dataclasses.asdict(d)
        d = dict(d)
        block = f"{C.SERVING}.{C.SERVING_KVCACHE}"
        tiers = KVTiersConfig.from_dict(
            _pop(d, C.SERVING_KVCACHE_TIERS, None))
        raw_pins = _pop(d, "pinned_prefixes", ())
        if raw_pins is None:
            raw_pins = ()
        if not isinstance(raw_pins, (list, tuple)):
            raise DeepSpeedConfigError(
                f"'{block}.pinned_prefixes' must be a list of token-id "
                f"lists, got {type(raw_pins).__name__}"
            )
        pins: List[Tuple[int, ...]] = []
        for i, spec in enumerate(raw_pins):
            if not isinstance(spec, (list, tuple)) or not spec:
                raise DeepSpeedConfigError(
                    f"'{block}.pinned_prefixes[{i}]' must be a non-empty "
                    f"list of token ids"
                )
            pins.append(tuple(int(t) for t in spec))
        out = cls(
            tiers=tiers,
            enabled=bool(_pop(d, "enabled", C.SERVING_KVCACHE_ENABLED_DEFAULT)),
            page_len=int(_pop(d, "page_len", C.SERVING_KVCACHE_PAGE_LEN_DEFAULT)),
            num_pages=int(_pop(d, "num_pages", C.SERVING_KVCACHE_NUM_PAGES_DEFAULT)),
            pinned_prefixes=tuple(pins),
            session_ttl_seconds=float(
                _pop(d, "session_ttl_seconds",
                     C.SERVING_KVCACHE_SESSION_TTL_SECONDS_DEFAULT)
            ),
            spill_dir=str(_pop(d, "spill_dir", C.SERVING_KVCACHE_SPILL_DIR_DEFAULT) or ""),
        )
        _check_empty(d, block, _known_keys(cls))
        if out.page_len < 1:
            raise DeepSpeedConfigError(
                f"'{block}.page_len' must be >= 1, got {out.page_len}"
            )
        if out.num_pages < 0:
            raise DeepSpeedConfigError(
                f"'{block}.num_pages' must be >= 0 (0 derives it from the "
                f"slot capacity), got {out.num_pages}"
            )
        if out.session_ttl_seconds < 0:
            raise DeepSpeedConfigError(
                f"'{block}.session_ttl_seconds' must be >= 0, "
                f"got {out.session_ttl_seconds}"
            )
        return out


@dataclass
class FrontdoorConfig:
    """``serving.frontdoor`` block (docs/serving.md §Front-door): the
    stdlib HTTP front-door — chunked streaming token responses, request
    deadlines mapped onto scheduler deadlines, ``Retry-After``-bearing
    429/503 overload answers, and SIGTERM graceful drain composed with
    the serving watchdog."""

    enabled: bool = C.SERVING_FRONTDOOR_ENABLED_DEFAULT
    host: str = C.SERVING_FRONTDOOR_HOST_DEFAULT
    port: int = C.SERVING_FRONTDOOR_PORT_DEFAULT  # 0 = ephemeral
    stream_poll_seconds: float = C.SERVING_FRONTDOOR_STREAM_POLL_SECONDS_DEFAULT
    max_body_bytes: int = C.SERVING_FRONTDOOR_MAX_BODY_BYTES_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "FrontdoorConfig":
        if d is None:
            return cls()
        if isinstance(d, FrontdoorConfig):
            d = dataclasses.asdict(d)
        d = dict(d)
        block = f"{C.SERVING}.{C.SERVING_FRONTDOOR}"
        out = cls(
            enabled=bool(_pop(d, "enabled", C.SERVING_FRONTDOOR_ENABLED_DEFAULT)),
            host=str(_pop(d, "host", C.SERVING_FRONTDOOR_HOST_DEFAULT)),
            port=int(_pop(d, "port", C.SERVING_FRONTDOOR_PORT_DEFAULT)),
            stream_poll_seconds=float(
                _pop(d, "stream_poll_seconds",
                     C.SERVING_FRONTDOOR_STREAM_POLL_SECONDS_DEFAULT)
            ),
            max_body_bytes=int(
                _pop(d, "max_body_bytes",
                     C.SERVING_FRONTDOOR_MAX_BODY_BYTES_DEFAULT)
            ),
        )
        _check_empty(d, block, _known_keys(cls))
        if not 0 <= out.port <= 65535:
            raise DeepSpeedConfigError(
                f"'{block}.port' must be in [0, 65535] (0 = ephemeral), "
                f"got {out.port}"
            )
        if out.stream_poll_seconds <= 0:
            raise DeepSpeedConfigError(
                f"'{block}.stream_poll_seconds' must be > 0, "
                f"got {out.stream_poll_seconds}"
            )
        if out.max_body_bytes < 1:
            raise DeepSpeedConfigError(
                f"'{block}.max_body_bytes' must be >= 1, "
                f"got {out.max_body_bytes}"
            )
        return out


# per-tenant override spec keys accepted under serving.tenants.overrides
_TENANT_SPEC_KEYS = (
    "refill_tokens_per_second",
    "burst_tokens",
    "weight",
    "slo_class",
    "kv_pages_max",
    "pinned_prefixes_max",
)


@dataclass
class TenantsConfig:
    """``serving.tenants`` block (docs/serving.md §Front-door): the
    multi-tenant dimension — per-tenant token-bucket admission rates,
    weighted-fair queueing ahead of priority tiers, SLO classes mapped
    onto the degradation ladder's priorities, and per-tenant paged-KV
    page / pinned-prefix quotas.  Field values are the defaults for any
    tenant; ``overrides`` refines them per tenant name."""

    enabled: bool = C.SERVING_TENANTS_ENABLED_DEFAULT
    refill_tokens_per_second: float = (
        C.SERVING_TENANTS_REFILL_TOKENS_PER_SECOND_DEFAULT)
    burst_tokens: float = C.SERVING_TENANTS_BURST_TOKENS_DEFAULT
    weight: float = C.SERVING_TENANTS_WEIGHT_DEFAULT
    slo_class: str = C.SERVING_TENANTS_SLO_CLASS_DEFAULT
    kv_pages_max: int = C.SERVING_TENANTS_KV_PAGES_MAX_DEFAULT
    pinned_prefixes_max: int = C.SERVING_TENANTS_PINNED_PREFIXES_MAX_DEFAULT
    overrides: Dict[str, Dict[str, Any]] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TenantsConfig":
        if d is None:
            return cls()
        if isinstance(d, TenantsConfig):
            d = dataclasses.asdict(d)
        d = dict(d)
        block = f"{C.SERVING}.{C.SERVING_TENANTS}"
        raw_over = _pop(d, "overrides", None) or {}
        if not isinstance(raw_over, dict):
            raise DeepSpeedConfigError(
                f"'{block}.overrides' must be a dict of per-tenant spec "
                f"dicts, got {type(raw_over).__name__}"
            )
        overrides: Dict[str, Dict[str, Any]] = {}
        for name, spec in raw_over.items():
            if not isinstance(spec, dict):
                raise DeepSpeedConfigError(
                    f"'{block}.overrides[{name!r}]' must be a dict, "
                    f"got {type(spec).__name__}"
                )
            unknown = sorted(set(spec) - set(_TENANT_SPEC_KEYS))
            if unknown:
                raise DeepSpeedConfigError(
                    f"'{block}.overrides[{name!r}]' has unknown keys "
                    f"{unknown}; known: {sorted(_TENANT_SPEC_KEYS)}"
                )
            slo = spec.get("slo_class")
            if slo is not None and slo not in C.SERVING_TENANTS_SLO_CLASSES:
                raise DeepSpeedConfigError(
                    f"'{block}.overrides[{name!r}].slo_class' must be one "
                    f"of {C.SERVING_TENANTS_SLO_CLASSES}, got '{slo}'"
                )
            overrides[str(name)] = dict(spec)
        out = cls(
            enabled=bool(_pop(d, "enabled", C.SERVING_TENANTS_ENABLED_DEFAULT)),
            refill_tokens_per_second=float(
                _pop(d, "refill_tokens_per_second",
                     C.SERVING_TENANTS_REFILL_TOKENS_PER_SECOND_DEFAULT)
            ),
            burst_tokens=float(
                _pop(d, "burst_tokens", C.SERVING_TENANTS_BURST_TOKENS_DEFAULT)
            ),
            weight=float(_pop(d, "weight", C.SERVING_TENANTS_WEIGHT_DEFAULT)),
            slo_class=str(
                _pop(d, "slo_class", C.SERVING_TENANTS_SLO_CLASS_DEFAULT)
            ).lower(),
            kv_pages_max=int(
                _pop(d, "kv_pages_max", C.SERVING_TENANTS_KV_PAGES_MAX_DEFAULT)
            ),
            pinned_prefixes_max=int(
                _pop(d, "pinned_prefixes_max",
                     C.SERVING_TENANTS_PINNED_PREFIXES_MAX_DEFAULT)
            ),
            overrides=overrides,
        )
        _check_empty(d, block, _known_keys(cls))
        if out.refill_tokens_per_second < 0:
            raise DeepSpeedConfigError(
                f"'{block}.refill_tokens_per_second' must be >= 0 "
                f"(0 with burst_tokens 0 = unlimited), "
                f"got {out.refill_tokens_per_second}"
            )
        if out.burst_tokens < 0:
            raise DeepSpeedConfigError(
                f"'{block}.burst_tokens' must be >= 0, got {out.burst_tokens}"
            )
        if out.weight <= 0:
            raise DeepSpeedConfigError(
                f"'{block}.weight' must be > 0, got {out.weight}"
            )
        if out.slo_class not in C.SERVING_TENANTS_SLO_CLASSES:
            raise DeepSpeedConfigError(
                f"'{block}.slo_class' must be one of "
                f"{C.SERVING_TENANTS_SLO_CLASSES}, got '{out.slo_class}'"
            )
        if out.kv_pages_max < 0 or out.pinned_prefixes_max < 0:
            raise DeepSpeedConfigError(
                f"'{block}.kv_pages_max'/'pinned_prefixes_max' must be >= 0 "
                f"(0 = uncapped), got "
                f"{out.kv_pages_max}/{out.pinned_prefixes_max}"
            )
        return out


@dataclass
class ServingConfig:
    """``serving`` block (TPU-native extension; docs/serving.md): the
    continuous-batching slot-pool engine.  ``num_slots`` concurrent
    sequences share one fixed-shape KV pool; prompts prefill in
    ``prefill_chunk``-token chunks interleaved with decode steps;
    ``max_queue`` bounds admission (submit() rejects past it) and
    ``deadline_seconds`` expires requests that wait too long for a
    slot."""

    num_slots: int = C.SERVING_NUM_SLOTS_DEFAULT
    max_len: int = C.SERVING_MAX_LEN_DEFAULT  # 0 = derive from the engine
    kv_cache_dtype: str = C.SERVING_KV_CACHE_DTYPE_DEFAULT
    prefill_chunk: int = C.SERVING_PREFILL_CHUNK_DEFAULT
    prefill_chunks_per_step: int = C.SERVING_PREFILL_CHUNKS_PER_STEP_DEFAULT
    # hand a step's programs to the device ahead of the host's reads, the
    # decode step first, and read a chunk that is not its prompt's last a
    # step late: the device runs the chunk while the host turns the step
    # (docs/serving.md §Scheduler policy).  Same programs, same tokens; a request
    # decodes from the step after its last chunk, not in it
    overlap_chunks: bool = C.SERVING_OVERLAP_CHUNKS_DEFAULT
    max_queue: int = C.SERVING_MAX_QUEUE_DEFAULT
    max_new_tokens: int = C.SERVING_MAX_NEW_TOKENS_DEFAULT
    deadline_seconds: float = C.SERVING_DEADLINE_SECONDS_DEFAULT
    # static top-k head width for per-slot sampling: traced per-request
    # top_k thresholds against the top-max_top_k logits (one executable
    # for any greedy/sampled mix); submit() rejects top_k > max_top_k
    max_top_k: int = C.SERVING_MAX_TOP_K_DEFAULT
    # -- resilience (docs/serving.md §Resilience) ----------------------
    # estimated-TTFT admission test: shed normal/low-priority submits
    # whose estimated TTFT (queue backlog / measured step rate) exceeds
    # this; 0 disables the test (hard max_queue bound still applies)
    slo_ttft_ms: float = C.SERVING_SLO_TTFT_MS_DEFAULT
    # degradation ladder: engage on queue_depth >= watermark*max_queue
    # sustained degrade_engage_steps ticks, step back down after
    # degrade_disengage_steps calm ticks (hysteresis)
    degrade_queue_watermark: float = C.SERVING_DEGRADE_QUEUE_WATERMARK_DEFAULT
    degrade_engage_steps: int = C.SERVING_DEGRADE_ENGAGE_STEPS_DEFAULT
    degrade_disengage_steps: int = C.SERVING_DEGRADE_DISENGAGE_STEPS_DEFAULT
    degrade_max_new_tokens: int = C.SERVING_DEGRADE_MAX_NEW_TOKENS_DEFAULT
    # graceful drain: SIGTERM stops admission and drains in-flight
    # requests for at most this long before the journal commit + exit 43
    drain_deadline_seconds: float = C.SERVING_DRAIN_DEADLINE_SECONDS_DEFAULT
    # write-ahead request journal ("" = off): submit/admit/first-token/
    # retire records under serving/journal.py's atomic segment protocol
    journal_dir: str = C.SERVING_JOURNAL_DIR_DEFAULT
    journal_segment_records: int = C.SERVING_JOURNAL_SEGMENT_RECORDS_DEFAULT
    journal_keep_segments: int = C.SERVING_JOURNAL_KEEP_SEGMENTS_DEFAULT
    # fleet front-door (docs/serving.md §Fleet): router + breaker +
    # hedging + supervised replica restart over N engine replicas
    fleet: FleetConfig = field(default_factory=FleetConfig)
    # paged KV pool with prefix dedup + COW + session reuse
    # (docs/serving.md §Paged KV & prefix caching)
    kvcache: KVCacheConfig = field(default_factory=KVCacheConfig)
    # stdlib HTTP front-door with chunked streaming + graceful drain
    # (docs/serving.md §Front-door)
    frontdoor: FrontdoorConfig = field(default_factory=FrontdoorConfig)
    # multi-tenant fairness/SLO/quota dimension (docs/serving.md
    # §Front-door)
    tenants: TenantsConfig = field(default_factory=TenantsConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ServingConfig":
        if d is None:
            return cls()
        d = dict(d)
        fleet = FleetConfig.from_dict(_pop(d, C.SERVING_FLEET, None))
        kvcache = KVCacheConfig.from_dict(_pop(d, C.SERVING_KVCACHE, None))
        frontdoor = FrontdoorConfig.from_dict(
            _pop(d, C.SERVING_FRONTDOOR, None))
        tenants = TenantsConfig.from_dict(_pop(d, C.SERVING_TENANTS, None))
        out = cls(
            fleet=fleet,
            kvcache=kvcache,
            frontdoor=frontdoor,
            tenants=tenants,
            num_slots=int(_pop(d, "num_slots", C.SERVING_NUM_SLOTS_DEFAULT)),
            max_len=int(_pop(d, "max_len", C.SERVING_MAX_LEN_DEFAULT)),
            kv_cache_dtype=str(
                _pop(d, "kv_cache_dtype", C.SERVING_KV_CACHE_DTYPE_DEFAULT)
            ).lower(),
            prefill_chunk=int(_pop(d, "prefill_chunk", C.SERVING_PREFILL_CHUNK_DEFAULT)),
            prefill_chunks_per_step=int(
                _pop(d, "prefill_chunks_per_step", C.SERVING_PREFILL_CHUNKS_PER_STEP_DEFAULT)
            ),
            overlap_chunks=bool(_pop(d, "overlap_chunks", C.SERVING_OVERLAP_CHUNKS_DEFAULT)),
            max_queue=int(_pop(d, "max_queue", C.SERVING_MAX_QUEUE_DEFAULT)),
            max_new_tokens=int(_pop(d, "max_new_tokens", C.SERVING_MAX_NEW_TOKENS_DEFAULT)),
            deadline_seconds=float(
                _pop(d, "deadline_seconds", C.SERVING_DEADLINE_SECONDS_DEFAULT)
            ),
            max_top_k=int(_pop(d, "max_top_k", C.SERVING_MAX_TOP_K_DEFAULT)),
            slo_ttft_ms=float(_pop(d, "slo_ttft_ms", C.SERVING_SLO_TTFT_MS_DEFAULT)),
            degrade_queue_watermark=float(
                _pop(d, "degrade_queue_watermark", C.SERVING_DEGRADE_QUEUE_WATERMARK_DEFAULT)
            ),
            degrade_engage_steps=int(
                _pop(d, "degrade_engage_steps", C.SERVING_DEGRADE_ENGAGE_STEPS_DEFAULT)
            ),
            degrade_disengage_steps=int(
                _pop(d, "degrade_disengage_steps", C.SERVING_DEGRADE_DISENGAGE_STEPS_DEFAULT)
            ),
            degrade_max_new_tokens=int(
                _pop(d, "degrade_max_new_tokens", C.SERVING_DEGRADE_MAX_NEW_TOKENS_DEFAULT)
            ),
            drain_deadline_seconds=float(
                _pop(d, "drain_deadline_seconds", C.SERVING_DRAIN_DEADLINE_SECONDS_DEFAULT)
            ),
            journal_dir=str(_pop(d, "journal_dir", C.SERVING_JOURNAL_DIR_DEFAULT) or ""),
            journal_segment_records=int(
                _pop(d, "journal_segment_records", C.SERVING_JOURNAL_SEGMENT_RECORDS_DEFAULT)
            ),
            journal_keep_segments=int(
                _pop(d, "journal_keep_segments", C.SERVING_JOURNAL_KEEP_SEGMENTS_DEFAULT)
            ),
        )
        _check_empty(d, C.SERVING, _known_keys(cls))
        if out.max_top_k < 1:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.max_top_k' must be >= 1, got {out.max_top_k}"
            )
        if out.num_slots < 1:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.num_slots' must be >= 1, got {out.num_slots}"
            )
        if out.kv_cache_dtype not in C.SERVING_KV_CACHE_DTYPES:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.kv_cache_dtype' must be one of "
                f"{C.SERVING_KV_CACHE_DTYPES}, got '{out.kv_cache_dtype}'"
            )
        if out.prefill_chunk < 1:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.prefill_chunk' must be >= 1, got {out.prefill_chunk}"
            )
        if out.prefill_chunks_per_step < 1:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.prefill_chunks_per_step' must be >= 1, "
                f"got {out.prefill_chunks_per_step}"
            )
        if out.max_len < 0:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.max_len' must be >= 0 (0 derives it from the "
                f"engine's capacity), got {out.max_len}"
            )
        if out.max_len and out.max_len % out.prefill_chunk and not out.kvcache.enabled:
            # the slot-contiguous pool's chunk writes land via one
            # dynamic_update_slice, whose start clamps near the cache end —
            # a chunk-multiple capacity is what guarantees the last chunk
            # never clamps.  Under the paged pool the rule is the cache
            # kind's: ServingEngine keeps it for every kind that does not
            # declare ``chunk_writes_drop_past_slot`` (docs/serving.md)
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.max_len' ({out.max_len}) must be a multiple of "
                f"prefill_chunk ({out.prefill_chunk})"
            )
        if out.max_queue < 0:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.max_queue' must be >= 0, got {out.max_queue}"
            )
        if out.max_new_tokens < 1:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.max_new_tokens' must be >= 1, got {out.max_new_tokens}"
            )
        if out.deadline_seconds < 0:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.deadline_seconds' must be >= 0, got {out.deadline_seconds}"
            )
        if out.slo_ttft_ms < 0:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.slo_ttft_ms' must be >= 0 (0 disables the "
                f"admission test), got {out.slo_ttft_ms}"
            )
        if not 0.0 < out.degrade_queue_watermark <= 1.0:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.degrade_queue_watermark' must be in (0, 1] "
                f"(a fraction of max_queue), got {out.degrade_queue_watermark}"
            )
        if out.degrade_engage_steps < 1 or out.degrade_disengage_steps < 1:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.degrade_engage_steps'/'degrade_disengage_steps' must "
                f"be >= 1, got {out.degrade_engage_steps}/{out.degrade_disengage_steps}"
            )
        if out.degrade_max_new_tokens < 0:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.degrade_max_new_tokens' must be >= 0 (0 disables "
                f"the clamp rung), got {out.degrade_max_new_tokens}"
            )
        if out.drain_deadline_seconds < 0:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.drain_deadline_seconds' must be >= 0, "
                f"got {out.drain_deadline_seconds}"
            )
        if out.journal_segment_records < 1:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.journal_segment_records' must be >= 1, "
                f"got {out.journal_segment_records}"
            )
        if out.journal_keep_segments < 1:
            raise DeepSpeedConfigError(
                f"'{C.SERVING}.journal_keep_segments' must be >= 1, "
                f"got {out.journal_keep_segments}"
            )
        return out


@dataclass
class SanitizerConfig:
    """``sanitizer`` block (ds_san; docs/ds_san.md).  Opt-in runtime
    checkers around the engine step: recompile-storm detection, implicit
    transfer attribution, use-after-donation, sharding drift, NaN
    provenance.  ``DS_SAN=1`` activates the env defaults without a
    config edit — the launch-time switch arms the sanitizer even when
    this block is absent or says disabled."""

    enabled: bool = C.SAN_ENABLED_DEFAULT
    checkers: List[str] = field(default_factory=lambda: list(C.SAN_CHECKERS))
    compile_budget: int = C.SAN_COMPILE_BUDGET_DEFAULT
    drift_interval: int = C.SAN_DRIFT_INTERVAL_DEFAULT
    report_path: Optional[str] = None

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "SanitizerConfig":
        if d is None:
            return cls()
        d = dict(d)
        explicit_enabled = "enabled" in d
        raw = _pop(d, "checkers", None)
        checkers = list(C.SAN_CHECKERS) if raw is None else [str(c).lower() for c in raw]
        out = cls(
            enabled=bool(_pop(d, "enabled", C.SAN_ENABLED_DEFAULT)),
            checkers=checkers,
            compile_budget=int(_pop(d, "compile_budget", C.SAN_COMPILE_BUDGET_DEFAULT)),
            drift_interval=int(_pop(d, "drift_interval", C.SAN_DRIFT_INTERVAL_DEFAULT)),
            report_path=_pop(d, "report_path", None),
        )
        _check_empty(d, C.SANITIZER, _known_keys(cls))
        unknown = set(out.checkers) - set(C.SAN_CHECKERS)
        if unknown:
            raise DeepSpeedConfigError(
                f"'{C.SANITIZER}.checkers' has unknown checker(s) "
                f"{sorted(unknown)}; valid: {C.SAN_CHECKERS}"
            )
        if out.compile_budget < 1:
            raise DeepSpeedConfigError(
                f"'{C.SANITIZER}.compile_budget' must be >= 1, got {out.compile_budget}"
            )
        if out.drift_interval < 1:
            raise DeepSpeedConfigError(
                f"'{C.SANITIZER}.drift_interval' must be >= 1, got {out.drift_interval}"
            )
        # an `enabled` key written in the JSON is an explicit decision:
        # `enabled: false` there opts the engine out even of a
        # process-wide (env/CLI-installed) sanitizer — but a block that
        # only tunes knobs must not disarm a DS_SAN=1 launch
        out._explicit = explicit_enabled
        return out

    @classmethod
    def from_env(cls, base: Optional["SanitizerConfig"] = None) -> "SanitizerConfig":
        """``DS_SAN=1`` defaults, refined by ``DS_SAN_CHECKERS`` (comma
        list), ``DS_SAN_BUDGET`` and ``DS_SAN_DRIFT_INTERVAL``.  ``base``
        (a knobs-only config block from the JSON) supplies the starting
        values so an env-armed launch keeps the block's tuning."""
        import os

        d: Dict[str, Any] = {"enabled": os.environ.get("DS_SAN", "") == "1"}
        if base is not None:
            d.update(
                checkers=list(base.checkers),
                compile_budget=base.compile_budget,
                drift_interval=base.drift_interval,
                report_path=base.report_path,
            )
        raw = os.environ.get("DS_SAN_CHECKERS")
        if raw:
            d["checkers"] = [c.strip() for c in raw.split(",") if c.strip()]
        if os.environ.get("DS_SAN_BUDGET"):
            d["compile_budget"] = int(os.environ["DS_SAN_BUDGET"])
        if os.environ.get("DS_SAN_DRIFT_INTERVAL"):
            d["drift_interval"] = int(os.environ["DS_SAN_DRIFT_INTERVAL"])
        if os.environ.get("DS_SAN_REPORT"):
            d["report_path"] = os.environ["DS_SAN_REPORT"]
        return cls.from_dict(d)


@dataclass
class ActivationCheckpointingConfig:
    """Reference ``runtime/activation_checkpointing/config.py``.  On TPU,
    ``partition_activations`` maps to sharding saved residuals over the
    model axis; ``cpu_checkpointing`` maps to a host-offload remat policy."""

    partition_activations: bool = False
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ActivationCheckpointingConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            partition_activations=bool(_pop(d, "partition_activations", False)),
            contiguous_memory_optimization=bool(_pop(d, "contiguous_memory_optimization", False)),
            cpu_checkpointing=bool(_pop(d, "cpu_checkpointing", False)),
            number_checkpoints=_pop(d, "number_checkpoints", None),
            synchronize_checkpoint_boundary=bool(_pop(d, "synchronize_checkpoint_boundary", False)),
            profile=bool(_pop(d, "profile", False)),
        )
        _check_empty(d, "activation_checkpointing", _known_keys(cls))
        return out


@dataclass
class FlopsProfilerConfig:
    enabled: bool = False
    # default 2, not the reference's 1: under JAX, step 1 includes the XLA
    # compile, which would make the timed window meaningless
    profile_step: int = 2
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "FlopsProfilerConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, "enabled", False)),
            profile_step=int(_pop(d, "profile_step", 2)),
            module_depth=int(_pop(d, "module_depth", -1)),
            top_modules=int(_pop(d, "top_modules", 1)),
            detailed=bool(_pop(d, "detailed", True)),
            output_file=_pop(d, "output_file", None),
        )
        _check_empty(d, "flops_profiler", _known_keys(cls))
        return out


@dataclass
class TelemetryConfig:
    """``telemetry`` block (TPU-native extension; docs/telemetry.md):
    the unified observability plane.  ``enabled`` arms the in-process
    metrics registry (host dict updates only — measured <1% steps/s;
    docs/telemetry.md overhead table); ``exporters`` turn on background
    sinks (``jsonl`` | ``prometheus`` | ``tensorboard``) flushing every
    ``export_interval_seconds`` off the hot path; ``trace`` records
    Chrome-trace spans (StepTimeline phases, checkpoint writer, serving
    request lifecycles) exported to ``trace_path``; ``profiler_dir``
    enables the programmatic ``jax.profiler`` window capture
    (on demand, or on the first serving TTFT above
    ``slo_ttft_breach_ms``); ``aggregate`` piggybacks compact metric
    snapshots on the supervision heartbeat so rank 0 exports cluster
    min/mean/max with dead-rank flags in the same stream."""

    enabled: bool = C.TELEMETRY_ENABLED_DEFAULT
    ring: int = C.TELEMETRY_RING_DEFAULT
    exporters: Tuple[str, ...] = ()
    export_interval_seconds: float = C.TELEMETRY_EXPORT_INTERVAL_DEFAULT
    output_path: str = C.TELEMETRY_OUTPUT_PATH_DEFAULT
    trace: bool = C.TELEMETRY_TRACE_ENABLED_DEFAULT
    trace_path: str = ""  # "" = <output_path>/trace.json
    trace_buffer_events: int = C.TELEMETRY_TRACE_BUFFER_DEFAULT
    profiler_dir: str = ""
    profiler_capture_ms: int = C.TELEMETRY_PROFILER_CAPTURE_MS_DEFAULT
    slo_ttft_breach_ms: float = C.TELEMETRY_SLO_TTFT_BREACH_MS_DEFAULT
    aggregate: bool = C.TELEMETRY_AGGREGATE_DEFAULT
    # runtime anomaly watch (telemetry/anomaly.py)
    spike_factor: float = C.TELEMETRY_SPIKE_FACTOR_DEFAULT
    spike_min_window: int = C.TELEMETRY_SPIKE_MIN_WINDOW_DEFAULT
    straggler_factor: float = C.TELEMETRY_STRAGGLER_FACTOR_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TelemetryConfig":
        if d is None:
            return cls()
        d = dict(d)
        raw_exp = _pop(d, "exporters", ())
        if isinstance(raw_exp, str):
            raw_exp = [raw_exp]
        out = cls(
            enabled=bool(_pop(d, "enabled", C.TELEMETRY_ENABLED_DEFAULT)),
            ring=int(_pop(d, "ring", C.TELEMETRY_RING_DEFAULT)),
            exporters=tuple(str(e).lower() for e in raw_exp),
            export_interval_seconds=float(
                _pop(d, "export_interval_seconds", C.TELEMETRY_EXPORT_INTERVAL_DEFAULT)
            ),
            output_path=str(_pop(d, C.TELEMETRY_OUTPUT_PATH, C.TELEMETRY_OUTPUT_PATH_DEFAULT)),
            trace=bool(_pop(d, "trace", C.TELEMETRY_TRACE_ENABLED_DEFAULT)),
            trace_path=str(_pop(d, "trace_path", "")),
            trace_buffer_events=int(
                _pop(d, "trace_buffer_events", C.TELEMETRY_TRACE_BUFFER_DEFAULT)
            ),
            profiler_dir=str(_pop(d, "profiler_dir", "")),
            profiler_capture_ms=int(
                _pop(d, "profiler_capture_ms", C.TELEMETRY_PROFILER_CAPTURE_MS_DEFAULT)
            ),
            slo_ttft_breach_ms=float(
                _pop(d, "slo_ttft_breach_ms", C.TELEMETRY_SLO_TTFT_BREACH_MS_DEFAULT)
            ),
            aggregate=bool(_pop(d, "aggregate", C.TELEMETRY_AGGREGATE_DEFAULT)),
            spike_factor=float(_pop(d, "spike_factor", C.TELEMETRY_SPIKE_FACTOR_DEFAULT)),
            spike_min_window=int(
                _pop(d, "spike_min_window", C.TELEMETRY_SPIKE_MIN_WINDOW_DEFAULT)
            ),
            straggler_factor=float(
                _pop(d, "straggler_factor", C.TELEMETRY_STRAGGLER_FACTOR_DEFAULT)
            ),
        )
        _check_empty(d, C.TELEMETRY, _known_keys(cls))
        unknown = set(out.exporters) - set(C.TELEMETRY_EXPORTERS)
        if unknown:
            raise DeepSpeedConfigError(
                f"'{C.TELEMETRY}.exporters' must be a subset of "
                f"{C.TELEMETRY_EXPORTERS}, got {sorted(unknown)}"
            )
        if out.ring < 16:
            raise DeepSpeedConfigError(
                f"'{C.TELEMETRY}.ring' must be >= 16, got {out.ring}"
            )
        if out.export_interval_seconds <= 0:
            raise DeepSpeedConfigError(
                f"'{C.TELEMETRY}.export_interval_seconds' must be > 0, "
                f"got {out.export_interval_seconds}"
            )
        if out.trace_buffer_events < 1000:
            raise DeepSpeedConfigError(
                f"'{C.TELEMETRY}.trace_buffer_events' must be >= 1000, "
                f"got {out.trace_buffer_events}"
            )
        if out.profiler_capture_ms <= 0:
            raise DeepSpeedConfigError(
                f"'{C.TELEMETRY}.profiler_capture_ms' must be > 0, "
                f"got {out.profiler_capture_ms}"
            )
        if out.slo_ttft_breach_ms < 0:
            raise DeepSpeedConfigError(
                f"'{C.TELEMETRY}.slo_ttft_breach_ms' must be >= 0, "
                f"got {out.slo_ttft_breach_ms}"
            )
        if out.spike_factor <= 1.0:
            raise DeepSpeedConfigError(
                f"'{C.TELEMETRY}.spike_factor' must be > 1, got {out.spike_factor}"
            )
        if out.straggler_factor <= 1.0:
            raise DeepSpeedConfigError(
                f"'{C.TELEMETRY}.straggler_factor' must be > 1, "
                f"got {out.straggler_factor}"
            )
        return out


@dataclass
class TensorboardConfig:
    enabled: bool = C.TENSORBOARD_ENABLED_DEFAULT
    output_path: str = C.TENSORBOARD_OUTPUT_PATH_DEFAULT
    job_name: str = C.TENSORBOARD_JOB_NAME_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TensorboardConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, C.TENSORBOARD_ENABLED, C.TENSORBOARD_ENABLED_DEFAULT)),
            output_path=_pop(d, C.TENSORBOARD_OUTPUT_PATH, C.TENSORBOARD_OUTPUT_PATH_DEFAULT),
            job_name=_pop(d, C.TENSORBOARD_JOB_NAME, C.TENSORBOARD_JOB_NAME_DEFAULT),
        )
        _check_empty(d, C.TENSORBOARD, _known_keys(cls))
        return out


@dataclass
class PipelineConfig:
    """``pipeline`` block (reference ``runtime/config.py:409`` area)."""

    stages: Any = "auto"
    partition: str = "best"
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    # "1f1b" (reference TrainSchedule, schedule.py:182 — live activations
    # bounded by the stage count) or "gpipe" (all-forward-then-all-
    # backward — lower bubble in the compiled formulation, O(M) memory)
    schedule: str = "1f1b"

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "PipelineConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            stages=_pop(d, "stages", "auto"),
            partition=_pop(d, "partition", "best"),
            seed_layers=bool(_pop(d, "seed_layers", False)),
            activation_checkpoint_interval=int(_pop(d, "activation_checkpoint_interval", 0)),
            schedule=str(_pop(d, "schedule", "1f1b")).lower(),
        )
        if out.schedule not in ("1f1b", "gpipe"):
            raise ValueError(f"pipeline.schedule must be '1f1b' or 'gpipe', got {out.schedule!r}")
        _check_empty(d, C.PIPELINE, _known_keys(cls))
        return out


@dataclass
class AioConfig:
    """``aio`` block (reference ``runtime/swap_tensor/aio_config.py``)."""

    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "AioConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            block_size=int(_pop(d, "block_size", 1048576)),
            queue_depth=int(_pop(d, "queue_depth", 8)),
            thread_count=int(_pop(d, "thread_count", 1)),
            single_submit=bool(_pop(d, "single_submit", False)),
            overlap_events=bool(_pop(d, "overlap_events", True)),
        )
        _check_empty(d, "aio", _known_keys(cls))
        return out


@dataclass
class QuantizeTrainingConfig:
    """MoQ progressive quantize-training (reference ``runtime/config.py:186-221``)."""

    enabled: bool = False
    quantize_verbose: bool = False
    quantizer_kernel: bool = False
    quantize_type: str = "symmetric"
    quantize_bits_start: int = 16
    quantize_bits_target: int = 8
    quantize_schedule_offset: int = 1000
    quantize_groups: int = 1
    fp16_mixed_quantize: bool = False
    quantize_change_ratio: float = 0.001
    quantize_rounding: str = "nearest"  # nearest | stochastic
    eigenvalue_enabled: bool = False
    eigenvalue_verbose: bool = False
    eigenvalue_max_iter: int = 100
    eigenvalue_tol: float = 1e-2
    eigenvalue_stability: float = 1e-6
    eigenvalue_gas_boundary_resolution: int = 1
    eigenvalue_layer_name: str = "bert.encoder.layer"
    eigenvalue_layer_num: int = 0

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "QuantizeTrainingConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, "enabled", False)),
            quantize_verbose=bool(_pop(d, "quantize_verbose", False)),
            quantizer_kernel=bool(_pop(d, "quantizer_kernel", False)),
            quantize_type=_pop(d, "quantize_type", "symmetric"),
            quantize_bits_start=int(_pop_alias(d, "quantize_bits_start", "start_bits", 16, "quantize_training")),
            quantize_bits_target=int(_pop_alias(d, "quantize_bits_target", "target_bits", 8, "quantize_training")),
            quantize_schedule_offset=int(_pop(d, "quantize_schedule_offset", 1000)),
            quantize_groups=int(_pop(d, "quantize_groups", 1)),
            fp16_mixed_quantize=bool(_pop(d, "fp16_mixed_quantize", False)),
            quantize_change_ratio=float(_pop(d, "quantize_change_ratio", 0.001)),
            quantize_rounding=_pop(d, "quantize_rounding", "nearest"),
            eigenvalue_enabled=bool(_pop(d, "eigenvalue_enabled", False)),
            eigenvalue_verbose=bool(_pop(d, "eigenvalue_verbose", False)),
            eigenvalue_max_iter=int(_pop(d, "eigenvalue_max_iter", 100)),
            eigenvalue_tol=float(_pop(d, "eigenvalue_tol", 1e-2)),
            eigenvalue_stability=float(_pop(d, "eigenvalue_stability", 1e-6)),
            eigenvalue_gas_boundary_resolution=int(_pop(d, "eigenvalue_gas_boundary_resolution", 1)),
            eigenvalue_layer_name=_pop(d, "eigenvalue_layer_name", "bert.encoder.layer"),
            eigenvalue_layer_num=int(_pop(d, "eigenvalue_layer_num", 0)),
        )
        _check_empty(d, "quantize_training", _known_keys(cls, "start_bits", "target_bits"))
        return out


@dataclass
class ProgressiveLayerDropConfig:
    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ProgressiveLayerDropConfig":
        if d is None:
            return cls()
        d = dict(d)
        out = cls(
            enabled=bool(_pop(d, "enabled", False)),
            theta=float(_pop(d, "theta", 0.5)),
            gamma=float(_pop(d, "gamma", 0.001)),
        )
        _check_empty(d, "progressive_layer_drop", _known_keys(cls))
        return out


@dataclass
class SparseAttentionConfig:
    mode: Optional[str] = None  # dense|fixed|variable|bigbird|bslongformer
    params: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "SparseAttentionConfig":
        if d is None:
            return cls()
        d = dict(d)
        mode = _pop(d, "mode", None)
        # remaining keys are mode params (block, different_layout_per_head, ...)
        return cls(mode=mode, params=d)


_KNOWN_TOP_LEVEL = {
    C.TRAIN_BATCH_SIZE,
    C.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
    C.GRADIENT_ACCUMULATION_STEPS,
    C.OPTIMIZER,
    C.SCHEDULER,
    C.FP16,
    C.BF16,
    C.AMP,
    C.GRADIENT_CLIPPING,
    C.PRESCALE_GRADIENTS,
    C.GRADIENT_PREDIVIDE_FACTOR,
    C.SPARSE_GRADIENTS,
    C.ALLREDUCE_ALWAYS_FP32,
    C.ZERO_OPTIMIZATION,
    C.STEPS_PER_PRINT,
    C.WALL_CLOCK_BREAKDOWN,
    C.MEMORY_BREAKDOWN,
    C.DUMP_STATE,
    C.DISABLE_ALLGATHER,
    C.TENSORBOARD,
    C.PIPELINE,
    C.CHECKPOINT_TAG_VALIDATION,
    C.MESH,
    C.RESILIENCE,
    C.OVERLAP,
    C.SANITIZER,
    C.COMM,
    C.SERVING,
    C.TELEMETRY,
    C.KERNELS,
    "activation_checkpointing",
    "flops_profiler",
    "aio",
    "elasticity",
    "quantize_training",
    "progressive_layer_drop",
    "sparse_attention",
    "zero_allow_untested_optimizer",
    "dataloader_drop_last",
    "seed",
}


@dataclass
class KernelsConfig:
    """``kernels`` block (TPU-native extension; docs/kernels.md): the
    Pallas kernel suite.  ``enabled``: ``"auto"`` arms the suite on
    device platform ``tpu`` only (the lax/XLA paths stay the CPU ground
    truth); ``true``/``false`` force it.  ``flash_decode`` /
    ``fused_update`` subtract individual kernels from an armed suite.
    ``autotune`` is the block-size tuner mode (``off`` = deterministic
    defaults only, ``cache`` = read cached measured winners, ``force``
    = allow re-measuring); ``autotune_cache_path`` overrides where the
    JSON cache lives (default: next to the persistent compile cache).
    The ``DS_KERNELS`` / ``DS_KERNEL_AUTOTUNE`` env vars win over this
    block (escape hatches)."""

    enabled: Any = C.KERNELS_ENABLED_AUTO
    flash_decode: bool = C.KERNELS_FLASH_DECODE_DEFAULT
    fused_update: bool = C.KERNELS_FUSED_UPDATE_DEFAULT
    autotune: str = C.KERNELS_AUTOTUNE_DEFAULT
    autotune_cache_path: str = ""

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "KernelsConfig":
        if d is None:
            return cls()
        d = dict(d)
        enabled = _pop(d, "enabled", C.KERNELS_ENABLED_AUTO)
        out = cls(
            enabled=enabled,
            flash_decode=bool(_pop(d, "flash_decode", C.KERNELS_FLASH_DECODE_DEFAULT)),
            fused_update=bool(_pop(d, "fused_update", C.KERNELS_FUSED_UPDATE_DEFAULT)),
            autotune=str(_pop(d, "autotune", C.KERNELS_AUTOTUNE_DEFAULT)).lower(),
            autotune_cache_path=str(_pop(d, "autotune_cache_path", "")),
        )
        _check_empty(d, C.KERNELS, _known_keys(cls))
        if out.enabled not in C.KERNELS_ENABLED_CHOICES:
            raise DeepSpeedConfigError(
                f"'{C.KERNELS}.enabled' must be one of {C.KERNELS_ENABLED_CHOICES}, "
                f"got {out.enabled!r}"
            )
        if out.autotune not in C.KERNELS_AUTOTUNE_MODES:
            raise DeepSpeedConfigError(
                f"'{C.KERNELS}.autotune' must be one of {C.KERNELS_AUTOTUNE_MODES}, "
                f"got {out.autotune!r}"
            )
        return out


class DeepSpeedConfig:
    """Parse a config dict / JSON path and resolve the batch-size triad.

    ``world_size`` here is the *data-parallel* world size (``data × fsdp``
    mesh axes), matching the reference's use of dp_world_size in
    ``runtime/config.py:736-898``.
    """

    def __init__(self, config: Any, world_size: Optional[int] = None, mesh_shape: Optional[Dict[str, int]] = None):
        if isinstance(config, str):
            with open(config, "r") as f:
                d = json.load(f)
        elif isinstance(config, dict):
            d = json.loads(json.dumps(config))  # deep copy + json-type check
        else:
            raise DeepSpeedConfigError(f"config must be a dict or a path to a JSON file, got {type(config)}")

        unknown = set(d.keys()) - _KNOWN_TOP_LEVEL
        if unknown:
            raise DeepSpeedConfigError(
                "Unknown top-level config key(s): "
                + _describe_unknown(unknown, "", _KNOWN_TOP_LEVEL)
            )

        self._raw = d
        self.train_batch_size = d.get(C.TRAIN_BATCH_SIZE)
        self.train_micro_batch_size_per_gpu = d.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        self.gradient_accumulation_steps = d.get(C.GRADIENT_ACCUMULATION_STEPS)

        self.optimizer = OptimizerConfig.from_dict(d.get(C.OPTIMIZER))
        self.scheduler = SchedulerConfig.from_dict(d.get(C.SCHEDULER))
        self.fp16 = Fp16Config.from_dict(d.get(C.FP16))
        self.bf16 = Bf16Config.from_dict(d.get(C.BF16))
        self.zero_config = ZeroConfig.from_dict(d.get(C.ZERO_OPTIMIZATION))
        self.mesh = MeshConfig.from_dict(d.get(C.MESH))
        if mesh_shape:
            for axis, size in mesh_shape.items():
                setattr(self.mesh, axis, size)
        self.activation_checkpointing = ActivationCheckpointingConfig.from_dict(d.get("activation_checkpointing"))
        self.flops_profiler = FlopsProfilerConfig.from_dict(d.get("flops_profiler"))
        self.tensorboard = TensorboardConfig.from_dict(d.get(C.TENSORBOARD))
        self.pipeline = PipelineConfig.from_dict(d.get(C.PIPELINE))
        self.aio = AioConfig.from_dict(d.get("aio"))
        self.quantize_training = QuantizeTrainingConfig.from_dict(d.get("quantize_training"))
        self.progressive_layer_drop = ProgressiveLayerDropConfig.from_dict(d.get("progressive_layer_drop"))
        self.sparse_attention = SparseAttentionConfig.from_dict(d.get("sparse_attention"))
        self.resilience = ResilienceConfig.from_dict(d.get(C.RESILIENCE))
        self.overlap = OverlapConfig.from_dict(d.get(C.OVERLAP))
        self.sanitizer = SanitizerConfig.from_dict(d.get(C.SANITIZER))
        self.comm = CommConfig.from_dict(d.get(C.COMM))
        self.serving = ServingConfig.from_dict(d.get(C.SERVING))
        self.telemetry = TelemetryConfig.from_dict(d.get(C.TELEMETRY))
        self.kernels = KernelsConfig.from_dict(d.get(C.KERNELS))
        self.elasticity_dict = d.get("elasticity")

        self.gradient_clipping = float(d.get(C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT))
        self.prescale_gradients = bool(d.get(C.PRESCALE_GRADIENTS, C.PRESCALE_GRADIENTS_DEFAULT))
        self.gradient_predivide_factor = float(d.get(C.GRADIENT_PREDIVIDE_FACTOR, C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT))
        self.sparse_gradients_enabled = bool(d.get(C.SPARSE_GRADIENTS, C.SPARSE_GRADIENTS_DEFAULT))
        self.allreduce_always_fp32 = bool(d.get(C.ALLREDUCE_ALWAYS_FP32, C.ALLREDUCE_ALWAYS_FP32_DEFAULT))
        self.steps_per_print = int(d.get(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT))
        self.wall_clock_breakdown = bool(d.get(C.WALL_CLOCK_BREAKDOWN, C.WALL_CLOCK_BREAKDOWN_DEFAULT))
        self.memory_breakdown = bool(d.get(C.MEMORY_BREAKDOWN, C.MEMORY_BREAKDOWN_DEFAULT))
        self.dump_state = bool(d.get(C.DUMP_STATE, C.DUMP_STATE_DEFAULT))
        self.disable_allgather = bool(d.get(C.DISABLE_ALLGATHER, C.DISABLE_ALLGATHER_DEFAULT))
        self.checkpoint_tag_validation_mode = d.get(C.CHECKPOINT_TAG_VALIDATION, C.CHECKPOINT_TAG_VALIDATION_DEFAULT)
        self.zero_allow_untested_optimizer = bool(d.get("zero_allow_untested_optimizer", False))
        self.dataloader_drop_last = bool(d.get("dataloader_drop_last", False))
        self.seed = int(d.get("seed", 42))

        if self.checkpoint_tag_validation_mode not in C.CHECKPOINT_TAG_VALIDATION_MODES:
            raise DeepSpeedConfigError(
                f"checkpoint_tag_validation must be one of {C.CHECKPOINT_TAG_VALIDATION_MODES}"
            )
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")

        self.world_size = world_size if world_size is not None else 1
        self._resolve_batch_triad()

    # --- batch triad (reference runtime/config.py:736-898) ---
    def _resolve_batch_triad(self) -> None:
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        ws = self.world_size

        if train is not None and micro is not None and gas is not None:
            pass
        elif train is not None and micro is not None:
            gas, rem = divmod(train, micro * ws)
            if rem:
                raise DeepSpeedConfigError(
                    f"train_batch_size ({train}) not divisible by micro_batch*world_size ({micro}*{ws})"
                )
        elif train is not None and gas is not None:
            micro, rem = divmod(train, gas * ws)
            if rem:
                raise DeepSpeedConfigError(
                    f"train_batch_size ({train}) not divisible by grad_accum*world_size ({gas}*{ws})"
                )
        elif micro is not None and gas is not None:
            train = micro * gas * ws
        elif train is not None:
            gas = 1
            micro, rem = divmod(train, ws)
            if rem:
                raise DeepSpeedConfigError(f"train_batch_size ({train}) not divisible by world_size ({ws})")
        elif micro is not None:
            gas = 1
            train = micro * ws
        else:
            raise DeepSpeedConfigError(
                "At least one of train_batch_size / train_micro_batch_size_per_gpu must be set"
            )

        self.train_batch_size = int(train)
        self.train_micro_batch_size_per_gpu = int(micro)
        self.gradient_accumulation_steps = int(gas)
        if self.train_batch_size != self.train_micro_batch_size_per_gpu * self.gradient_accumulation_steps * ws:
            raise DeepSpeedConfigError(
                f"Batch triad check failed: {self.train_batch_size} != "
                f"{self.train_micro_batch_size_per_gpu} * {self.gradient_accumulation_steps} * {ws}"
            )

    # --- convenience ---
    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self) -> int:
        return self.zero_config.stage

    @property
    def compute_dtype(self) -> str:
        if self.fp16.enabled:
            return "float16"
        if self.bf16.enabled:
            return "bfloat16"
        return "float32"

    def print_config(self) -> str:
        return json.dumps(self._raw, indent=2, sort_keys=True)
