"""The training engine.

TPU-native re-design of the reference's ``DeepSpeedEngine``
(``runtime/engine.py:85``).  The reference engine is a mutable
``nn.Module`` wrapper that intercepts autograd; here the hot path is a
**pure jitted train step** over an explicit ``TrainState`` pytree, and the
engine object is a thin stateful host shell (step counters, timers,
checkpoint I/O) — SURVEY.md §7 design stance.

API mapping (reference → here):

* ``engine(batch); engine.backward(loss); engine.step()`` →  the same
  three calls work (micro-batch at a time, grad accumulation in state),
  but ``forward`` runs the fused forward+backward (JAX cannot split
  autodiff across Python calls); ``backward`` folds the cached grads into
  the accumulator; ``step`` applies the update at the boundary.
* ``engine.train_batch(batch)`` — one full global batch (all
  micro-batches) in a single compiled step; preferred path.
* ZeRO stage selection (``_configure_zero_optimizer``,
  engine.py:888-982) → sharding-rule selection (zero/stages.py).
* fp16 loss scaling (``_configure_fp16_optimizer``) → LossScaleState in
  the TrainState; bf16 default needs none.
"""
from __future__ import annotations

import functools
import os
import time
from contextlib import nullcontext
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.analysis.shard import hooks as shard_hooks
from deepspeed_tpu.comm.mesh import MeshInfo
from deepspeed_tpu.config.config import DeepSpeedConfig
from deepspeed_tpu.sharding import (
    batch_pspec,
    build_mesh,
    derive_topology,
    dp_rows_spec,
    stacked_batch_pspec,
)
from deepspeed_tpu.sharding.rules import PartitionRules
from deepspeed_tpu.config import constants as C
from deepspeed_tpu.runtime.fp16.loss_scaler import LossScaler
from deepspeed_tpu.runtime.lr_schedules import get_lr_schedule
from deepspeed_tpu.runtime.zero.stages import ZeroShardingRules, opt_state_specs
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (
    BACKWARD_TIMER,
    FORWARD_TIMER,
    STEP_TIMER,
    TRAIN_BATCH_TIMER,
    SynchronizedWallClockTimer,
    ThroughputTimer,
)

MEMORY_OPT_ALLREDUCE_SIZE = 500_000_000


class _PlacedBatch:
    """Explicit marker for batches already stacked + device-placed by
    ``engine.prefetch_loader`` — lets ``train_batch`` skip re-placement
    without guessing from shapes."""

    __slots__ = ("tree",)

    def __init__(self, tree: Any):
        self.tree = tree


def _global_norm(tree: Any) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves)) if leaves else jnp.zeros((), jnp.float32)


def _clip_by_global_norm(tree: Any, max_norm: float) -> Tuple[Any, jnp.ndarray]:
    norm = _global_norm(tree)
    factor = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * factor).astype(g.dtype), tree), norm


class DeepSpeedEngine:
    def __init__(
        self,
        model: Callable,
        params: Any,
        config: DeepSpeedConfig,
        optimizer: Any = None,
        lr_scheduler: Any = None,
        mesh=None,
        tp_spec_fn=None,
        partition_rules=None,
        loss_fn: Optional[Callable] = None,
        rng: Optional[jax.Array] = None,
        dist_init_required: Optional[bool] = None,
    ):
        """``model``: callable ``(params, batch, rng) -> loss`` (or outputs
        if ``loss_fn`` given, then ``loss_fn(outputs, batch) -> loss``).
        ``params``: initial parameter pytree (host or device arrays).
        ``partition_rules``: how parameter layouts resolve — a
        :class:`~deepspeed_tpu.sharding.rules.PartitionRules`, a family
        name (``"gpt2"``/``"bert"``/``"neo"``/``"moe"``), or an ordered
        ``(regex, PartitionSpec)`` table; ``tp_spec_fn`` (legacy) wraps
        into the same engine.
        """
        self.config = config
        self._model_fn = model
        self._loss_fn = loss_fn
        if mesh is not None:
            self.mesh = mesh
            self.topology = derive_topology(mesh)
        else:
            self.mesh, self.topology = build_mesh(config.mesh)
        self.mesh_info = MeshInfo.from_mesh(self.mesh)
        # -- partition-rule engine (docs/sharding.md) ----------------------
        self.partition_rules = PartitionRules.coerce(partition_rules, tp_spec_fn)
        self.global_rank = jax.process_index()
        self.world_size = self.mesh_info.world_size

        # -- precision ----------------------------------------------------
        if config.fp16.enabled:
            self.compute_dtype = jnp.float16
        elif config.bf16.enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        self.loss_scaler = LossScaler.from_config(config.fp16)

        # -- sharding rules (ZeRO stage -> specs), resolved through the
        # partition-rule engine; data_size arms cross-replica
        # weight-update sharding (the default ZeRO-1, docs/sharding.md)
        self.zero_rules = ZeroShardingRules(
            config.zero_config,
            fsdp_size=self.mesh_info.fsdp_world_size,
            tp_spec_fn=self.partition_rules.tp_spec_fn(),
            data_size=self.mesh_info.sizes.get("data", 1),
        )

        # -- optimizer -----------------------------------------------------
        self.optimizer = optimizer if optimizer is not None else self._configure_basic_optimizer()
        self.lr_schedule = self._configure_lr_schedule(lr_scheduler)
        self.client_lr_scheduler = lr_scheduler

        # -- ZeRO-Offload / Infinity (host-resident optimizer) -------------
        # reference: cpu_offload grads→host + DeepSpeedCPUAdam
        # (stage2.py:898-1023, engine.py:776-780); NVMe moments via the
        # pipelined swapper.  Device keeps compute-dtype params only.
        self._offload_cfg = config.zero_config.offload_optimizer
        self._offload = bool(self._offload_cfg.enabled)
        self._host_opt = None
        if config.zero_config.offload_param.enabled and getattr(model, "stream_spec", None) is None:
            # The real param-offload path is the streaming
            # ZeroInfinityEngine (runtime/zero/param_offload.py), chosen
            # by initialize() when the model advertises a ``stream_spec``
            # and the combo is streamable.  Landing here without a spec
            # means params stay HBM-resident (sharded 1/fsdp per chip).
            logger.warning(
                "offload_param: model exposes no stream_spec, so params stay "
                "HBM-resident (sharded 1/fsdp); models.gpt2.make_model "
                "provides the >HBM layer-streaming path"
            )
        # Multi-host offload: fp32 masters + moments are sharded 1/P per
        # host as one flat slice (the reference's per-DP-rank partitioned
        # CPU buffers, stage2.py:898-1023); each host steps its slice and
        # the updated masters reassemble via a process all-gather.
        # DS_OFFLOAD_SHARDS=K simulates K hosts in one process (tests).
        env_shards = int(os.environ.get("DS_OFFLOAD_SHARDS", "1"))
        if jax.process_count() > 1:
            # real multi-host: one slice per process, always — a larger
            # env override would leave slices no process owns
            if env_shards > 1 and env_shards != jax.process_count():
                logger.warning(
                    f"DS_OFFLOAD_SHARDS={env_shards} ignored: with "
                    f"{jax.process_count()} processes each host owns exactly one slice"
                )
            self._offload_shards = jax.process_count()
        else:
            self._offload_shards = max(1, env_shards)
        if self._offload:
            if optimizer is not None:
                raise ValueError(
                    "offload_optimizer cannot be combined with a client optimizer "
                    "(the host step owns the update); drop optimizer= or the offload block"
                )
            if not getattr(self, "_use_grad_acc", True):
                raise NotImplementedError("offload_optimizer is not supported with the pipeline engine yet")

        # -- flat-fallback leaves (reference flattened partitions,
        # stage2.py:432 / partition_parameters.py:688): leaves with no
        # fsdp-divisible dim live in engine state as zero-padded 1-D
        # vectors sharded over fsdp; the model sees them re-materialized
        # inside the differentiated step, so their grads come back flat
        # (and reduce-scattered) automatically.  Disabled for the
        # pipeline engine, which owns its own parameter layout.
        self._flat_plan = (
            self.zero_rules.plan_flat(params) if getattr(self, "_use_grad_acc", True) else {}
        )
        if self._flat_plan:
            params = self._flatten_state_leaves(params)
            log_dist(
                f"zero: {len(self._flat_plan)} param(s) with no fsdp-divisible dim "
                f"stored flat-padded over fsdp={self.mesh_info.fsdp_world_size}"
            )

        # -- state ---------------------------------------------------------
        self._param_specs = self.zero_rules.tree_param_specs(params)
        self._grad_specs = self.zero_rules.tree_grad_specs(params)
        # update-phase layout: one params-shaped tree of opt-state specs;
        # constraining the averaged grads to it inside the update makes
        # GSPMD shard the whole optimizer computation across the dp grid
        # (cross-replica weight-update sharding, arXiv:2004.13336)
        self._update_specs = self.zero_rules.tree_opt_specs_like(params)
        if self._offload:
            self._host_opt = self._configure_host_offload_optimizer(params)
            params = self._shard_params(params, dtype=self.compute_dtype)
            opt_state = {}
            self._opt_specs = {}
        else:
            params = self._shard_params(params)
            opt_state = jax.eval_shape(self.optimizer.init, params)
            self._opt_specs = opt_state_specs(opt_state, params, self.zero_rules)
            opt_state = jax.jit(
                self.optimizer.init,
                out_shardings=jax.tree.map(self._sh, self._opt_specs, is_leaf=lambda x: isinstance(x, P)),
            )(params)

        if rng is None:
            rng = jax.random.PRNGKey(config.seed)
        # subclasses that never accumulate (pipeline) skip the fp32 buffer
        self._use_grad_acc = getattr(self, "_use_grad_acc", True)
        # gas==1: train_batch consumes grads inside the same compiled
        # program, so the persistent params-sized fp32 accumulator is dead
        # HBM (3.1GB at 774M — the margin between fitting selective-remat
        # activations on one chip or not).  Allocate it lazily, only if
        # the three-call micro API (forward/backward/step) is used.
        self._lazy_grad_acc = (
            self._use_grad_acc
            and config.gradient_accumulation_steps == 1
            and not self._offload
        )
        self.state: Dict[str, Any] = {
            "params": params,
            "opt_state": opt_state,
            "grad_acc": jax.jit(
                lambda p: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
                out_shardings=jax.tree.map(self._sh, self._grad_specs, is_leaf=lambda x: isinstance(x, P)),
            )(params)
            if self._use_grad_acc and not self._lazy_grad_acc
            else {},
            "micro_step": jnp.zeros((), jnp.int32),
            "global_step": jnp.zeros((), jnp.int32),
            "global_samples": jnp.zeros((), jnp.int32),
            "loss_scale": self.loss_scaler.init(),
            "rng": rng,
        }
        self._state_shardings = {
            "params": jax.tree.map(self._sh, self._param_specs, is_leaf=lambda x: isinstance(x, P)),
            "opt_state": jax.tree.map(self._sh, self._opt_specs, is_leaf=lambda x: isinstance(x, P)),
            "grad_acc": jax.tree.map(self._sh, self._grad_specs, is_leaf=lambda x: isinstance(x, P))
            if self._use_grad_acc and not self._lazy_grad_acc
            else {},
            "micro_step": self._sh(P()),
            "global_step": self._sh(P()),
            "global_samples": self._sh(P()),
            "loss_scale": jax.tree.map(lambda _: self._sh(P()), self.state["loss_scale"]),
            "rng": self._sh(P()),
        }
        # Place every state leaf with its NamedSharding now: leaves created
        # by plain jnp ops otherwise enter the first compiled call with a
        # default GSPMDSharding, which differs from the NamedSharding the
        # step's outputs carry — forcing a silent full recompile at step 2.
        self.state = jax.device_put(self.state, self._state_shardings)

        # -- activation checkpointing (reference _configure_checkpointing,
        # engine.py:523) — publish the config block to the module-level
        # checkpoint() API so user models pick it up
        from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as act_ckpt

        act_ckpt.configure(deepspeed_config=config)

        # -- MoQ quantize-training + progressive layer drop ----------------
        # (reference engine hooks: _take_model_step :1284-1290 for MoQ,
        # forward :1101 / step :1343 for PLD)
        self.quantizer = None
        if config.quantize_training.enabled:
            if self._offload:
                raise NotImplementedError("quantize_training (MoQ) is not supported with offload_optimizer")
            from deepspeed_tpu.runtime.quantize import Quantizer

            self.quantizer = Quantizer(config.quantize_training)
        self.progressive_layer_drop = None
        if config.progressive_layer_drop.enabled:
            if not getattr(self, "_use_grad_acc", True):
                raise NotImplementedError(
                    "progressive_layer_drop is not wired into the pipeline engine yet "
                    "(theta injection lives in the micro-step path)"
                )
            from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop

            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=config.progressive_layer_drop.theta, gamma=config.progressive_layer_drop.gamma
            )

        # -- 1-bit Adam/LAMB compressed-exchange phase ---------------------
        # After freeze_step the engine switches to a SECOND compiled
        # train step that keeps per-rank gradients UNREDUCED (vmap over
        # data-axis slices) and exchanges the momentum through the
        # error-feedback 1-bit collective (comm/collectives.py) — the
        # reference's comm-volume saving (onebit/adam.py:110-220 over
        # nccl.py:47-186; onebit/lamb.py for the large-batch rung),
        # realized as two executables because a single program would pay
        # for both exchange paths every step.
        from deepspeed_tpu.runtime.fp16.onebit.adam import OnebitAdam
        from deepspeed_tpu.runtime.fp16.onebit.lamb import OnebitLamb

        self._onebit_frozen = False
        # fsdp>1 composes via the two-level exchange (flat dim sharded over
        # fsdp, 1-bit over data within each group) and gradient clipping
        # runs on the per-rank local norms before the exchange — both
        # envelope restrictions of round 2 are gone (VERDICT r2 #6).
        onebit_blockers = {
            "data axis must be > 1": self.mesh_info.sizes.get("data", 1) > 1,
            "pipeline engine unsupported": self._use_grad_acc,
            "offload_optimizer unsupported": not self._offload,
            "quantize_training (MoQ) unsupported": self.quantizer is None,
            "progressive_layer_drop unsupported": self.progressive_layer_drop is None,
        }
        self._onebit_exchange_ok = isinstance(
            self.optimizer, (OnebitAdam, OnebitLamb)
        ) and all(onebit_blockers.values())
        if (
            self._onebit_exchange_ok
            and self.mesh_info.fsdp_world_size > 1
            and self.zero_stage >= 1
        ):
            # the frozen layout replicates int8 momentum signs (1 B) +
            # flat fp32 variance (4 B) + packed params (4 B) and keeps a
            # per-chip fp32 worker-error row (1/n of an (n, Mp) grid ≈
            # 4 B/param/chip) — ~13 bytes/param/chip STATIC, plus
            # step-transient decompressed fp32 momentum and grad rows.
            # Models that only fit BECAUSE of ZeRO sharding will OOM at
            # the freeze step, not at init.
            n_p = sum(int(np.prod(np.shape(p))) for p in jax.tree.leaves(params))
            logger.warning(
                "1-bit optimizer + ZeRO(fsdp>1): the compressed phase "
                "replicates the momentum signs (int8) + flat fp32 "
                "variance/params and keeps a per-chip fp32 worker-error row "
                f"(~{13 * n_p / 2**30:.1f}GiB static per chip, plus fp32 "
                "momentum/grad transients during the step) — ZeRO's state "
                "sharding does not apply after freeze_step; ensure HBM "
                "headroom or keep fsdp=1 "
                "(layout trade-off measured in tests/test_onebit.py::"
                "test_frozen_variance_layout_wire_bytes)"
            )
        if isinstance(self.optimizer, (OnebitAdam, OnebitLamb)) and not self._onebit_exchange_ok:
            failed = [k for k, ok in onebit_blockers.items() if not ok]
            logger.warning(
                f"1-bit {type(self.optimizer).__name__}: compressed gradient "
                "exchange DISABLED — the optimizer will fall back to local "
                "momentum quantization with full-precision allreduce "
                f"({'; '.join(failed)})"
            )

        # -- resilience (watchdog / divergence guard / checkpoint dirs) ----
        # (docs/resilience.md; engines built without a DeepSpeedConfig
        # resilience block fall back to the defaults)
        from deepspeed_tpu.config.config import ResilienceConfig
        from deepspeed_tpu.resilience import DivergenceGuard, PreemptionWatchdog

        self.resilience = getattr(config, "resilience", None) or ResilienceConfig()
        self._divergence_guard = (
            DivergenceGuard(
                threshold=self.resilience.divergence.threshold,
                action=self.resilience.divergence.action,
            )
            if self.resilience.divergence.enabled
            else None
        )
        # the directory emergency saves / auto-rollback target: explicit
        # watchdog.save_dir, else wherever the run last saved/loaded
        self._resilience_ckpt_dir: Optional[str] = self.resilience.watchdog.save_dir
        self._watchdog = None
        if self.resilience.watchdog.enabled:
            self._watchdog = PreemptionWatchdog(
                grace_seconds=self.resilience.watchdog.grace_seconds,
                exit_code=self.resilience.watchdog.exit_code,
            ).install()

        # -- distributed supervision (heartbeat plane + hung-collective
        # watchdog + exit-44 rescue; docs/resilience.md).  Launcher-
        # spawned children also pick up their DS_FAULT_PLAN here, so
        # kill/stall sites fire inside real multi-process tests.
        from deepspeed_tpu.resilience import faults as _faults

        _faults.install_from_env()
        self._supervision = None
        self._train_loader = None  # registered resumable dataloader
        if self.resilience.supervision.enabled:
            self._supervision = self._build_supervisor(self.resilience.supervision)

        # -- overlap: input prefetch / async checkpointing / step timeline
        # (docs/performance.md; runtime/overlap/)
        from deepspeed_tpu.config.config import OverlapConfig
        from deepspeed_tpu.runtime.overlap import AsyncCheckpointWriter, StepTimeline

        self.overlap = getattr(config, "overlap", None) or OverlapConfig()
        self.timeline = StepTimeline(
            enabled=self.overlap.timeline.enabled, window=self.overlap.timeline.window
        )
        # per-step compute fencing costs a host<->device round trip per
        # step (the sync ThroughputTimer deliberately avoids off report
        # steps); default follows the wall_clock_breakdown opt-in, whose
        # per-step timers already sync
        fence = self.overlap.timeline.fence
        self._timeline_fence = config.wall_clock_breakdown if fence is None else bool(fence)
        self._async_writer = (
            AsyncCheckpointWriter(
                drain_timeout_seconds=self.overlap.async_checkpoint.drain_timeout_seconds
            )
            if self.overlap.async_checkpoint.enabled
            else None
        )
        # executables built so far — the compile-stability regression
        # tests pin this to 1 over a steady-state training loop (any
        # shape/static-arg drift shows up as a recount)
        self.compilation_count = 0

        # -- telemetry plane (docs/telemetry.md) ---------------------------
        # Arm the process-wide registry/tracer BEFORE the comm layer so
        # its trace-time strategy decisions land in the registry; the
        # TensorBoard monitor is created here (it is a telemetry sink —
        # the engine's loss/lr/loss-scale events route through the
        # manager, never via direct add_scalar: ds_lint raw-metric-emit)
        from deepspeed_tpu import telemetry as _telemetry
        from deepspeed_tpu.utils.monitor import TensorBoardMonitor

        self.monitor = TensorBoardMonitor(
            output_path=config.tensorboard.output_path,
            job_name=config.tensorboard.job_name,
            enabled=config.tensorboard.enabled,
            rank=self.global_rank,
        )
        self.telemetry = _telemetry.configure(
            getattr(config, "telemetry", None),
            rank=self.global_rank, label="train", monitor=self.monitor,
        )
        if self.telemetry.collect or self.telemetry.tracer.enabled:
            self.timeline.attach_telemetry(self.telemetry, prefix="train")

        # -- Pallas kernel suite (docs/kernels.md) -------------------------
        # Process-wide arming from the `kernels` block; resolved by the
        # ops-level dispatches at trace time (fused update, flash decode)
        from deepspeed_tpu.ops import kernels as _kernels_mod

        _kernels_mod.configure_from_config(getattr(config, "kernels", None))

        # -- unified comm layer (docs/comm.md) -----------------------------
        # Strategy-selected collectives: the gradient exchange routes
        # through self.comm, which picks dense / int8-quantized (EQuARX)
        # / error-feedback-compressed per (size, dtype, topology) at
        # TRACE time — no recompile per strategy, one executable each.
        self._init_comm_layer(config)

        # -- ds_san runtime sanitizer (opt-in: `sanitizer` config block
        # or DS_SAN=1; docs/ds_san.md).  None in production — every hook
        # below is a near-free attribute check.
        from deepspeed_tpu.analysis.sanitizer import maybe_from_config

        self._sanitizer = maybe_from_config(getattr(config, "sanitizer", None))
        self._san_last_batch = None  # last stacked batch, for the NaN probe

        # -- host-side bookkeeping ----------------------------------------
        from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler

        self._last_loss = None
        self._last_info = None
        self.flops_profiler = FlopsProfiler(config.flops_profiler, engine=self)
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size, steps_per_output=config.steps_per_print
        )
        self.wall_clock_breakdown = config.wall_clock_breakdown
        self._cached_loss = None
        self._compiled = {}
        self._train_executable = None
        self._train_step_cost: Dict[str, float] = {}
        # elements the fused update gave the Pallas kernels and the XLA
        # leaf path in the step traced last (empty: the update is XLA's);
        # engine_update fills it while the step is traced
        self._fused_update_split: Dict[str, int] = {}
        self.skipped_steps = 0
        # Host-side mirror of state["global_step"].  Reading the device
        # scalar blocks the host until every step dispatched so far has
        # finished, so the hot path must never sync on it; the mirror
        # advances with every non-skipped step and is reconciled from
        # the device value at checkpoint load.
        self._host_global_step = 0
        self._host_micro_step = 0

        log_dist(
            f"engine: zero_stage={self.zero_stage} dtype={self.compute_dtype.__name__} "
            f"micro_bs={config.train_micro_batch_size_per_gpu} gas={config.gradient_accumulation_steps} "
            f"dp={self.mesh_info.dp_world_size} (data={self.mesh_info.sizes.get('data',1)} × "
            f"fsdp={self.mesh_info.fsdp_world_size}) tp={self.mesh_info.model_parallel_world_size} "
            f"pp={self.mesh_info.pipe_parallel_world_size}"
        )

    # ------------------------------------------------------------------
    # configuration helpers
    # ------------------------------------------------------------------
    def _sh(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec if spec is not None else P())

    def _configure_basic_optimizer(self):
        """Reference ``_configure_basic_optimizer`` (engine.py:752-809)."""
        from deepspeed_tpu.ops.adam.fused_adam import SGD, FusedAdam, FusedAdamW
        from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb

        name = self.config.optimizer.name or C.ADAM_OPTIMIZER
        params = dict(self.config.optimizer.params)
        params.pop("torch_adam", None)
        lr = params.pop("lr", 1e-3)
        if name == C.ADAM_OPTIMIZER:
            adam_w_mode = params.pop("adam_w_mode", True)
            return FusedAdam(lr=lr, adam_w_mode=adam_w_mode, **params)
        if name == C.ADAMW_OPTIMIZER:
            return FusedAdamW(lr=lr, **params)
        if name == C.LAMB_OPTIMIZER:
            return FusedLamb(lr=lr, **params)
        if name == C.ONEBIT_ADAM_OPTIMIZER:
            from deepspeed_tpu.runtime.fp16.onebit.adam import OnebitAdam

            return OnebitAdam(lr=lr, fsdp_size=self.mesh_info.fsdp_world_size, **params)
        if name == C.ONEBIT_LAMB_OPTIMIZER:
            from deepspeed_tpu.runtime.fp16.onebit.lamb import OnebitLamb

            return OnebitLamb(lr=lr, **params)
        if name == C.SGD_OPTIMIZER:
            return SGD(lr=lr, **params)
        raise ValueError(f"Unknown optimizer '{name}'")

    def _configure_lr_schedule(self, client_scheduler):
        if callable(client_scheduler):
            return client_scheduler
        if self.config.scheduler.type:
            return get_lr_schedule(self.config.scheduler.type, self.config.scheduler.params)
        base_lr = getattr(self.optimizer, "lr", 1e-3)
        return lambda step: jnp.asarray(base_lr, jnp.float32)

    def _shard_params(self, params: Any, dtype=jnp.float32) -> Any:
        shardings = jax.tree.map(self._sh, self._param_specs, is_leaf=lambda x: isinstance(x, P))

        def host_cast(p):
            # cast host-side (ml_dtypes handles bf16) so device transfer
            # moves target-dtype bytes — no full-precision staging in HBM
            return np.asarray(p).astype(dtype) if not isinstance(p, jax.Array) else jnp.asarray(p, dtype)

        return jax.device_put(jax.tree.map(host_cast, params), shardings)

    def _configure_host_offload_optimizer(self, params):
        """Build the host optimizer (reference _configure_basic_optimizer's
        DeepSpeedCPUAdam branch, engine.py:776-780).  With P > 1 offload
        shards, fp32 masters + moments live as one flat 1/P slice per
        host (reference per-DP-rank partitioned pinned buffers,
        stage2.py:898-1023); each host steps only its slice."""
        from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer

        name = self.config.optimizer.name or C.ADAM_OPTIMIZER
        if name not in (C.ADAM_OPTIMIZER, C.ADAMW_OPTIMIZER):
            raise ValueError(f"offload_optimizer supports Adam/AdamW, got '{name}'")
        p = dict(self.config.optimizer.params)
        nvme_dir = None
        if self._offload_cfg.device == "nvme":
            if not self._offload_cfg.nvme_path:
                raise ValueError("offload_optimizer.device=nvme requires nvme_path")
            nvme_dir = os.path.join(self._offload_cfg.nvme_path, "zero_infinity_swap")
        kw = dict(
            lr=p.get("lr", 1e-3),
            betas=tuple(p.get("betas", (0.9, 0.999))),
            eps=p.get("eps", 1e-8),
            weight_decay=p.get("weight_decay", 0.0),
            adamw_mode=(name == C.ADAMW_OPTIMIZER) or bool(p.get("adam_w_mode", True)),
            aio_config=self.config.aio,
            pipeline=self._offload_cfg.pipeline_read or self._offload_cfg.pipeline_write,
        )
        if self._offload_shards <= 1:
            return HostOffloadOptimizer(
                jax.tree.map(np.asarray, params), nvme_swap_dir=nvme_dir, **kw
            )
        from deepspeed_tpu.runtime.fp16.onebit.adam import pack_flat

        P_shards = self._offload_shards
        flat = np.asarray(pack_flat(jax.tree.map(np.asarray, params), P_shards))
        L = flat.shape[0] // P_shards
        self._offload_slice_len = L

        def mk(i):
            nv = None if nvme_dir is None else os.path.join(nvme_dir, f"shard{i}")
            if nv is not None:
                os.makedirs(nv, exist_ok=True)
            return HostOffloadOptimizer({"flat": flat[i * L : (i + 1) * L].copy()}, nvme_swap_dir=nv, **kw)

        if jax.process_count() > 1:
            # one slice per host; reassembly goes through process_allgather
            self._host_shard_ids = [jax.process_index()]
        else:
            # simulated multi-host (DS_OFFLOAD_SHARDS): this process owns
            # every slice and steps them in turn — exercises the exact
            # slice/step/assemble math single-process
            self._host_shard_ids = list(range(P_shards))
        self._host_opts = [mk(i) for i in self._host_shard_ids]
        log_dist(
            f"ZeRO-Offload: masters sharded 1/{P_shards} per host "
            f"({L * 4 / 1e9:.2f} GB master slice/host)"
        )
        return self._host_opts[0]

    # ------------------------------------------------------------------
    # properties (reference engine exposes config as methods, :227-506)
    # ------------------------------------------------------------------
    @property
    def zero_stage(self) -> int:
        return self.config.zero_config.stage
    zero_optimization_stage = zero_stage

    @property
    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    @property
    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    @property
    def global_steps(self) -> int:
        return self._host_global_step

    @property
    def micro_steps(self) -> int:
        return self._host_micro_step

    @property
    def loss_scale(self) -> float:
        # explicit d2h read (sanitizer transfer-guard clean)
        return float(jax.device_get(self.state["loss_scale"].scale))

    @property
    def module(self):
        return self._model_fn

    def get_lr(self):
        return [float(self.lr_schedule(self._host_global_step))]

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._host_micro_step % self.gradient_accumulation_steps == 0

    # ------------------------------------------------------------------
    # flat-fallback leaf layout (see __init__)
    # ------------------------------------------------------------------
    def _flatten_state_leaves(self, tree: Any) -> Any:
        """Natural layout → state layout (flat-pad leaves in the plan)."""
        from deepspeed_tpu.runtime.zero.stages import _path_str

        def f(path, leaf):
            info = self._flat_plan.get(_path_str(path))
            if info is None:
                return leaf
            _, n, padded = info
            flat = jnp.ravel(jnp.asarray(leaf))
            return jnp.pad(flat, (0, padded - n))

        return jax.tree_util.tree_map_with_path(f, tree)

    def _unflatten_state_leaves(self, tree: Any) -> Any:
        """State layout → natural layout (no dtype change)."""
        from deepspeed_tpu.runtime.zero.stages import _path_str

        def f(path, leaf):
            info = self._flat_plan.get(_path_str(path))
            if info is None:
                return leaf
            shape, n, _ = info
            return leaf[:n].reshape(shape)

        return jax.tree_util.tree_map_with_path(f, tree)

    def _materialize_params(self, params: Any, dtype) -> Any:
        """State-layout params → full-shape compute-dtype params (traced
        inside the step; for flat leaves the replicate-constraint turns
        the fsdp shards into an all-gather at first use)."""
        from deepspeed_tpu.runtime.zero.stages import _path_str

        def f(path, leaf):
            info = self._flat_plan.get(_path_str(path)) if self._flat_plan else None
            x = leaf
            if info is not None:
                shape, n, _ = info
                x = jax.lax.with_sharding_constraint(x, self._sh(P()))
                x = x[:n].reshape(shape)
            return x.astype(dtype)

        return jax.tree_util.tree_map_with_path(f, params)

    def _map_param_shaped_subtrees(self, tree: Any, ref: Any, fn) -> Any:
        """Convert optimizer-state m/v mirrors between layouts (shared
        traversal lives in zero/stages.py)."""
        from deepspeed_tpu.runtime.zero.stages import map_param_shaped_subtrees

        return map_param_shaped_subtrees(tree, ref, fn)

    # -- portable (natural-layout) checkpoint conversion ----------------
    # Flat-padded leaf sizes depend on fsdp_size, so checkpoints store
    # the natural layout: a job restoring at a different fsdp degree
    # re-pads for its own mesh (the elastic-resize story stays intact).
    def _to_portable_state(self, state: Any) -> Any:
        if not self._flat_plan:
            return state
        ref = state["params"]  # state layout — the shape reference for m/v mirrors
        out = dict(state)
        out["params"] = self._unflatten_state_leaves(state["params"])
        if self._use_grad_acc and out.get("grad_acc"):
            out["grad_acc"] = self._unflatten_state_leaves(state["grad_acc"])
        if out.get("opt_state"):
            out["opt_state"] = self._map_param_shaped_subtrees(
                state["opt_state"], ref, self._unflatten_state_leaves
            )
        return out

    def _from_portable_state(self, portable: Any) -> Any:
        if not self._flat_plan:
            return portable
        out = dict(portable)
        if out.get("opt_state"):
            out["opt_state"] = self._map_param_shaped_subtrees(
                portable["opt_state"], portable["params"], self._flatten_state_leaves
            )
        out["params"] = self._flatten_state_leaves(portable["params"])
        if self._use_grad_acc and out.get("grad_acc"):
            out["grad_acc"] = self._flatten_state_leaves(portable["grad_acc"])
        return out

    def _portable_target(self) -> Any:
        """Abstract (ShapeDtypeStruct) tree describing the on-disk
        checkpoint layout, with shardings for orbax resharding-on-read."""
        abstract = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=s),
            self.state,
            self._state_shardings,
        )
        if not self._flat_plan:
            return abstract
        from deepspeed_tpu.runtime.zero.stages import _path_str

        repl = self._sh(P())

        def unflat_abs(tree):
            def f(path, leaf):
                info = self._flat_plan.get(_path_str(path))
                if info is None:
                    return leaf
                shape, _, _ = info
                return jax.ShapeDtypeStruct(shape, leaf.dtype, sharding=repl)

            return jax.tree_util.tree_map_with_path(f, tree)

        out = dict(abstract)
        out["params"] = unflat_abs(abstract["params"])
        if self._use_grad_acc and out.get("grad_acc"):
            out["grad_acc"] = unflat_abs(abstract["grad_acc"])
        if out.get("opt_state"):
            out["opt_state"] = self._map_param_shaped_subtrees(
                out["opt_state"], abstract["params"], unflat_abs
            )
        return out

    # ------------------------------------------------------------------
    # core compiled steps
    # ------------------------------------------------------------------
    def _compute_loss(self, params, batch, rng, ls_state):
        cparams = self._materialize_params(params, self.compute_dtype)
        out = self._model_fn(cparams, batch, rng)
        loss = self._loss_fn(out, batch) if self._loss_fn is not None else out
        loss = jnp.asarray(loss)
        if loss.ndim != 0:
            loss = jnp.mean(loss)
        return self.loss_scaler.scale_loss(loss.astype(jnp.float32), ls_state), loss

    def _micro_grads(self, state, batch):
        """Shared micro-batch body: fused forward+backward, returns the raw
        (still loss-scaled) grads without touching the accumulator."""
        if self.progressive_layer_drop is not None and isinstance(batch, dict):
            from deepspeed_tpu.runtime.progressive_layer_drop import PLD_THETA_KEY

            batch = dict(batch)
            batch[PLD_THETA_KEY] = self.progressive_layer_drop.get_theta(state["global_step"])
        rng = jax.random.fold_in(state["rng"], state["micro_step"])
        (scaled_loss, loss), grads = jax.value_and_grad(
            lambda p: self._compute_loss(p, batch, rng, state["loss_scale"]), has_aux=True
        )(state["params"])
        # dense grad-exchange site: the comm layer's sharding constraint
        # is what GSPMD lowers to the grad psum / psum_scatter
        grads = self.comm.constrain_grads(
            grads, jax.tree.map(self._sh, self._grad_specs, is_leaf=lambda x: isinstance(x, P))
        )
        state = dict(state)
        state["micro_step"] = state["micro_step"] + 1
        state["global_samples"] = state["global_samples"] + self.train_micro_batch_size_per_gpu * self.mesh_info.dp_world_size
        return state, loss, grads

    def _micro_step_impl(self, state, batch):
        """One micro-batch: fused forward+backward, accumulate grads."""
        state, loss, grads = self._micro_grads(state, batch)
        state["grad_acc"] = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), state["grad_acc"], grads
        )
        return state, loss

    def _apply_step_impl(self, state):
        """Optimizer step at the grad-accumulation boundary (reference
        ``_take_model_step``, engine.py:1269)."""
        gas = self.gradient_accumulation_steps
        grads = jax.tree.map(lambda g: g / gas, state["grad_acc"])
        state, info = self._apply_update(state, grads)
        state["grad_acc"] = jax.tree.map(jnp.zeros_like, state["grad_acc"])
        return state, info

    def _note_update_split(self, span) -> None:
        """Inside a ``ds.train.compile`` span, after the step has been
        traced: how many of the optimizer's elements take the one-pass
        kernels (docs/kernels.md), as the span's arguments and one line
        of the log."""
        split = self._fused_update_split
        if not split:
            return
        span.set_metadata(**{f"fused_update_{k}": v for k, v in split.items()})
        log_dist(
            f"kernels: fused_update takes {split['pallas_elems']:,} elements in one "
            f"pass a leaf, {split['xla_elems']:,} stay on the XLA leaf path"
        )

    def _apply_update(self, state, grads):
        """Unscale/clip/update given already-averaged grads (shared by the
        grad-accumulation path and the pipeline engine's fused batch)."""
        grads, overflow = self.loss_scaler.unscale_and_check(grads, state["loss_scale"])
        return self._apply_update_unscaled(state, grads, overflow)

    def _apply_update_unscaled(self, state, grads, overflow):
        """Clip + optimizer update for ALREADY-unscaled averaged grads
        with the overflow decision made by the caller (the explicit
        comm-exchange path checks finiteness on the pre-quantization
        rows, where an inf is still visible)."""
        if self.zero_rules.cross_replica_active:
            # cross-replica weight-update sharding: pin the averaged
            # grads to the optimizer-state layout so the partitioner
            # computes each replica's 1/dp slice of the update (a local
            # slice of the reduced grads — no extra comm on entry; the
            # updated params all-gather once at the out_shardings pin)
            grads = jax.lax.with_sharding_constraint(
                grads,
                jax.tree.map(self._sh, self._update_specs, is_leaf=lambda x: isinstance(x, P)),
            )
        grad_norm = jnp.zeros((), jnp.float32)
        if self.config.gradient_clipping > 0.0:
            grads, grad_norm = _clip_by_global_norm(grads, self.config.gradient_clipping)
        lr = jnp.asarray(self.lr_schedule(state["global_step"]), jnp.float32)
        upd_kw = {}
        if getattr(self.optimizer, "state_precision", "fp32") in ("8bit", "bf16"):
            # stochastic rounding of the 8-bit Adam state needs fresh
            # bits each step — without them v falls back to nearest
            # rounding and sub-LSB EMA increments are systematically lost
            upd_kw["rng"] = jax.random.fold_in(state["rng"], state["global_step"] + 997_001)
        # fused-update kernel seam (ops/kernels, docs/kernels.md): when
        # armed and the optimizer/state is kernel-eligible, ONE Pallas
        # kernel per leaf does the master-weight read + moment update +
        # param-dtype cast in a single HBM pass, with the overflow skip
        # folded in-producer.  Trace-time static decision; the XLA path
        # below stays the fallback and the numerics ground truth.
        fused = None
        from deepspeed_tpu.ops import kernels as _kernels

        if _kernels.fused_update_armed():
            if _kernels.on_tpu_backend() and self.mesh.devices.size > 1:
                # compiled Mosaic custom calls are opaque to the GSPMD
                # partitioner: on a multi-device mesh the sharded update
                # (cross-replica ZeRO-1, fsdp state) would lose its
                # per-replica-slice contract.  Multi-chip fused updates
                # need the shard_map integration (future arc); keep the
                # partitionable XLA path.  (Off-TPU interpret mode
                # lowers to plain jax ops, which partition fine — the
                # 8-device CPU dryrun tests run the seam.)
                _kernels.warn_once(
                    f"fused-update-multichip-{id(self)}",
                    "kernels: fused_update armed but the mesh spans "
                    f"{self.mesh.devices.size} devices — keeping the "
                    "partitionable XLA update (docs/kernels.md)",
                )
            else:
                from deepspeed_tpu.ops.kernels.fused_update import engine_update

                # split: counted in trace-time Python, read by
                # _note_update_split once the step being traced has compiled
                fused = engine_update(
                    self.optimizer, grads, state["opt_state"], state["params"], lr, overflow,
                    split=self._fused_update_split,
                )
        if fused is not None:
            new_params, new_opt = fused
        else:
            in_producer_skip = getattr(self.optimizer, "supports_skip", False)
            if in_producer_skip:
                # overflow handling happens INSIDE the optimizer's producer
                # pass: updates come out zero and the state keeps its old
                # values.  The alternative — where(overflow, old, new) over
                # the state tree below — re-reads old AND new (state-sized
                # extra HBM traffic; ~26 ms/step at 774M, because the donated
                # output buffer forces `new` to materialize before the select)
                upd_kw["skip"] = overflow
            updates, new_opt = self.optimizer.update(
                grads, state["opt_state"], state["params"], lr=lr, **upd_kw
            )

            if in_producer_skip:
                new_params = jax.tree.map(
                    lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
                    state["params"], updates,
                )
            else:
                def apply_or_skip(p, u):
                    return jnp.where(overflow, p, (p.astype(jnp.float32) + u).astype(p.dtype))

                new_params = jax.tree.map(apply_or_skip, state["params"], updates)
                # on overflow, keep the old optimizer state too
                new_opt = jax.tree.map(
                    lambda old, new: jnp.where(overflow, old, new) if hasattr(old, "shape") else new,
                    state["opt_state"],
                    new_opt,
                )
        if self.quantizer is not None:
            # MoQ: fake-quantize weights right after the update
            # (reference _take_model_step :1284-1290); an overflow step is
            # a no-op, so keep the un-quantized (== previous) params then
            qrng = jax.random.fold_in(state["rng"], state["global_step"] + 1_000_003)
            quantized = self.quantizer.quantize_params(new_params, state["global_step"], rng=qrng)
            new_params = jax.tree.map(lambda p, q: jnp.where(overflow, p, q), new_params, quantized)
        state = dict(state)
        state["params"] = new_params
        state["opt_state"] = new_opt
        state["global_step"] = state["global_step"] + jnp.where(overflow, 0, 1)
        state["loss_scale"] = self.loss_scaler.update(state["loss_scale"], overflow)
        return state, {"lr": lr, "grad_norm": grad_norm, "overflow": overflow}

    def _scoped(self, fn):
        """This engine's mesh becomes ambient for the trace (see
        parallel.sequence.scoped_to)."""
        from deepspeed_tpu.parallel.sequence import scoped_to

        return scoped_to(self.mesh, fn)

    def _get_compiled(self, name: str, fn, donate: bool = True, out_shardings=None):
        if name not in self._compiled:
            self._compiled[name] = jax.jit(
                self._scoped(fn),
                donate_argnums=(0,) if donate else (),
                out_shardings=out_shardings,
            )
            self.compilation_count += 1
            if self._sanitizer is not None:
                self._sanitizer.recompile.note(f"engine.{name}", None, owner=id(self))
        return self._compiled[name]

    # ------------------------------------------------------------------
    # ZeRO-Offload step executor (host path)
    # ------------------------------------------------------------------
    def _host_apply_step(self) -> Dict[str, Any]:
        """Optimizer step on host: averaged grads device→host, native CPU
        Adam over fp32 masters (NVMe-pipelined moments when configured),
        bf16 masters host→device.  Replaces the jitted ``_apply_step_impl``
        when ``offload_optimizer`` is enabled."""
        from deepspeed_tpu.runtime.zero.offload import host_unscale_clip_and_check

        gas = self.gradient_accumulation_steps

        if "fetch_grads" not in self._compiled:

            def fetch(state):
                grads = jax.tree.map(lambda g: g / gas, state["grad_acc"])
                state = dict(state)
                state["grad_acc"] = jax.tree.map(jnp.zeros_like, state["grad_acc"])
                return state, grads

            # _scoped: the grad fetch runs under the engine mesh like every
            # other executable (and ds_lint's bare-jit rule stays clean)
            self._compiled["fetch_grads"] = jax.jit(self._scoped(fetch), donate_argnums=(0,))
            # ds_shard Pass 1/2 feed (no-op unless the audit armed it)
            if shard_hooks.armed():
                budget, decisions = shard_hooks.train_budget(self)
                shard_hooks.note_jit(
                    self, "train.offload_drain", self._compiled["fetch_grads"],
                    (self.state,),
                    leaves=shard_hooks.live_param_leaves(self.state["params"]),
                    budget=budget, decisions=decisions,
                )
        self.state, grads = self._compiled["fetch_grads"](self.state)
        # copy=True: device_get may hand back read-only buffers and the
        # host path unscales/clips in place
        g_np = jax.tree.map(lambda g: np.array(jax.device_get(g), np.float32, copy=True), grads)

        scale = float(self.state["loss_scale"].scale)
        leaves = jax.tree.leaves(g_np)
        # every host holds the full (replicated) grads, so the norm/
        # overflow decision is computed identically everywhere — no
        # cross-host exchange needed even in sharded mode
        _, grad_norm, overflow = host_unscale_clip_and_check(
            leaves, scale, self.config.gradient_clipping
        )
        lr = float(self.lr_schedule(self._host_global_step))
        if not (overflow and self.loss_scaler.dynamic):
            step_count = self._host_global_step + 1
            dtype = self.compute_dtype
            if self._offload_shards > 1:
                masters = self._sharded_host_step(g_np, leaves, lr, step_count)
            else:
                masters = self._host_opt.step(
                    jax.tree.unflatten(jax.tree.structure(g_np), leaves), lr, step_count
                )
            self.state["params"] = jax.device_put(
                jax.tree.map(lambda m: np.asarray(m, dtype), masters),
                self._state_shardings["params"],
            )
            self.state["global_step"] = self.state["global_step"] + 1
            self._host_global_step += 1
        self.state["loss_scale"] = self.loss_scaler.update(
            self.state["loss_scale"], jnp.asarray(overflow)
        )
        return {
            "lr": jnp.asarray(lr),
            "grad_norm": jnp.asarray(grad_norm, jnp.float32),
            "overflow": jnp.asarray(overflow),
        }

    # ------------------------------------------------------------------
    # 1-bit Adam frozen phase
    # ------------------------------------------------------------------
    def _sync_onebit_phase(self, global_step: int) -> None:
        """Align the compressed-exchange phase with a tag's step count
        (called before checkpoint restore so state layouts match).  A
        tag at exactly freeze_step is still warm-layout — the phase
        flips lazily at the start of the NEXT train_batch — and loading
        a pre-freeze tag into a frozen engine rolls the layout back."""
        if not self._onebit_exchange_ok:
            return
        if not self._onebit_frozen and global_step > self.optimizer.freeze_step:
            self._enter_onebit_frozen()
        elif self._onebit_frozen and global_step <= self.optimizer.freeze_step:
            self._exit_onebit_frozen()

    def _dp_exchange_axes(self):
        """The explicit (1-bit frozen / quantized-grad) exchange runs
        flat across the WHOLE dp grid — (data × fsdp) when ZeRO shards
        state, so the compressed wire saving covers every data-parallel
        rank (the reference never composes 1-bit with ZeRO; here the
        ring is just wider)."""
        if "fsdp" in self.mesh.axis_names and self.mesh_info.fsdp_world_size > 1:
            return ("data", "fsdp")
        return "data"

    _onebit_exchange_axes = _dp_exchange_axes  # historical name

    def _enter_onebit_frozen(self) -> None:
        n = self.mesh_info.dp_world_size  # exchange rows = full dp grid
        row_spec = dp_rows_spec(self._dp_exchange_axes())
        # NOTE: the frozen layout replicates the momentum (in its int8
        # compressed exchange form — 1 byte/param) and the fp32 variance
        # (the exchange needs the full momentum on every rank to
        # compress it) — ZeRO-1's moment sharding is traded for the
        # 1-bit wire in this phase
        specs = self.optimizer.frozen_specs(row_spec)
        sh = jax.tree.map(self._sh, specs, is_leaf=lambda x: isinstance(x, P))
        self.state["opt_state"] = jax.jit(
            lambda s: self.optimizer.make_frozen_state(s, n), out_shardings=sh
        )(self.state["opt_state"])
        self._state_shardings["opt_state"] = sh
        self._opt_specs = specs
        # the frozen path accumulates into its own (n, Mp) rows buffer —
        # free the params-sized fp32 accumulator
        self.state["grad_acc"] = {}
        self._state_shardings["grad_acc"] = {}
        self._purge_train_executables()
        self._onebit_frozen = True
        self.comm.note(
            "momentum-exchange", "onebit",
            f"1-bit {type(self.optimizer).__name__} compressed-exchange phase",
        )
        log_dist(
            f"1-bit {type(self.optimizer).__name__}: entering compressed-exchange "
            f"phase at step {self._host_global_step} "
            f"(freeze_step={self.optimizer.freeze_step}, dp_ranks={n})"
        )

    def _exit_onebit_frozen(self) -> None:
        """Frozen → warmup layout (pre-freeze checkpoint rollback): the
        values are about to be overwritten by the restore, so fresh
        zero-initialized warm state with the right shapes suffices."""
        params = self.state["params"]
        opt_state = jax.eval_shape(self.optimizer.init, params)
        self._opt_specs = opt_state_specs(opt_state, params, self.zero_rules)
        opt_sh = jax.tree.map(self._sh, self._opt_specs, is_leaf=lambda x: isinstance(x, P))
        self.state["opt_state"] = jax.jit(self.optimizer.init, out_shardings=opt_sh)(params)
        self._state_shardings["opt_state"] = opt_sh
        if not self._lazy_grad_acc:
            grad_sh = jax.tree.map(self._sh, self._grad_specs, is_leaf=lambda x: isinstance(x, P))
            self.state["grad_acc"] = jax.jit(
                lambda p: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
                out_shardings=grad_sh,
            )(params)
            self._state_shardings["grad_acc"] = grad_sh
        self._purge_train_executables()
        self._onebit_frozen = False
        log_dist(
            f"1-bit {type(self.optimizer).__name__}: rolled back to warmup "
            "(pre-freeze) state layout"
        )

    def _purge_train_executables(self) -> None:
        """Drop compiled steps that close over opt-state layout or
        loss-scaler constants (1-bit phase transitions, divergence-guard
        loss-scale-floor changes)."""
        self._compiled = {
            k: v
            for k, v in self._compiled.items()
            if not (isinstance(k, tuple) and k[0] in ("train_batch", "train_batches"))
            and k not in ("micro_step", "apply_step")
        }

    def _frozen_full_step(self, state, stacked):
        """Compiled train step for the compressed phase: per-rank grads
        stay unreduced; only 1-bit momentum crosses the wire."""
        from deepspeed_tpu.runtime.fp16.onebit.adam import pack_flat, pack_rows, unpack_flat

        n = self.mesh_info.dp_world_size  # exchange rows = full dp grid
        axes = self._onebit_exchange_axes()
        gas = self.gradient_accumulation_steps
        mp = state["opt_state"].m_signs.shape[0]
        row_sh = self._sh(dp_rows_spec(axes))
        acc0 = jax.lax.with_sharding_constraint(jnp.zeros((n, mp), jnp.float32), row_sh)

        def body(carry, mb):
            st, acc = carry
            rng = jax.random.fold_in(st["rng"], st["micro_step"])

            def rows_of(x):
                return x.reshape((n, x.shape[0] // n) + x.shape[1:])

            b_rows = jax.tree.map(rows_of, mb)

            def slice_loss(p, b, r):
                return self._compute_loss(p, b, r, st["loss_scale"])

            # independent rng per DP slice — dropout noise must not
            # repeat across the n slices of the global batch
            (_, loss), g = jax.vmap(
                jax.value_and_grad(slice_loss, has_aux=True), in_axes=(None, 0, 0)
            )(st["params"], b_rows, jax.random.split(rng, n))
            g_rows = jax.lax.with_sharding_constraint(pack_rows(g, n, n), row_sh)
            st = dict(st)
            st["micro_step"] = st["micro_step"] + 1
            st["global_samples"] = (
                st["global_samples"]
                + self.train_micro_batch_size_per_gpu * self.mesh_info.dp_world_size
            )
            return (st, acc + g_rows), jnp.mean(loss)

        (state, acc), losses = jax.lax.scan(body, (state, acc0), stacked)
        scale = self.loss_scaler.scale_loss(jnp.float32(1.0), state["loss_scale"])
        g_rows = acc / (gas * scale)
        overflow = ~jnp.isfinite(jnp.sum(g_rows))
        # Per-rank local-gradient norms — the reference's clipping
        # semantics under 1-bit (unfused_optimizer.py:187-226 computes
        # get_grad_norm over the rank's own grads before they fold into
        # the momentum; no full-precision cross-rank reduction, so the
        # wire stays 1-bit).  The scalar row norms do cross ranks (bytes
        # ≈ 4n, noise next to the exchange itself).
        row_norms = jnp.sqrt(jnp.sum(g_rows * g_rows, axis=1))  # (n,)
        grad_norm = jnp.sqrt(jnp.mean(row_norms * row_norms))
        if self.config.gradient_clipping > 0.0:
            clip = jnp.minimum(
                1.0, self.config.gradient_clipping / (row_norms + 1e-6)
            )
            g_rows = g_rows * clip[:, None]
        lr = jnp.asarray(self.lr_schedule(state["global_step"]), jnp.float32)
        p_flat = pack_flat(state["params"], n)
        upd, new_opt = self.optimizer.frozen_apply(
            g_rows, state["opt_state"], p_flat, lr, self.mesh, axes
        )
        state = dict(state)
        state["params"] = unpack_flat(jnp.where(overflow, p_flat, p_flat + upd), state["params"])
        state["opt_state"] = jax.tree.map(
            lambda old, new: jnp.where(overflow, old, new), state["opt_state"], new_opt
        )
        state["global_step"] = state["global_step"] + jnp.where(overflow, 0, 1)
        state["loss_scale"] = self.loss_scaler.update(state["loss_scale"], overflow)
        info = {"lr": lr, "grad_norm": grad_norm, "overflow": overflow}
        return state, jnp.mean(losses), info

    def _save_host_optimizer(self, ckpt_dir: str) -> None:
        """Persist host-resident optimizer state (per-shard npz files)."""
        if self._host_opt is None:
            return
        if self._offload_shards <= 1:
            self._host_opt.save(os.path.join(ckpt_dir, f"host_optimizer_rank{jax.process_index()}.npz"))
            return
        for j, i in enumerate(self._host_shard_ids):
            self._host_opts[j].save(os.path.join(ckpt_dir, f"host_optimizer_shard{i}.npz"))

    def _load_host_optimizer(self, ckpt_dir: str, restored_params, use_files: bool = True) -> None:
        """Restore host optimizer state; if the tag has none (saved by a
        non-offload run) or ``use_files`` is off, rebuild fp32 masters
        from the restored params."""
        if self._host_opt is None:
            return
        exists = lambda p: use_files and os.path.exists(p)

        def warn_if_other_layout(expected: str):
            import glob

            others = glob.glob(os.path.join(ckpt_dir, "host_optimizer_*.npz"))
            if others:
                logger.warning(
                    f"host optimizer state {expected} not found, but the tag has "
                    f"{[os.path.basename(o) for o in others]} — the checkpoint was "
                    "saved under a different offload shard layout (process count / "
                    "DS_OFFLOAD_SHARDS); Adam moments are being RESET from params"
                )

        if self._offload_shards <= 1:
            path = os.path.join(ckpt_dir, f"host_optimizer_rank{jax.process_index()}.npz")
            if exists(path):
                self._host_opt.load(path)
            else:
                if use_files:
                    warn_if_other_layout(os.path.basename(path))
                self._host_opt.load_masters(jax.tree.map(np.asarray, restored_params))
            return
        from deepspeed_tpu.runtime.fp16.onebit.adam import pack_flat

        flat = None
        for j, i in enumerate(self._host_shard_ids):
            path = os.path.join(ckpt_dir, f"host_optimizer_shard{i}.npz")
            if exists(path):
                self._host_opts[j].load(path)
            else:
                if use_files:
                    warn_if_other_layout(os.path.basename(path))
                if flat is None:
                    flat = np.asarray(
                        pack_flat(jax.tree.map(np.asarray, restored_params), self._offload_shards)
                    )
                L = self._offload_slice_len
                self._host_opts[j].load_masters({"flat": flat[i * L : (i + 1) * L]})

    def _sharded_host_step(self, g_np, unscaled_leaves, lr, step_count):
        """Step only this host's flat master slice(s) and reassemble the
        full masters — the multi-host ZeRO-Offload path (each process
        allgather-joins its 1/P slice).  With DS_OFFLOAD_SHARDS in one
        process, every slice is stepped locally (same math, testable)."""
        from deepspeed_tpu.runtime.fp16.onebit.adam import unpack_flat

        P_shards = self._offload_shards
        L = self._offload_slice_len
        flat_g = np.concatenate([np.asarray(l, np.float32).reshape(-1) for l in unscaled_leaves])
        pad = (-flat_g.shape[0]) % P_shards
        if pad:
            flat_g = np.concatenate([flat_g, np.zeros(pad, np.float32)])
        slices = {}
        for j, i in enumerate(self._host_shard_ids):
            mt = self._host_opts[j].step({"flat": flat_g[i * L : (i + 1) * L]}, lr, step_count)
            slices[i] = mt["flat"]
        if jax.process_count() > 1:
            # masters reassembly routes through the comm layer (dense
            # host allgather of fp32 slices; supervision-armed)
            with self._sup_region("offload.masters_allgather"):
                stacked = np.asarray(
                    self.comm.host_allgather(slices[self._host_shard_ids[0]])
                )
            full = stacked.reshape(-1)
        else:
            full = np.concatenate([slices[i] for i in sorted(slices)])
        return unpack_flat(full, self.state["params"])

    # ------------------------------------------------------------------
    # unified comm layer (docs/comm.md)
    # ------------------------------------------------------------------
    def _init_comm_layer(self, config) -> None:
        """Build the strategy-selected comm layer and resolve the
        gradient-exchange strategy ONCE, at trace-decision time: dense
        keeps the GSPMD constraint path untouched; int8 / onebit switch
        ``train_batch`` to the explicit per-rank-rows step
        (:meth:`_comm_full_step`).  The onebit strategy's error-feedback
        residual rows live in ``state['comm']`` and ride checkpoints
        with the rest of the state."""
        from deepspeed_tpu.comm.strategy import STRATEGY_DENSE, STRATEGY_ONEBIT, CommLayer
        from deepspeed_tpu.config.config import CommConfig

        self.comm = CommLayer(
            self.mesh, self.mesh_info, getattr(config, "comm", None) or CommConfig(),
            zero_config=config.zero_config, topology=self.topology,
        )
        # satellite: the previously-unwired reduce_scatter flag is now
        # honored by ZeroShardingRules.grad_spec; warn once when it
        # forces the dense all-reduce path (reference stage2 fallback)
        if (
            config.zero_config.stage >= 2
            and self.mesh_info.fsdp_world_size > 1
            and not config.zero_config.reduce_scatter
        ):
            self.comm.note(
                "zero-grad-reduce", STRATEGY_DENSE,
                "zero_optimization.reduce_scatter=false forces the dense all-reduce path",
            )
            logger.warning(
                "zero_optimization.reduce_scatter=false: gradient reduction stays a "
                "full all-reduce (grads replicated over fsdp) — ~2x the wire bytes "
                "and a params-sized grad buffer per chip (the reference's stage2 "
                "allreduce fallback); drop the flag to restore the psum_scatter path"
            )
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.state["params"]))
        self._comm_n_params = n_params
        n = max(1, self.mesh_info.dp_world_size)
        self._comm_flat_len = -(-n_params // n) * n
        axes = self._dp_exchange_axes()
        want = self.comm.select(4 * n_params, jnp.float32, axes, site="grad-exchange")
        explicit = want != STRATEGY_DENSE
        if explicit:
            blockers = {
                "data-parallel grid must be > 1": self.mesh_info.dp_world_size > 1,
                "pipeline engine unsupported": getattr(self, "_use_grad_acc", True),
                "offload_optimizer unsupported": not self._offload,
                "1-bit optimizer owns its own exchange": not self._onebit_exchange_ok,
            }
            failed = [k for k, ok in blockers.items() if not ok]
            if failed:
                logger.warning(
                    f"comm: '{want}' gradient exchange requested but DISABLED "
                    f"({'; '.join(failed)}); falling back to dense"
                )
                self.comm.note("grad-exchange", STRATEGY_DENSE, f"forced dense: {'; '.join(failed)}")
                want, explicit = STRATEGY_DENSE, False
        self._comm_grad_strategy = want
        self._comm_explicit = explicit
        self.state["comm"] = {}
        self._state_shardings["comm"] = {}
        if explicit:
            # the explicit path accumulates into its own (n, Mp) rows
            # buffer inside the compiled step — free the params-sized
            # fp32 accumulator (as the 1-bit frozen phase does)
            self.state["grad_acc"] = {}
            self._state_shardings["grad_acc"] = {}
            mp = self._comm_flat_len
            if want == STRATEGY_ONEBIT and self.comm.config.error_feedback:
                row_sh = self._sh(dp_rows_spec(axes))
                comm_sh = {"worker_error": row_sh, "server_error": row_sh}
                self.state["comm"] = jax.jit(
                    lambda: {
                        "worker_error": jnp.zeros((n, mp), jnp.float32),
                        "server_error": jnp.zeros((n, mp // n), jnp.float32),
                    },
                    out_shardings=comm_sh,
                )()
                self._state_shardings["comm"] = comm_sh
            log_dist(
                f"comm: '{want}' gradient exchange over {axes} "
                f"(n={n} ranks, {mp} padded coords, "
                f"{'EF residuals in state' if self.state['comm'] else 'stateless'})"
            )
        summ = self.comm_summary()
        self.timeline.set_comm(summ["strategy"], summ["grad_exchange_bytes"])
        if self.telemetry is not None:
            self.telemetry.set_comm(summ)

    def train_step_executable(self):
        """The newest compiled ``train_batch`` executable (its
        ``as_text()`` is the optimized HLO), or None before the first
        step."""
        return self._train_executable

    def comm_summary(self) -> Dict[str, Any]:
        """Active comm-strategy table + the per-step comm-bytes model
        (docs/comm.md) — surfaced by ds_report."""
        from deepspeed_tpu.comm.strategy import step_comm_bytes

        model = step_comm_bytes(
            self._comm_n_params,
            self.mesh_info.sizes,
            stage=self.zero_stage,
            gas=self.gradient_accumulation_steps,
            strategy=self._comm_grad_strategy,
            reduce_scatter=self.config.zero_config.reduce_scatter,
            topology=self.topology,
        )
        return {
            "strategy": self._comm_grad_strategy,
            "grad_exchange_bytes": model["grad-exchange"],
            "model": model,
            "table": self.comm.table(),
        }

    def _comm_full_step(self, state, stacked):
        """Compiled train step for the explicit compressed gradient
        exchange (comm.strategy int8 / onebit): per-rank gradients stay
        UNREDUCED as (n, Mp) rows accumulated across micro batches; ONE
        strategy-compressed exchange per step replaces the per-micro
        dense psum, then the dense-identical unscaled update applies
        (clipping on the exchanged average — dense semantics, so the
        loss trajectory stays comparable)."""
        from deepspeed_tpu.runtime.fp16.onebit.adam import pack_rows, unpack_flat

        n = self.mesh_info.dp_world_size
        axes = self._dp_exchange_axes()
        gas = self.gradient_accumulation_steps
        mp = self._comm_flat_len
        row_sh = self._sh(dp_rows_spec(axes))
        acc0 = jax.lax.with_sharding_constraint(jnp.zeros((n, mp), jnp.float32), row_sh)

        def body(carry, mb):
            st, acc = carry
            if self.progressive_layer_drop is not None and isinstance(mb, dict):
                from deepspeed_tpu.runtime.progressive_layer_drop import PLD_THETA_KEY

                mb = dict(mb)
                mb[PLD_THETA_KEY] = self.progressive_layer_drop.get_theta(st["global_step"])
            rng = jax.random.fold_in(st["rng"], st["micro_step"])

            def rows_of(x):
                return x.reshape((n, x.shape[0] // n) + x.shape[1:])

            b_rows = jax.tree.map(rows_of, mb)

            def slice_loss(p, b, r):
                return self._compute_loss(p, b, r, st["loss_scale"])

            # independent rng per DP slice (dropout must differ per slice)
            (_, loss), g = jax.vmap(
                jax.value_and_grad(slice_loss, has_aux=True), in_axes=(None, 0, 0)
            )(st["params"], b_rows, jax.random.split(rng, n))
            g_rows = jax.lax.with_sharding_constraint(pack_rows(g, n, n), row_sh)
            st = dict(st)
            st["micro_step"] = st["micro_step"] + 1
            st["global_samples"] = (
                st["global_samples"]
                + self.train_micro_batch_size_per_gpu * self.mesh_info.dp_world_size
            )
            return (st, acc + g_rows), jnp.mean(loss)

        (state, acc), losses = jax.lax.scan(body, (state, acc0), stacked)
        scale = self.loss_scaler.scale_loss(jnp.float32(1.0), state["loss_scale"])
        g_rows = acc / (gas * scale)
        overflow = ~jnp.isfinite(jnp.sum(g_rows))
        # quantizing an inf row would poison every rank's output AND the
        # EF residuals; the overflow flag above already discards the step
        g_rows = jnp.where(jnp.isfinite(g_rows), g_rows, 0.0)
        state = dict(state)
        if self._comm_grad_strategy == "onebit" and self.state["comm"]:
            werr = state["comm"]["worker_error"]
            serr = state["comm"]["server_error"]
            g_mean, new_res = self.comm.exchange_rows(
                g_rows, axes, "onebit", residuals=(werr, serr)
            )
            state["comm"] = {
                "worker_error": jnp.where(overflow, werr, new_res[0]),
                "server_error": jnp.where(overflow, serr, new_res[1]),
            }
        else:
            # int8 stochastic rounding (or EF-less onebit) needs fresh
            # bits each step; fold the step counter so replays differ
            rng = jax.random.fold_in(state["rng"], state["global_step"] + 777_001)
            g_mean, _ = self.comm.exchange_rows(
                g_rows, axes, self._comm_grad_strategy, rng=rng
            )
        grads = unpack_flat(g_mean, state["params"])  # params are fp32 masters
        grads = self.comm.constrain_grads(
            grads,
            jax.tree.map(self._sh, self._grad_specs, is_leaf=lambda x: isinstance(x, P)),
            site="grad-specs",
        )
        state, info = self._apply_update_unscaled(state, grads, overflow)
        return state, jnp.mean(losses), info

    # ------------------------------------------------------------------
    # public training API
    # ------------------------------------------------------------------
    def _stacked_sharding(self, ndim_stacked: int):
        return self._sh(
            stacked_batch_pspec(ndim_stacked, seq_sharded=self.mesh_info.seq_parallel_world_size > 1)
        )

    def _stack_and_place(self, batch: Any) -> Any:
        """Reshape a flat (gas·mb, ...) batch to (gas, mb, ...) and place
        it with the engine's batch sharding.  Batches already processed
        (wrapped in ``_PlacedBatch`` by ``prefetch_loader``) unwrap and
        pass straight through — no shape heuristics."""
        if isinstance(batch, _PlacedBatch):
            return batch.tree
        gas = self.gradient_accumulation_steps
        leaves = jax.tree.leaves(batch)
        if (
            leaves
            and np.ndim(leaves[0]) >= 1
            and not getattr(self, "_batch_mismatch_warned", False)
        ):
            fed = np.shape(leaves[0])[0]
            expect = gas * self.train_micro_batch_size_per_gpu * self.mesh_info.dp_world_size
            if fed != expect:
                # a config/batch mismatch silently changes the effective
                # micro-batch (shape[0] // gas wins below) and every
                # per-chip throughput normalization drifts with it —
                # surface it once; callers that need the hard guarantee
                # pin train_batch_size to the fed shape
                self._batch_mismatch_warned = True
                logger.warning(
                    f"train_batch fed {fed} samples but the config triad says "
                    f"train_batch_size = gas({gas}) × micro_bs("
                    f"{self.train_micro_batch_size_per_gpu}) × dp("
                    f"{self.mesh_info.dp_world_size}) = {expect}; proceeding with "
                    f"effective global micro-batch {fed // gas} — per-chip "
                    "throughput normalizations will not match the config"
                )

        def one(x):
            x = np.asarray(x) if not isinstance(x, (jax.Array, np.ndarray)) else x
            mb = x.shape[0] // gas
            x = x.reshape((gas, mb) + x.shape[1:])
            return jax.device_put(x, self._stacked_sharding(np.ndim(x)))

        return jax.tree.map(one, batch)

    def prefetch_loader(self, loader, prefetch_depth: Optional[int] = None):
        """Wrap a host batch iterator so loader pulls and stacking +
        sharded device placement run ahead of the compiled step as a
        two-stage pipeline (runtime/overlap ``DevicePrefetcher``); feed
        the result to ``train_batch``.  ``prefetch_depth`` defaults to
        the ``overlap.prefetch.depth`` config (2 = double buffering);
        with ``overlap.prefetch.enabled = false`` the wrap is a
        synchronous pass-through (A/B knob for measuring the overlap) —
        unless the caller passes ``prefetch_depth`` explicitly, which is
        a direct API request for background prefetch and wins over the
        config default."""
        from deepspeed_tpu.runtime.overlap import DevicePrefetcher, InlineLoader

        place = lambda b: _PlacedBatch(self._stack_and_place(b))  # noqa: E731
        if not self.overlap.prefetch.enabled and prefetch_depth is None:
            return self.register_dataloader(InlineLoader(
                loader, place, timeline=self.timeline, sanitizer=self._sanitizer
            ))
        depth = self.overlap.prefetch.depth if prefetch_depth is None else int(prefetch_depth)
        return self.register_dataloader(DevicePrefetcher(
            loader, depth=depth, place_fn=place, timeline=self.timeline,
            sanitizer=self._sanitizer,
        ))

    def _prepare_batch(self, batch: Any) -> Any:
        def put(x):
            x = np.asarray(x) if not isinstance(x, (jax.Array, np.ndarray)) else x
            sh = self._sh(batch_pspec(np.ndim(x), seq_sharded=self.mesh_info.seq_parallel_world_size > 1))
            return jax.device_put(x, sh)

        return jax.tree.map(put, batch)

    def forward(self, batch: Any) -> jnp.ndarray:
        """Fused forward+backward on one micro-batch; returns the loss.

        Deviation from the reference (engine.py:1089): JAX autodiff cannot
        be split across Python calls, so gradients are produced here and
        folded into the accumulator; ``backward()`` validates ordering.
        """
        if self._onebit_frozen:
            raise RuntimeError(
                "the 1-bit compressed phase runs whole batches (its gradient "
                "accumulator lives inside the compiled step); use train_batch()"
            )
        if self._comm_explicit:
            raise RuntimeError(
                f"comm.strategy '{self._comm_grad_strategy}' runs whole batches "
                "(the per-rank gradient rows live inside the compiled step); "
                "use train_batch()"
            )
        if self._lazy_grad_acc and not self.state["grad_acc"]:
            # the micro API needs the accumulator train_batch's gas==1
            # fused path avoids; allocate it on first use
            acc_sh = jax.tree.map(self._sh, self._grad_specs, is_leaf=lambda x: isinstance(x, P))
            self.state["grad_acc"] = jax.jit(
                lambda p: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
                out_shardings=acc_sh,
            )(self.state["params"])
            self._state_shardings["grad_acc"] = acc_sh
        if self.wall_clock_breakdown:
            self.timers(FORWARD_TIMER).start()
        with self.timeline.phase("data_wait"):
            batch = self._prepare_batch(batch)
        fn = self._get_compiled(
            "micro_step", self._micro_step_impl,
            out_shardings=(self._state_shardings, self._sh(P())),
        )
        san = self._sanitizer
        donated = jax.tree.leaves(self.state) if san is not None else None
        t_compute = time.perf_counter()
        with san.transfer.guard("engine.forward") if san is not None else nullcontext():
            self.state, loss = fn(self.state, batch)
        if san is not None:
            san.donation.note(donated, "engine.forward", step=self._host_global_step)
            self._san_last_batch = ("micro", batch)
        if self.timeline.enabled and self._timeline_fence:
            jax.block_until_ready(loss)
            self.timeline.note("compute", time.perf_counter() - t_compute)
        self._host_micro_step += 1
        self._cached_loss = loss
        self._last_loss = loss  # step()'s divergence check_loss reads this
        if self.wall_clock_breakdown:
            self.timers(FORWARD_TIMER).stop(sync_token=loss)
        return loss

    __call__ = forward

    def backward(self, loss: Any = None, allreduce_gradients: bool = True) -> Any:
        """Grad accumulation already happened in ``forward``; this is the
        ordering checkpoint (and the place a future pipeline engine hooks)."""
        if self._cached_loss is None:
            raise RuntimeError("backward() called before forward()")
        if self.wall_clock_breakdown:
            self.timers(BACKWARD_TIMER).start()
            self.timers(BACKWARD_TIMER).stop()
        loss = self._cached_loss
        self._cached_loss = None
        return loss

    def allreduce_gradients(self, bucket_size: int = MEMORY_OPT_ALLREDUCE_SIZE) -> None:
        """Reference API shim (engine.py:1147).  Gradient reduction is
        in-graph here: ``psum``/``psum_scatter`` over the data/fsdp axes
        are inserted by GSPMD from the grad sharding constraints
        (zero/stages.py) — there is nothing to launch from the host, and
        bucketing/overlap are XLA scheduler decisions."""
        return None

    def step(self) -> None:
        """Apply the optimizer step at the gradient-accumulation boundary
        (reference engine.step, :1318)."""
        if self.wall_clock_breakdown:
            self.timers(STEP_TIMER).start()
        if self.is_gradient_accumulation_boundary():
            if self._offload:
                info = self._host_apply_step()
            else:
                # pin the output state to the declared layout: the
                # cross-replica update computes over dp-sharded state,
                # and without the pin GSPMD would keep the updated
                # params dp-sharded too (sharding drift vs the declared
                # replicated param spec; the pin is where the one
                # updated-params all-gather lands)
                scalar = self._sh(P())
                fn = self._get_compiled(
                    "apply_step", self._apply_step_impl,
                    out_shardings=(self._state_shardings,
                                   {"lr": scalar, "grad_norm": scalar, "overflow": scalar}),
                )
                san = self._sanitizer
                donated = jax.tree.leaves(self.state) if san is not None else None
                with self._sup_region("engine.step"):
                    with san.transfer.guard("engine.step") if san is not None else nullcontext():
                        self.state, info = fn(self.state)
                if san is not None:
                    san.donation.note(donated, "engine.step", step=self._host_global_step)
            overflowed = False
            if self.loss_scaler.dynamic:
                # explicit d2h read: the deliberate once-per-step host
                # sync must not look like an implicit transfer under the
                # sanitizer's guard
                with self._sup_region("engine.overflow_sync"):
                    overflowed = bool(jax.device_get(info["overflow"]))
                if overflowed:
                    self.skipped_steps += 1
                    log_dist(f"step skipped on overflow; loss scale -> {self.loss_scale}")
                elif not self._offload:
                    self._host_global_step += 1
            elif not self._offload:
                self._host_global_step += 1
            self._maybe_report_progress()
            self._on_step_boundary(overflowed, loss=self._last_loss)
            self.timeline.end_step()
        if self.wall_clock_breakdown:
            self.timers(STEP_TIMER).stop(sync_token=self.state["global_step"])
            self.timers.log([FORWARD_TIMER, BACKWARD_TIMER, STEP_TIMER])

    def train_batch(self, batch: Any) -> jnp.ndarray:
        """One full global batch — all GAS micro-batches + optimizer step in
        a single compiled program (lax.scan over micro-batches).

        ``batch`` leaves must have leading dim ``gas * micro_batch`` (one
        full train_batch worth of per-replica samples) or ``micro_batch``
        (gas==1).  Batches already stacked/placed by
        ``prefetch_loader()`` pass through untouched (no re-put: the
        host-side staging of ``device_put`` stays off the hot path).

        In a ``jax.profiler`` trace the call is one ``ds.train.step``
        span carrying the step number, with ``ds.train.data_wait``,
        ``.compile`` and ``.dispatch`` inside it (docs/telemetry.md); no
        fence is added, so the device may still be running when it ends.
        """
        with self.timeline.annotation("step", step=self._host_global_step + 1):
            return self._train_batch(batch)

    def _train_batch(self, batch: Any) -> jnp.ndarray:
        self.tput_timer.start()
        if (
            self._onebit_exchange_ok
            and not self._onebit_frozen
            and self._host_global_step >= self.optimizer.freeze_step
        ):
            self._enter_onebit_frozen()
        san = self._sanitizer
        was_placed = isinstance(batch, _PlacedBatch)
        t_place = time.perf_counter()
        with self.timeline.annotation("data_wait"), (
            san.transfer.guard("engine.train_batch.place") if san is not None else nullcontext()
        ):
            stacked = self._stack_and_place(batch)
        if not was_placed:
            # prefetched batches had their wait noted by the prefetcher
            self.timeline.note("data_wait", time.perf_counter() - t_place)

        tb_key = (
            "train_batch",
            self._onebit_frozen,
            bool(self.state["grad_acc"]),
            tuple(np.shape(x) for x in jax.tree.leaves(stacked)),
        )
        if tb_key not in self._compiled:
            apply_in_graph = not self._offload
            full_step = self._full_step_fn()

            # AOT compile: the executable's cost_analysis feeds the flops
            # profiler for free (no second trace/compile at profile time).
            # out_shardings pin the output state to the input layout —
            # without them GSPMD may pick different output shardings and
            # the next call would mismatch (plain jit hides that as a
            # silent recompile).
            scalar = self._sh(P())
            if apply_in_graph:
                out_sh = (self._state_shardings, scalar,
                          {"lr": scalar, "grad_norm": scalar, "overflow": scalar})
            else:
                out_sh = (self._state_shardings, scalar)
            # the function's name is the program's in the profiler's
            # trace: jit_train_step on the devices' "XLA Modules" lines
            train_step = self._scoped(full_step)
            train_step.__name__ = "train_step"
            with self.timeline.phase("compile") as span:
                executable = (
                    jax.jit(train_step, donate_argnums=(0,), out_shardings=out_sh)
                    .lower(self.state, stacked)
                    .compile()
                )
                self._note_update_split(span)
            self._compiled[tb_key] = executable
            self._train_executable = executable
            self.compilation_count += 1
            # ds_shard Pass 1/2 feed (no-op unless the audit armed it)
            shard_hooks.note_train(self, "train.train_batch", executable,
                                   fn=self._scoped(full_step),
                                   args=(self.state, stacked),
                                   out_state_shardings=out_sh[0])
            if san is not None:
                # signature of exactly what was lowered: a recount here
                # names the state/batch leaf whose shape/dtype/sharding
                # drifted since the last executable was built
                san.recompile.note("engine.train_batch", (self.state, stacked), owner=id(self))
            cost = executable.cost_analysis() or {}
            self._train_step_cost = {k: float(v) for k, v in cost.items() if np.isscalar(v)}
            if self.telemetry is not None:
                # the compiled step's cost analysis is the numerator of
                # the live MFU / HBM-GB/s gauges (docs/telemetry.md)
                self.telemetry.set_step_cost(self._train_step_cost)
        profile_step = self._host_global_step + 1
        self.flops_profiler.start_step(profile_step)
        donated = jax.tree.leaves(self.state) if san is not None else None
        t_compute = time.perf_counter()
        # supervision: the compiled step is the step-boundary collective
        # (grad psum over the data axis) — the armed deadline plus the
        # peer-death escalation live here (docs/resilience.md)
        guard = san.transfer.guard("engine.train_batch") if san is not None else nullcontext()
        with self._sup_region("engine.train_batch"):
            # ds.train.dispatch: the call of the compiled step until it
            # returns (XLA dispatch is async: not the device's time)
            if self._offload:
                with self.timeline.annotation("dispatch"), guard:
                    self.state, loss = self._compiled[tb_key](self.state, stacked)
                # the host optimizer step is a deliberate host-I/O region
                # (grads device->host, masters host->device) — not guarded
                info = self._host_apply_step()
            else:
                with self.timeline.annotation("dispatch"), guard:
                    self.state, loss, info = self._compiled[tb_key](self.state, stacked)
        if san is not None:
            san.donation.note(donated, "engine.train_batch", step=self._host_global_step)
            self._san_last_batch = ("stacked", stacked)
        if self.timeline.enabled and self._timeline_fence:
            # fence: XLA dispatch is async — an unfenced delta would only
            # measure Python overhead (ds_lint `unfenced-timing`).  Off
            # (the default without wall_clock_breakdown), no compute note
            # is recorded: host-measurable phases stay honest and the hot
            # path keeps its dispatch pipelining
            jax.block_until_ready(loss)
            self.timeline.note("compute", time.perf_counter() - t_compute)
        self.flops_profiler.end_step(profile_step, cost=self._train_step_cost, sync_token=loss)
        self._last_loss = loss
        self._last_info = info  # lr / grad_norm / overflow of this step
        # host sync on the overflow flag only when dynamic scaling is live
        # (explicit device_get: a deliberate sync, not an implicit
        # transfer — the sanitizer's guard budget stays honest)
        overflowed = False
        if self.loss_scaler.dynamic:
            # the overflow read is where the host actually BLOCKS on the
            # cross-process step (dispatch above is async) — armed too
            with self._sup_region("engine.overflow_sync"):
                overflowed = bool(jax.device_get(info["overflow"]))
            if overflowed:
                self.skipped_steps += 1
                log_dist(f"step skipped on overflow; loss scale -> {self.loss_scale}")
            elif not self._offload:
                self._host_global_step += 1
        elif not self._offload:
            self._host_global_step += 1
        self._host_micro_step += self.gradient_accumulation_steps
        self.tput_timer.stop(sync_token=loss)
        self._maybe_report_progress()
        self._on_step_boundary(overflowed, loss=loss)
        self.timeline.end_step()
        return loss

    def _full_step_fn(self) -> Callable:
        """One full train step as a pure function ``(state, stacked) ->
        (state, loss[, info])`` — the unit ``train_batch`` compiles and
        ``train_batches`` scans.  With offload, the program ends after
        the micro-batch scan (the optimizer step runs on host — ZeRO-
        Offload splits exactly here)."""
        apply_in_graph = not self._offload
        if self._onebit_frozen:
            return self._frozen_full_step
        if self._comm_explicit:
            return self._comm_full_step
        if apply_in_graph and self._use_grad_acc and not self.state["grad_acc"]:
            # gas==1 fused path (no persistent accumulator was
            # allocated): grads flow straight into the update
            def full_step(state, stacked):
                mb = jax.tree.map(lambda x: jnp.squeeze(x, 0), stacked)
                state, loss, grads = self._micro_grads(state, mb)
                state, info = self._apply_update(state, grads)
                return state, loss, info

            return full_step

        def full_step(state, stacked):
            def body(st, mb):
                return self._micro_step_impl(st, mb)

            state, losses = jax.lax.scan(body, state, stacked)
            if apply_in_graph:
                state, info = self._apply_step_impl(state)
                return state, jnp.mean(losses), info
            return state, jnp.mean(losses)

        return full_step

    def train_batches(self, batches, unroll=False) -> np.ndarray:
        """Run N full train steps in ONE compiled program — a
        ``lax.scan`` of the train step over a stacked run of batches.

        TPU-idiomatic driver loop: per-program dispatch costs (argument
        marshalling, launch latency) amortize over the whole run, the way
        t5x/pax drive entire loops inside one program.  What a dispatch
        costs on an attached chip: not measured (PERF.md).  Semantics are identical to
        calling ``train_batch`` N times: same grads, same updates, same
        overflow skipping; per-step losses return as one (N,) array.

        Not available with host offload (the optimizer step leaves the
        graph) or across the 1-bit warmup→frozen transition (the state
        layout changes mid-run) — those fall back to the per-step loop.

        ``unroll``: False = plain ``lax.scan`` (one XLA while loop,
        carry double-buffered per iteration); True = fully unrolled
        (no loop, n× graph); an int k >= 2 = partial unroll (k step
        bodies per while iteration — carry copies amortize 1/k at k×
        graph size); k == 1 is the plain scan, identical to False.
        """
        batches = list(batches)
        n = len(batches)
        if n == 0:
            return np.zeros((0,), np.float32)
        crosses_freeze = (
            self._onebit_exchange_ok
            and not self._onebit_frozen
            and self._host_global_step + n > getattr(self.optimizer, "freeze_step", 0)
        )
        if self._offload or crosses_freeze or n == 1:
            return np.asarray([float(self.train_batch(b)) for b in batches], np.float32)
        self.tput_timer.start()
        with self.timeline.phase("data_wait"):
            stacked = [self._stack_and_place(b) for b in batches]
            run = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
        san = self._sanitizer
        unroll_k = n if unroll is True else max(1, min(int(unroll), n))
        key = (
            "train_batches", n, unroll_k, self._onebit_frozen, bool(self.state["grad_acc"]),
            tuple(np.shape(x) for x in jax.tree.leaves(run)),
        )
        if key not in self._compiled:
            full_step = self._full_step_fn()

            def full_run(state, run):
                def body(st, stk):
                    st, loss, info = full_step(st, stk)
                    return st, (loss, info["overflow"], info["lr"], info["grad_norm"])

                # unroll=n removes the while-loop: no carry double-buffer
                # copies of the big state, at the cost of an n× graph
                state, (losses, ovf, lrs, gns) = jax.lax.scan(
                    body, state, run, unroll=unroll_k
                )
                return state, losses, jnp.sum(ovf.astype(jnp.int32)), lrs[-1], gns[-1]

            scalar = self._sh(P())
            with self.timeline.phase("compile") as span:
                self._compiled[key] = (
                    jax.jit(
                        self._scoped(full_run), donate_argnums=(0,),
                        out_shardings=(self._state_shardings, scalar, scalar, scalar, scalar),
                    )
                    .lower(self.state, run)
                    .compile()
                )
                self._note_update_split(span)
            self.compilation_count += 1
            if san is not None:
                san.recompile.note("engine.train_batches", (self.state, run), owner=id(self))
        donated = jax.tree.leaves(self.state) if san is not None else None
        t_compute = time.perf_counter()
        with san.transfer.guard("engine.train_batches") if san is not None else nullcontext():
            self.state, losses, ovf_count, last_lr, last_gn = self._compiled[key](self.state, run)
        if san is not None:
            san.donation.note(donated, "engine.train_batches", step=self._host_global_step)
            self._san_last_batch = ("stacked", stacked[-1])
        # explicit d2h reads (materializing losses = the compute fence)
        losses = np.asarray(jax.device_get(losses))
        self.timeline.note("compute", time.perf_counter() - t_compute)
        skipped = int(jax.device_get(ovf_count))
        if self.loss_scaler.dynamic:
            self.skipped_steps += skipped
            self._host_global_step += n - skipped
        else:
            self._host_global_step += n  # matches the per-step loop's host count
        self._host_micro_step += n * self.gradient_accumulation_steps
        # progress reports read these — same dict shape as the per-step
        # loop (lr/grad_norm from the LAST step of the run).  NB the
        # step_per_print/monitor cadence coalesces: boundaries crossed
        # strictly inside the run emit one report at run end
        self._last_loss = losses[-1]
        self._last_info = {"lr": last_lr, "grad_norm": last_gn, "overflow": skipped > 0}
        self.tput_timer.stop(sync_token=losses[-1] if len(losses) else None)
        self._maybe_report_progress()
        # the compiled run only exposes the skip COUNT, not per-step order:
        # a fully-skipped run provably contains n consecutive skips (feed
        # the guard one record per step so n >= threshold trips it within
        # the run); partially-skipped runs reset the streak
        records = n if skipped == n else 1
        guard = getattr(self, "_divergence_guard", None)
        trips_before = guard.trips if guard is not None else 0
        for i in range(records):
            self._on_step_boundary(
                skipped == n, loss=self._last_loss if i == records - 1 else None
            )
            if guard is not None and guard.trips > trips_before:
                break  # one action per detection, not one per threshold-multiple
        self.timeline.end_step(count=n)
        return losses

    def eval_batch(self, batch: Any) -> Any:
        batch = self._prepare_batch(batch)
        if "eval" not in self._compiled:

            def eval_fn(state, b):
                # rng=None ⇒ deterministic eval (model convention)
                _, loss = self._compute_loss(state["params"], b, None, state["loss_scale"])
                return loss

            self._compiled["eval"] = jax.jit(self._scoped(eval_fn))
        return self._compiled["eval"](self.state, batch)

    def predict(self, batch: Any) -> Any:
        """Raw model outputs (inference forward)."""
        batch = self._prepare_batch(batch)
        if "predict" not in self._compiled:

            def pred_fn(state, b):
                cparams = self._materialize_params(state["params"], self.compute_dtype)
                return self._model_fn(cparams, b, None)

            self._compiled["predict"] = jax.jit(self._scoped(pred_fn))
        return self._compiled["predict"](self.state, batch)

    def _maybe_report_progress(self):
        step = self._host_global_step
        if self.quantizer is not None:
            self.quantizer.maybe_log(step)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(step)
        if step > 0 and step % self.config.steps_per_print == 0:
            log_dist(f"step={step} lr={self.get_lr()[0]:.3e} loss_scale={self.loss_scale:.1f}")
            if self.wall_clock_breakdown and self.timeline.enabled:
                log_dist(self.timeline.format_summary(self.config.steps_per_print))
            tm = self.telemetry
            if tm is not None and (tm.collect or tm.monitor_enabled):
                # loss/lr/loss-scale route through the telemetry
                # registry; the manager forwards the reference
                # Train/Samples/* tags (engine.py:1178-1188, :1356-1382)
                # to the TensorBoard sink unchanged.  The d2h reads are
                # a deliberate report-cadence sync — paid ONLY when a
                # consumer is armed (monitor / sinks / the already-
                # syncing wall_clock_breakdown); the default registry-
                # only path stays transfer-free: samples come from the
                # host step mirror and the loss gauge is skipped.
                sync = tm.monitor_enabled or tm.exports_armed or self.wall_clock_breakdown
                if sync:
                    samples = int(jax.device_get(self.state["global_samples"]))
                    loss = (
                        float(jax.device_get(self._last_loss))
                        if self._last_loss is not None else None
                    )
                else:
                    # micro-step mirror, not global_step: overflow-
                    # skipped steps still CONSUME their samples (the
                    # device global_samples counts them too)
                    samples = (
                        self._host_micro_step
                        * self.config.train_micro_batch_size_per_gpu
                        * self.mesh_info.dp_world_size
                    )
                    loss = None
                tm.publish_train_progress(
                    step=step, samples=samples, loss=loss,
                    lr=float(self.get_lr()[0]), loss_scale=float(self.loss_scale),
                )

    # ------------------------------------------------------------------
    # resilience: preemption + divergence + supervision handling
    # (docs/resilience.md)
    # ------------------------------------------------------------------
    def _note_checkpoint_dir(self, directory: str) -> None:
        """Remember where this run checkpoints (emergency saves and
        divergence rollback target it)."""
        self._resilience_ckpt_dir = os.path.abspath(directory)

    def register_dataloader(self, loader):
        """Register the training loader for resume-cursor round-trips:
        checkpoint saves record its ``state_dict()`` in the client
        state, loads restore it — a restarted job neither replays nor
        skips batches (docs/resilience.md).  ``prefetch_loader`` calls
        this automatically."""
        self._train_loader = loader
        return loader

    def _build_supervisor(self, sv):
        """Construct + start the rank supervisor for the configured side
        channel; None (with a warning) when no channel is reachable."""
        from deepspeed_tpu.resilience.supervision import Supervisor
        from deepspeed_tpu.resilience.supervision import heartbeat as hb

        # the supervision plane is LAUNCHER-scoped, not jax-scoped: a
        # job whose ranks run per-process replicas (no jax.distributed)
        # still has a failure domain, so fall back to the launcher's
        # RANK/WORLD_SIZE env when jax sees a single process
        rank, world = jax.process_index(), jax.process_count()
        if world <= 1:
            rank = int(os.environ.get("RANK", rank))
            world = int(os.environ.get("WORLD_SIZE", world))
        kind = sv.channel
        addr, port = hb.resolve_endpoint()
        if kind == "auto":
            if world > 1 and port:
                kind = "tcp"
            elif sv.beat_dir:
                kind = "file"
            else:
                logger.warning(
                    "resilience.supervision enabled but no side channel is available "
                    "(no DS_SUPERVISION_PORT from the launcher, no supervision.beat_dir); "
                    "supervision stays OFF"
                )
                return None
        if kind == "tcp":
            if not port:
                logger.warning(
                    "resilience.supervision channel 'tcp' needs DS_SUPERVISION_PORT "
                    "(set by launcher/launch.py); supervision stays OFF"
                )
                return None
            channel = hb.TcpBeatChannel(
                rank, world, address=addr, port=port,
                beat_timeout=sv.beat_timeout_seconds,
                connect_grace=sv.connect_grace_seconds,
            )
        else:
            if not sv.beat_dir:
                logger.warning(
                    "resilience.supervision channel 'file' needs supervision.beat_dir; "
                    "supervision stays OFF"
                )
                return None
            channel = hb.FileBeatChannel(
                sv.beat_dir, rank, world, beat_timeout=sv.beat_timeout_seconds
            )
        # telemetry piggyback (docs/telemetry.md): rank-local compact
        # snapshots ride every beat; rank 0 aggregates min/mean/max and
        # flags dead ranks in the same exported stream.  The JSONL
        # aggregate stream needs an explicit telemetry.output_path (no
        # silent files in cwd); the cluster/* gauges always flow.
        metrics_fn = None
        aggregator = None
        tcfg = getattr(self.config, "telemetry", None)
        if tcfg is not None and tcfg.enabled and tcfg.aggregate:
            from deepspeed_tpu import telemetry as _tel

            reg = _tel.get_registry()
            metrics_fn = lambda: (reg.snapshot_compact() or None) if reg.enabled else None
            if rank == 0:
                agg_path = (
                    os.path.join(tcfg.output_path, f"aggregate_rank{rank}.jsonl")
                    if tcfg.output_path else None
                )
                aggregator = _tel.CrossRankAggregator(
                    world, jsonl_path=agg_path, registry=reg,
                    straggler_factor=tcfg.straggler_factor,
                )
        sup = Supervisor(
            rank=rank,
            world_size=world,
            channel=channel,
            beat_interval=sv.beat_interval_seconds,
            sync_timeout=sv.sync_timeout_seconds,
            rescue_grace=sv.rescue_grace_seconds,
            exit_code=sv.exit_code,
            save_dir_fn=lambda: self._resilience_ckpt_dir,
            checksum=self.resilience.checkpoint.checksum,
            metrics_fn=metrics_fn,
            aggregator=aggregator,
        ).start()
        log_dist(
            f"supervision: rank {rank}/{world} armed on the {channel.name} channel "
            f"(beat {sv.beat_interval_seconds:g}s, death deadline "
            f"{sv.beat_timeout_seconds:g}s, sync deadline {sv.sync_timeout_seconds:g}s)"
        )
        return sup

    def _sup_region(self, site: str):
        """Armed-deadline region around one blocking sync.  An exception
        inside the region while a peer is (or is about to be declared)
        dead routes into the rescue path — the collective usually errors
        out milliseconds after the peer dies, before the beat deadline."""
        from contextlib import nullcontext

        sup = getattr(self, "_supervision", None)
        if sup is None:
            return nullcontext()
        return _SupervisedRegion(self, sup, site)

    def _supervision_snapshot(self) -> None:
        """Host snapshot of the portable state + its checkpoint meta at
        a step boundary — what the supervisor commits (pure host I/O)
        if this process must rescue while the main thread is wedged."""
        from deepspeed_tpu.runtime import checkpointing as _ckpt

        sup = self._supervision
        step = self._host_global_step
        client_state = {}
        loader_sd = _ckpt._loader_state(self)
        if loader_sd is not None:
            client_state["__dataloader__"] = loader_sd
        meta = _ckpt._build_meta(self, f"emergency_step{step}", client_state)
        sup.snapshot.update(_ckpt._snapshot_state_to_host(self), meta)

    def _handle_peer_failure(self, pf, fresh_snapshot: bool = True):
        """A peer died: commit a verified emergency tag (rank-local
        ``local_npz`` — no collectives; in DP topologies this rank's
        host snapshot holds the full logical state) and exit with the
        supervision contract code (default 44, "peer-failed-and-saved")
        so the launcher's ``--restarts`` can relaunch-and-resume.  Exits
        1 when no save could be certified."""
        sup = self._supervision
        sup.main_handling = True
        if not sup.claim_rescue("main"):
            # the supervisor thread won the race and is mid-commit; it
            # will os._exit with the right code — don't double-stage the
            # same tag (the loser's StageInFlightError would read as a
            # failed save).  The sleep only ends if the supervisor hangs.
            logger.error("supervision: supervisor thread owns the rescue; waiting for its exit")
            time.sleep(max(30.0, sup.rescue_grace * 4))
            raise SystemExit(1)
        logger.error(
            f"supervision: peer rank {pf.rank} failed ({pf.reason}); committing an "
            f"emergency checkpoint before exiting"
        )
        if fresh_snapshot:
            # we are at a clean step boundary: snapshot the LIVE state
            # (fresher than the last boundary snapshot)
            try:
                self._supervision_snapshot()
            except BaseException as e:  # noqa: BLE001 — fall back to the last one
                logger.warning(f"fresh emergency snapshot failed ({e!r}); using the last boundary snapshot")
        code = sup.rescue_save(reason=f"peer rank {pf.rank} failed: {pf.reason}")
        sup.stop()
        raise SystemExit(code)

    def _on_step_boundary(self, overflowed: bool, loss=None) -> None:
        """Host-side hook after every optimizer-step boundary: fault
        sites and supervision first (a peer death or injected kill at a
        boundary must win over progress reporting), then a pending
        preemption request, then the divergence guard."""
        from deepspeed_tpu.resilience import faults as _faults

        _faults.check("step.boundary")
        sup = getattr(self, "_supervision", None)
        if sup is not None:
            pf = sup.peer_failure
            if pf is not None:
                self._handle_peer_failure(pf)
            if not getattr(self, "_supervision_snapshot_broken", False) and sup.snapshot_due(
                self._host_global_step, self.resilience.supervision.snapshot_interval_steps
            ):
                try:
                    self._supervision_snapshot()
                except Exception as e:  # noqa: BLE001 — e.g. non-addressable shards
                    # state spanning non-addressable devices (multi-host
                    # sharded topologies) cannot be host-snapshotted from
                    # one rank; degrade to no boundary snapshots (rescue
                    # then certifies exit 1, the crash contract) instead
                    # of killing the training loop every step
                    self._supervision_snapshot_broken = True
                    logger.warning(
                        f"supervision: step-boundary snapshot failed ({e!r}); disabling "
                        "boundary snapshots — a rescue on this rank will exit 1 "
                        "(resume from the previous verified tag)"
                    )
        wd = getattr(self, "_watchdog", None)
        if wd is not None and wd.preemption_requested:
            self._handle_preemption()
        san = getattr(self, "_sanitizer", None)
        if san is not None and san.drift.due(self._host_global_step):
            san.drift.check_state(self, step=self._host_global_step)
        guard = getattr(self, "_divergence_guard", None)
        if guard is None:
            return
        from deepspeed_tpu.resilience import faults

        diverged = bool(overflowed) or faults.check_flag("engine.force_overflow")
        if not diverged and self.resilience.divergence.check_loss and loss is not None:
            # opt-in host sync: the only NaN signal without dynamic loss
            # scaling (bf16 default has no overflow flag)
            diverged = not bool(np.isfinite(np.asarray(jax.device_get(loss))))
        action = guard.record(diverged)
        if action is not None:
            if san is not None:
                # name the first non-finite op before the action mutates
                # state (floor recompiles, rollback replaces params)
                san.nanprobe.probe_engine_step(self, self._san_last_batch)
            self._apply_divergence_action(action)

    def _handle_preemption(self) -> None:
        """Emergency checkpoint + exit.  Exit-code contract: the
        configured code (default 43) means "preempted AND saved" — a
        scheduler can requeue and resume blindly; exit 1 means the save
        did not happen (deadline passed or save failed) — treat as a
        crash and resume from the previous tag."""
        wd = self._watchdog
        from deepspeed_tpu.telemetry import get_registry

        get_registry().counter("resilience/preemptions").inc()
        log_dist(
            f"preemption signal ({wd.signal_name}) received; attempting emergency "
            f"checkpoint ({wd.remaining():.0f}s of grace left)"
        )
        if self._resilience_ckpt_dir is None:
            logger.error(
                "preempted but no checkpoint dir is known (no prior save/load and no "
                "'resilience.watchdog.save_dir'); exiting WITHOUT saving"
            )
            raise SystemExit(1)
        if wd.remaining() <= 0:
            logger.error(
                f"preemption grace deadline ({wd.grace_seconds}s) already passed; "
                "exiting WITHOUT saving"
            )
            raise SystemExit(1)
        writer = self._async_writer
        if writer is not None and writer.in_flight:
            # drain-before-exit: an in-flight background commit must land
            # (or provably fail) before the emergency save touches the
            # tree; the budget is capped by the remaining grace window
            log_dist("draining in-flight async checkpoint before the emergency save")
            try:
                writer.drain(timeout=max(1.0, min(writer.drain_timeout_seconds, wd.remaining())))
            except BaseException as e:  # hung drain => cannot certify "saved"
                logger.error(f"drain of in-flight async save failed: {e!r}")
                raise SystemExit(1) from e
        try:
            # synchronous: exit code 43 must certify a COMMITTED tag
            path = self.save_checkpoint(self._resilience_ckpt_dir, async_save=False)
        except BaseException as e:  # a failed save must NOT exit as "saved"
            logger.error(f"emergency checkpoint failed: {e!r}")
            raise SystemExit(1) from e
        log_dist(f"emergency checkpoint saved to {path}; exiting with code {wd.exit_code}")
        raise SystemExit(wd.exit_code)

    def _apply_divergence_action(self, action: str) -> None:
        from deepspeed_tpu.telemetry import get_registry

        get_registry().counter("resilience/divergence_actions", action=action).inc()
        n = self.resilience.divergence.threshold
        if action == C.DIVERGENCE_ACTION_FLOOR:
            old = self.loss_scaler.min_scale
            self.loss_scaler.min_scale = max(old / 2.0, 2.0**-24)
            # the floor is baked into compiled steps as a constant
            self._purge_train_executables()
            logger.warning(
                f"divergence guard: {n} consecutive skipped steps — lowering loss-scale "
                f"floor {old} -> {self.loss_scaler.min_scale} (recompiling train step)"
            )
        elif action == C.DIVERGENCE_ACTION_ROLLBACK:
            if self._resilience_ckpt_dir is None:
                logger.error(
                    f"divergence guard: {n} consecutive skipped steps and action=rollback, "
                    "but no checkpoint dir is known (no prior save/load); cannot roll back"
                )
                return
            logger.warning(
                f"divergence guard: {n} consecutive skipped steps — rolling back to the "
                f"last verified checkpoint under {self._resilience_ckpt_dir}"
            )
            # strict=False even under fail_on_missing: a failed rollback
            # must degrade to the error log below, not crash the step
            path, _ = self.load_checkpoint(self._resilience_ckpt_dir, strict=False)
            if path is None:
                logger.error("divergence rollback found no loadable checkpoint")
        else:
            logger.warning(
                f"divergence guard: {n} consecutive NaN/overflow-skipped steps "
                f"(loss scale {self.loss_scale}) — the run is likely diverging"
            )

    # ------------------------------------------------------------------
    # checkpointing (engine.save_checkpoint, reference :1854)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None, client_state: Optional[dict] = None, save_latest: bool = True, async_save: Optional[bool] = None):
        """``async_save``: None defers to the ``overlap.async_checkpoint``
        config; True/False forces the background/synchronous path for
        this save (see docs/performance.md)."""
        from deepspeed_tpu.runtime.checkpointing import save_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state, save_latest=save_latest, async_save=async_save)

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None, **kw):
        from deepspeed_tpu.runtime.checkpointing import load_checkpoint as _load

        return _load(self, load_dir, tag=tag, **kw)


class _SupervisedRegion:
    """Armed-deadline region around one of the engine's blocking syncs.

    On a normal exit the deadline disarms.  On an exception, a pending
    (or imminent — the channel gets one beat-timeout to confirm) peer
    death converts the error into the engine's peer-failure rescue:
    commit a verified emergency tag, exit with the supervision contract
    code.  Anything else re-raises untouched.
    """

    def __init__(self, engine, sup, site: str):
        self.engine = engine
        self.sup = sup
        self.site = site
        self._armed = sup.armed(site)

    def __enter__(self):
        self._armed.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._armed.__exit__(exc_type, exc, tb)
        if exc is None or isinstance(exc, SystemExit):
            return False
        if self.sup.main_handling:
            return False
        wait = getattr(self.sup.channel, "beat_timeout", 2.0)
        pf = self.sup.confirm_peer_failure(wait=wait)
        if pf is not None:
            logger.error(
                f"supervision: blocking sync '{self.site}' raised "
                f"{exc_type.__name__} with peer rank {pf.rank} dead; entering rescue"
            )
            # state buffers may be donated into the failed computation:
            # rescue from the last boundary snapshot, not live state
            self.engine._handle_peer_failure(pf, fresh_snapshot=False)
        return False
