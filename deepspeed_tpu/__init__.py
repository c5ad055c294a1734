"""deepspeed_tpu — a TPU-native training/inference framework with the
capabilities of DeepSpeed (reference v0.4.5), re-designed for JAX/XLA:
SPMD named-axis meshes instead of process groups, sharding rules instead
of optimizer-wrapper hooks (ZeRO 1-3), Pallas kernels instead of CUDA,
XLA collectives over ICI instead of NCCL.

Public API mirrors the reference's ``deepspeed/__init__.py``:
``initialize`` (:58), ``init_inference`` (:227), ``init_distributed``,
``add_config_arguments`` (:211).
"""
from __future__ import annotations

import argparse
from typing import Any, Callable, Optional, Tuple

from deepspeed_tpu.version import __version__
from deepspeed_tpu.comm.distributed import init_distributed
from deepspeed_tpu.config.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.utils.device import setup_compile_cache
from deepspeed_tpu.utils.logging import log_dist, logger

__git_hash__ = None
__git_branch__ = None


def initialize(
    args=None,
    model: Optional[Callable] = None,
    model_parameters: Any = None,
    optimizer: Any = None,
    training_data: Any = None,
    lr_scheduler: Any = None,
    mesh=None,
    tp_spec_fn=None,
    partition_rules=None,
    loss_fn: Optional[Callable] = None,
    dist_init_required: Optional[bool] = None,
    collate_fn: Optional[Callable] = None,
    config: Any = None,
    config_params: Any = None,
):
    """Build a ready-to-train engine.

    Reference signature preserved (``deepspeed/__init__.py:58-157``) with
    TPU-native meanings:

    * ``model`` — callable ``(params, batch, rng) -> loss`` (or outputs if
      ``loss_fn`` is given).  Flax modules: pass
      ``lambda p, b, rng: module.apply({'params': p}, b, rngs={'dropout': rng})``.
    * ``model_parameters`` — the initial parameter pytree (the reference
      passes ``model.parameters()`` here).
    * ``config`` — dict or path to a DeepSpeed-style JSON config.
    * ``mesh`` — optional prebuilt ``jax.sharding.Mesh``; default built
      from the config's ``mesh`` block over all devices.

    Returns ``(engine, optimizer, dataloader, lr_scheduler)``.
    """
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    from deepspeed_tpu.comm.mesh import MeshInfo, make_mesh

    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config") and args.deepspeed_config:
        config = args.deepspeed_config
    if config is None:
        raise DeepSpeedConfigError("initialize() needs `config` (dict or json path)")
    if model is None:
        raise ValueError("initialize() needs `model` (callable (params, batch, rng) -> loss/outputs)")
    is_pipe = isinstance(model, PipelineModule)
    if model_parameters is None and not is_pipe:
        raise ValueError("initialize() needs `model_parameters` (initial parameter pytree)")

    if dist_init_required is None or dist_init_required:
        init_distributed(verbose=False)
    setup_compile_cache()

    # Resolve the mesh first (the batch triad needs the dp world size).
    if mesh is None:
        import json as _json

        from deepspeed_tpu.config.config import MeshConfig

        raw = config
        if isinstance(raw, str):
            with open(raw) as f:
                raw = _json.load(f)
        mesh = make_mesh(MeshConfig.from_dict(raw.get("mesh")))
    info = MeshInfo.from_mesh(mesh)
    ds_config = DeepSpeedConfig(config, world_size=info.dp_world_size)

    stream_reason = "pipeline module" if is_pipe else None
    if not is_pipe and ds_config.zero_config.offload_param.enabled:
        from deepspeed_tpu.runtime.zero.param_offload import ZeroInfinityEngine

        stream_reason = ZeroInfinityEngine.streamable(model, ds_config, info, optimizer)
        if stream_reason is not None:
            # refuse (not warn-then-OOM) when the model the user asked to
            # STREAM would not fit the in-HBM fallback engine
            ZeroInfinityEngine.check_fallback_fits(
                model_parameters, ds_config, info, stream_reason
            )
            from deepspeed_tpu.utils.logging import logger as _logger

            _logger.warning(
                f"offload_param: falling back to the in-HBM engine — {stream_reason}"
            )
    if not is_pipe and ds_config.zero_config.offload_param.enabled and stream_reason is None:
        # ZeRO-Infinity param offload: params exceed HBM — stream layer
        # groups through the device (reference
        # partitioned_param_swapper.py:36 / features.md:116 "13B on one
        # 32GB device"); models advertise streamability via
        # model.stream_spec (models/gpt2.py)
        from deepspeed_tpu.runtime.zero.param_offload import ZeroInfinityEngine

        engine = ZeroInfinityEngine(
            model=model,
            params=model_parameters,
            config=ds_config,
            mesh=mesh,
            lr_scheduler=lr_scheduler,
        )
    elif is_pipe:
        # reference: PipelineEngine iff model is a PipelineModule
        # (deepspeed/__init__.py:125-149)
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

        if loss_fn is not None:
            if model.loss_fn is not None and model.loss_fn is not loss_fn:
                raise ValueError("loss_fn given both to PipelineModule and initialize()")
            model.loss_fn = loss_fn
        engine = PipelineEngine(
            module=model,
            config=ds_config,
            mesh=mesh,
            params=model_parameters,
            optimizer=optimizer,
            lr_scheduler=lr_scheduler,
            tp_spec_fn=tp_spec_fn,
            partition_rules=partition_rules,
        )
    else:
        engine = DeepSpeedEngine(
            model=model,
            params=model_parameters,
            config=ds_config,
            optimizer=optimizer,
            lr_scheduler=lr_scheduler,
            mesh=mesh,
            tp_spec_fn=tp_spec_fn,
            partition_rules=partition_rules,
            loss_fn=loss_fn,
            dist_init_required=dist_init_required,
        )

    dataloader = None
    if training_data is not None:
        import jax

        local_dp = max(1, info.dp_world_size // jax.process_count())
        dataloader = DeepSpeedDataLoader(
            training_data,
            batch_size=ds_config.train_micro_batch_size_per_gpu * local_dp,
            shuffle=True,
            seed=ds_config.seed,
            drop_last=ds_config.dataloader_drop_last,
            collate_fn=collate_fn,
        )

    return engine, engine.optimizer, dataloader, engine.lr_schedule


def init_inference(model=None, **kwargs):
    """Reference ``init_inference`` (:227) — builds an InferenceEngine."""
    from deepspeed_tpu.inference.engine import InferenceEngine

    setup_compile_cache()
    return InferenceEngine(model=model, **kwargs)


def init_serving(model=None, serving=None, **kwargs):
    """TPU-native extension: a continuous-batching ServingEngine over an
    :func:`init_inference` engine (docs/serving.md).  ``serving`` is the
    ``serving`` config block (dict or ServingConfig); remaining kwargs go
    to ``init_inference``."""
    from deepspeed_tpu.serving import ServingEngine

    return ServingEngine(init_inference(model=model, **kwargs), config=serving)


def add_config_arguments(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Reference ``add_config_arguments`` (:211): the standard argparse
    group so recipes keep their ``--deepspeed --deepspeed_config x.json``
    flags."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument(
        "--deepspeed",
        default=False,
        action="store_true",
        help="Enable DeepSpeed (helper flag for user code, no impact on engine)",
    )
    group.add_argument("--deepspeed_config", default=None, type=str, help="DeepSpeed json configuration file")
    group.add_argument(
        "--deepscale",
        default=False,
        action="store_true",
        help="Deprecated enable DeepSpeed (helper flag for user code, no impact on engine)",
    )
    group.add_argument("--deepscale_config", default=None, type=str, help="Deprecated DeepSpeed json configuration file")
    group.add_argument("--local_rank", default=-1, type=int, help="Reserved for compatibility; unused on TPU")
    return parser


# `zero` namespace for reference-style `with deepspeed.zero.Init()` usage.
from deepspeed_tpu.runtime.zero import api as zero  # noqa: E402
