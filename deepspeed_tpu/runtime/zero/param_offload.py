"""ZeRO-Infinity parameter offload — train models whose params exceed HBM.

Reference capability being reproduced: ``AsyncPartitionedParameterSwapper``
(``runtime/swap_tensor/partitioned_param_swapper.py:36``) + ZeRO-3 param
partitioning let one 32GB GPU train 13B params by keeping fp16 params on
CPU/NVMe and fetching each submodule's params just in time
(``docs/_pages/features.md:116``).

TPU-native form: the reference hooks ``nn.Module`` forward/backward to
swap eager tensors; under XLA the unit of streaming is instead a **layer
group** of the model's stacked block params, and the train step becomes
five small compiled programs orchestrated from host:

    embed → [group fwd] × G → head(+vjp) → [group vjp] × G → embed bwd

HBM holds: resident params (embeddings/head), ONE group's params, the
G+1 boundary activations, and one group's grads — never the full model.
Masters + Adam moments live on host (``HostOffloadOptimizer``; moments
optionally on NVMe through the kernel-AIO engine); with
``offload_param.device == "nvme"`` the bf16 group params themselves
stage through NVMe with one-group-ahead prefetch (``AsyncTensorSwapper``
over the same AIO engine), so host RAM holds fp32 masters and HBM holds
one group — the single-chip >HBM capability row.

The model advertises its streaming structure via
``model_fn.stream_spec`` (see ``models/gpt2.py``).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.comm.mesh import MeshInfo, batch_pspec
from deepspeed_tpu.runtime.zero.offload import (
    HostOffloadOptimizer,
    _flatten_with_paths,
    host_unscale_clip_and_check,
)
from deepspeed_tpu.utils.logging import log_dist, logger


@dataclasses.dataclass
class StreamSpec:
    """Layer-streaming structure a model exposes for param offload.

    ``blocks_key``: params subtree whose leaves are stacked on a leading
    layer dim.  ``embed(resident, tokens) -> x``;
    ``group(gblocks, x, rngs, deterministic) -> x``;
    ``head_loss(resident, x, batch) -> loss``.
    """

    n_layer: int
    blocks_key: str
    embed: Callable
    group: Callable
    head_loss: Callable
    deterministic: bool = True
    supported: bool = True


class ZeroInfinityEngine:
    """Streaming train executor for ``offload_param.enabled`` models.

    API mirrors the core engine where it matters: ``train_batch``,
    ``eval_batch``, ``save_checkpoint`` / ``load_checkpoint``,
    ``global_steps``.  Unsupported combos raise at init, not at step N.
    """

    @staticmethod
    def streamable(model, config, mesh_info, optimizer=None) -> Optional[str]:
        """None if this (model, config, mesh) combo can stream; else the
        reason it can't — ``initialize()`` falls back to the in-HBM
        engine (with a warning) rather than crashing configs that
        worked before the streaming path existed."""
        spec = getattr(model, "stream_spec", None)
        if spec is None:
            return "model exposes no stream_spec"
        if not spec.supported:
            return "model config is not streamable (MoE blocks)"
        if config.fp16.enabled:
            return "requires bf16 (no dynamic loss scale on the host path)"
        if mesh_info.model_parallel_world_size > 1:
            return "model (TP) sharding of streamed params is not implemented"
        if optimizer is not None:
            return "client optimizer objects are unsupported (host Adam owns the update)"
        name = (config.optimizer.name or "adamw").lower()
        if name not in ("adam", "adamw"):
            return f"host step supports Adam/AdamW, got '{config.optimizer.name}'"
        return None

    @staticmethod
    def check_fallback_fits(params, config, mesh_info, reason: str) -> None:
        """``offload_param`` was requested but this combo can't stream
        (``reason``).  The fallback to the in-HBM engine is only safe if
        the model actually FITS per device — for a >HBM model it would
        OOM at step time with no mention of why streaming refused.
        Estimate the fallback engine's resident bytes and refuse early,
        carrying the streamable-reason.  HBM budget: real device
        ``memory_stats()['bytes_limit']`` (override with
        ``DS_TPU_HBM_BYTES``); unknown budget (CPU backend) skips the
        check."""
        hbm = os.environ.get("DS_TPU_HBM_BYTES")
        if hbm is None:
            try:
                stats = jax.local_devices()[0].memory_stats() or {}
                hbm = stats.get("bytes_limit")
            except Exception:  # noqa: BLE001 — stats are backend-optional
                hbm = None
        if hbm is None:
            return
        n = sum(int(np.size(l)) for l in jax.tree.leaves(params))
        dt = 2 if (config.bf16.enabled or config.fp16.enabled) else 4
        zc = config.zero_config
        pg_shards = max(1, mesh_info.fsdp_world_size) if zc.stage >= 3 else 1
        opt_dev = 0 if zc.offload_optimizer.enabled else 12  # fp32 master+m+v
        opt_shards = max(1, mesh_info.fsdp_world_size) if zc.stage >= 1 else 1
        # grads accumulate in fp32 on device regardless of compute dtype
        # (and stay on device even with offload_optimizer) — counting
        # them at compute width under-estimated bf16 runs by 2 B/param
        per_dev = n * ((dt + 4) / pg_shards + opt_dev / opt_shards)
        if per_dev > 0.9 * float(hbm):
            raise RuntimeError(
                f"offload_param requested but this combination cannot stream "
                f"({reason}); the in-HBM fallback would keep "
                f"~{per_dev / 1e9:.1f} GB/device resident of {float(hbm) / 1e9:.1f} GB "
                "HBM and OOM at step time. Fix the streaming blocker instead."
            )

    def __init__(self, model, params, config, mesh, lr_scheduler=None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec: StreamSpec = model.stream_spec
        if not spec.supported:
            raise NotImplementedError("offload_param: this model config is not streamable (MoE blocks)")
        if config.fp16.enabled:
            raise NotImplementedError("offload_param requires bf16 (no dynamic loss scale on the host path)")
        self.config = config
        self.spec = spec
        self.mesh = mesh
        self.mesh_info = MeshInfo.from_mesh(mesh)
        if self.mesh_info.model_parallel_world_size > 1:
            raise NotImplementedError(
                "offload_param streams layer groups over data/fsdp axes only "
                "(model-axis TP sharding of streamed params is not implemented)"
            )
        self.compute_dtype = jnp.bfloat16 if config.bf16.enabled else jnp.float32

        # -- comm layer (docs/comm.md): the streaming engine's exchanges
        # are GSPMD reduce-scatters (group_bwd out_shardings) and the
        # host-side flag/partial allgathers; quantized strategies do not
        # apply to the host-resident optimizer path, so everything here
        # is recorded dense
        from deepspeed_tpu.comm.strategy import STRATEGY_DENSE, CommLayer
        from deepspeed_tpu.config.config import CommConfig

        self.comm = CommLayer(
            mesh, self.mesh_info, getattr(config, "comm", None) or CommConfig(),
            zero_config=config.zero_config,
        )
        self.comm.note(
            "group-grad-reduce", STRATEGY_DENSE,
            "GSPMD reduce-scatter over fsdp (+ psum over data) from group_bwd out_shardings",
        )
        self.comm.note(
            "offload-host-sync", STRATEGY_DENSE,
            "host process_allgather for grad-norm partials and checkpoint flags",
        )
        if getattr(config, "comm", None) is not None and config.comm.strategy not in ("dense", "auto"):
            from deepspeed_tpu.utils.logging import logger as _logger

            _logger.warning(
                f"comm.strategy '{config.comm.strategy}' is not supported by the "
                "streaming ZeRO-Infinity engine (host-resident optimizer); staying dense"
            )

        zc = config.zero_config
        # layers per HBM-resident group: offload_param.buffer_count, or
        # the largest divisor of n_layer below it (so any model depth
        # works with the default)
        want = max(1, int(getattr(zc.offload_param, "buffer_count", 1) or 1))
        gl = max(d for d in range(1, min(want, spec.n_layer) + 1) if spec.n_layer % d == 0)
        self.group_layers = gl
        self.n_groups = spec.n_layer // gl

        # -- host-resident state ------------------------------------------
        params = jax.tree.map(lambda p: np.asarray(p, np.float32), params)
        # Multi-host master sharding (reference ``stage3.py:2633-2686`` +
        # ``partitioned_param_swapper.py:36`` — ZeRO-Infinity swaps each
        # DP rank's PARTITION, never the whole model): the stacked-blocks
        # fp32 masters + Adam moments live 1/H per HOST along the fsdp
        # axis.  Each process keeps only the master rows covering its
        # local devices' fsdp shards; group uploads assemble the global
        # array from the process-local slices and group grads drain back
        # shard-local, so host RAM and NVMe bytes both scale 1/H.  When
        # fsdp sits inside one host (or fsdp == 1) the local range is the
        # whole axis and behavior is the replicated-masters path.
        blocks_full = params[spec.blocks_key]
        bflat = _flatten_with_paths(blocks_full)
        self._blocks_gshapes = [tuple(np.shape(v)) for _, v in bflat]
        self._blocks_tdef = jax.tree.structure(blocks_full)
        self._setup_host_partition(mesh)
        params = dict(params)
        params[spec.blocks_key] = jax.tree.unflatten(
            self._blocks_tdef,
            [self._leaf_to_local(v, gs) for (_, v), gs in zip(bflat, self._blocks_gshapes)],
        )
        # flat-leaf classification for the distributed grad norm: each
        # block leaf carries its fsdp-sharded dim (None = replicated)
        bdims = {
            k: self._sharded_dim((gl,) + gs[1:])
            for (k, _), gs in zip(bflat, self._blocks_gshapes)
        }
        _prefix = f"{spec.blocks_key}/"
        self._flat_leaf_kinds = [
            ("block", bdims[k[len(_prefix):]]) if k.startswith(_prefix) else ("resident", None)
            for k, _ in _flatten_with_paths(params)
        ]
        opt_cfg = dict(config.optimizer.params or {})
        opt_name = (config.optimizer.name or "adamw").lower()
        if opt_name not in ("adam", "adamw"):
            raise ValueError(f"offload_param supports Adam/AdamW, got '{config.optimizer.name}'")
        nvme_dir = None
        if (zc.offload_optimizer.enabled and zc.offload_optimizer.device == "nvme") or (
            zc.offload_param.enabled and zc.offload_param.device == "nvme"
        ):
            nvme_dir = zc.offload_param.nvme_path or zc.offload_optimizer.nvme_path or "/tmp/ds_tpu_nvme"
            if jax.process_count() > 1:
                # on a real multi-host job the same path names each
                # host's LOCAL disk; the rank suffix additionally keeps
                # co-located test processes from clobbering each other
                nvme_dir = os.path.join(nvme_dir, f"rank{jax.process_index()}")
        self._host_opt = HostOffloadOptimizer(
            params,
            lr=opt_cfg.get("lr", 1e-3),
            betas=tuple(opt_cfg.get("betas", (0.9, 0.999))),
            eps=opt_cfg.get("eps", 1e-8),
            weight_decay=opt_cfg.get("weight_decay", 0.0),
            adamw_mode=opt_name == "adamw",
            nvme_swap_dir=os.path.join(nvme_dir, "moments") if (
                nvme_dir and zc.offload_optimizer.enabled and zc.offload_optimizer.device == "nvme"
            ) else None,
            aio_config=config.aio,
        )
        self._treedef = jax.tree.structure(params)
        # Host param views alias the optimizer's MASTER arrays by
        # construction (masters_tree() unflattens the very ndarrays
        # opt.step mutates in place) — the per-group write-back hook
        # fires mid-step and must see each group's freshly-updated rows,
        # so the aliasing is load-bearing, not an accident of
        # ascontiguousarray happening to return its input.
        self._params_host = self._host_opt.masters_tree()
        self._blocks_host = self._params_host[spec.blocks_key]
        self._resident_host = {
            k: v for k, v in self._params_host.items() if k != spec.blocks_key
        }

        # -- NVMe param staging (ZeRO-Infinity proper) ---------------------
        self._param_swapper = None
        if zc.offload_param.enabled and zc.offload_param.device == "nvme":
            from deepspeed_tpu.runtime.swap.async_swapper import AsyncTensorSwapper

            self._param_swapper = AsyncTensorSwapper(
                os.path.join(nvme_dir, "params"), aio_config=config.aio
            )
            self._swap_out_all_groups()
            log_dist(
                f"ZeRO-Infinity param offload: {self.n_groups} "
                f"{np.dtype(self._stage_np_dtype).name} layer-group files on NVMe "
                f"at {nvme_dir} (kernel AIO), one group resident in HBM at a time"
            )
        else:
            log_dist(
                f"ZeRO-Offload param streaming: params host-resident, "
                f"{self.group_layers} layer(s)/group × {self.n_groups} groups through HBM"
            )

        # -- schedules / bookkeeping --------------------------------------
        from deepspeed_tpu.runtime.lr_schedules import get_lr_schedule

        if callable(lr_scheduler):
            self.lr_schedule = lr_scheduler
        elif config.scheduler.type:
            self.lr_schedule = get_lr_schedule(config.scheduler.type, config.scheduler.params)
        else:
            base_lr = opt_cfg.get("lr", 1e-3)
            self.lr_schedule = lambda step: base_lr
        self.client_lr_scheduler = None
        self.optimizer = self._host_opt
        self.global_steps = 0
        self.skipped_steps = 0
        self._compiled: Dict[str, Any] = {}
        # batch rows shard over the whole DP world (data × fsdp), the
        # same convention as the in-HBM engine (sharding/layout.py,
        # re-exported through comm.mesh)
        self._batch_sh = NamedSharding(mesh, batch_pspec(1))
        # ZeRO-3 × ZeRO-Infinity composition (reference stage3.py:2633-2686
        # + partitioned_param_swapper.py:36 swap per-rank *partitions*):
        # each uploaded group is SHARDED over the fsdp axis — per-device
        # HBM holds group/fsdp param bytes; GSPMD all-gathers shards
        # inside the group programs and reduce-scatters group grads back
        # to the same 1/P layout (out_shardings below).  Shardings are
        # built from GLOBAL group shapes — the host slices are 1/H.
        self._group_gshapes = [(gl,) + gs[1:] for gs in self._blocks_gshapes]
        self._group_shardings = jax.tree.unflatten(
            self._blocks_tdef,
            [NamedSharding(mesh, self._fsdp_leaf_spec(gs)) for gs in self._group_gshapes],
        )
        log_dist(
            f"ZeRO-Infinity engine: {spec.n_layer} layers in {self.n_groups} groups, "
            f"micro_bs={config.train_micro_batch_size_per_gpu} gas={config.gradient_accumulation_steps} "
            f"dp={self.mesh_info.dp_world_size}"
        )

    # ------------------------------------------------------------------
    # host <-> device staging
    # ------------------------------------------------------------------
    def _fsdp_leaf_spec(self, shape):
        """fsdp PartitionSpec for one stacked-block leaf ``(gl, ...)``:
        shard the largest trailing dim divisible by the fsdp size (the
        leading stacked-layer dim stays whole — group_layers may be
        smaller than the axis); replicate when nothing divides.
        Resolved through the partition-rule engine's layout helper."""
        from deepspeed_tpu.sharding.layout import fsdp_trailing_spec

        return fsdp_trailing_spec(shape, self.mesh_info.fsdp_world_size)

    def _sharded_dim(self, group_shape) -> Optional[int]:
        """Index of the fsdp-sharded dim of one group leaf, or None."""
        for i, s in enumerate(self._fsdp_leaf_spec(group_shape)):
            if s == "fsdp":
                return i
        return None

    def _setup_host_partition(self, mesh) -> None:
        """Locate this host on the fsdp axis: the contiguous range of
        fsdp parts its local devices cover (masters / moments / NVMe
        bytes are kept ONLY for that range), and the sub-range it OWNS
        for grad-norm accounting (a part is owned by the lowest process
        index holding it, so every part is counted exactly once
        globally)."""
        me = jax.process_index()
        P = self.mesh_info.fsdp_world_size
        axis_i = list(mesh.axis_names).index("fsdp")
        owner: Dict[int, int] = {}
        local = set()
        for coord, dev in np.ndenumerate(mesh.devices):
            f = int(coord[axis_i])
            pi = int(dev.process_index)
            owner[f] = min(owner.get(f, pi), pi)
            if pi == me:
                local.add(f)
        parts = sorted(local)
        if parts != list(range(parts[0], parts[-1] + 1)):
            raise NotImplementedError(
                "offload_param: this host's fsdp shards are non-contiguous "
                f"on the mesh ({parts}); arrange the mesh so each host "
                "covers a contiguous fsdp range"
            )
        owned = sorted(f for f in parts if owner[f] == me)
        if owned and owned != list(range(owned[0], owned[-1] + 1)):
            raise NotImplementedError(
                f"offload_param: non-contiguous owned fsdp range {owned}"
            )
        self._part_local = (parts[0], parts[-1] + 1)
        self._part_owned = (owned[0], owned[-1] + 1) if owned else (0, 0)
        self._masters_sharded = (self._part_local[1] - self._part_local[0]) < P
        if self._masters_sharded:
            log_dist(
                f"ZeRO-Infinity multi-host: masters sharded 1/{P} per fsdp "
                f"part, this host keeps parts [{parts[0]}, {parts[-1] + 1})"
            )

    def _leaf_to_local(self, arr: np.ndarray, gshape) -> np.ndarray:
        """This host's slice of one full stacked-blocks leaf (the whole
        leaf when masters are not sharded across hosts)."""
        d = self._sharded_dim((self.group_layers,) + tuple(gshape[1:]))
        if d is None or not self._masters_sharded:
            return arr
        plo, phi = self._part_local
        per = gshape[d] // self.mesh_info.fsdp_world_size
        sl = [slice(None)] * len(gshape)
        sl[d] = slice(plo * per, phi * per)
        return np.ascontiguousarray(arr[tuple(sl)])

    @staticmethod
    def _to_local_np(garr, dtype=np.float32) -> np.ndarray:
        """Host copy of the process-local region of a (possibly
        multi-host) device array: the bounding box of this process's
        addressable shards — the full array single-process, this host's
        fsdp slice for sharded group grads."""
        if jax.process_count() == 1:
            return np.asarray(garr, dtype)
        shape = garr.shape
        boxes, lo, hi = [], list(shape), [0] * len(shape)
        for sh in garr.addressable_shards:
            b = []
            for i, sl in enumerate(sh.index):
                start = 0 if sl.start is None else int(sl.start)
                stop = shape[i] if sl.stop is None else int(sl.stop)
                b.append((start, stop))
                lo[i] = min(lo[i], start)
                hi[i] = max(hi[i], stop)
            boxes.append(b)
        out = np.empty([h - l for l, h in zip(lo, hi)], dtype)
        for sh, b in zip(garr.addressable_shards, boxes):
            dest = tuple(slice(s - l, e - l) for (s, e), l in zip(b, lo))
            out[dest] = np.asarray(sh.data, dtype)
        return out

    def _drain_group(self, tree) -> Any:
        """Group grads device→host, keeping only this host's local
        region of each leaf (matches the 1/H master slices)."""
        leaves = [self._to_local_np(l) for l in jax.tree.leaves(tree)]
        return jax.tree.unflatten(self._blocks_tdef, leaves)

    def _clip_and_check_global(self, grad_flat: List[np.ndarray]):
        """Global grad-norm clip + overflow check over host-sharded
        grads.  Each fsdp part is counted by exactly one process (its
        lowest-indexed holder) and the replicated resident leaves by
        process 0; the per-host partial sums meet in one tiny
        process_allgather.  Single-process: the numpy fast path."""
        clip = self.config.gradient_clipping
        if jax.process_count() == 1:
            _, norm, overflow = host_unscale_clip_and_check(grad_flat, 1.0, clip)
            return norm, overflow
        me = jax.process_index()
        plo, phi = self._part_local
        olo, ohi = self._part_owned
        sq, overflow = 0.0, False
        for (kind, d), g in zip(self._flat_leaf_kinds, grad_flat):
            if not np.all(np.isfinite(g)):
                overflow = True
            if kind == "resident" or d is None:
                if me == 0:
                    sq += float(np.sum(np.square(g, dtype=np.float64)))
            elif ohi > olo:
                per = g.shape[d] // (phi - plo)
                sl = [slice(None)] * g.ndim
                sl[d] = slice((olo - plo) * per, (ohi - plo) * per)
                sq += float(np.sum(np.square(g[tuple(sl)], dtype=np.float64)))
        from deepspeed_tpu.comm.collectives import host_allgather

        vec = np.asarray(
            host_allgather(np.asarray([sq, 1.0 if overflow else 0.0], np.float32))
        ).reshape(jax.process_count(), 2)
        norm = float(np.sqrt(vec[:, 0].sum()))
        overflow = bool(vec[:, 1].max() > 0)
        if clip > 0.0 and np.isfinite(norm) and norm > clip:
            factor = clip / (norm + 1e-6)
            for g in grad_flat:
                g *= factor
        return norm, overflow

    def _group_slice_host(self, g: int) -> Any:
        lo = g * self.group_layers
        return jax.tree.map(lambda a: a[lo : lo + self.group_layers], self._blocks_host)

    def _group_key(self, g: int) -> str:
        return f"group{g:04d}"

    @property
    def _stage_np_dtype(self):
        """NVMe staging dtype — the COMPUTE dtype, so a pure-fp32 config
        stages fp32 (no silent truncation to bf16)."""
        import ml_dtypes

        return ml_dtypes.bfloat16 if self.compute_dtype == jnp.bfloat16 else np.float32

    def _issue_group_swap_out(self, g: int) -> None:
        """Start the async NVMe write of group ``g``'s compute-dtype
        params (sourced from the just-updated master rows).  The write
        rides the swapper's dedicated write handle; a next-step read of
        the same group synchronizes it first (read-after-write hazard
        handled inside AsyncTensorSwapper)."""
        dt = self._stage_np_dtype
        flat = np.concatenate([
            np.asarray(l, dt).view(np.uint8).reshape(-1)
            for l in jax.tree.leaves(self._group_slice_host(g))
        ])
        self._param_swapper.swap_out(self._group_key(g), flat, async_op=True)

    def _swap_out_all_groups(self) -> None:
        """Write every group's compute-dtype params to NVMe and wait
        (init and checkpoint-load; the per-step path issues groups
        incrementally from the optimizer-step hook instead)."""
        for g in range(self.n_groups):
            self._issue_group_swap_out(g)
        self._param_swapper.synchronize_writes()

    def _upload_group(self, g: int) -> Any:
        """compute-dtype group params → device (from NVMe when staged)."""
        return self._finish_upload(g, self._issue_swap_in(g))

    def _issue_swap_in(self, g) -> Optional[np.ndarray]:
        """Start the async NVMe read of group ``g``'s staged bytes.
        Returns the in-flight host buffer (valid after the next
        ``_finish_upload``), or None when params live in host memory
        (no disk hop to hide — device_put happens at finish time).

        One read is kept in flight at a time: ``synchronize()`` waits on
        ALL pending aio ops, so issuing deeper would make finishing
        group g also wait for g+2's bytes."""
        if g is None or not (0 <= g < self.n_groups) or self._param_swapper is None:
            return None
        return self._param_swapper.swap_in(self._group_key(g), async_op=True)

    def _finish_upload(self, g: int, flat: Optional[np.ndarray]) -> Any:
        """Complete group ``g``'s upload: wait for its NVMe bytes (if
        staged) and hand them to the device (device_put is async — the
        H2D copy itself overlaps with whatever compute is in flight)."""
        host = self._group_slice_host(g)
        if self._param_swapper is None:
            return self._put_group(host)
        if flat is None:
            flat = self._param_swapper.swap_in(self._group_key(g), async_op=True)
        # wait for THIS read only — other groups' write-backs keep
        # overlapping this group's upload + compute
        self._param_swapper.synchronize_reads()
        dt = self._stage_np_dtype
        itemsize = np.dtype(dt).itemsize
        leaves, treedef = jax.tree.flatten(host)
        out, off = [], 0
        for l in leaves:
            nb = l.size * itemsize
            out.append(flat[off : off + nb].view(dt).reshape(l.shape))
            off += nb
        return self._put_group(jax.tree.unflatten(treedef, out))

    def _put_group(self, host_tree) -> Any:
        """One group's compute-dtype params → device, each device
        receiving only its 1/P fsdp slice.  Multi-host, the global array
        is assembled from each process's LOCAL 1/H master slice
        (``make_array_from_process_local_data``) — no host ever
        materializes a full group.  Casting happens on HOST (ml_dtypes);
        staging a full group on one device first would transiently break
        the per-device HBM bound the fsdp composition provides."""
        dt = self._stage_np_dtype
        if jax.process_count() == 1:
            return jax.device_put(
                jax.tree.map(lambda a: np.asarray(a, dt), host_tree),
                self._group_shardings,
            )
        out = [
            jax.make_array_from_process_local_data(sh, np.asarray(a, dt), tuple(gs))
            for a, sh, gs in zip(
                jax.tree.leaves(host_tree),
                jax.tree.leaves(self._group_shardings),
                self._group_gshapes,
            )
        ]
        return jax.tree.unflatten(self._blocks_tdef, out)

    @staticmethod
    def _start_host_copy(tree) -> None:
        """Kick off the D2H transfer of every leaf (best effort — not
        every backend exposes copy_to_host_async)."""
        for leaf in jax.tree.leaves(tree):
            try:
                leaf.copy_to_host_async()
            except Exception:
                return

    def _upload_resident(self) -> Any:
        from deepspeed_tpu.sharding.layout import replicated_sharding

        # explicit replicated sharding: under multi-process execution
        # every host holds identical resident params and device_put
        # places each process's addressable shards (a bare device_put
        # would commit to one local device and break the global mesh)
        return jax.device_put(
            jax.tree.map(lambda a: jnp.asarray(a, self.compute_dtype), self._resident_host),
            replicated_sharding(self.mesh),
        )

    # ------------------------------------------------------------------
    # compiled stage programs (shapes identical across groups — one
    # compile each, reused G times per step)
    # ------------------------------------------------------------------
    def _programs(self):
        if self._compiled:
            return self._compiled
        spec = self.spec

        def embed(res, tokens):
            return spec.embed(res, tokens)

        def group_fwd(gp, x, rngs):
            return spec.group(gp, x, rngs, spec.deterministic)

        def head(res, x, batch):
            def f(res_, x_):
                return spec.head_loss(res_, x_, batch)

            loss, vjp = jax.vjp(f, res, x)
            d_res, dx = vjp(jnp.float32(1.0).astype(loss.dtype))
            return loss, d_res, dx

        def group_bwd(gp, x, rngs, dy):
            def f(gp_, x_):
                return spec.group(gp_, x_, rngs, spec.deterministic)

            _, vjp = jax.vjp(f, gp, x)
            dgp, dx = vjp(dy)
            return dgp, dx

        def embed_bwd(res, tokens, dx0):
            def f(res_):
                return spec.embed(res_, tokens)

            _, vjp = jax.vjp(f, res)
            (d_res,) = vjp(dx0)
            return d_res

        # eval variants: deterministic blocks (dropout OFF regardless of
        # training mode) and a forward-only head (no logits-cotangent)
        def group_eval(gp, x, rngs):
            return spec.group(gp, x, rngs, True)

        def head_eval(res, x, batch):
            return spec.head_loss(res, x, batch)

        from deepspeed_tpu.parallel.sequence import scoped_to

        mesh = self.mesh  # ambient mesh for traces
        self._compiled = {
            "embed": jax.jit(scoped_to(mesh, embed)),
            "group_fwd": jax.jit(scoped_to(mesh, group_fwd)),
            "head": jax.jit(scoped_to(mesh, head)),
            # group grads leave in the groups' own 1/P fsdp layout —
            # GSPMD lowers the grad reduction to a reduce-scatter over
            # fsdp (+ psum over data) instead of a full allreduce
            "group_bwd": jax.jit(
                scoped_to(mesh, group_bwd), donate_argnums=(3,),
                out_shardings=(self._group_shardings, self._batch_sh),
            ),
            "embed_bwd": jax.jit(scoped_to(mesh, embed_bwd), donate_argnums=(2,)),
            "group_eval": jax.jit(scoped_to(mesh, group_eval)),
            "head_eval": jax.jit(scoped_to(mesh, head_eval)),
        }
        return self._compiled

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------
    def _layer_rngs(self, step: int, micro: int):
        key = jax.random.fold_in(jax.random.PRNGKey(self.config.seed), step * 1000 + micro)
        return jax.random.split(key, self.spec.n_layer).reshape(self.n_groups, self.group_layers, 2)

    def train_batch(self, batch: Any, timing: Optional[dict] = None) -> jnp.ndarray:
        """One training step.  ``timing``: pass a dict to run this step
        SERIALIZED (block_until_ready after every phase) and receive a
        wall-clock decomposition — upload_s (host→device incl. NVMe
        read waits), fwd_s / bwd_s (chip compute), drain_s (device→host
        grad pulls), opt_s (host Adam + NVMe write issuance).  The
        serialized step is slower than a normal pipelined step (the
        overlaps are deliberately removed so each phase is attributable);
        use normal steps for throughput numbers."""
        import time as _time

        progs = self._programs()
        gas = self.config.gradient_accumulation_steps
        mb = self.config.train_micro_batch_size_per_gpu * self.mesh_info.dp_world_size
        batch = {k: np.asarray(v) for k, v in batch.items()}
        n_rows = next(iter(batch.values())).shape[0]
        if n_rows != mb * gas:
            raise ValueError(f"batch rows {n_rows} != micro_bs*dp*gas {mb * gas}")

        if timing is not None:
            timing.update({k: 0.0 for k in ("upload_s", "fwd_s", "bwd_s", "drain_s", "opt_s")})

        def _phase(key, fn, *a, **kw):
            if timing is None:
                return fn(*a, **kw)
            t0 = _time.time()
            out = fn(*a, **kw)
            jax.block_until_ready(out)
            timing[key] += _time.time() - t0
            return out

        res_dev = _phase("upload_s", self._upload_resident)
        grad_acc: Optional[List[np.ndarray]] = None
        losses = []
        for micro in range(gas):
            rows = slice(micro * mb, (micro + 1) * mb)
            mbatch = {
                k: jax.device_put(v[rows], self._batch_sh) for k, v in batch.items()
            }
            rngs = self._layer_rngs(self.global_steps, micro)
            tokens = mbatch["input_ids"]

            # ---- forward sweep: keep only the group BOUNDARY activations.
            # Pipeline: finish group g's upload, immediately issue the
            # NVMe read for g+1, then dispatch g's compute — the next
            # read and H2D ride under the current group's compute.
            xs = [_phase("fwd_s", progs["embed"], res_dev, tokens)]
            inflight = self._issue_swap_in(0)
            for g in range(self.n_groups):
                g_dev = _phase("upload_s", self._finish_upload, g, inflight)
                inflight = self._issue_swap_in(g + 1) if g + 1 < self.n_groups else None
                xs.append(_phase("fwd_s", progs["group_fwd"], g_dev, xs[-1], rngs[g]))

            loss, d_res, dx = _phase("fwd_s", progs["head"], res_dev, xs[-1], mbatch)
            losses.append(loss)

            # ---- backward sweep: re-upload groups in reverse, vjp each.
            # Group grads drain to host one group behind compute (async
            # D2H started at dispatch, converted next iteration), so HBM
            # holds at most TWO groups' grads — never the model's.
            micro_grads: List[Any] = [None] * self.n_groups
            inflight = self._issue_swap_in(self.n_groups - 1)
            pend_g, pend_dgp = None, None
            _drain = self._drain_group

            for g in range(self.n_groups - 1, -1, -1):
                g_dev = _phase("upload_s", self._finish_upload, g, inflight)
                inflight = self._issue_swap_in(g - 1) if g > 0 else None
                dgp, dx = _phase("bwd_s", progs["group_bwd"], g_dev, xs[g], rngs[g], dx)
                self._start_host_copy(dgp)
                if pend_g is not None:
                    micro_grads[pend_g] = _phase("drain_s", _drain, pend_dgp)
                pend_g, pend_dgp = g, dgp
            # dispatch the embed backward BEFORE draining the last
            # group's grads — the host-side conversion below blocks on
            # D2H and would otherwise idle the device
            d_res_embed = _phase("bwd_s", progs["embed_bwd"], res_dev, tokens, dx)
            if pend_g is not None:
                micro_grads[pend_g] = _phase("drain_s", _drain, pend_dgp)
            pend_dgp = None

            # ---- host grad accumulation (resident grads sum embed+head)
            d_res_total = _phase(
                "drain_s",
                lambda: jax.tree.map(
                    lambda a, b: np.asarray(a, np.float32) + np.asarray(b, np.float32),
                    jax.device_get(d_res), jax.device_get(d_res_embed),
                ),
            )
            blocks_grads = jax.tree.map(
                lambda *gs: np.concatenate([np.asarray(g, np.float32) for g in gs], axis=0),
                *micro_grads,
            )
            full = dict(d_res_total)
            full[self.spec.blocks_key] = blocks_grads
            flat = [np.asarray(l, np.float32) for l in jax.tree.leaves(full)]
            if grad_acc is None:
                grad_acc = flat
            else:
                for a, g_ in zip(grad_acc, flat):
                    a += g_

        for a in grad_acc:
            a /= gas
        grad_norm, overflow = self._clip_and_check_global(grad_acc)
        lr = float(self.lr_schedule(self.global_steps))
        if not overflow:
            grads_tree = jax.tree.unflatten(self._treedef, grad_acc)
            # NVMe path: step the stacked blocks group-major and start
            # each group's write-back the moment its master rows land —
            # the writes overlap the remaining groups' CPU Adam and the
            # next step's forward uploads instead of serializing at the
            # step boundary (was: _swap_out_all_groups + global wait,
            # ~model-size synchronous writes per step)
            swap = self._param_swapper is not None
            gl = self.group_layers
            masters = _phase(
                "opt_s",
                lambda: self._host_opt.step(
                    grads_tree, lr, self.global_steps + 1,
                    row_groups=[(g * gl, (g + 1) * gl) for g in range(self.n_groups)] if swap else None,
                    row_group_prefix=f"{self.spec.blocks_key}/" if swap else "",
                    on_group=self._issue_group_swap_out if swap else None,
                ),
            )
            self._params_host = masters
            self._blocks_host = masters[self.spec.blocks_key]
            self._resident_host = {k: v for k, v in masters.items() if k != self.spec.blocks_key}
            self.global_steps += 1
        else:
            self.skipped_steps += 1
            logger.warning("offload_param step skipped on non-finite grads")
        self._last_info = {"lr": lr, "grad_norm": grad_norm, "overflow": overflow}
        # telemetry (docs/telemetry.md): the streaming engine has no
        # StepTimeline — publish its step counters/gauges directly
        from deepspeed_tpu.telemetry import get_registry

        reg = get_registry()
        if reg.enabled:
            reg.counter("zinf/steps", engine="offload").inc()
            reg.gauge("zinf/lr", engine="offload").set(lr)
            if overflow:
                reg.counter("zinf/overflow_skips", engine="offload").inc()
            if timing is not None:
                for key, v in timing.items():
                    reg.gauge(f"zinf/{key}", engine="offload").set(v)
        return jnp.mean(jnp.stack(losses))

    def eval_batch(self, batch: Any) -> jnp.ndarray:
        progs = self._programs()
        batch = {k: jax.device_put(np.asarray(v), self._batch_sh) for k, v in batch.items()}
        res_dev = self._upload_resident()
        x = progs["embed"](res_dev, batch["input_ids"])
        rngs = self._layer_rngs(0, 0)
        inflight = self._issue_swap_in(0)
        for g in range(self.n_groups):
            g_dev = self._finish_upload(g, inflight)
            inflight = self._issue_swap_in(g + 1) if g + 1 < self.n_groups else None
            x = progs["group_eval"](g_dev, x, rngs[g])
        return progs["head_eval"](res_dev, x, batch)

    # ------------------------------------------------------------------
    # checkpointing (host masters are the source of truth)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None, client_state: Optional[dict] = None, save_latest: bool = True):
        tag = tag or f"global_step{self.global_steps}"
        path = os.path.join(os.path.abspath(save_dir), str(tag))
        os.makedirs(path, exist_ok=True)
        # Each process writes its OWN file — its full masters when
        # replicated, its 1/H fsdp slice when multi-host-sharded — so
        # per-host local disks work (no shared-FS assumption) and ranks
        # never race on one filename.  The barrier between phases is a
        # flag ALLGATHER, not sync_global_devices: every rank reaches it
        # even after a local write failure, so a failing rank surfaces
        # as a raised error on ALL ranks instead of a deadlock.
        def _sync_ok(ok: bool, what: str, cause=None) -> None:
            if jax.process_count() > 1:
                from deepspeed_tpu.comm.collectives import host_allgather

                flags = np.asarray(
                    host_allgather(np.float32(0.0 if ok else 1.0))
                ).reshape(-1)
                if flags.max() > 0:
                    raise RuntimeError(
                        f"checkpoint {what} write failed on rank(s) "
                        f"{np.nonzero(flags)[0].tolist()}"
                    ) from cause
            elif not ok:
                raise RuntimeError(f"checkpoint {what} write failed") from cause

        err = None
        try:
            self._host_opt.save(
                os.path.join(path, f"host_optimizer_rank{jax.process_index()}.npz")
            )
        except Exception as e:  # noqa: BLE001 — must still reach the barrier
            err = e
        _sync_ok(err is None, "optimizer-state", err)
        meta_err = None
        if jax.process_index() == 0:
            # rank 0 writes meta + the latest tag only after all opt
            # files are durable; everyone leaves only once those exist
            try:
                meta = {
                    "tag": str(tag), "global_step": self.global_steps,
                    "skipped_steps": self.skipped_steps, "client_state": client_state or {},
                    "engine": "zero_infinity_param_offload",
                    "process_count": jax.process_count(),
                    "masters_sharded": self._masters_sharded,
                }
                from deepspeed_tpu.resilience.atomic import atomic_write_text

                atomic_write_text(os.path.join(path, "meta.json"), json.dumps(meta, indent=2))
                if save_latest:
                    atomic_write_text(os.path.join(os.path.abspath(save_dir), "latest"), str(tag))
            except Exception as e:  # noqa: BLE001
                meta_err = e
        _sync_ok(meta_err is None, "meta/latest", meta_err)
        log_dist(f"saved ZeRO-Infinity checkpoint {path}")
        return path

    def _reassemble_host_state(self, path: str, meta: dict):
        """Reassemble the FULL host masters/moments from every saved
        rank's npz and re-slice for THIS engine's fsdp partition — the
        "resharding-compatible" topology relaxation: a sharded-master
        checkpoint restores at any process count, as long as all of the
        saving job's per-rank files are reachable (shared filesystem).
        Returns None when some rank file is missing (the caller raises
        the strict topology error then).

        Assumes the saving mesh gave each rank a contiguous, ascending
        fsdp range (the only layout ``_setup_host_partition`` accepts),
        so rank-order concatenation along each leaf's sharded dim
        recovers the full axis."""
        S = int(meta.get("process_count", 1))
        saved_sharded = bool(meta.get("masters_sharded", False))
        # replicated-masters saves: every rank file holds the SAME full
        # state, so rank 0's alone suffices (and avoids loading S
        # identical copies into host RAM)
        need = S if (saved_sharded and S > 1) else 1
        files = [os.path.join(path, f"host_optimizer_rank{r}.npz") for r in range(need)]
        if not all(os.path.exists(f) for f in files):
            return None
        datas = []
        for f in files:
            with np.load(f) as z:
                datas.append({k.replace("::", "/"): z[k] for k in z.files})
        plo, phi = self._part_local
        P = self.mesh_info.fsdp_world_size
        # _flat_leaf_kinds is aligned with the host optimizer's flat key
        # order (both come from _flatten_with_paths of the same tree)
        kinds = dict(zip(self._host_opt.keys, self._flat_leaf_kinds))
        out = {}
        for k in self._host_opt.keys:
            kind, d = kinds[k]
            for pfx in ("master", "m", "v"):
                key = f"{pfx}/{k}"
                if kind != "block" or d is None or not saved_sharded or S == 1:
                    full = datas[0][key]
                else:
                    full = np.concatenate([dd[key] for dd in datas], axis=d)
                if kind == "block" and d is not None and self._masters_sharded:
                    if full.shape[d] % P:
                        raise ValueError(
                            f"resharding-compatible restore: leaf '{k}' dim {d} "
                            f"({full.shape[d]}) is not divisible by fsdp={P}"
                        )
                    per = full.shape[d] // P
                    sl = [slice(None)] * full.ndim
                    sl[d] = slice(plo * per, phi * per)
                    full = np.ascontiguousarray(full[tuple(sl)])
                out[key] = full
        return out

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None, **_kw):
        load_dir = os.path.abspath(load_dir)
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.exists(latest):
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        path = os.path.join(load_dir, str(tag))
        # topology validation BEFORE any state is replaced: loading a
        # mismatched slice layout would corrupt the masters and only
        # raise afterwards (review finding r5)
        meta = {}
        meta_path = os.path.join(path, "meta.json")
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)
        topo_mismatch = "masters_sharded" in meta and (
            bool(meta["masters_sharded"]) != self._masters_sharded
            or (self._masters_sharded and int(meta.get("process_count", 1)) != jax.process_count())
        )
        if topo_mismatch:
            # resharding-compatible (not identical) topology contract:
            # with every saved rank's file present, reassemble the full
            # masters and re-slice for this engine's partition
            data = self._reassemble_host_state(path, meta)
            if data is None:
                raise ValueError(
                    f"ZeRO-Infinity checkpoint {path} was saved with "
                    f"masters_sharded={meta['masters_sharded']} over "
                    f"{meta.get('process_count', 1)} processes; this engine has "
                    f"masters_sharded={self._masters_sharded} over "
                    f"{jax.process_count()} — and not all "
                    f"{meta.get('process_count', 1)} per-rank files are reachable, "
                    "so the fsdp axis cannot be resharded. Restore with a "
                    "matching topology or from a shared filesystem."
                )
            log_dist(
                f"ZeRO-Infinity: resharding host masters from "
                f"{meta.get('process_count', 1)} saved rank file(s) to this "
                f"topology (fsdp parts [{self._part_local[0]}, {self._part_local[1]}))"
            )
            self._host_opt.load_state_dict(data)
        else:
            # prefer this process's own file (per-host local disks); the
            # rank-0 file is equivalent on a shared filesystem ONLY when
            # masters are replicated — a sharded-master checkpoint holds a
            # different 1/H slice per rank
            opt_path = os.path.join(path, f"host_optimizer_rank{jax.process_index()}.npz")
            if not os.path.exists(opt_path):
                if self._masters_sharded:
                    raise FileNotFoundError(
                        f"ZeRO-Infinity checkpoint {path} has no file for rank "
                        f"{jax.process_index()} and masters are host-sharded "
                        "(each rank's slice differs; the rank-0 file is not a "
                        "substitute). Restore with the same process topology."
                    )
                opt_path = os.path.join(path, "host_optimizer_rank0.npz")
            if not os.path.exists(opt_path):
                logger.warning(f"ZeRO-Infinity checkpoint {path} not found")
                return None, {}
            self._host_opt.load(opt_path)
        masters = self._host_opt.masters_tree()
        self._params_host = masters
        self._blocks_host = masters[self.spec.blocks_key]
        self._resident_host = {k: v for k, v in masters.items() if k != self.spec.blocks_key}
        if self._param_swapper is not None:
            self._swap_out_all_groups()
        self.global_steps = int(meta.get("global_step", 0))
        self.skipped_steps = int(meta.get("skipped_steps", 0))
        log_dist(f"loaded ZeRO-Infinity checkpoint {path} (global_step={self.global_steps})")
        return path, meta.get("client_state", {})

    # -- API-compat shims ----------------------------------------------
    @property
    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    @property
    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def get_lr(self):
        return [float(self.lr_schedule(self.global_steps))]
