"""Inference engine.

TPU-native re-design of the reference ``InferenceEngine``
(``inference/engine.py:19``): builds the model-parallel mesh (:88), loads
checkpoints (:150), converts dtype (:175), applies the injection policy
(:135) and wraps forward (:204).  Differences, by design:

* **MP group → mesh axis.**  ``mp_size`` becomes the size of the
  ``model`` axis of a ``jax.sharding.Mesh``; weights are ``device_put``
  with Megatron-style PartitionSpecs and GSPMD inserts the collectives
  the reference's fused kernels issue manually.
* **Kernel injection → pytree transform.**  A policy
  (``inference/injection.py``) maps HF/Megatron weights into the stacked
  fused-block layout; the whole network then runs the KV-cache path in
  ``ops/transformer/inference.py`` — there is no module tree to mutate.
* **Checkpoint resize for free.**  The sharded checkpoint format reshards
  on load (orbax/tensorstore), subsuming ``MegatronSDLoader.merge/split``
  (``state_dict_factory.py:199``).
* ``generate()`` is a compiled prefill + ``lax.scan`` decode loop with a
  static-capacity KV cache (greedy, temperature, and top-k sampling).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.analysis.shard import hooks as shard_hooks
from deepspeed_tpu.comm.mesh import MESH_AXES, MeshInfo
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.utils.logging import log_dist, logger

# Host→device staging is chunked so the transient flat buffer never adds
# more than this many bytes of HBM on top of the parameters themselves
# (an XL-class model staged as ONE flat buffer peaks at ~2x its size).
_STAGE_CHUNK_BYTES = 256 << 20


def sample_logits(logits32, r, do_sample: bool, temperature: float, top_k: int):
    """The generation sampling head (STATIC params — compiled into each
    ``generate()`` signature).  ``logits32`` (..., V) float32; greedy
    when ``do_sample`` is False (note ``x / 1.0`` is bit-exact, so the
    default ``temperature=1.0`` greedy path equals a bare argmax)."""
    logits32 = logits32 / jnp.maximum(temperature, 1e-6)
    if not do_sample:
        return jnp.argmax(logits32, axis=-1).astype(jnp.int32)
    if top_k > 0:
        # k > V degenerates to no filtering; lax.top_k requires k <= V
        top_k = min(top_k, logits32.shape[-1])
        kth = jax.lax.top_k(logits32, top_k)[0][..., -1:]
        logits32 = jnp.where(logits32 < kth, -jnp.inf, logits32)
    return jax.random.categorical(r, logits32, axis=-1).astype(jnp.int32)


def sample_logits_pooled(logits32, keys, sample_flag, temperature, top_k, max_top_k: int):
    """:func:`sample_logits` for a slot pool: per-row TRACED sampling
    params (serving's per-request temperature/top-k/seed ride the fixed
    decode signature — one executable for any greedy/sampled mix).

    ``logits32`` (S, V); ``keys`` (S,) PRNG keys; ``sample_flag`` (S,)
    bool; ``temperature`` (S,) f32; ``top_k`` (S,) i32 (0 = no top-k
    filter).  Rows with ``sample_flag`` False take the bare argmax —
    bit-identical to ``sample_logits(do_sample=False, temperature=1.0)``,
    the serving ⇄ solo-``generate()`` greedy parity contract.  Traced
    per-row k thresholds against the STATIC top-``max_top_k`` head
    (``jax.lax.top_k`` needs a static k; requests with
    ``top_k > max_top_k`` are rejected at submit)."""
    greedy = jnp.argmax(logits32, axis=-1).astype(jnp.int32)
    lg = logits32 / jnp.maximum(temperature[:, None], 1e-6)
    # lax.top_k requires k <= V: a vocab narrower than max_top_k clamps
    # the static head (per-row k >= V then keeps every logit — the same
    # no-filter semantics, and greedy-only pools stay V-agnostic)
    head_k = min(max_top_k, logits32.shape[-1])
    head = jax.lax.top_k(lg, head_k)[0]  # (S, head_k), sorted desc
    kth = jnp.take_along_axis(
        head, jnp.clip(top_k - 1, 0, head_k - 1)[:, None], axis=-1
    )
    lg = jnp.where((top_k[:, None] > 0) & (lg < kth), -jnp.inf, lg)
    sampled = jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)
    return jnp.where(sample_flag, sampled, greedy)


@functools.partial(jax.jit, static_argnums=1)
def _split_flat(buf, shapes):
    """Split one flat staging buffer into per-leaf arrays on device.
    Module-level (static ``shapes``) so jit's in-process trace cache hits
    across engines.  No donation: XLA cannot alias one flat buffer into
    many reshaped outputs (it would just warn per call) — the HBM peak
    is bounded by _STAGE_CHUNK_BYTES chunking, not aliasing."""
    outs, off = [], 0
    for shp in shapes:
        # static `shapes` (static_argnums=1): host int math, not a sync
        n = int(np.prod(shp)) if shp else 1  # ds-lint: disable=host-sync-in-jit
        outs.append(jax.lax.dynamic_slice(buf, (off,), (n,)).reshape(shp))
        off += n
    return outs


class InferenceEngine:
    def __init__(
        self,
        model: Any = None,
        mp_size: int = 1,
        dtype: Any = None,
        checkpoint: Optional[str] = None,
        checkpoint_tag: Optional[str] = None,
        injection_policy: Optional[type] = None,
        replace_with_kernel_inject: bool = True,
        max_out_tokens: int = 1024,
        mesh=None,
        model_config: Any = None,
        params: Any = None,
        quantize_bits: int = 0,
        quantize_groups: int = 1,
        kv_cache_dtype: str = "model",
        seed: int = 0,
        init_on_device: bool = False,
        kernels: Any = None,
        donate_params: bool = False,
        **kwargs,
    ):
        """``model`` may be:

        * a HF/torch module or plain state dict — converted through an
          injection policy (``replace_with_kernel_inject`` path);
        * a preset name (``"gpt2"``, ``"bert-base"``, ...);
        * ``None`` with explicit ``model_config`` + ``params``.

        ``donate_params``: the caller hands a device-resident ``params``
        tree over — it is donated to the cast/reshard, so a tree already
        in the engine's dtype and placement is taken as it is instead of
        being copied (a model that fills most of the chip cannot be held
        twice).  The caller's arrays are invalid afterwards.
        """
        self.mp_world_size = int(mp_size)
        self.dtype = dtype if dtype is not None else jnp.bfloat16
        self.max_out_tokens = int(max_out_tokens)
        if self.max_out_tokens < 1:
            raise ValueError(
                f"max_out_tokens must be >= 1 (it bounds prompt+generated "
                f"length and sizes the KV cache), got {self.max_out_tokens}"
            )
        # "model" -> cache in self.dtype; "int8" -> quantized cache (the
        # cache read rivals the weight read at long contexts; int8
        # halves that roofline term — see ops/transformer/inference)
        if kv_cache_dtype not in ("model", "int8"):
            raise ValueError(f"kv_cache_dtype must be 'model' or 'int8', got {kv_cache_dtype!r}")
        self.kv_cache_dtype = kv_cache_dtype
        self._kv_dtype = "int8" if kv_cache_dtype == "int8" else self.dtype
        self._compiled: Dict[Any, Callable] = {}

        # Pallas kernel suite (docs/kernels.md): `kernels` may be a
        # KernelsConfig, a raw `kernels` config dict, or None (keep the
        # process state — DS_KERNELS env still wins inside the dispatch)
        if kernels is not None:
            from deepspeed_tpu.config.config import KernelsConfig
            from deepspeed_tpu.ops import kernels as _kernels_mod

            if isinstance(kernels, dict):
                kernels = KernelsConfig.from_dict(kernels)
            _kernels_mod.configure_from_config(kernels)

        # -- resolve model family + params --------------------------------
        from deepspeed_tpu.models import bert as bert_mod
        from deepspeed_tpu.models import gpt2 as gpt2_mod

        if model is not None and isinstance(model, str):
            # GPT-2 presets win name collisions ("tiny"); use "bert-*"
            # names for the BERT family.
            if model in gpt2_mod.PRESETS:
                self.model_config = gpt2_mod.PRESETS[model]
            elif model in bert_mod.PRESETS or model.replace("bert-", "") in bert_mod.PRESETS:
                self.model_config = bert_mod.PRESETS.get(model) or bert_mod.PRESETS[model.replace("bert-", "")]
            else:
                raise ValueError(f"unknown model preset '{model}'")
        elif model is not None and (hasattr(model, "state_dict") or isinstance(model, dict)):
            if not replace_with_kernel_inject and injection_policy is None:
                raise ValueError("torch/state-dict models require kernel injection (replace_with_kernel_inject)")
            from deepspeed_tpu.inference.injection import replace_transformer_layer

            self.model_config, params = replace_transformer_layer(model, policy=injection_policy)
        elif model_config is not None:
            self.model_config = model_config
        else:
            raise ValueError("init_inference needs `model` (module/state_dict/preset) or model_config=")

        # the family seam (docs/serving.md §Model families): the config's
        # class names its model module, which supplies the parameter
        # tree, names its partition-rule table and — a causal family —
        # supplies its cache kind and its serving forward.  Duck-typed
        # configs outside the built-in MROs keep falling back to BERT's
        # encoder layout and rule table, here and nowhere else.
        from deepspeed_tpu.models import family_of

        family = family_of(self.model_config)
        self._family = family or bert_mod
        self._is_gpt = self._family is gpt2_mod  # the GPT-2 parameter layout (generate(), the slot cache)
        self._causal = bool(getattr(self._family, "CAUSAL_LM", False))
        # partition-rule engine: the family table every param layout
        # resolves through (sharding/rules.py; packed-int8 aware)
        from deepspeed_tpu.sharding.rules import rules_for_config, rules_for_family

        self._rules = rules_for_config(self.model_config) if family is not None else rules_for_family("bert")
        # disable remat for inference (no backward to save memory for)
        if getattr(self.model_config, "remat", False):
            self.model_config = dataclasses.replace(self.model_config, remat=False)

        # -- mesh ----------------------------------------------------------
        if mesh is None:
            from deepspeed_tpu.comm.mesh import make_mesh

            n_dev = len(jax.devices())
            if n_dev % self.mp_world_size:
                raise ValueError(f"mp_size={self.mp_world_size} does not divide {n_dev} devices")
            mesh = make_mesh(MeshConfig(model=self.mp_world_size, data=n_dev // self.mp_world_size, fsdp=1))
        self.mesh = mesh
        self.mesh_info = MeshInfo.from_mesh(mesh)

        # -- checkpoint / dtype / shard ------------------------------------
        if checkpoint is not None:
            # a random init would only serve as a shape template here, so
            # skip it — the restore target comes from checkpoint metadata
            params = self._load_checkpoint_params(checkpoint, checkpoint_tag, params)
        owns_params = bool(donate_params) and params is not None  # only engine-created or handed-over trees may be donated
        if params is None:
            if init_on_device and getattr(self.model_config, "n_experts", 0) == 0:
                # generate the random init ON the chip: host generation of
                # an XL-class model is minutes of numpy plus a multi-GB
                # upload, on-chip generation is seconds
                params = self._family.init_params_device(self.model_config, seed=seed, dtype=self.dtype)
            else:
                params = self._family.init_params(self.model_config, seed=seed)
            owns_params = True
        self._packed_int8 = False
        if quantize_bits:
            if quantize_bits == 8 and self._is_gpt:
                # true int8 serving: weights stay int8 in HBM and matmuls
                # run as (x @ q) * s in the fused decode path
                from deepspeed_tpu.runtime.weight_quantizer import pack_int8_tree

                params = pack_int8_tree(params, donate=owns_params, mesh=self.mesh)
                owns_params = True  # pack outputs are fresh arrays
                self._packed_int8 = True
            else:
                from deepspeed_tpu.runtime.weight_quantizer import WeightQuantization

                params = WeightQuantization(bits=quantize_bits, groups=quantize_groups).quantize_dequantize_tree(params)
        self.params = self._shard_params(params, owned=owns_params)
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.params))
        log_dist(
            f"inference engine: {type(self.model_config).__name__} params={n_params/1e6:.1f}M "
            f"mp={self.mp_world_size} dtype={jnp.dtype(self.dtype).name}"
        )

    # ----------------------------------------------------------------------
    @property
    def module(self):
        """Reference parity: the 'injected model' is (config, params)."""
        return (self.model_config, self.params)

    @property
    def generation_capacity(self) -> int:
        """Hard bound on prompt + generated length: ``max_out_tokens``
        clamped by the model's positional table — the number every
        length check (generate, init_cache, serving admission) derives
        from."""
        if self._causal:
            return min(self.max_out_tokens, self.model_config.n_positions)
        return self.max_out_tokens

    def _tp_spec(self, path: str, shape) -> P:
        if self.mp_world_size <= 1:
            return P()
        # partition-rule engine resolution: the family rule table
        # normalizes packed-int8 paths itself (.../<name>_w/q carries
        # the weight spec; .../<name>_w/s drops the contracted dim)
        spec = self._rules.spec(path, shape)
        return spec if spec is not None else P()

    def _shard_params(self, params, owned: bool = False):
        # int8 payloads must stay int8; scales stay f32.  Cast on HOST
        # (ml_dtypes handles bf16) so no full-precision staging copy
        # ever lands in HBM — device_put of fp32 then casting on-device
        # doubles transfer and OOMs XL-class models.
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        pstrs = ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in flat]
        def _target_dtype(pstr, leaf):
            if np.dtype(getattr(leaf, "dtype", np.float32)) == np.int8:
                return np.int8
            return np.float32 if pstr.endswith("/s") else self.dtype

        tgt_dtypes = [_target_dtype(pstr, leaf) for pstr, (_, leaf) in zip(pstrs, flat)]
        shardings = [
            NamedSharding(self.mesh, self._tp_spec(pstr, np.shape(leaf)))
            for pstr, (_, leaf) in zip(pstrs, flat)
        ]

        if all(isinstance(leaf, jax.Array) for _, leaf in flat):
            # params already device-resident (init_params_device /
            # pack_int8_tree on device): no host staging at all — one
            # jitted cast, resharded by out_shardings.  Donation only
            # when the engine created the tree — a CALLER-provided tree
            # must stay valid after init.
            dtypes = tuple(jnp.dtype(d) for d in tgt_dtypes)

            def cast_all(leaves):
                return [l.astype(d) for l, d in zip(leaves, dtypes)]

            placed = jax.jit(
                cast_all, donate_argnums=0 if owned else (), out_shardings=shardings
            )([leaf for _, leaf in flat])
            return jax.tree_util.tree_unflatten(treedef, list(placed))

        arrays = [np.asarray(leaf).astype(dt, copy=False) for (_, leaf), dt in zip(flat, tgt_dtypes)]
        if self.mp_world_size > 1:
            # TP: leaves carry different shardings — batched device_put
            placed = jax.device_put(arrays, shardings)
            return jax.tree_util.tree_unflatten(treedef, list(placed))
        # mp=1: every transfer pays a fixed host->device dispatch, and an
        # XL-class tree has ~600-1200 leaves.
        # Upload flat staging buffers (grouped by dtype, capped at
        # _STAGE_CHUNK_BYTES so peak HBM overhead stays bounded) and
        # split on device (_split_flat deliberately does NOT donate the
        # staging buffer — peak HBM is bounded by the chunk cap instead;
        # see its docstring).
        placed = [None] * len(arrays)
        by_dtype = {}
        for i, a in enumerate(arrays):
            by_dtype.setdefault(a.dtype, []).append(i)
        rep = NamedSharding(self.mesh, P())
        for dt, idxs in by_dtype.items():
            chunk, chunk_bytes = [], 0
            chunks = [chunk]
            for i in idxs:
                chunk.append(i)
                chunk_bytes += arrays[i].nbytes
                if chunk_bytes >= _STAGE_CHUNK_BYTES:
                    chunk, chunk_bytes = [], 0
                    chunks.append(chunk)
            for idx_chunk in chunks:
                if not idx_chunk:
                    continue
                buf = np.concatenate([arrays[i].reshape(-1) for i in idx_chunk])
                dev = jax.device_put(buf, rep)
                shapes = tuple(arrays[i].shape for i in idx_chunk)
                for i, part in zip(idx_chunk, _split_flat(dev, shapes)):
                    placed[i] = part
        return jax.tree_util.tree_unflatten(treedef, placed)

    def _load_checkpoint_params(self, checkpoint: str, tag: Optional[str], params):
        """Load params from a training checkpoint dir (orbax sharded
        format written by runtime/checkpointing.py); MP/DP layout of the
        writer is irrelevant — tensorstore reshards on read (the
        ``MegatronSDLoader`` merge/split analog)."""
        import orbax.checkpoint as ocp

        from deepspeed_tpu.runtime.checkpointing import LATEST_FILE

        checkpoint = os.path.abspath(checkpoint)
        state_dir = checkpoint
        if not os.path.isdir(os.path.join(state_dir, "state")):
            if tag is None:
                latest = os.path.join(checkpoint, LATEST_FILE)
                if not os.path.exists(latest):
                    raise FileNotFoundError(f"no '{LATEST_FILE}' in {checkpoint}")
                with open(latest) as f:
                    tag = f.read().strip()
            state_dir = os.path.join(checkpoint, str(tag))
        ckptr = ocp.PyTreeCheckpointer()
        state_path = os.path.join(state_dir, "state")
        if params is not None:
            target = {"params": jax.tree.map(lambda x: np.zeros(np.shape(x), np.float32), params)}
        else:
            # no template → build the restore target for the params
            # subtree from on-disk metadata (avoids materializing a full
            # random init just for its shapes)
            meta = ckptr.metadata(state_path)
            meta_params = (meta["params"] if isinstance(meta, dict) else meta.item_metadata.tree["params"])
            target = {
                "params": jax.tree.map(
                    lambda m: np.zeros(m.shape, np.float32), meta_params,
                    is_leaf=lambda m: hasattr(m, "shape"),
                )
            }
        try:
            restored = ckptr.restore(
                state_path, args=ocp.args.PyTreeRestore(item=target, partial_restore=True)
            )
        except TypeError:
            # older orbax has no partial_restore kwarg: read the whole
            # tree (host arrays, disk shapes) and keep the params subtree
            restored = ckptr.restore(state_path)
            restored = {
                "params": jax.tree.map(
                    lambda t, v: np.asarray(v, t.dtype), target["params"],
                    restored["params"],
                )
            }
        log_dist(f"inference: loaded params from {state_dir}")
        return restored["params"]

    # ----------------------------------------------------------------------
    # forward
    # ----------------------------------------------------------------------
    def _scoped(self, fn):
        """This engine's mesh becomes ambient for the trace (see
        parallel.sequence.scoped_to)."""
        from deepspeed_tpu.parallel.sequence import scoped_to

        return scoped_to(self.mesh, fn)

    def forward(self, input_ids, **kw):
        """Full-sequence forward: GPT → logits (B,T,V); BERT → encoder
        hidden states (BERT accepts token_type_ids/attention_mask
        kwargs)."""
        if self._causal and not self._is_gpt:
            raise NotImplementedError(
                f"{type(self.model_config).__name__} runs on a cache kind of its own: serve it "
                "through ServingEngine (docs/serving.md §Model families)"
            )
        if self._is_gpt and kw:
            raise TypeError(
                f"forward() got unexpected kwargs {sorted(kw)} for a GPT-family "
                "model (token_type_ids/attention_mask are BERT-only)"
            )
        input_ids = jnp.asarray(np.asarray(input_ids), jnp.int32)
        if self._is_gpt and input_ids.shape[1] > self.model_config.n_positions:
            # past n_positions the position lookup would clamp and return
            # garbage logits — raise with the derived numbers instead
            raise ValueError(
                f"forward() sequence length {input_ids.shape[1]} exceeds the "
                f"model's n_positions={self.model_config.n_positions}"
            )
        key = ("fwd", input_ids.shape, tuple(sorted(kw)))
        if key not in self._compiled:
            cfg = self.model_config
            if self._is_gpt and self._packed_int8:
                # packed weights are only understood by the fused
                # inference blocks — run the full sequence through the
                # cache path (pos=0 prefill over the whole input)
                from deepspeed_tpu.ops.transformer.inference import (
                    DeepSpeedInferenceConfig,
                    forward_with_cache,
                    init_kv_cache,
                )

                B, T = input_ids.shape
                icfg = DeepSpeedInferenceConfig(
                    hidden_size=cfg.n_embd, heads=cfg.n_head,
                    layer_norm_eps=cfg.layer_norm_epsilon, dtype=self.dtype,
                    max_out_tokens=T, use_flash_attention=cfg.use_flash_attention,
                )

                def fn(p, ids):
                    k0, v0 = init_kv_cache(cfg.n_layer, B, cfg.n_head, T, cfg.head_dim, self._kv_dtype)
                    return forward_with_cache(p, ids, k0, v0, 0, icfg)[0]

            elif self._is_gpt:
                fn = lambda p, ids: self._family.apply(p, ids, cfg, deterministic=True)
            else:
                fn = lambda p, ids, **k: self._family.encode(p, ids, cfg, deterministic=True, **k)
            self._compiled[key] = jax.jit(self._scoped(fn))
        return self._compiled[key](self.params, input_ids, **{k: jnp.asarray(v) for k, v in kw.items()})

    __call__ = forward

    # ----------------------------------------------------------------------
    # external-cache prefill/decode surface (the serving/ subsystem and
    # custom decode loops build on this instead of the closed generate())
    # ----------------------------------------------------------------------
    def inference_config(self, max_len: int):
        """The fused-block config for a cache of capacity ``max_len``."""
        from deepspeed_tpu.ops.transformer.inference import DeepSpeedInferenceConfig

        return DeepSpeedInferenceConfig.for_model(self.model_config, self.dtype, self.mp_world_size, max_len)

    def init_cache(self, batch: int, max_len: int):
        """Externally-owned KV cache ``(layers, batch, heads, max_len,
        head_dim)`` in the engine's cache dtype (bf16/f32 or the int8
        code+scale pair).  ``max_len`` is validated against
        :attr:`generation_capacity` so a cache that silently wraps past
        ``max_out_tokens`` cannot be built."""
        from deepspeed_tpu.ops.transformer.inference import init_kv_cache

        if not self._is_gpt:
            raise ValueError("init_cache() requires a causal-LM (GPT-family) model")
        if max_len > self.generation_capacity:
            raise ValueError(
                f"cache max_len={max_len} exceeds the generation capacity "
                f"min(max_out_tokens={self.max_out_tokens}, "
                f"n_positions={self.model_config.n_positions}) = "
                f"{self.generation_capacity}"
            )
        cfg = self.model_config
        return init_kv_cache(cfg.n_layer, int(batch), cfg.n_head, int(max_len), cfg.head_dim, self._kv_dtype)

    def _cache_step_fn(self, T: int, max_len: int, static_prefill: bool, per_slot: bool):
        """Compiled ``forward_with_cache`` wrapper, cached per (token
        shape, cache capacity, pos form) — the caller owns the cache."""
        key = ("cstep", T, max_len, static_prefill, per_slot)
        if key not in self._compiled:
            from deepspeed_tpu.ops.transformer.inference import forward_with_cache

            icfg = self.inference_config(max_len)

            if static_prefill:
                fn = lambda p, t, k, v: forward_with_cache(p, t, k, v, 0, icfg)
            else:
                fn = lambda p, t, k, v, pos: forward_with_cache(p, t, k, v, pos, icfg)
            self._compiled[key] = jax.jit(self._scoped(fn))
        return self._compiled[key]

    def prefill(self, tokens, k_cache, v_cache):
        """Initial prefill (write offset 0, causal fast path) into an
        externally-owned cache.  Returns ``(logits, k_cache, v_cache)``."""
        tokens = jnp.asarray(np.asarray(tokens), jnp.int32)
        B, T = tokens.shape
        S = jax.tree.leaves(k_cache)[0].shape[3]
        if T > S:
            raise ValueError(f"prefill length {T} exceeds the cache capacity {S}")
        fn = self._cache_step_fn(T, S, static_prefill=True, per_slot=False)
        return fn(self.params, tokens, k_cache, v_cache)

    def decode_step(self, tokens, k_cache, v_cache, pos):
        """One decode/continuation step at write offset ``pos`` (scalar,
        or a per-row (B,) vector for slot-pool continuous batching).
        ``pos`` is traced — every position reuses one executable.
        Returns ``(logits, k_cache, v_cache)``."""
        tokens = jnp.asarray(np.asarray(tokens), jnp.int32)
        B, T = tokens.shape
        S = jax.tree.leaves(k_cache)[0].shape[3]
        # pos is concrete host-side here: bound it BEFORE tracing — past
        # capacity the cache write would clamp and silently overwrite the
        # last position forever (the wrap the max_out_tokens satellite
        # exists to forbid)
        pos_host = np.asarray(pos)
        if int(pos_host.max()) + T > S:
            raise ValueError(
                f"decode_step write offset pos={int(pos_host.max())} + T={T} "
                f"exceeds the cache capacity {S}; the sequence is out of "
                f"room (grow the cache via init_cache, or stop generating)"
            )
        pos = jnp.asarray(pos, jnp.int32)
        fn = self._cache_step_fn(T, S, static_prefill=False, per_slot=pos.ndim == 1)
        return fn(self.params, tokens, k_cache, v_cache, pos)

    # ----------------------------------------------------------------------
    # generation (GPT family)
    # ----------------------------------------------------------------------
    def _build_generate(self, B: int, T: int, N: int, do_sample: bool, temperature: float, top_k: int, eos_token_id, masked: bool = False):
        from deepspeed_tpu.ops.transformer.inference import (
            forward_with_cache,
            init_kv_cache,
        )

        cfg = self.model_config
        # Static cache capacity: T+N, rounded up to the flash-decode
        # kernel's 128-row grid when the suite is armed (docs/kernels.md)
        # — the padded tail sits beyond every query position (pos < T+N)
        # so it is never attendable; without alignment the token loop
        # would silently fall back to the lax path for most (T, N).
        from deepspeed_tpu.ops import kernels as _kernels_mod

        S = T + N
        if _kernels_mod.flash_decode_armed():
            S = -(-S // 128) * 128
        icfg = self.inference_config(S)
        eos = -1 if eos_token_id is None else int(eos_token_id)

        def sample_token(logits32, r):
            return sample_logits(
                logits32, r, do_sample=do_sample, temperature=temperature, top_k=top_k
            )

        def gen(params, tokens, rng, attention_mask):
            k_cache, v_cache = init_kv_cache(cfg.n_layer, B, cfg.n_head, S, cfg.head_dim, self._kv_dtype)
            if masked:
                # left-padded prompts: real positions start at 0 per
                # example; padded cache slots are never attendable
                # (incl. the kernel-alignment tail beyond T+N)
                prompt_mask = attention_mask.astype(bool)  # (B, T)
                position_ids = jnp.maximum(jnp.cumsum(prompt_mask.astype(jnp.int32), axis=1) - 1, 0)
                real_len = jnp.sum(prompt_mask.astype(jnp.int32), axis=1)  # (B,)
                full_mask = jnp.concatenate(
                    [prompt_mask, jnp.ones((B, N), bool),
                     jnp.zeros((B, S - T - N), bool)], axis=1)
                logits, k_cache, v_cache = forward_with_cache(
                    params, tokens, k_cache, v_cache, 0, icfg,
                    key_padding_mask=full_mask, position_ids=position_ids,
                )
            else:
                real_len = jnp.full((B,), T, jnp.int32)
                full_mask = None
                logits, k_cache, v_cache = forward_with_cache(params, tokens, k_cache, v_cache, 0, icfg)
            r0, rng = jax.random.split(rng)
            first = sample_token(logits[:, -1].astype(jnp.float32), r0)
            finished = first == eos

            # prefill ran with the STACKED cache (layer scan amortizes);
            # the token loop carries PER-LAYER cache tuples instead —
            # each unrolled layer then owns its buffer and the stacked
            # cache's per-token slice/reassembly copies (profiled at
            # ~7ms/token at XL) disappear
            n_layer = jax.tree.leaves(k_cache)[0].shape[0]

            def _split_layers(c):
                if isinstance(c, dict):
                    return tuple({k: v[i] for k, v in c.items()} for i in range(n_layer))
                return tuple(c[i] for i in range(n_layer))

            k_tup = _split_layers(k_cache)
            v_tup = _split_layers(v_cache)

            def body(carry, xs):
                tok, kc, vc, pos, fin = carry
                r, step = xs
                # the token fed at scan step s was generated at step s-1,
                # so its logical position is real_len + (s-1)
                pos_ids = (real_len + step - 1)[:, None] if masked else None
                lg, kc, vc = forward_with_cache(
                    params, tok[:, None], kc, vc, pos, icfg,
                    key_padding_mask=full_mask, position_ids=pos_ids,
                )
                nxt = sample_token(lg[:, -1].astype(jnp.float32), r)
                nxt = jnp.where(fin, eos if eos >= 0 else 0, nxt)
                fin = fin | (nxt == eos)
                return (nxt, kc, vc, pos + 1, fin), nxt

            (_, _, _, _, _), rest = jax.lax.scan(
                body,
                (first, k_tup, v_tup, jnp.int32(T), finished),
                (jax.random.split(rng, N - 1), jnp.arange(1, N, dtype=jnp.int32)),
            )
            return jnp.concatenate([tokens, first[:, None], rest.T], axis=1)

        return jax.jit(self._scoped(gen))

    def generate(
        self,
        input_ids,
        max_new_tokens: int = 32,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        attention_mask=None,
    ):
        """Autoregressive generation (KV-cache decode).  ``input_ids``
        (B, T); ragged prompts are LEFT-padded with ``attention_mask``
        (B, T, 1=real) — positions and attention then follow each
        example's real length (HF convention).  Returns
        (B, T + max_new_tokens)."""
        if not self._is_gpt:
            raise ValueError("generate() requires a causal-LM (GPT-family) model")
        input_ids = jnp.asarray(np.asarray(input_ids), jnp.int32)
        B, T = input_ids.shape
        if T + max_new_tokens > self.generation_capacity:
            raise ValueError(
                f"T+max_new_tokens = {T}+{max_new_tokens} = {T + max_new_tokens} "
                f"exceeds the generation capacity "
                f"min(max_out_tokens={self.max_out_tokens}, "
                f"n_positions={self.model_config.n_positions}) = "
                f"{self.generation_capacity} (raise max_out_tokens in "
                f"init_inference, or shorten the prompt)"
            )
        masked = attention_mask is not None
        if masked:
            am_np = np.asarray(attention_mask)
            if not np.array_equal(np.sort(am_np, axis=1), am_np):
                raise ValueError(
                    "attention_mask must be LEFT-padded (rows of 0s then 1s); "
                    "right-padded prompts would silently generate from a pad position"
                )
            if np.all(am_np == 1):
                masked = False  # all-real prompts: take the unmasked fast path
            attention_mask = jnp.asarray(am_np, jnp.int32)
        else:
            attention_mask = jnp.ones((B, T), jnp.int32)
        key = ("gen", B, T, max_new_tokens, do_sample, float(temperature), int(top_k), eos_token_id, masked)
        if key not in self._compiled:
            self._compiled[key] = self._build_generate(
                B, T, max_new_tokens, do_sample, temperature, top_k, eos_token_id, masked=masked
            )
            # ds_shard Pass 1/2 feed (no-op unless the audit armed it)
            if shard_hooks.armed():
                shard_hooks.note_jit(
                    self, "inference.generate", self._compiled[key],
                    (self.params, input_ids, jax.random.PRNGKey(seed), attention_mask),
                    leaves=shard_hooks.live_param_leaves(self.params),
                )
        # telemetry (docs/telemetry.md): closed-generate calls count
        # tokens dispatched; no fence is added — the span measures the
        # host call window, the caller owns the sync
        from deepspeed_tpu.telemetry import get_registry, get_tracer

        reg, tracer = get_registry(), get_tracer()
        if reg.enabled:
            reg.counter("inference/generate_calls", engine="inference").inc()
            reg.counter("inference/tokens_requested", engine="inference").inc(B * max_new_tokens)
        with tracer.span("generate", "inference",
                         args={"batch": B, "prompt_len": T,
                               "max_new_tokens": max_new_tokens}):
            return self._compiled[key](self.params, input_ids, jax.random.PRNGKey(seed), attention_mask)
