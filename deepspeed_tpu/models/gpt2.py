"""GPT-2 family — the flagship training model.

The reference trains GPT-2 through client Megatron-LM code (SURVEY.md §6
workload ladder: GPT-2 345M/1.5B ZeRO-3); this framework ships the model
natively, TPU-idiomatic:

* all transformer blocks **stacked on a leading layer dim** and executed
  with ``lax.scan`` — one trace/compile regardless of depth, and the
  layer dim doubles as the pipeline-partition dim;
* attention through the Pallas flash-attention op (ops/attention);
* Megatron-style tensor parallelism expressed as PartitionSpecs on the
  weights (``tp_spec_fn``): qkv/fc column-parallel, proj row-parallel,
  vocab-sharded embedding — GSPMD inserts the psums the reference gets
  from explicit mpu collectives;
* activation checkpointing via ``jax.checkpoint`` policy on the scanned
  block (reference ``runtime/activation_checkpointing``).

Params are a plain pytree of jnp arrays (fp32 masters; engine casts).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention.flash_attention import flash_attention, mha_reference
# single shared implementation (ops/normalize.py); aliased because
# models/bert.py imports these names from here
from deepspeed_tpu.ops.normalize import dropout as _dropout, layer_norm as _layer_norm, token_nll
# the family seam of ``ServingEngine`` (docs/serving.md §Model families): the fused inference blocks' step on the
# paged pool, and on the slot-contiguous one (``serving.kvcache.enabled: false``); ``cache_kind`` is below
from deepspeed_tpu.ops.transformer.inference import serving_forward, slot_serving_forward  # noqa: F401


CAUSAL_LM = True  # models/__init__.py: what the engines ask of a family
PARTITION_RULES = "gpt2"  # the family's table in sharding/rules.py


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    use_flash_attention: bool = True
    # "flash" | "ring" | "ulysses" | "sparse" — ring/ulysses run
    # sequence-parallel over the mesh's `seq` axis (parallel/sequence.py);
    # sparse uses the block-sparse kernel with `sparsity_config`
    # (default: unidirectional BigBird), the reference's long-sequence
    # recipe (SURVEY §5.7)
    attention_mode: str = "flash"
    # a SparsityConfig instance (ops/attention/sparse.py); None ⇒ BigBird
    sparsity_config: Any = None
    # MoE: >0 replaces every block's FFN with an n_experts MoE layer
    # (experts sharded over the `expert` mesh axis, moe/layer.py)
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    remat: bool = True  # activation checkpointing per block
    # >0: cross-entropy computed in time-chunks of this size under remat,
    # so the (B, T, vocab) logits tensor never materializes whole —
    # memory drops by ~B*T*V*6 bytes at ~10% extra logit-matmul flops
    xent_chunk_size: int = 0
    remat_policy: str = "nothing_saveable"  # or "dots_with_no_batch_dims_saveable"
    # selective checkpointing: non-empty ⇒ overrides remat_policy with
    # save_only_these_names over the tags placed in _block —
    # "qkv" (B,T,3D), "attn_ctx" (B,T,D), "ffn_pre" (B,T,4D).  Saving all
    # three keeps 8D·B·T bytes/layer and cuts the backward's recompute
    # from a full block forward (~1/4 of step flops under
    # nothing_saveable) to the flash-attention forward + elementwise ops
    # (~3%) — the reference gets the same effect from its fused kernels
    # saving their intermediates (csrc/transformer/ds_transformer_cuda.cpp)
    remat_save_names: tuple = ()
    # lax.scan unroll factor for the layer loop: >1 trades compile time
    # for fewer loop-carried copies / less per-iteration bookkeeping
    scan_unroll: int = 1
    # flash kernel block override: (block_q, block_k[, bwd_block_q,
    # bwd_block_k]); empty ⇒ the op's measured defaults
    flash_blocks: tuple = ()
    dtype: Any = jnp.float32  # activation dtype is set by the engine cast

    @property
    def head_dim(self) -> int:
        assert self.n_embd % self.n_head == 0
        return self.n_embd // self.n_head

    def num_params(self) -> int:
        d, l, v, s = self.n_embd, self.n_layer, self.vocab_size, self.n_positions
        if self.n_experts > 0:
            E = self.n_experts
            # attention (qkv+proj) + LNs + router + E expert FFNs
            per_layer = 4 * d * d + 8 * d + d * E + E * (8 * d * d + 5 * d)
        else:
            per_layer = 12 * d * d + 13 * d
        return v * d + s * d + l * per_layer + 2 * d


# Model zoo (sizes as in the GPT-2 paper; 1.5B == "xl" is the BASELINE
# north-star model).
GPT2_TINY = GPT2Config(vocab_size=512, n_positions=128, n_embd=64, n_layer=2, n_head=4)
GPT2_SMALL = GPT2Config()  # 124M
GPT2_MEDIUM = GPT2Config(n_embd=1024, n_layer=24, n_head=16)  # 350M
GPT2_LARGE = GPT2Config(n_embd=1280, n_layer=36, n_head=20)  # 774M
GPT2_XL = GPT2Config(n_embd=1600, n_layer=48, n_head=25)  # 1.5B

# GPT-Neo-2.7B dims (BASELINE ladder's inference rung; HF weights map
# through HFGPTNEOLayerPolicy — this preset serves the random-init
# serving/throughput path at the same scale)
GPT_NEO_27B = GPT2Config(n_positions=2048, n_embd=2560, n_layer=32, n_head=20)

PRESETS = {
    "tiny": GPT2_TINY,
    "gpt2": GPT2_SMALL,
    "gpt2-small": GPT2_SMALL,
    "gpt2-medium": GPT2_MEDIUM,
    "gpt2-large": GPT2_LARGE,
    "gpt2-xl": GPT2_XL,
    "gpt2-1.5b": GPT2_XL,
    "gpt-neo-2.7b": GPT_NEO_27B,
    "gpt-neo": GPT_NEO_27B,
}


def init_params(cfg: GPT2Config, seed: int = 0) -> Dict[str, Any]:
    """GPT-2 init: normal(0.02), residual projections scaled by
    1/sqrt(2*n_layer)."""
    rng = np.random.default_rng(seed)
    d, l = cfg.n_embd, cfg.n_layer
    std = 0.02
    proj_std = std / np.sqrt(2 * l)

    def n(*shape, s=std):
        return (rng.standard_normal(shape) * s).astype(np.float32)

    def z(*shape):
        return np.zeros(shape, np.float32)

    def o(*shape):
        return np.ones(shape, np.float32)

    if cfg.n_experts > 0:
        from deepspeed_tpu.moe.layer import MoEConfig, init_moe_params

        mcfg = MoEConfig(num_experts=cfg.n_experts, d_model=d, d_ff=4 * d)
        per_layer = [init_moe_params(mcfg, rng, std=std, proj_std=proj_std) for _ in range(l)]
        ffn = {k: np.stack([p[k] for p in per_layer]) for k in per_layer[0]}
    else:
        ffn = {
            "fc_w": n(l, d, 4 * d),
            "fc_b": z(l, 4 * d),
            "fc_proj_w": n(l, 4 * d, d, s=proj_std),
            "fc_proj_b": z(l, d),
        }
    return {
        "wte": n(cfg.vocab_size, d),
        "wpe": n(cfg.n_positions, d, s=0.01),
        "blocks": {
            "ln1_g": o(l, d),
            "ln1_b": z(l, d),
            "qkv_w": n(l, d, 3 * d),
            "qkv_b": z(l, 3 * d),
            "proj_w": n(l, d, d, s=proj_std),
            "proj_b": z(l, d),
            "ln2_g": o(l, d),
            "ln2_b": z(l, d),
            **ffn,
        },
        "lnf_g": o(d),
        "lnf_b": z(d),
    }


def init_params_device(cfg: GPT2Config, seed: int = 0, dtype=jnp.float32):
    """Random init generated ON DEVICE (same tree structure/shapes as
    ``init_params``, independent random stream).

    For benchmark/serving paths: host generation of an XL-class model is
    minutes of single-threaded numpy plus a multi-GB upload, on-chip
    generation is seconds.  Not bitwise-equal to ``init_params`` —
    use the host init when pinned numerics matter."""
    if cfg.n_experts > 0:
        raise NotImplementedError("device init does not cover MoE; use init_params")
    d, l = cfg.n_embd, cfg.n_layer
    std, proj_std = 0.02, 0.02 / np.sqrt(2 * l)

    def build(key):
        ks = iter(jax.random.split(key, 8))

        def n(shape, s=std):
            return (jax.random.normal(next(ks), shape, jnp.float32) * s).astype(dtype)

        z = lambda *shape: jnp.zeros(shape, dtype)
        o = lambda *shape: jnp.ones(shape, dtype)
        return {
            "wte": n((cfg.vocab_size, d)),
            "wpe": n((cfg.n_positions, d), s=0.01),
            "blocks": {
                "ln1_g": o(l, d), "ln1_b": z(l, d),
                "qkv_w": n((l, d, 3 * d)), "qkv_b": z(l, 3 * d),
                "proj_w": n((l, d, d), s=proj_std), "proj_b": z(l, d),
                "ln2_g": o(l, d), "ln2_b": z(l, d),
                "fc_w": n((l, d, 4 * d)), "fc_b": z(l, 4 * d),
                "fc_proj_w": n((l, 4 * d, d), s=proj_std), "fc_proj_b": z(l, d),
            },
            "lnf_g": o(d),
            "lnf_b": z(d),
        }

    # out_shardings=None: init params land unsharded; the engine shards
    # them on first scoped step (docs/ds_lint.md, bare-jit)
    return jax.jit(build, out_shardings=None)(jax.random.PRNGKey(seed))


def tp_spec_fn(path: str, shape) -> Optional[P]:
    """Megatron-style tensor-parallel specs over the ``model`` axis
    (reference delegates TP to Megatron mpu; inference-side slicing in
    module_inject/replace_module.py:11-88 follows the same column/row
    split), plus expert-parallel specs over ``expert`` for MoE weights.
    Thin adapter over the partition-rule engine's ``gpt2`` family table
    (sharding/rules.py) — the single source of truth for this layout."""
    from deepspeed_tpu.sharding.rules import rules_for_family

    return rules_for_family("gpt2").spec(path, shape)


def cache_kind(cfg: GPT2Config, dtype):
    """The family's cache kind for the serving pools: K and V a head, a
    jnp dtype or ``"int8"`` (the code+scale pair)."""
    from deepspeed_tpu.serving.kvcache.pages import PerHeadKV

    return PerHeadKV(cfg.n_head, cfg.head_dim, dtype)


# per-(config-values, seq) layout cache: layouts are static numpy, built once
_SPARSE_LAYOUTS: Dict[Any, Any] = {}


def _sparsity_cache_key(sc, T: int):
    # value-based key (id() would collide after gc and never hit for
    # per-call default configs)
    vals = tuple(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in sorted(vars(sc).items())
        if isinstance(v, (int, float, str, bool, list, tuple, type(None)))
    )
    return (type(sc).__name__, vals, T)


def _sparse_attn(cfg: GPT2Config, q, k, v, T: int):
    from deepspeed_tpu.ops.attention.sparse import BigBirdSparsityConfig, block_sparse_attention

    sc = cfg.sparsity_config
    if sc is None:
        # prefer BIG blocks: the splash kernels run one (q-row, edge)
        # pair per grid step, so per-step launch overhead (~1µs)
        # amortizes over block² work — block 256 beat 128 by ~1.3x at
        # 8k on v5e (r5 crossover sweep), and MXU efficiency rises too
        # T/block must cover the 3-block sliding window or make_layout
        # refuses (short sequences fall back to smaller blocks)
        block = next((b for b in (256, 128, 64, 16) if T % b == 0 and T // b >= 3), 16)
        sc = BigBirdSparsityConfig(
            num_heads=cfg.n_head, block=block, num_random_blocks=1,
            num_sliding_window_blocks=3, num_global_blocks=1, attention="unidirectional",
        )
    key = _sparsity_cache_key(sc, T)
    if key not in _SPARSE_LAYOUTS:
        _SPARSE_LAYOUTS[key] = sc.make_layout(T)
    return block_sparse_attention(q, k, v, _SPARSE_LAYOUTS[key], sc.block, causal=True)


def _block(cfg: GPT2Config, x, lp, rng, deterministic: bool, token_mask=None):
    """One transformer block; ``lp`` holds this layer's slice of the
    stacked params."""
    B, T, D = x.shape
    H, hd = cfg.n_head, cfg.head_dim
    r1 = r2 = r3 = None
    if rng is not None:
        r1, r2, r3 = jax.random.split(rng, 3)

    h = _layer_norm(x, lp["ln1_g"], lp["ln1_b"], cfg.layer_norm_epsilon)
    qkv = h @ lp["qkv_w"].astype(h.dtype) + lp["qkv_b"].astype(h.dtype)
    qkv = checkpoint_name(qkv, "qkv")
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(B, T, H, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    if cfg.attention_mode == "ring":
        from deepspeed_tpu.parallel.sequence import ring_attention

        attn = ring_attention(q, k, v, causal=True)
    elif cfg.attention_mode == "ulysses":
        from deepspeed_tpu.parallel.sequence import ulysses_attention

        attn = ulysses_attention(q, k, v, causal=True, use_flash=cfg.use_flash_attention)
    elif cfg.attention_mode == "sparse":
        attn = _sparse_attn(cfg, q, k, v, T)
    elif cfg.attention_mode != "flash":
        raise ValueError(f"unknown attention_mode {cfg.attention_mode!r} (flash|ring|ulysses|sparse)")
    elif cfg.use_flash_attention and T >= 128:
        fb = cfg.flash_blocks
        fb_kw = (
            dict(zip(("block_q", "block_k", "bwd_block_q", "bwd_block_k"), fb)) if fb else {}
        )
        attn = flash_attention(q, k, v, causal=True, **fb_kw)
    else:
        attn = mha_reference(q, k, v, causal=True)
    attn = attn.transpose(0, 2, 1, 3).reshape(B, T, D)
    attn = checkpoint_name(attn, "attn_ctx")
    attn = attn @ lp["proj_w"].astype(attn.dtype) + lp["proj_b"].astype(attn.dtype)
    x = x + _dropout(attn, cfg.dropout, r1, deterministic)

    h = _layer_norm(x, lp["ln2_g"], lp["ln2_b"], cfg.layer_norm_epsilon)
    if cfg.n_experts > 0:
        from deepspeed_tpu.moe.layer import moe_ffn_from_block

        # training ⇔ a dropout/jitter rng was threaded in (eval passes None)
        h, aux = moe_ffn_from_block(
            lp, h, top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
            rng=r2, training=rng is not None, token_mask=token_mask,
        )
    else:
        h = h @ lp["fc_w"].astype(h.dtype) + lp["fc_b"].astype(h.dtype)
        h = checkpoint_name(h, "ffn_pre")
        h = jax.nn.gelu(h, approximate=True)
        h = _dropout(h, cfg.dropout, r2, deterministic)
        h = h @ lp["fc_proj_w"].astype(h.dtype) + lp["fc_proj_b"].astype(h.dtype)
        aux = jnp.zeros((), jnp.float32)
    x = x + _dropout(h, cfg.dropout, r3, deterministic)
    return x, aux


def apply(params: Dict[str, Any], tokens: jnp.ndarray, cfg: GPT2Config, rng=None, deterministic: bool = True, return_aux: bool = False, token_mask=None, pld_theta=None, return_hidden: bool = False):
    """Forward pass: ``tokens (B, T) int32`` → logits ``(B, T, V)``.

    ``return_aux=True`` additionally returns the summed MoE
    load-balancing loss (zero for dense models).  ``token_mask (B, T)``
    excludes padding from MoE routing/aux.  ``pld_theta`` (traced scalar)
    enables progressive layer drop: layer l of L is kept with probability
    ``1 - (l+1)/L·(1-theta)`` via ``lax.cond`` — dropped layers skip
    their compute entirely (runtime/progressive_layer_drop.py).
    ``return_hidden=True`` returns the post-final-LN hidden states
    (B, T, D) instead of logits (used by the chunked-xent loss so the
    full logits tensor never materializes)."""
    B, T = tokens.shape
    x = jnp.take(params["wte"], tokens, axis=0) + params["wpe"][:T][None]
    x = x.astype(params["blocks"]["qkv_w"].dtype)

    n_layer = cfg.n_layer
    if rng is not None:
        layer_rngs = jax.random.split(rng, n_layer)
    else:
        layer_rngs = jnp.zeros((n_layer, 2), jnp.uint32)

    block_fn = functools.partial(_block, cfg)
    use_pld = pld_theta is not None and rng is not None and not deterministic
    keep_probs = None
    if use_pld:
        from deepspeed_tpu.runtime.progressive_layer_drop import layer_keep_probs

        keep_probs = layer_keep_probs(pld_theta, n_layer)

    def scan_body(carry, xs):
        x, aux_acc = carry
        if use_pld:
            lp, lr, keep_p = xs
        else:
            lp, lr = xs
        r = lr if rng is not None else None

        def run_block(x_in):
            return block_fn(x_in, lp, r, deterministic, token_mask)

        if use_pld:
            keep = jax.random.bernoulli(jax.random.fold_in(lr, 7), keep_p)

            def kept_branch(x_in):
                # inverted stochastic-depth scaling: the block's residual
                # delta is scaled by 1/keep_p so the training-time
                # expectation matches the deterministic eval forward
                y_in, aux_in = run_block(x_in)
                y_scaled = x_in + (y_in - x_in) / keep_p.astype(y_in.dtype)
                return y_scaled, aux_in

            y, aux = jax.lax.cond(keep, kept_branch, lambda x_in: (x_in, jnp.zeros((), jnp.float32)), x)
        else:
            y, aux = run_block(x)
        return (y, aux_acc + aux), None

    if cfg.remat:
        if cfg.remat_save_names:
            policy = jax.checkpoint_policies.save_only_these_names(*cfg.remat_save_names)
        else:
            policy = getattr(jax.checkpoint_policies, cfg.remat_policy, None)
        scan_body = jax.checkpoint(scan_body, policy=policy, prevent_cse=False)

    scan_xs = (params["blocks"], layer_rngs, keep_probs) if use_pld else (params["blocks"], layer_rngs)
    (x, aux_total), _ = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), scan_xs, unroll=max(1, cfg.scan_unroll)
    )
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"], cfg.layer_norm_epsilon)
    if return_hidden:
        return (x, aux_total) if return_aux else x
    logits = x @ params["wte"].T.astype(x.dtype)  # tied embedding head
    if return_aux:
        return logits, aux_total
    return logits


def _chunked_xent(hidden: jnp.ndarray, wte: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Masked-mean next-token NLL computed per time-chunk under remat:
    each chunk's (B, C, V) logits are built, reduced, and discarded —
    the backward recomputes them chunk-by-chunk, so peak memory holds
    one chunk of logits instead of the whole (B, T, V) tensor."""
    B, T, D = hidden.shape
    pad = (-T) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (T + pad) // chunk
    hs = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    ms = mask.reshape(B, n, chunk).transpose(1, 0, 2)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, inp):
        xc, lc, mc = inp
        logits = xc @ wte.T.astype(xc.dtype)
        nll = token_nll(logits, lc) * mc
        s, c = carry
        return (s + jnp.sum(nll), c + jnp.sum(mc)), None

    (total, count), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hs, ls, ms))
    return total / jnp.maximum(count, 1.0)


def loss_fn(params: Dict[str, Any], batch: Dict[str, Any], rng=None, cfg: GPT2Config = None, deterministic: bool = False) -> jnp.ndarray:
    """Next-token cross entropy.  ``batch``: {"input_ids": (B, T)} with
    optional "labels" (default: shifted input_ids) and "attention_mask"."""
    from deepspeed_tpu.runtime.progressive_layer_drop import PLD_THETA_KEY

    tokens = batch["input_ids"]
    chunked = cfg.xent_chunk_size > 0
    out, moe_aux = apply(
        params, tokens, cfg, rng=rng, deterministic=deterministic, return_aux=True,
        token_mask=batch.get("attention_mask") if cfg.n_experts > 0 else None,
        pld_theta=batch.get(PLD_THETA_KEY), return_hidden=chunked,
    )
    # one shared shift/mask derivation for both reductions: mask indexes
    # the *label* position (tokens[:, 1:]), not the query
    if "labels" in batch:
        labels, out_shift = batch["labels"], out
        mask = batch.get("attention_mask")
        mask = mask[:, : labels.shape[1]].astype(jnp.float32) if mask is not None else None
    else:
        labels, out_shift = tokens[:, 1:], out[:, :-1]
        mask = batch.get("attention_mask")
        mask = mask[:, 1 : 1 + labels.shape[1]].astype(jnp.float32) if mask is not None else None
    aux = cfg.moe_aux_weight * moe_aux if cfg.n_experts > 0 else 0.0

    if chunked:
        ones = jnp.ones(labels.shape, jnp.float32) if mask is None else mask
        return _chunked_xent(out_shift, params["wte"], labels, ones, cfg.xent_chunk_size) + aux

    nll = token_nll(out_shift, labels)
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0) + aux
    return jnp.mean(nll) + aux


def _stream_embed(cfg: GPT2Config, resident, tokens):
    """Streaming executor's stage 0: token+position embedding."""
    T = tokens.shape[1]
    x = jnp.take(resident["wte"], tokens, axis=0) + resident["wpe"][:T][None].astype(resident["wte"].dtype)
    return x


def _stream_group(cfg: GPT2Config, gblocks, x, rngs, deterministic):
    """Streaming executor's repeated stage: scan of ``_block`` over one
    GROUP of stacked layers (gblocks leaves lead with the group dim).
    Remat per block keeps the in-group activation footprint O(1)."""
    block_fn = functools.partial(_block, cfg)

    def body(carry, xs):
        lp, lr = xs
        r = lr if not deterministic else None
        y, _aux = block_fn(carry, lp, r, deterministic, None)
        return y, None

    if cfg.remat:
        if cfg.remat_save_names:
            policy = jax.checkpoint_policies.save_only_these_names(*cfg.remat_save_names)
        else:
            policy = getattr(jax.checkpoint_policies, cfg.remat_policy, None)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, (gblocks, rngs))
    return x


def _stream_head_loss(cfg: GPT2Config, resident, x, batch):
    """Streaming executor's final stage: final LN + tied head + xent
    (mirrors ``loss_fn``'s tail, chunked when configured)."""
    x = _layer_norm(x, resident["lnf_g"], resident["lnf_b"], cfg.layer_norm_epsilon)
    tokens = batch["input_ids"]
    if "labels" in batch:
        labels, x_shift = batch["labels"], x
        mask = batch.get("attention_mask")
        mask = mask[:, : labels.shape[1]].astype(jnp.float32) if mask is not None else None
    else:
        labels, x_shift = tokens[:, 1:], x[:, :-1]
        mask = batch.get("attention_mask")
        mask = mask[:, 1 : 1 + labels.shape[1]].astype(jnp.float32) if mask is not None else None
    if cfg.xent_chunk_size > 0:
        ones = jnp.ones(labels.shape, jnp.float32) if mask is None else mask
        return _chunked_xent(x_shift, resident["wte"], labels, ones, cfg.xent_chunk_size)
    logits = x_shift @ resident["wte"].T.astype(x_shift.dtype)
    nll = token_nll(logits, labels)
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def make_model(cfg: GPT2Config):
    """Returns (model_fn, init_fn, tp_spec_fn) — ``model_fn`` plugs
    straight into ``deepspeed_tpu.initialize(model=...)``.

    ``model_fn.stream_spec`` advertises the layer-streaming structure the
    ZeRO-Infinity param-offload executor needs (runtime/zero/
    param_offload.py): which params subtree is stacked per layer, and the
    embed / layer-group / head stage functions."""

    def model_fn(params, batch, rng):
        # rng=None ⇒ eval mode (engine passes None from eval_batch/predict)
        deterministic = rng is None or cfg.dropout == 0.0
        return loss_fn(params, batch, rng=rng, cfg=cfg, deterministic=deterministic)

    from deepspeed_tpu.runtime.zero.param_offload import StreamSpec

    model_fn.stream_spec = StreamSpec(
        n_layer=cfg.n_layer,
        blocks_key="blocks",
        embed=functools.partial(_stream_embed, cfg),
        group=functools.partial(_stream_group, cfg),
        head_loss=functools.partial(_stream_head_loss, cfg),
        deterministic=cfg.dropout == 0.0,
        # MoE experts need the expert mesh axis; ring/ulysses need the
        # seq axis — both incompatible with the data-only streaming
        # mesh.  flash and sparse are fine: both are single-device
        # kernels with host-side (numpy) layout prep only.
        supported=cfg.n_experts == 0 and cfg.attention_mode in ("flash", "sparse"),
    )
    return model_fn, functools.partial(init_params, cfg), tp_spec_fn
