"""Builds the system under test through its public entry points, from a
configuration file and the seed.  Shared by the runners and by
``control.py``, so the limits of ``correct`` are read from exactly the
engines the benchmark's runs check."""
from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import checks, weights
from .reference_gpt2 import BLOCK_MATRICES, Reference

MODEL_KEYS = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head")


def gpt2_config(cfg: Dict[str, Any]):
    """The program's ``GPT2Config`` for a configuration file: the
    published sizes plus the file's ``model_options`` (remat recipe)."""
    from deepspeed_tpu.models import gpt2

    options = dict(cfg.get("model_options", {}))
    if "remat_save_names" in options:
        options["remat_save_names"] = tuple(options["remat_save_names"])
    return gpt2.GPT2Config(**{k: cfg["model"][k] for k in MODEL_KEYS}, **options)


def weight_dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The model's sizes plus the file's ``weights`` options (see :mod:`weights`)."""
    return {**cfg["model"], **cfg.get("weights", {})}


def reference(cfg: Dict[str, Any], seed: int, precision: str = "float32") -> Reference:
    return Reference({**weight_dims(cfg), "layer_norm_epsilon": gpt2_config(cfg).layer_norm_epsilon}, seed, precision)


def loss_path(cfg: Dict[str, Any], seq: int, seed: int, say=lambda msg: None) -> Dict[str, Any]:
    """Seeded float32 weights on the default device, and the program's
    loss path on the probe sequences: ``make_model``'s loss function —
    the one ``deepspeed_tpu.initialize`` is handed — on the engine's
    compute copy of the weights."""
    from deepspeed_tpu.models import gpt2

    params = weights.stacked_params(seed, weight_dims(cfg), jnp.float32)
    jax.block_until_ready(params)
    say("seeded weights on the device")
    probe = checks.check_sequences(seed, cfg["model"]["vocab_size"], seq)
    compute = jnp.bfloat16 if cfg["engine"].get("bf16", {}).get("enabled") else jnp.float32
    model_fn, _, tp_fn = gpt2.make_model(gpt2_config(cfg))
    nll_prog = checks.program_nll(
        lambda p, batch, rng: model_fn(jax.tree.map(lambda a: a.astype(compute), p), batch, rng), params, probe)
    say("program loss path on the probe sequences done")
    return {"params": params, "probe": probe, "nll_prog": nll_prog, "model_fn": model_fn, "tp_fn": tp_fn}


def train_engine(cfg: Dict[str, Any], seq: int, seed: int, devices: Sequence[Any], say=lambda msg: None) -> Dict[str, Any]:
    """:func:`loss_path` → ``deepspeed_tpu.initialize`` on the same
    weights.  Returns the engine and what the first-step check needs."""
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig

    ds_config = dict(cfg["engine"])
    lp = loss_path(cfg, seq, seed, say)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lp["model_fn"], model_parameters=lp.pop("params"), config=ds_config, tp_spec_fn=lp["tp_fn"],
        mesh=make_mesh(MeshConfig.from_dict(ds_config["mesh"]), devices=list(devices)),
    )
    rows = ds_config["train_micro_batch_size_per_gpu"] * ds_config["gradient_accumulation_steps"] * len(devices)
    return {"engine": engine, "probe": lp["probe"], "nll_prog": lp["nll_prog"], "rows": rows, "seq": seq,
            "lr": float(ds_config["optimizer"]["params"]["lr"])}


def first_step(built: Dict[str, Any]) -> Dict[str, Any]:
    """The engine's first optimizer step — the one that compiles — on the
    probe sequences tiled over the global batch, so that the batch's mean
    loss and gradient are the two sequences' own.  Keeps what the check
    needs on the host (the loss, and the block matrices as the step left
    them), so that the reference's sweep can wait until the measured
    window has closed and set-up times the program alone."""
    engine, probe, rows = built["engine"], built["probe"], built["rows"]
    if rows % len(probe):
        raise ValueError(f"{rows} rows a step do not tile the {len(probe)} probe sequences")
    loss1 = float(engine.train_batch({"input_ids": np.tile(probe, (rows // len(probe), 1))}))
    blocks = engine.state["params"]["blocks"]
    return {"loss": loss1, "after": {n: np.asarray(blocks[n]) for n in BLOCK_MATRICES}}


def first_step_numbers(built: Dict[str, Any], stepped: Dict[str, Any], ref: Reference) -> Dict[str, float]:
    """:func:`first_step` against the reference: the three numbers of :mod:`checks`."""
    nll_ref, sweep = ref.nll_and_block_grads(built["probe"])
    nll_ref = np.asarray(nll_ref)
    return {
        "loss_abs_err": abs(stepped["loss"] - float(nll_ref.mean())),
        "logprob_rms_err": float(np.sqrt(np.mean((built["nll_prog"] - nll_ref) ** 2))),
        "update_disagreement": checks.update_disagreement(ref, sweep, stepped["after"], built["lr"]),
    }


def serving_engine(cfg: Dict[str, Any], seed: int, devices: Sequence[Any], **overrides):
    """Seeded bf16 weights → ``deepspeed_tpu.init_inference`` →
    ``ServingEngine`` on an explicit one-device mesh (the default spreads
    ``data`` over every device and replicates the pool).  ``overrides``
    replace fields of the file's ``serving`` block."""
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.serving import ServingEngine

    if len(devices) != 1:
        raise ValueError("the serving engine is driven on one chip")
    scfg = {**cfg["serving"], **overrides}
    params = weights.stacked_params(seed, weight_dims(cfg), jnp.bfloat16)
    inf = deepspeed_tpu.init_inference(
        model_config=gpt2_config(cfg), params=params, dtype=jnp.bfloat16, max_out_tokens=scfg["max_len"],
        mesh=make_mesh(MeshConfig(), devices=list(devices)),
    )
    del params
    return ServingEngine(inf, config=scfg)
