"""Builds the MiMo-V2-Flash serving engine and its reference from a
configuration file and the seed, through the program's public entry
points.  Shared by ``runners/serve_mimo.py`` and ``control_mimo.py``, so
the limits of ``correct`` are read from exactly the engine the cell's
runs check."""
from __future__ import annotations

from typing import Any, Dict, Sequence

import jax.numpy as jnp

from . import weights_mimo as weights
from .reference_mimo import Reference


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the weights, the reference and the program are made
    from: the file's ``model`` keys as run, with the router back at its
    published width and the experts held here named
    (``share.first_expert``, ``model.n_routed_experts`` of them).  The
    vocabulary stays the slice: a sliced vocabulary is a smaller one."""
    m, share = dict(cfg["model"]), cfg["share"]
    return {**m, "n_routed_experts": share["published"]["n_routed_experts"], "experts_held": [share["first_expert"], m["n_routed_experts"]]}


def model_config(cfg: Dict[str, Any]):
    """The program's ``MiMoV2Config`` for a configuration file."""
    try:
        from deepspeed_tpu.models import mimo_v2
    except ImportError as e:  # a checkout from before the family existed: fail at once, before any weight is made
        raise SystemExit(f"benchmark: this checkout cannot run MiMo-V2 ({e}); the cell needs deepspeed_tpu/models/mimo_v2.py, "
                         "a page kind with a value width and a geometry a group of WindowedKV")
    dims = dims_of(cfg)
    return mimo_v2.MiMoV2Config.from_hf(dims, experts_held=dims["experts_held"])


def reference(cfg: Dict[str, Any], seed: int, precision: str = "float32", **variant) -> Reference:
    """``variant``: the controls that are variants of the reference (``reference_mimo``)."""
    return Reference(dims_of(cfg), seed, precision, **variant)


def serving_engine(cfg: Dict[str, Any], seed: int, devices: Sequence[Any], say=lambda msg: None, **overrides):
    """Seeded bf16 weights (made block by block on the device) →
    ``deepspeed_tpu.init_inference`` → ``ServingEngine`` on an explicit
    one-device mesh.  The weight tree is handed over to the engine
    (``donate_params``): 7.86 GB beside 4.96 GB of caches cannot be held
    twice.  ``overrides`` replace fields of the file's ``serving`` block."""
    mcfg = model_config(cfg)  # first: a checkout without the family stops here
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import make_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.serving import ServingEngine

    if len(devices) != 1:
        raise ValueError("the serving engine is driven on one chip")
    scfg = {**cfg["serving"], **overrides}
    params = weights.program_params(seed, dims_of(cfg), jnp.bfloat16)
    say("seeded weights on the device")
    inf = deepspeed_tpu.init_inference(
        model_config=mcfg, params=params, dtype=jnp.bfloat16, max_out_tokens=scfg["max_len"],
        mesh=make_mesh(MeshConfig(), devices=list(devices)), donate_params=True,
    )
    del params
    return ServingEngine(inf, config=scfg)
