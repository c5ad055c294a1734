"""Pallas kernel suite: fused flash-decode, fused optimizer update, and
the persistent block-size autotuner (docs/kernels.md).

Arming model — one process-wide decision read at **trace time** (the
dispatch inside ``cache_attention`` / ``_apply_update_unscaled`` is a
Python branch, so flipping it after an executable is built has no
effect on that executable; engines resolve it once per compile):

* ``configure(...)`` — the ``kernels`` config block
  (docs/config-json.md), called by engine constructors;
* ``DS_KERNELS`` env — the escape hatch that wins over config:
  ``auto`` (default: armed on TPU only, so CPU tier-1 never changes
  numerics under anyone's feet), ``1``/``on`` (force-armed — off-TPU
  the kernels run under ``interpret=True``; the parity tests use
  this), ``0``/``off`` (lax/XLA paths everywhere);
* per-kernel knobs (``flash_decode`` / ``fused_update``) subtract from
  an armed suite, never add to a disarmed one.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

from deepspeed_tpu.ops.kernels.autotune import (  # noqa: F401 — public surface
    Autotuner,
    autotune_mode,
    default_blocks,
    get_autotuner,
    reset_autotuner,
)
from deepspeed_tpu.utils.device import on_tpu_backend  # noqa: F401

_STATE: Dict[str, Any] = {
    "enabled": "auto",        # "auto" | True | False (config layer)
    "flash_decode": True,
    "fused_update": True,
}

_WARNED: set = set()


def warn_once(key, msg: str, level: str = "warning") -> None:
    """Trace-time-safe single-shot warning (dispatch sites run while
    tracing, where per-instance flags would be a traced side effect);
    ``level="info"`` for a site that says which form it took."""
    if key not in _WARNED:
        _WARNED.add(key)
        from deepspeed_tpu.utils.logging import logger

        getattr(logger, level)(msg)


def configure(
    enabled: Any = None,
    flash_decode: Optional[bool] = None,
    fused_update: Optional[bool] = None,
    autotune: Optional[str] = None,
    autotune_cache_path: Optional[str] = None,
) -> None:
    """Install the ``kernels`` config block's decisions (engine
    constructors call this with their validated config; None leaves a
    field untouched so partial configs compose)."""
    if enabled is not None:
        _STATE["enabled"] = enabled
    if flash_decode is not None:
        _STATE["flash_decode"] = bool(flash_decode)
    if fused_update is not None:
        _STATE["fused_update"] = bool(fused_update)
    if autotune is not None or autotune_cache_path is not None:
        # env stays the top-priority escape hatch: only swap the process
        # tuner when the env is not dictating the mode/path
        mode = None if os.environ.get("DS_KERNEL_AUTOTUNE") else autotune
        path = None if os.environ.get("DS_KERNEL_AUTOTUNE_CACHE") else (
            autotune_cache_path or None
        )
        # re-configuring with the settings the process tuner already has
        # (every engine construction passes the defaults) must NOT drop
        # the in-process LRU and hit/miss stats
        cur = get_autotuner()
        # merge with the current tuner so a partial re-configure (one
        # engine sets the path, another the mode) composes instead of
        # reverting the other field to its default
        new_path = path or cur.path
        new_mode = mode if mode is not None else cur._mode
        if new_path != cur.path or new_mode != cur._mode:
            reset_autotuner(path=new_path, mode=new_mode)


def configure_from_config(config) -> None:
    """Wire a :class:`~deepspeed_tpu.config.config.KernelsConfig` (or an
    object exposing its fields) into the process state."""
    if config is None:
        return
    configure(
        enabled=getattr(config, "enabled", None),
        flash_decode=getattr(config, "flash_decode", None),
        fused_update=getattr(config, "fused_update", None),
        autotune=getattr(config, "autotune", None) or None,
        autotune_cache_path=getattr(config, "autotune_cache_path", None) or None,
    )


def _suite_armed() -> bool:
    env = os.environ.get("DS_KERNELS", "").strip().lower()
    if env in ("1", "on", "true"):
        return True
    if env in ("0", "off", "false"):
        return False
    if env != "auto":
        # no env override: the config layer decides
        enabled = _STATE["enabled"]
        if enabled in (True, False):
            return bool(enabled)
    # auto (explicit env "auto" overrides config, per the escape-hatch
    # contract): device platform "tpu" only — the lax/XLA paths stay the
    # CPU tier-1 ground truth
    return on_tpu_backend()


def flash_decode_armed() -> bool:
    return _suite_armed() and _STATE["flash_decode"]


def fused_update_armed() -> bool:
    return _suite_armed() and _STATE["fused_update"]


def grouped_matmul_armed() -> bool:
    """The held experts' grouped matmul (``grouped_matmul.py``) has no
    knob of its own: it follows the suite."""
    return _suite_armed()


def kernels_report() -> Dict[str, Any]:
    """ds_report rows: which kernels are armed and the autotuner cache
    state (path / entries / hits)."""
    return {
        "suite_armed": _suite_armed(),
        "flash_decode": flash_decode_armed(),
        "fused_update": fused_update_armed(),
        "env": os.environ.get("DS_KERNELS", "") or "(auto)",
        "autotune": get_autotuner().stats(),
    }
