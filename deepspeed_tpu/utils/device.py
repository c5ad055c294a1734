"""What device this process runs on, and where its caches live.

One home for the three facts the kernels, the entry points and the
benchmarks key on: the device platform, whether Pallas kernels compile
(Mosaic) or interpret, and the directory the XLA compile cache and the
kernel autotune cache share.  A backend that fails to initialise is an
error here, never "not a TPU".
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def device_platform() -> str:
    return jax.devices()[0].platform


def on_tpu_backend() -> bool:
    """Arms the Pallas suite (``kernels.enabled: "auto"``) and selects
    the TPU shapes in the tools."""
    return device_platform() == "tpu"


def pallas_interpret_default() -> bool:
    """Pallas kernels run in the interpreter on the CPU only (tests);
    every other platform hands them to its compiler, which fails loudly
    where it cannot build them."""
    return device_platform() == "cpu"


def cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    must not move between runs)."""
    return os.environ.get(_CACHE_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def setup_compile_cache() -> Optional[str]:
    """Turn on XLA's persistent compile cache; returns its directory, or
    None where none is used.  With ``JAX_COMPILATION_CACHE_DIR`` set JAX
    reads it itself and nothing is set in code.  Otherwise the cache
    goes to :func:`cache_dir` on a TPU only: XLA:CPU executables cached
    on these virtual machines were observed to load under a different
    CPU feature set and corrupt numerics (tests/conftest.py)."""
    if os.environ.get(_CACHE_ENV):
        return os.environ[_CACHE_ENV]
    if not on_tpu_backend():
        return None
    jax.config.update("jax_compilation_cache_dir", cache_dir())
    return cache_dir()
