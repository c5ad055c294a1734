"""Native (C++) op JIT builder.

The TPU-native remnant of the reference's ``op_builder/`` ninja JIT
(``builder.py:349-390``): device compute needs no build step (XLA/Pallas
compile at trace time), so the only native code left is **host-side** —
the async disk I/O engine (``csrc/aio``) and the SIMD host optimizer
(``csrc/adam``) used by ZeRO-Offload/Infinity.  Those are compiled here
with g++ at first use into a shared library loaded via ctypes, cached by
source hash (rebuild on source change) and, for ``-march=native``
builds, by the CPU they were compiled for — a tree copied to another
machine rebuilds instead of loading foreign code — mirroring the
reference's compile-at-first-use contract without torch cpp_extension.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from typing import List, Optional

from deepspeed_tpu.utils.logging import logger

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC_DIR = os.path.join(os.path.dirname(_PKG_DIR), "csrc")
BUILD_DIR = os.path.join(CSRC_DIR, "build")

BASE_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-fopenmp", "-Wall"]
ARCH_FLAGS = ["-march=native", "-funroll-loops"]


def _machine_tag() -> str:
    """What ``-march=native`` resolves against: the CPU architecture and
    its feature flags."""
    feats = platform.processor()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    feats = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return f"{platform.machine()} {feats}"


def _source_hash(paths: List[str], flags: List[str]) -> str:
    h = hashlib.sha256()
    for p in sorted(paths):
        with open(p, "rb") as f:
            h.update(f.read())
    h.update(" ".join(flags).encode())
    if "-march=native" in flags:
        h.update(_machine_tag().encode())
    return h.hexdigest()[:16]


def has_compiler() -> bool:
    try:
        subprocess.run(["g++", "--version"], capture_output=True, check=True)
        return True
    except Exception:
        return False


def build_native(name: str, sources: List[str], extra_flags: Optional[List[str]] = None) -> str:
    """Compile ``sources`` (paths relative to csrc/) into
    ``csrc/build/<name>-<hash>.so`` and return the path.  Raises on
    compiler failure — callers fall back to their Python implementation
    (the reference's ``is_compatible`` contract)."""
    srcs = [s if os.path.isabs(s) else os.path.join(CSRC_DIR, s) for s in sources]
    flags = BASE_FLAGS + ARCH_FLAGS + (extra_flags or [])
    tag = _source_hash(srcs, flags)
    out = os.path.join(BUILD_DIR, f"{name}-{tag}.so")
    if os.path.exists(out):
        return out
    os.makedirs(BUILD_DIR, exist_ok=True)
    cmd = ["g++", *flags, *srcs, "-o", out]
    try:
        subprocess.run(cmd, capture_output=True, check=True, text=True)
    except subprocess.CalledProcessError as e:
        # -march=native can fail in emulated/cross environments; retry portable
        logger.warning(f"native build of '{name}' failed with arch flags, retrying portable: {e.stderr[-500:]}")
        flags = BASE_FLAGS + (extra_flags or [])
        tag = _source_hash(srcs, flags)
        out = os.path.join(BUILD_DIR, f"{name}-{tag}.so")
        if not os.path.exists(out):
            cmd = ["g++", *flags, *srcs, "-o", out]
            res = subprocess.run(cmd, capture_output=True, text=True)
            if res.returncode != 0:
                raise RuntimeError(f"native build of '{name}' failed:\n{res.stderr[-2000:]}") from None
    logger.info(f"built native op '{name}' -> {out}")
    return out


def load_native(name: str, sources: List[str], extra_flags: Optional[List[str]] = None) -> ctypes.CDLL:
    return ctypes.CDLL(build_native(name, sources, extra_flags))
