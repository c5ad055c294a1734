"""Persistent block-size autotuner for the Pallas kernel suite.

The reference hand-picked tile shapes per CUDA kernel and shipped them
as compile-time constants (``csrc/``); on TPU the right (block_q,
block_k, block_slots) depends on shape, dtype, topology AND jaxlib
version, so hardcoding loses measurable throughput on every new
deployment.  This module is the one home for that decision:

* **Deterministic defaults** (``default_blocks``): a table keyed by
  kernel kind + shape class.  CI and tier-1 only ever see this path —
  tuning never runs unless explicitly requested, so compiled artifacts
  are reproducible.
* **Measured search** (``Autotuner.tune``): times a caller-supplied
  closure per candidate and records the winner.  Tuning is a HOST-side
  pre-trace step (you cannot time anything inside a jit trace): the
  bench harness / an engine warmup calls it before executables build,
  trace-time lookups are pure dict reads.
* **Persistence**: winners land in a JSON cache next to XLA's
  persistent compile cache (same lifecycle: both survive restarts,
  both key on the jaxlib fingerprint), fronted by an in-process LRU.
  A corrupt or unreadable cache degrades to the defaults table with a
  warning — never an exception on the serving path.

Escape hatch: ``DS_KERNEL_AUTOTUNE={off,cache,force}`` (default
``cache``).  ``off`` ignores the cache entirely (pure defaults),
``cache`` reads-but-never-measures, ``force`` allows ``tune()`` to
re-measure even over an existing entry.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger

_LRU_MAX = 256
_CACHE_VERSION = 1

_VALID_MODES = ("off", "cache", "force")


def autotune_mode() -> str:
    """Resolve ``DS_KERNEL_AUTOTUNE``; unknown values degrade to
    ``cache`` with a warning (an env typo must not flip CI to tuning)."""
    mode = os.environ.get("DS_KERNEL_AUTOTUNE", "cache").strip().lower()
    if mode not in _VALID_MODES:
        logger.warning(
            f"DS_KERNEL_AUTOTUNE={mode!r} not in {_VALID_MODES}; using 'cache'"
        )
        return "cache"
    return mode


def default_cache_path() -> str:
    """The cache file rides in XLA's persistent compile cache directory
    (same lifecycle and cleanup story; ``utils.device.cache_dir``).
    ``DS_KERNEL_AUTOTUNE_CACHE`` overrides."""
    from deepspeed_tpu.utils.device import cache_dir

    return os.environ.get("DS_KERNEL_AUTOTUNE_CACHE") or os.path.join(
        cache_dir(), "kernel_autotune.json"
    )


def _jaxlib_fingerprint() -> str:
    try:
        import jax
        import jaxlib

        return f"{jax.__version__}/{jaxlib.__version__}"
    except Exception:  # noqa: BLE001
        return "nojax"


def _topology_fingerprint() -> str:
    try:
        import jax

        devs = jax.devices()
        return f"{devs[0].device_kind}x{len(devs)}"
    except Exception:  # noqa: BLE001
        return "unknown"


def fingerprint(kind: str, **key: Any) -> str:
    """Stable cache key: kernel kind + sorted shape/dtype facts +
    (device kind × count) + jaxlib version.  A new jaxlib or topology
    re-tunes rather than trusting a stale winner."""
    parts = [kind] + [f"{k}={key[k]}" for k in sorted(key)]
    parts.append(f"topo={_topology_fingerprint()}")
    parts.append(f"jaxlib={_jaxlib_fingerprint()}")
    return "|".join(parts)


# ---------------------------------------------------------------------------
# deterministic defaults (the only path CI / tier-1 ever takes)
# ---------------------------------------------------------------------------

def _divisor_floor(n: int, pref: int, floor: int = 128) -> int:
    """Largest power-of-two-ish block <= pref that divides n (the same
    halving search flash_attention.pick uses); n itself when nothing
    >= floor divides."""
    b = min(pref, n)
    if n % b == 0:
        return b
    while b > floor:
        b //= 2
        if n % b == 0:
            return b
    return n


def default_blocks(kind: str, **key: Any) -> Dict[str, int]:
    """Table-driven defaults per kernel kind.

    * ``flash_decode``: ``block_k`` grows with context (more kv rows per
      program amortize the DMA prologue; int8 packs 2× the elements per
      byte so it takes the larger block a step earlier), ``block_slots``
      groups pool slots per program when the pool is wide and the
      context short (program-count bound).
    * ``flash_attention``: the measured (512, 512) train-step winner
      (see flash_attention.py block_q/block_k docstring).
    """
    if kind == "flash_decode":
        S = int(key.get("S", 1024))
        int8 = bool(key.get("int8", False))
        pref = 1024 if (S >= 8192 or (int8 and S >= 4096)) else (512 if S >= 2048 else 256)
        block_k = _divisor_floor(S, pref)
        B = int(key.get("B", 1))
        block_slots = 1
        if S <= 1024 and B >= 8:
            for cand in (4, 2):
                if B % cand == 0:
                    block_slots = cand
                    break
        return {"block_k": block_k, "block_slots": block_slots}
    if kind == "flash_attention":
        sq = int(key.get("sq", 512))
        sk = int(key.get("sk", sq))
        return {
            "block_q": _divisor_floor(sq, 512),
            "block_k": _divisor_floor(sk, 512),
        }
    raise KeyError(f"no default block table for kernel kind {kind!r}")


def candidate_blocks(kind: str, **key: Any) -> List[Dict[str, int]]:
    """The measured-search space per kind (every candidate must divide
    the relevant dims; generated, not hardcoded, so ragged shapes never
    produce an invalid grid)."""
    out: List[Dict[str, int]] = []
    if kind == "flash_decode":
        S = int(key.get("S", 1024))
        B = int(key.get("B", 1))
        ks = sorted({_divisor_floor(S, p) for p in (256, 512, 1024, 2048) if p <= max(S, 128)})
        slots = sorted({s for s in (1, 2, 4, 8) if s <= B and B % s == 0})
        for bk in ks:
            for bs in slots:
                out.append({"block_k": bk, "block_slots": bs})
    elif kind == "flash_attention":
        sq, sk = int(key.get("sq", 512)), int(key.get("sk", 512))
        qs = sorted({_divisor_floor(sq, p) for p in (256, 512, 1024)})
        kks = sorted({_divisor_floor(sk, p) for p in (256, 512, 1024)})
        out = [{"block_q": q, "block_k": k} for q in qs for k in kks]
    if not out:
        out = [default_blocks(kind, **key)]
    return out


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------

class Autotuner:
    """Fingerprint → winning blocks, with an in-process LRU over a JSON
    file.  Thread-safe (the serving engine and a bench warmup may race
    a lookup); file writes are atomic (tmp + replace)."""

    def __init__(self, path: Optional[str] = None, mode: Optional[str] = None,
                 lru_max: int = _LRU_MAX):
        self.path = path or default_cache_path()
        self._mode = mode
        self._lru: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._lru_max = lru_max
        self._lock = threading.RLock()
        self._disk: Optional[Dict[str, Any]] = None  # lazy, None = not loaded
        self._disk_ok = True
        self.hits = 0
        self.misses = 0
        self.tunes = 0

    # -- mode ------------------------------------------------------------
    @property
    def mode(self) -> str:
        return self._mode or autotune_mode()

    # -- disk ------------------------------------------------------------
    def _load_disk(self) -> Dict[str, Any]:
        if self._disk is not None:
            return self._disk
        entries: Dict[str, Any] = {}
        try:
            if os.path.exists(self.path):
                with open(self.path) as f:
                    doc = json.load(f)
                if not isinstance(doc, dict) or "entries" not in doc or not isinstance(
                    doc["entries"], dict
                ):
                    raise ValueError("autotune cache: missing/invalid 'entries' map")
                for k, v in doc["entries"].items():
                    if not (isinstance(v, dict) and isinstance(v.get("blocks"), dict)):
                        raise ValueError(f"autotune cache: malformed entry {k!r}")
                entries = doc["entries"]
        except Exception as e:  # noqa: BLE001 — corrupt cache degrades to defaults
            logger.warning(
                f"kernel autotune cache at {self.path!r} unreadable ({e!r}); "
                "falling back to the deterministic defaults table"
            )
            self._disk_ok = False
            entries = {}
        self._disk = entries
        return entries

    def _save_disk(self) -> None:
        if not self._disk_ok:
            return  # never overwrite a cache we could not parse
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"version": _CACHE_VERSION, "entries": self._disk or {}}, f, indent=1)
            os.replace(tmp, self.path)
        except OSError as e:
            logger.warning(f"kernel autotune cache write failed ({e}); tuning not persisted")

    # -- lookup ----------------------------------------------------------
    def lookup(self, fp: str) -> Optional[Dict[str, int]]:  # ds-race: entry
        """Cached blocks for a fingerprint, or None.  Mode ``off`` never
        consults the cache (pure defaults — the CI determinism story)."""
        if self.mode == "off":
            return None
        with self._lock:
            if fp in self._lru:
                self._lru.move_to_end(fp)
                self.hits += 1
                return dict(self._lru[fp]["blocks"])
            entry = self._load_disk().get(fp)
            if entry is not None:
                self._lru[fp] = entry
                while len(self._lru) > self._lru_max:
                    self._lru.popitem(last=False)
                self.hits += 1
                return dict(entry["blocks"])
            self.misses += 1
            return None

    def blocks_for(self, kind: str, **key: Any) -> Dict[str, int]:  # ds-race: entry
        """The trace-time entry point: cached winner when one exists,
        else the defaults table.  Never measures, never raises."""
        try:
            cached = self.lookup(fingerprint(kind, **key))
        except Exception as e:  # noqa: BLE001 — a broken cache must not break a trace
            logger.warning(f"kernel autotune lookup failed ({e!r}); using defaults")
            cached = None
        if cached is not None:
            return cached
        return default_blocks(kind, **key)

    # -- record / tune ---------------------------------------------------
    def record(self, fp: str, blocks: Dict[str, int], measured_ms: float) -> None:
        with self._lock:
            entry = {
                "blocks": dict(blocks),
                "ms": round(float(measured_ms), 6),
                "ts": time.time(),
            }
            self._load_disk()[fp] = entry
            self._lru[fp] = entry
            while len(self._lru) > self._lru_max:
                self._lru.popitem(last=False)
            self._save_disk()

    def tune(  # ds-race: entry — a bench warmup thread tunes while the engine serves
        self,
        kind: str,
        timer: Callable[[Dict[str, int]], float],
        candidates: Optional[Iterable[Dict[str, int]]] = None,
        **key: Any,
    ) -> Dict[str, int]:
        """Measured search: ``timer(blocks) -> seconds`` per candidate
        (the caller owns warmup + block_until_ready fencing), best
        recorded and returned.  Outside ``force`` mode an existing cache
        entry short-circuits the search (``cache`` = read-mostly); mode
        ``off`` returns the defaults without measuring at all."""
        mode = self.mode
        fp = fingerprint(kind, **key)
        if mode == "off":
            return default_blocks(kind, **key)
        if mode != "force":
            cached = self.lookup(fp)
            if cached is not None:
                return cached
        best: Optional[Tuple[float, Dict[str, int]]] = None
        failures = 0
        cands = list(candidates) if candidates is not None else candidate_blocks(kind, **key)
        for blocks in cands:
            try:
                dt = float(timer(dict(blocks)))
            except Exception as e:  # noqa: BLE001 — an invalid candidate is data, not death
                logger.warning(f"autotune[{kind}] candidate {blocks} failed: {e!r}")
                failures += 1
                continue
            if best is None or dt < best[0]:
                best = (dt, dict(blocks))
        if best is None:
            logger.warning(
                f"autotune[{kind}]: all {failures} candidate(s) failed; using defaults"
            )
            return default_blocks(kind, **key)
        with self._lock:
            # same lock stats() reads under — an unlocked += here loses
            # counts when two warmup threads tune concurrently
            self.tunes += 1
        self.record(fp, best[1], best[0] * 1e3)
        logger.info(
            f"autotune[{kind}] {fp.split('|topo=')[0]}: picked {best[1]} "
            f"({best[0] * 1e3:.3f} ms over {len(cands)} candidate(s))"
        )
        return best[1]

    # -- reporting (ds_report kernels rows) -------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            disk = self._load_disk()
            return {
                "mode": self.mode,
                "path": self.path,
                "entries": len(disk),
                "lru": len(self._lru),
                "hits": self.hits,
                "misses": self.misses,
                "tunes": self.tunes,
                "cache_ok": self._disk_ok,
            }


_GLOBAL: Optional[Autotuner] = None
_GLOBAL_LOCK = threading.Lock()


def get_autotuner() -> Autotuner:
    """Process-wide tuner (the LRU only helps if everyone shares it)."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = Autotuner()
    return _GLOBAL


def reset_autotuner(path: Optional[str] = None, mode: Optional[str] = None) -> Autotuner:
    """Swap the process tuner (tests; a config with an explicit cache
    path).  Returns the new instance."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = Autotuner(path=path, mode=mode)
    return _GLOBAL
