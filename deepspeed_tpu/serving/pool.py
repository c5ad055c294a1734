"""Slot-pool KV cache: a fixed-shape device cache + a host-side slot
allocator.

The pool is ONE pair of ``(layers, num_slots, heads, max_len, head_dim)``
cache buffers (bf16/f32, or the int8 code+scale pair reusing the
``init_kv_cache`` int8 machinery) whose **slot axis is the batch axis**
of the fused inference blocks: every compiled serving step sees the same
shapes no matter which subset of slots is live, so admitting or retiring
a sequence never changes an abstract signature — the no-recompile
property the whole continuous-batching design rests on (docs/serving.md).

The allocator is pure host bookkeeping: ``alloc()`` hands out the
longest-free slot (FIFO over frees, so reuse is fair and stale-cache
paths get exercised), ``free()`` returns it.  Freeing does NOT touch
device memory — a freed slot's stale keys/values are unreachable by
construction (the next occupant's writes start at position 0 and the
position mask only ever exposes positions the occupant itself wrote;
see the overwrite-before-attend invariant in docs/serving.md).
"""
from __future__ import annotations

from collections import deque
from typing import Any, Deque, Dict, Optional

import jax
import numpy as np


class SlotPoolError(RuntimeError):
    pass


class SlotKVPool:
    """Fixed-shape KV slot pool + host-side allocator.

    ``kv_dtype`` follows ``init_kv_cache``: a jnp dtype for the plain
    cache or ``"int8"`` for the quantized code+scale pair.  The device
    buffers live in ``self.k`` / ``self.v``; the serving engine donates
    them through its compiled steps and rebinds the outputs via
    :meth:`swap`.
    """

    def __init__(self, n_layer: int, num_slots: int, heads: int, max_len: int,
                 head_dim: int, kv_dtype: Any, sharding: Any = None):
        from deepspeed_tpu.ops.transformer.inference import init_kv_cache

        if num_slots < 1:
            raise SlotPoolError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 1:
            raise SlotPoolError(f"max_len must be >= 1, got {max_len}")
        self.n_layer = int(n_layer)
        self.num_slots = int(num_slots)
        self.heads = int(heads)
        self.max_len = int(max_len)
        self.head_dim = int(head_dim)
        self.kv_dtype = kv_dtype
        self.k, self.v = init_kv_cache(n_layer, num_slots, heads, max_len, head_dim, kv_dtype)
        if sharding is not None:
            # place on the serving mesh up front — otherwise the first
            # compiled step reshards the pool implicitly (a transfer the
            # ds_san guard rightly flags)
            self.k, self.v = jax.device_put((self.k, self.v), sharding)
        self.state = None  # no slot-axis group beside K and V (PagedKVPool's HybridKV has one)
        self._free: Deque[int] = deque(range(num_slots))
        self._owner: Dict[int, Any] = {}  # slot -> request id

    # -- allocator --------------------------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def live_slots(self) -> int:
        return self.num_slots - len(self._free)

    def owner(self, slot: int) -> Optional[Any]:
        return self._owner.get(slot)

    def owners(self) -> Dict[int, Any]:
        """Snapshot of slot -> request id (the serving drain logs the
        in-flight set a deadline cut off; a copy, safe to iterate while
        the scheduler retires)."""
        return dict(self._owner)

    def alloc(self, request_id: Any) -> Optional[int]:
        """Claim a slot for ``request_id``; None when the pool is full.
        A request id may own at most one slot — a second alloc under the
        same id would orphan the first slot's bookkeeping (its free()
        could land on either slot), so it raises instead."""
        if request_id in self._owner.values():
            raise SlotPoolError(
                f"request {request_id!r} already owns a slot; "
                f"free it before re-allocating"
            )
        if not self._free:
            return None
        slot = self._free.popleft()
        self._owner[slot] = request_id
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._owner:
            raise SlotPoolError(f"slot {slot} is not allocated")
        del self._owner[slot]
        self._free.append(slot)

    # -- device buffers ---------------------------------------------------
    def swap(self, k, v, state=None) -> None:
        """Rebind the cache buffers after a donated compiled step (the
        old arrays were consumed by the donation); ``state`` is what a
        step hands back for the group this pool does not have."""
        self.k, self.v = k, v

    def cache_bytes(self) -> int:
        """HBM bytes held by the pool (both caches, all leaves)."""
        return int(
            sum(l.size * l.dtype.itemsize for l in jax.tree.leaves((self.k, self.v)))
        )

    def shape_math(self) -> str:
        """Human-readable pool sizing (ds_report serving rows)."""
        kind = "int8+f32 scales" if isinstance(self.k, dict) else str(np.dtype(
            jax.tree.leaves(self.k)[0].dtype))
        return (
            f"2 x ({self.n_layer} layers x {self.num_slots} slots x "
            f"{self.heads} heads x {self.max_len} positions x "
            f"{self.head_dim} head_dim) [{kind}] = "
            f"{self.cache_bytes() / 1e6:.1f} MB"
        )
