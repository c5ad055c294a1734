"""Data loading.

Analog of the reference's ``runtime/dataloader.py``
(``DeepSpeedDataLoader`` :33 with ``DistributedSampler``;
``RepeatingLoader`` :10).  On TPU the "distributed sampler" story changes:
within one process, SPMD sharding of the batch across the (data, fsdp)
mesh axes replaces per-rank samplers; across hosts, each process loads its
``jax.process_index()`` slice and the engine assembles a global array.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence

import jax
import numpy as np


class RepeatingLoader:
    """Wrap an iterator to auto-restart at StopIteration (reference :10)."""

    def __init__(self, loader: Iterable):
        self.loader = loader
        self.data_iter = iter(self.loader)

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self.data_iter)
        except StopIteration:
            self.data_iter = iter(self.loader)
            return next(self.data_iter)

    def __len__(self):
        return len(self.loader)


class DeepSpeedDataLoader:
    """Batches an indexable dataset of pytrees/arrays.

    ``dataset`` may be: a dict/tuple of equal-length numpy arrays, or a
    sequence of per-example pytrees (collated by stacking).  Yields
    host numpy batches of size ``batch_size`` (the per-process batch =
    micro_batch × local share of the DP world); the engine device_puts
    them with the right sharding.
    """

    def __init__(
        self,
        dataset: Any,
        batch_size: int,
        shuffle: bool = False,
        seed: int = 0,
        drop_last: bool = True,
        collate_fn: Optional[Callable] = None,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
    ):
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.collate_fn = collate_fn
        self.process_index = process_index if process_index is not None else jax.process_index()
        self.process_count = process_count if process_count is not None else jax.process_count()
        self.epoch = 0
        # resumable-cursor state (docs/resilience.md): the checkpoint
        # client_state records (epoch, cursor, seed) so a restarted job
        # neither replays nor skips batches.  The cursor counts batches
        # YIELDED in the current iteration; load_state_dict arms a skip
        # for the next __iter__.
        self._cursor = 0
        self._start = 0

        # Columnar = dict (or tuple) of equal-length arrays, one row per
        # example.  A *list* is always treated as a sequence of per-example
        # pytrees — a list of equal-shape arrays is ambiguous, and rows win.
        self._columnar = isinstance(dataset, (dict, tuple)) and all(
            isinstance(x, np.ndarray) for x in jax.tree.leaves(dataset)
        )
        if self._columnar:
            lengths = {len(x) for x in jax.tree.leaves(dataset)}
            if len(lengths) != 1:
                raise ValueError(f"columnar dataset has unequal column lengths: {sorted(lengths)}")
            self._n = lengths.pop()
        else:
            self._n = len(dataset)

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def state_dict(self) -> dict:
        """Resume cursor: epoch + batches yielded this iteration + the
        shuffle seed (the epoch-derived RNG key is ``seed + epoch``, so
        (seed, epoch) IS the shuffle RNG state)."""
        return {"epoch": int(self.epoch), "cursor": int(self._cursor), "seed": int(self.seed)}

    def load_state_dict(self, sd: dict) -> None:
        """Restore a cursor saved by :meth:`state_dict`: the next
        ``__iter__`` recreates the same permutation and skips the
        already-consumed batches — no replays, no skips."""
        self.epoch = int(sd.get("epoch", 0))
        self.seed = int(sd.get("seed", self.seed))
        self._start = int(sd.get("cursor", 0))
        self._cursor = self._start

    def __len__(self) -> int:
        per_proc = self._n // self.process_count
        if self.drop_last:
            return per_proc // self.batch_size
        return math.ceil(per_proc / self.batch_size)

    def __iter__(self) -> Iterator[Any]:
        # eager prologue (the cursor reset must happen at iter() time,
        # not first next(): wrappers read the cursor between the two)
        start, self._start = self._start, 0
        self._cursor = start
        idx = np.arange(self._n)
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self.epoch)
            rng.shuffle(idx)
        # contiguous per-process shard (DistributedSampler semantics)
        per_proc = self._n // self.process_count
        idx = idx[self.process_index * per_proc : (self.process_index + 1) * per_proc]
        return self._generate(idx, start)

    def _generate(self, idx: np.ndarray, start: int) -> Iterator[Any]:
        n_batches = len(self)
        for b in range(start, n_batches):
            sel = idx[b * self.batch_size : (b + 1) * self.batch_size]
            if len(sel) == 0:
                return
            if self._columnar:
                batch = jax.tree.map(lambda col: col[sel], self.dataset)
            else:
                examples = [self.dataset[int(i)] for i in sel]
                if self.collate_fn is not None:
                    batch = self.collate_fn(examples)
                else:
                    batch = jax.tree.map(lambda *xs: np.stack(xs), *examples)
            # cursor advances at hand-off: a checkpoint taken after this
            # batch's step must resume at b + 1
            self._cursor = b + 1
            yield batch


class ResumableWrapperMixin:
    """Consumed-cursor bookkeeping shared by loader wrappers that pull
    AHEAD of training (``DevicePrefetchLoader`` here, the overlap
    ``DevicePrefetcher``): the checkpointable cursor is the inner
    loader's cursor at iteration start plus the batches actually handed
    to training — never the inner loader's own cursor, which runs ahead
    by up to the prefetch depth.  Wrappers call :meth:`_capture_base`
    right after ``iter(self.loader)`` (the inner loader's eager
    prologue has applied any resume skip by then) and bump ``_served``
    at each yield."""

    _served = 0
    _base_state: Optional[dict] = None

    def _capture_base(self) -> None:
        fn = getattr(self.loader, "state_dict", None)
        self._base_state = dict(fn()) if fn is not None else None
        self._served = 0

    def state_dict(self) -> Optional[dict]:
        """None when the wrapped loader has no state protocol (the
        checkpoint then simply carries no resume cursor)."""
        if self._base_state is not None:
            base = dict(self._base_state)
            base["cursor"] = int(base.get("cursor", 0)) + self._served
            return base
        fn = getattr(self.loader, "state_dict", None)
        return dict(fn()) if fn is not None else None

    def load_state_dict(self, sd: dict) -> None:
        fn = getattr(self.loader, "load_state_dict", None)
        if fn is None:
            return
        fn(sd)
        self._served = 0
        self._base_state = None


class DevicePrefetchLoader(ResumableWrapperMixin):
    """Wraps any batch iterator with ahead-of-time ``jax.device_put``.

    NOTE: ``engine.prefetch_loader`` now routes through the two-stage
    pipelined ``runtime.overlap.DevicePrefetcher`` (load and place
    overlap each other AND the step); this single-worker wrapper stays
    for direct users of the plain ``device_put`` path.

    The engine's compiled step dispatches asynchronously; what the host
    still does in line is the per-step host→device input transfer.
    Keeping ``prefetch_depth`` batches in flight overlaps the next
    transfers with the current step — the JAX-native equivalent of the
    reference dataloader's pinned-memory + non-blocking H2D copies.

    ``sharding``: optional pytree/str of shardings passed to
    ``device_put`` (defaults to the engine's batch placement when driven
    through ``engine.train_batch``, which treats already-device-resident
    arrays as a no-op).
    """

    def __init__(self, loader: Iterable, prefetch_depth: int = 2, sharding=None, transform=None):
        self.loader = loader
        self.prefetch_depth = max(1, int(prefetch_depth))
        self.sharding = sharding
        # optional host-side transform + placement combo (e.g. the
        # engine's stack-micro-batches + shard put); overrides the
        # default device_put when given
        self.transform = transform

    def __iter__(self):
        it = iter(self.loader)
        self._capture_base()
        return self._pipeline(it)

    def _pipeline(self, it):
        import collections
        from concurrent.futures import ThreadPoolExecutor

        import jax

        def put(batch):
            if self.transform is not None:
                return self.transform(batch)
            if self.sharding is not None:
                return jax.device_put(batch, self.sharding)
            return jax.device_put(batch)

        # device_put stages the batch on the calling thread — run it in
        # a worker thread so transfers overlap the compiled step instead
        # of serializing with it
        queue = collections.deque()
        with ThreadPoolExecutor(max_workers=1) as pool:
            try:
                for _ in range(self.prefetch_depth):
                    queue.append(pool.submit(put, next(it)))
            except StopIteration:
                pass
            while queue:
                out = queue.popleft()
                try:
                    queue.append(pool.submit(put, next(it)))
                except StopIteration:
                    pass
                result = out.result()
                self._served += 1
                yield result

    def __len__(self):
        try:
            return len(self.loader)
        except TypeError:
            raise TypeError("wrapped loader is a generator with no len()") from None
