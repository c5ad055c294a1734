"""Sharding-aware double-buffered input prefetch.

The engine's compiled step dispatches asynchronously; what serializes a
training loop is the host work per batch — pulling the next batch out of
the loader (tokenization, disk reads) and ``jax.device_put`` of it with
the engine's batch sharding (host-side staging and, for a sharded batch,
one slice per device).  :class:`DevicePrefetcher` runs both ahead of the
consumer as a two-stage pipeline:

    loader thread:  ``next(loader)``      -> bounded queue (depth N)
    place  thread:  ``place_fn(batch)``   -> bounded queue (depth N)
    consumer:       pops device-resident batches; the jitted step never
                    waits on host transfer while the pipeline keeps up

Each stage is backpressured by its queue (``depth`` batches in flight
per stage), so host memory is bounded at ``~2*depth`` batches.  The
consumer-side queue wait — the time the accelerator would have idled on
input — is reported to the engine's ``StepTimeline`` as ``data_wait``.

Exceptions raised by the loader or the placement function are re-raised
in the consumer at the position they occurred; iteration order is
preserved exactly.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, List, Optional

from deepspeed_tpu.runtime.dataloader import ResumableWrapperMixin


class _End:
    """Sentinel: the upstream stage is exhausted."""


class _Raised:
    """Sentinel wrapper: the upstream stage raised."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


_ABORT = object()  # returned by _get when the pipeline is being closed


def _put(q: "queue.Queue", item: Any, stop: threading.Event) -> bool:
    """Blocking put that aborts when ``stop`` is set, so a worker blocked
    on a full queue can never outlive :meth:`DevicePrefetcher.close`."""
    while True:
        try:
            q.put(item, timeout=0.05)
            return True
        except queue.Full:
            if stop.is_set():
                return False


def _get(q: "queue.Queue", stop: threading.Event) -> Any:
    """Blocking get with the same abort contract as :func:`_put`."""
    while True:
        try:
            return q.get(timeout=0.05)
        except queue.Empty:
            if stop.is_set():
                return _ABORT


def _load_worker(it, out_q: "queue.Queue", stop: threading.Event) -> None:
    while not stop.is_set():
        try:
            item = next(it)
        except StopIteration:
            item = _End()
        except BaseException as e:  # noqa: BLE001 — re-raised in the consumer
            item = _Raised(e)
        if not _put(out_q, item, stop):
            return
        if isinstance(item, (_End, _Raised)):
            return


def _place_worker(place: Callable[[Any], Any], in_q: "queue.Queue", out_q: "queue.Queue", stop: threading.Event) -> None:
    while not stop.is_set():
        item = _get(in_q, stop)
        if item is _ABORT:
            return
        if not isinstance(item, (_End, _Raised)):
            try:
                item = place(item)
            except BaseException as e:  # noqa: BLE001 — re-raised in the consumer
                item = _Raised(e)
        if not _put(out_q, item, stop):
            return
        if isinstance(item, (_End, _Raised)):
            return


class DevicePrefetcher(ResumableWrapperMixin):
    """Wraps a host batch iterator with pipelined load + device placement.

    ``place_fn``: host batch -> device-resident batch (the engine passes
    its stack-micro-batches + sharded ``device_put``); when omitted,
    ``sharding`` (a pytree of shardings, or None for default placement)
    drives a plain ``jax.device_put``.

    ``depth``: batches in flight per stage (2 = double buffering).

    ``timeline``: optional ``StepTimeline``; consumer-side queue waits
    are noted as ``data_wait``.

    ``sanitizer``: optional ds_san :class:`Sanitizer`; the place stage
    then runs under its transfer guard (region ``prefetch.place``), so a
    loader that smuggles implicit host↔device transfers into the
    pipeline is attributed instead of silently re-staging every batch.
    Violations re-raise in the consumer like any other place error.
    """

    def __init__(
        self,
        loader: Iterable,
        depth: int = 2,
        place_fn: Optional[Callable[[Any], Any]] = None,
        sharding: Any = None,
        timeline: Any = None,
        sanitizer: Any = None,
    ):
        self.loader = loader
        self.depth = max(1, int(depth))
        self.sharding = sharding
        self.place_fn = place_fn
        self.timeline = timeline
        self.sanitizer = sanitizer
        self._stop: Optional[threading.Event] = None
        self._threads: List[threading.Thread] = []

    def _place_inner(self, batch: Any) -> Any:
        if self.place_fn is not None:
            return self.place_fn(batch)
        import jax

        if self.sharding is not None:
            return jax.device_put(batch, self.sharding)
        return jax.device_put(batch)

    def _place(self, batch: Any) -> Any:
        if self.sanitizer is None:
            return self._place_inner(batch)
        # jax's transfer-guard context is thread-local, so arming it on
        # the place worker cannot leak into the consumer's own guards
        with self.sanitizer.transfer.guard("prefetch.place"):
            return self._place_inner(batch)

    def __iter__(self):
        self.close()  # a fresh iteration owns fresh threads/queues
        it = iter(self.loader)
        self._capture_base()
        stop = threading.Event()
        self._stop = stop
        loaded: "queue.Queue" = queue.Queue(maxsize=self.depth)
        placed: "queue.Queue" = queue.Queue(maxsize=self.depth)
        threads = [
            threading.Thread(
                target=_load_worker, args=(it, loaded, stop),
                daemon=True, name="ds-prefetch-load",
            ),
            threading.Thread(
                target=_place_worker, args=(self._place, loaded, placed, stop),
                daemon=True, name="ds-prefetch-place",
            ),
        ]
        self._threads = threads
        for t in threads:
            t.start()
        return self._consume(placed)

    def _consume(self, placed: "queue.Queue"):
        try:
            while True:
                t0 = time.perf_counter()
                item = placed.get()
                if self.timeline is not None:
                    self.timeline.note("data_wait", time.perf_counter() - t0)
                if isinstance(item, _End):
                    return
                if isinstance(item, _Raised):
                    raise item.exc
                self._served += 1
                yield item
        finally:
            self.close()

    def close(self) -> None:
        """Stop the pipeline threads (idempotent; runs automatically when
        iteration ends or the consumer breaks out)."""
        if self._stop is not None:
            self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)
        self._threads = []
        self._stop = None

    def __len__(self):
        try:
            return len(self.loader)
        except TypeError:
            raise TypeError("wrapped loader is a generator with no len()") from None


class InlineLoader:
    """The unoverlapped fallback (``overlap.prefetch.enabled = false``):
    same interface as :class:`DevicePrefetcher` — re-iterable, with
    ``__len__`` — but synchronous load + place on the consumer thread,
    so swapping the knob never changes iteration semantics."""

    def __init__(
        self,
        loader: Iterable,
        place_fn: Callable[[Any], Any],
        timeline: Any = None,
        sanitizer: Any = None,
    ):
        self.loader = loader
        self.place_fn = place_fn
        self.timeline = timeline
        if sanitizer is not None:
            self.place_fn = sanitizer.transfer.wrap_callable(place_fn, "prefetch.place")

    def state_dict(self) -> Optional[dict]:
        # synchronous wrap: the inner cursor tracks consumption exactly
        fn = getattr(self.loader, "state_dict", None)
        return dict(fn()) if fn is not None else None

    def load_state_dict(self, sd: dict) -> None:
        fn = getattr(self.loader, "load_state_dict", None)
        if fn is not None:
            fn(sd)

    def __iter__(self):
        it = iter(self.loader)
        while True:
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                return
            out = self.place_fn(batch)
            if self.timeline is not None:
                self.timeline.note("data_wait", time.perf_counter() - t0)
            yield out

    def __len__(self):
        try:
            return len(self.loader)
        except TypeError:
            raise TypeError("wrapped loader is a generator with no len()") from None


def inline_loader(loader: Iterable, place_fn: Callable[[Any], Any], timeline: Any = None):
    """Back-compat alias for :class:`InlineLoader`."""
    return InlineLoader(loader, place_fn, timeline=timeline)
