"""Overlap subsystem: keep the accelerator busy while the host moves bytes.

The reference DeepSpeed hides host work behind device compute (pinned-
memory input pipelines, overlapped collectives, background NVMe swaps in
ZeRO-Infinity).  This package is the TPU-native expression of the same
principle, attacking the two biggest host-side stalls of a JAX training
loop plus the instrumentation to prove it:

* :mod:`~deepspeed_tpu.runtime.overlap.prefetch` —
  :class:`DevicePrefetcher`, a two-stage (load / sharded ``device_put``)
  pipelined input prefetcher (``engine.prefetch_loader`` routes here);
* :mod:`~deepspeed_tpu.runtime.overlap.async_writer` —
  :class:`AsyncCheckpointWriter`, background stage->manifest->rename
  checkpoint commits with drain semantics (``overlap.async_checkpoint``
  config block; durability contract unchanged from docs/resilience.md);
* :mod:`~deepspeed_tpu.runtime.overlap.timeline` —
  :class:`StepTimeline`, honest (fenced) per-step attribution of wall
  time to ``data_wait`` / ``compute`` / ``ckpt_stall`` / ``compile`` /
  ``other``, exported through the telemetry plane and ``ds_report``;
* :mod:`~deepspeed_tpu.runtime.overlap.worker` —
  :class:`BoundedWorker`, the shared bounded-queue background thread
  (serving KV tier migration rides on it; see
  ``deepspeed_tpu/serving/kvcache/tiers.py``).

See ``docs/performance.md`` for the architecture and the config knobs.
"""
from deepspeed_tpu.runtime.overlap.async_writer import (  # noqa: F401
    AsyncCheckpointWriter,
    PendingSave,
)
from deepspeed_tpu.runtime.overlap.prefetch import (  # noqa: F401
    DevicePrefetcher,
    InlineLoader,
    inline_loader,
)
from deepspeed_tpu.runtime.overlap.timeline import PHASES, StepTimeline  # noqa: F401
from deepspeed_tpu.runtime.overlap.worker import BoundedWorker  # noqa: F401
