"""ServingEngine: continuous-batching decode against one compiled
executable.

Sits on top of an :class:`~deepspeed_tpu.inference.engine.InferenceEngine`
(whose params/mesh/dtype it reuses) and replaces the closed
``generate()`` loop with a request stream:

* ``submit()`` — admission-controlled (queue bound, per-request
  queue-wait deadlines, capacity validation with the derived numbers);
* ``step()`` — one scheduler tick: expire/admit, up to
  ``prefill_chunks_per_step`` prompt chunks, then ONE decode step over
  the whole slot pool;
* ``drain()`` — run until every request finishes, return the results.

A step hands its programs to the device ahead of the host's reads, so
a chunk that is not its prompt's last runs while the host turns the
step (:meth:`ServingEngine._step_programs_overlapped`; the default since
PR 50).  ``serving.overlap_chunks: false`` keeps the serial step — each
program dispatched, then read back, the chunks before the decode step —
which the equality tests compare against.

Exactly **two** executables serve any churning live set: a prefill-chunk
step (fixed ``(1, prefill_chunk)`` tokens, traced slot + position
scalars) and a decode step (fixed ``(num_slots, 1)`` tokens, traced
per-slot position vector).  Admitting, retiring, or chunk-advancing
sequences only changes *values*, never abstract signatures — proven
under an armed ds_san run (tests/test_serving.py) rather than asserted.
Both executables donate the cache pool, so the slot cache is updated
in place.  Decoding is greedy by default (``generate(do_sample=False)``
bit-parity); per-request sampling (``submit(do_sample=True,
temperature=..., top_k=..., seed=...)``) rides the same fixed signature
as per-slot vectors — temperature/top-k/seed per slot, keys derived
from (seed, position) so outputs are reproducible regardless of slot
assignment or pool churn.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu import telemetry as _telemetry
from deepspeed_tpu.analysis.shard import hooks as shard_hooks
from deepspeed_tpu.config.config import DeepSpeedConfigError, ServingConfig
from deepspeed_tpu.resilience import faults
from deepspeed_tpu.serving.journal import JournalError, RequestJournal
from deepspeed_tpu.serving.kvcache import PagedKVPool, PerHeadKV
from deepspeed_tpu.serving.pool import SlotKVPool
from deepspeed_tpu.serving.scheduler import (
    PRIORITY_NORMAL,
    ContinuousScheduler,
    PrefillJob,
    Request,
    ServingDraining,
    ServingOverloaded,
    ServingQueueFull,
    advance_request_ids,
)
from deepspeed_tpu.serving.staging import PackedLayout
from deepspeed_tpu.serving.watchdog import ServingWatchdog
from deepspeed_tpu.utils.logging import log_dist, logger

# steps the engine's StepTimeline holds: ``stats()`` and the timeline's
# summary cover every step since ``timeline.reset_window()`` up to this
# many (``steps_dropped`` counts what fell out); 8 bytes a number a step
TIMELINE_STEPS = 32768


class ServingEngine:
    def __init__(self, engine, config: Any = None, **overrides):
        """``engine``: a built InferenceEngine (GPT family).  ``config``:
        a :class:`ServingConfig`, a raw ``serving`` config dict, or None;
        ``overrides`` replace individual fields (``num_slots=2, ...``)."""
        if config is None:
            config = ServingConfig()
        elif isinstance(config, dict):
            config = ServingConfig.from_dict(config)
        if overrides:
            config = dataclasses.replace(config, **overrides)
        # re-validate unconditionally: a directly-constructed
        # ServingConfig (or replace()d fields) never went through
        # from_dict's chunk-multiple / dtype checks
        config = ServingConfig.from_dict(dataclasses.asdict(config))
        if not engine._causal:
            raise ValueError("ServingEngine requires a causal-LM (GPT-family) InferenceEngine")
        self.engine = engine
        self.config = config
        mcfg = engine.model_config

        capacity = engine.generation_capacity
        if config.max_len:
            if config.max_len > capacity:
                raise ValueError(
                    f"serving.max_len={config.max_len} exceeds the engine's "
                    f"generation capacity min(max_out_tokens={engine.max_out_tokens}, "
                    f"n_positions={mcfg.n_positions}) = {capacity}"
                )
            max_len = config.max_len
            from deepspeed_tpu.ops import kernels as _kernels_mod

            if _kernels_mod.flash_decode_armed() and max_len % 128:
                logger.warning(
                    f"serving.max_len={max_len} is not a multiple of 128, so "
                    "the fused flash-decode kernel cannot serve this pool "
                    "(decode falls back to the lax dequant path; "
                    "docs/kernels.md) — align max_len to 128 to arm it"
                )
        else:
            # derive: the engine capacity floored to a chunk multiple
            # (chunk-multiple capacity guarantees the last prefill
            # chunk's write never clamps — docs/serving.md)
            max_len = (capacity // config.prefill_chunk) * config.prefill_chunk
            if max_len < 1:
                raise ValueError(
                    f"serving.prefill_chunk={config.prefill_chunk} exceeds the "
                    f"engine's generation capacity {capacity}; lower the chunk "
                    f"or raise max_out_tokens"
                )
            from deepspeed_tpu.ops import kernels as _kernels_mod

            if _kernels_mod.flash_decode_armed() and max_len % 128:
                # flash-decode kernel grid wants S % 128 == 0: floor the
                # derived capacity to a (chunk, 128) common multiple so
                # the decode hot path actually takes the kernel; keep
                # the chunk floor when the capacity is too small for one
                import math

                step = math.lcm(config.prefill_chunk, 128)
                aligned = (capacity // step) * step
                if aligned >= config.prefill_chunk:
                    log_dist(
                        f"serving: derived max_len {max_len} -> {aligned} "
                        "(floored to the flash-decode kernel's "
                        f"lcm(chunk={config.prefill_chunk}, 128)={step} grid; "
                        "set serving.max_len explicitly to keep the larger "
                        "capacity on the lax path — docs/kernels.md)"
                    )
                    max_len = aligned
                else:
                    logger.warning(
                        f"serving: derived max_len={max_len} cannot align to "
                        "the flash-decode kernel's 128-row grid within the "
                        f"engine capacity {capacity}; decode falls back to "
                        "the lax path (docs/kernels.md)"
                    )
        kv_dtype = "int8" if config.kv_cache_dtype == "int8" else engine._kv_dtype
        from deepspeed_tpu.sharding.layout import replicated_sharding

        self._replicated = replicated_sharding(engine.mesh)
        kvc = config.kvcache
        self._paged = bool(kvc.enabled)
        # the family seam (docs/serving.md §Model families): the family
        # supplies the kind of its pool and its own step on it; the
        # engine keeps scheduler, staging, sampling, program names and
        # timeline
        family = engine._family
        kind = family.cache_kind(mcfg, kv_dtype)
        if kv_dtype == "int8" and not isinstance(kind, PerHeadKV):
            raise ValueError(f"{type(mcfg).__name__}: no int8 form of its cache kind (kv_cache_dtype must be 'model')")
        # per-step counters a family's step returns beside the tokens
        # (DeepSeek-V2: tokens per held expert; stats()["moe"])
        self._aux_total = None
        self._aux_decode_touched = 0
        self._aux_decode_steps = 0
        # what a cache kind with per-slot state is asked about (stats()["hybrid"])
        self._state_resets = 0
        self._unread_chunks: list = []  # serving.overlap_chunks: (job, what _launch_prefill returned) of chunks not read back yet
        self._programs_read = 0  # programs read back so far: a step that read none has measured no device time
        self._decode_rows = 0
        self._decode_steps = 0
        # how far the paged decode kernel's work list engages (stats()):
        # the pages its grid walks, summed over decode steps
        self._decode_pages_walked = 0
        # ... the steps its grid takes for them and the pages those fetch,
        # under the tile the kernel reads off the pool (None: a pool whose
        # pages another kernel reads)
        self._decode_grid_steps = 0
        self._decode_pages_read = 0
        self._decode_tile: Optional[Tuple[int, int]] = None  # (blocks of KV heads a page, pages an item)
        # learned sparse attention (a family whose config names ``select_topk``): the positions a decoding
        # row could attend and those its selection keeps, summed over decode steps (stats())
        self._select_topk = int(getattr(mcfg, "select_topk", 0) or 0)
        self._dsa_attendable = 0
        self._dsa_selected = 0
        # the same of the prefill chunks' real queries: the chunks, and the positions their queries could attend
        self._dsa_chunks = 0
        self._dsa_chunk_attendable = 0
        self._dsa_steps0 = 0  # the decode steps before the counters were last started afresh
        if self._paged:
            import math

            if config.max_len:
                if max_len % kvc.page_len:
                    raise ValueError(
                        f"serving.max_len={max_len} must be a multiple of "
                        f"serving.kvcache.page_len={kvc.page_len} — the paged "
                        "pool maps slots as whole pages (docs/serving.md "
                        "§Paged KV & prefix caching)"
                    )
                if max_len % config.prefill_chunk and not getattr(kind, "chunk_writes_drop_past_slot", False):
                    # the last chunk of a long prompt runs past the slot's end; only a kind whose writes
                    # drop those positions may have it (a clipped write lands on the slot's last valid page)
                    raise DeepSpeedConfigError(
                        f"'serving.max_len' ({max_len}) must be a multiple of prefill_chunk "
                        f"({config.prefill_chunk}) for the cache kind {type(kind).__name__}"
                    )
            else:
                # re-floor the derived capacity to a (chunk, page_len)
                # common multiple: chunk-multiple keeps the last prefill
                # write from clamping, page-multiple keeps slots whole
                step = math.lcm(config.prefill_chunk, kvc.page_len)
                aligned = (capacity // step) * step
                if aligned < config.prefill_chunk:
                    raise ValueError(
                        f"serving.kvcache.page_len={kvc.page_len} cannot align "
                        f"to the engine capacity {capacity} within "
                        f"lcm(prefill_chunk={config.prefill_chunk}, page_len)="
                        f"{step}; lower page_len or raise max_out_tokens"
                    )
                if aligned != max_len:
                    log_dist(
                        f"serving: derived max_len {max_len} -> {aligned} "
                        f"(floored to lcm(chunk={config.prefill_chunk}, "
                        f"page_len={kvc.page_len})={step} for the paged pool)"
                    )
                    max_len = aligned
            self.pool = PagedKVPool(
                mcfg.n_layer, config.num_slots, getattr(mcfg, "n_head", 0), max_len,
                getattr(mcfg, "head_dim", 0), kv_dtype, page_len=kvc.page_len,
                num_pages=(kvc.num_pages or None), sharding=self._replicated,
                prefill_chunk=config.prefill_chunk,
                pinned_prefixes=kvc.pinned_prefixes,
                session_ttl_seconds=kvc.session_ttl_seconds,
                spill_dir=(kvc.spill_dir or None),
                kind=kind,
            )
            k = self.pool.k
            if isinstance(k, dict) and "k" in k:  # K pages beside another leaf (the indexer's keys): the walk is over the K pages
                k = k["k"]
            if self.pool.v is not None and (not isinstance(k, dict) or "q" in k):  # K/V pages a KV head: flash_decode_paged's
                from deepspeed_tpu.ops.kernels.flash_decode import paged_tile

                heads, span = paged_tile(k, self.pool.pages_per_slot, self.pool.v)
                self._decode_tile = (jax.tree.leaves(k)[0].shape[2] // heads, span)
            make_forward = family.serving_forward
        elif not isinstance(kind, PerHeadKV):
            raise ValueError(
                f"{type(mcfg).__name__} is served on its own cache kind, which lives in the "
                "paged pool only: set serving.kvcache.enabled"
            )
        else:
            self.pool = SlotKVPool(
                mcfg.n_layer, config.num_slots, kind.heads, max_len, kind.head_dim,
                kv_dtype, sharding=self._replicated,
            )
            make_forward = family.slot_serving_forward
        # the model's step on the pool that was built: fwd(params, tokens, k, v, pos, page_table=, write_mask=,
        # row_valid=, take=, state=, slot=) -> (logits (B, V), k, v, state, aux)
        self._family_forward = make_forward(mcfg)
        bind = getattr(self._family_forward, "bind", None)
        if bind is not None:  # a forward that wants what a model config does not hold
            bind(dtype=engine.dtype, mp_size=engine.mp_world_size, pool=self.pool)
        # the forms the two programs took, said while they were traced (stats())
        self._trace_notes: Dict[str, Any] = getattr(self._family_forward, "trace_notes", {})
        # what the newest decode step of a family that says ``decode_keeps`` left on the device (docs/serving.md §Model families)
        self.decode_keeps = bool(getattr(self._family_forward, "decode_keeps", False))
        self.decode_kept: Optional[Dict[str, Any]] = None
        self.scheduler = ContinuousScheduler(
            self.pool,
            prefill_chunk=config.prefill_chunk,
            prefill_chunks_per_step=config.prefill_chunks_per_step,
            max_queue=config.max_queue,
            deadline_seconds=config.deadline_seconds,
            capacity=min(max_len, capacity),
            slo_ttft_ms=config.slo_ttft_ms,
            degrade_queue_watermark=config.degrade_queue_watermark,
            degrade_engage_steps=config.degrade_engage_steps,
            degrade_disengage_steps=config.degrade_disengage_steps,
            degrade_max_new_tokens=config.degrade_max_new_tokens,
        )
        # the admission controller's measured-service-rate feed: the
        # telemetry registry's recent window when the plane is armed,
        # the engine's local EWMA otherwise (scheduler stays jax-free)
        self.scheduler.step_seconds_fn = self._measured_step_seconds
        self._step_wall_ewma: Optional[float] = None
        # each program's host-made inputs travel as ONE packed int32
        # array (serving/staging.py), laid out from the program's field
        # list.  Each is filled through the views of a buffer kept
        # across steps and handed over as a copy
        self._prefill_layout = PackedLayout(self._prefill_fields())
        self._prefill_buffer = self._prefill_layout.buffer()
        self._prefill_views = self._prefill_layout.views(self._prefill_buffer)
        self._decode_layout = PackedLayout(self._decode_fields())
        self._decode_buffer = self._decode_layout.buffer()
        self._decode_views = self._decode_layout.views(self._decode_buffer)

        # client_key -> request id (the fleet router's at-most-once
        # admission map; seeded from the journal when one is armed)
        self._client_keys: Dict[str, int] = {}

        # write-ahead request journal (docs/serving.md §Resilience):
        # "" = off.  A construction failure disables journaling rather
        # than the engine — availability over durability, loudly.
        self._journal: Optional[RequestJournal] = None
        if config.journal_dir:
            try:
                self._journal = RequestJournal(
                    config.journal_dir,
                    segment_records=config.journal_segment_records,
                    keep_segments=config.journal_keep_segments,
                )
                # id-reuse guard: a restarted process submitting BEFORE
                # recover() must not hand out a journaled incomplete id
                # (its retire record would drop the old acknowledged
                # request from the replay set)
                advance_request_ids(self._journal.last_request_id)
                # at-most-once admission: journaled client keys survive
                # a restart, so a duplicate resubmit dedups here too
                self._client_keys.update(self._journal.client_keys)
            except OSError as e:
                logger.error(
                    f"serving: request journal at {config.journal_dir!r} failed "
                    f"to open ({e!r}); journaling DISABLED — a crash loses "
                    "in-flight and queued requests"
                )
        self._watchdog: Optional[ServingWatchdog] = None
        self._journal_quarantined: Optional[str] = None

        from deepspeed_tpu.runtime.overlap.timeline import StepTimeline

        # stage/dispatch/wait/note are timed inside prefill and decode
        # (each summed over the step's two programs); ``wait`` is the
        # blocking read, so wall - wait is the step's host overhead.  The
        # phases also stand in the profiler's trace as ds.serve.* spans,
        # every instant of a step under exactly one leaf of them
        # (docs/telemetry.md).  The summary holds the whole of a window
        # of TIMELINE_STEPS steps: 17 minutes of 31 ms steps
        self.timeline = StepTimeline(
            enabled=True, window=TIMELINE_STEPS, phases=("sweep", "sched", "prefill", "decode", "commit"),
            sub_phases=("stage", "dispatch", "wait", "note"), blocked_on="wait", prefix="serve",
            # a step that waits for the chunk before its own, the decode step and a prompt's last chunk is no stall for
            # being three times one that waits for the decode step alone: a stall is told among the steps that read as many
            stall_among="reads",
        )
        self._stall_logged = 0  # the last step stats() has logged as a stall
        for name in ("chunks_awaited", "chunks_deferred"):
            # on stats() from the first step: chunks waited for in their step (every chunk of the serial step, a
            # prompt's last of the overlapped one) and chunks left unread a step; their sum is the chunks run
            self.timeline.count(name, 0)

        # telemetry (docs/telemetry.md): attach to whatever plane the
        # process armed (the train engine's configure(), or an explicit
        # telemetry.configure() from a tool) —
        # a no-config process gets no-op publishes.  The scheduler's
        # lifecycle events become per-request spans + TTFT/TPOT
        # histograms; step phases ride the timeline attachment.
        # NB arm the plane BEFORE constructing engines: the timeline
        # attachment and the manager's SLO config are captured here —
        # a later configure() reaches the registry/tracer flags but not
        # these construction-time decisions.
        self.telemetry = _telemetry.manager_for("serving")
        self._tel_ttft = self.telemetry.histogram("serving/ttft_ms")
        self._tel_tpot = self.telemetry.histogram("serving/tpot_ms")
        self._tel_queue_wait = self.telemetry.histogram("serving/queue_wait_ms")
        if self.telemetry.collect or self.telemetry.tracer.enabled:
            # the plane keeps the phases it has: sweep, commit and note
            # are on the profiler's clock and in the summary only
            self.timeline.attach_telemetry(
                self.telemetry, prefix="serving",
                phases=("sched", "prefill", "decode", "stage", "dispatch", "wait"))
        self.scheduler.on_event = self._on_request_event

        from deepspeed_tpu.analysis.sanitizer import maybe_from_config

        self._sanitizer = maybe_from_config(None)
        self._prefill_fn = None
        self._prefill_jit = None  # unwrapped jit handle (ds_shard audit)
        self._decode_fn = None
        self._decode_jit = None  # unwrapped jit handle (compiled_step)
        self.prefill_compiles = 0
        self.decode_compiles = 0
        self._step_count = 0
        # kvcache event watermarks: deltas become Perfetto instants
        self._kv_evt_seen = {"evictions": 0, "session_spills": 0}
        # hierarchical KV tiering (docs/serving.md §KV tiering): the
        # tier manager's migration worker moves T1<->T2 in the
        # background; the engine thread drives T0<->T1 through tick()
        # at step boundaries (and from stats()/drain(), so an idle
        # engine still drains pending demotions)
        self._tiers = None
        if self._paged and kvc.tiers.enabled:
            from deepspeed_tpu.serving.kvcache.tiers import PageTierManager

            self._tiers = PageTierManager(
                self.pool,
                host_pages=kvc.tiers.host_pages,
                disk_dir=(kvc.tiers.disk_dir or None),
                residency_window=kvc.tiers.residency_window,
                demote_watermark=kvc.tiers.demote_watermark,
                prefetch_ahead=kvc.tiers.prefetch_ahead,
                demote_batch=kvc.tiers.demote_batch,
            )
            self._tiers.telemetry = self.telemetry
            self.pool.attach_tiers(self._tiers)
        # multi-tenant dimension (docs/serving.md §Front-door): rate
        # limits + weighted-fair queueing + SLO classes + KV quotas +
        # billing-grade accounting, all keyed by submit(tenant=...)
        self.tenants = None
        tcfg = getattr(config, "tenants", None)
        if tcfg is not None and tcfg.enabled:
            from deepspeed_tpu.serving.frontdoor.tenants import TenantRegistry

            self.tenants = TenantRegistry(tcfg)
            self.scheduler.tenants = self.tenants
            attach = getattr(self.pool, "attach_tenants", None)
            if attach is not None:
                attach(self.tenants)
        log_dist(
            f"serving engine: {config.num_slots} slots x {max_len} positions "
            f"(kv={'int8' if kv_dtype == 'int8' else jnp.dtype(kv_dtype).name}, "
            f"chunk={config.prefill_chunk}, pool {self.pool.cache_bytes() / 1e6:.1f} MB)"
        )

    # ------------------------------------------------------------------
    # compiled steps (built once; churn only changes traced values)
    # ------------------------------------------------------------------
    def _wrap(self, fn, site: str):
        """Sanitizer recompile proof: when armed, every call's abstract
        signature is checked — a second signature at either site is a
        recorded recompile (the compile-stability tests gate on this).
        Owner-scoped so several serving engines in one armed process
        (a fleet builds one a replica) each keep their first-compile grace."""
        san = self._sanitizer
        if san is not None:
            return san.recompile.wrap(fn, site=site, owner=id(self))
        return fn

    def _get_prefill(self):
        if self._prefill_fn is None:
            from deepspeed_tpu.inference.engine import sample_logits_pooled

            fwd = self._family_forward
            kind = getattr(self.pool, "kind", None)  # the paged pool's cache kind: its copy-on-write
            chunk = self.config.prefill_chunk
            max_top_k = self.config.max_top_k
            unpack = self._prefill_layout.unpack

            def serve_prefill(params, packed, k_pool, v_pool, state_pool):
                f = unpack(packed)
                pos, take_idx, table, slot = f["pos"], f["take_idx"], f.get("table"), f.get("slot")
                if "cow_src" in f:
                    # the slot's pending copy-on-write lands BEFORE this
                    # chunk's writes: a traced (src, dst) page pair rides
                    # the request's first chunk ((0, 0) — garbage page
                    # onto itself — is the identity when nothing pends).
                    # The form is the cache kind's.  A matter of pages:
                    # it never touches ``state_pool``
                    k_pool = kind.copy_page(k_pool, f["cow_src"], f["cow_dst"])
                    v_pool = kind.copy_page(v_pool, f["cow_src"], f["cow_dst"])
                # one chunk of one request, a batch of one: ``table`` its
                # pages, ``slot`` (a field where the cache keeps something
                # by slot) its rows of the slot axis.  The chunk's padded
                # tail is computed and left out of the family's counters.
                # A cache without a slot-axis group hands None for
                # ``state_pool`` (an empty pytree: nothing is donated)
                logits, k_pool, v_pool, state_pool, aux = fwd(
                    params, f["tokens"], k_pool, v_pool, pos[None], page_table=None if table is None else table[None, :],
                    row_valid=(jnp.arange(chunk, dtype=jnp.int32) <= take_idx)[None, :],
                    take=take_idx[None], state=state_pool, slot=None if slot is None else slot[None],
                )
                # the first generated token samples with the request's
                # params (the same key schedule as decode: key = seed
                # folded with the fed token's cache position)
                key = jax.random.fold_in(jax.random.PRNGKey(f["seed"]), pos + take_idx)
                first = sample_logits_pooled(
                    logits.astype(jnp.float32), key[None], f["do_sample"][None], f["temperature"][None],
                    f["top_k"][None], max_top_k,
                )[0]
                # what the model's step counted rides beside the token (``_note_aux``)
                return (first if aux is None else (first, aux)), k_pool, v_pool, state_pool

            # the function's name is the program's in the profiler's
            # trace: jit_serve_prefill on the device's "XLA Modules" line
            self._prefill_jit = jax.jit(self.engine._scoped(serve_prefill), donate_argnums=(2, 3, 4))
            self._prefill_fn = self._wrap(self._prefill_jit, "serving.prefill")
            self.prefill_compiles += 1
            # ds_shard Pass 2 feed (no-op unless the audit armed it)
            shard_hooks.note_serving(
                self, "serving.prefill", self._prefill_jit,
                self._prefill_abstract_args(),
            )
        return self._prefill_fn

    def _get_decode(self):
        if self._decode_fn is None:
            from deepspeed_tpu.inference.engine import sample_logits_pooled

            fwd = self._family_forward
            max_top_k = self.config.max_top_k
            unpack = self._decode_layout.unpack
            # a family may ask that arrays of its decode step stay on the device until the next one
            # (``decode_kept``: never fetched here; Keye's selection, which a check of the served program reads)
            keeps = self.decode_keeps

            def serve_decode(params, packed, k_pool, v_pool, state_pool):
                f = unpack(packed)
                toks, pos, write_mask = f["toks"], f["pos"], f.get("write_mask")
                kept = {} if keeps else None
                # row b is slot b, at its own ``pos``: the rows of
                # ``state_pool`` are the batch's.  Per-slot page tables
                # are traced values of the one fixed signature;
                # ``write_mask`` redirects non-decoding slots' writes to
                # the garbage page (pages.py).  The slot-contiguous pool
                # has neither
                logits, k_pool, v_pool, state_pool, aux = fwd(
                    params, toks[:, None], k_pool, v_pool, pos, page_table=f.get("tables"), write_mask=write_mask,
                    row_valid=None if write_mask is None else write_mask[:, None], state=state_pool,
                    **({"kept": kept} if keeps else {}),
                )
                # per-(request seed, position) keys: reproducible per
                # request regardless of slot assignment or pool churn
                keys = jax.vmap(
                    lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
                )(f["seeds"], pos)
                nxt = sample_logits_pooled(
                    logits.astype(jnp.float32), keys, f["flags"], f["temps"], f["topks"], max_top_k,
                )
                if keeps:
                    nxt = (nxt, aux, kept)
                elif aux is not None:  # what the model's step counted rides beside the tokens (``_note_aux``)
                    nxt = (nxt, aux)
                return nxt, k_pool, v_pool, state_pool

            self._decode_jit = jax.jit(self.engine._scoped(serve_decode), donate_argnums=(2, 3, 4))
            self._decode_fn = self._wrap(self._decode_jit, "serving.decode")
            self.decode_compiles += 1
            # ds_shard Pass 2 feed (no-op unless the audit armed it)
            shard_hooks.note_serving(
                self, "serving.decode", self._decode_jit,
                self._decode_abstract_args(),
            )
        return self._decode_fn

    def _abstract_tree(self, tree):
        """ShapeDtypeStructs carrying the tree's own shardings, so an AOT
        lowering is the executable the live call runs."""
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=a.sharding), tree
        )

    def _abstract_staged(self, layout: PackedLayout):
        """A program's packed host-made inputs, as the step stages them
        (one int32 vector, replicated)."""
        return jax.ShapeDtypeStruct((layout.size,), jnp.int32, sharding=self._replicated)

    def _decode_fields(self):
        """The decode program's host-made inputs, one entry a slot:
        ``(name, shape, dtype)`` in the order the program reads them —
        the one parameter of its packed layout (serving/staging.py)."""
        S = self.pool.num_slots
        fields = [
            ("toks", (S,), np.int32),
            ("pos", (S,), np.int32),
            ("flags", (S,), np.bool_),
            ("temps", (S,), np.float32),
            ("topks", (S,), np.int32),
            ("seeds", (S,), np.uint32),
        ]
        if self._paged:
            fields += [
                ("write_mask", (S,), np.bool_),
                ("tables", (S, self.pool.pages_per_slot), np.int32),
            ]
        return fields

    def _prefill_fields(self):
        """The prefill program's host-made inputs (one chunk, one slot).
        ``slot`` is a field where the cache keeps something by slot: the
        slot-contiguous pool, and a family's kind with per-slot state."""
        scalar = lambda name, dtype=np.int32: (name, (), dtype)  # noqa: E731
        fields = [("tokens", (1, self.config.prefill_chunk), np.int32)]
        if self._paged:
            fields.append(("table", (self.pool.pages_per_slot,), np.int32))
        if not self._paged or self.pool.state is not None:
            fields.append(scalar("slot"))
        fields += [scalar("pos"), scalar("take_idx")]
        if self._paged:
            fields += [scalar("cow_src"), scalar("cow_dst")]
        fields += [
            scalar("do_sample", np.bool_),
            scalar("temperature", np.float32),
            scalar("top_k"),
            scalar("seed", np.uint32),
        ]
        return fields

    def _abstract_args(self, layout: PackedLayout):
        """A serve executable's argument signature as ShapeDtypeStructs
        (pool-derived, nothing executes): the params, the program's one
        staged array and the donated cache — shared by ``compiled_step``
        and the ds_shard collective audit's AOT feed."""
        return (
            self._abstract_tree(self.engine.params),
            self._abstract_staged(layout),
            *(self._abstract_tree(a) for a in self._pool_args()),
        )

    def _decode_abstract_args(self):
        return self._abstract_args(self._decode_layout)

    def _prefill_abstract_args(self):
        return self._abstract_args(self._prefill_layout)

    def compiled_step(self, which: str):
        """The ``"prefill"`` or ``"decode"`` step AOT-compiled against
        the pool's own shapes and shardings — abstract args only, so
        nothing executes, no slot state is touched, and the sanitizer's
        one-executable recompile proof is unaffected."""
        if which == "decode":
            self._get_decode()  # ensure the jit handle exists
            return self._decode_jit.lower(*self._decode_abstract_args()).compile()
        if which == "prefill":
            self._get_prefill()
            return self._prefill_jit.lower(*self._prefill_abstract_args()).compile()
        raise ValueError(f"compiled_step: 'prefill' or 'decode', got {which!r}")

    # ------------------------------------------------------------------
    # measured service rate (the admission controller's feed)
    # ------------------------------------------------------------------
    def _measured_step_seconds(self) -> Optional[float]:
        """Recent mean serving-step wall in seconds.  THIS engine's EWMA
        (compile steps excluded) wins once it exists; before the first
        measured step, the telemetry registry's process-wide
        ``serving/step_wall_ms`` window (the gauge the timeline
        attachment publishes) seeds a fresh engine in an armed,
        already-serving process.  None on a cold engine — which admits:
        shedding needs evidence."""
        if self._step_wall_ewma:
            return self._step_wall_ewma
        if self.telemetry.collect:
            wm = self.telemetry.gauge("serving/step_wall_ms").window_mean()
            if wm:
                return wm / 1e3
        return None

    # ------------------------------------------------------------------
    # journal plumbing (quarantine-on-failure; docs/serving.md)
    # ------------------------------------------------------------------
    def _journal_record(self, method: str, *args) -> None:
        """Append one record; a failed append quarantines (the journal
        can no longer certify anything) and serving continues."""
        j = self._journal
        if j is None:
            return
        try:
            getattr(j, method)(*args)
        except JournalError as e:
            self._quarantine_journal(e)

    def _journal_commit(self) -> bool:
        """Commit appended records; False (after quarantine) when the
        journal could not certify durability."""
        j = self._journal
        if j is None or not j.dirty:
            return j is not None
        try:
            j.commit()
            return True
        except JournalError as e:
            self._quarantine_journal(e)
            return False

    def _quarantine_journal(self, err: Exception) -> None:
        j, self._journal = self._journal, None
        logger.error(
            f"serving: journal commit failed ({err}); quarantining — serving "
            "continues WITHOUT crash recovery for new work"
        )
        j.quarantine()
        self._journal_quarantined = j.quarantined
        if self.telemetry.collect:
            self.telemetry.counter("serving/journal_quarantined").inc()

    # ------------------------------------------------------------------
    # request API
    # ------------------------------------------------------------------
    def submit(
        self,
        prompt,
        max_new_tokens: Optional[int] = None,
        eos_token_id: Optional[int] = None,
        deadline_seconds: Optional[float] = None,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        seed: int = 0,
        priority: Optional[int] = None,
        client_key: Optional[str] = None,
        session_id: Optional[str] = None,
        tenant: Optional[str] = None,
    ) -> int:
        """Enqueue one request; returns its id.  Raises
        :class:`ServingQueueFull` when the queue is at its bound,
        :class:`ServingOverloaded` (with ``retry_after``) when the
        estimated TTFT exceeds ``serving.slo_ttft_ms`` or the
        degradation ladder sheds the tier, :class:`ServingDraining`
        after SIGTERM, and ``ValueError`` when the request cannot ever
        fit the pool.  With a journal armed, the id is returned only
        after the submit record committed — an acknowledged request
        survives a crash.

        ``priority``: 0 high (never TTFT-shed) / 1 normal (default) /
        2 low (first shed under overload).

        Sampling is per-request (``do_sample``/``temperature``/``top_k``/
        ``seed`` become per-slot vectors of the fixed decode signature):
        tokens are reproducible for a given (seed, position) regardless
        of slot assignment or what else shares the pool; greedy requests
        (the default) bit-match solo ``generate(do_sample=False)``.

        ``client_key`` is an idempotency key (docs/serving.md §Fleet):
        a resubmit carrying a key this engine has already acknowledged
        — in memory or in the journal, i.e. across a crash/restart —
        returns the ORIGINAL id without a second admission.

        ``session_id`` (paged pool only; docs/serving.md §Paged KV &
        prefix caching): a finished turn's KV pages park under this id,
        and the next turn whose prompt extends the parked history
        rebinds them — prefill restarts at the first uncached chunk.
        Ignored (beyond journaling) on the slot-contiguous pool.

        ``tenant`` (docs/serving.md §Front-door): the multi-tenant
        dimension.  With ``serving.tenants`` armed, the submit is
        charged against the tenant's token bucket (raises
        :class:`TenantThrottled` with ``retry_after`` past the limit),
        queued under weighted-fair queueing ahead of the priority
        tiers, and — when ``priority`` is not given explicitly — tiered
        by the tenant's SLO class.  The label journals (``tn``), so
        per-tenant accounting reconciles exactly across a crash."""
        if client_key is not None:
            known = self._client_keys.get(client_key)
            if known is not None:
                if self.scheduler.request(known) is not None:
                    return known
                # the original admission was delivered and popped — the
                # dedup window is the request's tracked lifetime, so a
                # retry after discharge is a NEW request (returning the
                # dead id would strand the caller waiting forever)
                del self._client_keys[client_key]
        if do_sample and top_k > self.config.max_top_k:
            raise ValueError(
                f"top_k={top_k} exceeds serving.max_top_k={self.config.max_top_k} "
                "(the static top-k head width of the one compiled decode step); "
                "raise serving.max_top_k or lower the request's top_k"
            )
        if self._watchdog is not None and self._watchdog.draining:
            if self.telemetry.collect:
                self.telemetry.counter("serving/rejected").inc()
            raise ServingDraining(
                f"serving engine is draining ({self._watchdog.signal_name} "
                f"received, {max(self._watchdog.remaining(), 0.0):.1f}s of drain "
                "budget left); retry against the restarted engine",
                retry_after=max(self._watchdog.remaining(), 0.0),
            )
        faults.check("serving.submit")
        effective_max_new = (
            max_new_tokens if max_new_tokens is not None else self.config.max_new_tokens
        )
        if self.tenants is not None:
            # SLO class → priority tier (an explicit priority wins),
            # then the token-bucket charge: reserved capacity
            # (prompt + budget), realized usage billed at retire.
            # Raises TenantThrottled (429 semantics) with retry_after.
            priority = self.tenants.priority_for(tenant, priority)
            cost = float(np.asarray(prompt).reshape(-1).shape[0]
                         + int(effective_max_new))
            try:
                self.tenants.admit(tenant, cost, now=time.monotonic())
            except ServingQueueFull:
                if self.telemetry.collect:
                    self.telemetry.counter("serving/rejected").inc()
                    self._tenant_counter(tenant, "throttled").inc()
                raise
        elif priority is None:
            priority = PRIORITY_NORMAL
        try:
            req = self.scheduler.submit(
                prompt,
                max_new_tokens=effective_max_new,
                eos_token_id=eos_token_id,
                deadline_seconds=deadline_seconds,
                do_sample=do_sample,
                temperature=temperature,
                top_k=top_k,
                seed=seed,
                priority=priority,
                client_key=client_key,
                session_id=session_id,
                tenant=tenant,
                now=time.monotonic(),
                step=self._step_count,
            )
        except ServingOverloaded as e:
            if self.telemetry.collect:
                self.telemetry.counter("serving/rejected").inc()
                self.telemetry.counter("serving/shed").inc()
                self.telemetry.histogram("serving/retry_after_s").observe(
                    e.retry_after or 0.0
                )
            if self.tenants is not None:
                self.tenants.note("rejected", tenant)
            raise
        except ServingQueueFull:
            if self.telemetry.collect:
                self.telemetry.counter("serving/rejected").inc()
            if self.tenants is not None:
                self.tenants.note("rejected", tenant)
            raise
        # WAL contract: the submit record is durable BEFORE the id is
        # acknowledged (a commit failure quarantines; the request still
        # serves — availability over durability, loudly)
        self._journal_record("record_submit", req)
        self._journal_commit()
        if client_key is not None:
            self._client_keys[client_key] = req.request_id
        if self.tenants is not None:
            self.tenants.note("admitted", tenant)
            if self.telemetry.collect:
                self._tenant_counter(tenant, "admitted").inc()
        if self.telemetry.collect:
            self.telemetry.counter("serving/submitted").inc()
        return req.request_id

    def _tenant_counter(self, tenant: Optional[str], kind: str):
        from deepspeed_tpu.serving.frontdoor.tenants import DEFAULT_TENANT

        return self.telemetry.counter(
            f"serving/tenant/{tenant or DEFAULT_TENANT}/{kind}")

    def client_request_id(self, client_key: str) -> Optional[int]:
        """The id this engine acknowledged for ``client_key`` (in memory
        or journaled), or None — the fleet router's at-most-once dedup
        probe (docs/serving.md §Fleet)."""
        return self._client_keys.get(client_key)

    def recover(self) -> list:
        """Replay the journal's incomplete requests into this engine
        under their **original ids** (idempotent: a second ``recover()``
        on the same engine re-reads the on-disk set, which now shows
        them incomplete-but-resubmitted — they are deduped by id at the
        scheduler).  Greedy and seeded-sampling replays bit-match the
        uninterrupted run (docs/serving.md §Resilience).  Returns the
        replayed ids, oldest first."""
        if self._paged:
            # re-register manifest-verified session spills FIRST, so a
            # replayed turn-N+1 rebinds its session exactly like the
            # uninterrupted run would have
            try:
                sids = self.pool.recover()
                if sids:
                    log_dist(
                        f"serving: kvcache re-registered {len(sids)} spilled "
                        f"session(s) from {self.pool.sessions.spill_dir!r}"
                    )
            except OSError as e:
                logger.warning(f"serving: kvcache session recovery failed: {e!r}")
        if self._journal is None:
            return []
        try:
            entries = self._journal.incomplete()
        except JournalError as e:
            self._quarantine_journal(e)
            return []
        replayed = []
        for e in entries:
            rid = int(e["id"])
            if self.scheduler.request(rid) is not None:
                continue  # already live here (double recover)
            req = self.scheduler.submit(
                np.asarray(e["prompt"], np.int32),
                max_new_tokens=int(e["max_new"]),
                eos_token_id=e.get("eos"),
                # 0 = NO deadline (None falls back to the scheduler
                # default): the queue wait already happened once, an
                # acknowledged replay must not expire a second time
                deadline_seconds=0.0,
                do_sample=bool(e.get("do_sample", False)),
                temperature=float(e.get("temperature", 1.0)),
                top_k=int(e.get("top_k", 0)),
                seed=int(e.get("seed", 0)),
                priority=int(e.get("priority", PRIORITY_NORMAL)),
                request_id=rid,
                bypass_admission=True,  # accepted before the crash
                client_key=e.get("ck"),
                session_id=e.get("sid"),
                # the journaled tenant label rides the replay — the
                # bucket is NOT re-charged (admission happened before
                # the crash; a replay must never double-bill)
                tenant=e.get("tn"),
                now=time.monotonic(),
                step=self._step_count,
            )
            if e.get("ck"):
                self._client_keys[str(e["ck"])] = rid
            if self.tenants is not None:
                self.tenants.note("replayed", e.get("tn"))
            advance_request_ids(rid)
            # re-journal into the live segment: recovery is self-contained
            # even after the old segments compact away
            self._journal_record("record_submit", req)
            replayed.append(rid)
        self._journal_commit()
        if replayed:
            log_dist(
                f"serving: replayed {len(replayed)} incomplete request(s) "
                f"from the journal (ids {replayed[0]}..{replayed[-1]})"
            )
            if self.telemetry.collect:
                self.telemetry.counter("serving/replayed").inc(len(replayed))
        return replayed

    def step(self) -> bool:
        """One serving step: tick the scheduler, land this step's prefill
        chunks, then one decode step over the pool.  Returns whether any
        work remains.  If a drain signal is pending (SIGTERM through the
        installed :class:`ServingWatchdog`), runs the graceful drain and
        exits with the watchdog's contract instead."""
        if self._watchdog is not None and self._watchdog.draining:
            self._drain_and_exit()
        return self._step_once(admit=True)

    def _step_once(self, admit: bool) -> bool:
        self._step_count += 1
        # one span round the whole step, carrying its number, so that
        # the phases' spans nest in it in the profiler's trace
        with self.timeline.annotation("step", step=self._step_count):
            return self._step_phases(admit)

    def _step_phases(self, admit: bool) -> bool:
        tl = self.timeline
        compiles0 = self.prefill_compiles + self.decode_compiles
        read0 = self._programs_read
        t0 = time.monotonic()
        with tl.phase("sweep"):
            if self._paged:
                # TTL sweep BEFORE admission: pages a cold session releases
                # this tick are available to the requests admitted in it
                self.pool.sweep(t0)
            if self._tiers is not None:
                # migration tick BEFORE admission: hinted prefetch pages
                # upcoming admits/rebinds back to T0 so their prefill chunk
                # runs against warm pages; watermark demotion batches the
                # device_get traffic at the step boundary
                self._tiers.tick(
                    t0, hints=self.scheduler.upcoming_hints(
                        self._tiers.prefetch_ahead))
        with tl.phase("sched"):
            plan = self.scheduler.tick(t0, self._step_count, admit=admit)
        if self.config.overlap_chunks:
            self._step_programs_overlapped(plan)
        else:
            # the serial step, kept for the equality tests: each program
            # read back before the next is staged, the chunks first
            with tl.phase("prefill"):
                for job in plan.prefill_jobs:
                    self._run_prefill(job)
            with tl.phase("decode"):
                decoding = self.scheduler.decoding()
                if decoding:
                    self._run_decode(decoding)
        wall = time.monotonic() - t0
        # the step's books, under one span: gauges, service rate, journal
        with tl.phase("commit"):
            tl.set_gauge("queue_depth", self.scheduler.queue_depth)
            tl.set_gauge("live_slots", self.pool.live_slots)
            tl.set_gauge("reads", self._programs_read - read0)
            # measured service rate for the admission controller (EWMA over
            # non-compile steps that read a program back — a jit trace in the
            # wall would poison the TTFT estimate into shedding everything for
            # minutes, and a step that left its only chunk unread has timed the
            # host alone; the registry window supersedes the EWMA when armed).
            # The wall ends at the step's last read: under the overlapped step
            # it holds the chunk before the step's own, which in steady state
            # is the same device time, so the estimate reads as the serial one
            if self._programs_read > read0 and (
                self.prefill_compiles + self.decode_compiles == compiles0
            ):
                self._step_wall_ewma = (
                    wall if self._step_wall_ewma is None
                    else 0.2 * wall + 0.8 * self._step_wall_ewma
                )
            # retirements this step become durable at the boundary
            self._journal_commit()
            if self._tiers is not None:
                # the step's wall window feeds the swap-hide overlap ratio
                self._tiers.note_step(t0, time.monotonic())
            self._publish_kvcache()
        # the step's record holds its own commit
        tl.end_step()
        return self.scheduler.has_work()

    def drain(self, max_steps: Optional[int] = None) -> Dict[int, Request]:
        """Step until every submitted request finishes (or ``max_steps``
        elapses); returns and clears the finished-request map.  Also
        sweeps queued-deadline expiry first, so an idle engine's
        over-deadline waiters expire even when no step runs."""
        self.scheduler.sweep_expired(time.monotonic(), self._step_count)
        if self._tiers is not None:
            # idle-engine demotion: a drain() with no work must still
            # turn the migration queue (mirror of the idle TTL sweep)
            self._tiers.tick(time.monotonic())
        steps = 0
        while self.scheduler.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        self._journal_commit()
        return self.scheduler.pop_finished()

    # ------------------------------------------------------------------
    # graceful drain (docs/serving.md §Resilience)
    # ------------------------------------------------------------------
    def install_watchdog(
        self,
        drain_deadline_seconds: Optional[float] = None,
        exit_code: Optional[int] = None,
    ) -> ServingWatchdog:
        """Arm SIGTERM/SIGINT graceful drain: admission stops, in-flight
        requests drain within the deadline, undone work persists in the
        journal, and the process exits 43 only after the journal
        commits (1 otherwise)."""
        if self._watchdog is None:
            kw = {}
            if exit_code is not None:
                kw["exit_code"] = exit_code
            self._watchdog = ServingWatchdog(
                drain_deadline_seconds=(
                    drain_deadline_seconds
                    if drain_deadline_seconds is not None
                    else self.config.drain_deadline_seconds
                ),
                **kw,
            ).install()
        return self._watchdog

    def _drain_and_exit(self) -> None:
        """The SIGTERM sequence.  Exit 43 certifies durable undone work
        (journal committed) — or a complete drain when no journal is
        armed; anything less is exit 1, the crash contract."""
        wd = self._watchdog
        log_dist(
            f"serving: drain signal ({wd.signal_name}) received; admission "
            f"stopped, draining {self.pool.live_slots} in-flight request(s) "
            f"within {max(wd.remaining(), 0.0):.1f}s "
            f"({self.scheduler.queue_depth} queued will replay from the journal)"
        )
        if self.telemetry.collect:
            self.telemetry.counter("serving/drains").inc()
        drained_all = True
        try:
            while self.scheduler.live and wd.remaining() > 0:
                self._step_once(admit=False)
            # a chunk left unread by the last step taken (the deadline's cut,
            # a cancel) is read before the sessions spill and the books close
            self._land_unread()
        except BaseException as e:  # a dying drain must still certify honestly
            logger.error(f"serving: drain loop failed: {e!r}")
            drained_all = False
        if self.scheduler.live:
            drained_all = False
            undone_live = sorted(self.pool.owners().values())
            logger.warning(
                f"serving: drain deadline ({wd.drain_deadline_seconds:g}s) cut "
                f"off {len(undone_live)} in-flight request(s) {undone_live}; "
                "they replay from the journal"
            )
        if self._paged:
            # persist every warm session before the process dies: the
            # restarted engine's recover() re-registers the spills and
            # turn N+1 rebinds across the restart (no-op w/o spill_dir)
            try:
                if self._tiers is not None:
                    # tiering path: demote every warm session and push
                    # T1 to disk, so tiered state survives the process
                    n_spilled = self._tiers.flush(time.monotonic())
                else:
                    n_spilled = self.pool.spill_sessions(time.monotonic())
                if n_spilled:
                    log_dist(
                        f"serving: kvcache spilled {n_spilled} warm "
                        f"session(s) at drain"
                    )
            except OSError as e:
                logger.error(
                    f"serving: kvcache session spill at drain failed: {e!r}"
                )
        undone = self.scheduler.pending_ids()
        if self._journal is not None:
            self._journal_record("record_drain", undone)
            committed = self._journal_commit()
            if committed:
                log_dist(
                    f"serving: journal committed ({len(undone)} undone request(s) "
                    f"durable); exiting with code {wd.exit_code}"
                )
                raise SystemExit(wd.exit_code)
            logger.error("serving: journal could not commit at drain; exiting 1")
            raise SystemExit(1)
        if drained_all and not undone:
            log_dist(
                "serving: drained completely (no journal armed, nothing undone); "
                f"exiting with code {wd.exit_code}"
            )
            raise SystemExit(wd.exit_code)
        logger.error(
            f"serving: {len(undone)} undone request(s) with no journal to "
            "persist them; exiting 1 (crash contract)"
        )
        raise SystemExit(1)

    def cancel(self, request_id: int) -> bool:
        """Retire a queued or in-flight request without finishing it
        (the hedge loser's path; docs/serving.md §Fleet).  The retire
        record journals and commits immediately — a cancelled request
        must not replay after a crash.  False when the id is unknown or
        already retired."""
        ok = self.scheduler.cancel(
            request_id, now=time.monotonic(), step=self._step_count
        )
        if ok:
            self._journal_commit()
        return ok

    def result(self, request_id: int) -> Optional[Request]:
        return self.scheduler.request(request_id)

    def pop_results(self) -> Dict[int, Request]:
        return self.scheduler.pop_finished()

    # ------------------------------------------------------------------
    # telemetry: per-request lifecycle (docs/telemetry.md span schema)
    # ------------------------------------------------------------------
    def _on_request_event(self, kind: str, r, now: float, step: int) -> None:
        """Scheduler lifecycle hook → spans on the request's own trace
        lane (tid = request id): queue → prefill → decode → retire, plus
        the TTFT / per-output-token histograms.
        Host dict ops only; spans cost nothing when tracing is off."""
        tm = self.telemetry
        tracer = tm.tracer if tm.tracer.enabled else None
        rid = r.request_id
        # journal lifecycle records (committed at the step boundary;
        # docs/serving.md §Resilience journal format)
        if kind == "admitted":
            self._journal_record("record_admit", r)
        elif kind == "first_token":
            self._journal_record("record_first_token", r)
        elif kind in ("finished", "cancelled"):
            self._journal_record("record_retire", r)
            if self.tenants is not None:
                # realized-usage billing, mirrored by the retire
                # record's ``n`` — the two ledgers reconcile exactly
                # after a crash + recover() (at most one retire per id)
                if kind == "finished":
                    self.tenants.bill(r.tenant, len(r.generated))
                    if tm.collect:
                        self._tenant_counter(r.tenant, "billed_tokens").inc(
                            len(r.generated))
                else:
                    self.tenants.note("cancelled", r.tenant)
        elif kind in ("expired", "shed"):
            # reject record, committed NOW rather than at the step
            # boundary: a crash in between must not resurrect a request
            # the client was already told to retry elsewhere
            self._journal_record("record_reject", r)
            self._journal_commit()
            if self.tenants is not None:
                self.tenants.note(kind, r.tenant)
        if kind == "admitted":
            self._tel_queue_wait.observe((now - r.submit_time) * 1e3)
            if tracer is not None:
                tracer.add_span(
                    "queue", "serving.request", r.submit_time, now,
                    pid=_telemetry.PID_REQUESTS, tid=rid,
                    args={"request": rid, "slot": r.slot, "prompt_len": r.prompt_len},
                    tid_name=f"request {rid}",
                )
        elif kind == "first_token":
            ttft_ms = (now - r.submit_time) * 1e3
            self._tel_ttft.observe(ttft_ms)
            if tracer is not None:
                tracer.add_span(
                    "prefill", "serving.request",
                    r.admit_time if r.admit_time is not None else r.submit_time, now,
                    pid=_telemetry.PID_REQUESTS, tid=rid,
                    args={"request": rid, "ttft_ms": round(ttft_ms, 3),
                          "chunks": -(-r.prompt_len // self.config.prefill_chunk)},
                )
            tm.check_slo(ttft_ms)
        elif kind == "finished":
            if tm.collect:
                tm.counter("serving/finished", reason=r.finish_reason or "?").inc()
                if len(r.generated) > 1 and r.first_token_time is not None:
                    self._tel_tpot.observe(
                        (now - r.first_token_time) * 1e3 / (len(r.generated) - 1)
                    )
            if tracer is not None:
                if r.first_token_time is not None:
                    tracer.add_span(
                        "decode", "serving.request", r.first_token_time, now,
                        pid=_telemetry.PID_REQUESTS, tid=rid,
                        args={"request": rid, "tokens": len(r.generated)},
                    )
                tracer.add_instant(
                    "retire", "serving.request", ts=now,
                    pid=_telemetry.PID_REQUESTS, tid=rid,
                    args={"request": rid, "finish_reason": r.finish_reason,
                          "tokens": len(r.generated)},
                )
        elif kind == "cancelled":
            if tm.collect:
                tm.counter("serving/cancelled").inc()
            if tracer is not None:
                tracer.add_instant(
                    "cancelled", "serving.request", ts=now,
                    pid=_telemetry.PID_REQUESTS, tid=rid,
                    args={"request": rid, "tokens": len(r.generated)},
                )
        elif kind == "expired":
            if tm.collect:
                tm.counter("serving/expired").inc()
            if tracer is not None:
                tracer.add_instant(
                    "expired", "serving.request", ts=now,
                    pid=_telemetry.PID_REQUESTS, tid=rid,
                    args={"request": rid,
                          "queue_wait_ms": round((now - r.submit_time) * 1e3, 3)},
                )
        elif kind == "shed":
            if tm.collect:
                tm.counter("serving/shed").inc()
                if r.retry_after is not None:
                    tm.histogram("serving/retry_after_s").observe(r.retry_after)
            if tracer is not None:
                tracer.add_instant(
                    "shed", "serving.request", ts=now,
                    pid=_telemetry.PID_REQUESTS, tid=rid,
                    args={"request": rid, "priority": r.priority,
                          "ladder_rung": self.scheduler.ladder.level,
                          "retry_after_s": r.retry_after},
                )

    def _publish_kvcache(self) -> None:
        """Paged-pool counters → ``kvcache/*`` registry gauges, plus
        Perfetto instants for eviction/spill deltas since the last
        publish (step-boundary granularity; host dict reads only)."""
        if not self._paged:
            return
        tm = self.telemetry
        tracer = tm.tracer if tm.tracer.enabled else None
        if not tm.collect and tracer is None:
            # nobody reads it: no pool.stats() a step, and the two
            # watermarks move on so that a tracer armed later shows
            # what happened since, not since the engine began
            self._kv_evt_seen = {"evictions": self.pool.evictions,
                                 "session_spills": self.pool.sessions.spills}
            return
        st = self.pool.stats()
        if tm.collect:
            for key in ("pages_live", "pages_free", "hit_rate", "tokens_saved",
                        "cow_copies", "evictions", "session_rebinds",
                        "session_spills", "session_restores", "prefix_entries",
                        "sessions_warm", "sessions_spilled"):
                tm.gauge(f"kvcache/{key}").set(float(st[key]))
        if tm.collect and self._tiers is not None and "tiers" in st:
            for key, val in st["tiers"].items():
                if isinstance(val, (int, float)):
                    tm.gauge(f"kvcache/tier/{key}").set(float(val))
        for key, name in (("evictions", "kvcache_evict"),
                          ("session_spills", "kvcache_spill")):
            delta = int(st[key]) - self._kv_evt_seen[key]
            if delta > 0 and tracer is not None:
                tracer.add_instant(
                    name, "serving.kvcache",
                    args={"count": delta, "pages_free": st["pages_free"],
                          "pages_live": st["pages_live"]},
                )
            self._kv_evt_seen[key] = int(st[key])

    # ------------------------------------------------------------------
    def _step_programs_overlapped(self, plan) -> None:
        """The step's two programs, all handed to the device before the
        host reads any of them back, the decode step first
        (``serving.overlap_chunks``, the default).  A chunk that is not
        its prompt's last is not waited for: the scheduler is told of
        its progress at once, its token (no request's) and counters are
        read a step later, when it has long run.  So the device runs a
        chunk while the host turns the step — the read-back, the notes,
        the commit, the caller's loop, the next step's staging and
        dispatch — and finds the next decode step queued behind it.  The
        device runs its programs in the order they were dispatched, each
        on the pool the one before left, so what they compute is what
        the serial step computes, and whatever else touches the pool
        (a tier's page moves, a session's spill) takes the newest
        program's output and queues behind it; what changes is *when*:
        the decode set is taken before the step's chunks land, so a
        request whose last chunk lands in step N (awaited: its first
        token is the request's, and its prompt is learned as a prefix
        then) decodes from step N + 1, not N.  The host runs at most
        one chunk ahead."""
        tl = self.timeline
        decoding = self.scheduler.decoding()
        launched, chunks = None, []
        try:
            if decoding:
                with tl.phase("decode"):
                    launched = self._launch_decode(decoding)
            with tl.phase("prefill"):
                for job in plan.prefill_jobs:
                    chunks.append((job, self._launch_prefill(job)))
                self._land_unread()  # earlier steps' chunks: run by now, or running ahead of all that was launched above
        except Exception:
            # a fault between two dispatches (an injected one, a refused
            # launch): what was handed over runs all the same, and a
            # cache kind with recurrent state cannot run it twice — the
            # scheduler hears of it before the fault goes up, as it does
            # in the serial step, which never has a program unread
            self._land_launched(decoding, launched, chunks)
            raise
        self._land_launched(decoding, launched, chunks)

    def _land_launched(self, decoding, launched, chunks) -> None:
        """Read the step's decode program back, then its chunks: a
        prompt's last is waited for, any other is noted and left."""
        tl = self.timeline
        if launched is not None:
            with tl.phase("decode"):
                self._land_decode(decoding, launched)
        with tl.phase("prefill"):
            for job, sent in chunks:
                if job.final:
                    self._land_prefill(job, sent)
                else:
                    tl.count("chunks_deferred")
                    self.scheduler.note_prefill(job, 0, now=time.monotonic(), step=self._step_count)
                    self._unread_chunks.append((job, sent))

    def _land_unread(self) -> None:
        """Read back what ``serving.overlap_chunks`` left on the device:
        the counters of chunks whose progress the scheduler already has."""
        unread, self._unread_chunks = self._unread_chunks, []
        for job, launched in unread:
            self._land_prefill(job, launched, noted=True)

    def _run_prefill(self, job: PrefillJob) -> None:
        self._land_prefill(job, self._launch_prefill(job))

    def _launch_prefill(self, job: PrefillJob):
        """Stage and dispatch one chunk; returns what
        :meth:`_land_prefill` reads back."""
        faults.check("serving.prefill")
        faults.check_latency("serving.prefill")
        san = self._sanitizer
        tl = self.timeline
        fn = self._get_prefill()
        r = job.req
        # explicit staging of the chunk's packed inputs onto the serving
        # mesh (transfer-guard clean: device_put is sanctioned, and
        # pre-placing on the mesh means the jit has nothing to move)
        with tl.phase("prefill.stage"):
            staged = self._stage(self._prefill_inputs(job))
        tracer = self.telemetry.tracer if self.telemetry.tracer.enabled else None
        t0 = tracer.now() if tracer is not None else 0.0
        guard = san.transfer.guard("serving.prefill") if san is not None else nullcontext()
        with tl.phase("prefill.dispatch", request=r.request_id, start=job.start, len=job.length), guard:
            tl.count("programs")
            first, *pools = fn(self.engine.params, staged, *self._pool_args())
        self.pool.swap(*pools)
        if job.start == 0:
            self._state_resets += 1  # a cache kind with per-slot state: taken inside the program, the chunk at position 0 starts from zero
        if self._select_topk:
            self._dsa_chunks += 1
            self._dsa_chunk_attendable += job.length * job.start + job.length * (job.length + 1) // 2  # query i of the chunk: start + i + 1
        return first, tracer, t0

    def _land_prefill(self, job: PrefillJob, launched, noted: bool = False) -> None:
        """Read a dispatched chunk back and hand it to the scheduler
        (``noted``: the scheduler has its progress already)."""
        tl = self.timeline
        r = job.req
        first, tracer, t0 = launched
        # explicit d2h read doubles as the fence that keeps prefill_ms
        # honest; the value is the first generated token on final chunks
        with tl.phase("prefill.wait"):
            tok = jax.device_get(first)
        # the hand-back: from the read's return to the scheduler's note
        with tl.phase("prefill.note"):
            self._programs_read += 1
            if not noted:
                tl.count("chunks_awaited")
            tok = int(self._note_aux(tok, decode=False))
            now = time.monotonic()
            if self._paged and job.final and not noted:
                # the whole prompt's KV is paged in: learn it as a shared
                # prefix (before note_prefill — a 1-token budget can retire
                # the request, releasing the slot, inside that call)
                self.pool.learn_prefix(r, now=now)
            if tracer is not None:
                # chunk-level detail on the request's own lane, between its
                # queue and prefill spans (the fenced read above makes the
                # span a real device-work window, not dispatch overhead)
                tracer.add_span(
                    "prefill_chunk", "serving.request", t0, now,
                    pid=_telemetry.PID_REQUESTS, tid=r.request_id,
                    args={"request": r.request_id, "start": job.start,
                          "len": job.length, "final": job.final},
                    tid_name=f"request {r.request_id}",
                )
            if not noted:
                self.scheduler.note_prefill(job, tok, now=now, step=self._step_count)

    def _run_decode(self, decoding) -> None:
        self._land_decode(decoding, self._launch_decode(decoding))

    def _launch_decode(self, decoding):
        """Stage and dispatch the decode step over ``decoding``; returns
        what :meth:`_land_decode` reads back."""
        faults.check("serving.decode")
        faults.check_latency("serving.decode")
        san = self._sanitizer
        tl = self.timeline
        fn = self._get_decode()
        with tl.phase("decode.stage"):
            staged = self._stage(self._decode_inputs())
        guard = san.transfer.guard("serving.decode") if san is not None else nullcontext()
        with tl.phase("decode.dispatch"), guard:
            tl.count("programs")
            nxt, *pools = fn(self.engine.params, staged, *self._pool_args())
        self.pool.swap(*pools)
        if self.decode_keeps:
            *nxt, self.decode_kept = nxt
        self._decode_rows += len(decoding)
        self._decode_steps += 1
        if self._paged:
            # a decoding row attends positions 0 ... prompt + generated - 1
            last = [len(r.prompt) + len(r.generated) - 1 for r in decoding]
            self._decode_pages_walked += sum(p // self.pool.page_len + 1 for p in last)
            if self._decode_tile is not None:
                blocks, span = self._decode_tile
                items = sum(p // (self.pool.page_len * span) + 1 for p in last)
                self._decode_grid_steps += items * blocks
                self._decode_pages_read += items * span  # the masked tail of a row's last span included
        if self._select_topk:
            fills = [len(r.prompt) + len(r.generated) for r in decoding]  # positions 0 ... fill - 1, the query's own among them
            self._dsa_attendable += sum(fills)
            self._dsa_selected += sum(min(f, self._select_topk) for f in fills)
        return nxt

    def _land_decode(self, decoding, nxt) -> None:
        tl = self.timeline
        with tl.phase("decode.wait"):
            out = jax.device_get(nxt)
        with tl.phase("decode.note"):
            self._programs_read += 1
            out = np.asarray(self._note_aux(out, decode=True))
            now = time.monotonic()
            self.scheduler.note_decode(
                {r.slot: int(out[r.slot]) for r in decoding}, now, self._step_count
            )

    def _prefill_inputs(self, job: PrefillJob) -> np.ndarray:
        """One chunk's packed inputs.  The slot's pending copy-on-write
        pair is consumed into its first chunk."""
        r, v = job.req, self._prefill_views
        v["tokens"][...] = job.tokens
        v["pos"][...] = job.start
        v["take_idx"][...] = job.take_idx
        v["do_sample"][...] = r.do_sample
        v["temperature"][...] = r.temperature
        v["top_k"][...] = r.top_k
        v["seed"][...] = r.seed & 0xFFFFFFFF
        if "slot" in v:
            v["slot"][...] = r.slot
        if self._paged:
            v["table"][...] = self.pool.table(r.slot)
            v["cow_src"][...], v["cow_dst"][...] = self.pool.consume_cow(r.slot)
        return self._prefill_buffer.copy()

    def _decode_inputs(self) -> np.ndarray:
        """The decode step's packed inputs: the scheduler writes its
        per-slot rows and the pool its page tables into the buffer kept
        across steps.  What is handed on — here and for a chunk — is a
        copy, the hand-over's own: ``device_put`` on the CPU backend may
        alias a NumPy array's memory, and the next step writes the buffer
        while the program may still read what was staged."""
        views = self._decode_views
        self.scheduler.write_decode_inputs(views)
        if self._paged:
            # non-decoding slots write to the garbage page (write_mask);
            # their reads were already safe behind the position mask
            self.pool.tables(out=views["tables"])
        return self._decode_buffer.copy()

    def _stage(self, host):
        """The step's one sanctioned host→device transfer a program:
        ``host`` onto the serving mesh, replicated; every array handed
        over is counted (``stage_puts``)."""
        self.timeline.count("stage_puts", 1 if isinstance(host, np.ndarray) else len(jax.tree.leaves(host)))
        return jax.device_put(host, self._replicated)

    def _pool_args(self) -> tuple:
        """The donated cache arguments of a serving step: K, V and the
        kind's slot-axis group (None, an empty pytree, for a cache that
        has none)."""
        return self.pool.k, self.pool.v, self.pool.state

    def _note_aux(self, got, decode: bool):
        """A step whose model counted something returns ``(tokens,
        aux)``: add the step's counters (host side, a few hundred
        integers) and hand the tokens on.  DeepSeek-V2's ``aux (moe
        layers, held + 1)``: tokens computed per held expert, and last
        the assignments routed to held experts."""
        if not isinstance(got, (tuple, list)):
            return got
        tokens, aux = got
        if aux is None:
            return tokens
        aux = np.asarray(aux, np.int64)
        self._aux_total = aux if self._aux_total is None else self._aux_total + aux
        if decode:
            self._aux_decode_steps += 1
            self._aux_decode_touched += int(np.count_nonzero(aux[:, :-1]))
        return tokens

    def reset_moe_counters(self) -> None:
        """Start the expert counters of :meth:`stats` afresh (a
        benchmark window opens)."""
        self._aux_total, self._aux_decode_touched, self._aux_decode_steps = None, 0, 0
        self._dsa_attendable = self._dsa_selected = self._dsa_chunks = self._dsa_chunk_attendable = 0
        self._dsa_steps0 = self._decode_steps

    def _moe_stats(self) -> Dict[str, Any]:
        per_expert = self._aux_total[:, :-1]
        computed, routed = int(per_expert.sum()), int(self._aux_total[:, -1].sum())
        mean = per_expert.mean(axis=1)
        return {
            "tokens_per_expert": per_expert.tolist(),  # [moe layer][held expert]
            "assignments_computed": computed,
            "assignments_routed_held": routed,
            "dropped_assignments": routed - computed,
            # the fullest held expert's load over the mean held expert's, the worst layer
            "load_max_over_mean": float(np.max(per_expert.max(axis=1) / np.maximum(mean, 1e-9))) if computed else None,
            "decode_steps": self._aux_decode_steps,
            # held experts (summed over the moe layers) with at least one token, a decode step
            "decode_experts_touched_mean": self._aux_decode_touched / max(1, self._aux_decode_steps),
        }

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """Counters + per-step phase attribution (prefill_ms/decode_ms/
        sched_ms, mean queue_depth/live_slots) for logs and bench
        records.  Host-side deadline sweep included: an idle engine's
        over-deadline waiters expire the moment anyone looks, not only
        when a ``step()`` happens to run."""
        self._land_unread()
        s = self.scheduler
        if s.sweep_expired(time.monotonic(), self._step_count):
            self._journal_commit()
        if self._paged:
            # same idle-sweep shape for parked-session TTLs: the pool's
            # per-step sweep never runs on a replica that receives no
            # traffic, so a drained-but-alive replica would pin its
            # pages forever without this (docs/serving.md §Elastic fleet)
            self.pool.sweep(time.monotonic())
        if self._tiers is not None:
            # idle-engine demotion: a quiescent engine must still drain
            # pending demotions instead of holding T0 pages forever
            self._tiers.tick(time.monotonic())
        if self.telemetry.collect:
            self.telemetry.gauge("serving/queue_depth_now").set(s.queue_depth)
            self.telemetry.gauge("serving/live_slots_now").set(self.pool.live_slots)
        j = self._journal
        out = {
            "submitted": s.submitted,
            "finished": s.finished_count,
            "rejected": s.rejected,
            "expired": s.expired,
            # resilience (docs/serving.md §Resilience)
            "shed": s.shed_count + s.admission.shed,
            "cancelled": s.cancelled_count,
            "degrade_level": s.ladder.level,
            "degrade_rung": s.ladder.rung,
            "degrade_engagements": s.ladder.engagements,
            "draining": bool(self._watchdog is not None and self._watchdog.draining),
            "journal": (
                "off" if j is None and not getattr(self, "_journal_quarantined", None)
                else ("quarantined" if j is None else "on")
            ),
            "journal_records": 0 if j is None else j.records,
            "journal_commits": 0 if j is None else j.commits,
            # instantaneous levels; the window MEANS arrive from the
            # timeline summary below as queue_depth / live_slots
            "queue_depth_now": s.queue_depth,
            "live_slots_now": self.pool.live_slots,
            "serving_steps": self._step_count,
            "prefill_compiles": self.prefill_compiles,
            "decode_compiles": self.decode_compiles,
            "pool_bytes": self.pool.cache_bytes(),
            "kv_dtype": "int8" if isinstance(self.pool.k, dict) and "q" in self.pool.k else str(
                np.dtype(jax.tree.leaves(self.pool.k)[0].dtype)
            ),
        }
        if self._paged:
            out["kvcache"] = self.pool.stats()
            if "groups" in out["kvcache"]:  # two page groups in one pool: each one's geometry and bytes, beside the counters
                out["kv_groups"] = {name: {key: g[key] for key in ("kv_heads", "k_dim", "v_dim", "bytes")}
                                    for name, g in out["kvcache"]["groups"].items()}
            self._publish_kvcache()
            out["decode_pages_walked"] = self._decode_pages_walked
            # what a grid of every page of every slot walks
            out["decode_pages_grid"] = self._decode_steps * self.pool.num_slots * self.pool.pages_per_slot
            if self._decode_tile is not None:
                # walked / grid_steps: the pages a grid step carried; read / walked: the price of the spans' tails
                out["decode_grid_steps"], out["decode_pages_read"] = self._decode_grid_steps, self._decode_pages_read
        if self._select_topk:
            out["dsa_positions_attendable"] = self._dsa_attendable
            out["dsa_positions_selected"] = self._dsa_selected
            # what the selection's kernel had to read: its work function (benchmark/kernels/dsa_select_threshold.py)
            out["dsa_decode_steps"], out["dsa_chunks"] = self._decode_steps - self._dsa_steps0, self._dsa_chunks
            out["dsa_chunk_positions_attendable"] = self._dsa_chunk_attendable
        if self.tenants is not None:
            out["tenants"] = self.tenants.snapshot()
        if self._aux_total is not None:
            out["moe"] = self._moe_stats()
        if getattr(self.pool, "state", None) is not None:
            out["hybrid"] = {
                "state_bytes": self.pool.state_bytes(),
                # fresh requests whose slot state started from zero inside the prefill program (no reset program exists)
                "state_resets_in_program": self._state_resets,
                "decode_rows_updated_mean": self._decode_rows / max(1, self._decode_steps),
            }
        # what the family's programs said of themselves while tracing
        # (DeepSeek-V2: mla_prefill_kernel / mla_prefill_fallback, moe_grouped_kernel / moe_grouped_fallback;
        # Solar-Open2: kda_decode_kernel / _fallback, kda_prefill_form, gqa_decode_kernel / _fallback, gqa_prefill_form;
        # ZAYA: cca_decode_kernel / _fallback, cca_prefill_form, moe_router_form;
        # Keye: dsa_index_form, dsa_select_form, dsa_decode_kernel, dsa_prefill_form, moe_router_form;
        # GigaChat3.5: gdn_decode_kernel / _fallback, gdn_prefill_form, mla_decode_kernel / _fallback, mla_prefill_form, moe_router_form;
        # the paged per-head pool: kv_write_form, prefill_attend_form;
        # all three that decode through flash_decode_paged: paged_decode_walk;
        # every family whose chunk attends over per-head pages: chunk_attention_kernel / chunk_attention_fallback)
        out.update(self._trace_notes)
        out.update(self.timeline.summary())
        for stall in self.timeline.stalls() if out["stall_steps"] else ():
            if stall["step"] > self._stall_logged:
                self._stall_logged = stall["step"]
                logger.info(f"serving: stalled step {stall}")
        if out.get("programs"):
            # host→device transfers a program since the timeline's last
            # reset: 1.0, each program's inputs being one packed array
            out["staged_puts_per_program"] = out["stage_puts"] / out["programs"]
        return out


__all__ = [
    "ServingEngine", "ServingQueueFull", "ServingOverloaded", "ServingDraining",
    "Request",
]
