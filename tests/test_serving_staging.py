"""A serve program's host-made inputs as one packed array
(serving/staging.py): the layout round-trips every field bit for bit, the
abstract signature is what the live step stages, one executable a site
survives churn, the buffer the engine stages is the one built from the
scheduler's and the pool's from-scratch builders at every step, what was
handed over is nobody else's to write, and ``staged_puts_per_program``
reads 1.0.

The parent staged every input as a leaf of its own.  That staging lives
on here as the reference (``leafwise``): the same program bodies below
their unpacking line, fed ``decode_inputs()`` + ``sampling_inputs()`` +
``pool.tables()`` + the write-mask loop, one ``device_put`` leaf each.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.sanitizer import core as san_core
from deepspeed_tpu.analysis.sanitizer.core import Sanitizer
from deepspeed_tpu.config.config import SanitizerConfig
from deepspeed_tpu.models import deepseek_v2, gpt2, solar_open2
from deepspeed_tpu.runtime.overlap.timeline import StepTimeline
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.staging import PackedLayout

# a temperature bf16 cannot hold (nor any short binary fraction), seeds past 2**31
TEMP, SEED = 0.7, 0xDEADBEEF
MAX_TOP_K = 64  # ServingConfig's default head width

# one engine a program kind: the slot-contiguous pool, the paged GPT-2 pool, a family's cache
# kind that is pages and nothing else, and one that keeps per-slot state (the only kind told a ``slot``)
VARIANTS = {
    "slot_pool": (gpt2.GPT2_TINY, None),
    "paged_gpt2": (gpt2.GPT2_TINY, {"enabled": True, "page_len": 16}),
    "deepseek_v2": (deepseek_v2.DEEPSEEK_V2_TINY, {"enabled": True, "page_len": 16}),
    "solar_open2": (solar_open2.SOLAR_OPEN2_TINY, {"enabled": True, "page_len": 16}),
}


@pytest.fixture(scope="module", params=list(VARIANTS))
def make(request):
    mcfg, kv = VARIANTS[request.param]
    inf = deepspeed_tpu.init_inference(model_config=mcfg, dtype=jnp.float32, max_out_tokens=128, seed=3)

    def build(**kw):
        cfg = {"num_slots": 3, "max_len": 128, "prefill_chunk": 16, "max_new_tokens": 8, **kw}
        if kv is not None:
            cfg["kvcache"] = kv
        return ServingEngine(inf, config=cfg)

    build.variant, build.vocab = request.param, min(256, mcfg.vocab_size)
    return build


def _mixed_requests(vocab):
    rng = np.random.default_rng(11)
    shapes = [(20, 6), (37, 9), (5, 4), (50, 7), (16, 1), (33, 8), (3, 3), (70, 5)]
    sampling = [{}, {"do_sample": True, "temperature": TEMP, "top_k": 0, "seed": SEED},
                {"do_sample": True, "temperature": 1.3, "top_k": 5, "seed": 7}, {},
                {"do_sample": True, "temperature": TEMP, "top_k": MAX_TOP_K, "seed": 2**31}, {},
                {"do_sample": True, "temperature": 2.0, "top_k": 0, "seed": 2**32 - 1}, {"do_sample": True, "seed": 1}]
    return [(rng.integers(1, vocab, n, dtype=np.int32), m, kw) for (n, m), kw in zip(shapes, sampling)]


def _serve(srv, reqs):
    ids = [srv.submit(p, max_new_tokens=m, **kw) for p, m, kw in reqs]
    done = srv.drain()
    return [list(done[i].generated) for i in ids]


# ---------------------------------------------------------------------------
# the from-scratch builders: the oracle, and the parent's leaf-wise staging
# ---------------------------------------------------------------------------


def scratch_decode_fields(srv) -> dict:
    """The decode program's fields from the scheduler's and the pool's
    public from-scratch builders, as the parent staged them every step."""
    toks, pos, decoding = srv.scheduler.decode_inputs()
    flags, temps, topks, seeds = srv.scheduler.sampling_inputs()
    fields = dict(toks=toks, pos=pos, flags=flags, temps=temps, topks=topks, seeds=seeds)
    if srv._paged:
        wmask = np.zeros((srv.pool.num_slots,), np.bool_)
        for r in decoding:
            wmask[r.slot] = True
        fields.update(write_mask=wmask, tables=srv.pool.tables())
    return fields


def scratch_prefill_fields(srv, job) -> dict:
    """One chunk's fields as the parent's NumPy scalars (the slot's
    pending copy-on-write pair is read, not consumed)."""
    r = job.req
    fields = dict(tokens=job.tokens[None, :], pos=np.int32(job.start), take_idx=np.int32(job.take_idx),
                  do_sample=np.bool_(r.do_sample), temperature=np.float32(r.temperature),
                  top_k=np.int32(r.top_k), seed=np.uint32(r.seed & 0xFFFFFFFF))
    if srv._paged:
        cow = srv.pool._pending_cow.get(r.slot, (0, 0))
        fields.update(table=srv.pool.table(r.slot), cow_src=np.int32(cow[0]), cow_dst=np.int32(cow[1]))
    if not srv._paged or srv.pool.state is not None:
        fields["slot"] = np.int32(r.slot)
    return fields


class _Leaves:
    """A layout whose staged form *is* the dict of leaves."""

    unpack = staticmethod(lambda leaves: leaves)


def leafwise(srv) -> ServingEngine:
    """The engine staging its inputs leaf by leaf, as the parent did."""
    assert srv._prefill_fn is None and srv._decode_fn is None
    srv._decode_layout = srv._prefill_layout = _Leaves()
    srv._decode_inputs = lambda: scratch_decode_fields(srv)

    def prefill_inputs(job):
        fields = scratch_prefill_fields(srv, job)
        if srv._paged:
            srv.pool.consume_cow(job.req.slot)
        return fields

    srv._prefill_inputs = prefill_inputs
    srv._decode_abstract_args = srv._prefill_abstract_args = lambda: ()  # the ds_shard feed: not armed here
    return srv


def assert_fields_equal(got: dict, want: dict) -> None:
    assert set(got) == set(want)
    for name, w in want.items():
        g = np.asarray(got[name])
        assert g.dtype == np.asarray(w).dtype and g.shape == np.shape(w), (name, g.dtype, g.shape)
        np.testing.assert_array_equal(g, w, err_msg=name)


# ---------------------------------------------------------------------------
# the layout: pack -> unpack, bit for bit
# ---------------------------------------------------------------------------


def _values(layout, rng):
    """A value a field, hard ones among them: seeds past 2**31, a
    temperature bf16 cannot hold, ``top_k`` 0 and the head's width,
    ``cow_src != cow_dst``."""
    hard = {
        "temps": [TEMP, 1.0, 1e-3], "temperature": TEMP,
        "seeds": [SEED, 0, 2**32 - 1], "seed": SEED,
        "topks": [0, MAX_TOP_K, 1], "top_k": MAX_TOP_K,
        "flags": [True, False, True], "do_sample": True, "write_mask": [True, True, False],
        "cow_src": 9, "cow_dst": 3,
    }
    out = {}
    for f in layout.fields:
        if f.name in hard:
            out[f.name] = np.asarray(hard[f.name], f.dtype).reshape(f.shape)
        else:
            out[f.name] = rng.integers(0, 50_000, f.shape).astype(f.dtype)
    return out


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_layout_round_trips_every_field_bit_for_bit(make, which):
    srv = make()
    layout = getattr(srv, f"_{which}_layout")
    S, P, chunk = srv.pool.num_slots, getattr(srv.pool, "pages_per_slot", 0), srv.config.prefill_chunk
    names = [f.name for f in layout.fields]
    if which == "decode":
        assert layout.size == S * ((7 + P) if srv._paged else 6)
        assert names[:6] == ["toks", "pos", "flags", "temps", "topks", "seeds"]
    else:
        assert layout.size == chunk + P + len(names) - 1 - bool(P)
        assert ("slot" in names) == (make.variant in ("slot_pool", "solar_open2"))
        assert ("cow_src" in names) == ("cow_dst" in names) == ("table" in names) == srv._paged
    want = _values(layout, np.random.default_rng(0))
    packed = layout.pack(**want)
    assert packed.dtype == np.int32 and packed.shape == (layout.size,) and packed.base is None
    assert layout.pack(**want) is not packed  # made afresh every time
    got = jax.jit(layout.unpack)(jnp.asarray(packed))
    assert_fields_equal(got, want)
    # the bits, not a conversion
    temp, seed = ("temps", "seeds") if which == "decode" else ("temperature", "seed")
    assert np.asarray(got[temp]).reshape(-1)[0] == np.float32(TEMP) != np.float32(jnp.bfloat16(TEMP))
    assert int(np.asarray(got[seed]).reshape(-1)[0]) == SEED >= 2**31
    # a reused buffer's views lay the same words
    buf = layout.buffer()
    for name, view in layout.views(buf).items():
        view[...] = want[name]
    np.testing.assert_array_equal(buf, packed)


def test_layout_sizes_of_the_benchmarks_cells():
    def decode(S, P):
        rows = [(n, (S,), d) for n, d in (("toks", np.int32), ("pos", np.int32), ("flags", np.bool_), ("temps", np.float32),
                                          ("topks", np.int32), ("seeds", np.uint32), ("write_mask", np.bool_))]
        return PackedLayout(rows + [("tables", (S, P), np.int32)])

    assert [decode(S, P).size for S, P in ((16, 8), (32, 64), (160, 64))] == [240, 2_272, 11_360]
    assert [f.offset for f in decode(16, 8).fields] == [0, 16, 32, 48, 64, 80, 96, 112]


def test_layout_refuses_what_it_cannot_carry_bit_exact():
    with pytest.raises(ValueError, match="32-bit"):
        PackedLayout([("x", (2,), np.float64)])
    with pytest.raises(ValueError, match="32-bit"):
        PackedLayout([("x", (2,), np.int8)])
    with pytest.raises(ValueError, match="duplicate"):
        PackedLayout([("x", (2,), np.int32), ("x", (), np.int32)])
    lay = PackedLayout([("x", (2,), np.int32), ("y", (), np.uint32)])
    with pytest.raises(ValueError, match="fields"):
        lay.pack(x=[1, 2])
    with pytest.raises(ValueError, match="fields"):
        lay.pack(x=[1, 2], y=3, z=0)


# ---------------------------------------------------------------------------
# the abstract signature is what the live step stages; one executable a site
# ---------------------------------------------------------------------------


def _record_staged(srv):
    """What ``_stage`` handed back, by the size of what it was given."""
    staged, stage = {}, srv._stage

    def recording(host):
        out = stage(host)
        staged.setdefault(np.shape(host), []).append((host, out))
        return out

    srv._stage = recording
    return staged


def test_abstract_signature_is_what_the_live_step_stages(make):
    srv = make()
    staged = _record_staged(srv)
    _serve(srv, _mixed_requests(make.vocab)[:4])
    for which in ("prefill", "decode"):
        layout = getattr(srv, f"_{which}_layout")
        args = getattr(srv, f"_{which}_abstract_args")()
        assert len(args) == 2 + len(srv._pool_args())
        abstract = args[1]
        assert isinstance(abstract, jax.ShapeDtypeStruct)
        for host, live in staged[(layout.size,)]:
            assert isinstance(host, np.ndarray) and host.dtype == np.int32  # one array, not a tuple of leaves
            assert (live.shape, live.dtype) == (abstract.shape, abstract.dtype) == ((layout.size,), jnp.int32)
            assert live.sharding.is_equivalent_to(abstract.sharding, live.ndim)
        # the pools' part of the signature is the donated part
        live_pools = jax.tree.leaves(srv._pool_args())
        abstract_pools = jax.tree.leaves(args[2:])
        assert [(a.shape, a.dtype) for a in live_pools] == [(a.shape, a.dtype) for a in abstract_pools]
        # and the AOT lowering takes it: the executable compiled_step() compiles is the live call's
        assert getattr(srv, f"_{which}_jit").lower(*args).as_text().startswith(f"module @jit_serve_{which} ")
    assert len(staged) == 2 and (srv.prefill_compiles, srv.decode_compiles) == (1, 1)


@pytest.fixture
def san():
    cfg = SanitizerConfig.from_dict({"enabled": True, "checkers": ["recompile", "transfer"], "compile_budget": 2})
    s = san_core.install(Sanitizer(cfg))
    try:
        yield s
    finally:
        san_core.uninstall()


def test_one_executable_a_site_over_admissions_finishes_and_a_slot_taken_back(make, san):
    """The sanitizer's recompile proof over the packed signature.  The
    scheduler preempts nothing by itself: the slot taken back mid-decode
    is a cancelled in-flight request's, and its next occupant's."""
    srv = make()
    assert srv._sanitizer is san
    reqs = _mixed_requests(make.vocab)
    ids = [srv.submit(p, max_new_tokens=m, **kw) for p, m, kw in reqs[:5]]
    for _ in range(4):
        srv.step()
    live = next(r for r in srv.scheduler._active.values() if r.generated)
    assert srv.cancel(live.request_id)
    ids += [srv.submit(p, max_new_tokens=m, **kw) for p, m, kw in reqs[5:]]
    done = srv.drain(max_steps=300)
    assert sorted(done) == sorted(ids)
    assert {done[i].status for i in ids} == {"done", "cancelled"}
    assert (srv.prefill_compiles, srv.decode_compiles) == (1, 1)
    counts = san.recompile.compile_counts()
    assert (counts.get("serving.prefill"), counts.get("serving.decode")) == (1, 1), counts
    # no recompiles, and no transfer but the sanctioned one
    assert san.findings == [], [f.format() for f in san.findings]
    assert srv.stats()["staged_puts_per_program"] == 1.0


# ---------------------------------------------------------------------------
# token identity with the parent's leaf-wise staging, greedy and sampled
# ---------------------------------------------------------------------------


def test_tokens_are_the_leafwise_stagings(make):
    """Greedy and sampled requests over three slots (so slots are
    reused, chunks are split, a one-token budget retires inside
    ``note_prefill``): token for token what the same bodies emit when
    every input is staged as a leaf of its own, built from scratch."""
    reqs = _mixed_requests(make.vocab)
    ref, srv = leafwise(make()), make()
    want, got = _serve(ref, reqs), _serve(srv, reqs)
    assert got == want
    assert [len(g) for g in got] == [m for _, m, _ in reqs]
    st, st_ref = srv.stats(), ref.stats()
    assert (st["prefill_compiles"], st["decode_compiles"]) == (1, 1)
    assert st["programs"] == st_ref["programs"] == st["stage_puts"]
    # the parent's leaves: 6 or 8 a decode, 8 to 11 a chunk
    assert st["staged_puts_per_program"] == 1.0 and 6.0 <= st_ref["staged_puts_per_program"] <= 11.0


def test_sampled_request_in_a_busy_pool_bit_matches_it_served_alone_leaf_by_leaf(make):
    """``do_sample``, a temperature that is no bf16, ``top_k`` and a seed
    past 2**31 through the packed path, among neighbours and in whatever
    slot: the tokens of the same request alone on the leaf-wise engine."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, make.vocab, 21, dtype=np.int32)
    kw = dict(max_new_tokens=8, do_sample=True, temperature=TEMP, top_k=7, seed=SEED)
    solo = leafwise(make())
    rid = solo.submit(prompt, **kw)
    want = list(solo.drain()[rid].generated)
    busy = make()
    for p, m, skw in _mixed_requests(make.vocab)[:2]:
        busy.submit(p, max_new_tokens=m, **skw)
    busy.step()
    rid = busy.submit(prompt, **kw)
    got = busy.drain()[rid]
    assert list(got.generated) == want and len(want) == 8
    greedy = make()
    rid = greedy.submit(prompt, max_new_tokens=8)
    assert list(greedy.drain()[rid].generated) != want  # the sampling parameters did arrive


# ---------------------------------------------------------------------------
# the staged buffer against the from-scratch builders, at every step
# ---------------------------------------------------------------------------


def _check_every_staging(srv) -> dict:
    """Hold every array the engine stages against the from-scratch
    builders, at the moment it is staged."""
    seen = {"prefill": 0, "decode": 0, "cow": 0}
    decode_inputs, prefill_inputs = srv._decode_inputs, srv._prefill_inputs

    def checked_decode():
        want = scratch_decode_fields(srv)
        host = decode_inputs()
        np.testing.assert_array_equal(host, srv._decode_layout.pack(**want))
        assert_fields_equal(srv._decode_layout.unpack(host), want)
        assert not np.shares_memory(host, srv._decode_buffer)
        seen["decode"] += 1
        return host

    def checked_prefill(job):
        want = scratch_prefill_fields(srv, job)
        host = prefill_inputs(job)
        np.testing.assert_array_equal(host, srv._prefill_layout.pack(**want))
        assert_fields_equal(srv._prefill_layout.unpack(host), want)
        seen["prefill"] += 1
        seen["cow"] += int(want.get("cow_src", 0) != want.get("cow_dst", 0))
        return host

    srv._decode_inputs, srv._prefill_inputs = checked_decode, checked_prefill
    return seen


@pytest.mark.parametrize("seed", [23, 24])
def test_staged_buffer_is_the_from_scratch_build_at_every_step(make, seed):
    """A seeded random sequence of submit / chunk / decode / finish /
    cancel and, where the pool reuses pages, session rebinds and prefix
    hits with copy-on-write."""
    reuses = make.variant in ("paged_gpt2", "deepseek_v2")
    # a pool that reuses prefixes gets chunks of half a page, so that a hit can end inside one
    srv = make(num_slots=4, prefill_chunk=8 if reuses else 16)
    assert reuses == bool(srv._paged and srv.pool.reuse)
    seen = _check_every_staging(srv)
    rng = np.random.default_rng(seed)
    mixed = _mixed_requests(make.vocab)
    shared = rng.integers(1, make.vocab, 40, dtype=np.int32)
    turns = {}  # session -> its history so far
    ids, steps, submitted = [], 0, 0
    while submitted < 18 or srv.scheduler.has_work():
        op = rng.integers(0, 10) if submitted < 18 else 9
        if op < 4:
            p, m, kw = mixed[int(rng.integers(0, len(mixed)))]
            kind = int(rng.integers(0, 3))
            if kind == 1:
                # sharers of a run that ends inside a page: the later ones map the first's pages and copy the tail
                p = np.concatenate([shared, rng.integers(1, make.vocab, int(rng.integers(2, 10)), dtype=np.int32)])
            elif kind == 2:
                sid = f"s{int(rng.integers(0, 2))}"
                p = np.concatenate([turns.get(sid, p[:12]), rng.integers(1, make.vocab, 5, dtype=np.int32)])
                if len(p) + m > 120:
                    continue
                kw = {**kw, "session_id": sid}
            ids.append(srv.submit(p, max_new_tokens=m, **kw))
            submitted += 1
        elif op == 4 and srv.scheduler._active:
            live = list(srv.scheduler._active.values())
            assert srv.cancel(live[int(rng.integers(0, len(live)))].request_id)
        elif op == 5 and srv.scheduler.queue_depth:
            assert srv.cancel(srv.scheduler._queue[-1].request_id)
        else:
            srv.step()
            steps += 1
            for rid, r in srv.pop_results().items():
                if r.session_id is not None and r.status == "done":
                    turns[r.session_id] = r.tokens()
    assert seen["decode"] > 20 and seen["prefill"] > 20 and steps > 20
    st = srv.stats()
    assert st["cancelled"] >= 1 and st["finished"] >= 6
    assert st["staged_puts_per_program"] == 1.0 and st["programs"] == seen["decode"] + seen["prefill"]
    if reuses:
        kv = st["kvcache"]
        assert kv["cow_copies"] >= 1 and seen["cow"] >= 1 and kv["prefix_hits"] >= 1
        assert seed != 23 or kv["session_rebinds"] >= 1


# ---------------------------------------------------------------------------
# the hand-over: what was staged is nobody else's to write
# ---------------------------------------------------------------------------


def test_writing_the_host_buffer_after_dispatch_reaches_neither_the_staged_array_nor_the_tokens(make):
    """On the CPU backend ``device_put`` may alias an aligned NumPy
    array's memory, and a dispatched program may not have read it yet."""
    reqs = _mixed_requests(make.vocab)
    want = _serve(make(), reqs)

    srv = make()
    srv._get_prefill(), srv._get_decode()
    hosts, handed, stage = [], [], srv._stage

    def staging(host):
        hosts.append(host)
        handed.append(host.copy())
        return stage(host)

    def scribbling(fn):
        def call(params, staged, *pools):
            out = fn(params, staged, *pools)
            # dispatched, not waited for: the buffers the next step fills are overwritten (the array
            # handed over is given away — where the backend aliased it, writing *it* would show) ...
            srv._decode_buffer[:] = -1
            srv._prefill_buffer[:] = -1
            # ... and the device's array is still what was handed over
            np.testing.assert_array_equal(np.asarray(staged), handed[-1])
            return out
        return call

    srv._stage = staging
    srv._prefill_fn, srv._decode_fn = scribbling(srv._prefill_fn), scribbling(srv._decode_fn)
    got = _serve(srv, reqs)
    assert got == want
    assert len(hosts) == srv.stats()["programs"] > 20
    for host in hosts:
        assert host.base is None
        assert not np.shares_memory(host, srv._decode_buffer) and not np.shares_memory(host, srv._prefill_buffer)


def test_a_staged_snapshot_outlives_the_buffers_next_fill(make):
    srv = make()
    for p, m, kw in _mixed_requests(make.vocab)[:3]:
        srv.submit(p, max_new_tokens=m, **kw)
    for _ in range(3):
        srv.step()
    host = srv._decode_inputs()
    staged = srv._stage(host)
    before = np.array(staged)
    np.testing.assert_array_equal(before, srv._decode_buffer)
    assert before.any()
    srv._decode_buffer += 7
    np.testing.assert_array_equal(np.asarray(staged), before)
    srv.drain()  # later steps fill the buffer again
    np.testing.assert_array_equal(np.asarray(staged), before)


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------


def test_staged_puts_per_program_is_one_and_resets_with_the_timeline(make):
    srv = make()
    assert "staged_puts_per_program" not in srv.stats()  # nothing ran yet
    srv._get_prefill(), srv._get_decode()
    calls = []

    def counting(fn):
        def call(*args):
            calls.append(1)
            return fn(*args)
        return call

    srv._prefill_fn, srv._decode_fn = counting(srv._prefill_fn), counting(srv._decode_fn)
    _serve(srv, _mixed_requests(make.vocab))
    tl, st = srv.timeline.summary(), srv.stats()
    assert tl["stage_puts"] == st["stage_puts"] == tl["programs"] == st["programs"] == len(calls) > 20
    assert st["staged_puts_per_program"] == 1.0 and isinstance(st["staged_puts_per_program"], float)
    assert tl["steps"] < len(calls) <= 2 * tl["steps"] + 8  # a decode every step, chunks in some
    # a benchmark window opens: the counters start afresh with the records
    srv.timeline.reset_window()
    tl, st = srv.timeline.summary(), srv.stats()
    assert (tl["stage_puts"], tl["programs"], tl["steps"]) == (0, 0, 0) and "staged_puts_per_program" not in st
    _serve(srv, _mixed_requests(make.vocab)[:2])
    assert srv.stats()["staged_puts_per_program"] == 1.0
    # the counter counts what is handed over, not what should be: two leaves are two transfers
    puts = srv.timeline.counts["stage_puts"]
    srv._stage((np.zeros(3, np.int32), np.zeros(2, np.int32)))
    assert srv.timeline.counts["stage_puts"] == puts + 2


def test_counters_leave_the_summarys_other_keys_alone():
    phases = dict(phases=("sched", "prefill", "decode"), sub_phases=("stage", "dispatch", "wait"), blocked_on="wait", prefix="serve")
    plain, counted, off = StepTimeline(**phases), StepTimeline(**phases), StepTimeline(enabled=False, **phases)
    for tl in (plain, counted, off):
        for step in range(3):
            tl.note("sched", 0.001), tl.note("prefill", 0.020 * step), tl.note("decode", 0.030)
            tl.note("stage", 0.002), tl.note("dispatch", 0.001), tl.note("wait", 0.040)
            tl.set_gauge("queue_depth", step), tl.set_gauge("live_slots", 3)
            if tl is not plain:
                tl.count("stage_puts", 1 + (step > 0)), tl.count("programs", 1 + (step > 0))
            tl._last_boundary = None  # the wall is what was noted: the timelines read no clock
            tl.end_step()
    a, b = plain.summary(), counted.summary()
    assert (b.pop("stage_puts"), b.pop("programs")) == (5, 5)
    assert a == b
    assert off.counts == {} and "stage_puts" not in off.summary()
    # totals since the last reset, whatever part of the window is summarised
    assert counted.summary(last_n=1)["stage_puts"] == 5
    counted.reset_window()
    assert counted.summary()["stage_puts"] == 0 and counted.summary()["steps"] == 0
