"""The engines' own phases on the profiler's clock, and as whole-window
percentiles (docs/telemetry.md, "On the profiler's clock").

``StepTimeline.summary()`` percentiles on hand-made records with the
serving sub-phases; a toy ``ServingEngine`` and a toy ``train_batch``
under ``jax.profiler.start_trace`` on the CPU write the whole ``ds.*``
vocabulary into the host plane, nested in the step's span with its
number, and nothing of a step taken while no trace runs; the three step
programs are named (``jit_serve_prefill``, ``jit_serve_decode``,
``jit_train_step``) and are otherwise what they were: the lowered text
is the same with a profiler trace running and without, and the same as
``jax.jit`` of the same function under its old name gives, apart from
the module name."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime.overlap.timeline import StepTimeline
from deepspeed_tpu.serving import ServingEngine

pytestmark = pytest.mark.telemetry

SERVE_VOCABULARY = {
    "ds.serve.step", "ds.serve.sweep", "ds.serve.sched", "ds.serve.commit",
    "ds.serve.prefill", "ds.serve.prefill.stage", "ds.serve.prefill.dispatch", "ds.serve.prefill.wait", "ds.serve.prefill.note",
    "ds.serve.decode", "ds.serve.decode.stage", "ds.serve.decode.dispatch", "ds.serve.decode.wait", "ds.serve.decode.note",
}
# the spans under which every instant of a step lies exactly once
SERVE_LEAVES = SERVE_VOCABULARY - {"ds.serve.step", "ds.serve.prefill", "ds.serve.decode"}
SERVE_PHASES = dict(phases=("sched", "prefill", "decode"), sub_phases=("stage", "dispatch", "wait"),
                    blocked_on="wait", prefix="serve")
# the serving engine's own since the hand-back, the sweep and the commit are timed
ENGINE_PHASES = dict(phases=("sweep", "sched", "prefill", "decode", "commit"), sub_phases=("stage", "dispatch", "wait", "note"),
                     blocked_on="wait", prefix="serve")


# ---------------------------------------------------------------------------
# summary(): whole-window percentiles, sub-phases
# ---------------------------------------------------------------------------


def _serve_step(tl, sched, prefill, decode, stage, dispatch, wait):
    """One step noted the way the engine's phases nest: the sub-phases
    of both programs lie inside prefill and decode."""
    tl.note("sched", sched)
    tl.note("prefill", prefill)
    tl.note("decode", decode)
    for name, (in_prefill, in_decode) in (("stage", stage), ("dispatch", dispatch), ("wait", wait)):
        tl.note(name, in_prefill)
        tl.note(name, in_decode)
    tl.end_step()


class TestSummaryPercentiles:
    def _timeline(self):
        tl = StepTimeline(**SERVE_PHASES)
        # ten steps of 237 ms and one slow one: 7 ms more of staging
        for _ in range(10):
            _serve_step(tl, 0.001, 0.088, 0.148, (0.001, 0.001), (0.001, 0.001), (0.085, 0.144))
        _serve_step(tl, 0.001, 0.092, 0.151, (0.005, 0.004), (0.001, 0.001), (0.085, 0.144))
        return tl

    def test_sub_phases_are_summed_per_step_and_left_out_of_the_wall(self):
        tl = self._timeline()
        rec = tl.records[0]
        assert rec["stage"] == pytest.approx(0.002) and rec["wait"] == pytest.approx(0.229)
        # counted again they would zero `other` and inflate `wall`
        assert rec["wall"] == pytest.approx(0.237) and rec["other"] == pytest.approx(0.0, abs=1e-9)
        assert tl.records[-1]["wall"] == pytest.approx(0.244)

    def test_percentiles_show_the_slow_step_the_mean_hides(self):
        s = self._timeline().summary()
        assert s["steps"] == 11
        assert s["wall_ms_p50"] == pytest.approx(237.0) and s["wall_ms_p95"] == pytest.approx(240.5)
        assert s["stage_ms_p50"] == pytest.approx(2.0) and s["stage_ms_p95"] == pytest.approx(5.5)
        assert s["wait_ms_p50"] == s["wait_ms_p95"] == pytest.approx(229.0)
        # host = wall - wait, per step: where the slow step's 7 ms went
        assert s["host_ms_p50"] == pytest.approx(8.0) and s["host_ms_p95"] == pytest.approx(11.5)
        assert s["wait_ms_p50"] + s["host_ms_p50"] == pytest.approx(s["wall_ms_p50"])
        for p in ("sched", "prefill", "decode", "other", "stage", "dispatch", "wait", "wall"):
            assert f"{p}_ms_p50" in s and f"{p}_ms_p95" in s

    def test_existing_keys_keep_their_values(self):
        plain = StepTimeline(phases=("sched", "prefill", "decode"))
        for tl in (plain, StepTimeline(**SERVE_PHASES)):
            tl.note("sched", 0.001)
            tl.note("prefill", 0.088)
            tl.note("decode", 0.148)
        plain.end_step()
        s0 = plain.summary()
        tl.note("stage", 0.002)
        tl.note("wait", 0.229)
        tl.end_step()
        s1 = tl.summary()
        assert {k: s1[k] for k in s0 if not k.endswith(("_p50", "_p95"))} == \
            {k: v for k, v in s0.items() if not k.endswith(("_p50", "_p95"))}
        assert (s1["sched_ms"], s1["prefill_ms"], s1["decode_ms"], s1["wall_ms"]) == (1.0, 88.0, 148.0, 237.0)
        assert "host_ms_p50" not in s0  # no `blocked_on`, no host remainder

    def test_a_dotted_phase_is_recorded_under_its_last_component(self):
        tl = StepTimeline(**SERVE_PHASES)
        with tl.phase("prefill"):
            with tl.phase("prefill.wait"):
                pass
        with tl.phase("decode"):
            with tl.phase("decode.wait"):
                pass
        tl.end_step()
        rec = tl.records[0]
        assert set(rec) == {"sched", "prefill", "decode", "stage", "dispatch", "wait", "other", "wall"}
        assert 0 < rec["wait"] <= rec["prefill"] + rec["decode"] <= rec["wall"]

    def test_empty_and_train_timelines(self):
        assert StepTimeline(**SERVE_PHASES).summary()["steps"] == 0
        tl = StepTimeline()
        tl.note("data_wait", 0.004)
        tl.end_step()
        s = tl.summary()
        assert s["data_wait_ms_p50"] == s["data_wait_ms_p95"] == s["wall_ms_p50"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# summary(): the whole window, and its stalls
# ---------------------------------------------------------------------------


def _engine_step(tl, wait=0.030, sweep=0.0002, sched=0.0001, note=0.0003, commit=0.0004, other=0.0):
    """One decode-only step as the engine's phases nest, its wall what
    was noted (the timeline reads no clock for it)."""
    stage, dispatch = 0.0006, 0.0005
    tl.note("sweep", sweep), tl.note("sched", sched), tl.note("commit", commit), tl.note("other", other)
    tl.note("decode", stage + dispatch + wait + note)
    tl.note("stage", stage), tl.note("dispatch", dispatch), tl.note("wait", wait), tl.note("note", note)
    tl._last_boundary = None
    tl.end_step()


class TestTheWholeWindow:
    @pytest.mark.parametrize("steps,window,held", [(700, 32768, 700), (1610, 32768, 1610), (1610, 1024, 1024), (513, 512, 512)])
    def test_every_step_since_the_reset_is_reported_or_counted_as_dropped(self, steps, window, held):
        tl = StepTimeline(window=window, **ENGINE_PHASES)
        for _ in range(37):  # before the window opens
            _engine_step(tl, wait=0.5)
        tl.reset_window()
        for k in range(steps):
            _engine_step(tl, wait=0.030 + 1e-6 * k)
        s = tl.summary()
        assert (s["steps"], s["steps_dropped"]) == (held, steps - held) and tl.total_steps == 37 + steps
        # the percentiles are the held steps', none of the 500 ms ones from before the reset
        first = steps - held
        assert s["wait_ms_p50"] == pytest.approx(30.0 + 1e-3 * (first + (held - 1) / 2), abs=2e-3)
        assert s["wait_ms_max"] == pytest.approx(30.0 + 1e-3 * (steps - 1), abs=2e-3) and s["wall_ms_max"] < 40
        recs = tl.records
        assert len(recs) == held and recs[0]["wait"] == pytest.approx(0.030 + 1e-6 * first)
        assert recs[-1]["wait"] == pytest.approx(0.030 + 1e-6 * (steps - 1))
        assert tl.summary(last_n=10)["steps"] == 10 and tl.summary(last_n=10)["wait_ms_p50"] > s["wait_ms_p50"]

    def test_the_new_phases_add_up_with_the_old_ones_to_the_wall(self):
        tl = StepTimeline(**ENGINE_PHASES)
        for _ in range(5):
            _engine_step(tl, other=0.0002)
        s = tl.summary()
        for q in ("", "_p50", "_p95"):
            assert sum(s[f"{p}_ms{q}"] for p in ("sweep", "sched", "prefill", "decode", "commit", "other")) == pytest.approx(s[f"wall_ms{q}"], abs=2e-3)
            # inside decode: the three of before and the hand-back
            assert sum(s[f"{p}_ms{q}"] for p in ("stage", "dispatch", "wait", "note")) == pytest.approx(s[f"decode_ms{q}"], abs=2e-3)
        assert (s["sweep_ms_p50"], s["commit_ms_p50"], s["note_ms_p50"], s["other_ms_p50"]) == (0.2, 0.4, 0.3, 0.2)
        assert s["host_ms_p50"] == pytest.approx(s["wall_ms_p50"] - 30.0, abs=2e-3)
        assert set(tl.records[0]) == {"sweep", "sched", "prefill", "decode", "commit", "stage", "dispatch", "wait", "note", "other", "wall"}

    def test_a_planted_step_of_five_medians_is_one_stall(self):
        tl = StepTimeline(**ENGINE_PHASES)
        assert tl.summary()["stall_first_at_s"] == -1.0 and tl.stalls() == []
        for k in range(700):
            # one step in four carries a chunk: 1.8 x the median, an ordinary step
            _engine_step(tl, wait=0.160 if k == 400 else 0.055 if k % 4 == 0 else 0.030)
        s = tl.summary()
        p50 = s["wall_ms_p50"]
        assert p50 == pytest.approx(32.1, abs=1e-2) and s["wall_ms_max"] == pytest.approx(162.1, abs=1e-2)
        assert s["stall_steps"] == 1 and s["stall_ms"] == pytest.approx(162.1 - p50, abs=1e-2) and s["stall_first_at_s"] != -1.0
        assert all(isinstance(v, (int, float, str)) for v in s.values())  # the summary stays scalars
        (stall,) = tl.stalls()
        assert stall["step"] == 401 and stall["wall_ms"] == pytest.approx(162.1) and stall["at_s"] == s["stall_first_at_s"]
        # where the step's time went: all of it inside the blocking read
        assert stall["wait_ms"] == pytest.approx(160.0) and stall["note_ms"] == pytest.approx(0.3) and stall["commit_ms"] == pytest.approx(0.4)
        assert {f"{p}_ms" for p in ("sweep", "sched", "prefill", "decode", "commit", "other", "stage", "dispatch", "wait", "note")} <= set(stall)
        # a window without it has none; the counters start afresh with the window
        assert tl.summary(last_n=100)["stall_steps"] == 0
        tl.reset_window()
        assert tl.summary()["stall_steps"] == 0 and tl.stalls() == []

    @pytest.mark.parametrize("among", [None, "reads"])
    def test_a_step_is_a_stall_among_the_steps_that_read_as_many_programs(self, among):
        """The default order of a serving step: one step in six waits for the chunk before its own, the decode step and
        a prompt's last chunk (3.5 window medians), one in three for two programs — no stall among them but the one planted."""
        tl = StepTimeline(stall_among=among, **ENGINE_PHASES)
        for k in range(480):
            reads = 3 if k % 6 == 0 else 2 if k % 3 == 0 else 1
            tl.set_gauge("reads", reads)
            _engine_step(tl, wait=0.500 if k == 300 else {1: 0.030, 2: 0.060, 3: 0.110}[reads])
        s = tl.summary()
        assert s["wall_ms_p50"] == pytest.approx(32.1, abs=1e-2) and s["reads"] == pytest.approx(1.5, abs=1e-2)
        if among is None:  # the window's median alone: every step of three programs reads as a stall
            assert s["stall_steps"] == 80
            return
        assert s["stall_steps"] == 1 and s["stall_ms"] == pytest.approx(502.1 - 112.1, abs=1e-2)
        assert [x["step"] for x in tl.stalls()] == [301]

    def test_stalls_names_the_longest_sixteen_in_step_order(self):
        tl = StepTimeline(**ENGINE_PHASES)
        for k in range(400):
            _engine_step(tl, wait=0.200 + 0.001 * k if k % 20 == 0 else 0.030)
        s = tl.summary()
        assert s["stall_steps"] == 20
        stalls = tl.stalls()
        assert [x["step"] for x in stalls] == [1 + 20 * j for j in range(4, 20)]

    def test_the_plane_is_handed_the_phases_it_is_told_and_no_others(self):
        class Plane:
            class tracer:
                enabled, spans = True, []
                now = staticmethod(lambda: 0.0)
                add_span = classmethod(lambda cls, name, *a, **kw: cls.spans.append(name))

            published = []

            def publish_step(self, prefix, rec, count=1, gauge_names=()):
                self.published.append(set(rec))
                self.last = rec

        tl = StepTimeline(**ENGINE_PHASES)
        plane = Plane()
        tl.attach_telemetry(plane, prefix="serving", phases=("sched", "prefill", "decode", "stage", "dispatch", "wait"))
        for name in ("sweep", "sched", "decode", "commit"):
            with tl.phase(name):
                if name == "decode":
                    with tl.phase("decode.wait"), tl.phase("decode.note"):
                        pass
        tl.set_gauge("queue_depth", 3)
        tl.end_step()
        assert Plane.tracer.spans == ["serving/sched", "serving/decode"]
        assert Plane.published == [{"sched", "prefill", "decode", "stage", "dispatch", "wait", "other", "wall", "queue_depth"}]
        # what the plane is not told of is its `other`: the phases it has still add up to the wall
        rec, got = tl.records[0], plane.last
        assert got["other"] == pytest.approx(rec["other"] + rec["sweep"] + rec["commit"]) and rec["sweep"] > 0 and rec["commit"] > 0
        assert sum(got[p] for p in ("sched", "prefill", "decode", "other")) == pytest.approx(got["wall"])
        # the summary has them all the same
        assert tl.summary()["commit_ms_p50"] >= 0 and "sweep_ms_p50" in tl.summary() and "note_ms_p50" in tl.summary()


# ---------------------------------------------------------------------------
# the ds.* spans in a jax.profiler trace
# ---------------------------------------------------------------------------


class _Trace:
    """A ``jax.profiler`` trace round a block, as the benchmark's harness
    takes it (no Python tracer); ``spans`` afterwards: the host planes'
    ``ds.*`` events as ``(name, start_ns, end_ns, step or None)``."""

    def __init__(self, path):
        self.path, self.spans, self.args, self.every = str(path), [], {}, {}

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.path, "plugins", "profile", "*", "*.xplane.pb"))
        for plane in jax.profiler.ProfileData.from_file(found[-1]).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("ds."):
                            stats = dict(e.stats)
                            self.args.setdefault(e.name, stats)  # the first event's arguments
                            self.every.setdefault(e.name, []).append((e.start_ns, stats))  # and every event's
                            step = stats.get("step")
                            self.spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                               None if step is None else int(step)))
        self.spans.sort(key=lambda s: s[1])

    def inside(self, outer):
        return [s for s in self.spans if s is not outer and outer[1] <= s[1] and s[2] <= outer[2]]


@pytest.fixture(scope="module")
def serving():
    cfg = dataclasses.replace(gpt2.GPT2_TINY, remat=False)
    eng = deepspeed_tpu.init_inference(
        model_config=cfg, params=gpt2.init_params(cfg, seed=7), dtype=jnp.float32,
        max_out_tokens=cfg.n_positions,
    )
    srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64,
                        config={"kvcache": {"enabled": True, "page_len": 16}})
    # both programs compile here, outside any trace
    srv.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
    srv.drain()
    return srv


def _train_engine():
    cfg = dataclasses.replace(gpt2.GPT2_TINY, remat=False, scan_unroll=gpt2.GPT2_TINY.n_layer)
    model_fn, init_fn, tp_fn = gpt2.make_model(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=init_fn(), tp_spec_fn=tp_fn,
        config={"train_micro_batch_size_per_gpu": 2, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
    )
    batch = {"input_ids": np.random.default_rng(0).integers(0, cfg.vocab_size, (16, 16), dtype=np.int32)}
    return engine, batch


class TestSpansInTheProfilersTrace:
    @pytest.mark.parametrize("serial", [False, True], ids=["default-order", "serial-order"])
    def test_serving_vocabulary_nested_in_the_step_with_its_number(self, serving, tmp_path, serial):
        srv = serving
        if serial:
            srv = ServingEngine(serving.engine, config=dataclasses.replace(serving.config, overlap_chunks=False))
            srv.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)  # learns the prompt as a prefix, outside the trace
            srv.drain()
        before = srv._step_count
        with _Trace(tmp_path) as tr:
            rid = srv.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
            srv.drain()
        assert {s[0] for s in tr.spans} == SERVE_VOCABULARY
        steps = [s for s in tr.spans if s[0] == "ds.serve.step"]
        # only the steps taken while the trace ran, each under its own number
        assert [s[3] for s in steps] == list(range(before + 1, srv._step_count + 1))
        assert all(s[3] is None for s in tr.spans if s[0] != "ds.serve.step")
        # every other span lies inside one step's span ...
        assert sum(len(tr.inside(s)) for s in steps) == len(tr.spans) - len(steps)
        # ... and a program's four sub-phases inside a phase of its own, in order
        for which in ("prefill", "decode"):
            one = [f"ds.serve.{which}.{p}" for p in ("stage", "dispatch", "wait", "note")]
            inner = [tr.inside(outer) for outer in tr.spans if outer[0] == f"ds.serve.{which}"]
            assert sum(len(i) for i in inner) == sum(1 for s in tr.spans if s[0] in one)
            if serial:  # each program read back before the next is staged (prefill: a chunk after a chunk)
                assert all([s[0] for s in i] == one * (len(i) // 4) for i in inner)
            # the n-th program staged is the n-th dispatched, waited for and noted, each after the other: whichever the
            # order, and though the default one reads a chunk that is not its prompt's last a step late
            runs = [[s for s in tr.spans if s[0] == name] for name in one]
            assert len({len(r) for r in runs}) == 1 and len(runs[0]) >= 1
            assert all(a[2] <= b[1] for earlier, later in zip(runs, runs[1:]) for a, b in zip(earlier, later))
        for step in steps:
            inside = tr.inside(step)
            assert [s[0] for s in inside][:2] == ["ds.serve.sweep", "ds.serve.sched"]
            # the serial step runs its chunks first; the default one hands the decode step over first where a row decodes
            assert inside[2][0] == "ds.serve.prefill" if serial else inside[2][0] in ("ds.serve.decode", "ds.serve.prefill")
            assert inside[-1][0] == "ds.serve.commit"
            # the leaves follow one another and none overlaps the next: every instant under at most one
            leaves = [s for s in inside if s[0] in SERVE_LEAVES]
            assert all(a[2] <= b[1] for a, b in zip(leaves, leaves[1:]))
        assert {s[0] for s in tr.spans if s[0] in SERVE_LEAVES} == SERVE_LEAVES
        # one request's chunks share an identifier: the chunk's span names its request and where it starts
        chunks = [{k: int(a[k]) for k in ("request", "start", "len")} for _, a in sorted(tr.every["ds.serve.prefill.dispatch"])]
        assert {c["request"] for c in chunks} == {rid} and all(0 < c["len"] <= 8 for c in chunks)
        # they tile the prompt from where the prefix cache let it begin to its end
        assert all(a["start"] + a["len"] == b["start"] for a, b in zip(chunks, chunks[1:])) and chunks[-1]["start"] + chunks[-1]["len"] == 11
        assert "request" not in tr.args["ds.serve.decode.dispatch"]

    def test_nothing_is_written_of_a_step_taken_while_no_trace_runs(self, serving, tmp_path):
        srv = serving
        srv.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=2)
        srv.drain()
        with _Trace(tmp_path) as tr:
            pass
        assert tr.spans == []
        # the timeline's own bookkeeping does not need the profiler
        s = srv.timeline.summary()
        assert s["wait_ms_p50"] > 0 and s["stage_ms_p50"] > 0 and s["host_ms_p95"] >= s["host_ms_p50"] > 0

    def test_the_engine_reports_every_step_of_a_window_and_its_stalls_once(self, serving, monkeypatch):
        from deepspeed_tpu.serving import engine as engine_mod

        srv = serving
        srv.timeline.reset_window()
        srv.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
        for k in range(600):  # past the 512 the summary used to hold; most of them idle
            if k == 300:
                srv.timeline.note("other", 5.0)  # a step that took five seconds, planted
            srv.step()
        s = srv.stats()
        assert (s["steps"], s["steps_dropped"]) == (600, 0) and srv.timeline.window == engine_mod.TIMELINE_STEPS
        for key in ("note_ms_p50", "commit_ms_p50", "sweep_ms_p50", "dispatch_ms_p50", "wall_ms_max", "wait_ms_max", "stall_ms"):
            assert isinstance(s[key], float), key
        assert s["stall_steps"] >= 1 and s["stall_ms"] > 4000 and s["stall_first_at_s"] >= 0 and s["wall_ms_max"] >= 5000
        # stats() names each stall once, at info; the summary stays scalars
        lines = []
        monkeypatch.setattr(engine_mod.logger, "info", lines.append)
        srv._stall_logged = 0
        srv.stats(), srv.stats()
        assert srv.timeline.total_steps == srv._step_count  # a stall's `step` is the engine's step number
        planted = [line for line in lines if "stalled step" in line and f"'step': {srv._step_count - 299}," in line]
        assert len(planted) == 1 and "'other_ms': 5" in planted[0]
        assert len(lines) == len(srv.timeline.stalls())

    def test_no_pool_stats_a_step_while_nobody_collects(self, serving, monkeypatch):
        srv = serving

        class Quiet:  # a plane nobody armed (the process's own may have been, by another test file of this worker)
            collect = False
            tracer = type("T", (), {"enabled": False})

        monkeypatch.setattr(srv, "telemetry", Quiet)
        calls = []
        stats = srv.pool.stats
        monkeypatch.setattr(srv.pool, "stats", lambda: calls.append(1) or stats())
        srv.pool.evictions += 2  # as if two pages had been evicted since the last publish
        for _ in range(3):
            srv.step()  # idle steps: sweep, sched, commit
        assert calls == []
        # the watermarks of the eviction / spill instants moved on all the same
        assert srv._kv_evt_seen == {"evictions": srv.pool.evictions, "session_spills": srv.pool.sessions.spills}
        class Collecting:  # a plane that collects: the gauges are read off the pool's stats again
            collect, gauges = True, {}
            tracer = type("T", (), {"enabled": False})
            gauge = classmethod(lambda cls, name: type("G", (), {"set": lambda self, v: cls.gauges.__setitem__(name, v)})())

        monkeypatch.setattr(srv, "telemetry", Collecting)
        srv._publish_kvcache()
        assert calls == [1] and Collecting.gauges["kvcache/pages_free"] == srv.pool.pages_free

    def test_train_batch_spans(self, tmp_path):
        engine, batch = _train_engine()
        engine.timeline.enabled = False  # the spans are written whether or not the timeline records
        with _Trace(tmp_path / "first") as tr:
            engine.train_batch(batch)
        assert [s[0] for s in tr.spans] == ["ds.train.step", "ds.train.data_wait", "ds.train.compile",
                                            "ds.train.dispatch"]
        assert tr.spans[0][3] == 1 and len(tr.inside(tr.spans[0])) == 3
        jax.block_until_ready(engine.train_batch(batch))
        with _Trace(tmp_path / "later") as tr:
            jax.block_until_ready(engine.train_batch(batch))
        assert [(s[0], s[3]) for s in tr.spans] == [("ds.train.step", 3), ("ds.train.data_wait", None),
                                                    ("ds.train.dispatch", None)]
        assert engine.timeline.summary()["steps"] == 0

    @pytest.mark.parametrize("kernels", ["1", "0"])
    def test_compile_span_carries_the_fused_update_split(self, kernels, tmp_path, monkeypatch):
        """Armed, the optimizer's elements on the one-pass kernels and on
        the XLA leaf path are arguments of ``ds.train.compile``, counted
        while the step was traced; not armed, the span has none."""
        monkeypatch.setenv("DS_KERNELS", kernels)
        engine, batch = _train_engine()
        with _Trace(tmp_path) as tr:
            engine.train_batch(batch)
        split = {k: int(v) for k, v in tr.args["ds.train.compile"].items() if k.startswith("fused_update_")}
        if kernels == "0":
            assert split == {} and engine._fused_update_split == {}
            return
        assert split == {f"fused_update_{k}": v for k, v in engine._fused_update_split.items()}
        n = sum(x.size for x in jax.tree.leaves(engine.state["params"]))
        assert split["fused_update_pallas_elems"] + split["fused_update_xla_elems"] == n
        # some of GPT2_TINY's weight matrices fill whole tiles; vectors and ragged leaves do not
        assert min(split.values()) > 0


# ---------------------------------------------------------------------------
# the programs: named, and otherwise what they were
# ---------------------------------------------------------------------------


def _renamed(fn, name):
    def old(*args):
        return fn(*args)

    old.__name__ = name
    return old


class TestProgramsAreNamedAndUnchanged:
    @pytest.mark.parametrize("which,donate", [("prefill", (2, 3)), ("decode", (2, 3))])
    def test_serve_program(self, serving, tmp_path, which, donate):
        srv = serving
        jitted = getattr(srv, f"_{which}_jit")
        args = getattr(srv, f"_{which}_abstract_args")()
        text = jitted.lower(*args).as_text()
        assert text.startswith(f"module @jit_serve_{which} ")
        with _Trace(tmp_path):
            traced = jitted.lower(*args).as_text()
        assert traced == text
        # the same function under the name it had: `fn`
        old = jax.jit(_renamed(jitted.__wrapped__, "fn"), donate_argnums=donate).lower(*args).as_text()
        assert old.startswith("module @jit_fn ")
        assert old.replace("@jit_fn ", f"@jit_serve_{which} ", 1) == text
        assert srv.compiled_step(which).as_text().startswith(f"HloModule jit_serve_{which},")

    def test_train_program(self, tmp_path):
        engine, batch = _train_engine()
        jax.block_until_ready(engine.train_batch(batch))
        assert engine.train_step_executable().as_text().startswith("HloModule jit_train_step,")

        stacked = engine._stack_and_place(batch)
        step = engine._scoped(engine._full_step_fn())
        assert step.__name__ == "full_step"
        lower = lambda fn: jax.jit(fn, donate_argnums=(0,)).lower(engine.state, stacked).as_text()  # noqa: E731
        text = lower(_renamed(step, "train_step"))
        with _Trace(tmp_path):
            traced = lower(_renamed(step, "train_step"))
        assert traced == text
        old = lower(step)
        assert old.startswith("module @jit_full_step ")
        assert old.replace("@jit_full_step ", "@jit_train_step ", 1) == text
