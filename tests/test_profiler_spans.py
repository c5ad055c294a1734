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
    "ds.serve.step", "ds.serve.sched",
    "ds.serve.prefill", "ds.serve.prefill.stage", "ds.serve.prefill.dispatch", "ds.serve.prefill.wait",
    "ds.serve.decode", "ds.serve.decode.stage", "ds.serve.decode.dispatch", "ds.serve.decode.wait",
}
SERVE_PHASES = dict(phases=("sched", "prefill", "decode"), sub_phases=("stage", "dispatch", "wait"),
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
# the ds.* spans in a jax.profiler trace
# ---------------------------------------------------------------------------


class _Trace:
    """A ``jax.profiler`` trace round a block, as the benchmark's harness
    takes it (no Python tracer); ``spans`` afterwards: the host planes'
    ``ds.*`` events as ``(name, start_ns, end_ns, step or None)``."""

    def __init__(self, path):
        self.path, self.spans, self.args = str(path), [], {}

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
    def test_serving_vocabulary_nested_in_the_step_with_its_number(self, serving, tmp_path):
        srv = serving
        before = srv._step_count
        with _Trace(tmp_path) as tr:
            srv.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
            srv.drain()
        assert {s[0] for s in tr.spans} == SERVE_VOCABULARY
        steps = [s for s in tr.spans if s[0] == "ds.serve.step"]
        # only the steps taken while the trace ran, each under its own number
        assert [s[3] for s in steps] == list(range(before + 1, srv._step_count + 1))
        assert all(s[3] is None for s in tr.spans if s[0] != "ds.serve.step")
        # every other span lies inside one step's span ...
        assert sum(len(tr.inside(s)) for s in steps) == len(tr.spans) - len(steps)
        # ... and a program's three sub-phases inside its phase, in order
        for which in ("prefill", "decode"):
            for outer in (s for s in tr.spans if s[0] == f"ds.serve.{which}"):
                inner = [s[0] for s in tr.inside(outer)]
                assert inner in ([], [f"ds.serve.{which}.{p}" for p in ("stage", "dispatch", "wait")])
        first = tr.inside(steps[0])
        assert [s[0] for s in first][:2] == ["ds.serve.sched", "ds.serve.prefill"]

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
