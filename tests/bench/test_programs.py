"""``benchmark/programs.py`` and the readers built on it, on a small
recorded dict (``data/programs_small.json`` is the shape
``programs.load_xplane`` gives: a serving trace of two steps with two
programs each and one gap between them, and a two-chip train trace of
three steps with one stray program), on a program that names neither its
programs nor its steps (every reader says None or the plain count, never
an error), and on a real profiler trace taken on the CPU."""
import copy
import json
import os

import pytest

from benchmark import programs
from benchmark.manifest import Manifest

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "programs_small.json")
M = Manifest()
TIMELINE = {"sched_ms_p50": 0.09, "stage_ms_p50": 3.1, "wait_ms_p50": 229.2, "host_ms_p50": 8.1, "host_ms_p95": 15.6,
            "wall_ms_p50": 237.3, "sched_ms": 0.1, "prefill_ms": 88.0, "decode_ms": 148.0}


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def _record(raw, timeline=None):
    """What a reader is handed, as far as the new readers look: the
    loaded trace sits where ``programs.of_run`` keeps it."""
    return {"programs": copy.deepcopy(raw), "trace": {}, "counters": {"timeline": timeline}}


def _unnamed(raw):
    """The same trace from a program that names nothing: both serving
    programs are ``jit_fn``, and only the harness's spans are there."""
    raw = copy.deepcopy(raw)
    raw["modules"] = {k: [["jit_fn", s, d] for _, s, d in v] for k, v in raw["modules"].items()}
    raw["spans"] = [s for s in raw["spans"] if s[0].startswith("bench.")]
    return raw


SERVE = {
    "serve_prefill_device_ms_p50": 85.0, "serve_decode_device_ms_p50": 144.0,
    # 9 ms before the second step's prefill + 4 ms between its programs
    "serve_step_gap_ms_p50": 13.0, "serve_programs_per_step": 2.0,
    "serve_sched_ms_p50": 0.09, "serve_stage_ms_p50": 3.1, "serve_wait_ms_p50": 229.2,
    "serve_host_overhead_ms_p50": 8.1, "serve_host_overhead_ms_p95": 15.6,
}
# chip 0: 4 - 1 (the stray program) and 2 ms; chip 1: 3 and 2 ms.  Seven programs in three steps on two chips
TRAIN = {"train_step_gap_ms_p50": 2.5, "train_programs_per_step": 7 / 6}
UNNAMED = {"serve_programs_per_step": 2.0, "train_programs_per_step": 7 / 6}


def test_the_new_metrics_are_the_manifests():
    per_layer = {m["name"]: m for m in M.data["per_layer"]}
    assert set(SERVE) | set(TRAIN) <= set(per_layer)
    for name in list(SERVE) + list(TRAIN):
        m = per_layer[name]
        assert "workloads" not in m and m["better"] == "lower"
        assert m["moves"] == ("serve_tokens_per_s" if name in SERVE else "train_tokens_per_s")
    serve_cells = [c for c in ("serve-xl-chat-open", "serve-xl-longprompt-backlog")
                   if set(SERVE) <= {m["name"] for m in M.per_layer(c)}]
    train_cells = [c for c in ("train-large-seq1024", "train-xl-zero3-4chip")
                   if set(TRAIN) <= {m["name"] for m in M.per_layer(c)}]
    assert len(serve_cells) == 2 and len(train_cells) == 2


@pytest.mark.parametrize("metric", sorted(SERVE) + sorted(TRAIN))
def test_reader_on_the_recorded_trace(recorded, metric):
    raw = recorded["serve" if metric in SERVE else "train"]
    value = M.module("metrics", metric).read(_record(raw, TIMELINE))
    assert value == pytest.approx({**SERVE, **TRAIN}[metric])


@pytest.mark.parametrize("metric", sorted(SERVE) + sorted(TRAIN))
def test_reader_on_a_program_that_names_nothing(recorded, metric):
    """The parent of the PR that brought these readers: no ``ds.*`` span,
    ``jit_fn`` twice, and a timeline summary of means only."""
    raw = _unnamed(recorded["serve" if metric in SERVE else "train"])
    means_only = {k: v for k, v in TIMELINE.items() if not k.endswith(("_p50", "_p95"))}
    value = M.module("metrics", metric).read(_record(raw, means_only))
    assert value == (pytest.approx(UNNAMED[metric]) if metric in UNNAMED else None)


@pytest.mark.parametrize("metric", sorted(SERVE) + sorted(TRAIN))
def test_reader_on_a_run_that_was_not_traced(metric):
    record = {"trace": None, "counters": {}}
    assert M.module("metrics", metric).read(record) is None
    assert record.get("programs") is None


def test_of_run_finds_no_trace_where_none_was_written(tmp_path):
    manifest = type("M", (), {"root": str(tmp_path)})()
    record = {"trace": {"busy_s": 1.0}, "manifest": manifest, "cell": {"name": "serve-xl-chat-open"}}
    assert programs.of_run(record) is None and record["programs"] is None


def test_a_program_belongs_to_the_span_that_holds_its_midpoint(recorded):
    raw = copy.deepcopy(recorded["serve"])
    # the device's clock 2 ms ahead of the host's: the first prefill
    # starts before its step's span does, and is still that step's
    raw["modules"]["/device:TPU:0"] = [[n, s - 3_500_000, d] for n, s, d in raw["modules"]["/device:TPU:0"]]
    assert programs.programs_per_step(raw) == 2.0
    assert programs.step_gap_ms_p50(raw, "ds.serve.step") == pytest.approx(13.0)
    # a step whose number does not follow the one before gives no gap
    raw["spans"] = [s[:3] + [9] if s[3] == 8 else s for s in raw["spans"]]
    assert programs.step_gap_ms_p50(raw, "ds.serve.step") is None
    assert programs.program_name("jit_serve_decode(16828633983051800625)") == "jit_serve_decode"
    assert programs.device_ms_p50({"modules": {}, "spans": []}, "jit_serve_decode") is None
    assert programs.programs_per_step({"modules": {}, "spans": raw["spans"]}) is None


def test_load_xplane_reads_the_engines_spans_with_their_step(tmp_path):
    """A real ``.xplane.pb`` (taken on the CPU, so with no device plane):
    ``ds.*`` and ``bench.*`` annotations come back with the ``step``
    argument, everything else is left out."""
    import jax

    from benchmark import trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.step"):
        with jax.profiler.TraceAnnotation("ds.serve.step", step=41):
            with jax.profiler.TraceAnnotation("ds.serve.sched"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
        with jax.profiler.TraceAnnotation("somebody.else"):
            pass
    jax.profiler.stop_trace()
    raw = programs.load_xplane(trace.find_xplane(str(tmp_path)))
    assert raw["modules"] == {}
    assert [(s[0], s[3]) for s in raw["spans"]] == [("bench.step", None), ("ds.serve.step", 41), ("ds.serve.sched", None)]
    outer, step = raw["spans"][0], raw["spans"][1]
    assert outer[1] <= step[1] and step[1] + step[2] <= outer[1] + outer[2]
