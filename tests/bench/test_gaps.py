"""``benchmark/gaps.py`` and the readers built on it, on a hand-made dict
of the shape ``programs.load_xplane`` gives: two serving steps on one
chip, the first with a prefill chunk and a decode step, the second with a
decode step alone, the harness's spans round them, and the device's clock
1.5 ms ahead of the host's.  Every number below is laid out in
``_trace``: the partition has to give each back, sum to the device's
idle time to the nanosecond, and say None — never a guess — of a trace
or a record that lacks what it reads."""
import copy

import pytest

from benchmark import gaps
from benchmark.manifest import Manifest

M = Manifest()
US = 1_000
AHEAD = 1_500 * US  # the device's clock ahead of the host's
SERVE_CELLS = ["serve-xl-chat-open", "serve-xl-longprompt-backlog", "serve-dsv2-longctx-backlog",
               "serve-solar2-reasoning-backlog", "serve-zaya1-longctx-backlog"]
NEW = ["serve_launch_ms_p50", "serve_readback_ms_p50", "serve_note_ms_p50", "serve_commit_ms_p50", "serve_sweep_ms_p50",
       "serve_dispatch_ms_p50", "serve_idle_between_steps_pct", "serve_idle_unattributed_pct", "serve_stall_steps", "serve_stall_ms"]
TIMELINE = {"note_ms_p50": 0.61, "commit_ms_p50": 0.12, "sweep_ms_p50": 0.004, "dispatch_ms_p50": 0.71, "stall_steps": 2, "stall_ms": 311.5,
            "stage_ms_p50": 0.6, "wall_ms_p50": 47.0, "steps": 1085}


def _trace(ahead=AHEAD):
    """Microseconds on the host's clock (the device's events ``ahead``
    of it).  Step 7 = [100, 59110): sweep, sched, a chunk (launch 690,
    18,000 on the device, read-back 500), a decode step (700, 35,000,
    695), commit.  Step 8 = [61100, 99410): a decode step alone (500,
    35,000, 510).  Ten-microsecond slivers between the leaves, a hundred
    where step 8's empty prefill phase lies; the harness's spans run
    [0, 59200), [60100, 60900) and [61000, 99500)."""
    spans = [
        ["bench.step", 0, 59200, None], ["ds.serve.step", 100, 59010, 7],
        ["ds.serve.sweep", 110, 20, None], ["ds.serve.sched", 140, 60, None],
        ["ds.serve.prefill", 210, 20295, None],
        ["ds.serve.prefill.stage", 220, 580, None], ["ds.serve.prefill.dispatch", 810, 400, None],
        ["ds.serve.prefill.wait", 1220, 18775, None], ["ds.serve.prefill.note", 20000, 500, None],
        ["ds.serve.decode", 20510, 37795, None],
        ["ds.serve.decode.stage", 20520, 580, None], ["ds.serve.decode.dispatch", 21110, 450, None],
        ["ds.serve.decode.wait", 21570, 35930, None], ["ds.serve.decode.note", 57505, 795, None],
        ["ds.serve.commit", 58310, 790, None],
        ["bench.submit", 60100, 800, None],
        ["bench.step", 61000, 38500, None], ["ds.serve.step", 61100, 38310, 8],
        ["ds.serve.sweep", 61110, 20, None], ["ds.serve.sched", 61140, 60, None],
        ["ds.serve.prefill", 61210, 5, None],
        ["ds.serve.decode", 61290, 37315, None],
        ["ds.serve.decode.stage", 61300, 600, None], ["ds.serve.decode.dispatch", 61910, 480, None],
        ["ds.serve.decode.wait", 62400, 35515, None], ["ds.serve.decode.note", 97920, 680, None],
        ["ds.serve.commit", 98610, 790, None],
    ]
    programs = [["jit_serve_prefill", 1500, 18000], ["jit_serve_decode", 21810, 35000], ["jit_serve_decode", 62410, 35000]]
    return {"modules": {"/device:TPU:0": [[n, s * US + ahead, d * US] for n, s, d in programs]},
            "spans": [[n, s * US, d * US, step] for n, s, d, step in spans]}


# idle: [0, 1500) + [19500, 21810) + [56810, 62410) + [97410, 99500) = 11,500 us of the window's 99,500
EXPECTED_US = {
    "between_steps": 100 + 1990 + 90, "sweep": 20 + 20, "sched": 60 + 60, "stage": 580 + 580 + 600,
    "launch": 690 + 700 + 500, "readback": 500 + 695 + 510, "note": 500 + 795 + 680, "commit": 790 + 790,
    "unattributed": (10 + 10 + 20 + 10) + (20 + 10) + (10 + 10 + 10 + 10 + 100 + 10) + (10 + 10),
}


def _ns(seconds):
    return round(seconds * 1e9)


def _record(raw, timeline=None):
    """What a reader is handed, as far as these readers look."""
    return {"programs": copy.deepcopy(raw), "trace": {}, "counters": {"timeline": timeline}}


def test_the_partition_gives_back_what_was_laid_out_and_sums_to_the_idle_time():
    table = gaps.partition(_trace())
    assert set(table["seconds"]) == set(gaps.LABELS) == set(EXPECTED_US)
    assert {k: _ns(v) for k, v in table["seconds"].items()} == {k: v * US for k, v in EXPECTED_US.items()}
    assert _ns(table["idle_s"]) == 11_500 * US == sum(_ns(v) for v in table["seconds"].values())
    assert _ns(table["window_s"]) == 99_500 * US and (table["executions"], table["programs"]) == (3, 3)
    # the least launch and the least read-back are both 500 us: the bounds lie 500 us either side of the truth
    assert table["clock_offset_ms"] == pytest.approx(1.5) and table["clock_halfwidth_ms"] == pytest.approx(0.5)
    # launch + device time + read-back against dispatch + wait: the sliver between a wait's end and its note's start
    assert table["join_error_ms_max"] == pytest.approx(0.005)


@pytest.mark.parametrize("ahead", [0, AHEAD, -2_700 * US, 40_000_000 * US])
def test_the_offset_between_the_clocks_is_found_and_taken_out(ahead):
    """Whatever the device's clock reads, the join is the same (a step
    holds its programs' midpoints while the offset is a few ms) and the
    split is read at the same place."""
    raw = _trace(ahead)
    if abs(ahead) > 5_000 * US:
        # no program's midpoint lies in its step any more: nothing pairs off, nothing is said
        assert gaps.executions(raw) is None and gaps.partition(raw) is None and gaps.launch_ms_p50(raw) is None
        return
    rows = gaps.executions(raw)
    assert [(r["program"], r["step"]) for r in rows] == [("jit_serve_prefill", 7), ("jit_serve_decode", 7), ("jit_serve_decode", 8)]
    offset, half = gaps.clock(rows)
    assert (offset, half) == (ahead, 500 * US)
    assert gaps.launch_ms_p50(raw) == pytest.approx(0.69) and gaps.readback_ms_p50(raw) == pytest.approx(0.51)
    for r in rows:  # free of the offset: launch + device time + read-back is the host's dispatch-to-note
        launch, readback = r["start_ns"] - offset - r["dispatch_ns"], r["note_ns"] - (r["end_ns"] - offset)
        assert launch + (r["end_ns"] - r["start_ns"]) + readback == r["note_ns"] - r["dispatch_ns"]
        assert abs(r["note_ns"] - r["wait_end_ns"]) <= 5 * US
    assert {k: _ns(v) for k, v in gaps.partition(raw)["seconds"].items()} == {k: v * US for k, v in EXPECTED_US.items()}


def test_a_step_that_does_not_pair_off_is_left_out_and_its_wait_is_unattributed():
    raw = _trace()
    raw["spans"] = [s for s in raw["spans"] if not (s[0] == "ds.serve.decode.note" and s[1] == 97920 * US)]
    rows = gaps.executions(raw)
    assert [r["step"] for r in rows] == [7, 7]
    table = gaps.partition(raw)
    got = {k: _ns(v) // US for k, v in table["seconds"].items()}
    # the bounds come from step 7 alone: 690 above, 500 below, the middle 95 us off — inside the half-width it states
    assert table["clock_offset_ms"] == pytest.approx(1.595) and table["clock_halfwidth_ms"] == pytest.approx(0.595)
    # so every launch reads 95 us short and every read-back 95 long; their sum does not move
    assert got["launch"] == 690 + 700 - 2 * 95 and got["readback"] == 500 + 695 + 2 * 95
    assert got["note"] == 500 + 795 and got["stage"] == EXPECTED_US["stage"] and got["between_steps"] == EXPECTED_US["between_steps"]
    # step 8's launch, read-back and the note span that is gone are under no leaf now; its stage still is
    assert got["unattributed"] == EXPECTED_US["unattributed"] + (500 - 95) + (510 + 95) + 680
    assert sum(got.values()) == 11_500 and (table["executions"], table["programs"]) == (2, 3)


def test_two_chips_are_a_mean_and_a_program_of_no_step_is_busy_time_all_the_same():
    raw = _trace()
    # a second chip that ran the same programs, and one stray program between the steps (59300-59800)
    raw["modules"]["/device:TPU:1"] = copy.deepcopy(raw["modules"]["/device:TPU:0"]) + [["jit_stray", 59300 * US + AHEAD, 500 * US]]
    table = gaps.partition(raw)
    assert table["executions"] == 6 and _ns(table["idle_s"]) == (11_500 * US + 11_000 * US) // 2
    assert _ns(table["seconds"]["between_steps"]) == (2180 * US + 1680 * US) // 2
    assert sum(_ns(v) for v in table["seconds"].values()) == _ns(table["idle_s"])


def test_without_the_harness_spans_the_window_is_the_steps_extent():
    raw = _trace()
    raw["spans"] = [s for s in raw["spans"] if not s[0].startswith("bench.")]
    table = gaps.partition(raw)
    assert _ns(table["window_s"]) == (99_410 - 100) * US
    assert _ns(table["seconds"]["between_steps"]) == 1990 * US and sum(_ns(v) for v in table["seconds"].values()) == _ns(table["idle_s"])


def _parent(raw):
    """The same trace from the program before this vocabulary: no
    ``sweep``, ``note`` or ``commit`` span."""
    raw = copy.deepcopy(raw)
    raw["spans"] = [s for s in raw["spans"] if not s[0].endswith((".note", ".sweep", ".commit"))]
    return raw


READINGS = {"serve_launch_ms_p50": 0.69, "serve_readback_ms_p50": 0.51, "serve_idle_between_steps_pct": 100 * 2180 / 11500,
            "serve_idle_unattributed_pct": 100 * 250 / 11500, "serve_note_ms_p50": 0.61, "serve_commit_ms_p50": 0.12,
            "serve_sweep_ms_p50": 0.004, "serve_dispatch_ms_p50": 0.71, "serve_stall_steps": 2, "serve_stall_ms": 311.5}


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_the_laid_out_trace_and_the_engines_counters(metric):
    assert M.module("metrics", metric).read(_record(_trace(), TIMELINE)) == pytest.approx(READINGS[metric])


@pytest.mark.parametrize("metric", NEW)
def test_reader_says_none_of_a_program_without_the_spans_or_the_keys(metric):
    """The parent of the PR that brought these readers: its trace has
    ``dispatch`` and ``wait`` but no ``note`` or ``commit``, its timeline
    summary none of the new keys — except ``dispatch_ms_p50``, which it
    always had and no metric read."""
    old_keys = {"stage_ms_p50": 0.6, "dispatch_ms_p50": 0.71, "wall_ms_p50": 47.0, "steps": 512}
    value = M.module("metrics", metric).read(_record(_parent(_trace()), old_keys))
    assert value == (pytest.approx(0.71) if metric == "serve_dispatch_ms_p50" else None)
    # spans but no device line (a trace taken on the CPU), and a trace of somebody else's spans
    for raw in ({"modules": {}, "spans": _trace()["spans"]}, {"modules": _trace()["modules"], "spans": [["bench.step", 0, 99500 * US, None]]}):
        assert M.module("metrics", metric).read(_record(raw, {})) is None


@pytest.mark.parametrize("metric", NEW)
def test_reader_on_a_run_that_was_not_traced(metric):
    record = {"trace": None, "counters": {}}
    assert M.module("metrics", metric).read(record) is None
    assert record.get("programs") is None


def test_a_commit_span_alone_is_not_enough():
    raw = _parent(_trace())
    raw["spans"].append(["ds.serve.commit", 58310 * US, 790 * US, None])
    assert gaps.executions(raw) is None and gaps.partition(raw) is None and gaps.share_pct(raw, "between_steps") is None
    assert gaps.partition(None) is None and gaps.executions(None) is None


def test_the_new_metrics_are_appended_for_the_five_serve_cells_and_no_other():
    assert [m["name"] for m in M.data["per_layer"][-len(NEW):]] == NEW
    for name in NEW:
        m = M.metric_entry(name)
        assert m["workloads"] == SERVE_CELLS and (m["layer"], m["moves"], m["better"]) == ("serving engine + scheduler", "serve_tokens_per_s", "lower")
        assert m["source"] == ("device_trace" if name in ("serve_launch_ms_p50", "serve_readback_ms_p50", "serve_idle_between_steps_pct",
                                                         "serve_idle_unattributed_pct") else "program_span")
    for cell in (w["name"] for w in M.data["workloads"]):
        assert set(NEW) <= {m["name"] for m in M.per_layer(cell)} if cell in SERVE_CELLS else not set(NEW) & {m["name"] for m in M.per_layer(cell)}


def test_the_engines_own_trace_loads_into_the_shape_the_partition_reads(tmp_path):
    """A toy ``ServingEngine`` under ``jax.profiler`` on the CPU, read by
    the benchmark's one loader: every leaf of the vocabulary is there, in
    its step, and with no device line nothing is said of the idle time."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from benchmark import programs, trace
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.serving import ServingEngine

    cfg = dataclasses.replace(gpt2.GPT2_TINY, remat=False)
    eng = deepspeed_tpu.init_inference(model_config=cfg, params=gpt2.init_params(cfg, seed=7), dtype=jnp.float32,
                                       max_out_tokens=cfg.n_positions)
    srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=64, config={"kvcache": {"enabled": True, "page_len": 16}})
    srv.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
    srv.drain()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        srv.submit(np.arange(3, 17, dtype=np.int32), max_new_tokens=3)
        srv.drain()
    finally:
        jax.profiler.stop_trace()
    raw = programs.load_xplane(trace.find_xplane(str(tmp_path)))
    steps = gaps._steps(raw)
    assert len(steps) >= 3 and set(gaps.LEAVES) <= {s[0] for s in raw["spans"]}
    held = gaps._by_step(steps, [s[1] for s in steps], [s for s in raw["spans"] if s[0] in gaps.LEAVES])
    assert sum(len(h) for h in held) == sum(1 for s in raw["spans"] if s[0] in gaps.LEAVES)
    assert all([s[0] for s in h][:2] == ["ds.serve.sweep", "ds.serve.sched"] and h[-1][0] == "ds.serve.commit" for h in held)
    assert raw["modules"] == {} and gaps.executions(raw) is None and gaps.partition(raw) is None
