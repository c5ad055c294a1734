"""The trace reducer, on a small trace recorded on a v5e (five steps of
a jitted flash-attention + matmul, ``bench.step``/``bench.idle`` spans;
``data/trace_small.json`` is ``trace.load_xplane``'s output) and on
hand-made events where the answer is known."""
import json
import os
import pytest

from benchmark import trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "trace_small.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_trace_busy_idle_and_kernel_time(recorded):
    r = trace.reduce(recorded)
    assert r["chips"] == 1 and r["span_counts"] == {"bench.step": 5, "bench.idle": 5}
    assert 0.055 < r["window_s"] < 0.065
    assert 0 < r["busy_s"] < 0.05 * r["window_s"]  # 0.48 ms programs, 10 ms sleeps between them
    k = r["kernels"]["flash_attention_fwd"]
    # the kernel is found by the name the program gave its pallas_call;
    # ~0.327 ms a call at (80, 1024, 64) on the chip that recorded it
    assert k["calls"] == 4 and k["seconds"] / k["calls"] == pytest.approx(327e-6, rel=0.02)
    assert k["out_elems"] == 4 * 80 * 1024 * 64
    assert r["device_ops"][0][0] == "flash_attention_fwd bf16[80,1024,64]"
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    # the device sat idle while the host slept in bench.idle
    assert r["idle_gaps"][0][0] == "bench.idle" and r["idle_gaps"][0][1] > 0.04
    assert r["collective_s"] == 0.0


def test_op_names_lose_the_compilers_suffixes():
    ev = "%flash_attention_fwd.1 = bf16[80,1024,64]{2,1,0:T(8,128)(2,1)S(1)} custom-call(bf16[80,1024,64]{2,1,0} %bitcast.5)"
    assert trace.op_name(ev) == "flash_attention_fwd"
    assert trace.op_group(ev) == "flash_attention_fwd bf16[80,1024,64]"
    assert trace.first_output_elems(ev) == 80 * 1024 * 64
    assert trace.op_name("%fused_adam.12.3 = (f32[4096,1024]{1,0}, f32[4096,1024]{1,0}) custom-call(…)") == "fused_adam"
    assert trace.first_output_elems("%fused_adam.12 = (f32[4096,1024]{1,0}, f32[4096,1024]{1,0}) custom-call(…)") == 4096 * 1024
    assert trace.op_name("%copy-start = (bf16[1280,1280]{1,0}, u32[]{:S(2)}) copy-start(…)") == "copy-start"
    assert trace.first_output_elems("%fusion = bf16[]{:T(256)} fusion(…)") == 0


def _raw(events, spans, devices=1):
    return {"devices": {f"/device:TPU:{i}": [list(e) for e in events] for i in range(devices)},
            "spans": [list(s) for s in spans]}


def test_self_time_does_not_count_a_loop_and_its_body_twice():
    ms = 1_000_000
    events = [("%while.1 = (f32[8]) while(…)", 0, 100 * ms),
              ("%fusion.1 = f32[8]{0} fusion(…)", 10 * ms, 30 * ms),
              ("%fusion.2 = f32[8]{0} fusion(…)", 50 * ms, 40 * ms)]
    r = trace.reduce(_raw(events, [("bench.step", 0, 200 * ms)]))
    assert r["busy_s"] == pytest.approx(0.100) and r["window_s"] == pytest.approx(0.200)
    assert r["kernels"]["fusion"]["seconds"] == pytest.approx(0.070)
    assert r["kernels"]["while"]["seconds"] == pytest.approx(0.030)
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(r["busy_s"])


def test_gaps_are_labelled_by_the_span_open_on_the_host_and_clipped_to_the_window():
    ms = 1_000_000
    events = [("%a = f32[1]{0} fusion(…)", -5 * ms, 10 * ms),   # starts before the window
              ("%b = f32[1]{0} fusion(…)", 30 * ms, 10 * ms),
              ("%c = f32[1]{0} fusion(…)", 41 * ms, 9 * ms)]    # 1 ms gap: under the clocks' agreement
    spans = [("bench.step", 0, 40 * ms), ("bench.submit", 10 * ms, 5 * ms), ("bench.idle", 40 * ms, 60 * ms)]
    r = trace.reduce(_raw(events, spans))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.005 + 0.010 + 0.009)
    assert dict(r["idle_gaps"]) == pytest.approx({"bench.step": 0.025, "bench.idle": 0.050})


def test_collective_time_and_the_mean_over_chips():
    ms = 1_000_000
    events = [("%fusion.7 = bf16[8,8]{1,0} fusion(…)", 0, 60 * ms),
              ("%collective-permute-done.3 = bf16[8,8]{1,0} collective-permute-done(…)", 60 * ms, 10 * ms),
              ("%all-gather.1 = bf16[32,8]{1,0} all-gather(…)", 70 * ms, 10 * ms)]
    r = trace.reduce(_raw(events, [("bench.step", 0, 100 * ms)], devices=4))
    assert r["chips"] == 4 and r["busy_s"] == pytest.approx(0.080) and r["collective_s"] == pytest.approx(0.020)
    assert r["kernels"]["fusion"]["calls"] == 4  # summed over chips, like its seconds


def test_a_trace_with_no_device_operation_reduces_to_nothing():
    assert trace.reduce({"devices": {}, "spans": [["bench.step", 0, 10]]}) is None
    assert trace.reduce({"devices": {"/device:TPU:0": []}, "spans": [["bench.step", 0, 10]]}) is None
    assert trace.reduce({"devices": {"/device:TPU:0": [["%a = f32[1] fusion()", 0, 5]]}, "spans": []}) is None
