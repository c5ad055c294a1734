"""The live anomaly watch (telemetry/anomaly.py): the step-wall spike
against the manager's trailing window, the cross-rank straggler flag
over the in-process 2-supervisor heartbeat channel, and the Perfetto
counter tracks of the trace buffer."""
import json
import time

import pytest

from deepspeed_tpu import telemetry as tel
from deepspeed_tpu.config.config import TelemetryConfig
from deepspeed_tpu.telemetry import (
    MetricsRegistry,
    TelemetryManager,
    TraceBuffer,
    check_step_spike,
    find_stragglers,
    validate_chrome_trace,
)

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _fresh_plane():
    tel.reset_for_tests()
    yield
    tel.reset_for_tests()


# ---------------------------------------------------------------------------
# runtime anomaly watch
# ---------------------------------------------------------------------------


class TestAnomalyWatch:
    def test_step_wall_spike_fires_window_relative(self):
        registry = MetricsRegistry(enabled=True)
        tracer = TraceBuffer(enabled=True)
        tm = TelemetryManager("train", registry, tracer, config=TelemetryConfig())
        steady = {"wall": 0.010}
        for _ in range(10):
            tm.publish_step("train", dict(steady))
        spikes = registry.counter("train/anomaly/step_spikes", engine="train")
        assert spikes.value == 0
        tm.publish_step("train", {"wall": 0.050})  # 5x the window mean
        assert spikes.value == 1
        names = [e.get("name") for e in tracer.events()]
        assert "step_wall_spike" in names

    def test_spike_needs_min_window_and_pure_fn_shape(self):
        assert check_step_spike(100.0, 10.0, window_count=3) is None  # < min
        assert check_step_spike(100.0, None, window_count=50) is None
        ev = check_step_spike(100.0, 10.0, window_count=50)
        assert ev["event"] == "step_wall_spike" and ev["factor"] == 10.0
        assert check_step_spike(20.0, 10.0, window_count=50) is None  # 2x < 2.5x

    def test_straggler_flag_fires_in_two_supervisor_aggregate(self, tmp_path):
        """The in-process 2-supervisor form of the straggler proof: two
        supervisors over a real TCP beat channel, rank 1's piggybacked
        step wall 4x rank 0's — the rank-0 aggregate stream flags rank 1
        as a straggler against the cluster median, and the cluster
        gauges carry it."""
        from deepspeed_tpu.resilience.supervision import Supervisor
        from deepspeed_tpu.resilience.supervision.heartbeat import TcpBeatChannel
        from deepspeed_tpu.telemetry import CrossRankAggregator

        registry = MetricsRegistry(enabled=True)
        agg_path = tmp_path / "aggregate.jsonl"
        agg = CrossRankAggregator(2, jsonl_path=str(agg_path), registry=registry)
        ch0 = TcpBeatChannel(rank=0, world_size=2, port=0, beat_timeout=5.0,
                             connect_grace=5.0)
        sup0 = Supervisor(
            rank=0, world_size=2, channel=ch0, beat_interval=0.05,
            metrics_fn=lambda: {"train/step_wall_ms{engine=train}": 100.0},
            aggregator=agg, on_rescue=lambda site, reason: None,
        ).start()
        ch1 = TcpBeatChannel(rank=1, world_size=2, address="127.0.0.1",
                             port=ch0.port, beat_timeout=5.0, connect_grace=5.0)
        sup1 = Supervisor(
            rank=1, world_size=2, channel=ch1, beat_interval=0.05,
            metrics_fn=lambda: {"train/step_wall_ms{engine=train}": 400.0},
            on_rescue=lambda site, reason: None,
        ).start()
        try:
            deadline = time.monotonic() + 8.0
            stragglers = []
            while time.monotonic() < deadline:
                stragglers = agg.aggregate()["stragglers"]
                if stragglers:
                    break
                time.sleep(0.02)
            assert stragglers, "straggler never flagged"
            (s,) = stragglers
            # median over {100, 400} = 250; rank 1 at 400 = 1.6x > 1.5x
            assert s["rank"] == 1 and s["factor"] == pytest.approx(1.6, abs=0.01)
            assert agg.export_line(force=True) is not None
            lines = [json.loads(l) for l in agg_path.read_text().splitlines()]
            assert any(l["stragglers"] for l in lines)
            assert registry.gauge("cluster/stragglers").value == 1
            assert registry.gauge("cluster/straggler_factor", rank=1).value == pytest.approx(1.6, abs=0.01)
        finally:
            sup0.stop()
            sup1.stop()
            ch0.stop()
            ch1.stop()

    def test_find_stragglers_needs_two_ranks_and_positive_median(self):
        assert find_stragglers({0: {"a/step_wall_ms": 100.0}}, [0]) == []
        flags = find_stragglers(
            {0: {"a/step_wall_ms": 100.0}, 1: {"a/step_wall_ms": 400.0},
             2: {"a/step_wall_ms": 110.0}},
            [0, 1, 2],
        )
        assert [f["rank"] for f in flags] == [1]


# ---------------------------------------------------------------------------
# Perfetto counter tracks
# ---------------------------------------------------------------------------


class TestCounterTracks:
    def test_add_counter_exports_schema_valid(self, tmp_path):
        buf = TraceBuffer(enabled=True)
        buf.add_counter("kvcache/tier/pages", {"hbm": 61.0})
        path = buf.export(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        assert validate_chrome_trace(doc) == []
        c = [e for e in doc["traceEvents"] if e["ph"] == "C"]
        assert c and c[0]["args"] == {"hbm": 61.0}

    def test_counter_without_args_rejected_by_validator(self):
        doc = {"traceEvents": [{"name": "x", "ph": "C", "ts": 1.0, "pid": 0, "tid": 0}]}
        assert validate_chrome_trace(doc)
