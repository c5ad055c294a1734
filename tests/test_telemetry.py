"""Unified telemetry plane tests (docs/telemetry.md).

Coverage per ISSUE 9: registry types/rings/labels/thread-safety and the
zero-overhead disabled path, Chrome-trace buffer + schema validation
(positive and negative), JSONL/Prometheus/TensorBoard exporters and the
off-hot-path export loop, cross-rank aggregation over both heartbeat
channels (incl. a socket-EOF death landing in the exported aggregate
stream), engine integration (MFU gauge consistency vs the analytic
count, monitor rewiring, armed-ds_san cleanliness, publish cost), the
serving per-request span lifecycle whose trace reconstructs the
requests' own TTFT stamps, and the finished flops
profiler + telemetry config validation satellites."""
import dataclasses
import json
import threading
import time

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry as tel
from deepspeed_tpu.config.config import DeepSpeedConfigError, TelemetryConfig
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.telemetry import (
    CrossRankAggregator,
    ExportLoop,
    JsonlExporter,
    MetricsRegistry,
    PrometheusTextfileExporter,
    TelemetryManager,
    TensorBoardSink,
    TraceBuffer,
    decode_metrics,
    encode_metrics,
    validate_chrome_trace,
)

pytestmark = [pytest.mark.telemetry, pytest.mark.usefixtures("cpu_peak")]


@pytest.fixture(autouse=True)
def _fresh_plane():
    tel.reset_for_tests()
    yield
    tel.reset_for_tests()


def _wait_for(cond, timeout=8.0, period=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(period)
    return cond()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry(enabled=True, ring=64)
        c = reg.counter("x/events", site="a")
        c.inc()
        c.inc(2)
        assert c.value == 3
        g = reg.gauge("x/level")
        g.set(5.0)
        g.set(7.0)
        assert g.value == 7.0 and g.window_mean() == 6.0
        h = reg.histogram("x/lat_ms")
        for v in (1.0, 2.0, 3.0, 100.0):
            h.observe(v)
        assert h.count == 4 and h.min == 1.0 and h.max == 100.0
        assert h.percentile(50) in (2.0, 3.0)
        snap = reg.snapshot()
        assert {m["name"] for m in snap["metrics"]} == {"x/events", "x/level", "x/lat_ms"}

    def test_handles_are_memoized_and_labels_distinguish(self):
        reg = MetricsRegistry(enabled=True)
        assert reg.counter("a", s="1") is reg.counter("a", s="1")
        assert reg.counter("a", s="1") is not reg.counter("a", s="2")
        assert reg.counter("a", s="1").qualified() == "a{s=1}"

    def test_disabled_registry_is_noop_and_late_enable_revives_handles(self):
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("n")
        c.inc()
        assert c.value == 0  # disabled: update dropped
        reg.configure(enabled=True)
        c.inc()  # the SAME cached handle goes live
        assert c.value == 1

    def test_ring_bounds_histogram_memory(self):
        reg = MetricsRegistry(enabled=True, ring=16)
        h = reg.histogram("h")
        for i in range(1000):
            h.observe(float(i))
        assert h.count == 1000  # cumulative stats keep counting
        assert len(h._ring) == 16  # the window stays bounded
        assert h.percentile(50) >= 984  # percentiles cover the recent window

    def test_configure_resizes_existing_rings(self):
        reg = MetricsRegistry(enabled=True, ring=256)
        h = reg.histogram("h")
        for i in range(200):
            h.observe(float(i))
        reg.configure(ring=16)  # a later engine's smaller bound applies
        assert h._ring.maxlen == 16 and len(h._ring) == 16
        assert h.percentile(50) >= 184  # recent window retained

    def test_compact_snapshot_shapes(self):
        reg = MetricsRegistry(enabled=True)
        reg.counter("c").inc(3)
        reg.gauge("g").set(1.5)
        reg.histogram("h").observe(2.0)
        compact = reg.snapshot_compact()
        assert compact == {"c": 3.0, "g": 1.5, "h": 2.0}

    def test_concurrent_publishers(self):
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("threads")

        def spin():
            for _ in range(1000):
                c.inc()
                reg.histogram("hh").observe(1.0)

        threads = [threading.Thread(target=spin) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000
        assert reg.histogram("hh").count == 8000


# ---------------------------------------------------------------------------
# trace buffer + chrome schema
# ---------------------------------------------------------------------------


class TestTrace:
    def test_spans_export_and_validate(self, tmp_path):
        tr = TraceBuffer(enabled=True)
        t0 = tr.now()
        tr.add_span("step", "train", t0, t0 + 0.01, args={"k": 1})
        tr.add_instant("mark", "train")
        with tr.span("block", "train"):
            pass
        path = tr.export(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        assert validate_chrome_trace(doc) == []
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"step", "mark", "block", "process_name"} <= names
        x = next(e for e in doc["traceEvents"] if e["name"] == "step")
        assert x["ph"] == "X" and abs(x["dur"] - 10_000) < 1000  # ~10ms in us

    def test_disabled_buffer_records_nothing(self):
        tr = TraceBuffer(enabled=False)
        tr.add_span("s", "c", 0.0, 1.0)
        with tr.span("t", "c"):
            pass
        assert tr.events() == []

    def test_ring_drops_are_counted_and_meta_survives_eviction(self):
        tr = TraceBuffer(enabled=True, max_events=1000)
        t0 = tr.now()
        for i in range(1500):
            tr.add_span(f"s{i}", "c", t0, t0)
        events = tr.events()
        assert len(events) == 1001  # 1000-span ring + rebuilt metadata row
        assert tr.dropped == 500
        # the process_name row is rebuilt at export, not evicted with
        # the early ring entries
        assert events[0]["ph"] == "M" and events[0]["name"] == "process_name"

    def test_validator_rejects_malformed_events(self):
        bad = {"traceEvents": [
            {"name": "ok", "cat": "c", "ph": "X", "ts": 1.0, "dur": 1.0, "pid": 0, "tid": 0},
            {"name": "", "cat": "c", "ph": "X", "ts": 1.0, "dur": 1.0, "pid": 0, "tid": 0},
            {"name": "negative", "cat": "c", "ph": "X", "ts": -5, "dur": 1.0, "pid": 0, "tid": 0},
            {"name": "weird", "ph": "Q", "pid": 0, "tid": 0},
            {"name": "nolabels", "cat": "c", "ph": "i", "ts": 1.0, "pid": "zero", "tid": 0},
        ]}
        problems = validate_chrome_trace(bad)
        assert len(problems) == 4, problems
        assert validate_chrome_trace([]) != []  # top level must be an object


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


class TestExporters:
    def _reg(self):
        reg = MetricsRegistry(enabled=True)
        reg.counter("e/count", engine="t").inc(2)
        reg.gauge("e/gauge").set(4.5)
        reg.histogram("e/hist_ms").observe(3.0)
        return reg

    def test_jsonl_appends_full_snapshots(self, tmp_path):
        reg = self._reg()
        ex = JsonlExporter(str(tmp_path / "m.jsonl"))
        ex.export(reg.snapshot())
        reg.gauge("e/gauge").set(5.0)
        ex.export(reg.snapshot())
        ex.close()
        lines = [json.loads(l) for l in open(tmp_path / "m.jsonl")]
        assert len(lines) == 2
        assert {m["name"] for m in lines[0]["metrics"]} == {"e/count", "e/gauge", "e/hist_ms"}

    def test_prometheus_textfile_format_and_atomicity(self, tmp_path):
        reg = self._reg()
        path = tmp_path / "m.prom"
        ex = PrometheusTextfileExporter(str(path))
        ex.export(reg.snapshot())
        text = path.read_text()
        assert "# TYPE ds_e_count counter" in text
        assert 'ds_e_count{rank="0",engine="t"} 2' in text
        assert 'ds_e_gauge{rank="0"} 4.5' in text
        assert "ds_e_hist_ms_count" in text and 'quantile="0.99"' in text
        assert not path.with_suffix(".prom.tmp").exists()  # atomic replace

    def test_tensorboard_sink_forwards_to_monitor(self, tmp_path, monkeypatch):
        import sys

        import deepspeed_tpu.utils.monitor as mon

        monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
        m = mon.TensorBoardMonitor(output_path=str(tmp_path), job_name="jb", enabled=True)
        reg = self._reg()
        reg.set_step(7)
        TensorBoardSink(m).export(reg.snapshot())
        m.close()
        events = [json.loads(l) for l in open(tmp_path / "jb" / "events.jsonl")]
        tags = {e["tag"] for e in events}
        assert "Telemetry/e/gauge" in tags and "Telemetry/e/count/engine.t" in tags
        assert all(e["step"] == 7 for e in events)

    def test_export_loop_flush_and_atexit_stop(self, tmp_path):
        reg = self._reg()
        ex = JsonlExporter(str(tmp_path / "loop.jsonl"))
        loop = ExportLoop(reg, [ex], interval_seconds=30.0).start()
        loop.flush()
        assert loop.exports == 1 and loop.last_export_age() is not None
        loop.stop()  # idempotent final flush + close
        loop.stop()
        lines = open(tmp_path / "loop.jsonl").read().strip().splitlines()
        assert len(lines) == 2  # explicit flush + stop flush


# ---------------------------------------------------------------------------
# cross-rank aggregation
# ---------------------------------------------------------------------------


class TestAggregation:
    def test_encode_decode_roundtrip_no_whitespace(self):
        m = {"train/loss{engine=train}": 1.25, "steps": 3.0}
        s = encode_metrics(m)
        assert " " not in s and "\n" not in s  # rides a space-split protocol
        assert decode_metrics(s) == m
        assert decode_metrics("not json") is None

    def test_min_mean_max_over_live_ranks_only(self, tmp_path):
        reg = MetricsRegistry(enabled=True)
        agg = CrossRankAggregator(3, jsonl_path=str(tmp_path / "agg.jsonl"), registry=reg)
        agg.update(0, 1, {"loss": 1.0})
        agg.update(1, 1, {"loss": 2.0})
        agg.update(2, 1, {"loss": 9.0})
        agg.mark_dead(2, "socket EOF")
        out = agg.aggregate()
        assert out["alive"] == [0, 1]
        assert [d["rank"] for d in out["dead"]] == [2]
        assert out["dead"][0]["last_metrics"] == {"loss": 9.0}  # post-mortem kept
        row = out["metrics"]["loss"]
        assert (row["min"], row["mean"], row["max"], row["n"]) == (1.0, 1.5, 2.0, 2)
        rec = agg.export_line()
        assert rec is not None
        assert agg.export_line() is None  # clean: nothing new to export
        agg.update(1, 1, {"loss": 2.0})  # the supervisor re-feeds every poll
        assert agg.export_line() is None  # equal-seq re-feed must not dirty
        line = json.loads(open(tmp_path / "agg.jsonl").read().strip())
        assert line["dead"][0]["rank"] == 2
        # the roll-up mirrors into cluster/* gauges on rank 0's registry
        assert reg.gauge("cluster/dead_ranks").value == 1
        assert reg.gauge("cluster/loss/mean").value == 1.5

    def test_stale_seq_never_overwrites_newer(self):
        agg = CrossRankAggregator(2)
        agg.update(1, 5, {"v": 5.0})
        agg.update(1, 3, {"v": 3.0})  # late/duplicate beat
        assert agg.aggregate()["metrics"]["v"]["max"] == 5.0

    def test_file_channel_piggybacks_metrics(self, tmp_path):
        from deepspeed_tpu.resilience.supervision.heartbeat import FileBeatChannel

        mon = FileBeatChannel(str(tmp_path), rank=0, world_size=2, beat_timeout=5.0)
        peer = FileBeatChannel(str(tmp_path), rank=1, world_size=2, beat_timeout=5.0)
        peer.beat(3, metrics={"loss": 2.5})
        mon.events()  # one scan pass collects the payload
        assert mon.peer_metrics()[1] == (3, {"loss": 2.5})

    def test_tcp_channel_piggybacks_metrics(self):
        from deepspeed_tpu.resilience.supervision.heartbeat import TcpBeatChannel

        srv = TcpBeatChannel(rank=0, world_size=2, port=0, beat_timeout=5.0,
                             connect_grace=5.0)
        srv.start()
        cli = TcpBeatChannel(rank=1, world_size=2, address="127.0.0.1", port=srv.port,
                             beat_timeout=5.0, connect_grace=5.0)
        cli.start()
        try:
            assert _wait_for(lambda: cli._client is not None)
            cli.beat(4, metrics={"train/loss": 1.75, "steps": 4.0})
            srv.beat(4, metrics={"train/loss": 1.25, "steps": 4.0})
            assert _wait_for(lambda: 1 in srv.peer_metrics())
            assert srv.peer_metrics()[1] == (4, {"train/loss": 1.75, "steps": 4.0})
            assert srv.peer_metrics()[0][1]["train/loss"] == 1.25
        finally:
            srv.stop()
            cli.stop()

    def test_supervised_death_lands_in_aggregate_stream(self, tmp_path):
        """The in-process form of the 2-process acceptance proof: two
        supervisors over a real TCP beat channel, rank-1 metrics arrive
        at rank 0 purely via beat piggyback, then rank 1 dies by socket
        EOF (the SIGKILL signature) — the exported aggregate stream
        first covers both ranks and then flags rank 1 dead with its
        last-seen snapshot."""
        from deepspeed_tpu.resilience.supervision import Supervisor
        from deepspeed_tpu.resilience.supervision.heartbeat import TcpBeatChannel

        reg = MetricsRegistry(enabled=True)
        agg_path = tmp_path / "aggregate.jsonl"
        agg = CrossRankAggregator(2, jsonl_path=str(agg_path), registry=reg)
        ch0 = TcpBeatChannel(rank=0, world_size=2, port=0, beat_timeout=0.5,
                             connect_grace=5.0)
        rescued = []
        sup0 = Supervisor(
            rank=0, world_size=2, channel=ch0, beat_interval=0.05,
            metrics_fn=lambda: {"train/loss": 1.0}, aggregator=agg,
            on_rescue=lambda site, reason: rescued.append((site, reason)),
        ).start()  # starting the supervisor starts (and binds) the channel
        ch1 = TcpBeatChannel(rank=1, world_size=2, address="127.0.0.1", port=ch0.port,
                             beat_timeout=0.5, connect_grace=5.0)
        sup1 = Supervisor(
            rank=1, world_size=2, channel=ch1, beat_interval=0.05,
            metrics_fn=lambda: {"train/loss": 2.0},
            on_rescue=lambda site, reason: None,
        ).start()
        try:
            # rank-1 metrics crossed the wire and joined the aggregate
            assert _wait_for(
                lambda: any(
                    row["n"] == 2 for row in agg.aggregate()["metrics"].values()
                )
            ), agg.aggregate()
            # kill rank 1 the SIGKILL way: stop beats, close the socket
            sup1._stop.set()
            ch1._stop.set()
            with ch1._client_lock:
                ch1._client.close()
            assert _wait_for(lambda: 1 in agg.aggregate() and False or
                             any(d["rank"] == 1 for d in agg.aggregate()["dead"]))
            assert rescued, "rank-0 supervisor never reacted to the death"
            lines = [json.loads(l) for l in agg_path.read_text().splitlines()]
            both = [l for l in lines if l["alive"] == [0, 1]
                    and any(r["n"] == 2 for r in l["metrics"].values())]
            assert both, "no line covered both live ranks"
            row = both[-1]["metrics"]["train/loss"]
            assert (row["min"], row["max"]) == (1.0, 2.0)
            dead = [l for l in lines if any(d["rank"] == 1 for d in l["dead"])]
            assert dead, "death never exported"
            assert dead[-1]["dead"][0]["last_metrics"] == {"train/loss": 2.0}
        finally:
            sup0.stop()
            sup1.stop()
            ch0.stop()
            ch1.stop()


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

TINY = dataclasses.replace(gpt2.GPT2_TINY, remat=False,
                           scan_unroll=gpt2.GPT2_TINY.n_layer)


def _train_engine(extra_config=None, cfg=TINY):
    model_fn, init_fn, tp_fn = gpt2.make_model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 2,
        **(extra_config or {}),
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=init_fn(), config=config, tp_spec_fn=tp_fn
    )
    return engine


def _batch():
    rng = np.random.default_rng(0)
    return {"input_ids": rng.integers(0, TINY.vocab_size, (16, 16), dtype=np.int32)}


class TestEngineIntegration:
    def test_mfu_gauge_consistent_with_analytic_count(self):
        """Acceptance: the 8-device dryrun train run's MFU gauge
        (compiled-cost flops over per-chip peak) agrees with the
        analytic 6N+attention count —
        the two derivations share steps/s, so the ratio isolates the
        flops source (measured ~1.1x on this mesh; the layer loop is
        unrolled so the scan caveat does not bite)."""
        import jax

        engine = _train_engine()
        batch = _batch()
        for _ in range(4):
            engine.train_batch(batch)
        reg = tel.get_registry()
        mfu = reg.gauge("mfu", engine="train").value
        wall_ms = reg.gauge("train/step_wall_ms", engine="train").value
        flops = reg.gauge("flops_per_step", engine="train").value
        assert mfu and wall_ms and flops
        # internal consistency: the gauge IS flops/wall/per-chip-peak
        from deepspeed_tpu.profiling.flops_profiler import peak_flops

        expect = flops / (wall_ms / 1e3) / peak_flops()
        assert mfu == pytest.approx(expect, rel=1e-6)
        # cross-check vs the analytic per-chip count at the same wall
        n_dev = jax.device_count()
        seq, tokens = 16, 16 * 16
        analytic_flops_per_dev = (
            (6 * TINY.num_params() + 12 * TINY.n_layer * TINY.n_embd * seq)
            * tokens / n_dev
        )
        analytic_mfu = analytic_flops_per_dev / (wall_ms / 1e3) / peak_flops()
        assert 0.3 < mfu / analytic_mfu < 3.0, (mfu, analytic_mfu)
        # HBM gauge rides the same cost analysis
        assert reg.gauge("hbm_bytes_per_step", engine="train").value > 0
        summ = engine.telemetry.summary()
        assert summ["mfu"] == pytest.approx(mfu, abs=1e-4)
        assert summ["telemetry"]["metrics"] > 5
        # the registry-only default path never paid a d2h sync for the
        # report: no loss gauge, samples from the host step mirror
        compact = reg.snapshot_compact()
        assert "train/loss{engine=train}" not in compact
        assert compact["train/samples{engine=train}"] == 4 * 16

    def test_progress_events_route_through_registry_to_monitor(self, tmp_path, monkeypatch):
        import sys

        monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
        engine = _train_engine({
            "tensorboard": {"enabled": True, "output_path": str(tmp_path), "job_name": "jb"},
        })
        batch = _batch()
        for _ in range(4):
            engine.train_batch(batch)
        # the registry carries the loss/lr/loss-scale gauges...
        reg = tel.get_registry()
        assert reg.gauge("train/loss", engine="train").value is not None
        assert reg.gauge("train/lr", engine="train").value == pytest.approx(1e-3)
        # ...and the monitor still receives the exact reference tags
        events = [json.loads(l) for l in open(tmp_path / "jb" / "events.jsonl")]
        tags = {e["tag"] for e in events}
        assert {"Train/Samples/lr", "Train/Samples/loss_scale",
                "Train/Samples/train_loss"} <= tags

    def test_monitor_events_survive_telemetry_disabled(self, tmp_path, monkeypatch):
        """tensorboard on + telemetry off: the reference event stream
        must keep flowing (the manager forwards; only registry
        collection is off)."""
        import sys

        monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
        engine = _train_engine({
            "telemetry": {"enabled": False},
            "tensorboard": {"enabled": True, "output_path": str(tmp_path), "job_name": "jb"},
        })
        batch = _batch()
        for _ in range(4):
            engine.train_batch(batch)
        assert not tel.get_registry().enabled
        assert tel.get_registry().size() == 0  # zero-overhead: nothing registered
        events = [json.loads(l) for l in open(tmp_path / "jb" / "events.jsonl")]
        assert any(e["tag"] == "Train/Samples/train_loss" for e in events)

    def test_publish_step_cost_is_hot_path_cheap(self):
        """The per-step registry publish must stay far under 1% of any
        real step (record: ~10-30us per publish on this container;
        docs/telemetry.md overhead table has the engine-level A/B)."""
        reg = MetricsRegistry(enabled=True)
        tm = TelemetryManager("train", reg, TraceBuffer(enabled=False))
        tm.set_step_cost({"flops": 1e9, "bytes accessed": 1e8})
        rec = {"data_wait": 0.001, "compute": 0.02, "ckpt_stall": 0.0,
               "compile": 0.0, "other": 0.001, "wall": 0.022}
        tm.publish_step("train", rec)  # warm the handles
        n = 200
        t0 = time.perf_counter()
        for _ in range(n):
            tm.publish_step("train", rec)
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 500e-6, f"publish_step cost {per_call * 1e6:.0f}us"

    def test_armed_ds_san_stays_clean_with_telemetry(self):
        """Acceptance: telemetry on the hot path adds no transfers and
        no recompiles under an armed sanitizer."""
        from deepspeed_tpu.analysis.sanitizer import core as san_core
        from deepspeed_tpu.analysis.sanitizer.core import Sanitizer
        from deepspeed_tpu.config.config import SanitizerConfig

        san = san_core.install(Sanitizer(SanitizerConfig.from_dict(
            {"enabled": True, "checkers": ["recompile", "transfer", "donation"]})))
        try:
            engine = _train_engine()
            assert engine._sanitizer is san
            assert engine.telemetry.collect
            batch = _batch()
            for _ in range(6):
                engine.train_batch(batch)
            assert engine.compilation_count == 1
            assert san.findings == [], [f.format() for f in san.findings]
        finally:
            san_core.uninstall()

    def test_flops_profiler_reports_hbm_and_mfu(self):
        engine = _train_engine({"flops_profiler": {"enabled": True, "profile_step": 2}})
        batch = _batch()
        for _ in range(3):
            engine.train_batch(batch)
        res = engine.flops_profiler.results
        assert res["flops_per_step"] > 0
        assert res["hbm_bytes_per_step"] > 0
        assert res["hbm_gbps"] > 0
        assert 0 < res["mfu"] < 10
        # the profile gauges mirror into the registry
        assert tel.get_registry().gauge("profile/mfu").value == pytest.approx(res["mfu"])


# ---------------------------------------------------------------------------
# serving: request lifecycle spans reconstruct the SLO bench's TTFT
# ---------------------------------------------------------------------------


def _serving_pair(**kw):
    cfg = dataclasses.replace(gpt2.GPT2_TINY, remat=False)
    params = gpt2.init_params(cfg, seed=7)
    import jax.numpy as jnp

    eng = deepspeed_tpu.init_inference(
        model_config=cfg, params=params, dtype=jnp.float32, max_out_tokens=cfg.n_positions
    )
    from deepspeed_tpu.serving import ServingEngine

    kw.setdefault("num_slots", 2)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_len", 64)
    return eng, ServingEngine(eng, **kw)


class TestServingTelemetry:
    def test_trace_reconstructs_request_ttft(self, tmp_path):
        """A serving run's exported trace.json is schema-valid and its
        per-request spans reconstruct the p50/p99 TTFT of the
        ``Request`` objects' own stamps (the timestamps the spans
        carry) within 5%, under a seeded Poisson arrival loop."""
        tel.configure(TelemetryConfig(trace=True,
                                      trace_path=str(tmp_path / "trace.json")),
                      label="test")
        eng, srv = _serving_pair(max_new_tokens=6)
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(1, eng.model_config.vocab_size, int(rng.integers(4, 33)), dtype=np.int32)
            for _ in range(12)
        ]
        # warm both executables so no request's TTFT holds a compile
        srv.submit(prompts[0], max_new_tokens=2)
        srv.drain(max_steps=10_000)
        pending = list(zip(np.cumsum(rng.exponential(1.0 / 50.0, size=12)), prompts))
        finished = {}
        t0 = time.monotonic()
        while pending or srv.scheduler.has_work():
            now = time.monotonic() - t0
            while pending and pending[0][0] <= now:
                srv.submit(pending.pop(0)[1], max_new_tokens=6)
            if srv.scheduler.has_work():
                srv.step()
            else:
                time.sleep(min(0.005, max(0.0, pending[0][0] - now)))
            finished.update(srv.pop_results())
        assert len(finished) == 12
        stamps = [(r.first_token_time - r.submit_time) * 1e3 for r in finished.values()]
        doc = json.load(open(tel.export_trace()))
        assert validate_chrome_trace(doc) == []
        # per-request TTFT off the trace: end of the prefill span minus
        # start of the queue span, per request lane.  The warm-up
        # request generates 2 tokens, the measured ones 6 — the retire
        # instant's token count filters it.
        events = doc["traceEvents"]
        measured = {
            e["tid"] for e in events
            if e["name"] == "retire" and e["args"]["tokens"] == 6
        }
        assert len(measured) == 12
        ttft = []
        for tid in measured:
            lane = [e for e in events if e.get("tid") == tid and e.get("ph") == "X"]
            queue = next(e for e in lane if e["name"] == "queue")
            prefill = next(e for e in lane if e["name"] == "prefill")
            ttft.append((prefill["ts"] + prefill["dur"] - queue["ts"]) / 1e3)
        for q in (50, 99):
            assert float(np.percentile(ttft, q)) == pytest.approx(
                float(np.percentile(stamps, q)), rel=0.05
            )

    def test_request_lifecycle_histograms_and_counters(self):
        tel.configure(TelemetryConfig(), label="test")
        _, srv = _serving_pair()
        rng = np.random.default_rng(3)
        for _ in range(3):
            srv.submit(rng.integers(1, 100, 12, dtype=np.int32), max_new_tokens=4)
        srv.drain(max_steps=500)
        reg = tel.get_registry()
        assert reg.histogram("serving/ttft_ms", engine="serving").count == 3
        assert reg.histogram("serving/tpot_ms", engine="serving").count == 3
        assert reg.counter("serving/finished", engine="serving", reason="length").value == 3
        assert reg.counter("serving/submitted", engine="serving").value == 3

    def test_slo_breach_counts_and_marks_trace(self, tmp_path):
        tel.configure(TelemetryConfig(trace=True, slo_ttft_breach_ms=1e-3),
                      label="test")
        _, srv = _serving_pair()
        srv.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=2)
        srv.drain(max_steps=100)
        reg = tel.get_registry()
        assert reg.counter("serving/slo_breaches", engine="serving").value >= 1
        names = {e["name"] for e in tel.get_tracer().events()}
        assert "slo_breach" in names

    def test_queue_full_rejection_counted(self):
        tel.configure(TelemetryConfig(), label="test")
        from deepspeed_tpu.serving import ServingQueueFull

        _, srv = _serving_pair(max_queue=1)
        srv.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=2)
        with pytest.raises(ServingQueueFull):  # queue bound hit before any tick
            srv.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=2)
        assert tel.get_registry().counter(
            "serving/rejected", engine="serving").value == 1


# ---------------------------------------------------------------------------
# config + satellites
# ---------------------------------------------------------------------------


class TestConfigAndSatellites:
    def test_telemetry_block_validates(self):
        from deepspeed_tpu.config.config import DeepSpeedConfig

        c = DeepSpeedConfig({
            "train_micro_batch_size_per_gpu": 1,
            "telemetry": {"enabled": True, "exporters": ["jsonl", "prometheus"],
                          "export_interval_seconds": 5, "trace": True},
        })
        assert c.telemetry.exporters == ("jsonl", "prometheus")
        with pytest.raises(DeepSpeedConfigError, match="exporters"):
            TelemetryConfig.from_dict({"exporters": ["grafana"]})
        with pytest.raises(DeepSpeedConfigError, match="export_interval_seconds"):
            TelemetryConfig.from_dict({"export_interval_seconds": 0})
        with pytest.raises(DeepSpeedConfigError, match="ring"):
            TelemetryConfig.from_dict({"ring": 2})
        with pytest.raises(DeepSpeedConfigError, match="slo_ttft_breach_ms"):
            TelemetryConfig.from_dict({"slo_ttft_breach_ms": -1})
        with pytest.raises(DeepSpeedConfigError):  # unknown key with suggestion
            TelemetryConfig.from_dict({"exporter": ["jsonl"]})

    def test_monitor_lifecycle_atexit_and_idempotent_close(self, tmp_path, monkeypatch):
        import atexit
        import sys

        import deepspeed_tpu.utils.monitor as mon

        monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
        registered = []
        monkeypatch.setattr(atexit, "register", lambda fn: registered.append(fn))
        m = mon.TensorBoardMonitor(output_path=str(tmp_path), job_name="jb", enabled=True)
        assert m.close in registered  # crash-safety: atexit flush/close
        m.add_scalar("t", 1.0, 0)
        m.flush()
        m.close()
        m.close()  # idempotent
        events = open(tmp_path / "jb" / "events.jsonl").read().strip().splitlines()
        assert len(events) == 1

    def test_see_memory_usage_reports_real_device_bytes_on_cpu(self):
        import jax.numpy as jnp

        from deepspeed_tpu.profiling import see_memory_usage

        keep = jnp.ones((256, 256), jnp.float32)  # 256KB live on device 0
        out = see_memory_usage("test")
        dev = sum(v for k, v in out.items() if k.endswith("/bytes_in_use"))
        assert dev >= keep.nbytes  # real accounting, not silent zeros
        assert any(k.startswith("host/") for k in out)

    def test_derive_step_stats_math(self):
        from deepspeed_tpu.profiling.flops_profiler import derive_step_stats, peak_flops

        stats = derive_step_stats(
            {"flops": 1e12, "bytes accessed": 5e9}, wall_s=0.5, device_kind="TPU v5 lite")
        assert stats["achieved_flops"] == pytest.approx(2e12)
        assert stats["mfu"] == pytest.approx(2e12 / peak_flops("TPU v5 lite")) == pytest.approx(2 / 197)
        assert stats["hbm_gbps"] == pytest.approx(10.0)

    def test_status_and_shutdown_roundtrip(self, tmp_path):
        tel.configure(TelemetryConfig(
            exporters=("prometheus",), output_path=str(tmp_path),
            export_interval_seconds=60), label="t")
        tel.get_registry().counter("s").inc()
        st = tel.status()
        assert st["enabled"] and st["sinks"] == ["prometheus"]
        tel.flush()
        assert tel.status()["last_export_age_seconds"] is not None
        tel.shutdown()
        assert (tmp_path / "metrics_rank0.prom").exists()
