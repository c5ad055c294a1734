"""Per-kernel attribution + perf-regression plane tests (ISSUE 11).

Coverage: the HLO cost walk's bucket totals calibrate to the module
``cost_analysis()`` within 1% and the matmul bucket pins to the
analytic ``6N`` count on the 8-device dryrun; roofline verdicts pinned
for the dryrun train step (matmul compute-bound) and the serving decode
executable (matmul memory-bound); attribution gauges + Perfetto counter
tracks; the runtime anomaly watch (step-wall spike, cross-rank
straggler over the in-process 2-supervisor heartbeat channel);
bench-history schema/append/child-guard; and ``bench_diff`` verdicts on
synthetic improve/regress/noise histories with the bless workflow."""
import dataclasses
import json
import time

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry as tel
from deepspeed_tpu.config.config import TelemetryConfig
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.telemetry import (
    MetricsRegistry,
    TelemetryManager,
    TraceBuffer,
    validate_chrome_trace,
)
from deepspeed_tpu.telemetry.attribution import (
    OTHER,
    analytic_matmul_flops,
    attribute_jit,
)
from deepspeed_tpu.telemetry import regression as reg

pytestmark = [pytest.mark.telemetry, pytest.mark.usefixtures("cpu_peak")]

TINY = dataclasses.replace(gpt2.GPT2_TINY, remat=False,
                           scan_unroll=gpt2.GPT2_TINY.n_layer)


@pytest.fixture(autouse=True)
def _fresh_plane():
    tel.reset_for_tests()
    yield
    tel.reset_for_tests()


def _train_engine(extra_config=None, cfg=TINY):
    model_fn, init_fn, tp_fn = gpt2.make_model(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 10_000,
        **(extra_config or {}),
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_fn, model_parameters=init_fn(), config=config, tp_spec_fn=tp_fn
    )
    return engine


# ---------------------------------------------------------------------------
# attribution: the compiled train step (8-device dryrun)
# ---------------------------------------------------------------------------


class TestTrainStepAttribution:
    def test_bucket_sum_6n_pin_and_roofline_verdict(self):
        """Acceptance: bucket FLOPs sum == cost_analysis() within 1%,
        the matmul bucket matches the analytic 6N count, and the train
        matmuls verdict compute-bound on this platform's roofline."""
        engine = _train_engine()
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, TINY.vocab_size, (16, 16), dtype=np.int32)}
        engine.train_batch(batch)

        attr = engine.train_step_attribution()
        assert attr is not None and attr.label == "train_step"
        # 1) calibrated totals: the table must answer for the WHOLE module
        assert attr.module_flops > 0 and attr.module_bytes > 0
        assert attr.total_flops() == pytest.approx(attr.module_flops, rel=0.01)
        assert attr.total_bytes() == pytest.approx(attr.module_bytes, rel=0.01)
        # the walk attributed the bulk analytically — the residual folded
        # into layernorm/other must stay a correction, not the story
        assert abs(attr.unattributed_flops) < 0.15 * attr.module_flops

        # 2) the matmul bucket IS the 6N parameter-matmul count
        tokens = 16 * 16
        expect = analytic_matmul_flops(TINY.num_params(), tokens, jax.device_count())
        assert attr.buckets["matmul"].flops == pytest.approx(expect, rel=0.15)
        # matmul dominates the step's flops (attention-score math is
        # bucketed separately)
        assert attr.buckets["matmul"].flops > 0.5 * attr.module_flops

        # 3) pinned roofline verdicts on the dryrun: train matmuls sit
        # above the CPU machine balance, the optimizer update below it
        assert attr.verdict("matmul") == "compute"
        assert attr.verdict("optimizer-update") == "memory"
        rows = attr.roofline()
        assert abs(sum(r["min_time_share_pct"] for r in rows) - 100.0) < 0.1
        for r in rows:
            assert r["bound"] in ("compute", "memory") and r["min_time_ms"] >= 0

    def test_attribution_gauges_published_and_in_summary(self):
        engine = _train_engine()
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, TINY.vocab_size, (16, 16), dtype=np.int32)}
        engine.train_batch(batch)
        registry = tel.get_registry()
        shares = {
            m.labels["bucket"]: m.value
            for m in registry.metrics()
            if m.name == "attribution/time_share_pct"
        }
        assert "matmul" in shares and sum(shares.values()) == pytest.approx(100, abs=1)
        top = engine.telemetry.summary()["attribution_top"]
        assert len(top) == 3
        assert top[0]["time_share_pct"] >= top[-1]["time_share_pct"]

    def test_attribute_jit_calibrates_standalone_fn(self):
        def fn(w, x):
            h = jax.numpy.tanh(x @ w)
            return (h * h).sum()

        w = np.zeros((64, 128), np.float32)
        x = np.zeros((32, 64), np.float32)
        attr = attribute_jit(fn, w, x, label="toy")
        assert attr is not None
        assert attr.total_flops() == pytest.approx(attr.module_flops, rel=0.01)
        # the lone dot: 2*32*128*64 flops, bucketed as matmul
        assert attr.buckets["matmul"].flops == pytest.approx(2 * 32 * 128 * 64, rel=0.01)
        assert attr.buckets[OTHER].flops > 0  # tanh/mul/reduce + residual


# ---------------------------------------------------------------------------
# attribution: the serving decode executable
# ---------------------------------------------------------------------------


class TestDecodeAttribution:
    def test_decode_matmul_memory_bound_and_calibrated(self):
        import jax.numpy as jnp

        from deepspeed_tpu.serving import ServingEngine

        eng = deepspeed_tpu.init_inference(
            model_config=gpt2.GPT2_TINY, params=gpt2.init_params(gpt2.GPT2_TINY),
            dtype=jnp.float32, max_out_tokens=gpt2.GPT2_TINY.n_positions,
        )
        srv = ServingEngine(eng, num_slots=2, prefill_chunk=8, max_len=32)
        attr = srv.attribute_decode()
        assert attr is not None and attr.label == "serving_decode"
        assert attr.total_flops() == pytest.approx(attr.module_flops, rel=0.01)
        # pinned: single-token decode matmuls are matrix-vector — far
        # below the machine balance on every platform we model
        assert attr.verdict("matmul") == "memory"
        # the on-demand AOT walk must not disturb the engine's
        # one-decode-executable accounting
        assert srv.decode_compiles == 1


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
        assert reg.check_step_spike(100.0, 10.0, window_count=3) is None  # < min
        assert reg.check_step_spike(100.0, None, window_count=50) is None
        ev = reg.check_step_spike(100.0, 10.0, window_count=50)
        assert ev["event"] == "step_wall_spike" and ev["factor"] == 10.0
        assert reg.check_step_spike(20.0, 10.0, window_count=50) is None  # 2x < 2.5x

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
        assert reg.find_stragglers({0: {"a/step_wall_ms": 100.0}}, [0]) == []
        flags = reg.find_stragglers(
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
        buf.add_counter("attribution/train/time_share_pct", {"matmul": 61.0})
        path = buf.export(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        assert validate_chrome_trace(doc) == []
        c = [e for e in doc["traceEvents"] if e["ph"] == "C"]
        assert c and c[0]["args"] == {"matmul": 61.0}

    def test_counter_without_args_rejected_by_validator(self):
        doc = {"traceEvents": [{"name": "x", "ph": "C", "ts": 1.0, "pid": 0, "tid": 0}]}
        assert validate_chrome_trace(doc)


# ---------------------------------------------------------------------------
# bench history + diff
# ---------------------------------------------------------------------------


def _append(path, metric, value, run_id, unit="tokens/s", **extra):
    reg.history_append(
        [{"metric": metric, "value": value, "unit": unit, "backend": "cpu", **extra}],
        rung="t", path=str(path), run_id=run_id, sha="s0",
    )


class TestBenchHistory:
    def test_schema_fields_and_fingerprint_stability(self, tmp_path):
        path = tmp_path / "h.jsonl"
        rec = {"metric": "m", "value": 1.0, "unit": "tokens/s", "backend": "cpu",
               "micro_bs": 8, "seq": 1024}
        _append(path, "m", 1.0, "r0", micro_bs=8, seq=1024)
        line = json.loads(path.read_text())
        assert line["schema"] == reg.HISTORY_SCHEMA and line["kind"] == "bench"
        for key in ("ts", "run_id", "git_sha", "rung", "metric", "value",
                    "unit", "backend", "fingerprint"):
            assert key in line
        assert line["fingerprint"] == reg.config_fingerprint(rec)
        # a config change changes the key; an outcome change does not
        assert reg.config_fingerprint({**rec, "seq": 512}) != line["fingerprint"]
        assert reg.config_fingerprint({**rec, "value": 9.9}) == line["fingerprint"]

    def test_skips_and_child_guard(self, tmp_path, monkeypatch):
        path = tmp_path / "h.jsonl"
        n = reg.history_append(
            [{"metric": "m", "skipped": True}, {"metric": "m2", "value": "nan?"}],
            path=str(path),
        )
        assert n == 0 and not path.exists()
        monkeypatch.setenv("DS_BENCH_CHILD", "1")
        n = reg.history_append([{"metric": "m", "value": 1.0}], path=str(path))
        assert n == 0 and not path.exists()

    def test_torn_tail_line_is_ignored(self, tmp_path):
        path = tmp_path / "h.jsonl"
        _append(path, "m", 1.0, "r0")
        with open(path, "a") as f:
            f.write('{"truncated": ')
        assert len(reg.history_load(str(path))) == 1


class TestBenchDiff:
    def test_improve_regress_noise_and_no_baseline(self, tmp_path):
        path = tmp_path / "h.jsonl"
        for i, v in enumerate((1000.0, 1010.0, 990.0)):
            _append(path, "decode_tokens_per_sec", v, f"r{i}")
            _append(path, "ttft_p99_ms", 50.0 + i, f"r{i}", unit="ms")
            _append(path, "train_tokens_per_sec", 500.0 + i, f"r{i}")
        # newest run: decode regresses 10%, ttft improves 30%, train wobbles
        _append(path, "decode_tokens_per_sec", 900.0, "r9")
        _append(path, "ttft_p99_ms", 35.0, "r9", unit="ms")
        _append(path, "train_tokens_per_sec", 505.0, "r9")
        _append(path, "fresh_metric", 1.0, "r9")
        v = {row["metric"]: row for row in reg.bench_diff(reg.history_load(str(path)))}
        assert v["decode_tokens_per_sec"]["verdict"] == "regress"
        assert v["ttft_p99_ms"]["verdict"] == "improve"  # lower-is-better
        assert v["train_tokens_per_sec"]["verdict"] == "noise"
        assert v["fresh_metric"]["verdict"] == "no-baseline"
        ok, bad = reg.gate(list(v.values()))
        assert not ok and [b["metric"] for b in bad] == ["decode_tokens_per_sec"]

    def test_noise_band_widens_with_dispersion(self, tmp_path):
        path = tmp_path / "h.jsonl"
        # historically noisy: ±20% swings — a 10% dip must NOT gate
        for i, v in enumerate((1000.0, 800.0, 1200.0, 950.0, 1150.0)):
            _append(path, "noisy", v, f"r{i}")
        _append(path, "noisy", 900.0, "r9")
        (row,) = reg.bench_diff(reg.history_load(str(path)))
        assert row["band_pct"] > 5.0
        assert row["verdict"] == "noise"

    def test_bless_resets_baseline(self, tmp_path):
        path = tmp_path / "h.jsonl"
        for i in range(3):
            _append(path, "m", 1000.0, f"r{i}")
        _append(path, "m", 700.0, "r3")
        (row,) = reg.bench_diff(reg.history_load(str(path)))
        assert row["verdict"] == "regress"
        reg.history_bless("m", note="intentional tradeoff", path=str(path))
        (row,) = reg.bench_diff(reg.history_load(str(path)))
        assert row["verdict"] == "no-baseline"
        _append(path, "m", 705.0, "r4")
        (row,) = reg.bench_diff(reg.history_load(str(path)))
        assert row["verdict"] == "noise"  # the new normal is the baseline

    def test_multi_record_run_cannot_self_baseline(self, tmp_path):
        path = tmp_path / "h.jsonl"
        _append(path, "m", 1000.0, "r0")
        _append(path, "m", 1001.0, "r0")  # same run, second record
        (row,) = reg.bench_diff(reg.history_load(str(path)))
        assert row["verdict"] == "no-baseline" and row["n_baseline"] == 0

    def test_injected_records_are_marked_and_never_baseline(self, tmp_path):
        path = tmp_path / "h.jsonl"
        for i in range(3):
            _append(path, "m", 1000.0, f"r{i}")
        # the sentinel's doctored run: marked in the durable stream...
        _append(path, "m", 900.0, "r3", injected={"pattern": "m", "scale": 0.9})
        lines = reg.history_load(str(path))
        assert lines[-1]["injected"]["scale"] == 0.9
        (row,) = reg.bench_diff(lines)
        assert row["verdict"] == "regress"
        # ...and a later honest run baselines on the HONEST history only
        _append(path, "m", 995.0, "r4")
        (row,) = reg.bench_diff(reg.history_load(str(path)))
        assert row["verdict"] == "noise" and row["baseline"] == 1000.0

    def test_band_cap_bounds_mad_widening(self, tmp_path):
        path = tmp_path / "h.jsonl"
        for i, v in enumerate((1000.0, 800.0, 1200.0)):  # wildly noisy seeds
            _append(path, "m", v, f"r{i}")
        _append(path, "m", 900.0, "r9")
        (row,) = reg.bench_diff(reg.history_load(str(path)))
        assert row["verdict"] == "noise"  # MAD-widened band swallows -10%
        (row,) = reg.bench_diff(reg.history_load(str(path)), band_cap=0.06)
        assert row["verdict"] == "regress" and row["band_pct"] == 6.0

    def test_direction_inference(self):
        assert reg.lower_is_better("serving_ttft_p99_ms")
        assert reg.lower_is_better("step_ms", "ms")
        assert not reg.lower_is_better("decode_tokens_per_sec", "tokens/s")
