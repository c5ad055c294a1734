"""A configuration, a traffic mix, a cell and a per-layer metric added
as **new files only** are run by the unchanged harness.

Toy sizes on the CPU: the run reports the program's counts and nothing
that is a time, a rate or a share of the device.
"""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

TOY_MODEL = {"vocab_size": 512, "n_positions": 128, "n_embd": 64, "n_layer": 2, "n_head": 4}
LOOSE = 1e9  # the toy run exercises the plumbing; test_reference.py owns the limits


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj))


@pytest.fixture
def toy_root(tmp_path):
    """A manifest of its own whose only directory holds new files: the
    runners, readers and kernels are found beside the harness."""
    root = str(tmp_path)
    _write(f"{root}/extra/configs/toy-train.json", {
        "runner": "train", "model": TOY_MODEL, "model_options": {"remat": True, "xent_chunk_size": 64},
        "engine": {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 1,
                   "bf16": {"enabled": True}, "zero_optimization": {"stage": 3}, "mesh": {"fsdp": 1, "data": 1},
                   "optimizer": {"type": "Adam", "params": {"lr": 1e-4}}, "steps_per_print": 10 ** 6},
        "warmup_steps": 1,
        "checks": {"loss_abs_err_max": LOOSE, "logprob_rms_err_max": LOOSE, "update_disagreement_max": LOOSE}})
    zero3 = json.loads(open(f"{root}/extra/configs/toy-train.json").read())
    zero3["engine"]["mesh"] = {"fsdp": 4, "data": 1}
    _write(f"{root}/extra/configs/toy-train-zero3.json", zero3)
    _write(f"{root}/extra/configs/toy-serve.json", {
        "runner": "serve", "model": TOY_MODEL,
        "serving": {"num_slots": 4, "max_len": 128, "kv_cache_dtype": "model", "prefill_chunk": 16,
                    "max_queue": 1000, "max_new_tokens": 16, "degrade_max_new_tokens": 0,
                    "kvcache": {"enabled": True, "page_len": 16, "num_pages": 33}},
        "checks": {"sample_requests": 2, "token_gap_mean_max": LOOSE, "token_gap_max_max": LOOSE}})
    _write(f"{root}/extra/traffic/toy-tokens.json", {"kind": "tokens", "seq": 128})
    _write(f"{root}/extra/traffic/toy-backlog.json", {
        "kind": "closed", "clients": 6, "pool": 8, "prompt": {"dist": "uniform", "min": 16, "max": 48},
        "answer": {"dist": "uniform", "min": 3, "max": 8}, "max_total": 128, "preroll_s": 0.5,
        "ttft_sample_share": 0.0})
    _write(f"{root}/extra/traffic/toy-open.json", {
        "kind": "open", "rate_rps": 8.0, "pool": 8, "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 64},
        "answer": {"dist": "uniform", "min": 4, "max": 4}, "max_total": 128, "preroll_s": 0.5,
        # counts only: on a loaded test machine a first token may take longer than the toy window has left
        "ttft_sample_share": 0.25})
    _write(f"{root}/extra/metrics/toy_steps_counted.py",
           '"""A new per-layer metric: a count the record already holds."""\n\n\n'
           "def read(record):\n    return record['window']['steps']\n")
    cells = [("toy-train", "toy-train", "toy-tokens", 1), ("toy-train-zero3", "toy-train-zero3", "toy-tokens", 4),
             ("toy-backlog", "toy-serve", "toy-backlog", 1), ("toy-open", "toy-serve", "toy-open", 1)]
    _write(f"{root}/BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["extra"], "run_seconds": 1,
        "configs": [{"name": n, "source": "test", "file": f"extra/configs/{n}.json", "reduced": [], "why": "toy"}
                    for n in ("toy-train", "toy-train-zero3", "toy-serve")],
        "workloads": [{"name": w, "config": c, "traffic": t, "chips": n, "why": "toy"} for w, c, t, n in cells],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s/chip", "better": "higher", "bound": 0.1,
             "source": "host_clock", "workloads": ["toy-train", "toy-train-zero3"]},
            {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1,
             "source": "host_clock", "workloads": ["toy-backlog", "toy-open"]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            {"name": "toy_steps_counted", "unit": "count", "better": "higher", "source": "program_counter",
             "layer": "toy", "moves": "setup_s"},
            {"name": "compiles_in_window", "unit": "count", "better": "lower", "source": "program_counter",
             "layer": "entry points", "moves": "setup_s"},
            {"name": "kv_alloc_waits", "unit": "count", "better": "lower", "source": "program_counter",
             "layer": "KV pool", "moves": "serve_tokens_per_s"},
            {"name": "train_step_ms_p50", "unit": "ms", "better": "lower", "source": "host_clock",
             "layer": "train engine", "moves": "train_tokens_per_s"},
            {"name": "flash_decode_paged_roofline", "unit": "%", "better": "higher", "source": "device_trace",
             "layer": "kernels", "moves": "serve_tokens_per_s"}]})
    return root


@pytest.mark.parametrize("cell", ["toy-train", "toy-train-zero3", "toy-backlog", "toy-open"])
def test_new_files_only_cell_runs_and_reports_counts_only(toy_root, cell):
    out = harness.run_cell(cell, seed=2 ** 31 + 11, seconds=2.0, trace=True, t_start=time.perf_counter(),
                           manifest_path=f"{toy_root}/BENCHMARK.json", require_tpu=False,
                           scratch=f"{toy_root}/scratch")
    res, rec = out["result"], out["record"]
    assert res["device"]["platform"] == "cpu"
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    # each number compared beside its limit, under the line's last key
    assert list(res)[-1] == "checks" and res["checks"] and json.loads(json.dumps(res)) == res
    assert all(c["ok"] and c["value"] is not None and c["limit"] is not None for c in res["checks"].values())
    # the new metric was found by name and read; counts only — no time,
    # rate or device share from a CPU run
    assert res["metrics"]["toy_steps_counted"]["value"] == rec["window"]["steps"] >= 1
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert not {"train_step_ms_p50", "flash_decode_paged_roofline"} & set(res["metrics"])
    assert "breakdown" not in res and "busy_s" not in res["device"]
    if cell == "toy-train-zero3":
        assert res["device"]["count"] == 4  # ZeRO-3 over four (virtual) devices, checked against the one-device reference
    if not cell.startswith("toy-train"):
        assert "kv_alloc_waits" in res["metrics"]
        # a closed loop counts every emitted token; an open one the tokens of the requests due in the window
        assert 0 < rec["window"]["tokens"] <= rec["window"]["tokens_emitted"]
        assert (rec["window"]["tokens"] == rec["window"]["tokens_emitted"]) or cell == "toy-open"


def test_end_to_end_line_on_cpu_has_no_device_number(toy_root):
    out = harness.run_cell("toy-backlog", seed=5, seconds=1.0, trace=False, t_start=time.perf_counter(),
                           manifest_path=f"{toy_root}/BENCHMARK.json", require_tpu=False,
                           scratch=f"{toy_root}/scratch")
    assert out["result"]["metrics"] == {}  # every end-to-end metric is a time or a rate
    assert out["record"]["end_to_end"]["serve_tokens_per_s"] > 0


def test_control_tool_reads_the_program_and_the_failing_control(toy_root):
    """``control.py``, the tool the limits were read with on the chip,
    rehearsed at toy size: the program's numbers sit far under the int8
    control's on the forward and on the update."""
    import subprocess

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_CONTROL_ALLOW_CPU": "1"}
    p = subprocess.run([sys.executable, "benchmark/control.py", "--workload", "toy-train", "--seeds", "2",
                        "--control-seeds", "1", "--out", f"{toy_root}/control.json",
                        "--manifest", f"{toy_root}/BENCHMARK.json"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = json.loads(p.stdout.strip().splitlines()[-1])["rows"]
    assert len(rows) == 2 and "control_int8" in rows[0] and "control_int8" not in rows[1]
    prog, ctl = rows[0]["program"], rows[0]["control_int8"]
    assert ctl["logprob_rms_err"] > 3 * prog["logprob_rms_err"]
    assert prog["update_disagreement"] < 0.002 < 0.01 < ctl["update_disagreement"]
    # off the chip, and not told otherwise, the tool refuses to read anything
    env.pop("BENCH_CONTROL_ALLOW_CPU")
    q = subprocess.run([sys.executable, "benchmark/control.py", "--workload", "toy-train", "--out",
                        f"{toy_root}/c2.json", "--manifest", f"{toy_root}/BENCHMARK.json"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert q.returncode != 0 and q.stdout.strip() == ""
