"""The Solar-Open2 cell's benchmark files: the configuration (published
widths, the stated cut), the traffic mix, the two kernels' work
functions on hand-worked shapes, the new readers, the runner at toy size
on the CPU (counts only) and the control tool rehearsed there."""
import json
import os
import time

import pytest

from benchmark import harness, traffic
from benchmark.manifest import Manifest

M = Manifest()
CELL, CONFIG = "serve-solar2-reasoning-backlog", "solar-open2-serve-ep8share"
NEW = ("kda_decode_roofline", "gqa_decode_paged_roofline", "linear_state_share_pct")  # the three metrics PR 32 brought
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HF = {"hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256, "moe_intermediate_size": 32, "rms_norm_eps": 1e-5, "gqa_layers": [0, 4], "n_routed_experts": 8,
      "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 4,
      "kda_allow_neg_eigval": True, "use_rope": False,
      "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None}}
# the published config.json, as ISSUE 32 and the guide's catalog give it
PUBLISHED = {"model_type": "solar_open2", "partial_rotary_factor": 1,
             "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None},
             "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
             "vocab_size": 196608, "intermediate_size": 10240, "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
             "rope_theta": 10000, "tie_word_embeddings": False, "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
             "use_rope": False, "gqa_interval": 3, "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
             "use_gqa_gate": True, "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 320,
             "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 8}


def test_configuration_has_the_published_widths_and_states_its_cut():
    cfg = M.config(CONFIG)
    entry = next(c for c in M.data["configs"] if c["name"] == CONFIG)
    if os.path.exists(CATALOG):  # the catalog row, where the guide is at hand, is what PUBLISHED copies
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Solar-Open2-250B")
        assert row["config"] == PUBLISHED and row["source_url"] == cfg["source"] == entry["source"]
    differ = sorted(k for k in PUBLISHED if cfg[k] != PUBLISHED[k])
    assert differ == sorted(cfg["reduced"]) == sorted(entry["reduced"]) == ["gqa_layers", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert set(PUBLISHED) <= set(cfg) and cfg["model"] == {k: cfg[k] for k in PUBLISHED}  # top level == model
    assert (cfg["num_hidden_layers"], cfg["gqa_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (4, [0], 40, 24576)
    share = cfg["share"]
    assert share["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    assert share["chips_per_layer"] == 8 and share["published"]["n_routed_experts"] // 8 == cfg["n_routed_experts"]
    assert share["published"]["vocab_size"] // 8 == cfg["vocab_size"] and share["published"]["num_hidden_layers"] // 12 == 4
    # no width is in the cut; the guide's floors: a whole period, >= 4 layers, >= 8 experts, >= 1/8 of the vocabulary
    assert not [k for k in cfg["reduced"] if k.endswith(("_size", "_dim", "_rank")) and k != "vocab_size"]
    # the arithmetic the file states, recomputed
    D, H, Hkv, hd, F, r = 4096, 64, 8, 128, 1280, 128
    gqa = D * (H + 2 * Hkv) * hd + 2 * D * H * hd
    kda = 4 * D * H * hd + 2 * (D * r + r * H * hd) + D * H + 4 * 3 * H * hd
    moe = 3 * D * F + D * 320 + 40 * 3 * D * F
    params = gqa + 3 * kda + 4 * moe + 2 * 24576 * D
    assert round(gqa / 1e6, 1) == 109.1 and round(kda / 1e6, 1) == 137.7 and round(params / 1e6) == 3308
    s = cfg["serving"]
    state = 160 * 3 * (H * hd * hd * 4 + 3 * 3 * H * hd * 2)
    pages = s["kvcache"]["num_pages"] * 128 * Hkv * hd * 2 * 2
    assert round(state / 1e9, 2) == 2.08 and round(pages / 1e9, 2) == 2.68
    assert 0.70 < (2 * params + state + pages) / 16e9 < 0.72
    assert (s["num_slots"], s["max_len"], s["prefill_chunk"], s["prefill_chunks_per_step"]) == (160, 8192, 512, 1)
    assert s["kvcache"] == {"enabled": True, "page_len": 128, "num_pages": 5121, "session_ttl_seconds": 0.0}
    assert s["deadline_seconds"] == 0.0 and s["slo_ttft_ms"] == 0.0 and s["journal_dir"] == "" and s["degrade_max_new_tokens"] == 0
    assert "float32 recurrent state" in cfg["precision"] and "float32 router" in cfg["precision"]
    assert {"gate_shapes", "router", "shared_expert", "weights", "decoding", "experts_held"} <= set(cfg["assumed"])
    assert cfg["checks"]["sample_requests"] >= 2 and cfg["checks"]["token_gap_mean_max"] > 0
    assert cfg["checks"]["state_sample_slots"] >= 2 and 7 < cfg["checks"]["state_mantissa_bits_min"] < 22  # between bf16's 7 and float32's 23
    assert 0 < cfg["checks"]["state_rel_err_max"] < 1


@pytest.fixture(params=["as_committed", "with_a_cell_appended"])
def manifest(request, tmp_path):
    """The manifest as it stands, and as a later PR leaves it: a configuration, a cell and a metric appended
    after everything that is there, the new cell added to the list of a metric this cell reports."""
    if request.param == "as_committed":
        return M
    data = json.loads(json.dumps(M.data))
    for c in data["configs"]:
        c["file"] = os.path.relpath(os.path.join(M.root, c["file"]), tmp_path)
    data["configs"].append({**data["configs"][-1], "name": "later-config"})
    data["workloads"].append({"name": "later-cell", "config": "later-config", "traffic": "longctx-turns-backlog", "chips": 1, "why": "a later PR's"})
    for m in data["end_to_end"] + data["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "gqa_decode_paged_roofline"):
            m["workloads"].append("later-cell")
    data["per_layer"].append({**M.metric_entry("kda_decode_roofline"), "name": "later_kernel_roofline", "workloads": ["later-cell"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(data, f)
    return Manifest(str(tmp_path / "BENCHMARK.json"))


def test_the_cell_is_the_issues_and_nothing_else_of_the_manifest_moved(manifest):
    M = manifest  # noqa: N806 - the body reads as it did when it asked the committed manifest alone
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reasoning-decode-backlog", 1) and len(cell["why"]) <= 200
    assert "4 tokens an expert" in cell["why"] and "8x its share" in cell["why"]
    # by name, not by place, as tests/bench/test_zaya1.py asks: a later PR appends cells, configurations and metrics after
    # these, and may add its cell to a metric's list (gqa_decode_paged_roofline lists the ZAYA1 cell too)
    assert [w["name"] for w in M.data["workloads"]].count(CELL) == 1 and [c["name"] for c in M.data["configs"]].count(CONFIG) == 1
    for m in map(M.metric_entry, NEW):
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s" and m["unit"] == "%" and m["source"] == "device_trace"
        assert m in M.data["per_layer"] and m["layer"] == "kernels"
    e2e = {m["name"] for m in M.end_to_end(CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in M.per_layer(CELL)}
    assert {*NEW, "serve_step_ms_p50", "kv_alloc_waits",
            "kv_pages_in_use_pct", "batch_occupancy_pct", "serve_hbm_peak_gb", "serve_device_idle_pct",
            "moe_dropped_assignments", "moe_expert_load_max_over_mean"} <= names  # the MoE layer is a fifth of its device time
    assert not {"flash_decode_paged_roofline", "mla_decode_paged_roofline"} & names
    assert M.config(CONFIG)["runner"] == "serve_solar2" and M.find("runners", "serve_solar2", ".py")


def test_traffic_file_is_the_reasoning_backlog():
    mix = M.traffic("reasoning-decode-backlog")
    assert (mix["kind"], mix["clients"], mix["pool"], mix["max_total"], mix["preroll_s"], mix["ttft_sample_share"]) == \
        ("closed", 200, 40, 8192, 30, 0.0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64, "max": 4096}
    assert mix["answer"] == {"dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256, "max": 4096}
    pool = traffic.length_pool(mix)
    assert len(pool) == 40 and all(64 <= p <= 4096 and 256 <= a <= 4096 and p + a <= 8192 for p, a in pool)
    prompts, answers = sorted(p for p, _ in pool), sorted(a for _, a in pool)
    assert 480 <= prompts[20] <= 545 and 990 <= answers[20] <= 1060  # the medians
    # pages a request maps at admission: ~20 on the time-weighted mean, 160 slots of them well inside 5,120
    pages = [-(-(p + a) // 128) for p, a in pool]
    weighted = sum(n * a for n, (_, a) in zip(pages, pool)) / sum(a for _, a in pool)
    assert 14 <= weighted <= 24 and 160 * weighted < 5120
    req = next(traffic.request_stream(mix, 2 ** 31 + 3, 24576))
    assert 1 <= req["prompt"].min() and req["prompt"].max() < 24576


def test_kda_decode_work_counts_each_decoding_rows_state_in_and_out_once():
    model = M.config(CONFIG)["model"]
    # 10 decode steps traced, 150 rows decoding in each
    shapes = {"model": model, "page_len": 128, "decode_steps_traced": 10, "decode_rows_traced": 1500, "decode_pages_traced": 19500}
    w = M.module("kernels", "kda_decode").work(shapes, calls=30, out_elems=0)  # 3 KDA layers x 10 steps
    state = 64 * 128 * 128 * 4
    per_row = 2 * state + 64 * (3 * 128 + 2 * 128 + 1) * 4
    assert state == 4_194_304 and w["bytes"] == pytest.approx(30 * 150 * per_row)
    assert w["flops"] == pytest.approx(30 * 150 * 7 * 64 * 128 * 128)
    assert w["flops"] / w["bytes"] < 1.0  # under a FLOP a byte: the bytes bound
    assert 30 * 150 * per_row / 10 / 1e9 == pytest.approx(3.85, abs=0.02)  # GB a decode step at 150 rows


def test_gqa_decode_paged_work_counts_each_filled_page_once_a_kv_head():
    model = M.config(CONFIG)["model"]
    # 10 decode steps traced; each had 150 live rows filling 13 pages each
    shapes = {"model": model, "page_len": 128, "decode_steps_traced": 10, "decode_rows_traced": 1500, "decode_pages_traced": 19500}
    w = M.module("kernels", "gqa_decode_paged").work(shapes, calls=10, out_elems=0)  # one GQA layer x 10 steps
    page = 8 * 128 * 128 * 2  # one page of K (or V): 8 KV heads, not 64 query heads
    per_call = 1950 * page * 2 + 150 * 64 * 128 * 2 * 2
    assert page == 262_144 and w["bytes"] == pytest.approx(10 * per_call)
    assert w["flops"] == pytest.approx(10 * 4 * 64 * 128 * 1950 * 128)
    assert w["flops"] / (10 * 1950 * page * 2) == pytest.approx(8.0)  # the group: 8 FLOP a cached byte


def test_new_readers_return_nothing_where_the_program_reports_nothing():
    bare = {"counters": {}, "trace": {"kernels": {}}, "shapes": {}, "device": {"kind": "TPU v5 lite"}, "manifest": M, "programs": None}
    for name in ("kda_decode_roofline", "gqa_decode_paged_roofline", "linear_state_share_pct"):
        assert M.module("metrics", name).read(bare) is None
        assert M.module("metrics", name).read({**bare, "trace": None}) is None
    # a GPT-2 run's flash_decode_paged is not a grouped one: the GQA reader leaves it to flash_decode_paged_roofline
    gpt2 = {**bare, "trace": {"kernels": {"flash_decode_paged": {"calls": 48, "seconds": 0.06, "out_elems": 0}}},
            "shapes": {"model": {"n_head": 25, "n_embd": 1600}}}
    assert M.module("metrics", "gqa_decode_paged_roofline").read(gpt2) is None
    # with a trace: the shares from the kernel's seconds
    shapes = {"model": M.config(CONFIG)["model"], "page_len": 128, "decode_steps_traced": 10, "decode_rows_traced": 1500,
              "decode_pages_traced": 19500}
    rec = {**bare, "shapes": shapes, "trace": {"kernels": {
        "kda_decode": {"calls": 30, "seconds": 0.06, "out_elems": 0},
        "flash_decode_paged": {"calls": 10, "seconds": 0.05, "out_elems": 0}}},
        "programs": {"modules": {"/device:TPU:0": [["jit_serve_decode", 0, 20_000_000]] * 10 + [["jit_serve_prefill", 0, 9_000_000]]},
                     "spans": []}}
    assert M.module("metrics", "kda_decode_roofline").read(rec) == pytest.approx(100 * (30 * 150 * (2 * 4_194_304 + 64 * 641 * 4) / 819e9) / 0.06)
    assert 0 < M.module("metrics", "gqa_decode_paged_roofline").read(rec) < 100
    assert M.module("metrics", "linear_state_share_pct").read(rec) == pytest.approx(100 * 0.06 / 0.2)


def _toy(root):
    def write(path, obj):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)

    write(f"{root}/extra/configs/toy-solar2.json", {
        "runner": "serve_solar2", "model": HF,
        "share": {"published": {"n_routed_experts": 16}, "first_expert": 4, "chips_per_layer": 2},
        "serving": {"num_slots": 4, "max_len": 128, "kv_cache_dtype": "model", "prefill_chunk": 16, "max_queue": 1000,
                    "max_new_tokens": 16, "degrade_max_new_tokens": 0,
                    "kvcache": {"enabled": True, "page_len": 16, "num_pages": 33}},
        "checks": {"sample_requests": 2, "pad_multiple": 64, "token_gap_mean_max": 0.05, "state_sample_slots": 2,
                   "state_rel_err_max": 0.1, "state_mantissa_bits_min": 16}})
    write(f"{root}/extra/traffic/toy-backlog.json", {
        "kind": "closed", "clients": 6, "pool": 8, "prompt": {"dist": "uniform", "min": 16, "max": 48},
        "answer": {"dist": "uniform", "min": 3, "max": 8}, "max_total": 128, "preroll_s": 0.5, "ttft_sample_share": 0.0})
    write(f"{root}/BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["extra"], "run_seconds": 1,
        "configs": [{"name": "toy-solar2", "source": "test", "file": "extra/configs/toy-solar2.json",
                     "reduced": ["n_routed_experts"], "why": "toy"}],
        "workloads": [{"name": "toy", "config": "toy-solar2", "traffic": "toy-backlog", "chips": 1, "why": "toy"}],
        "end_to_end": [{"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1, "source": "host_clock"},
                       {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"} for m in M.data["per_layer"] if m["name"] in (
            "compiles_in_window", "kv_alloc_waits", "kv_pages_in_use_pct", "batch_occupancy_pct", "moe_dropped_assignments",
            "kda_decode_roofline", "gqa_decode_paged_roofline", "linear_state_share_pct", "serve_step_ms_p50")]})


def test_runner_serves_a_toy_cell_on_the_cpu_counts_only(tmp_path):
    root = str(tmp_path)
    _toy(root)
    out = harness.run_cell("toy", seed=2 ** 31 + 11, seconds=2.0, trace=True, t_start=time.perf_counter(),
                           manifest_path=f"{root}/BENCHMARK.json", require_tpu=False, scratch=f"{root}/scratch")
    res, rec = out["result"], out["record"]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    # the recurrent state read off the timed engine where the window closed is one of the judged numbers
    judged = {c["name"]: c for c in rec["checks"]}
    assert set(judged) == {"served_sample", "token_gap_mean", "state_rel_err", "state_mantissa_bits", "moe_dropped_assignments"}
    assert judged["state_mantissa_bits"]["value"] >= 22  # a float32 recurrence
    assert 0.0 < judged["state_rel_err"]["value"] < judged["state_rel_err"]["limit"] == 0.1
    assert res["metrics"]["moe_dropped_assignments"]["value"] == 0 and res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["kv_alloc_waits"]["value"] == 0
    # no device number from the CPU
    assert not {"kda_decode_roofline", "gqa_decode_paged_roofline", "linear_state_share_pct", "serve_step_ms_p50"} & set(res["metrics"])
    hy, moe = rec["counters"]["hybrid"], rec["counters"]["moe"]
    assert hy["state_bytes"] > 0 and hy["state_resets_in_program"] >= res["attempted"] and 0 < hy["decode_rows_updated_mean"] <= 4
    assert len(moe["tokens_per_expert"]) == 8 and len(moe["tokens_per_expert"][0]) == 8
    assert moe["assignments_computed"] == moe["assignments_routed_held"] > 0 and rec["window"]["tokens"] > 0
    assert sum(rec["window"]["tokens_by_sixth"]) == rec["window"]["tokens"]
    assert {"timeline", "kv_pages_live", "kv_num_pages", "num_slots"} <= set(rec["counters"])
    stats = rec["counters"]["engine_stats"]
    assert stats["kda_prefill_form"].startswith("chunked") and stats["kda_decode_fallback"] and stats["gqa_decode_fallback"]
    assert rec["shapes"]["model"]["n_routed_experts"] == 16 and rec["shapes"]["model"]["experts_held"] == [4, 8]


def test_control_tool_reads_the_program_and_both_controls(tmp_path):
    """``control_solar_open2.py``, the tool the cell's limits were read
    with on the chip, rehearsed at toy size."""
    import subprocess
    import sys

    root = str(tmp_path)
    _toy(root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_CONTROL_ALLOW_CPU": "1"}
    cmd = [sys.executable, "benchmark/control_solar_open2.py", "--workload", "toy", "--seeds", "1", "--control-seeds", "1",
           "--requests", "2", "--out", f"{root}/control.json", "--manifest", f"{root}/BENCHMARK.json"]
    p = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    row = json.loads(p.stdout.strip().splitlines()[-1])["rows"][0]
    # at this size and a dozen tokens the three need not separate (they do at the cell's size: PERF.md section 2);
    # what is rehearsed is that the tool reads all of them
    assert 0.0 <= row["program"]["token_gap_mean"] <= row["program"]["token_gap_max"] < 0.05
    assert row["control_int8"]["tokens"] == row["control_bf16_state"]["tokens"] == row["program"]["tokens"] >= 6
    assert row["control_bf16_state"]["token_gap_mean"] >= 0.0 and row["control_bf16_state"]["tokens_differ"] >= 0
    # every variant goes through the runner's own checks; the state is read, per slot and linear-attention layer
    for who in ("program", "control_bf16_state", "control_int8"):
        names = [c["name"] for c in row[who]["checks"]]
        assert names == ["served_sample", "token_gap_mean", "state_rel_err", "state_mantissa_bits", "moe_dropped_assignments"]
        assert row[who]["correct"] == all(c["ok"] for c in row[who]["checks"])
        assert len(row[who]["by_slot_and_layer"]) == 2 and len(row[who]["by_slot_and_layer"][0]) == 6
    # the program is correct; a state held in bfloat16 is refused by the bits it carries, whatever its distance
    assert row["program"]["correct"] is True and row["program"]["state_mantissa_bits"] >= 22
    low = {c["name"]: c["ok"] for c in row["control_bf16_state"]["checks"]}
    assert row["control_bf16_state"]["correct"] is False and low["state_mantissa_bits"] is False
    assert row["control_bf16_state"]["state_mantissa_bits"] <= 7
    assert row["moe"]["dropped_assignments"] == 0
    env.pop("BENCH_CONTROL_ALLOW_CPU")
    q = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=600)
    assert q.returncode != 0 and q.stdout.strip() == ""


def test_mantissa_bits_tell_a_float32_state_from_one_held_in_fewer():
    import jax.numpy as jnp
    import numpy as np

    from benchmark.runners.serve_solar2 import mantissa_bits

    x = np.random.default_rng(0).standard_normal((3, 4, 16, 16)).astype(np.float32)
    assert 22 <= mantissa_bits(x) <= 23
    assert mantissa_bits(np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))) <= 7
    assert mantissa_bits(x.astype(np.float16).astype(np.float32)) <= 10
    assert mantissa_bits(np.zeros((4, 4), np.float32)) == 0.0 and mantissa_bits(np.full((4,), 0.5, np.float32)) == 0.0
