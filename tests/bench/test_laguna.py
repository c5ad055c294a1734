"""The Laguna cell's benchmark files: the configuration (published widths,
the stated cut, every assumed form with its other reading), the traffic
mix, the new kernel's work function on hand-worked shapes, the new
readers, the runner at toy size on the CPU (counts only) and the control
tool rehearsed there.  Everything is asked for **by name**: a later PR
appends cells, configurations and metrics after these, and its cell to
these lists."""
import json
import os
import time

import pytest

from benchmark import harness, traffic
from benchmark.manifest import Manifest

M = Manifest()
CELL, CONFIG, TRAFFIC = "serve-laguna-mixedlen-backlog", "laguna-s21-serve-ep8stage", "mixedlen-agent-backlog"
NEW = ("swa_decode_paged_roofline", "swa_decode_share_pct", "full_decode_share_pct", "swa_chunk_share_pct", "kv_window_bytes_pct")
LAYER = "model (window attention)"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FULL, SLIDING = "full_attention", "sliding_attention"
HF = {"model_type": "laguna", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 5,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "max_position_embeddings": 4096, "attention_bias": False,
      "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
      "shared_expert_intermediate_size": 32, "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
      "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 8,
      "rope_parameters": {FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 8, "original_max_position_embeddings": 64,
                                 "beta_slow": 1, "beta_fast": 32, "attention_factor": 1.2, "partial_rotary_factor": 0.5},
                          SLIDING: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
      "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL], "moe_apply_router_weight_on_input": False,
      "mlp_layer_types": ["dense"] + ["sparse"] * 4, "gating_types": ["per_head"] * 5, "moe_routed_scaling_factor": 2.5,
      "num_attention_heads_per_layer": [4, 6, 6, 6, 4], "moe_router_logit_softcapping": 0}
REDUCED = ["gating_types", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer", "num_experts", "num_hidden_layers", "vocab_size"]


def test_configuration_has_the_published_widths_and_states_its_cut():
    cfg = M.config(CONFIG)
    entry = next(c for c in M.data["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == REDUCED and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    if os.path.exists(CATALOG):  # every key of the catalog row's config under the same name; what differs is what `reduced` names
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Laguna-S-2.1")
        pub = row["config"]
        assert row["source_url"] == cfg["source"] and set(pub) <= set(cfg)
        assert sorted(k for k in pub if cfg[k] != pub[k]) == REDUCED
        assert cfg["model"] == {k: cfg[k] for k in pub}  # top level == model
        for k in ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer"):
            assert cfg[k] == pub[k][:12]  # the lists cut to the layers run, nothing else changed
    # no width is cut
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"], cfg["num_attention_heads"], cfg["sliding_window"]) == (3072, 128, 8, 48, 512)
    assert (cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"], cfg["intermediate_size"]) == (1024, 1024, 12288)
    assert (cfg["num_experts_per_tok"], cfg["moe_routed_scaling_factor"], cfg["norm_topk_prob"]) == (10, 2.5, True)
    assert cfg["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 3 and cfg["layer_types"] == [FULL, SLIDING, SLIDING, SLIDING] * 3
    rp = cfg["rope_parameters"]
    assert (rp[FULL]["rope_theta"], rp[FULL]["factor"], rp[FULL]["original_max_position_embeddings"], rp[FULL]["beta_fast"], rp[FULL]["beta_slow"]) == (500000, 128, 8192, 32, 1)
    assert abs(rp[FULL]["attention_factor"] - 1.4852030263919618) < 1e-12 and rp[FULL]["partial_rotary_factor"] == 0.5
    assert rp[SLIDING] == {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (12, 32, 12544)
    share = cfg["share"]
    assert (share["published"]["num_hidden_layers"], share["published"]["num_experts"], share["published"]["vocab_size"]) == (48, 256, 100352)
    assert share["chips_per_layer"] == 8 and share["first_expert"] == 0 and "4 pipeline stages" in share["deployment"] and "8.65 GB" in share["arithmetic"]
    assert {"gate", "router", "shared_expert", "attention", "weights", "decoding", "experts_held"} <= set(cfg["assumed"])
    for form in ("gate", "router", "shared_expert", "attention"):
        assert "other reading" in cfg["assumed"][form].lower()
    s = cfg["serving"]
    assert (s["num_slots"], s["max_len"], s["prefill_chunk"], s["prefill_chunks_per_step"], s["kv_cache_dtype"]) == (24, 21504, 1024, 1, "model")
    assert s["kvcache"]["enabled"] is True and s["kvcache"]["page_len"] == 128 and s.get("overlap_chunks", True) is True  # no key turns the serial step on
    assert "float32 router" in cfg["precision"] and cfg["runner"] == "serve_laguna"
    lim = cfg["checks"]
    assert {"token_gap_mean_max", "router_overlap_mean_min", "router_logit_mantissa_bits_min", "read_on_chip"} <= set(lim)
    assert lim["max_context"] == lim["pad_multiple"] == 8192 and lim["sample_requests"] >= 2
    # the program reads the file: the family's config, its share, its cache kind
    from benchmark import build_laguna as build

    mcfg = build.model_config(cfg)
    assert (mcfg.num_hidden_layers, mcfg.num_experts, mcfg.held, mcfg.vocab_rows) == (12, 256, (0, 32), 12544)
    assert mcfg.full_layers == (0, 4, 8) and len(mcfg.sliding_layers) == 9


def test_the_cell_is_the_issues_and_is_asked_for_by_name():
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1) and len(cell["why"]) <= 200
    assert "0.94 tokens an expert" in cell["why"] and "24 slots" in cell["why"] and "ring of 9 window layers" in cell["why"]
    assert [w["name"] for w in M.data["workloads"]].count(CELL) == 1 and [c["name"] for c in M.data["configs"]].count(CONFIG) == 1
    for m in map(M.metric_entry, NEW):
        assert m in M.data["per_layer"] and m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" and m["unit"] == "%"
    assert M.metric_entry("swa_decode_paged_roofline")["layer"] == "kernels" and M.metric_entry("swa_decode_paged_roofline")["better"] == "higher"
    assert {M.metric_entry(n)["layer"] for n in NEW[1:4]} == {LAYER} and {M.metric_entry(n)["source"] for n in NEW[:4]} == {"device_trace"}
    assert M.metric_entry("kv_window_bytes_pct")["source"] == "program_counter"
    assert {m["name"] for m in M.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in M.per_layer(CELL)}
    assert {*NEW, "gqa_decode_paged_roofline", "moe_dropped_assignments", "moe_expert_load_max_over_mean", "serve_dispatch_ms_p50",
            "serve_note_ms_p50", "serve_commit_ms_p50", "serve_sweep_ms_p50", "serve_stall_steps", "serve_stall_ms",
            "serve_step_ms_p50", "kv_alloc_waits", "batch_occupancy_pct", "serve_hbm_peak_gb", "kv_pages_in_use_pct"} <= names
    # the readers that look for another family's kernel or key set do not list the cell
    assert not {"kda_decode_roofline", "linear_state_share_pct", "mla_decode_paged_roofline", "flash_decode_paged_roofline",
                "gdn_decode_roofline", "dsa_sparse_decode_roofline"} & names
    assert M.find("runners", "serve_laguna", ".py") and M.find("kernels", "swa_decode_paged", ".py") and M.find("traffic", TRAFFIC, ".json")
    for name in NEW:
        assert M.find("metrics", name, ".py")


def test_traffic_file_is_the_mixed_length_agent_backlog():
    mix = M.traffic(TRAFFIC)
    assert (mix["kind"], mix["clients"], mix["pool"], mix["max_total"], mix["preroll_s"], mix["ttft_sample_share"]) == \
        ("closed", 32, 48, 21504, 30, 0.0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 6144, "sigma": 0.7, "min": 512, "max": 20480}
    assert mix["answer"] == {"dist": "lognormal", "median": 384, "sigma": 0.5, "min": 128, "max": 1024}
    pool = traffic.length_pool(mix)
    assert len(pool) == 48 and all(512 <= p <= 20480 and 128 <= a <= 1024 and p + a <= 21504 for p, a in pool)
    prompts, answers = sorted(p for p, _ in pool), sorted(a for _, a in pool)
    assert 5900 <= prompts[24] <= 6400 and 370 <= answers[24] <= 400  # the medians
    assert max(prompts) == 20480 and min(prompts) > 1024  # short and long in one queue
    # by the lengths: ~7.8 chunks of 1,024 and ~430 decode steps a request, ~63 pages a request: 24 slots' worth fits the 2,304 pages
    assert 7.3 < sum(-(-p // 1024) for p in prompts) / 48 < 8.2 and 400 < sum(answers) / 48 < 460
    pages = [-(-(p + a) // 128) for p, a in pool]
    assert 58 < sum(pages) / 48 < 68 and 24 * sum(pages) / 48 < 2304
    # a served sample the reference is asked for (contexts to 8,192) whose ring has lapped exists in every cycle
    assert sum(1 for p, a in pool if 512 + 1024 < p + a <= 8192) >= 24


def test_swa_decode_paged_work_counts_the_windows_positions_and_not_the_pages_read():
    work = M.module("kernels", "swa_decode_paged").work
    shapes = {"model": M.config(CONFIG)["model"], "page_len": 128, "window": 512, "window_heads": 72, "decode_steps_traced": 10,
              "decode_rows_traced": 240, "decode_pages_traced": 14000, "decode_window_positions_traced": 240 * 512 - 10 * 112}
    w = work(shapes, calls=90, out_elems=0)
    positions = (240 * 512 - 1120) / 10
    assert w["bytes"] == pytest.approx(90 * (positions * 8 * 128 * 2 * 2 + 24 * 72 * 128 * 2 * 2))
    assert w["flops"] == pytest.approx(90 * 4 * 72 * 128 * positions)
    assert w["flops"] / w["bytes"] < 10  # 9 FLOP a cached byte: under the ridge, the bytes bound
    # the full layers' calls are counted by the accepted work function, from the full layers' 48 heads and the filled pages
    g = M.module("kernels", "gqa_decode_paged").work(shapes, calls=30, out_elems=0)
    assert g["flops"] == pytest.approx(30 * 4 * 48 * 128 * 1400 * 128)


def test_new_readers_return_nothing_where_the_program_reports_nothing():
    bare = {"counters": {}, "trace": {"kernels": {}}, "shapes": {}, "device": {"kind": "TPU v5 lite"}, "manifest": M, "programs": None,
            "scopes": None}
    for name in NEW:
        assert M.module("metrics", name).read(bare) is None
        assert M.module("metrics", name).read({**bare, "trace": None}) is None
    # another family's run: flash_decode_paged in the trace and no window group: none of the five speaks
    other = {**bare, "trace": {"kernels": {"flash_decode_paged": {"calls": 30, "seconds": 0.06, "out_elems": 0}}},
             "programs": {"modules": {"/device:TPU:0": [["jit_serve_decode", 0, 20_000_000]]}, "spans": []},
             "shapes": {"model": {"num_attention_heads": 64, "num_key_value_heads": 8, "head_dim": 128}, "page_len": 128,
                        "decode_steps_traced": 1, "decode_rows_traced": 9, "decode_pages_traced": 90},
             "counters": {"kv_groups": None, "kv_cache_bytes": 10}}
    assert all(M.module("metrics", name).read(other) is None for name in NEW)
    shapes = {"model": M.config(CONFIG)["model"], "page_len": 128, "window": 512, "window_heads": 72, "decode_steps_traced": 10,
              "decode_rows_traced": 240, "decode_pages_traced": 14000, "decode_window_positions_traced": 240 * 512}
    rec = {**bare, "shapes": shapes,
           "trace": {"kernels": {"swa_decode_paged": {"calls": 90, "seconds": 0.012, "out_elems": 0},
                                 "flash_decode_paged": {"calls": 30, "seconds": 0.03, "out_elems": 0}}},
           "programs": {"modules": {"/device:TPU:0": [["jit_serve_decode", 0, 15_000_000]] * 10 + [["jit_serve_prefill", 0, 50_000_000]]},
                        "spans": []},
           "scopes": {"ops": {"/device:TPU:0": [["fusion.1", 10, 30], ["fusion.2", 50, 20], ["fusion.3", 200, 40]]},
                      "modules": {"/device:TPU:0": [["jit_serve_prefill", 0, 100], ["jit_serve_decode", 150, 100]]},
                      "scoped_ops": {"jit_serve_prefill": {"fusion.1": ["swa.chunk"], "fusion.2": ["moe.router"]},
                                     "jit_serve_decode": {"fusion.3": ["moe.router"]}}},
           "counters": {"kv_groups": {"full": {"bytes": 3_630_000_000}, "window": {"bytes": 570_000_000}}, "kv_cache_bytes": 4_200_000_000}}
    per_call = 24 * 512 * 8 * 128 * 4 + 24 * 72 * 128 * 4
    assert M.module("metrics", "swa_decode_paged_roofline").read(rec) == pytest.approx(100 * (90 * per_call / 819e9) / 0.012)
    assert M.module("metrics", "swa_decode_paged_roofline").read(rec) < 100
    assert M.module("metrics", "swa_decode_share_pct").read(rec) == pytest.approx(100 * 0.012 / 0.15)
    assert M.module("metrics", "full_decode_share_pct").read(rec) == pytest.approx(100 * 0.03 / 0.15)
    assert M.module("metrics", "swa_chunk_share_pct").read(rec) == pytest.approx(30.0)  # 30 of the prefill execution's 100 ns
    assert M.module("metrics", "kv_window_bytes_pct").read(rec) == pytest.approx(100 * 0.57 / 4.2)
    assert M.module("metrics", "gqa_decode_paged_roofline").read(rec) < 100


def _toy(root):
    def write(path, obj):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)

    write(f"{root}/extra/configs/toy-laguna.json", {
        "runner": "serve_laguna", "model": HF,
        "share": {"published": {"num_experts": 16}, "first_expert": 4, "chips_per_layer": 2},
        "serving": {"num_slots": 4, "max_len": 128, "kv_cache_dtype": "model", "prefill_chunk": 16, "max_queue": 1000,
                    "max_new_tokens": 16, "degrade_max_new_tokens": 0,
                    "kvcache": {"enabled": True, "page_len": 4, "num_pages": 129}},
        "checks": {"sample_requests": 2, "pad_multiple": 64, "max_context": 128, "routing_sample_slots": 2, "token_gap_mean_max": 0.05,
                   "router_overlap_mean_min": 0.8, "router_logit_mantissa_bits_min": 16}})
    write(f"{root}/extra/traffic/toy-backlog.json", {
        "kind": "closed", "clients": 6, "pool": 8, "prompt": {"dist": "uniform", "min": 26, "max": 60},
        "answer": {"dist": "uniform", "min": 6, "max": 10}, "max_total": 128, "preroll_s": 0.5, "ttft_sample_share": 0.0})
    write(f"{root}/BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["extra"], "run_seconds": 1,
        "configs": [{"name": "toy-laguna", "source": "test", "file": "extra/configs/toy-laguna.json", "reduced": ["num_experts"], "why": "toy"}],
        "workloads": [{"name": "toy", "config": "toy-laguna", "traffic": "toy-backlog", "chips": 1, "why": "toy"}],
        "end_to_end": [{"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1, "source": "host_clock"},
                       {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"} for m in M.data["per_layer"] if m["name"] in (
            "compiles_in_window", "kv_alloc_waits", "kv_pages_in_use_pct", "batch_occupancy_pct", "moe_dropped_assignments",
            "gqa_decode_paged_roofline", "serve_step_ms_p50", *NEW)]})


def test_runner_serves_a_toy_cell_on_the_cpu_counts_only(tmp_path):
    root = str(tmp_path)
    _toy(root)
    out = harness.run_cell("toy", seed=2 ** 31 + 11, seconds=2.0, trace=True, t_start=time.perf_counter(),
                           manifest_path=f"{root}/BENCHMARK.json", require_tpu=False, scratch=f"{root}/scratch")
    res, rec = out["result"], out["record"]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    judged = {c["name"]: c for c in rec["checks"]}
    assert list(judged) == ["served_sample", "wrapped_contexts", "token_gap_mean", "router_overlap_mean", "router_logit_mantissa_bits",
                            "moe_dropped_assignments"]
    assert judged["wrapped_contexts"]["value"] >= 1  # a sampled context past window + chunk = 24: its ring has lapped
    assert judged["router_logit_mantissa_bits"]["value"] >= 21 and judged["router_overlap_mean"]["value"] > 0.8  # float32 routers
    assert res["metrics"]["moe_dropped_assignments"]["value"] == 0 and res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["kv_alloc_waits"]["value"] == 0
    # the one new metric that is a count is read on the CPU too; no device number from the CPU
    g = rec["counters"]["kv_groups"]
    assert res["metrics"]["kv_window_bytes_pct"]["value"] == pytest.approx(100 * g["window"]["bytes"] / rec["counters"]["kv_cache_bytes"])
    assert not {*NEW[:4], "gqa_decode_paged_roofline", "serve_step_ms_p50"} & set(res["metrics"])
    assert (g["full"]["layers"], g["window"]["layers"], g["window"]["pages_per_slot"], g["window"]["positions_per_slot"]) == (2, 3, 3, 12)
    assert g["window"]["bytes"] == 2 * 3 * (1 + 4 * 3) * 2 * 4 * 16 * 2 and g["full"]["bytes"] == 2 * 2 * 129 * 2 * 4 * 16 * 2
    c = rec["counters"]
    moe = c["moe"]
    assert len(moe["tokens_per_expert"]) == 4 and len(moe["tokens_per_expert"][0]) == 8  # four sparse layers of the five
    assert moe["assignments_computed"] == moe["assignments_routed_held"] > 0 and rec["window"]["tokens"] > 0
    stats = c["engine_stats"]
    assert stats["swa_decode_form"].startswith("jnp over the ring") and stats["swa_chunk_form"].startswith("banded jnp")
    assert stats["swa_ring_positions"] == 12 and stats["moe_router_form"].startswith("softmax_topk") and "x 2.5" in stats["moe_router_form"]
    sh = rec["shapes"]
    assert sh["model"]["num_experts"] == 16 and sh["model"]["experts_held"] == [4, 8] and (sh["window"], sh["window_heads"]) == (8, 6)
    assert {"decode_rows_traced", "decode_pages_traced", "decode_steps_traced", "decode_window_positions_traced", "page_len"} <= set(sh)
    assert sh["decode_window_positions_traced"] <= 8 * sh["decode_rows_traced"]  # min(fill, window) a row
    # a traced run keeps the scoped operations of both programs beside its trace
    kept = json.load(open(os.path.join(f"{root}/scratch", "trace", "toy", "scoped_ops.json")))
    assert set(kept) == {"jit_serve_prefill", "jit_serve_decode"}
    assert "swa.chunk" in {s for v in kept["jit_serve_prefill"].values() for s in v}
    assert "swa.chunk" not in {s for v in kept["jit_serve_decode"].values() for s in v}
    assert "moe.router" in {s for v in kept["jit_serve_decode"].values() for s in v}


def test_control_tool_reads_the_program_and_all_four_controls(tmp_path):
    """``control_laguna.py``, the tool the cell's limits were read with on the chip, rehearsed at toy size."""
    import subprocess
    import sys

    root = str(tmp_path)
    _toy(root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_CONTROL_ALLOW_CPU": "1"}
    cmd = [sys.executable, "benchmark/control_laguna.py", "--workload", "toy", "--seeds", "1", "--control-seeds", "1",
           "--requests", "2", "--out", f"{root}/control.json", "--manifest", f"{root}/BENCHMARK.json"]
    p = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    row = json.loads(p.stdout.strip().splitlines()[-1])["rows"][0]
    names = ["served_sample", "wrapped_contexts", "token_gap_mean", "router_overlap_mean", "router_logit_mantissa_bits", "moe_dropped_assignments"]
    controls = ("control_window_as_full", "control_no_gate", "control_48_of_72_heads", "control_bf16_router")
    for who in ("program", *controls):
        assert [c["name"] for c in row[who]["checks"]] == names
        assert row[who]["correct"] == all(c["ok"] for c in row[who]["checks"])
        assert row[who]["tokens"] == row["program"]["tokens"] >= 6
    assert row["program"]["correct"] is True and row["program"]["router_logit_mantissa_bits"] >= 21
    # the bf16 router is refused by the bits its logits carry, through the runner's own judged()
    low = {c["name"]: c["ok"] for c in row["control_bf16_router"]["checks"]}
    assert row["control_bf16_router"]["correct"] is False and low["router_logit_mantissa_bits"] is False
    assert row["control_bf16_router"]["router_logit_mantissa_bits"] <= 7
    # the three attention controls move the emitted tokens' gap past the program's (by how much at the cell's size, and the limit
    # that refuses them, are read on the chip: at 64 wide a window of 8 in contexts of 60 moves a logit by thousandths)
    for who in controls[:3]:
        assert row[who]["token_gap_mean"] > row["program"]["token_gap_mean"] + 1e-3, who
    assert row["moe"]["dropped_assignments"] == 0 and row["forms"]["groups"]["window"]["pages_per_slot"] == 3
    env.pop("BENCH_CONTROL_ALLOW_CPU")
    q = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=600)
    assert q.returncode != 0 and q.stdout.strip() == ""
