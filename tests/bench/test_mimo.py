"""The MiMo-V2-Flash cell's benchmark files: the configuration (the catalog
row outside ``reduced``, the stated cut, every assumed form with its other
reading), the traffic mix and its pool, the two work functions on
hand-counted shapes, the new readers, and the runner at toy size on the
CPU (counts only).  Everything is asked for **by name**, never by place
or count: a later PR appends cells, configurations and metrics after
these, and its cell to these lists."""
import json
import os
import time

import pytest

from benchmark import harness, traffic
from benchmark.manifest import Manifest

M = Manifest()
CELL, CONFIG, TRAFFIC = "serve-mimo-longctx-agent-backlog", "mimo-v2-flash-serve-ep16stage", "longctx-agent-backlog"
NEW = ("swa_sink_decode_paged_roofline", "asym_gqa_decode_paged_roofline", "full_chunk_share_pct")
JOINED = ("serve_tokens_per_s", "moe_expert_load_max_over_mean", "moe_dropped_assignments", "swa_decode_share_pct", "full_decode_share_pct",
          "swa_chunk_share_pct", "kv_window_bytes_pct", "serve_launch_ms_p50", "serve_readback_ms_p50", "serve_note_ms_p50",
          "serve_commit_ms_p50", "serve_sweep_ms_p50", "serve_dispatch_ms_p50", "serve_idle_between_steps_pct",
          "serve_idle_unattributed_pct", "serve_stall_steps", "serve_stall_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts", "num_hidden_layers", "vocab_size"]
HF = {"model_type": "mimo_v2_flash", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 4,
      "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 24, "v_head_dim": 16, "swa_num_attention_heads": 8,
      "swa_num_key_value_heads": 4, "swa_head_dim": 24, "swa_v_head_dim": 16, "sliding_window": 6, "sliding_window_size": 6,
      "attention_chunk_size": 6, "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1], "rope_theta": 5000000,
      "swa_rope_theta": 10000, "partial_rotary_factor": 0.334, "attention_value_scale": 0.707, "attention_bias": False,
      "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False, "layernorm_epsilon": 1e-5, "hidden_act": "silu",
      "moe_intermediate_size": 32, "n_routed_experts": 8, "n_shared_experts": None, "num_experts_per_tok": 4, "norm_topk_prob": True,
      "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc", "routed_scaling_factor": None,
      "tie_word_embeddings": False, "max_position_embeddings": 4096}


def test_configuration_is_the_catalog_row_outside_reduced_and_states_its_cut():
    cfg = M.config(CONFIG)
    entry = next(c for c in M.data["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] == "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json"
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == REDUCED and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["why"].startswith("SWA 128 with learned sinks (8 KV heads) 5:1 with full GQA (4 KV heads), qk 192 / v 128") and len(entry["why"]) <= 200
    if os.path.exists(CATALOG):  # every key of the catalog row's config under the same name; what differs is what `reduced` names
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "MiMo-V2-Flash")
        pub = row["config"]
        assert row["source_url"] == cfg["source"] and set(pub) <= set(cfg)
        assert sorted(k for k in pub if cfg[k] != pub[k]) == REDUCED
        assert cfg["model"] == {k: cfg[k] for k in pub}  # top level == model
        for k in ("hybrid_layer_pattern", "moe_layer_freq"):
            assert cfg[k] == pub[k][:8]  # the lists cut to the layers run, nothing else changed
    # no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"]) == (4096, 64, 4, 8)
    assert (cfg["head_dim"], cfg["v_head_dim"], cfg["swa_head_dim"], cfg["swa_v_head_dim"], cfg["sliding_window"]) == (192, 128, 192, 128, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]) == (16384, 2048, 8)
    assert (cfg["rope_theta"], cfg["swa_rope_theta"], cfg["partial_rotary_factor"], cfg["attention_value_scale"]) == (5000000, 10000, 0.334, 0.707)
    assert cfg["add_swa_attention_sink_bias"] is True and cfg["add_full_attention_sink_bias"] is False and cfg["scoring_func"] == "sigmoid"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (8, 16, 19072)
    assert cfg["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1, 1] and cfg["moe_layer_freq"] == [0] + [1] * 7
    share = cfg["share"]
    assert (share["published"]["num_hidden_layers"], share["published"]["n_routed_experts"], share["published"]["vocab_size"]) == (48, 256, 152576)
    assert share["chips_per_layer"] == 16 and share["first_expert"] == 0 and "6 pipeline stages" in share["deployment"]
    assert "7.86 GB" in share["arithmetic"] and "5,120 B" in share["arithmetic"] and "30,720 B" in share["arithmetic"] and "3 : 1" in share["deployment"]
    assert {"sink", "window", "rotary", "value_scale", "router", "norms", "mtp", "weights", "decoding", "experts_held"} <= set(cfg["assumed"])
    for form in ("sink", "window", "rotary", "value_scale", "router", "norms"):
        assert "other reading" in cfg["assumed"][form].lower()
    s = cfg["serving"]
    assert (s["num_slots"], s["max_len"], s["prefill_chunk"], s["prefill_chunks_per_step"], s["max_new_tokens"], s["kv_cache_dtype"]) == \
        (32, 69632, 1024, 1, 4096, "model")
    assert s["kvcache"] == {"enabled": True, "page_len": 128, "num_pages": 7169, "session_ttl_seconds": 0.0}
    assert "float32 router" in cfg["precision"] and cfg["runner"] == "serve_mimo"
    lim = cfg["checks"]
    assert {"token_gap_mean_max", "router_overlap_mean_min", "router_logit_mantissa_bits_min", "window_edge_margin_min", "read_on_chip"} <= set(lim)
    assert lim["max_context"] == lim["pad_multiple"] == 8192 and lim["wrapped_past"] == 4096 and lim["sample_requests"] >= 2
    # the program reads the file: the family's config, its share, its cache kind — and the pool's bytes are the issue's, to the page
    from benchmark import build_mimo as build

    mcfg = build.model_config(cfg)
    assert (mcfg.num_hidden_layers, mcfg.n_routed_experts, mcfg.held, mcfg.vocab_rows) == (8, 256, (0, 16), 19072)
    assert mcfg.full_layers == (0, 5) and mcfg.window_layers == (1, 2, 3, 4, 6, 7)
    from deepspeed_tpu.models import mimo_v2

    kind = mimo_v2.cache_kind(mcfg, "bfloat16")
    full_bytes = 7169 * 128 * kind.paged_layers * kind.pages.position_bytes()
    window_bytes = (1 + 32 * kind.ring_pages(128)) * 128 * kind.window_layers * kind.window_pages.position_bytes()
    assert (full_bytes, window_bytes) == (7169 * 128 * 5120, 65 * 128 * 30720)  # 4.70 GB + 0.26 GB, K 192 and V 128 wide, unpadded
    assert round(100 * window_bytes / (full_bytes + window_bytes), 1) == 5.2


def test_the_cell_is_the_issues_and_its_metrics_are_asked_for_by_name():
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert cell["why"] == ("closed loop, 40 clients over 32 slots; prompts 3k-64k (median 16k), answers 256-4k; 2 full layers' pages to 68k "
                           "beside 6 sink-biased 128-windows on rings; 1 token an expert a step, attention at 16x")
    assert [w["name"] for w in M.data["workloads"]].count(CELL) == 1 and [c["name"] for c in M.data["configs"]].count(CONFIG) == 1
    assert [w["name"] for w in M.data["workloads"] if w["config"] == CONFIG] == [CELL]  # no second cell
    for m in map(M.metric_entry, NEW):
        assert m in M.data["per_layer"] and m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" and m["unit"] == "%"
        assert m["source"] == "device_trace"
    assert {M.metric_entry(n)["layer"] for n in NEW[:2]} == {"kernels"} and {M.metric_entry(n)["better"] for n in NEW[:2]} == {"higher"}
    assert M.metric_entry("full_chunk_share_pct")["layer"] == "model (window attention)"
    for name in JOINED:  # the accepted metrics the cell joins: appended to their lists
        assert CELL in M.metric_entry(name)["workloads"], name
    for name in ("swa_decode_paged_roofline", "gqa_decode_paged_roofline"):  # they count one width and one KV head count
        assert CELL not in M.metric_entry(name)["workloads"]
    assert {m["name"] for m in M.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in M.per_layer(CELL)}
    assert {*NEW, *JOINED[1:], "serve_step_ms_p50", "kv_alloc_waits", "batch_occupancy_pct", "serve_hbm_peak_gb", "kv_pages_in_use_pct",
            "compiles_in_window", "serve_device_idle_pct"} <= names
    assert not {"kda_decode_roofline", "mla_decode_paged_roofline", "flash_decode_paged_roofline", "gdn_decode_roofline",
                "dsa_sparse_decode_roofline", "swa_decode_paged_roofline", "gqa_decode_paged_roofline"} & names
    assert M.find("runners", "serve_mimo", ".py") and M.find("traffic", TRAFFIC, ".json")
    for name in ("swa_sink_decode_paged", "asym_gqa_decode_paged"):
        assert M.find("kernels", name, ".py")
    for name in NEW:
        assert M.find("metrics", name, ".py")


def test_traffic_file_is_the_long_context_agent_backlog_and_its_pool_fits_the_pages():
    mix = M.traffic(TRAFFIC)
    assert (mix["kind"], mix["clients"], mix["pool"], mix["max_total"], mix["preroll_s"], mix["ttft_sample_share"]) == \
        ("closed", 40, 48, 69632, 60, 0.0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 16384, "sigma": 0.7, "min": 2048, "max": 65536}
    assert mix["answer"] == {"dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256, "max": 4096}
    pool = traffic.length_pool(mix)
    prompts, answers = sorted(p for p, _ in pool), sorted(a for _, a in pool)
    assert len(pool) == 48 and (prompts[0], prompts[-1], answers[0], answers[-1]) == (3250, 65536, 256, 4096)
    assert round(sum(prompts) / 48) == 20388 and round(sum(answers) / 48) == 1218
    pages = sorted(-(-(p + a) // 128) for p, a in pool)
    assert round(sum(pages) / 48) == 169 and pages[-1] == 516 and sum(pages[-32:]) == 6942 <= 7168  # kv_alloc_waits is 0 by construction
    # contexts the reference is asked for (to 8,192) exist in every cycle, all but one past 4,096: the ring lapped many times over
    fits = [p + a for p, a in pool if p + a <= 8192]
    assert len(fits) == 5 and sum(1 for c in fits if c > 4096) == 4


def test_the_two_work_functions_count_each_width_and_each_groups_kv_heads():
    model = M.config(CONFIG)["model"]
    shapes = {"model": model, "page_len": 128, "window": 128, "window_heads": 64, "decode_steps_traced": 10, "decode_rows_traced": 320,
              "decode_pages_traced": 51200, "decode_window_positions_traced": 320 * 128 - 10 * 28}
    w = M.module("kernels", "swa_sink_decode_paged").work(shapes, calls=60, out_elems=0)
    positions = (320 * 128 - 280) / 10
    assert w["bytes"] == pytest.approx(60 * (positions * 8 * (192 + 128) * 2 + 32 * 64 * (192 + 128) * 2))  # 5,120 B a window position
    assert w["flops"] == pytest.approx(60 * 2 * 64 * 320 * positions)
    g = M.module("kernels", "asym_gqa_decode_paged").work(shapes, calls=20, out_elems=0)
    assert g["bytes"] == pytest.approx(20 * (5120 * 128 * 4 * 320 * 2 + 32 * 64 * 320 * 2))  # 2,560 B a position a layer
    assert g["flops"] == pytest.approx(20 * 2 * 64 * 320 * 5120 * 128)
    assert w["flops"] / w["bytes"] < 10 and g["flops"] / g["bytes"] < 17  # 8 and 16 FLOP a cached byte: under the ridge, the bytes bound
    # the accepted work functions would miscount this family: one width for K and V
    old = M.module("kernels", "gqa_decode_paged").work(shapes, calls=20, out_elems=0)
    assert old["bytes"] > 1.19 * g["bytes"]


def test_new_readers_return_nothing_where_the_program_reports_nothing_and_read_the_trace_where_it_does():
    bare = {"counters": {}, "trace": {"kernels": {}}, "shapes": {}, "device": {"kind": "TPU v5 lite"}, "manifest": M, "programs": None,
            "scopes": None}
    for name in NEW:
        assert M.module("metrics", name).read(bare) is None
        assert M.module("metrics", name).read({**bare, "trace": None}) is None
    # another family's run (Laguna's shapes): the kernels are in the trace and the model names no width of its own for V
    other = {**bare, "trace": {"kernels": {"flash_decode_paged": {"calls": 30, "seconds": 0.06, "out_elems": 0},
                                           "swa_decode_paged": {"calls": 90, "seconds": 0.01, "out_elems": 0}}},
             "shapes": {"model": {"num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128}, "page_len": 128,
                        "decode_steps_traced": 1, "decode_rows_traced": 9, "decode_pages_traced": 90, "decode_window_positions_traced": 900}}
    assert all(M.module("metrics", name).read(other) is None for name in NEW[:2])
    shapes = {"model": M.config(CONFIG)["model"], "page_len": 128, "window": 128, "window_heads": 64, "decode_steps_traced": 10,
              "decode_rows_traced": 320, "decode_pages_traced": 51200, "decode_window_positions_traced": 320 * 128}
    rec = {**bare, "shapes": shapes,
           "trace": {"kernels": {"swa_decode_paged": {"calls": 60, "seconds": 0.003, "out_elems": 0},
                                 "flash_decode_paged": {"calls": 20, "seconds": 0.05, "out_elems": 0},
                                 "flash_chunk_paged": {"calls": 4, "seconds": 0.02, "out_elems": 0}}},
           "programs": {"modules": {"/device:TPU:0": [["jit_serve_decode", 0, 15_000_000]] * 10 + [["jit_serve_prefill", 0, 50_000_000]] * 2},
                        "spans": []}}
    swa = 32 * 128 * 8 * 320 * 2 + 32 * 64 * 320 * 2
    assert M.module("metrics", "swa_sink_decode_paged_roofline").read(rec) == pytest.approx(100 * (60 * swa / 819e9) / 0.003)
    full = 5120 * 128 * 4 * 320 * 2 + 32 * 64 * 320 * 2
    assert M.module("metrics", "asym_gqa_decode_paged_roofline").read(rec) == pytest.approx(100 * (20 * full / 819e9) / 0.05)
    assert all(M.module("metrics", n).read(rec) < 100 for n in NEW[:2])
    assert M.module("metrics", "full_chunk_share_pct").read(rec) == pytest.approx(100 * 0.02 / 0.1)
    # where the walk is not the kernel, the scope speaks
    scoped = {**rec, "trace": {"kernels": {}},
              "scopes": {"ops": {"/device:TPU:0": [["fusion.1", 10, 30], ["fusion.2", 50, 20]]},
                         "modules": {"/device:TPU:0": [["jit_serve_prefill", 0, 100]]},
                         "scoped_ops": {"jit_serve_prefill": {"fusion.1": ["swa.chunk"], "fusion.2": ["full.chunk"]}}}}
    assert M.module("metrics", "full_chunk_share_pct").read(scoped) == pytest.approx(20.0)


def _toy(root):
    def write(path, obj):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)

    write(f"{root}/extra/configs/toy-mimo.json", {
        "runner": "serve_mimo", "model": HF,
        "share": {"published": {"n_routed_experts": 16}, "first_expert": 4, "chips_per_layer": 2},
        "serving": {"num_slots": 4, "max_len": 128, "kv_cache_dtype": "model", "prefill_chunk": 16, "max_queue": 1000,
                    "max_new_tokens": 16, "degrade_max_new_tokens": 0,
                    "kvcache": {"enabled": True, "page_len": 8, "num_pages": 65}},
        "checks": {"sample_requests": 2, "pad_multiple": 64, "max_context": 128, "wrapped_past": 22, "routing_sample_slots": 2,
                   "routing_max_context": 128, "token_gap_mean_max": 0.05, "router_overlap_mean_min": 0.8,
                   "router_logit_mantissa_bits_min": 16, "window_edge_margin_min": -1.0}})
    write(f"{root}/extra/traffic/toy-backlog.json", {
        "kind": "closed", "clients": 6, "pool": 8, "prompt": {"dist": "uniform", "min": 26, "max": 60},
        "answer": {"dist": "uniform", "min": 6, "max": 10}, "max_total": 128, "preroll_s": 0.5, "ttft_sample_share": 0.0})
    write(f"{root}/BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["extra"], "run_seconds": 1,
        "configs": [{"name": "toy-mimo", "source": "test", "file": "extra/configs/toy-mimo.json", "reduced": ["n_routed_experts"], "why": "toy"}],
        "workloads": [{"name": "toy", "config": "toy-mimo", "traffic": "toy-backlog", "chips": 1, "why": "toy"}],
        "end_to_end": [{"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1, "source": "host_clock"},
                       {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"} for m in M.data["per_layer"] if m["name"] in (
            "compiles_in_window", "kv_alloc_waits", "kv_pages_in_use_pct", "batch_occupancy_pct", "moe_dropped_assignments",
            "kv_window_bytes_pct", "serve_step_ms_p50", *NEW)]})


def test_runner_serves_a_toy_cell_on_the_cpu_counts_only(tmp_path):
    root = str(tmp_path)
    _toy(root)
    out = harness.run_cell("toy", seed=2 ** 31 + 11, seconds=2.0, trace=True, t_start=time.perf_counter(),
                           manifest_path=f"{root}/BENCHMARK.json", require_tpu=False, scratch=f"{root}/scratch")
    res, rec = out["result"], out["record"]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    judged = {c["name"]: c for c in rec["checks"]}
    assert list(judged) == ["served_sample", "wrapped_contexts", "token_gap_mean", "router_overlap_mean", "router_logit_mantissa_bits",
                            "moe_dropped_assignments", "window_edge_margin"]
    assert judged["wrapped_contexts"]["value"] >= 1 and judged["router_logit_mantissa_bits"]["value"] >= 21
    assert res["metrics"]["moe_dropped_assignments"]["value"] == 0 and res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["kv_alloc_waits"]["value"] == 0 and not {*NEW, "serve_step_ms_p50"} & set(res["metrics"])  # no device number from the CPU
    g = rec["counters"]["kv_groups"]
    assert res["metrics"]["kv_window_bytes_pct"]["value"] == pytest.approx(100 * g["window"]["bytes"] / rec["counters"]["kv_cache_bytes"])
    assert (g["full"]["kv_heads"], g["full"]["k_dim"], g["full"]["v_dim"], g["window"]["kv_heads"]) == (2, 24, 16, 4)
    assert g["window"]["bytes"] == 2 * (1 + 4 * 2) * 4 * 8 * (24 + 16) * 2 and g["full"]["bytes"] == 2 * 65 * 2 * 8 * (24 + 16) * 2
    stats = rec["counters"]["engine_stats"]
    assert "sink" in stats["swa_decode_form"] and "sink" in stats["swa_chunk_form"] and stats["swa_ring_positions"] == 16
    assert stats["moe_router_form"].startswith("sigmoid_topk") and "K (24 wide)" in stats["kv_write_form"]
    sh = rec["shapes"]
    assert sh["model"]["n_routed_experts"] == 16 and sh["model"]["experts_held"] == [4, 8] and (sh["window"], sh["window_heads"]) == (6, 8)
    assert sh["decode_window_positions_traced"] <= 6 * sh["decode_rows_traced"]  # min(fill, window) a row
    kept = json.load(open(os.path.join(f"{root}/scratch", "trace", "toy", "scoped_ops.json")))
    scopes_of = lambda prog: {s for v in kept[prog].values() for s in v}  # noqa: E731
    assert {"swa.chunk", "full.chunk"} <= scopes_of("jit_serve_prefill") and "moe.router" in scopes_of("jit_serve_decode")
    assert not {"swa.chunk", "full.chunk"} & scopes_of("jit_serve_decode")
