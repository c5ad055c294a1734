"""The GigaChat3.5 cell's benchmark files: the configuration (published
widths, the stated cut, every assumed form with its other reading), the
traffic mix, the new kernel's work function on hand-worked shapes, the
new readers, the runner at toy size on the CPU (counts only) and the
control tool rehearsed there."""
import json
import os
import time

import pytest

from benchmark import harness, traffic
from benchmark.manifest import Manifest

M = Manifest()
CELL, CONFIG = "serve-gigachat35-longreason-backlog", "gigachat35-serve-ep16share"
NEW = ("gdn_decode_roofline", "gdn_state_share_pct", "gdn_chunk_share_pct", "mla_attend_share_pct")  # the four metrics PR 47 brought
LAYER = "model (gated delta rule + latent attention on one hybrid pool)"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HF = {"vocab_size": 256, "max_position_embeddings": 4096, "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
      "num_hidden_layers": 5, "num_attention_heads": 4, "n_shared_experts": 1, "n_routed_experts": 8, "routed_scaling_factor": 2.5,
      "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16, "n_group": 1,
      "topk_group": 1, "num_experts_per_tok": 4, "first_k_dense_replace": 1, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
      "rope_theta": 100000, "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                                             "original_max_position_embeddings": 16, "type": "yarn"},
      "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post", "layernorm_gating_weight": 2, "gated_attention": True,
      "use_mla_scaling_factor": True, "linear_attention_type": "GigaChat35GatedDeltaNet", "full_attention_layers": [4],
      "linear_key_head_dim": 16, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "linear_num_key_heads": 2,
      "linear_num_value_heads": 4, "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered", "linear_sigmoid_gate_scale": 2,
      "linear_attn_o_norm_eps": 1e-6, "swiglu_limit": 10, "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
# the published config.json, as ISSUE 47 and the guide's catalog give it
PUBLISHED = {"vocab_size": 128256, "max_position_embeddings": 262144, "hidden_size": 7168, "intermediate_size": 18432,
             "moe_intermediate_size": 2048, "num_hidden_layers": 40, "nextn_is_sparse": False, "num_attention_heads": 64,
             "n_shared_experts": 1, "n_routed_experts": 256, "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
             "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128, "qk_head_dim": 192, "n_group": 1, "topk_group": 1,
             "num_experts_per_tok": 8, "first_k_dense_replace": 3, "norm_topk_prob": True, "rope_interleave": True,
             "num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 100000,
             "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                              "original_max_position_embeddings": 32768, "type": "yarn"},
             "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
             "gated_attention": True, "use_shared_expert_sigmoid": False, "use_mla_scaling_factor": True,
             "linear_attention_type": "GigaChat35GatedDeltaNet", "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
             "linear_key_head_dim": 128, "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
             "linear_num_value_heads": 64, "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered", "linear_sigmoid_gate_scale": 2,
             "linear_attn_o_norm_eps": 1e-06, "swiglu_limit": 10, "tie_word_embeddings": False, "num_nextn_predict_layers": 2,
             "model_type": "gigachat3_5", "tf_legacy_loss": False}
REDUCED = ["first_k_dense_replace", "full_attention_layers", "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers", "vocab_size"]


def test_configuration_has_the_published_widths_and_states_its_cut():
    cfg = M.config(CONFIG)
    entry = next(c for c in M.data["configs"] if c["name"] == CONFIG)
    if os.path.exists(CATALOG):  # the catalog row, where the guide is at hand, is what PUBLISHED copies
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "GigaChat3.5-432B-A28B")
        assert row["config"] == PUBLISHED and row["source_url"] == cfg["source"] == entry["source"]
    differ = sorted(k for k in PUBLISHED if cfg[k] != PUBLISHED[k])
    assert differ == sorted(cfg["reduced"]) == sorted(entry["reduced"]) == REDUCED
    assert set(PUBLISHED) <= set(cfg) and cfg["model"] == {k: cfg[k] for k in PUBLISHED}  # top level == model
    assert [cfg[k] for k in REDUCED] == [1, [4], 16, 5, 0, 16032]
    share = cfg["share"]
    assert share["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    assert share["chips_per_layer"] == 16 and share["published"]["n_routed_experts"] // 16 == cfg["n_routed_experts"]
    assert share["published"]["vocab_size"] // 8 == cfg["vocab_size"]  # the guide's floor: an eighth
    # no width is in the cut; the guide's floors: a whole period and 4 layers after the dense one, >= 8 experts, >= 1/8 of the vocabulary
    assert not [k for k in cfg["reduced"] if k.endswith(("_size", "_dim", "_rank")) and k != "vocab_size"]
    assert "multi-token-prediction" in share["deployment"] and "10 pipeline stages" in share["deployment"]
    # the arithmetic the file states, recomputed
    D, H, F, Fe = 7168, 64, 18432, 2048
    mla = D * 1536 + 1536 * H * 192 + D * 576 + 512 * H * 256 + 2 * D * H * 128
    conv = 2 * 32 * 128 + 64 * 128
    gdn = D * conv + D * 64 * 128 + 2 * D * 64 + 4 * conv + 64 * 128 * D
    expert = 3 * D * Fe
    moe = expert + D * 256 + 16 * expert
    params = (gdn + 3 * D * F) + 3 * (gdn + moe) + (mla + moe) + 2 * 16032 * D
    assert conv == 16384 and round(mla / 1e6, 1) == 159.8 and round(gdn / 1e6, 1) == 235.9 and round(params / 1e6) == 4732
    s = cfg["serving"]
    state = s["num_slots"] * 4 * (64 * 128 * 128 * 4 + 3 * conv * 2)
    pages = s["kvcache"]["num_pages"] * 128 * 576 * 2
    assert round(state / 1e9, 2) == 1.65 and round(pages / 1e9, 2) == 1.21
    assert 0.76 < (2 * params + state + pages) / 16e9 < 0.78
    assert (s["num_slots"], s["max_len"], s["prefill_chunk"], s["prefill_chunks_per_step"], s["max_new_tokens"]) == (96, 35840, 1024, 1, 3072)
    # the step's programs handed over ahead of the host's reads (PERF.md section 6: the cell's own runs could not hold their bound under the serial step)
    assert s["overlap_chunks"] is True
    assert s["max_len"] // s["kvcache"]["page_len"] == 280 and s["max_len"] % s["prefill_chunk"] == 0
    assert s["kvcache"] == {"enabled": True, "page_len": 128, "num_pages": 8193, "session_ttl_seconds": 0.0}
    assert s["deadline_seconds"] == 0.0 and s["slo_ttft_ms"] == 0.0 and s["journal_dir"] == "" and s["degrade_max_new_tokens"] == 0
    assert "float32 recurrent state" in cfg["precision"] and "float32 router" in cfg["precision"] and "latent pages" in cfg["precision"]
    # every assumed form, with its other reading beside it
    assumed = cfg["assumed"]
    assert {"norm", "sandwich", "mla_scaling", "attention_gate", "delta_rule", "delta_rule_gate", "swiglu_limit", "router", "weights"} <= set(assumed)
    for k in ("norm", "sandwich", "mla_scaling", "attention_gate", "delta_rule", "delta_rule_gate", "swiglu_limit", "router"):
        assert "other reading" in assumed[k], k
    assert "NOT 0" in assumed["weights"]
    c = cfg["checks"]
    assert c["sample_requests"] >= 2 and c["cache_sample_slots"] >= 2 and c["token_gap_mean_max"] > 0
    assert 7 < c["state_mantissa_bits_min"] < 22 and 0 < c["state_rel_err_max"] < 1 and 0 < c["latent_boundary_rel_err_max"] < 1
    assert c["max_context"] % c["pad_multiple"] == 0 and c["max_context"] >= 4 * s["prefill_chunk"]


def test_the_cell_is_the_issues_and_is_asked_for_by_name():
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longreason-backlog", 1) and len(cell["why"]) <= 200
    assert "3 tokens an expert" in cell["why"] and "16x" in cell["why"]
    assert [w["name"] for w in M.data["workloads"]].count(CELL) == 1 and [c["name"] for c in M.data["configs"]].count(CONFIG) == 1
    for m in map(M.metric_entry, NEW):
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s" and m["unit"] == "%" and m["source"] == "device_trace"
        assert m in M.data["per_layer"] and m["layer"] == ("kernels" if m["name"].endswith("_roofline") else LAYER)
    assert {m["name"] for m in M.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in M.per_layer(CELL)}
    assert {*NEW, "mla_decode_paged_roofline", "moe_dropped_assignments", "moe_expert_load_max_over_mean", "serve_dispatch_ms_p50",
            "serve_launch_ms_p50", "serve_readback_ms_p50", "serve_note_ms_p50", "serve_commit_ms_p50", "serve_sweep_ms_p50",
            "serve_idle_between_steps_pct", "serve_idle_unattributed_pct", "serve_stall_steps", "serve_stall_ms",
            "serve_step_ms_p50", "kv_alloc_waits", "batch_occupancy_pct", "serve_hbm_peak_gb"} <= names
    # the readers that look for another family's kernel or key set do not list the cell
    assert not {"kda_decode_roofline", "linear_state_share_pct", "gqa_decode_paged_roofline", "flash_decode_paged_roofline"} & names
    assert M.config(CONFIG)["runner"] == "serve_gigachat35" and M.find("runners", "serve_gigachat35", ".py")
    # by name, never by place: a later PR appends cells, configurations and metrics after these and its cell to these lists


def test_traffic_file_is_the_long_reasoning_backlog():
    mix = M.traffic("longreason-backlog")
    assert (mix["kind"], mix["clients"], mix["pool"], mix["max_total"], mix["preroll_s"], mix["ttft_sample_share"]) == \
        ("closed", 120, 48, 35840, 30, 0.0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 4096, "sigma": 0.8, "min": 512, "max": 32768}
    assert mix["answer"] == {"dist": "lognormal", "median": 768, "sigma": 0.6, "min": 192, "max": 3072}
    pool = traffic.length_pool(mix)
    assert len(pool) == 48 and all(512 <= p <= 32768 and 192 <= a <= 3072 and p + a <= 35840 for p, a in pool)
    prompts, answers = sorted(p for p, _ in pool), sorted(a for _, a in pool)
    assert 3900 <= prompts[24] <= 4300 and 740 <= answers[24] <= 800  # the medians
    assert max(prompts) > 16384  # a few of tens of thousands
    # by the lengths: ~5.9 chunks of 1,024 and ~914 decode steps a request, ~51 pages a request at admission
    chunks = sum(-(-p // 1024) for p in prompts) / 48
    assert 5.5 < chunks < 6.3 and 880 < sum(answers) / 48 < 950
    pages = [-(-(p + a) // 128) for p, a in pool]
    assert 45 < sum(pages) / 48 < 56 and 96 * sum(pages) / 48 < 8192
    req = next(traffic.request_stream(mix, 2 ** 31 + 3, 16032))
    assert 1 <= req["prompt"].min() and req["prompt"].max() < 16032


def test_gdn_decode_work_counts_each_decoding_rows_state_in_and_out_once_and_a_shared_key_once():
    model = M.config(CONFIG)["model"]
    # 10 decode steps traced, 90 rows decoding in each
    shapes = {"model": model, "page_len": 128, "decode_steps_traced": 10, "decode_rows_traced": 900, "decode_pages_traced": 45000}
    w = M.module("kernels", "gdn_decode").work(shapes, calls=40, out_elems=0)  # 4 delta-rule layers x 10 steps
    state = 64 * 128 * 128 * 4
    per_row = 2 * state + (2 * 32 * 128 + 2 * 64 * 128 + 2 * 64) * 4
    assert state == 4_194_304 and w["bytes"] == pytest.approx(40 * 90 * per_row)
    assert w["flops"] == pytest.approx(40 * 90 * 7 * 64 * 128 * 128)
    assert w["flops"] / w["bytes"] < 1.0  # under a FLOP a byte: the bytes bound
    assert 40 * 90 * per_row / 10 / 1e9 == pytest.approx(3.05, abs=0.02)  # GB a decode step at 90 rows
    # mla_decode_paged's work reads the same flat keys: 64 heads, one latent layer a step
    m = M.module("kernels", "mla_decode_paged").work(shapes, calls=10, out_elems=0)
    assert m["bytes"] == pytest.approx(10 * (4500 * 128 * 576 * 2 + 90 * 64 * (576 + 512) * 2))
    assert m["flops"] == pytest.approx(10 * 2.0 * 64 * (576 + 512) * 4500 * 128)


def test_new_readers_return_nothing_where_the_program_reports_nothing():
    bare = {"counters": {}, "trace": {"kernels": {}}, "shapes": {}, "device": {"kind": "TPU v5 lite"}, "manifest": M, "programs": None,
            "scopes": None}
    for name in NEW:
        assert M.module("metrics", name).read(bare) is None
        assert M.module("metrics", name).read({**bare, "trace": None}) is None
    # another family's run: kda_decode in the trace and no gdn_decode, scopes of its own
    other = {**bare, "trace": {"kernels": {"kda_decode": {"calls": 30, "seconds": 0.06, "out_elems": 0}}},
             "scopes": {"ops": {"/device:TPU:0": [["fusion.1", 10, 5]]}, "modules": {"/device:TPU:0": [["jit_serve_prefill", 0, 100]]},
                        "scoped_ops": {"jit_serve_prefill": {"fusion.1": ["cca.mix"]}}}}
    assert all(M.module("metrics", name).read(other) is None for name in NEW)
    # with a trace: the shares from the kernel's seconds and the scoped operations' self time
    shapes = {"model": M.config(CONFIG)["model"], "page_len": 128, "decode_steps_traced": 10, "decode_rows_traced": 900,
              "decode_pages_traced": 45000}
    rec = {**bare, "shapes": shapes, "trace": {"kernels": {"gdn_decode": {"calls": 40, "seconds": 0.05, "out_elems": 0}}},
           "programs": {"modules": {"/device:TPU:0": [["jit_serve_decode", 0, 20_000_000]] * 10 + [["jit_serve_prefill", 0, 9_000_000]]},
                        "spans": []},
           "scopes": {"ops": {"/device:TPU:0": [["fusion.1", 10, 30], ["fusion.2", 50, 20], ["fusion.3", 200, 40]]},
                      "modules": {"/device:TPU:0": [["jit_serve_prefill", 0, 100], ["jit_serve_decode", 150, 100]]},
                      "scoped_ops": {"jit_serve_prefill": {"fusion.1": ["gdn.chunk"], "fusion.2": ["mla.attend"]},
                                     "jit_serve_decode": {"fusion.3": ["mla.attend"]}}}}
    per_row = 2 * 4_194_304 + (2 * 32 * 128 + 2 * 64 * 128 + 2 * 64) * 4
    assert M.module("metrics", "gdn_decode_roofline").read(rec) == pytest.approx(100 * (40 * 90 * per_row / 819e9) / 0.05)
    assert M.module("metrics", "gdn_decode_roofline").read(rec) < 100
    assert M.module("metrics", "gdn_state_share_pct").read(rec) == pytest.approx(100 * 0.05 / 0.2)
    assert M.module("metrics", "gdn_chunk_share_pct").read(rec) == pytest.approx(30.0)   # 30 of the prefill execution's 100 ns
    assert M.module("metrics", "mla_attend_share_pct").read(rec) == pytest.approx(20.0)  # the decode program's is not counted


def _toy(root):
    def write(path, obj):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)

    write(f"{root}/extra/configs/toy-gigachat35.json", {
        "runner": "serve_gigachat35", "model": HF,
        "share": {"published": {"n_routed_experts": 16}, "first_expert": 4, "chips_per_layer": 2},
        "serving": {"num_slots": 4, "max_len": 128, "kv_cache_dtype": "model", "prefill_chunk": 16, "max_queue": 1000,
                    "max_new_tokens": 16, "degrade_max_new_tokens": 0,
                    "kvcache": {"enabled": True, "page_len": 16, "num_pages": 33}},
        "checks": {"sample_requests": 2, "pad_multiple": 64, "max_context": 128, "cache_sample_slots": 2, "token_gap_mean_max": 0.05,
                   "state_rel_err_max": 0.36, "state_mantissa_bits_min": 16, "latent_boundary_rel_err_max": 0.7}})  # the cell's own limits: at 64 wide in bf16 a row reads up to 0.33
    write(f"{root}/extra/traffic/toy-backlog.json", {
        "kind": "closed", "clients": 6, "pool": 8, "prompt": {"dist": "uniform", "min": 16, "max": 48},
        "answer": {"dist": "uniform", "min": 6, "max": 10}, "max_total": 128, "preroll_s": 0.5, "ttft_sample_share": 0.0})
    write(f"{root}/BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["extra"], "run_seconds": 1,
        "configs": [{"name": "toy-gigachat35", "source": "test", "file": "extra/configs/toy-gigachat35.json",
                     "reduced": ["n_routed_experts"], "why": "toy"}],
        "workloads": [{"name": "toy", "config": "toy-gigachat35", "traffic": "toy-backlog", "chips": 1, "why": "toy"}],
        "end_to_end": [{"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1, "source": "host_clock"},
                       {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"} for m in M.data["per_layer"] if m["name"] in (
            "compiles_in_window", "kv_alloc_waits", "kv_pages_in_use_pct", "batch_occupancy_pct", "moe_dropped_assignments",
            "mla_decode_paged_roofline", "serve_step_ms_p50", *NEW)]})


def test_runner_serves_a_toy_cell_on_the_cpu_counts_only(tmp_path):
    root = str(tmp_path)
    _toy(root)
    out = harness.run_cell("toy", seed=2 ** 31 + 11, seconds=2.0, trace=True, t_start=time.perf_counter(),
                           manifest_path=f"{root}/BENCHMARK.json", require_tpu=False, scratch=f"{root}/scratch")
    res, rec = out["result"], out["record"]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    # both halves of the cache read off the timed engine where the window closed are among the judged numbers
    judged = {c["name"]: c for c in rec["checks"]}
    assert list(judged) == ["served_sample", "token_gap_mean", "state_rel_err", "state_mantissa_bits", "latent_boundary_rel_err",
                            "moe_dropped_assignments"]
    assert judged["state_mantissa_bits"]["value"] >= 22  # a float32 recurrence
    assert 0.0 < judged["state_rel_err"]["value"] < judged["state_rel_err"]["limit"] == 0.36
    assert 0.0 < judged["latent_boundary_rel_err"]["value"] < judged["latent_boundary_rel_err"]["limit"] == 0.7
    assert res["metrics"]["moe_dropped_assignments"]["value"] == 0 and res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["kv_alloc_waits"]["value"] == 0
    # no device number from the CPU
    assert not {*NEW, "mla_decode_paged_roofline", "serve_step_ms_p50"} & set(res["metrics"])
    c = rec["counters"]
    hy, moe = c["hybrid"], c["moe"]
    assert hy["state_bytes"] > 0 and hy["state_resets_in_program"] >= res["attempted"] and 0 < hy["decode_rows_updated_mean"] <= 4
    assert len(moe["tokens_per_expert"]) == 4 and len(moe["tokens_per_expert"][0]) == 8  # four expert layers of the five
    assert moe["assignments_computed"] == moe["assignments_routed_held"] > 0 and rec["window"]["tokens"] > 0
    assert c["kv_page_kind"] == "LatentKV" and set(c["kv_page_leaves"]) == {"k"} and set(c["kv_state_leaves"]) == {"s", "conv"}
    assert c["kv_page_leaves"]["k"] == 1 * 33 * 40 * 16 * 2 and c["kv_state_leaves"]["s"] == 4 * 4 * 4 * 16 * 16 * 4
    stats = c["engine_stats"]
    assert stats["gdn_prefill_form"].startswith("chunked jnp, scalar decay") and stats["gdn_decode_fallback"]
    assert stats["mla_prefill_form"].startswith("blockwise jnp") and stats["moe_router_form"].startswith("sigmoid_topk")
    assert {"gdn_decode_kernel", "mla_decode_kernel", "moe_grouped_kernel"} <= set(stats)
    m = rec["shapes"]["model"]
    assert m["n_routed_experts"] == 16 and m["experts_held"] == [4, 8] and m["linear_num_value_heads"] == 4 and m["kv_lora_rank"] == 32
    assert {"decode_rows_traced", "decode_pages_traced", "decode_steps_traced", "page_len"} <= set(rec["shapes"])
    # a traced run keeps the scoped operations of both programs beside its trace
    kept = json.load(open(os.path.join(f"{root}/scratch", "trace", "toy", "scoped_ops.json")))
    found = {s for ops in kept.values() for scopes_ in ops.values() for s in scopes_}
    assert set(kept) == {"jit_serve_prefill", "jit_serve_decode"} and {"gdn.conv", "mla.attend", "moe.router"} <= found
    assert "gdn.chunk" in {s for v in kept["jit_serve_prefill"].values() for s in v}
    assert "gdn.step" in {s for v in kept["jit_serve_decode"].values() for s in v}


def test_boundary_positions_are_the_first_three_every_page_edge_and_the_prompts_end():
    from benchmark.runners.serve_gigachat35 import boundary_positions

    at = boundary_positions(n_prompt=40, consumed=45, page_len=16).tolist()
    assert at == [0, 1, 2, 15, 16, 17, 31, 32, 33, 39, 40, 41]
    assert boundary_positions(5, 6, 16).tolist() == [0, 1, 2, 4, 5]  # only what the slot has consumed
    long = boundary_positions(4096, 4100, 128)
    assert {1023, 1024, 1025, 127, 128, 129, 4095, 4096, 4097} <= set(long.tolist()) and len(long) == 3 + 3 * 32  # the prompt ends on a page edge here


def test_control_tool_reads_the_program_and_all_three_controls(tmp_path):
    """``control_gigachat35.py``, the tool the cell's limits were read
    with on the chip, rehearsed at toy size."""
    import subprocess
    import sys

    root = str(tmp_path)
    _toy(root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_CONTROL_ALLOW_CPU": "1"}
    cmd = [sys.executable, "benchmark/control_gigachat35.py", "--workload", "toy", "--seeds", "1", "--control-seeds", "1",
           "--requests", "2", "--out", f"{root}/control.json", "--manifest", f"{root}/BENCHMARK.json"]
    p = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    row = json.loads(p.stdout.strip().splitlines()[-1])["rows"][0]
    names = ["served_sample", "token_gap_mean", "state_rel_err", "state_mantissa_bits", "latent_boundary_rel_err", "moe_dropped_assignments"]
    for who in ("program", "control_int8", "control_bf16_state", "control_no_decay"):
        assert [c["name"] for c in row[who]["checks"]] == names
        assert row[who]["correct"] == all(c["ok"] for c in row[who]["checks"])
        assert len(row[who]["state_by_slot_and_layer"]) == 2 and len(row[who]["state_by_slot_and_layer"][0]) == 4
        assert row[who]["tokens"] == row["program"]["tokens"] >= 6
    # the program is correct; each control is refused through the runner's own judged()
    assert row["program"]["correct"] is True and row["program"]["state_mantissa_bits"] >= 22
    low = {c["name"]: c["ok"] for c in row["control_bf16_state"]["checks"]}
    assert row["control_bf16_state"]["correct"] is False and low["state_mantissa_bits"] is False
    assert row["control_bf16_state"]["state_mantissa_bits"] <= 7
    gone = {c["name"]: c["ok"] for c in row["control_no_decay"]["checks"]}
    assert row["control_no_decay"]["correct"] is False and gone["state_rel_err"] is False
    assert row["control_no_decay"]["state_rel_err"] > 3 * row["program"]["state_rel_err"]
    assert row["control_int8"]["state_rel_err"] > row["program"]["state_rel_err"]
    assert row["control_int8"]["latent_boundary_rel_err"] > row["program"]["latent_boundary_rel_err"]
    assert row["moe"]["dropped_assignments"] == 0 and row["forms"]["page_kind"] == "LatentKV"
    env.pop("BENCH_CONTROL_ALLOW_CPU")
    q = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=600)
    assert q.returncode != 0 and q.stdout.strip() == ""


# ---------------------------------------------------------------------------
# the cell's yardstick on the CPU: counts and a priced step, no measurement
# ---------------------------------------------------------------------------

class StepModel:
    """The cell's run as the engine's step sequence, which the schedule
    fixes (96 slots, one 1,024-token chunk a step — the oldest admitted
    prompt's —, a request's first token out of its last chunk, the
    traffic's fixed order), priced: ``chunk_ms`` a chunk, ``decode_ms`` +
    ``row_ms`` × rows a decode step, ``host_ms`` of host a program and
    ``step_ms`` a step.  ``overlap`` prices ``serving.overlap_chunks``:
    the decode set is taken before the step's chunk lands, and a step
    whose chunk is not its prompt's last leaves the device no gap (the
    host turns the step while the chunk runs); one whose chunk is the
    last waits for it and then pays the step's host time once.  The
    prices are the traced run's (PERF.md section 5); the test below holds
    the model to the measured runs' tokens by sixth."""

    def __init__(self, mix, slots=96, chunk=1024, steps=2400, overlap=False):
        stream = traffic.request_stream(mix, 0, 16032)

        def new():
            r = next(stream)
            return {"chunks": -(-len(r["prompt"]) // chunk), "max_new": r["max_new"], "gen": 0}

        waiting, live, self.steps = [new() for _ in range(int(mix["clients"]))], [], []
        for _ in range(steps):
            while waiting and len(live) < slots:
                live.append(waiting.pop(0))
            decoding = [r for r in live if r["chunks"] == 0 and r["gen"] >= 1] if overlap else None
            has_chunk = final = emitted = 0
            for r in live:
                if r["chunks"] > 0:
                    r["chunks"] -= 1
                    has_chunk = 1
                    if r["chunks"] == 0:
                        r["gen"], final, emitted = 1, 1, 1
                    break
            if decoding is None:
                decoding = [r for r in live if r["chunks"] == 0 and r["gen"] >= 1]
            rows = 0
            for r in decoding:
                if r["gen"] < r["max_new"]:
                    r["gen"] += 1
                    rows += 1
            for r in [r for r in live if r["chunks"] == 0 and r["gen"] >= r["max_new"]]:
                live.remove(r)
                waiting.append(new())
            self.steps.append((has_chunk, final, rows, emitted + rows))
        self.overlap = overlap

    def window(self, host_ms, preroll_s=30.0, seconds=51.0, chunk_ms=61.8, decode_ms=15.0, row_ms=0.0615, step_ms=1.0):
        """``(tokens/s, tokens by sixth)`` of the window that opens at
        the first step boundary ``preroll_s`` in."""
        t, t_open, sixth = 0.0, None, [0] * 6
        for has_chunk, final, rows, emitted in self.steps:
            if t_open is None and t >= preroll_s * 1e3:
                t_open = t
            host = host_ms * (has_chunk + (rows > 0)) + step_ms
            if self.overlap and has_chunk:
                host = 0.0 if not final else host_ms + step_ms
            t += has_chunk * chunk_ms + (rows > 0) * (decode_ms + row_ms * rows) + host
            if t_open is not None:
                if t >= t_open + seconds * 1e3:
                    return sum(sixth) / seconds, sixth
                sixth[min(5, int((t - t_open) * 6 / (seconds * 1e3)))] += emitted
        raise AssertionError("the model's steps ended inside the window")


# tokens by sixth of the window, measured (my chip run, PR 47: set A's first run on a fast host, its fifth on a slow one)
MEASURED_SIXTHS = {"fast": (2.0, [6617, 7819, 8352, 13278, 14986, 13561]), "slow": (4.0, [6379, 6991, 7924, 12334, 12278, 14527])}


@pytest.mark.parametrize("host", sorted(MEASURED_SIXTHS))
def test_the_step_model_reads_what_the_chip_read_under_the_serial_step(host):
    host_ms, measured = MEASURED_SIXTHS[host]
    rate, sixth = StepModel(M.traffic("longreason-backlog")).window(host_ms)
    assert all(abs(a - b) <= 0.04 * b for a, b in zip(sixth, measured)), (sixth, measured)
    assert abs(rate - sum(measured) / 51.0) <= 0.02 * rate


def test_the_serial_step_reads_the_hosts_slow_state_past_the_bound_and_the_overlapped_step_inside_it():
    """Why the configuration asks for ``serving.overlap_chunks``: the
    host's slow state (every program 2 ms dearer) moves the serial step's
    reading by more than the whole bound of ``serve_tokens_per_s`` (0.03),
    the overlapped step's by less than half of it."""
    mix = M.traffic("longreason-backlog")
    serial, overlapped = StepModel(mix), StepModel(mix, overlap=True)
    loss = lambda m: 1.0 - m.window(4.0)[0] / m.window(2.0)[0]  # noqa: E731
    assert loss(serial) > 0.05 and 0.0 <= loss(overlapped) < 0.015
    assert overlapped.window(2.0)[0] > 1.05 * serial.window(2.0)[0]
    assert M.config(CONFIG)["serving"]["overlap_chunks"] is True
