"""The Keye cell's benchmark files: the configuration (published widths,
the stated cut and its arithmetic), the traffic mix, the new kernels' work
functions, the new readers where nothing is to be read, the seeded weights
at the published widths, the runner at toy size on the CPU (counts only)
and the control tool rehearsed there."""
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, traffic
from benchmark.manifest import Manifest

M = Manifest()
CELL, CONFIG = "serve-keye-32k-sparse-backlog", "keye-vl2-30b-serve-ep8share"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("dsa_sparse_decode_roofline", "dsa_index_scores_paged_roofline", "dsa_select_threshold_roofline", "dsa_index_share_pct", "dsa_select_share_pct",
       "dsa_attend_share_pct", "dsa_selected_fraction_pct")
APPENDED = ("moe_expert_load_max_over_mean", "moe_dropped_assignments", "serve_launch_ms_p50", "serve_readback_ms_p50",
            "serve_note_ms_p50", "serve_commit_ms_p50", "serve_sweep_ms_p50", "serve_dispatch_ms_p50", "serve_idle_between_steps_pct",
            "serve_idle_unattributed_pct", "serve_stall_steps", "serve_stall_ms")
HF = {"model_type": "KeyeVL2", "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
      "head_dim": 16, "vocab_size": 256, "moe_intermediate_size": 32, "rms_norm_eps": 1e-6, "num_experts": 4,
      "num_experts_per_tok": 2, "norm_topk_prob": True, "rope_theta": 10000000, "rope_scaling": {"mrope_section": [2, 3, 3]},
      "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2, "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                    "q_chunk_size": 512, "topk": 16},
      "decoder_sparse_step": 1, "mlp_only_layers": [], "tie_word_embeddings": False, "sliding_window": None,
      "max_position_embeddings": 4096}
# the published config.json, as ISSUE 45 and the guide's catalog give it
PUBLISHED = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
             "intermediate_size": 6144, "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [],
             "model_type": "KeyeVL2", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
             "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
             "num_local_experts": 128, "rms_norm_eps": 1e-06,
             "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"}, "rope_theta": 10000000,
             "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                           "q_chunk_size": 512, "topk": 2048},
             "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}


def test_configuration_has_the_published_widths_and_states_its_cut():
    cfg = M.config(CONFIG)
    entry = next(c for c in M.data["configs"] if c["name"] == CONFIG)
    if os.path.exists(CATALOG):  # the catalog row, where the guide is at hand, is what PUBLISHED copies
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert row["config"] == PUBLISHED and row["source_url"] == cfg["source"] == entry["source"]
    differ = sorted(k for k in PUBLISHED if cfg[k] != PUBLISHED[k])
    assert differ == sorted(cfg["reduced"]) == sorted(entry["reduced"]) == ["num_experts", "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert set(PUBLISHED) <= set(cfg) and cfg["model"] == {k: cfg[k] for k in PUBLISHED}  # top level == model
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["num_local_experts"], cfg["vocab_size"]) == (8, 16, 16, 18992)
    share = cfg["share"]
    assert share["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    assert share["chips_per_layer"] == 8 and share["first_expert"] == 0 and "6 pipeline stages of 8" in share["deployment"]
    assert share["published"]["num_experts"] // 8 == cfg["num_experts"] and share["published"]["vocab_size"] // 8 == cfg["vocab_size"]
    assert "1 token a decode step" in share["deployment"] and "8 x its share" in share["deployment"]
    # no width is in the cut; the guide's floors: >= 4 layers, >= 8 experts, >= 1/8 of the vocabulary
    assert not [k for k in cfg["reduced"] if k.endswith(("_size", "_dim", "_rank")) and k != "vocab_size"]
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # the arithmetic the file states, recomputed
    D, H, Hkv, d, F, E, Hi, di = 2048, 32, 4, 128, 768, 128, 16, 64
    attn = D * H * d + 2 * D * Hkv * d + H * d * D
    indexer = D * Hi * di + D * di + D * Hi
    layer = attn + indexer + D * E + 16 * 3 * D * F
    params = 8 * layer + 2 * 18992 * D
    assert round(attn / 1e6, 2) == 18.87 and round(indexer / 1e6, 2) == 2.26 and round(layer / 1e6, 1) == 96.9
    assert round(params / 1e6) == 853 and "853 M" in share["arithmetic"] and "1.71 GB" in share["arithmetic"]
    s = cfg["serving"]
    row = 2 * Hkv * d * 2 + di * 2
    page = 128 * 8 * row
    pages = s["kvcache"]["num_pages"] * page
    assert row == 2176 and page == 2_228_224 and round(pages / 1e9, 2) == 7.42 and "2,176 B" in share["arithmetic"]
    assert 0.56 < (2 * params + pages) / 16e9 < 0.58
    assert (s["num_slots"], s["max_len"], s["prefill_chunk"], s["prefill_chunks_per_step"], s["max_new_tokens"]) == (16, 33792, 2048, 1, 1024)
    assert s["kvcache"] == {"enabled": True, "page_len": 128, "num_pages": 3329, "session_ttl_seconds": 0.0} and s["kv_cache_dtype"] == "model"
    assert s["max_len"] // 128 == 264 and s["deadline_seconds"] == 0.0 and s["slo_ttft_ms"] == 0.0 and s["journal_dir"] == ""
    assert "float32 indexer" in cfg["precision"] and "float32 router" in cfg["precision"] and "indexer keys" in cfg["precision"]
    assert {"qk_norm", "mrope_layout", "indexer_query", "indexer_norm_rope", "indexer_score", "indexer_chunks", "indexer_cache",
            "intermediate_size", "vision_tower", "weights", "decoding", "experts_held"} <= set(cfg["assumed"])
    c = cfg["checks"]
    assert c["sample_requests"] >= 1 and c["kv_sample_slots"] >= 1 and c["token_gap_mean_max"] > 0
    assert 0 < c["kv_first_layer_rel_err_max"] < c["kv_layer_median_rel_err_max"] < 1 and 0.5 < c["selection_overlap_mean_min"] < 1
    assert 8 < c["index_score_mantissa_bits_min"] < 21
    # the program's config from the file: the router back at its published width, the share named
    from benchmark import build_keye

    mc = build_keye.model_config(cfg)
    assert (mc.num_experts, mc.held, mc.vocab_rows, mc.num_hidden_layers, mc.select_topk) == (128, (0, 16), 18992, 8, 2048)
    assert (mc.index_n_heads, mc.index_head_dim, mc.index_rotary_dim, mc.mrope_section, mc.rope_theta) == (16, 64, 32, (16, 24, 24), 1e7)


def test_the_cell_is_the_issues_and_the_cells_that_were_there_keep_their_metrics():
    cell = M.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-32k-backlog", 1) and len(cell["why"]) <= 200
    assert "1 token an expert" in cell["why"] and "8x its share" in cell["why"]
    assert [c["name"] for c in M.data["configs"]].count(CONFIG) == 1
    for name in NEW:
        m = M.metric_entry(name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" and m["unit"] == "%"
        assert m["layer"] == ("kernels" if name.endswith("_roofline") else "model (learned sparse attention)")
        assert m["better"] == ("higher" if name.endswith("_roofline") else "lower")
        assert m["source"] == ("program_counter" if name == "dsa_selected_fraction_pct" else "device_trace")
        assert M.find("metrics", name, ".py")
    for name in APPENDED:
        assert M.metric_entry(name)["workloads"][-1] == CELL
    assert {m["name"] for m in M.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in M.per_layer(CELL)}
    assert {*NEW, *APPENDED, "serve_step_ms_p50", "kv_alloc_waits", "kv_pages_in_use_pct", "batch_occupancy_pct", "serve_hbm_peak_gb",
            "serve_device_idle_pct"} <= names
    # their work counts filled pages: not this cell's
    assert not {"flash_decode_paged_roofline", "gqa_decode_paged_roofline", "mla_decode_paged_roofline", "kda_decode_roofline",
                "cca_mix_share_pct", "linear_state_share_pct"} & names
    # the cells that were there keep their metrics, and gain none of the new ones
    for other in ("train-large-seq1024", "serve-xl-chat-open", "train-xl-zero3-4chip", "serve-xl-longprompt-backlog",
                  "serve-dsv2-longctx-backlog", "serve-solar2-reasoning-backlog", "serve-zaya1-longctx-backlog"):
        assert not set(NEW) & {m["name"] for m in M.per_layer(other)}
    assert len(M.data["workloads"]) == 8 and sum(w["chips"] == 4 for w in M.data["workloads"]) == 1
    assert M.config(CONFIG)["runner"] == "serve_keye" and M.find("runners", "serve_keye", ".py")
    for kernel in ("dsa_sparse_decode", "dsa_index_scores_paged", "dsa_select_threshold"):
        assert M.find("kernels", kernel, ".py")


def test_traffic_file_is_the_long_document_backlog():
    mix = M.traffic("longdoc-32k-backlog")
    assert (mix["kind"], mix["clients"], mix["pool"], mix["max_total"], mix["preroll_s"], mix["ttft_sample_share"]) == \
        ("closed", 24, 16, 33792, 30, 0.0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 16384, "sigma": 0.5, "min": 8192, "max": 32768}
    assert mix["answer"] == {"dist": "lognormal", "median": 384, "sigma": 0.5, "min": 128, "max": 1024}
    pool = traffic.length_pool(mix)
    assert len(pool) == 16 and all(8192 <= p <= 32768 and 128 <= a <= 1024 and p + a <= 33792 for p, a in pool)
    # pages a request maps: ~141 on the mean, 16 slots of them under the 3,328 usable; the longest fits a slot of 264
    pages = [-(-(p + a) // 128) for p, a in pool]
    usable = M.config(CONFIG)["serving"]["kvcache"]["num_pages"] - 1
    assert 135 <= np.mean(pages) <= 147 and 16 * np.mean(pages) < 0.75 * usable and max(pages) <= 264
    assert sum(pages) <= usable  # a whole cycle of the pool in flight at once (one of each length) still fits: no allocation waits
    # about a third of the steps carry a chunk: ~8 chunks and ~400 decode steps a request over 16 slots
    chunks, steps = np.mean([-(-p // 2048) for p, _ in pool]), np.mean([a for _, a in pool])
    assert 7 <= chunks <= 10 and 0.2 < chunks / (chunks + steps / 16) < 0.4
    req = next(traffic.request_stream(mix, 2 ** 31 + 3, 18992))
    assert 1 <= req["prompt"].min() and req["prompt"].max() < 18992


def test_work_functions_count_the_selected_rows_and_the_rows_indexer_keys():
    model = M.config(CONFIG)["model"]
    # 10 traced steps of 16 rows at a fill of 17,000: 2,048 selected each
    shapes = {"model": model, "page_len": 128, "decode_steps_traced": 10, "decode_rows_traced": 160,
              "decode_pages_traced": 160 * 133, "decode_positions_traced": 160 * 17000, "decode_selected_traced": 160 * 2048}
    w = M.module("kernels", "dsa_sparse_decode").work(shapes, calls=80, out_elems=0)  # 8 layers x 10 steps
    per_call = 16 * 2048 * 4 * 128 * 2 * 2 + 16 * 32 * 128 * 2 * 2
    assert w["bytes"] == pytest.approx(80 * per_call) and 16 * 2048 * 2048 / 1e6 == pytest.approx(67.1, abs=0.1)  # 67 MB a layer
    assert w["flops"] == pytest.approx(80 * 4 * 32 * 128 * 16 * 2048)
    # a row under topk selects everything it could attend
    short = {**shapes, "decode_positions_traced": 160 * 100, "decode_selected_traced": 160 * 100}
    assert M.module("kernels", "dsa_sparse_decode").work(short, 80, 0)["bytes"] == pytest.approx(80 * (16 * 100 * 2048 + 16 * 32 * 128 * 4))
    wi = M.module("kernels", "dsa_index_scores_paged").work(shapes, calls=80, out_elems=0)
    assert wi["bytes"] == pytest.approx(80 * (16 * 17000 * (128 + 4) + 16 * (16 * 64 + 16) * 4))
    assert wi["flops"] == pytest.approx(80 * 2 * 16 * 64 * 16 * 17000) and 16 * 17000 * 128 / 1e6 == pytest.approx(34.8, abs=0.1)  # 35 MB a layer
    # the selection: 100 decode steps of 16 slots (12 rows live at a fill of 17,000) and 30 chunks of 2,048 (2,000 real queries at a
    # context of 8,000) over the window; the traced calls computed 8 layers x (10 steps x 16 + 3 chunks x 2,048) rows
    select = {"slots": 16, "prefill_chunk": 2048, "decode_steps": 100, "chunks": 30,
              "decode_positions_attendable": 100 * 12 * 17000, "chunk_positions_attendable": 30 * 2000 * 8000}
    rows = 8 * (10 * 16 + 3 * 2048)
    ws = M.module("kernels", "dsa_select_threshold").work({**shapes, "select": select}, calls=8 * 13, out_elems=rows * 128)
    per_row = (100 * 12 * 17000 + 30 * 2000 * 8000) / (100 * 16 + 30 * 2048)
    assert ws["bytes"] == pytest.approx(rows * (per_row * 4 + 8)) and per_row < 8000  # padded rows and slots that do not decode need nothing


def test_new_readers_return_nothing_where_the_program_reports_nothing():
    bare = {"counters": {}, "trace": {"kernels": {}}, "shapes": {}, "device": {"kind": "TPU v5 lite"}, "manifest": M, "programs": None,
            "cell": {"name": "no-such-cell"}}
    for name in NEW:
        assert M.module("metrics", name).read(bare) is None
        assert M.module("metrics", name).read({**bare, "trace": None}) is None
    # the parent's program (no dsa.* scope, no dsa counter) in a traced run: nothing to read, nothing raised
    solar = {**bare, "trace": {"kernels": {"flash_decode_paged": {"calls": 200, "seconds": 0.15, "out_elems": 0}}},
             "counters": {"engine_stats": {"gqa_decode_kernel": 1}}, "scopes": {"ops": {}, "modules": {}, "scoped_ops": {}}}
    for name in NEW:
        assert M.module("metrics", name).read(solar) is None
    ours = {**bare, "counters": {"engine_stats": {"dsa_positions_attendable": 17000 * 16, "dsa_positions_selected": 2048 * 16}}}
    assert M.module("metrics", "dsa_selected_fraction_pct").read(ours) == pytest.approx(100 * 2048 / 17000)
    model = M.config(CONFIG)["model"]
    shapes = {"model": model, "page_len": 128, "decode_steps_traced": 10, "decode_rows_traced": 160, "decode_pages_traced": 160 * 133,
              "decode_positions_traced": 160 * 17000, "decode_selected_traced": 160 * 2048}
    assert M.module("metrics", "dsa_select_threshold_roofline").read({**bare, "trace": {"kernels": {}}}) is None
    traced = {**bare, "shapes": shapes, "trace": {"kernels": {"dsa_sparse_decode": {"calls": 80, "seconds": 0.08, "out_elems": 0},
                                                               "dsa_index_scores_paged": {"calls": 80, "seconds": 0.02, "out_elems": 0}}}}
    per_call = 16 * 2048 * 4 * 128 * 2 * 2 + 16 * 32 * 128 * 2 * 2
    got = M.module("metrics", "dsa_sparse_decode_roofline").read(traced)
    assert got == pytest.approx(100 * (80 * per_call / 819e9) / 0.08, rel=0.02) and got < 100
    assert 0 < M.module("metrics", "dsa_index_scores_paged_roofline").read(traced) < 100


@pytest.mark.parametrize("context", [600])
def test_seeded_weights_keep_the_token_in_the_residual_stream_and_the_hidden_states_apart_at_the_published_widths(context):
    """What ``weights_keye`` seeds so that a selection and a precision can
    be judged on the model (its docstring), at the published widths over
    the cell's 8 layers (a short context and a small ``topk``, so that the
    selection engages): attention logits N(0, 2^2), the indexer's scores
    spread, a residual stream that keeps about half of its power in the
    token's own embedding, hidden states that do not collapse onto one
    vector, a router that spreads a batch over the experts."""
    import jax
    import jax.numpy as jnp

    from benchmark import build_keye
    from benchmark import weights_keye as W
    from benchmark.reference_keye import Reference, indexer, mrope
    from benchmark.reference_solar_open2 import rms

    cfg = M.config(CONFIG)
    dims = build_keye.dims_of(cfg)
    dims = {**dims, "sa_config": {**dims["sa_config"], "topk": 128}, "experts_held": [0, 16], "vocab_size": 1024}
    assert (W.Q_GAIN, W.K_GAIN, W.EMBED_STD, W.O_STD, W.DOWN_STD, W.STD) == (1.0, 2.0, 1.0, 0.005, 0.2, 0.02)
    key = W.seed_key(2 ** 31 + 5)
    ap = W.attn_params(key, 0, dims)
    z = W.sizes(dims)
    toks = np.random.default_rng(0).integers(1, 1024, context, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        x0 = jnp.take(W.embedding(key, dims), jnp.asarray(toks), axis=0)
        u = rms(x0, ap["attn_norm"], 1e-6)
        qkv = u @ ap["qkv"]
        p3 = jnp.broadcast_to(jnp.arange(context), (3, context))
        q = mrope(rms(qkv[:, :4096].reshape(context, 32, 128), ap["q_norm"], 1e-6), p3, 1e7, z["sections"])
        k = mrope(rms(qkv[:, 4096:4608].reshape(context, 4, 128), ap["k_norm"], 1e-6), p3, 1e7, z["sections"])
        logits = np.asarray(jnp.einsum("qhd,kd->hqk", q[:, :8], k[:, 0]) * 128 ** -0.5)  # the first KV head's 8 query heads
        assert 1.8 < logits[:, -1].std() < 2.2 and abs(logits[:, -1].mean()) < 0.3  # N(0, 2^2): no position of its own
        p = jax.nn.softmax(jnp.asarray(logits[:, -1, :128]), axis=-1)  # over as many keys as a selection of 128 holds
        assert 0.15 < float(jnp.max(p, -1).mean()) < 0.6  # peaked, and not on one key (of 2,048 the largest takes ~6 %)
        qi, ki, w = indexer(ap, u, jnp.arange(context), z, 1e-6, "float32")
        scores = np.asarray(jnp.sum(w[-1][:, None] * jax.nn.relu(jnp.einsum("hd,kd->hk", qi[-1], ki)), axis=0))
        assert 0.2 < scores.std() < 1.5 and np.median(np.diff(np.sort(scores))) > 1e-5  # float32 resolves neighbours in rank
        ref = Reference(dims, 2 ** 31 + 5)
        x = np.asarray(ref.hidden(toks))
    x0 = np.asarray(x0)
    unit = x / np.linalg.norm(x, axis=1, keepdims=True)
    off = (unit[-200:] @ unit[-200:].T)[~np.eye(200, dtype=bool)]
    assert abs(off.mean()) < 0.1  # apart: with every projection at 0.02 the 8 layers bring every pair above 0.97 (diffuse attention)
    own = np.sum(x * x0, axis=1) / (np.linalg.norm(x, axis=1) * np.linalg.norm(x0, axis=1))
    assert 0.5 < own.mean() < 0.9 and 1.1 < np.linalg.norm(x, axis=1).mean() / np.linalg.norm(x0, axis=1).mean() < 2.0  # the token is still there
    rp = W.router_params(key, 3, dims)
    from deepspeed_tpu.moe.layer import softmax_topk

    idx, wts = softmax_topk(jnp.asarray(rms(jnp.asarray(x), jnp.ones((2048,)), 1e-6)) @ rp["router"], 8, True)
    load = np.bincount(np.asarray(idx).reshape(-1), minlength=128) / (context * 8 / 128)
    assert load.max() < 4.0 and (load > 0).mean() > 0.9 and np.allclose(np.asarray(wts).sum(-1), 1.0, atol=1e-5)


def _toy(root):
    def write(path, obj):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)

    write(f"{root}/extra/configs/toy-keye.json", {
        "runner": "serve_keye", "model": {**HF, "num_local_experts": 4},
        "share": {"published": {"num_experts": 8}, "first_expert": 4, "chips_per_layer": 2},
        "serving": {"num_slots": 4, "max_len": 128, "kv_cache_dtype": "model", "prefill_chunk": 16, "max_queue": 1000,
                    "max_new_tokens": 16, "degrade_max_new_tokens": 0,
                    "kvcache": {"enabled": True, "page_len": 16, "num_pages": 33}},
        "checks": {"sample_requests": 2, "pad_multiple": 64, "token_gap_mean_max": 0.05, "kv_sample_slots": 2,
                   "kv_layer_median_rel_err_max": 0.2, "kv_first_layer_rel_err_max": 0.05, "selection_overlap_mean_min": 0.8,
                   "index_score_mantissa_bits_min": 16}})
    write(f"{root}/extra/traffic/toy-backlog.json", {
        "kind": "closed", "clients": 6, "pool": 8, "prompt": {"dist": "uniform", "min": 20, "max": 60},
        "answer": {"dist": "uniform", "min": 6, "max": 12}, "max_total": 128, "preroll_s": 0.5, "ttft_sample_share": 0.0})
    write(f"{root}/BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["extra"], "run_seconds": 1,
        "configs": [{"name": "toy-keye", "source": "test", "file": "extra/configs/toy-keye.json",
                     "reduced": ["num_experts"], "why": "toy"}],
        "workloads": [{"name": "toy", "config": "toy-keye", "traffic": "toy-backlog", "chips": 1, "why": "toy"}],
        "end_to_end": [{"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1, "source": "host_clock"},
                       {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"} for m in M.data["per_layer"] if m["name"] in (
            "compiles_in_window", "kv_alloc_waits", "kv_pages_in_use_pct", "batch_occupancy_pct", "moe_dropped_assignments",
            *NEW, "serve_step_ms_p50")]})


def test_runner_serves_a_toy_cell_on_the_cpu_counts_only(tmp_path):
    root = str(tmp_path)
    _toy(root)
    out = harness.run_cell("toy", seed=2 ** 31 + 11, seconds=2.0, trace=True, t_start=time.perf_counter(),
                           manifest_path=f"{root}/BENCHMARK.json", require_tpu=False, scratch=f"{root}/scratch")
    res, rec = out["result"], out["record"]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    # the rows of all three leaves read off the timed engine where the window closed, and the selection itself, are judged numbers
    judged = {c["name"]: c for c in rec["checks"]}
    assert set(judged) == {"served_sample", "token_gap_mean", "kv_layer_median_rel_err", "kv_first_layer_rel_err", "selection_overlap_mean",
                           "index_score_mantissa_bits", "moe_dropped_assignments"}
    assert judged["index_score_mantissa_bits"]["value"] >= 20 and 0 < judged["kv_first_layer_rel_err"]["value"] < 0.02
    # bf16 at 64 wide; a write that lands elsewhere reads ~1
    assert 0.0 < judged["kv_layer_median_rel_err"]["value"] < 0.1 < judged["kv_layer_median_rel_err"]["limit"]
    assert judged["selection_overlap_mean"]["value"] >= 0.9
    assert res["metrics"]["moe_dropped_assignments"]["value"] == 0 and res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["kv_alloc_waits"]["value"] == 0
    # contexts of 20-72 against topk 16: the selection engages; a counter, so the CPU may report it — and no device number
    assert 15 < res["metrics"]["dsa_selected_fraction_pct"]["value"] < 80
    assert not {n for n in NEW if n != "dsa_selected_fraction_pct"} & set(res["metrics"]) and "serve_step_ms_p50" not in res["metrics"]
    moe, c = rec["counters"]["moe"], rec["counters"]
    assert len(moe["tokens_per_expert"]) == 2 and len(moe["tokens_per_expert"][0]) == 4  # (expert layers, held)
    assert moe["assignments_computed"] == moe["assignments_routed_held"] > 0 and rec["window"]["tokens"] > 0
    assert set(c["kv_page_leaves"]) == {"k", "v", "idx"} and c["kv_page_leaves"]["idx"] == 2 * 33 * 16 * 8 * 2  # a bf16 leaf
    stats = c["engine_stats"]
    assert stats["dsa_select_form"].startswith("threshold by bisection") and stats["dsa_decode_kernel"].startswith("lax")
    assert stats["dsa_prefill_form"].startswith("paged_chunk_attention") and stats["moe_router_form"].startswith("softmax_topk")
    assert stats["dsa_positions_selected"] < stats["dsa_positions_attendable"]
    sh = rec["shapes"]
    assert sh["model"]["num_experts"] == 8 and sh["model"]["experts_held"] == [4, 4]
    assert {"decode_positions_traced", "decode_selected_traced", "decode_rows_traced", "decode_steps_traced"} <= set(sh)


def test_control_tool_reads_the_program_and_the_three_controls(tmp_path):
    """``control_keye.py``, the tool the cell's limits were read with on
    the chip, rehearsed at toy size: the recent window is refused by the
    selection itself."""
    import subprocess
    import sys

    root = str(tmp_path)
    _toy(root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_CONTROL_ALLOW_CPU": "1"}
    cmd = [sys.executable, "benchmark/control_keye.py", "--workload", "toy", "--seeds", "1", "--control-seeds", "1",
           "--int8-seeds", "1", "--requests", "2", "--out", f"{root}/control.json", "--manifest", f"{root}/BENCHMARK.json"]
    p = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    row = json.loads(p.stdout.strip().splitlines()[-1])["rows"][0]
    for who in ("program", "control_recent", "control_bf16_indexer", "control_int8"):
        assert [c["name"] for c in row[who]["checks"]] == ["served_sample", "token_gap_mean", "kv_layer_median_rel_err", "kv_first_layer_rel_err",
                                                           "selection_overlap_mean", "index_score_mantissa_bits", "moe_dropped_assignments"]
        assert row[who]["correct"] == all(c["ok"] for c in row[who]["checks"])
        assert len(row[who]["by_sample_and_layer"]) == 2 and len(row[who]["by_sample_and_layer"][0]) == 2
    assert row["program"]["correct"] is True and row["program"]["kv_layer_median_rel_err"] < 0.1 and row["program"]["selection_overlap_mean"] >= 0.9
    assert all(16 in at and 15 in at for at in row["program"]["positions"])  # chunk boundaries (also page boundaries here) among the positions
    low = {c["name"]: c["ok"] for c in row["control_recent"]["checks"]}
    assert row["control_recent"]["correct"] is False and low["selection_overlap_mean"] is False
    assert row["control_recent"]["selection_overlap_mean"] < 0.8
    # a bfloat16 ranking selects nearly what the float32 one does: what it is refused by is the bits its scores carry
    bf = {c["name"]: c["ok"] for c in row["control_bf16_indexer"]["checks"]}
    assert row["control_bf16_indexer"]["correct"] is False and bf["index_score_mantissa_bits"] is False
    assert row["control_bf16_indexer"]["index_score_mantissa_bits"] <= 8 and row["program"]["index_score_mantissa_bits"] >= 20
    assert 0.5 < row["control_bf16_indexer"]["selection_overlap_mean"] <= 1.0 and len(row["program"]["overlap_by_sample_and_layer"][0]) == 2
    assert row["control_int8"]["token_gap_mean"] >= row["program"]["token_gap_mean"]
    assert row["moe"]["dropped_assignments"] == 0
    env.pop("BENCH_CONTROL_ALLOW_CPU")
    q = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=600)
    assert q.returncode != 0 and q.stdout.strip() == ""


def test_a_checkout_without_the_family_stops_before_any_weight_is_made(monkeypatch):
    import builtins

    from benchmark import build_keye

    real = builtins.__import__

    def no_keye(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "deepspeed_tpu.models" and "keye" in (fromlist or ()):
            raise ImportError("cannot import name 'keye' from 'deepspeed_tpu.models'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_keye)
    with pytest.raises(SystemExit, match="cannot run Keye"):
        build_keye.model_config(M.config(CONFIG))
