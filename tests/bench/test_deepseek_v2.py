"""DeepSeek-V2 at a small size on the CPU: the program (its forward on
the paged latent pool, its routing, its kernel in interpret mode)
against ``benchmark/reference_deepseek_v2.py``, the share test, and the
benchmark's new files (configuration, traffic, work function, readers,
the runner at toy size — counts only)."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, traffic
from benchmark import weights_deepseek_v2 as W
from benchmark.manifest import Manifest
from benchmark.reference_deepseek_v2 import Reference, softmax_scale, yarn_inv_freq
from deepspeed_tpu.models import deepseek_v2 as ds
from deepspeed_tpu.moe.layer import dropless_held_experts, group_limited_topk
from deepspeed_tpu.ops.kernels.mla_decode import mla_decode_paged
from deepspeed_tpu.ops.transformer import latent_attention as la
from deepspeed_tpu.serving.kvcache.pages import LatentKV

M = Manifest()
CELL, CONFIG = "serve-dsv2-longctx-backlog", "deepseek-v2-serve-ep4share"
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 64, "type": "yarn"}
HF = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
      "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
      "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 16,
      "n_shared_experts": 2, "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.0,
      "norm_topk_prob": False, "first_k_dense_replace": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
      "max_position_embeddings": 4096, "rope_scaling": YARN}
SEED = 2 ** 31 + 5


def _program(dims):
    cfg = ds.DeepseekV2Config.from_hf(dims, experts_held=dims.get("experts_held"), vocab_held=dims.get("vocab_held"))
    return cfg, W.program_params(SEED, dims, jnp.float32)


def _pool(cfg, pages=20, page_len=16):
    return LatentKV(cfg.cache_width, jnp.float32).buffers(cfg.n_layer, pages, page_len)[0]


@pytest.mark.parametrize("share", [None, (4, 8)])
def test_chunked_prefill_then_decode_through_the_latent_pool_is_the_references_full_forward(share):
    dims = dict(HF) if share is None else {**HF, "experts_held": list(share), "vocab_held": 128}
    cfg, params = _program(dims)
    toks = np.random.default_rng(0).integers(1, 128, 45, dtype=np.int32)
    want = np.asarray(Reference(dims, SEED).logits(toks[None])[0])
    pool, table, chunk, n_prompt = _pool(cfg), jnp.asarray([[3, 7, 1, 9, 0, 0, 0, 0]], jnp.int32), 16, 37
    got = {}
    with jax.default_matmul_precision("highest"):
        for start in range(0, n_prompt, chunk):  # expanded form, the last chunk padded
            n = min(chunk, n_prompt - start)
            t = np.zeros((1, chunk), np.int32)
            t[0, :n] = toks[start:start + n]
            logits, pool, _ = ds.forward_with_cache(
                params, jnp.asarray(t), pool, jnp.asarray([start], jnp.int32), cfg, table,
                row_valid=(jnp.arange(chunk) < n)[None], take=jnp.asarray([n - 1], jnp.int32))
            got[start + n - 1] = np.asarray(logits[0])
        for p in range(n_prompt, len(toks)):  # absorbed form
            logits, pool, aux = ds.forward_with_cache(params, jnp.asarray(toks[p:p + 1])[None], pool,
                                                      jnp.asarray([p], jnp.int32), cfg, table)
            got[p] = np.asarray(logits[0])
    for p, g in got.items():
        np.testing.assert_allclose(g, want[p], atol=2e-5, err_msg=f"position {p}")
    assert aux.shape == (cfg.n_moe_layers, cfg.held[1] + 1)
    assert (np.asarray(aux[:, :-1]).sum(1) == np.asarray(aux[:, -1])).all()


def test_expanded_and_absorbed_are_the_same_function():
    cfg = ds.DEEPSEEK_V2_TINY
    H, dn, dr, dv, c = 4, 16, 8, 16, 32
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.standard_normal((1, 6, c + dr, 16)), jnp.float32)
    table = jnp.asarray([[2, 5, 1, 0], [4, 3, 0, 0]], jnp.int32)
    pos = jnp.asarray([40, 17], jnp.int32)
    q_nope = jnp.asarray(rng.standard_normal((2, 1, H, dn)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((2, 1, H, dr)), jnp.float32)
    w_kvb = jnp.asarray(rng.standard_normal((c, H, dn + dv)) * 0.2, jnp.float32)
    with jax.default_matmul_precision("highest"):
        a = la.absorbed_attention(q_nope, q_pe, pool, 0, table, pos, w_kvb, dn, cfg.softmax_scale, use_kernel=False)
        e = la.expanded_attention(q_nope, q_pe, pool, 0, table, pos, w_kvb, dn, cfg.softmax_scale, block_pages=2)
    np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=1e-5)


def test_mla_decode_paged_in_interpret_mode_is_the_absorbed_jnp_form():
    H, W_, c, page_len = 8, 160, 128, 128
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.standard_normal((2, 7, W_, page_len)), jnp.float32)
    table = jnp.asarray([[3, 6, 2, 0], [5, 0, 0, 0], [1, 4, 0, 0]], jnp.int32)
    pos = jnp.asarray([300, 0, 255], jnp.int32)
    q = jnp.asarray(rng.standard_normal((3, H, W_)) * 0.3, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = mla_decode_paged(q, pool, 1, table, pos, v_width=c, sm_scale=0.11, interpret=True)
        want = la.absorbed_attention_reference(q[..., :c], q[..., c:], pool, 1, table, pos, 0.11)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_yarn_frequencies_and_scale_against_hand_worked_values():
    dims = {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "rope_theta": 10000,
            "rope_scaling": {**YARN, "original_max_position_embeddings": 4096}}
    f = yarn_inv_freq(dims)
    # correction range: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 -> lo 10; with beta 1: 22.51 -> hi 23
    assert f[0] == pytest.approx(1.0) and f[10] == pytest.approx(10000 ** (-20 / 64))       # turns often: kept
    assert f[23] == pytest.approx(10000 ** (-46 / 64) / 40) and f[31] == pytest.approx(10000 ** (-62 / 64) / 40)
    m = 1 - (16 - 10) / 13                                                                  # pair 16, on the ramp
    assert f[16] == pytest.approx((1 - m) * 10000 ** (-32 / 64) / 40 + m * 10000 ** (-32 / 64), rel=1e-6)
    assert softmax_scale(dims) == pytest.approx(192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    assert softmax_scale(dims) == pytest.approx(0.11473, rel=1e-4)
    cfg = ds.DeepseekV2Config()  # the program's own copy of both
    np.testing.assert_allclose(ds.yarn_inv_freq(cfg), f, rtol=1e-6)
    assert cfg.softmax_scale == pytest.approx(softmax_scale(dims))
    # two positions beyond the original 4,096: the angle of pair 16 and of pair 31, and the rotation itself
    for p in (5000, 150000):
        cos, sin = ds.rope_cos_sin(cfg, jnp.asarray([p]))
        for i in (16, 31):
            assert float(cos[0, i]) == pytest.approx(np.cos(p * float(f[i])), abs=2e-3)
        x = jnp.zeros((1, 64)).at[0, 31].set(1.0)  # half layout: dim 31 pairs with dim 63
        y = np.asarray(ds.apply_rope(x, cos, sin))[0]
        assert y[31] == pytest.approx(np.cos(p * float(f[31])), abs=2e-3) and y[63] == pytest.approx(np.sin(p * float(f[31])), abs=2e-3)


def test_routing_is_the_references_choice_including_a_near_tie():
    from benchmark.reference_deepseek_v2 import route

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    # token 0: experts 5 and 9 a hair apart, at the edge of the top 4
    router = router.at[:, 9].set(router[:, 5])
    x = x.at[0].set(0.0).at[0, 0].set(1.0)
    router = router.at[0].set(jnp.asarray([3, 2.5, 0, 0, 2, 1.0, 0, 0, 1.9, 1.0 - 1e-6, 0, 0, -5, -5, -5, -5.0]))
    with jax.default_matmul_precision("highest"):
        idx_r, w_r = route(router, x, HF, "float32")
        probs = jax.nn.softmax(x @ router, axis=-1)
    idx_p, w_p = group_limited_topk(probs, HF["n_group"], HF["topk_group"], HF["num_experts_per_tok"],
                                    HF["routed_scaling_factor"], False)
    np.testing.assert_array_equal(np.asarray(idx_p), np.asarray(idx_r))
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_r), rtol=1e-6)
    # groups {0-3}, {4-7}, {8-11} hold the largest scores 3, 2, 1.9: kept are groups 0 and 1, so the
    # near-tied pair resolves to expert 5 (group 1) whatever expert 9 (group 2, dropped) scores
    assert sorted(np.asarray(idx_p[0]).tolist()) == [0, 1, 4, 5]


def test_the_four_shares_and_the_shared_experts_once_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts that the shares (experts 0-3,
    4-7, 8-11, 12-15) give — by the PROGRAM's held-experts layer — plus
    what every chip computes alike, counted once, are the uncut
    reference's expert layer."""
    ref = Reference(HF, SEED)
    h = jnp.asarray(np.random.default_rng(4).standard_normal((24, 64)), jnp.float32)
    l = 1
    with jax.default_matmul_precision("highest"):
        routed_all, shared = ref.moe_parts(l, h)  # the uncut layer: every expert
        idx, w = ref.routing(l, h)
        x = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + HF["rms_norm_eps"])
        total = jnp.zeros_like(h)
        for first in (0, 4, 8, 12):
            ex = [W.expert_params(W.seed_key(SEED), l, e, HF) for e in range(first, first + 4)]
            part, counts = dropless_held_experts(x, idx, w, jnp.stack([e["gu"] for e in ex]),
                                                 jnp.stack([e["down"] for e in ex]), (first, 4))
            np.testing.assert_allclose(np.asarray(part), np.asarray(ref.moe_parts(l, h, (first, 4))[0]), atol=2e-5)
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(routed_all), atol=5e-5)
    np.testing.assert_allclose(np.asarray(h + total + shared), np.asarray(h + routed_all + shared), atol=5e-5)


def test_no_assignment_is_dropped_under_a_deliberately_skewed_router():
    N, K, D, F, count = 48, 4, 16, 8, 4
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    # every token sends its first choice to expert 6 (held, local 2): 48 rows for one expert, none for expert 7
    idx = jnp.asarray(np.stack([np.full(N, 6), rng.integers(0, 4, N), rng.integers(8, 16, N), np.full(N, 5)], 1), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (N, K)), jnp.float32)
    gu = jnp.asarray(rng.standard_normal((count, D, 2 * F)) * 0.3, jnp.float32)
    down = jnp.asarray(rng.standard_normal((count, F, D)) * 0.3, jnp.float32)
    valid = jnp.arange(N) < 40
    with jax.default_matmul_precision("highest"):
        out, counts = dropless_held_experts(x, idx, w, gu, down, (4, count), valid)
        want = jnp.zeros((N, D))
        for k in range(K):
            for e in range(4, 8):
                g, u = jnp.split(x @ gu[e - 4], 2, axis=-1)
                want = want + jnp.where(idx[:, k] == e, w[:, k], 0.0)[:, None] * ((jax.nn.silu(g) * u) @ down[e - 4])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    assert np.asarray(counts).tolist() == [0, 40, 40, 0, 80]  # real tokens only: experts 5 and 6, nothing dropped


# ---- the benchmark's new files ------------------------------------------------------------

def test_configuration_has_the_published_widths_and_states_its_cut():
    cfg = M.config(CONFIG)
    published = {"hidden_size": 5120, "intermediate_size": 12288, "moe_intermediate_size": 1536, "kv_lora_rank": 512,
                 "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "num_attention_heads": 128, "num_key_value_heads": 128, "n_shared_experts": 2, "num_experts_per_tok": 6,
                 "n_group": 8, "topk_group": 3, "routed_scaling_factor": 16, "first_k_dense_replace": 1,
                 "rope_theta": 10000, "max_position_embeddings": 163840}
    for k, v in published.items():
        assert cfg[k] == v and cfg["model"][k] == v, k
    assert cfg["model"] == {k: cfg[k] for k in cfg["model"]}  # the top level repeats `model`
    assert cfg["rope_scaling"]["factor"] == 40 and cfg["rope_scaling"]["original_max_position_embeddings"] == 4096
    entry = next(c for c in M.data["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 40, 25600)
    assert cfg["share"]["published"] == {"num_hidden_layers": 60, "n_routed_experts": 160, "vocab_size": 102400}
    assert cfg["share"]["chips_per_layer"] == 4 and len(cfg["source"]) <= 200 and cfg["source"] == entry["source"]
    s = cfg["serving"]
    assert (s["num_slots"], s["max_len"], s["prefill_chunk"], s["kvcache"]["num_pages"]) == (32, 8192, 512, 2049)
    assert s["slo_ttft_ms"] == 0 and s["deadline_seconds"] == 0 and s["degrade_max_new_tokens"] == 0 and s["journal_dir"] == ""
    # weights + latent pool reckon to >= 11.8 GB
    from benchmark import build_deepseek_v2 as build

    shapes = ds.param_shapes(build.model_config(cfg))
    n = sum(int(np.prod(s_)) for s_ in jax.tree.leaves(shapes, is_leaf=lambda t: isinstance(t, tuple)))
    assert n == pytest.approx(5164e6, rel=2e-3)
    pool = 5 * 2049 * 128 * 576 * 2
    assert pool == pytest.approx(1.51e9, rel=5e-3) and 2 * n + pool >= 11.8e9


def test_traffic_file_is_the_long_context_backlog():
    mix = M.traffic("longctx-decode-backlog")
    assert (mix["kind"], mix["clients"], mix["pool"], mix["preroll_s"], mix["ttft_sample_share"]) == ("closed", 48, 16, 30, 0.0)
    pairs = traffic.length_pool(mix)
    assert len(pairs) == 16 and all(1024 <= p <= 7168 and 128 <= a <= 1024 and p + a <= 8192 for p, a in pairs)
    assert 2500 < np.median([p for p, _ in pairs]) < 3700 and 300 < np.median([a for _, a in pairs]) < 480
    req = next(traffic.request_stream(mix, 2 ** 31 + 3, 25600))
    assert 1 <= req["prompt"].min() and req["prompt"].max() < 25600


def test_mla_decode_paged_work_counts_each_filled_page_once():
    model = M.config(CONFIG)["model"]
    # 10 decode steps traced; each had 4 live rows filling 30 pages each
    shapes = {"model": model, "page_len": 128, "decode_steps_traced": 10, "decode_rows_traced": 40, "decode_pages_traced": 1200}
    w = M.module("kernels", "mla_decode_paged").work(shapes, calls=50, out_elems=0)  # 5 layers x 10 steps
    page = 128 * 576 * 2                              # one page of one layer: keys and values are the one row
    per_call = 120 * page + 4 * 128 * (576 + 512) * 2
    assert w["bytes"] == pytest.approx(50 * per_call) and page == 147_456
    assert w["flops"] == pytest.approx(50 * 2 * 128 * (576 + 512) * 120 * 128)
    assert w["flops"] / (50 * 120 * page) == pytest.approx(241.8, abs=0.1)   # the v5e's ridge is 240.5


def test_new_readers_return_nothing_where_the_program_reports_nothing():
    bare = {"counters": {}, "trace": {"kernels": {}}, "shapes": {}, "device": {"kind": "TPU v5 lite"}, "manifest": M}
    for name in ("moe_expert_load_max_over_mean", "moe_dropped_assignments", "mla_decode_paged_roofline"):
        assert M.module("metrics", name).read(bare) is None
    moe = {"counters": {"moe": {"load_max_over_mean": 1.7, "dropped_assignments": 0}}}
    assert M.module("metrics", "moe_expert_load_max_over_mean").read(moe) == 1.7
    assert M.module("metrics", "moe_dropped_assignments").read(moe) == 0
    cell_metrics = {m["name"] for m in M.per_layer(CELL)}
    assert {"mla_decode_paged_roofline", "moe_expert_load_max_over_mean", "moe_dropped_assignments",
            "serve_step_ms_p50", "kv_pages_in_use_pct", "serve_decode_device_ms_p50"} <= cell_metrics
    assert "flash_decode_paged_roofline" not in cell_metrics and "itl_p95_ms" not in {m["name"] for m in M.end_to_end(CELL)}


def test_runner_serves_a_toy_cell_on_the_cpu_counts_only(tmp_path):
    root = str(tmp_path)

    def write(path, obj):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)

    model = {**HF, "n_routed_experts": 8}
    write(f"{root}/extra/configs/toy-dsv2.json", {
        "runner": "serve_dsv2", "model": model,
        "share": {"published": {"n_routed_experts": 16}, "first_expert": 4, "chips_per_layer": 2},
        "serving": {"num_slots": 4, "max_len": 128, "kv_cache_dtype": "model", "prefill_chunk": 16, "max_queue": 1000,
                    "max_new_tokens": 16, "degrade_max_new_tokens": 0,
                    "kvcache": {"enabled": True, "page_len": 16, "num_pages": 33}},
        "checks": {"sample_requests": 2, "pad_multiple": 64, "token_gap_mean_max": 1e-3}})
    write(f"{root}/extra/traffic/toy-backlog.json", {
        "kind": "closed", "clients": 6, "pool": 8, "prompt": {"dist": "uniform", "min": 16, "max": 48},
        "answer": {"dist": "uniform", "min": 3, "max": 8}, "max_total": 128, "preroll_s": 0.5, "ttft_sample_share": 0.0})
    write(f"{root}/BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["extra"], "run_seconds": 1,
        "configs": [{"name": "toy-dsv2", "source": "test", "file": "extra/configs/toy-dsv2.json",
                     "reduced": ["n_routed_experts"], "why": "toy"}],
        "workloads": [{"name": "toy", "config": "toy-dsv2", "traffic": "toy-backlog", "chips": 1, "why": "toy"}],
        "end_to_end": [{"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1, "source": "host_clock"},
                       {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"} for m in M.data["per_layer"] if m["name"] in (
            "compiles_in_window", "kv_alloc_waits", "kv_pages_in_use_pct", "batch_occupancy_pct",
            "moe_expert_load_max_over_mean", "moe_dropped_assignments", "mla_decode_paged_roofline", "serve_step_ms_p50")]})
    out = harness.run_cell("toy", seed=2 ** 31 + 11, seconds=2.0, trace=True, t_start=time.perf_counter(),
                           manifest_path=f"{root}/BENCHMARK.json", require_tpu=False, scratch=f"{root}/scratch")
    res, rec = out["result"], out["record"]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert res["metrics"]["moe_dropped_assignments"]["value"] == 0 and res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1.0
    assert not {"mla_decode_paged_roofline", "serve_step_ms_p50"} & set(res["metrics"])  # no device number from the CPU
    moe = rec["counters"]["moe"]
    assert len(moe["tokens_per_expert"]) == 2 and len(moe["tokens_per_expert"][0]) == 8
    assert moe["assignments_computed"] == moe["assignments_routed_held"] > 0 and rec["window"]["tokens"] > 0
    assert {"timeline", "kv_pages_live", "kv_num_pages", "num_slots"} <= set(rec["counters"])


def test_control_tool_reads_the_program_the_int8_control_and_the_routing_flips(tmp_path):
    """``control_deepseek_v2.py``, the tool the cell's limits were read
    with on the chip, rehearsed at toy size (float32 program: it agrees
    with the reference to rounding, the int8 control does not)."""
    import subprocess
    import sys

    root = str(tmp_path)
    os.makedirs(f"{root}/extra/configs"), os.makedirs(f"{root}/extra/traffic")
    json.dump({"runner": "serve_dsv2", "model": {**HF, "n_routed_experts": 8},
               "share": {"published": {"n_routed_experts": 16}, "first_expert": 4, "chips_per_layer": 2},
               "serving": {"num_slots": 4, "max_len": 128, "kv_cache_dtype": "model", "prefill_chunk": 16, "max_queue": 1000,
                           "max_new_tokens": 16, "kvcache": {"enabled": True, "page_len": 16, "num_pages": 33}},
               "checks": {"sample_requests": 2, "pad_multiple": 64, "token_gap_mean_max": 1.0}},
              open(f"{root}/extra/configs/toy-dsv2.json", "w"))
    json.dump({"kind": "closed", "clients": 6, "pool": 8, "prompt": {"dist": "uniform", "min": 16, "max": 48},
               "answer": {"dist": "uniform", "min": 6, "max": 10}, "max_total": 128, "preroll_s": 0.5},
              open(f"{root}/extra/traffic/toy-backlog.json", "w"))
    json.dump({"command": ["python3", "benchmark/run.py"], "paths": ["extra"], "run_seconds": 1,
               "configs": [{"name": "toy-dsv2", "source": "test", "file": "extra/configs/toy-dsv2.json", "reduced": [], "why": "toy"}],
               "workloads": [{"name": "toy", "config": "toy-dsv2", "traffic": "toy-backlog", "chips": 1, "why": "toy"}],
               "end_to_end": [], "per_layer": []}, open(f"{root}/BENCHMARK.json", "w"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_CONTROL_ALLOW_CPU": "1"}
    cmd = [sys.executable, "benchmark/control_deepseek_v2.py", "--workload", "toy", "--seeds", "1", "--control-seeds", "1",
           "--requests", "2", "--out", f"{root}/control.json", "--manifest", f"{root}/BENCHMARK.json"]
    p = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    row = json.loads(p.stdout.strip().splitlines()[-1])["rows"][0]
    # at this size and a dozen tokens the two need not separate (they do at the cell's size: PERF.md section 2);
    # what is rehearsed is that the tool reads all of them
    assert 0.0 <= row["program"]["token_gap_mean"] <= row["program"]["token_gap_max"] < 0.05
    assert row["control_int8"]["tokens"] == row["program"]["tokens"] >= 12 and row["control_int8"]["token_gap_max"] >= 0.0
    assert 0.0 <= row["routing_flip_share_held"] <= row["routing_flip_share"] < 0.5
    assert row["moe"]["dropped_assignments"] == 0
    env.pop("BENCH_CONTROL_ALLOW_CPU")
    q = subprocess.run(cmd, cwd=M.root, env=env, capture_output=True, text=True, timeout=600)
    assert q.returncode != 0 and q.stdout.strip() == ""
