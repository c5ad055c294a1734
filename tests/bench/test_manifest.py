"""``BENCHMARK.json`` loads, keeps to the contract's limits, and every
name in it resolves to a file of its own."""
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.manifest import Manifest  # noqa: E402

M = Manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in M.data["workloads"]]
METRICS = [m["name"] for m in M.data["per_layer"]]


def test_top_level_keys_and_limits():
    d = M.data
    assert set(d) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51
    assert os.path.getsize(M.path) <= 64 * 1024
    assert 1 <= len(d["workloads"]) <= 24 and 1 <= len(d["configs"]) <= 24
    four = sum(1 for w in d["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(d["workloads"]) // 4)
    assert {c["name"] for c in d["configs"]} == {w["config"] for w in d["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
    assert len(set(names)) == len(names)
    setup = M.metric_entry("setup_s")
    assert setup["bound"] <= 0.1 and "workloads" not in setup
    for m in d["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in d["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert M.metric_entry(m["moves"]) in d["end_to_end"]
    for m in d["end_to_end"] + d["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
    for p in d["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files_by_name(cell):
    w = M.cell(cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["config"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    cfg = M.config(w["config"])
    entry = next(c for c in M.data["configs"] if c["name"] == w["config"])
    assert entry["file"].startswith(tuple(p + "/" for p in M.data["paths"]))
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    if entry["reduced"]:
        # a configuration lists its cuts: each is a key of the file as it is run, and ``share`` states
        # the deployment this is one chip of, with the published value of every key that was cut
        assert set(entry["reduced"]) <= set(cfg) and len(entry["reduced"]) <= 16
        share = cfg["share"]
        assert set(share["published"]) == set(entry["reduced"]) and share["chips_per_layer"] > 1 and share["deployment"]
        assert all(share["published"][k] != cfg[k] for k in entry["reduced"])
    assert hasattr(M.module("runners", cfg["runner"]), "run")
    assert M.traffic(w["traffic"])["kind"] in ("open", "closed", "tokens")
    e2e = {m["name"] for m in M.end_to_end(cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert M.per_layer(cell), "every cell reports at least one per-layer metric"
    # every per-layer metric of the cell moves an end-to-end metric the cell reports
    assert {m["moves"] for m in M.per_layer(cell)} <= e2e


@pytest.mark.parametrize("metric", METRICS)
def test_per_layer_metric_has_a_reader_of_its_own(metric):
    assert callable(M.module("metrics", metric).read)
    if metric.endswith("_roofline"):
        assert M.metric_entry(metric)["unit"] == "%"
        assert callable(M.module("kernels", metric[: -len("_roofline")]).work)


def test_widths_are_the_published_ones():
    """GPT-2 Large and XL as OpenAI's ``config.json`` states them."""
    large = M.config("gpt2-large-train-1chip")["model"]
    xl = M.config("gpt2-xl-train-zero3")["model"]
    assert (large["n_embd"], large["n_layer"], large["n_head"]) == (1280, 36, 20)
    assert (xl["n_embd"], xl["n_layer"], xl["n_head"]) == (1600, 48, 25)
    assert M.config("gpt2-xl-serve-paged")["model"] == xl
    for c in (large, xl):
        assert (c["vocab_size"], c["n_positions"]) == (50257, 1024)


def test_the_gpt2_serve_pool_holds_every_slot_at_its_full_length():
    """``1 + num_slots x max_len / page_len`` pages (the garbage page first): what the engine asks for by default,
    and what both serve programs have held since the pool is written in place (PR 40; 80 until PR 44)."""
    cfg = M.config("gpt2-xl-serve-paged")
    s, kv = cfg["serving"], cfg["serving"]["kvcache"]
    assert kv["num_pages"] == 1 + s["num_slots"] * s["max_len"] // kv["page_len"] == 129
    m = cfg["model"]
    pool_bytes = kv["num_pages"] * kv["page_len"] * m["n_layer"] * m["n_embd"] * 2 * 2  # K and V, bf16
    assert round(pool_bytes / 1e9, 2) == 5.07 and "5.07 GB" in cfg["pool_note"] and "129" in cfg["pool_note"]
    assert "22.10G" not in cfg["pool_note"], "the note tells of the programs that run now, not PR 23's"


def test_wall_clock_rules_that_drop_work_are_off():
    s = M.config("gpt2-xl-serve-paged")["serving"]
    assert s["slo_ttft_ms"] == 0 and s["deadline_seconds"] == 0 and s["degrade_max_new_tokens"] == 0
    assert s["journal_dir"] == "" and s["kvcache"]["session_ttl_seconds"] == 0
