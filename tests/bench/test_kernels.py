"""Each kernel's operations-and-bytes function against a hand figure
from the cells' shapes, and the roofline share built from them."""
import pytest

from benchmark import roofline, stats
from benchmark.manifest import Manifest

M = Manifest()
LARGE = M.config("gpt2-large-train-1chip")["model"]
XL = M.config("gpt2-xl-serve-paged")["model"]


def _work(kernel, shapes, calls=1, out_elems=0):
    return M.module("kernels", kernel).work(shapes, calls, out_elems)


def test_flash_decode_paged_counts_one_layers_filled_pages_of_the_live_rows():
    # 10 decode steps traced; each had 4 live rows filling 3 pages each
    shapes = {"model": XL, "page_len": 128, "decode_steps_traced": 10, "decode_rows_traced": 40, "decode_pages_traced": 120}
    w = _work("flash_decode_paged", shapes, calls=480)  # 48 layers x 10 steps
    page = 25 * 128 * 64 * 2          # one page of one layer's K (or V): 409,600 bytes
    per_call = 12 * page * 2 + 4 * 25 * 64 * 2 * 2   # 12 pages of K and of V, q in and o out for 4 rows
    assert w["bytes"] == pytest.approx(480 * per_call)
    assert per_call == pytest.approx(9.86e6, rel=0.01)
    # not the pool: one layer's K and V slice of an 80-page pool is 65.5 MB,
    # the whole pool 3.15 GB — PR 22's 0.897 needed ~1 GB a call
    assert 80 * page * 2 == 65_536_000 and per_call < 0.2 * 80 * page * 2
    assert w["flops"] / w["bytes"] < 2  # one query a row: bandwidth-bound by far


def test_flash_attention_counts_the_causal_half():
    shapes = {"model": LARGE, "seq": 1024, "rows_per_device": 4}
    f = _work("flash_attention_fwd", shapes)
    b = _work("flash_attention_bwd", shapes)
    bh, t, d = 80, 1024, 64
    assert f["flops"] == 2 * bh * t * t * d == pytest.approx(10.74e9, rel=0.01)   # dense would be 21.5e9
    assert b["flops"] == 2.5 * f["flops"]
    assert f["bytes"] == 4 * bh * t * d * 2 + bh * t * 4
    assert b["bytes"] == 8 * bh * t * d * 2 + bh * t * 4
    # the scout trace read 327 us a forward call at this shape: 10.74 GFLOP / 197 TFLOP/s = 54.5 us -> 16.7 %
    pk = stats.peak("TPU v5 lite")
    assert f["flops"] / pk["bf16_flops"] == pytest.approx(54.5e-6, rel=0.01)


def test_fused_adam_moves_28_bytes_an_element_the_trace_counted():
    w = _work("fused_adam", {}, calls=13, out_elems=710_000_000)
    assert w["bytes"] == 28 * 710_000_000
    assert w["bytes"] / stats.peak("TPU v5 lite")["hbm_bytes_per_s"] == pytest.approx(24.3e-3, rel=0.01)


def test_roofline_share_is_least_time_over_measured_time_and_is_not_clipped():
    record = {"manifest": M, "device": {"kind": "TPU v5 lite"},
              "shapes": {"model": LARGE, "seq": 1024, "rows_per_device": 4},
              "trace": {"kernels": {"flash_attention_fwd": {"seconds": 36 * 327e-6, "calls": 36, "out_elems": 0}}}}
    assert roofline.share_pct(record, "flash_attention_fwd") == pytest.approx(16.7, rel=0.01)
    record["trace"]["kernels"]["flash_attention_fwd"]["seconds"] = 36 * 27e-6  # faster than the chip can be
    assert roofline.share_pct(record, "flash_attention_fwd") > 105  # shown, so the fault is seen
    assert roofline.share_pct(record, "flash_attention_bwd") is None  # nothing to read: left out
    record["device"]["kind"] = "cpu"
    with pytest.raises(ValueError):
        roofline.share_pct(record, "flash_attention_fwd")


def test_mfu_arithmetic():
    n = stats.gpt2_param_count(LARGE)
    assert n == pytest.approx(774.0e6, rel=0.005)
    assert stats.gpt2_param_count(XL) == pytest.approx(1557.6e6, rel=0.005)
    per_token = stats.train_flops_per_token(LARGE, 1024)
    assert per_token == 6 * n + 12 * 36 * 1280 * 1024
    # PR 22's 14,452.77 tokens/s/chip is 38 % of 197 TFLOP/s
    assert 100 * 14452.77 * per_token / 197e12 == pytest.approx(38.2, abs=0.3)
