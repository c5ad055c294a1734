"""Every seed of a traffic mix carries the same work."""
import collections
import itertools
import json
import os

import pytest

from benchmark import traffic
from benchmark.manifest import Manifest

M = Manifest()
SERVE_MIXES = sorted({w["traffic"] for w in M.data["workloads"] if M.traffic(w["traffic"])["kind"] != "tokens"})
OPEN_CELLS = [w["name"] for w in M.data["workloads"] if M.traffic(w["traffic"])["kind"] == "open"]
SEEDS = [0, 1, 12345, 2 ** 31 + 7, 4_000_000_011]


def _lengths(mix, seed, n):
    return [(len(r["prompt"]), r["max_new"]) for r in itertools.islice(traffic.request_stream(mix, seed, 50257), n)]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_every_seed_yields_the_same_multiset_of_lengths(name):
    mix = M.traffic(name)
    n = mix["pool"]
    want = collections.Counter(traffic.length_pool(mix))
    streams = set()
    for seed in SEEDS:
        got = _lengths(mix, seed, 3 * n)
        for cycle in range(3):  # every cycle of the stream, not only the first
            assert collections.Counter(got[cycle * n:(cycle + 1) * n]) == want
        assert got[:n] != got[n:2 * n], "each cycle has its own order"
        streams.add(tuple(got))
    # ... and in the same order: the seed draws token ids, never a length or a place in the queue
    assert len(streams) == 1
    assert len({a for _, a in want}) > n // 2, "answer lengths vary, so slots never finish together"
    assert all(p + a <= mix["max_total"] for p, a in want)
    assert tuple(_lengths(dict(mix, schedule_seed=1), SEEDS[0], 3 * n)) in streams, "no key of a mix reorders it"


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_lengths_stay_inside_the_mix_and_the_pool(name):
    mix = M.traffic(name)
    cfg = next(M.config(w["config"]) for w in M.data["workloads"] if w["traffic"] == name)
    for p, a in traffic.length_pool(mix):
        assert mix["prompt"]["min"] <= p <= mix["prompt"]["max"]
        assert 1 <= a <= mix["answer"]["max"]
        assert p + a <= cfg["serving"]["max_len"], "no request the engine would refuse"


def test_same_seed_same_requests_and_token_ids_in_range():
    mix = M.traffic(SERVE_MIXES[0])
    a = list(itertools.islice(traffic.request_stream(mix, 2 ** 31 + 5, 50257), 20))
    b = list(itertools.islice(traffic.request_stream(mix, 2 ** 31 + 5, 50257), 20))
    c = list(itertools.islice(traffic.request_stream(mix, 2 ** 31 + 6, 50257), 20))
    assert all((x["prompt"] == y["prompt"]).all() and x["max_new"] == y["max_new"] for x, y in zip(a, b))
    assert not any((x["prompt"] == y["prompt"]).all() for x, y in zip(a, c)), "another seed, other token ids"
    assert all(1 <= int(x["prompt"].min()) and int(x["prompt"].max()) < 50257 for x in a)


def test_open_loop_offers_each_cycle_over_the_same_time():
    mix = {"rate_rps": 2.0, "pool": 16}
    gaps = list(itertools.islice(traffic.arrival_gaps(mix), 48))
    totals = {round(sum(gaps[c * 16:(c + 1) * 16]), 9) for c in range(3)}
    assert sorted(round(g, 9) for g in gaps[:16]) == sorted(round(g, 9) for g in gaps[16:32])
    assert gaps[:16] != gaps[16:32]
    assert totals == {8.0}  # 16 arrivals at 2 a second in every cycle
    assert gaps == list(itertools.islice(traffic.arrival_gaps(mix), 48))
    assert max(gaps) > 3 * min(gaps)  # still bursts and lulls, not a metronome


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_an_open_loops_window_and_preroll_hold_whole_cycles_of_the_pool(cell):
    """``rate_rps`` is ``pool x k / run_seconds`` and the pre-roll a whole number of cycles, so the window opens on a
    cycle boundary and holds the same k x pool requests for every seed."""
    mix = M.traffic(M.cell(cell)["traffic"])
    cycle_s = mix["pool"] / mix["rate_rps"]
    k, pre = M.data["run_seconds"] / cycle_s, mix["preroll_s"] / cycle_s
    assert k == pytest.approx(round(k), abs=1e-6) and round(k) >= 1
    assert pre == pytest.approx(round(pre), abs=1e-6) and round(pre) >= 1 and mix["preroll_s"] >= 10
    gaps, due, t = traffic.arrival_gaps(mix), [], 0.0
    while t < mix["preroll_s"] + M.data["run_seconds"] + cycle_s:
        t += next(gaps)
        due.append(t)
    t_open = mix["preroll_s"]
    inside = [d for d in due if t_open + 1e-6 < d <= t_open + M.data["run_seconds"] + 1e-6]
    assert len(inside) == round(k) * mix["pool"]  # an arrival is the end of its gap: each cycle's last falls on the boundary
    assert f"{round(k)} cycles" in mix["rate_note"] and f"{round(pre)} cycles" in mix["preroll_note"]
    # the window opens at the first step boundary after the cycle boundary (tens of ms late) and closes as much later:
    # no arrival is due so soon after either edge that it falls now on one side of it and now on the other
    t_close = t_open + M.data["run_seconds"]
    assert min(d - t_open for d in due if d > t_open + 1e-6) > 0.1 and min(d - t_close for d in due if d > t_close + 1e-6) > 0.1


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_an_open_loops_rate_is_four_fifths_of_a_knee_swept_on_a_tpu(cell):
    """The sweep that found the knee is kept beside the mix (``benchmark/sweep.py`` wrote its rows on the chip): it shows
    a rate that holds no queue and one that does, and the cell's rate lies at 0.75-0.85 of the knee they give."""
    mix = M.traffic(M.cell(cell)["traffic"])
    with open(os.path.join(M.root, "benchmark", "sweeps", cell + ".json")) as f:
        sweep = json.load(f)
    assert sweep["workload"] == cell and sweep["device"].startswith("TPU") and "chip run" in sweep["origin"]
    rows = sorted(sweep["rows"], key=lambda r: r["rate_rps"])
    assert 6 <= len(rows) <= 12 and all(r["correct"] and r["seconds"] >= 30 and r["seed"] > 2 ** 31 for r in rows)
    assert rows[0]["rate_rps"] < mix["rate_rps"] / 0.8 < rows[-1]["rate_rps"], "the rows bracket the knee the rate implies"
    holds = [r["rate_rps"] for r in rows if r["queue_depth_at_close"] == 0 and r["failed"] == 0]
    grows = [r["rate_rps"] for r in rows if r["queue_depth_at_close"] >= mix["pool"]]
    assert holds and grows and max(holds) < min(grows)
    # the knee by the file's own rule: what the saturated rows emit, over the pool's mean answer
    answers = [a for _, a in traffic.length_pool(mix)]
    saturated = [r["serve_tokens_per_s"] for r in rows if r["rate_rps"] >= min(grows)]
    knee = sum(saturated) / len(saturated) / (sum(answers) / len(answers))
    assert knee == pytest.approx(sweep["knee_rps"], rel=1e-3) and max(holds) <= knee <= min(grows)
    assert 0.75 <= mix["rate_rps"] / knee <= 0.85 and mix["rate_rps"] < max(holds), "four fifths of the knee, under a rate seen to hold no queue"


def test_quantile_lengths_follow_the_distribution():
    xs = traffic.quantile_lengths({"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 16, "max": 640}, 16)
    assert xs == sorted(xs) and xs[7] < 128 < xs[8] and 16 <= xs[0] and xs[-1] <= 640
    assert traffic.quantile_lengths({"dist": "uniform", "min": 16, "max": 64}, 4) == [22, 34, 46, 58]
    assert traffic.quantile_lengths({"dist": "uniform", "min": 4, "max": 4}, 3) == [4, 4, 4]  # one length: min == max
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "zipf", "min": 1, "max": 2}, 4)


def test_training_batches_are_seeded():
    mix = M.traffic("tokens-seq1024")
    a = next(traffic.token_batches(mix, 9, 50257, 4))["input_ids"]
    b = next(traffic.token_batches(mix, 9, 50257, 4))["input_ids"]
    c = next(traffic.token_batches(mix, 10, 50257, 4))["input_ids"]
    assert a.shape == (4, 1024) and (a == b).all() and not (a == c).all()
