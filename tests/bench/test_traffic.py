"""Every seed of a traffic mix carries the same work."""
import collections
import itertools
import pytest

from benchmark import traffic
from benchmark.manifest import Manifest

M = Manifest()
SERVE_MIXES = sorted({w["traffic"] for w in M.data["workloads"] if M.traffic(w["traffic"])["kind"] != "tokens"})
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
