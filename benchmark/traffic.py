"""The one traffic generator.  A traffic mix is a JSON file of
parameters under ``traffic/``; nothing here knows a mix by name.

Steady by construction: the requests of a mix are a **fixed multiset**
of (prompt length, answer length) pairs — the pool is filled at evenly
spaced quantiles of the mix's two length distributions, paired through
a fixed shuffle — sent in an order, and at arrival gaps, that are fixed
too (one schedule for every mix and run, each cycle its own
permutation).  The
run's seed draws the token ids (and the weights), never a length, an
order or a gap: every seed offers the same work at the same times.
PR 23 measured why: with the order drawn from the run's seed the same
48 requests a window gave emitted tokens/s spreading by 9.8 % and a
median first-token time by 21 % over six seeds, while two runs of one
seed agreed to 0.4 % and 2-6 % (PERF.md section 6).

File format (``kind`` selects the loop)::

  {"kind": "open",   "rate_rps": 1.2, ...}      arrivals at a fixed rate: one fixed schedule of exponential gaps
  {"kind": "closed", "clients": 32,  ...}      each client sends its next request when its last finishes
  {"kind": "tokens", "seq": 1024}              training batches of seeded uniform token ids

  "pool": 16                        size of the length multiset: one cycle of the stream.  Small on
                                    purpose — a window holds a few whole cycles, so its work is the
                                    same for every seed up to the cycle cut by its edges
  "prompt": {"dist": "lognormal", "median": 128, "sigma": 0.9, "min": 16, "max": 640}
  "answer": {"dist": "uniform", "min": 16, "max": 64}
  "max_total": 1024                 prompt + answer never exceeds this
  "preroll_s": 20                   seconds under the same traffic before the window opens (set-up).  An
                                    open loop that states whole cycles of its pool (``pool / rate_rps``
                                    seconds each) opens its window on a cycle boundary
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

_PAIRING_SEED = 0x5EED  # pairs prompt quantiles with answer quantiles; the same for every run
_SCHEDULE_SEED = 0  # orders each cycle's requests and arrival gaps; the same for every mix and run


def quantile_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` whole-number lengths at the mid-point quantiles
    ``(i + 0.5) / n`` of the distribution ``spec`` describes."""
    lo, hi = int(spec["min"]), int(spec["max"])
    us = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "uniform":
        xs = [lo + u * (hi - lo) for u in us]
    elif spec["dist"] == "lognormal":
        mu, sigma, nd = math.log(spec["median"]), float(spec["sigma"]), NormalDist()
        xs = [math.exp(mu + sigma * nd.inv_cdf(u)) for u in us]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r} (uniform|lognormal)")
    return [int(min(hi, max(lo, round(x)))) for x in xs]


def length_pool(mix: Dict[str, Any]) -> List[Tuple[int, int]]:
    """The mix's fixed multiset of (prompt, answer) lengths."""
    n = int(mix["pool"])
    prompts = quantile_lengths(mix["prompt"], n)
    answers = quantile_lengths(mix["answer"], n)
    order = np.random.default_rng(_PAIRING_SEED).permutation(n)
    cap = int(mix["max_total"])
    pairs = []
    for p, j in zip(prompts, order):
        a = answers[int(j)]
        if p + a > cap:
            a = cap - p
        if a < 1:
            raise ValueError(f"prompt {p} leaves no room for an answer under max_total {cap}")
        pairs.append((p, a))
    return pairs


def request_stream(mix: Dict[str, Any], seed: int, vocab: int) -> Iterator[Dict[str, Any]]:
    """Endless stream of requests: the pool in the fixed order, cycle
    after cycle (each cycle its own permutation), each request with
    token ids in ``[1, vocab)`` drawn from the run's seed."""
    pool = length_pool(mix)
    order = np.random.default_rng([_SCHEDULE_SEED, 1])
    ids = np.random.default_rng([int(seed), 1])
    while True:
        for i in order.permutation(len(pool)):
            p, a = pool[int(i)]
            yield {"prompt": ids.integers(1, vocab, p, dtype=np.int32), "max_new": a}


def arrival_gaps(mix: Dict[str, Any]) -> Iterator[float]:
    """Seconds between successive arrivals of an open loop at
    ``rate_rps``.  The gaps of one cycle (``pool`` arrivals) are the
    mid-point quantiles of the exponential distribution — a Poisson
    process's gaps — in the fixed order, scaled so that their mean
    is exactly ``1 / rate_rps``: every cycle offers the same requests
    over the same time, and bursts and lulls still come."""
    rng = np.random.default_rng([_SCHEDULE_SEED, 2])
    n = int(mix["pool"])
    q = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    q *= 1.0 / float(mix["rate_rps"]) / q.mean()
    while True:
        for i in rng.permutation(n):
            yield float(q[int(i)])


def token_batches(mix: Dict[str, Any], seed: int, vocab: int, rows: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless training batches ``{"input_ids": (rows, seq)}`` of seeded
    uniform token ids, from a host iterator as a data loader would."""
    rng = np.random.default_rng([int(seed), 3])
    seq = int(mix["seq"])
    while True:
        yield {"input_ids": rng.integers(0, vocab, (rows, seq), dtype=np.int32)}
