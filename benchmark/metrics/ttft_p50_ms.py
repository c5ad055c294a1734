"""Median of due time -> first token over the requests due in the first
90 % of the window.  Recorded, not judged: first-token times are whole
engine steps (~235 ms today) and 43 samples a window, so the median moves
by 4-6 % between runs of the same schedule (PERF.md section 6, PR 23)."""
from benchmark.stamps import pct


def read(record):
    return pct(record["window"]["ttft_ms"], 50)
