"""Median over requests of the mean time per output token after the
first, from the token stamps inside the window."""
from benchmark.stamps import pct


def read(record):
    return pct(record["window"]["tpot_ms"], 50)
