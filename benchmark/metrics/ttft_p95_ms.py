"""95th percentile of due time -> first token.  About 45 requests a
window today, so this is the third-highest sample: recorded, not judged."""
from benchmark.stamps import pct


def read(record):
    return pct(record["window"]["ttft_ms"], 95)
