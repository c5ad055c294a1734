"""The share of the traced decode steps' device time spent on **the indexer (its projections, the key's LayerNorm and rotary, the write of the indexer key, the scores)**: self time of the device
operations under the named scope ``dsa.index`` inside ``jit_serve_decode`` executions over their summed
device time.  None where the trace holds no such scope or program."""
from benchmark import scopes


def read(record):
    raw = scopes.of_run(record)
    return scopes.scope_share_pct(raw, "dsa.index", "jit_serve_decode") if raw else None
