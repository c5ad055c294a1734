"""The share of the traced decode steps' device time spent on **the exact top-2,048 (the threshold by bisection, the tie's last position, the mask)**: self time of the device
operations under the named scope ``dsa.select`` inside ``jit_serve_decode`` executions over their summed
device time.  None where the trace holds no such scope or program."""
from benchmark import scopes


def read(record):
    raw = scopes.of_run(record)
    return scopes.scope_share_pct(raw, "dsa.select", "jit_serve_decode") if raw else None
