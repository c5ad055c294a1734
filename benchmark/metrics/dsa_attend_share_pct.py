"""The share of the traced decode steps' device time spent on **attention over the selected positions**: self time of the device
operations under the named scope ``dsa.attend`` inside ``jit_serve_decode`` executions over their summed
device time.  None where the trace holds no such scope or program."""
from benchmark import scopes


def read(record):
    raw = scopes.of_run(record)
    return scopes.scope_share_pct(raw, "dsa.attend", "jit_serve_decode") if raw else None
