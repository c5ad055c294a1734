"""The share of the traced prefill chunks' device time spent in the **chunked delta rule**: self
time of the device operations under the named scope ``gdn.chunk`` (the WY transform within chunks
of 64 and the carried state, every delta-rule layer) inside ``jit_serve_prefill`` executions over
their summed device time.  None where the trace holds no such scope or program."""
from benchmark import scopes


def read(record):
    raw = scopes.of_run(record)
    return scopes.scope_share_pct(raw, "gdn.chunk", "jit_serve_prefill") if raw else None
