"""The share of the traced prefill chunks' device time spent in the **window layers' banded attention**:
self time of the device operations under the named scope ``swa.chunk`` (the window's earlier pages
gathered from the ring, then query blocks against their band of keys) inside ``jit_serve_prefill``
executions over their summed device time.  None where the trace holds no such scope or program."""
from benchmark import scopes


def read(record):
    raw = scopes.of_run(record)
    return scopes.scope_share_pct(raw, "swa.chunk", "jit_serve_prefill") if raw else None
