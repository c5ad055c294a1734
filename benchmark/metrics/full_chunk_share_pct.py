"""The share of the traced prefill chunks' device time spent in the **full-attention layers' walk over their pages** in a
cell that also has window layers: ``flash_chunk_paged``'s self time in the trace where the walk is that kernel, else the self
time of the device operations under the named scope ``full.chunk`` (the ``jnp`` walk), over the summed device time of the
``jit_serve_prefill`` executions.  None where the trace holds neither, no such program, or the run kept no window positions
(a cell with no window layers)."""
from benchmark import programs, scopes


def read(record):
    if "decode_window_positions_traced" not in record.get("shapes", {}):
        return None
    k = ((record.get("trace") or {}).get("kernels") or {}).get("flash_chunk_paged")
    if k and k["seconds"] > 0:
        raw = programs.of_run(record)
        if raw is None:
            return None
        prefill_ns = sum(e[2] for events in raw["modules"].values() for e in events if e[0] == "jit_serve_prefill")
        return 100.0 * k["seconds"] / (prefill_ns / 1e9) if prefill_ns else None
    raw = scopes.of_run(record)
    return scopes.scope_share_pct(raw, "full.chunk", "jit_serve_prefill") if raw else None
