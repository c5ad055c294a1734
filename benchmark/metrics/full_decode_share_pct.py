"""``flash_decode_paged``'s share of the device time of the traced decode steps in a cell whose window
layers decode under another name (``swa_decode_paged``): what a decode step spends attending over the
full-attention layers' pages by length.  None where the trace holds no such kernel or program, or the
run kept no window positions (a cell with no window layers)."""
from benchmark import programs


def read(record):
    k = ((record.get("trace") or {}).get("kernels") or {}).get("flash_decode_paged")
    raw = programs.of_run(record)
    if not k or raw is None or "decode_window_positions_traced" not in record.get("shapes", {}):
        return None
    decode_ns = sum(e[2] for events in raw["modules"].values() for e in events if e[0] == "jit_serve_decode")
    return 100.0 * k["seconds"] / (decode_ns / 1e9) if decode_ns else None
