"""``swa_decode_paged``'s share of the device time of the traced decode steps: the kernel's self time
in the trace over the summed device time of every ``jit_serve_decode`` execution there — what a decode
step spends attending over the window layers' rings.  None where the trace holds no such kernel or program."""
from benchmark import programs


def read(record):
    k = ((record.get("trace") or {}).get("kernels") or {}).get("swa_decode_paged")
    raw = programs.of_run(record)
    if not k or raw is None:
        return None
    decode_ns = sum(e[2] for events in raw["modules"].values() for e in events if e[0] == "jit_serve_decode")
    return 100.0 * k["seconds"] / (decode_ns / 1e9) if decode_ns else None
