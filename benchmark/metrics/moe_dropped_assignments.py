"""(token, held expert) assignments the router made over the window that
the expert layer did not compute: 0 for a dropless layer.  The guard
against a later change that buys speed with a capacity.  None from an
engine that reports no expert counters."""


def read(record):
    moe = record["counters"].get("moe")
    return moe.get("dropped_assignments") if moe else None
