"""Prefill's share of the time the engine spends in its own step phases
(``StepTimeline``: sched + prefill + decode; idle time between steps of
an open loop is not a step's)."""


def read(record):
    tl = record["counters"]["timeline"]
    phases = tl["sched_ms"] + tl["prefill_ms"] + tl["decode_ms"]
    return 100.0 * tl["prefill_ms"] / phases if phases else None
