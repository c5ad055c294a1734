"""Mean live slots per step over the slots the pool has (scheduler gauge)."""


def read(record):
    c = record["counters"]
    return 100.0 * c["timeline"]["live_slots"] / c["num_slots"] if c["timeline"].get("steps") else None
