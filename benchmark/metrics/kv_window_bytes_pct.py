"""The window group's bytes (a ring of pages a slot for every window layer, whatever the requests'
lengths) over the whole pool's ``cache_bytes()``: what the window layers cost of the cache.  From the
pool's own counters (``pool.stats()["groups"]``); None where the pool has no window group."""


def read(record):
    c = record["counters"]
    window = ((c.get("kv_groups") or {}).get("window") or {}).get("bytes")
    if not window or not c.get("kv_cache_bytes"):
        return None
    return 100.0 * window / c["kv_cache_bytes"]
