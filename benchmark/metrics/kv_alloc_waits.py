"""Admissions that found a free slot but no pages, inside the window
(``pool.stats()["alloc_waits"]``)."""


def read(record):
    return record["counters"].get("kv_alloc_waits")
