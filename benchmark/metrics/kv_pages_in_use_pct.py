"""Pages mapped by live slots or the prefix index at the close, over the
usable pages of the pool."""


def read(record):
    c = record["counters"]
    if not c.get("kv_num_pages"):
        return None
    return 100.0 * c["kv_pages_live"] / (c["kv_num_pages"] - 1)
