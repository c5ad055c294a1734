"""Longest time a request waited for its first token during the window
(still waiting at the close counts up to the close)."""


def read(record):
    return record["window"]["oldest_waiting_s"]
