"""Share of the traced window a core's op line spends inside collective
operations (the ``-done`` waits of asynchronous ones included): while
such an op is on the line no compute op runs on that core, so this is
the communication the step failed to hide.  Mean over the chips."""


def read(record):
    tr = record.get("trace")
    return 100.0 * tr["collective_s"] / tr["window_s"] if tr else None
