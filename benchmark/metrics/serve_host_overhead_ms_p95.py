"""95th percentile, over the whole window, of a serving step's wall minus the time it was
blocked on the device (``StepTimeline.summary()["host_ms_p95"]``; see
``serve_host_overhead_ms_p50``).  A slow period inside a run moves it."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "host_ms_p95")
