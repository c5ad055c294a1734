"""Median time a serving step is blocked reading its two programs' results back (device time plus
the read-back; prefill and decode summed), over the whole window
(``StepTimeline.summary()["wait_ms_p50"]``)."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "wait_ms_p50")
