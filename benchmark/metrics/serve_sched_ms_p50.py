"""Median host time of the scheduler's tick in one serving step, over the whole window
(``StepTimeline.summary()["sched_ms_p50"]``)."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "sched_ms_p50")
