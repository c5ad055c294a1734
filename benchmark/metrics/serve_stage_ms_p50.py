"""Median host time a serving step spends staging its two programs' small inputs on the device
(``device_put``; prefill and decode summed), over the whole window
(``StepTimeline.summary()["stage_ms_p50"]``)."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "stage_ms_p50")
