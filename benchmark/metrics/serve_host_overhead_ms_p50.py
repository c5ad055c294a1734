"""Median, over the whole window, of a serving step's wall (boundary to boundary, the engine's
own clock) minus the time it was blocked on the device: every millisecond in which this serial
engine has given the device nothing (``StepTimeline.summary()["host_ms_p50"]``).  A slow run
moves it."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "host_ms_p50")
