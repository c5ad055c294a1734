"""What the window's stalled steps took beyond the median step, summed
(``StepTimeline.summary()["stall_ms"]``: every step since the window opened)."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "stall_ms")
