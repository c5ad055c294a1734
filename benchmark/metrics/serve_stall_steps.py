"""Steps of the window whose wall is over three times the window's median
(``StepTimeline.summary()["stall_steps"]``: every step since the window opened)."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "stall_steps")
