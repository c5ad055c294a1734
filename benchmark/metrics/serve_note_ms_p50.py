"""Median host time a serving step spends handing its programs' results back, from ``device_get``
returning to the end of ``note_prefill`` / ``note_decode`` (the expert counters, the slot-to-token
loop, ``learn_prefix``; both programs summed), over every step of the window
(``StepTimeline.summary()["note_ms_p50"]``)."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "note_ms_p50")
