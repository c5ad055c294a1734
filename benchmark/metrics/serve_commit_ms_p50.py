"""Median host time a serving step spends at its end on the journal's commit, the tiers' note and
the pool's gauges (``ds.serve.commit``), over every step of the window
(``StepTimeline.summary()["commit_ms_p50"]``)."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "commit_ms_p50")
