"""Median host time a serving step spends before scheduling on the pool's TTL sweep and the tiers'
tick (``ds.serve.sweep``), over every step of the window
(``StepTimeline.summary()["sweep_ms_p50"]``)."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "sweep_ms_p50")
