"""Median host time a serving step spends in the calls of its compiled programs until they return
(``ds.serve.{prefill,decode}.dispatch``, both programs summed), over every step of the window
(``StepTimeline.summary()["dispatch_ms_p50"]``).  The clearest mark of the host's slow state:
every call into PJRT takes twice as long for a whole run (PERF.md section 2)."""
from benchmark import programs


def read(record):
    return programs.timeline_ms(record, "dispatch_ms_p50")
