"""Median device idle time of one serving step: from the end of the last program of one
``ds.serve.step`` to the end of the last program of the next, less the time that step's programs
ran (the wait for its first program plus the wait between its programs).  With the two programs'
device times it adds up to the step; it is what the engine's host work costs the device."""
from benchmark import programs


def read(record):
    return programs.step_gap_ms_p50(programs.of_run(record), "ds.serve.step")
