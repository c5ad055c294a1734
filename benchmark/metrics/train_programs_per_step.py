"""Compiled programs each chip runs per ``train_batch`` (``XLA Modules`` executions per
``bench.step`` span).  Anything that adds a device program to a step shows here."""
from benchmark import programs


def read(record):
    return programs.programs_per_step(programs.of_run(record))
