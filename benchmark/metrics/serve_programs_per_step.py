"""Compiled programs the device runs per ``ServingEngine.step()`` (``XLA Modules`` executions per
``bench.step`` span): 2.0 where every step has a prefill chunk and a decode.  Anything that adds
a device program to a step shows here."""
from benchmark import programs


def read(record):
    return programs.programs_per_step(programs.of_run(record))
