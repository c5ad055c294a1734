"""Median device idle time between two consecutive executions of the train step
(``jit_train_step`` on each chip's ``XLA Modules`` line), less any other program in between."""
from benchmark import programs


def read(record):
    return programs.between_ms_p50(programs.of_run(record), "jit_train_step")
