"""Median device time of one execution of the serving engine's decode program (one token for
every slot): ``jit_serve_decode`` on the ``XLA Modules`` line of the traced window."""
from benchmark import programs


def read(record):
    return programs.device_ms_p50(programs.of_run(record), "jit_serve_decode")
