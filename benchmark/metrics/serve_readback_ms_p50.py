"""Median, over the traced program executions, of the read-back: from the program's end on the
device to ``device_get`` returning, which is where its ``ds.serve.*.note`` span begins
(``benchmark/gaps.py``; the join and the clocks as for ``serve_launch_ms_p50``)."""
from benchmark import gaps, programs


def read(record):
    return gaps.readback_ms_p50(programs.of_run(record))
