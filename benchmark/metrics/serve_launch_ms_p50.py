"""Median, over the traced program executions, of the launch: from the start of the
``ds.serve.{prefill,decode}.dispatch`` span that launched a program to its first instruction on
the device (``benchmark/gaps.py``: the k-th dispatch span of a ``ds.serve.step`` and the k-th
``jit_serve_*`` execution whose midpoint the step holds; the offset between the two clocks read
at the middle of its bounds).  With the read-back it is what ``wait`` holds beside the program."""
from benchmark import gaps, programs


def read(record):
    return gaps.launch_ms_p50(programs.of_run(record))
