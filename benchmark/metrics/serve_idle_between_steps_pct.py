"""Of the device's idle time in the traced window, the share that lies outside every
``ds.serve.step``: the caller's loop between one ``step()`` and the next (``benchmark/gaps.py``)."""
from benchmark import gaps, programs


def read(record):
    return gaps.share_pct(programs.of_run(record), "between_steps")
