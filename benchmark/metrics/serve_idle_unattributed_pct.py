"""Of the device's idle time in the traced window, the share that lies inside a ``ds.serve.step``
under none of ``sched``, ``sweep``, ``*.stage``, launch, read-back, ``*.note``, ``commit``
(``benchmark/gaps.py``): a guard that the attribution stays whole when host work is added."""
from benchmark import gaps, programs


def read(record):
    return gaps.share_pct(programs.of_run(record), "unattributed")
