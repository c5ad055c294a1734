"""Programs built inside the measured window: the engines' own counts of
step executables (``engine.compilation_count``; ``prefill_compiles +
decode_compiles``) plus every XLA program the process compiled or loaded
from the persistent cache meanwhile (JAX's monitoring events), however
small.  Has to be 0."""


def read(record):
    return record["counters"].get("compiles_in_window")
