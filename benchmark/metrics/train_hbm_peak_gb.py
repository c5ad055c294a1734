"""Peak device memory of a train cell, as PJRT saw it."""
from benchmark.readers import hbm_peak_gb as read  # noqa: F401
