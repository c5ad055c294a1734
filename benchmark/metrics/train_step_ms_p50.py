"""Median wall of one ``train_batch`` call closed by ``block_until_ready``."""
from benchmark.readers import step_ms_p50 as read  # noqa: F401
