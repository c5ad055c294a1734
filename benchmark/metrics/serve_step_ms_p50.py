"""Median wall of one ``ServingEngine.step()`` (it ends with the tokens on the host)."""
from benchmark.readers import step_ms_p50 as read  # noqa: F401
