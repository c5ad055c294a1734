"""Device idle share of a serve cell's traced window."""
from benchmark.readers import device_idle_pct as read  # noqa: F401
