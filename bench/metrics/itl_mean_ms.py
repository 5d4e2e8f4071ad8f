"""Mean of every token gap of the window (ms)."""
from bench.harness.readers import itl_mean_ms as read  # noqa: F401
