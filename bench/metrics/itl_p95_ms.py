"""p95 of every token gap of the window (ms)."""
from bench.harness.readers import itl_p95_ms as read  # noqa: F401
