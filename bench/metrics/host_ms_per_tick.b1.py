"""Host time per tick, less the time blocked on the token transfer (ms)."""
from bench.harness.readers import host_ms_per_tick as read  # noqa: F401
