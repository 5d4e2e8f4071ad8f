"""Share of the traced window in which no op ran on the device (%)."""
from bench.harness.readers import device_idle as read  # noqa: F401
