"""The decode step's share of the chip's roofline (%)."""
from bench.harness.readers import step_mfu as read  # noqa: F401
