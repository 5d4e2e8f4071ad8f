"""Process start to the window's opening: imports, runtime, weights, warm-up and compiles (s)."""
from bench.harness.readers import setup_s as read  # noqa: F401
