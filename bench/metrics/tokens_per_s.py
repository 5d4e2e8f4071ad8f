"""Tokens completed in the window over the window's length."""
from bench.harness.readers import tokens_per_s as read  # noqa: F401
