"""The fused paged attention kernel's share of its roofline (%)."""
from bench.harness.readers import paged_attn_roofline as read  # noqa: F401
