"""Roofline share of the fused Lloyd statistics kernel."""
from benchmark.metrics._lib import kernel_roofline as read  # noqa: F401
