"""Roofline share of the resident fused GLM value-and-gradient kernel."""
from benchmark.metrics._lib import kernel_roofline as read  # noqa: F401
